"""Share of the traced segment in which no operation ran on the device,
in percent."""

from record import idle_share


def read(rec):
    return idle_share(rec)
