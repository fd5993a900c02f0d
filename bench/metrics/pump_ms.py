"""Mean time of a ``Daemon.pump`` that answered at least one request,
from the benchmark's span around it."""

from record import span_mean_ms


def read(rec):
    return span_mean_ms(rec, "pump", served_only=True)
