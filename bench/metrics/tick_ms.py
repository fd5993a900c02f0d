"""Mean time of one daemon tick (absorb, watchdog sweeps, publish),
from the benchmark's span around ``Daemon.tick``."""

from record import span_mean_ms


def read(rec):
    return span_mean_ms(rec, "tick")
