"""95th percentile, over every request due in the window, of the time
from when it was due to its answer; a shed or unanswered request counts
with its wait until the run's close."""

from record import p95


def read(rec):
    return p95(rec.latencies_ms)
