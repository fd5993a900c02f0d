"""95th percentile, over every measurement due in the window, of the
time from when it was due to the publish of the first snapshot that
absorbed it; a dropped or unpublished one counts with its wait until the
run's close."""

from record import p95


def read(rec):
    return p95(rec.freshness_s)
