"""Set-up time: process start to the first timed operation."""


def read(rec):
    return rec.setup_s
