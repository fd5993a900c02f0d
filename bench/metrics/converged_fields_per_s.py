"""Fields solved to tolerance per second: B x converged solves over the
time of all solves of the window."""


def read(rec):
    if not rec.solves:
        return None
    busy = sum(t1 - t0 for t0, t1, _, _ in rec.solves)
    ok = sum(1 for s in rec.solves if s[3])
    return rec.fields * ok / busy
