"""Query points x fields answered per second over the whole window."""


def read(rec):
    if rec.window_s <= 0:
        return None
    return rec.rows_answered * rec.fields / rec.window_s
