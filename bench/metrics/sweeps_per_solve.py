"""Mean sweeps per solve (the watchdog receipts' sweep counts): a count,
so it moves only with a change to the mathematics."""


def read(rec):
    if not rec.solves:
        return None
    return sum(s[2] for s in rec.solves) / len(rec.solves)
