"""Slot pool: live rows over the rows the decode steps computed."""


def read(rec):
    calls = [c for c in rec.calls if c.kind == "decode"]
    rows = sum(c.n_slots for c in calls)
    return 100.0 * sum(len(c.rows) for c in calls) / rows if rows else None
