"""Slot pool: real prompt tokens over the ``n_slots x Sq`` token rows the
chunk steps computed."""


def read(rec):
    calls = [c for c in rec.calls if c.kind == "chunk"]
    rows = sum(c.n_slots * c.sq for c in calls)
    return (100.0 * sum(n for c in calls for _, n in c.rows) / rows
            if rows else None)
