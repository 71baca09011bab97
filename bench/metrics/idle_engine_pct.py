"""Engine host path: share of the traced slice in which the device is idle
while the host's innermost open span is an engine span other than
``engine.sync`` (scheduling, dispatch, page tables, token bookkeeping,
control; ``benchkit.spans.idle_engine_pct``)."""
from benchkit import spans


def read(rec):
    t = rec.traced or {}
    if "idle" not in t:
        return None
    lo, hi = t["slice"]
    return spans.idle_engine_pct(t["engine_spans"], t["idle"], lo, hi)
