"""Engine host path: median over the ``engine.step`` spans that start in
the traced slice of their length minus the time their ``engine.sync``
spans cover: the host work of one quantum (``benchkit.spans
.step_host_ms_p50``)."""
from benchkit import spans


def read(rec):
    t = rec.traced or {}
    if "slice" not in t:
        return None
    lo, hi = t["slice"]
    return spans.step_host_ms_p50(t["engine_spans"], lo, hi)
