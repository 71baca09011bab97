"""Load generator: p99 of how late each LS request of the window was handed
to the engine after it fell due. The engine is synchronous, so this is the
length of the step in progress when the request fell due (host clock)."""
from benchkit import stats


def read(rec):
    lags = [s.t_submit - s.t_due for s in rec.window_ls()]
    return stats.percentile(lags, 99) * 1e3 if lags else None
