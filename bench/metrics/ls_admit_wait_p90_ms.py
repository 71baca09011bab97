"""Scheduler: p90 of the wait from an LS request's due time to its
admission into a decode slot (the program's ``Request.t_admit``); a request
not admitted by the end of the run enters with its age then."""
from benchkit import stats


def read(rec):
    waits = [(s.t_admit if s.t_admit is not None else rec.t_stop)
             - s.t_due for s in rec.window_ls()]
    return stats.percentile(waits, 90) * 1e3 if waits else None
