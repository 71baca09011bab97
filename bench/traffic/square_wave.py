"""Open-loop tide: each ``period_s`` opens with ``on_share`` of the period
at ``on_rate_per_s`` (exponential gaps inside it) and closes with no
arrivals."""
import numpy as np


def schedule(u, seconds, period_s, on_rate_per_s, on_share=0.5):
    on_s = period_s * on_share
    n_on = int(round(on_rate_per_s * on_s))
    out = []
    t = 0.0
    while t < seconds - 1e-9:
        if n_on:
            gaps = -np.log1p(-u(n_on)) / on_rate_per_s
            starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
            span = min(on_s, seconds - t)
            out.append(t + starts * (on_s / gaps.sum()))
            out[-1] = out[-1][out[-1] < t + span]
        t += period_s
    return np.concatenate(out) if out else np.zeros(0)
