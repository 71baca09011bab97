"""Open-loop arrivals at ``rate_per_s``: exponential gaps, as from
independent users."""
import numpy as np


def schedule(u, seconds, rate_per_s):
    """Due times in [0, seconds) from the stratified uniforms ``u`` (one
    per block); the number of arrivals is ``round(rate * seconds)`` for
    every seed."""
    n = int(round(rate_per_s * seconds))
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-u(n)) / rate_per_s
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return starts * (seconds / gaps.sum())
