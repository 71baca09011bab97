"""Percentiles and spreads, kept with the benchmark so that no change to the
program can move the yardstick.

``percentile`` is the nearest-rank definition (rank = ceil(q/100 * n),
1-based), copied from ``repro.obs.metrics.percentile``: every reported
percentile is a sample some request really saw, and p99 of ten samples is
their maximum rather than an interpolation below it.
"""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(vals: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile; ``None`` on an empty sample."""
    xs = sorted(float(v) for v in vals)
    if not xs:
        return None
    if q <= 0:
        return xs[0]
    rank = math.ceil(q / 100.0 * len(xs))
    return xs[min(max(rank, 1), len(xs)) - 1]


def spread(vals: Sequence[float]) -> float:
    """Inter-quartile distance as a share of the median (Python's
    ``statistics.quantiles``, the measure the bounds are set from)."""
    q1, med, q3 = statistics.quantiles(vals, n=4)
    return (q3 - q1) / med if med else float("inf")
