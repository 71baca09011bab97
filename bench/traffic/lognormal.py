"""Lognormal lengths: ``median``, ``sigma`` (of the log), clipped to
[``min``, ``max``] and, with ``grid``, rounded up to the next
``grid * k + grid_offset``."""
import math
from statistics import NormalDist

_N = NormalDist()


def quantiles(u, median, sigma, **_):
    return [math.exp(math.log(median) + sigma * _N.inv_cdf(x)) for x in u]
