"""Uniform lengths on [``min``, ``max``] (rounded up to the grid, when one
is given, as for every length kind)."""


def quantiles(u, min, max, **_):
    return [min + x * (max - min) for x in u]
