"""Closed-loop backlog: the harness tops the tenant's waiting queue up to
``depth`` requests before every engine step, from a pool of ``pool``
request sizes taken in order and cycled; the sizes follow a low-discrepancy
sequence (``benchkit.traffic``), so that the few a window uses cover the
distribution alike for every seed."""


def pool_size(depth, pool=64):
    return int(pool)
