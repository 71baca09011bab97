"""Dual-tenant matmul (TPU Pallas) — the elastic-SM-multiplexing analogue at
grid-block granularity (§4, Fig. 8).

One pallas_call executes an LS matmul and a BE matmul in a single grid. The
leading grid axis interleaves tile rows so that, per scheduling round of
`round_tiles` tiles, the BE tenant holds at most floor(sm_be * round_tiles)
tiles — the TPU rendition of "a co-executing BE kernel may only use SM_BE% of
compute partitions", with BE preemption latency bounded by one tile. On a
multi-core TPU (megacore) the grid axis is split across cores, making the
interleave a true spatial partition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _schedule(n_ls: int, n_be: int, sm_be: float, round_tiles: int = 8):
    """Static interleave of LS/BE tile-row ids honoring the BE quota.

    Fractional quotas accumulate as credit across rounds (``sm_be *
    round_tiles < 1`` earns BE roughly one tile every ``1 / (sm_be *
    round_tiles)`` rounds instead of starving until LS drains), and once
    either tenant runs out of tiles the other fills every remaining round —
    a pure-BE tail after LS completes runs at full width (tidal lending),
    it no longer waits for a terminal drain clause."""
    round_tiles = max(int(round_tiles), 2)
    be_frac = max(0.0, min(float(sm_be), (round_tiles - 1) / round_tiles))
    order = []
    i = j = 0
    credit = 0.0
    while i < n_ls and j < n_be:
        # per-round BE quota with carried fractional credit; BE never takes
        # the whole round while LS tiles remain
        credit += be_frac * round_tiles
        be_now = min(int(credit), round_tiles - 1, n_be - j)
        for _ in range(round_tiles - be_now):
            if i < n_ls:
                order.append((0, i))
                i += 1
        for _ in range(be_now):
            order.append((1, j))
            j += 1
            credit -= 1.0
    # interleaved drain: whichever tenant still holds tiles owns every
    # remaining round in full
    while i < n_ls:
        order.append((0, i))
        i += 1
    while j < n_be:
        order.append((1, j))
        j += 1
    return order


def _kernel(owner_ref, row_ref, a_ls_ref, b_ls_ref, a_be_ref, b_be_ref,
            o_ls_ref, o_be_ref, acc, *, n_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    owner = owner_ref[pl.program_id(0)]
    a = jnp.where(owner == 0, a_ls_ref[...], a_be_ref[...]).astype(jnp.float32)
    b = jnp.where(owner == 0, b_ls_ref[...], b_be_ref[...]).astype(jnp.float32)
    acc[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _():
        @pl.when(owner == 0)
        def _():
            o_ls_ref[...] = acc[...].astype(o_ls_ref.dtype)

        @pl.when(owner == 1)
        def _():
            o_be_ref[...] = acc[...].astype(o_be_ref.dtype)


def dual_tenant_matmul(a_ls, b_ls, a_be, b_be, *, sm_be=0.3, block_m=128,
                       block_n=128, block_k=128, interpret=False):
    """(a_ls @ b_ls, a_be @ b_be) in one grid with the BE tile quota.
    Shapes: a_*: [M*, K]; b_*: [K, N] (shared K, N)."""
    m_ls, K = a_ls.shape
    m_be = a_be.shape[0]
    N = b_ls.shape[1]
    block_m = min(block_m, m_ls, m_be)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    assert m_ls % block_m == 0 and m_be % block_m == 0
    assert K % block_k == 0 and N % block_n == 0
    n_ls, n_be = m_ls // block_m, m_be // block_m
    order = _schedule(n_ls, n_be, sm_be)
    owner = jnp.asarray([o for o, _ in order], jnp.int32)
    row = jnp.asarray([r for _, r in order], jnp.int32)
    n_k = K // block_k
    grid = (len(order), N // block_n, n_k)   # k innermost: acc accumulates

    def a_map(which):
        def f(t, n, k, owner, row):
            # rows of the non-owner tenant park on block 0 (no effect)
            r = jnp.where(owner[t] == which, row[t], 0)
            return (r, k)
        return f

    out_shapes = (jax.ShapeDtypeStruct((m_ls, N), a_ls.dtype),
                  jax.ShapeDtypeStruct((m_be, N), a_be.dtype))

    def o_map(which):
        def f(t, n, k, owner, row):
            r = jnp.where(owner[t] == which, row[t], 0)
            return (r, n)
        return f

    o_ls, o_be = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        out_shape=out_shapes,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), a_map(0)),
                pl.BlockSpec((block_k, block_n),
                             lambda t, n, k, ow, rw: (k, n)),
                pl.BlockSpec((block_m, block_k), a_map(1)),
                pl.BlockSpec((block_k, block_n),
                             lambda t, n, k, ow, rw: (k, n)),
            ],
            out_specs=(pl.BlockSpec((block_m, block_n), o_map(0)),
                       pl.BlockSpec((block_m, block_n), o_map(1))),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(owner, row, a_ls, b_ls, a_be, b_be)
    return o_ls, o_be
