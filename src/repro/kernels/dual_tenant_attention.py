"""Dual-tenant fused attention (TPU Pallas) — the SM-quota split applied
*inside* attention, not just matmuls.

One pallas_call executes an LS causal flash attention and a BE causal flash
attention in a single grid, reusing :func:`dual_tenant_matmul._schedule`'s
round-interleave discipline: the leading grid axis interleaves (owner, row)
work units — one unit is one query block of one (batch, head) pair — so
that per scheduling round of ``round_tiles`` units BE holds at most its
``sm_be`` share (fractional quotas carry credit across rounds), and BE
preemption latency is bounded by one query-block tile. The kv axis is the
inner sequential dimension carrying the online-softmax (m, l, acc) scratch,
with the same causal early-exit as ``flash_attention``: kv blocks past a
query block's diagonal are index-map-pinned and compute-predicated off.

Outputs are independent of ``sm_be``: the schedule permutes only the
leading grid axis and every (owner, row) unit owns a disjoint output block,
so the quota knob trades placement, never numerics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dual_tenant_matmul import _schedule
from .pallas_compat import interpret_default

NEG_INF = -1e30


def _kernel(owner_ref, row_ref, q_ls_ref, k_ls_ref, v_ls_ref,
            q_be_ref, k_be_ref, v_be_ref, o_ls_ref, o_be_ref,
            m_scr, l_scr, acc_scr, *, scale, block_q, block_k, nq):
    t = pl.program_id(0)
    ki = pl.program_id(1)
    owner = owner_ref[t]
    qi = row_ref[t] % nq

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal early exit: kv blocks wholly past this unit's query block
    @pl.when(ki * block_k <= qi * block_q + block_q - 1)
    def _compute():
        q = jnp.where(owner == 0, q_ls_ref[0, 0],
                      q_be_ref[0, 0]).astype(jnp.float32) * scale
        k = jnp.where(owner == 0, k_ls_ref[0, 0],
                      k_be_ref[0, 0]).astype(jnp.float32)
        v = jnp.where(owner == 0, v_ls_ref[0, 0],
                      v_be_ref[0, 0]).astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                        s.shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                        s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o = acc_scr[...] / l

        @pl.when(owner == 0)
        def _():
            o_ls_ref[0, 0] = o.astype(o_ls_ref.dtype)

        @pl.when(owner == 1)
        def _():
            o_be_ref[0, 0] = o.astype(o_be_ref.dtype)


def dual_tenant_attention(q_ls, k_ls, v_ls, q_be, k_be, v_be, *, sm_be=0.3,
                          block_q=128, block_k=128, round_tiles=8,
                          interpret=None):
    """(causal_attn(q_ls,k_ls,v_ls), causal_attn(q_be,k_be,v_be)) in one
    grid with the BE tile quota. q_*: [B*,S,H,D]; k_*/v_*: [B*,S,Hkv,D]
    (GQA via H // Hkv); the two tenants share S, H, Hkv, D and may differ
    in batch. Returns (o_ls, o_be), each [B*,S,H,D]."""
    if interpret is None:
        interpret = interpret_default()
    B_ls, S, H, D = q_ls.shape
    B_be = q_be.shape[0]
    Hkv = k_ls.shape[2]
    assert q_be.shape[1:] == (S, H, D), (q_ls.shape, q_be.shape)
    assert k_be.shape[2] == Hkv, (k_ls.shape, k_be.shape)
    G = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq = S // block_q
    n_ls, n_be = B_ls * H * nq, B_be * H * nq
    order = _schedule(n_ls, n_be, sm_be, round_tiles=round_tiles)
    owner = jnp.asarray([o for o, _ in order], jnp.int32)
    row = jnp.asarray([r for _, r in order], jnp.int32)
    grid = (len(order), S // block_k)

    # layout: [B,H,S,D] / [B,Hkv,S,D] blocks; a work unit r decomposes as
    # (b, h, qi) = (r // (H*nq), (r // nq) % H, r % nq); non-owner operands
    # park on block 0 (never written, see module docstring)
    def q_map(which):
        def f(t, j, owner, row):
            r = jnp.where(owner[t] == which, row[t], 0)
            return (r // (H * nq), (r // nq) % H, r % nq, 0)
        return f

    def kv_map(which):
        def f(t, j, owner, row):
            r = jnp.where(owner[t] == which, row[t], 0)
            qi = r % nq
            jj = jnp.minimum(j, (qi * block_q + block_q - 1) // block_k)
            return (r // (H * nq), ((r // nq) % H) // G, jj, 0)
        return f

    in_specs = []
    for which in (0, 1):
        in_specs += [
            pl.BlockSpec((1, 1, block_q, D), q_map(which)),
            pl.BlockSpec((1, 1, block_k, D), kv_map(which)),
            pl.BlockSpec((1, 1, block_k, D), kv_map(which)),
        ]
    o_ls, o_be = pl.pallas_call(
        functools.partial(_kernel, scale=D ** -0.5, block_q=block_q,
                          block_k=block_k, nq=nq),
        out_shape=(jax.ShapeDtypeStruct((B_ls, H, S, D), q_ls.dtype),
                   jax.ShapeDtypeStruct((B_be, H, S, D), q_be.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=(pl.BlockSpec((1, 1, block_q, D), q_map(0)),
                       pl.BlockSpec((1, 1, block_q, D), q_map(1))),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, D), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(owner, row,
      q_ls.transpose(0, 2, 1, 3), k_ls.transpose(0, 2, 1, 3),
      v_ls.transpose(0, 2, 1, 3),
      q_be.transpose(0, 2, 1, 3), k_be.transpose(0, 2, 1, 3),
      v_be.transpose(0, 2, 1, 3))
    return o_ls.transpose(0, 2, 1, 3), o_be.transpose(0, 2, 1, 3)
