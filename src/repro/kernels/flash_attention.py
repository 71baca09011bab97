"""Flash attention (TPU Pallas): tiled causal/local GQA attention with
running-softmax accumulation in VMEM scratch.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks); the kv axis is the
sequential ("arbitrary") dimension carrying (m, l, acc) scratch. Blocks are
MXU-aligned (block_q x head_dim and block_k x head_dim tiles); supports
GQA head mapping, attention logit softcap (gemma2), and local windows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, block_q, block_k, causal, window, softcap):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bk, d]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                  # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    block_q=128, block_k=128, interpret=False):
    """q: [B,S,H,D]; k,v: [B,S,Hkv,D] -> [B,S,H,D]."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    scale = D ** -0.5
    # layout: [B,H,S,D] blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (B, H, S // block_q, S // block_k)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, window=window,
                          softcap=softcap),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
