"""Chunked linear-recurrence scan (TPU Pallas) for SSD/Mamba2-style state
space layers: S_t = diag(w_t) S_{t-1} + k_t v_t^T, y_t = q_t . S_t.

Grid: (batch, heads, num_chunks); the chunk axis is sequential and carries
the [K, P] state in VMEM scratch. Within a chunk, the intra-chunk term uses
an MXU matmul against the causally-masked decay-weighted score matrix; the
cross-chunk term is a single [L,K]x[K,P] matmul against the carried state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


CLAMP = 20.0


def _kernel(q_ref, k_ref, v_ref, w_ref, o_ref, state, *, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    q = q_ref[0, 0].astype(jnp.float32)                 # [L, K]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)                 # [L, P]
    w = w_ref[0, 0].astype(jnp.float32)                 # [L, K] log-decay <= 0
    s = jnp.cumsum(w, axis=0)                           # inclusive cumsum
    q_dec = q * jnp.exp(jnp.clip(s, -CLAMP, 0.0))
    k_dec = k * jnp.exp(jnp.clip(-s, None, CLAMP))
    scores = jnp.dot(q_dec, k_dec.T, preferred_element_type=jnp.float32)
    i = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(i >= j, scores, 0.0)
    y = jnp.dot(scores, v, preferred_element_type=jnp.float32)
    y += jnp.dot(q_dec, state[...], preferred_element_type=jnp.float32)
    o_ref[0, 0] = y.astype(o_ref.dtype)
    # state update
    s_last = jnp.clip(s[-1:], -CLAMP, 0.0)              # [1, K]
    k_tail = k * jnp.exp(jnp.clip(s_last - s, -CLAMP, 0.0))
    state[...] = (jnp.exp(s_last).T * state[...]
                  + jnp.dot(k_tail.T, v, preferred_element_type=jnp.float32))


def ssd_scan(q, k, v, log_w, *, chunk=64, interpret=False):
    """q,k,log_w: [B,T,H,K]; v: [B,T,H,P] -> y [B,T,H,P] (inclusive scan)."""
    B, T, H, K = q.shape
    P = v.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0
    tr = lambda x: x.transpose(0, 2, 1, 3)              # [B,H,T,*]
    grid = (B, H, T // chunk)
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((B, H, T, P), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        scratch_shapes=[pltpu.VMEM((K, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tr(q), tr(k), tr(v), tr(log_w))
    return out.transpose(0, 2, 1, 3)
