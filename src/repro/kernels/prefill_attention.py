"""Chunked-prefill flash attention (TPU Pallas): an Sq-token prompt chunk
per row attends to its cached-context window — the kernel behind the serving
engine's chunked prefill and batched prefix-cache suffix replay.

Two entry points share one online-softmax kernel body (the chunk-width
generalisation of ``decode_attention``):

``prefill_attention``        dense KV-major cache [B,Hkv,Smax,D] with
                             per-row chunk start positions ``pos`` [B]:
                             the query at pos+i sees keys <= pos+i.
``prefill_attention_paged``  page-pool cache [n_pages,Hkv,page,D], or the
                             layer stack [L,n_pages,Hkv,page,D] read at one
                             ``layer``, addressed through a per-row page
                             table (the serving engine's PagedKVCache
                             layout; no dense gather or per-layer slice is
                             materialized).

The chunk's own K/V must already be resident in the cache (the jnp-side
scatter in ``models.attention`` runs before the call). All query heads AND
chunk positions of one KV head are flattened into one [Sq*G, D] MXU operand;
the causal mask is per flattened row (``k_pos <= pos[b] + row // G``).
Ragged early-exit as in decode: kv blocks past a row's last chunk position
are index-map-pinned and compute-predicated off, so per-row cost scales with
``pos + Sq``, not ``Smax``.

Abort/progress protocol (sub-chunk preemption): ``abort`` is a per-row cap
on how many of the chunk's query positions may complete this launch.
Compute for kv blocks past position ``pos + abort - 1`` is ``pl.when``-
predicated off (abort == 0 skips the row entirely), rows at or past the cap
are causally masked out, and the wrapper reports per row how far the launch
got — ``progress = min(abort, Sq)``, a function of the inputs alone, so the
kernel emits no output for it. Because each query row's online
softmax is independent and already causal, the first ``abort`` rows are
bit-equal to running a chunk of exactly ``abort`` tokens, which is what
lets the serving engine abort a BE chunk at tile granularity and later
resume it as a smaller chunk with no token drift. ``interpret=None``
auto-detects the backend (CPU hosts interpret, TPU compiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import paged_pools, scores, weighted_values
from .pallas_compat import interpret_default

NEG_INF = -1e30


def _kernel(pos_ref, abort_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, block_k, group, d_major=False):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # early exit past the last *allowed* query position (pos + abort - 1);
    # an aborted-at-zero row runs no kv block at all
    @pl.when((abort_ref[b] > 0)
             & (ki <= (pos_ref[b] + abort_ref[b] - 1) // block_k))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [Sq*G, D]
        s = scores(q, k_ref, d_major)                        # [Sq*G, bk]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        q_pos = pos_ref[b] + row
        # causal mask plus the abort cap: rows at/past the cap see no keys
        s = jnp.where((k_pos <= q_pos) & (row < abort_ref[b]), s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + weighted_values(p, v_ref,
                                                              d_major)
        m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _prefill_attention_paged_kernel(pt_ref, pos_ref, abort_ref, layer_ref,
                                    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                                    acc_scr, *, scale, block_k, group,
                                    d_major):
    # the page table and the layer are consumed by the BlockSpec index maps
    del pt_ref, layer_ref
    _kernel(pos_ref, abort_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, scale=scale, block_k=block_k, group=group,
            d_major=d_major)


def _abort_array(abort, B, Sq):
    """Per-row position cap as an int32 [B] prefetch scalar, clamped to
    [0, Sq]; ``None`` means the whole chunk (the no-preemption launch)."""
    if abort is None:
        return jnp.full((B,), Sq, jnp.int32)
    arr = jnp.broadcast_to(jnp.asarray(abort, jnp.int32), (B,))
    return jnp.clip(arr, 0, Sq)


def prefill_attention(q, k_cache, v_cache, pos, *, block_k=128,
                      interpret=None, abort=None):
    """q: [B,Sq,H,D] (one prompt chunk per row); caches: KV-major
    [B,Hkv,Smax,D] with the chunk's keys/values already written; pos: [B]
    int32 chunk start positions (query i of row b sits at pos[b]+i).
    Returns [B,Sq,H,D]; with ``abort`` (scalar or [B] int32 position cap)
    returns ``(out, progress)`` where ``progress`` [B] int32 reports the
    completed positions per row — rows past the cap hold garbage."""
    if interpret is None:
        interpret = interpret_default()
    B, Sq, H, D = q.shape
    Hkv, Smax = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    block_k = min(block_k, Smax)
    kt, vt = k_cache, v_cache
    if Smax % block_k:
        # same block-divisor policy as decode_attention: prefer a decent
        # divisor, pad only pathological windows
        d = block_k
        while Smax % d:
            d -= 1
        if d >= 32:
            block_k = d
        else:
            pad = block_k - Smax % block_k
            kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
            Smax += pad
    qg = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Hkv, Sq * G, D)
    pos_arr = jnp.asarray(pos, jnp.int32)
    abort_arr = _abort_array(abort, B, Sq)

    def _kv_index(b, h, j, pos, ab):
        last = pos[b] + jnp.maximum(ab[b], 1) - 1
        return (b, h, jnp.minimum(j, last // block_k), 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=D ** -0.5, block_k=block_k, group=G),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Sq * G, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, Smax // block_k),
            in_specs=[
                pl.BlockSpec((1, 1, Sq * G, D),
                             lambda b, h, j, pos, ab: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, D), _kv_index),
                pl.BlockSpec((1, 1, block_k, D), _kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, Sq * G, D),
                                   lambda b, h, j, pos, ab: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Sq * G, 1), jnp.float32),
                pltpu.VMEM((Sq * G, 1), jnp.float32),
                pltpu.VMEM((Sq * G, D), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, abort_arr, qg, kt, vt)
    out = out.reshape(B, Hkv, Sq, G, D).transpose(0, 2, 1, 3, 4) \
             .reshape(B, Sq, H, D)
    if abort is None:
        return out
    return out, abort_arr    # progress: the cap, already clamped to [0, Sq]


def prefill_attention_paged(q, k_pages, v_pages, page_table, pos, *,
                            layer=None, interpret=None, abort=None):
    """Paged chunked-prefill flash attention: each row's kv blocks are
    gathered through its page table inside the BlockSpec index map (one page
    = one kv block, no dense window view).

    q: [B,Sq,H,D]; {k,v}_pages: [n_pages,Hkv,page_size,D], or the stacked
    pools of every layer [L,n_pages,Hkv,page_size,D] with ``layer`` the one
    to read (a 4-D pool is the ``L = 1``, ``layer = 0`` case); page_table:
    [B,P] int32 (entries >= n_pages unmapped — never touched, the index map
    clamps to the row's last valid page); pos: [B] int32 chunk starts.
    Returns [B,Sq,H,D]; with ``abort`` returns ``(out, progress)`` under the
    same sub-chunk protocol as :func:`prefill_attention`."""
    if interpret is None:
        interpret = interpret_default()
    B, Sq, H, D = q.shape
    k_pages, v_pages, layer_arr, d_major = paged_pools(k_pages, v_pages,
                                                       layer)
    _, n_pages, Hkv = k_pages.shape[:3]
    tile = k_pages.shape[3:]              # (page, D), or (D, page) D-major
    page_size = tile[1] if d_major else tile[0]
    P = page_table.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, Hkv, Sq * G, D)
    pos_arr = jnp.asarray(pos, jnp.int32)
    pt = jnp.asarray(page_table, jnp.int32)
    abort_arr = _abort_array(abort, B, Sq)

    def _kv_index(b, h, j, pt, pos, ab, layer):
        last = pos[b] + jnp.maximum(ab[b], 1) - 1
        jj = jnp.minimum(j, last // page_size)
        return (layer[0], jnp.minimum(pt[b, jj], n_pages - 1), h, 0, 0)

    out = pl.pallas_call(
        functools.partial(_prefill_attention_paged_kernel, scale=D ** -0.5,
                          block_k=page_size, group=G, d_major=d_major),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Sq * G, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, Hkv, P),
            in_specs=[
                pl.BlockSpec((1, 1, Sq * G, D),
                             lambda b, h, j, pt, pos, ab, layer: (b, h, 0, 0)),
                pl.BlockSpec((None, 1, 1) + tile, _kv_index),
                pl.BlockSpec((None, 1, 1) + tile, _kv_index),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, Sq * G, D),
                lambda b, h, j, pt, pos, ab, layer: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Sq * G, 1), jnp.float32),
                pltpu.VMEM((Sq * G, 1), jnp.float32),
                pltpu.VMEM((Sq * G, D), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pt, pos_arr, abort_arr, layer_arr, qg, k_pages, v_pages)
    out = out.reshape(B, Hkv, Sq, G, D).transpose(0, 2, 1, 3, 4) \
             .reshape(B, Sq, H, D)
    if abort is None:
        return out
    return out, abort_arr    # progress: the cap, already clamped to [0, Sq]
