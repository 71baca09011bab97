"""Flash-decode (TPU Pallas): one-new-token GQA attention against a KV cache.

Two entry points share one online-softmax kernel body:

``decode_attention``       dense cache [B,Smax,Hkv,D] (or KV-major
                           [B,Hkv,Smax,D] via ``kv_layout="bhsd"``), with
                           *ragged* per-row valid lengths: ``pos`` may be a
                           scalar or a [B] vector (continuous batching).
``decode_attention_paged`` page-pool cache [n_pages,Hkv,page,D], or the
                           whole layer stack of pools [L,n_pages,Hkv,page,D]
                           read at one ``layer``, addressed through a per-row
                           page table — the serving engine's PagedKVCache
                           layout; no dense gather or per-layer slice is
                           materialized.

Ragged early-exit: the kv grid axis is sequential and its BlockSpec index
map pins every block past a row's last valid block to that last block
(Pallas elides the copy when consecutive steps request the same block), and
``pl.when`` skips the compute — so per-row cost scales with the row's actual
sequence length, not ``Smax``. Grid: (batch, kv_heads, num_kv_blocks) with
(m, l, acc) scratch sized [group, D]; all query heads of one KV head are
processed together (the MXU-friendly GQA decode layout).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_compat import interpret_default

NEG_INF = -1e30


def scores(q, k_ref, d_major):
    """q [rows, D] against a kv block held as it lies in HBM ([1, 1, bk, D],
    or [1, 1, D, bk] for a D-major pool): [rows, bk] in float32."""
    k = k_ref[0, 0].astype(jnp.float32)
    if d_major:
        return jnp.dot(q, k, preferred_element_type=jnp.float32)
    return jnp.dot(q, k.T, preferred_element_type=jnp.float32)


def weighted_values(p, v_ref, d_major):
    """p [rows, bk] times the value block (laid out as in :func:`scores`):
    [rows, D] in float32."""
    v = v_ref[0, 0].astype(jnp.float32)
    if d_major:
        return jax.lax.dot_general(p, v, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    return jnp.dot(p, v, preferred_element_type=jnp.float32)


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, block_k, d_major=False):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # per-row early exit: blocks past this row's position carry no valid
    # keys — their BlockSpec index is pinned (no new HBM traffic) and the
    # compute is predicated off entirely
    @pl.when(ki <= pos_ref[b] // block_k)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [G, D]
        s = scores(q, k_ref, d_major)                        # [G, bk]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos_ref[b], s, NEG_INF)

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


def _decode_attention_paged_kernel(pt_ref, pos_ref, layer_ref, q_ref, k_ref,
                                   v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                                   scale, block_k, d_major):
    # the page table and the layer are consumed by the BlockSpec index maps
    del pt_ref, layer_ref
    _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            scale=scale, block_k=block_k, d_major=d_major)


def paged_pools(k_pages, v_pages, layer):
    """The paged kernels' view of the pools: (k, v, layer [1] int32,
    d_major). A 4-D pool [n_pages, Hkv, page, D] is the one-layer stack at
    layer 0 (a free reshape). XLA's default TPU layout stores a [page, D]
    tile D-major when D is under one 128-lane width and the page fills one
    (stablelm: D 64, page 128); such pools are handed over transposed, [..,
    D, page], which reads them as they lie (a bitcast) where a [page, D]
    block would make XLA relayout every pool the kernel is given."""
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    page_size, D = k_pages.shape[-2:]
    d_major = D < 128 <= page_size
    if d_major:
        k_pages = jnp.swapaxes(k_pages, -1, -2)
        v_pages = jnp.swapaxes(v_pages, -1, -2)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    return k_pages, v_pages, layer, d_major


def _pos_vector(pos, B):
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))


def decode_attention(q, k_cache, v_cache, pos, *, block_k=128,
                     interpret=None, kv_layout="bshd"):
    """q: [B,H,D] (one new token); caches: [B,Smax,Hkv,D] (``kv_layout=
    "bshd"``, the default) or KV-major [B,Hkv,Smax,D] (``"bhsd"``, the
    serving cache layout — saves the transpose); pos: scalar int32 or [B]
    per-row positions. ``interpret=None`` auto-detects the backend
    (CPU hosts interpret, TPU compiles). Returns [B,H,D]."""
    if interpret is None:
        interpret = interpret_default()
    B, H, D = q.shape
    if kv_layout == "bshd":
        kt = k_cache.transpose(0, 2, 1, 3)                   # [B,Hkv,S,D]
        vt = v_cache.transpose(0, 2, 1, 3)
    elif kv_layout == "bhsd":
        kt, vt = k_cache, v_cache
    else:
        raise ValueError(f"unknown kv_layout {kv_layout!r}")
    Smax, Hkv = kt.shape[2], kt.shape[1]
    G = H // Hkv
    block_k = min(block_k, Smax)
    if Smax % block_k:
        # non-aligned window: prefer the largest decent divisor (zero-copy
        # lowering); only pathological (e.g. prime) windows pad the caches
        # to a block multiple — a per-call copy, so callers wanting the
        # fast path should align Smax. Padded keys sit past every valid
        # position: the mask kills them and the early-exit index map never
        # fetches them.
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
    qg = q.reshape(B, Hkv, G, D)
    pos_arr = _pos_vector(pos, B)

    def _kv_index(b, h, j, pos):
        # pin out-of-range blocks to the row's last valid block: Pallas
        # skips the DMA when the block index repeats between steps
        return (b, h, jnp.minimum(j, pos[b] // block_k), 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=D ** -0.5, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, Smax // block_k),
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, j, pos: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, D), _kv_index),
                pl.BlockSpec((1, 1, block_k, D), _kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D),
                                   lambda b, h, j, pos: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, qg, kt, vt)
    return out.reshape(B, H, D)


def decode_attention_paged(q, k_pages, v_pages, page_table, pos, *,
                           layer=None, interpret=None):
    """Paged flash-decode: the KV lives in a shared page pool and each row's
    blocks are gathered through its page table *inside the BlockSpec index
    map* (one page = one kv block; no [B,Smax] dense view is materialized).

    q: [B,H,D]; {k,v}_pages: [n_pages,Hkv,page_size,D], or the stacked
    pools of every layer [L,n_pages,Hkv,page_size,D] with ``layer`` the one
    to read (the index map addresses it, so no per-layer slice is copied; a
    4-D pool is the ``L = 1``, ``layer = 0`` case); page_table: [B,P] int32
    (entries >= n_pages are unmapped — they are never touched because the kv
    index map clamps to the row's last valid page); pos: [B] int32. The
    visible window is P * page_size tokens. Returns [B,H,D].
    """
    if interpret is None:
        interpret = interpret_default()
    B, H, D = q.shape
    k_pages, v_pages, layer_arr, d_major = paged_pools(k_pages, v_pages,
                                                       layer)
    _, n_pages, Hkv = k_pages.shape[:3]
    tile = k_pages.shape[3:]              # (page, D), or (D, page) D-major
    page_size = tile[1] if d_major else tile[0]
    P = page_table.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    pos_arr = _pos_vector(pos, B)
    pt = jnp.asarray(page_table, jnp.int32)

    def _kv_index(b, h, j, pt, pos, layer):
        jj = jnp.minimum(j, pos[b] // page_size)
        return (layer[0], jnp.minimum(pt[b, jj], n_pages - 1), h, 0, 0)

    out = pl.pallas_call(
        functools.partial(_decode_attention_paged_kernel, scale=D ** -0.5,
                          block_k=page_size, d_major=d_major),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, Hkv, P),
            in_specs=[
                pl.BlockSpec((1, 1, G, D),
                             lambda b, h, j, pt, pos, layer: (b, h, 0, 0)),
                pl.BlockSpec((None, 1, 1) + tile, _kv_index),
                pl.BlockSpec((None, 1, 1) + tile, _kv_index),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, G, D), lambda b, h, j, pt, pos, layer: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pt, pos_arr, layer_arr, qg, k_pages, v_pages)
    return out.reshape(B, H, D)
