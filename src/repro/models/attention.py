"""Attention blocks: GQA (with qk-norm, logit softcap, local windows), MLA
(DeepSeek-V2 latent attention, with absorbed-matmul decode and a compressed
latent KV cache), and cross-attention (whisper / VLM image layers).

Full-sequence paths use a grouped einsum formulation (no KV-head repeat
materialization); the Pallas flash kernel in ``repro.kernels`` is an optional
drop-in for the same contract (see ``use_flash`` seam in transformer.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import apply_rope, causal_mask, dense_init, local_mask, ones, rms_norm, softcap

NEG_INF = -2.0 ** 30  # large-negative that survives bf16


# ---------------------------------------------------------------------------
# parameter builders
# ---------------------------------------------------------------------------

def init_gqa(key, path, cfg, dtype):
    D, H, Hkv, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # head-split weights: the fan-in is every input axis (D in, H*Dh out)
    p = {
        "wq": dense_init(key, path + "/wq", (D, H, Dh), dtype, D ** -0.5),
        "wk": dense_init(key, path + "/wk", (D, Hkv, Dh), dtype, D ** -0.5),
        "wv": dense_init(key, path + "/wv", (D, Hkv, Dh), dtype, D ** -0.5),
        "wo": dense_init(key, path + "/wo", (H, Dh, D), dtype,
                         (H * Dh) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_gamma"] = jnp.zeros((Dh,), dtype)
        p["k_gamma"] = jnp.zeros((Dh,), dtype)
    return p


def init_mla(key, path, cfg, dtype):
    m, D, H = cfg.mla, cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": dense_init(key, path + "/wq_a", (D, m.q_lora_rank), dtype),
        "q_ln": jnp.zeros((m.q_lora_rank,), dtype),
        "wq_b": dense_init(key, path + "/wq_b", (m.q_lora_rank, H, qk), dtype,
                           m.q_lora_rank ** -0.5),
        "wkv_a": dense_init(key, path + "/wkv_a",
                            (D, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_ln": jnp.zeros((m.kv_lora_rank,), dtype),
        "wk_b": dense_init(key, path + "/wk_b",
                           (m.kv_lora_rank, H, m.qk_nope_head_dim), dtype,
                           m.kv_lora_rank ** -0.5),
        "wv_b": dense_init(key, path + "/wv_b",
                           (m.kv_lora_rank, H, m.v_head_dim), dtype,
                           m.kv_lora_rank ** -0.5),
        "wo": dense_init(key, path + "/wo", (H, m.v_head_dim, D), dtype,
                         (H * m.v_head_dim) ** -0.5),
    }


def init_cross_attn(key, path, cfg, kv_dim, dtype):
    D, H, Dh = cfg.d_model, cfg.num_heads, cfg.head_dim
    Hkv = cfg.num_kv_heads
    return {
        "wq": dense_init(key, path + "/wq", (D, H, Dh), dtype, D ** -0.5),
        "wk": dense_init(key, path + "/wk", (kv_dim, Hkv, Dh), dtype,
                         kv_dim ** -0.5),
        "wv": dense_init(key, path + "/wv", (kv_dim, Hkv, Dh), dtype,
                         kv_dim ** -0.5),
        "wo": dense_init(key, path + "/wo", (H, Dh, D), dtype,
                         (H * Dh) ** -0.5),
        "gate": jnp.zeros((), dtype),   # VLM-style tanh gate on the residual
    }


# ---------------------------------------------------------------------------
# core grouped attention
# ---------------------------------------------------------------------------

BLOCKED_THRESHOLD = 2048   # use q-blocked attention above this seq length


def blocked_attention(q, k, v, *, causal=True, window=None, cap=None,
                      q_offset=0, block_q=512, unroll=False):
    """Memory-bounded attention: scan over query blocks with the full K/V
    resident (scores never exceed [B,Hkv,G,block_q,Skv]). GQA without KV
    repeat. This is the lowering-scale path (prefill_32k / train_4k);
    the Pallas flash kernel implements the same contract on real TPUs."""
    B, Sq, H, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    block_q = min(block_q, Sq)
    assert Sq % block_q == 0
    nb = Sq // block_q
    qb = q.reshape(B, nb, block_q, Hkv, G, Dh).transpose(1, 0, 2, 3, 4, 5)
    k_pos = jnp.arange(Skv)[None, :]

    def one(i, qblk):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, k,
                       preferred_element_type=jnp.float32) * (Dh ** -0.5)
        if cap is not None:
            s = softcap(s, cap)
        q_pos = (i * block_q + jnp.arange(block_q))[:, None] + q_offset
        mask = jnp.ones((block_q, Skv), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    if unroll:
        outs = [one(i, qb[i]) for i in range(nb)]
        out = jnp.stack(outs, axis=0)
    else:
        out = jax.lax.scan(
            lambda c, inp: (c, one(inp[0], inp[1])),
            0, (jnp.arange(nb), qb))[1]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, v.shape[-1])
    return out.astype(q.dtype)


def grouped_attention(q, k, v, mask, cap=None):
    """q: [B,Sq,H,Dh]; k,v: [B,Sk,Hkv,Dh]; mask: [B?,Sq,Sk] or [Sq,Sk] bool.

    Returns [B,Sq,H,Dh]. Grouped (GQA) without repeating KV heads.
    """
    B, Sq, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, Dh)
    # bf16 operands with fp32 accumulation (MXU-native); never materialize a
    # fp32 copy of the K/V (cache) tensors
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * (Dh ** -0.5)
    if cap is not None:
        scores = softcap(scores, cap)
    if mask is not None:
        if mask.ndim == 2:                     # [Sq,Sk]
            mask = mask[None, None, None]
        elif mask.ndim == 3:                   # [B,Sq,Sk]
            mask = mask[:, None, None]
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def _proj_qkv(p, x, cfg, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_gamma"], cfg.norm_eps)
        k = rms_norm(k, p["k_gamma"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(p, x, cfg, *, layer_kind="global", positions=None, causal=True):
    """Full-sequence self attention. x: [B,S,D]."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _proj_qkv(p, x, cfg, positions)
    window = cfg.local_window if (layer_kind == "local" and causal) else None
    if S > BLOCKED_THRESHOLD:
        out = blocked_attention(q, k, v, causal=causal, window=window,
                                cap=cfg.attn_logit_softcap,
                                unroll=not cfg.scan_layers)
    else:
        if not causal:
            mask = None
        elif window:
            mask = local_mask(S, S, window)
        else:
            mask = causal_mask(S, S)
        out = grouped_attention(q, k, v, mask, cfg.attn_logit_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def _decode_core(q, cache_k, cache_v, positions, cfg, layer_kind, x_dtype,
                 *, use_flash=False):
    """Shared cached-context attention core over a dense KV window.
    q: [B,Sq,H,Dh] (Sq == 1 for decode, a token chunk for chunked prefill);
    cache_{k,v}: [B,Hkv,S,Dh] (KV-major); positions: [B,Sq] — each query row
    attends to cached positions <= its own. When ``use_flash`` is set (and
    the layer has no softcap/local window, which the Pallas kernels don't
    implement) the ragged flash kernels replace the jnp einsum core — the
    decode kernel for one-token rows, the chunked-prefill kernel otherwise —
    same contract, per-row early exit."""
    B, Sq, H, Dh = q.shape
    Hkv, S = cache_k.shape[1], cache_k.shape[2]
    G = H // Hkv
    window = cfg.local_window if layer_kind == "local" else None
    if use_flash and not cfg.attn_logit_softcap and not window:
        from ..kernels import ops as kops    # lazy: keep pallas off cold paths
        if Sq == 1:
            out = kops.decode_attention(q[:, 0], cache_k, cache_v,
                                        positions[:, 0].astype(jnp.int32),
                                        kv_layout="bhsd")
            return out[:, None].astype(x_dtype)
        out = kops.prefill_attention(q, cache_k, cache_v,
                                     positions[:, 0].astype(jnp.int32))
        return out.astype(x_dtype)
    kv_pos = jnp.arange(S)[None, None, :]
    valid = kv_pos <= positions[:, :, None]         # [B, Sq, S]
    if window:
        valid &= kv_pos > positions[:, :, None] - window
    qg = q.reshape(B, Sq, Hkv, G, Dh)
    scores = jnp.einsum("bqhgd,bhkd->bhgqk", qg, cache_k,
                        preferred_element_type=jnp.float32) * (Dh ** -0.5)
    if cfg.attn_logit_softcap:
        scores = softcap(scores, cfg.attn_logit_softcap)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bqhgd", w.astype(cache_v.dtype), cache_v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, Dh).astype(x_dtype)


def _chunk_scatter(cache, new, pos, *, axis):
    """Scatter a contiguous Sq-token chunk into the cache's sequence axis at
    per-row start positions ``pos`` [B] (new: cache-shaped on every axis but
    ``axis``, where it carries Sq entries). Rows whose positions fall outside
    the window write nothing — the vector-``pos`` analogue of the decode
    paths' drop-out-of-range contract, so a sentinel ``pos >= Smax`` masks a
    row out of the batched call entirely."""
    Smax, Sq = cache.shape[axis], new.shape[axis]
    idx = jnp.arange(Smax)[None, :] - pos[:, None]            # [B, Smax]
    sel = (idx >= 0) & (idx < Sq)
    shape = [1] * cache.ndim
    shape[0], shape[axis] = idx.shape[0], Smax
    gather = jnp.clip(idx, 0, Sq - 1).reshape(shape)
    src = jnp.take_along_axis(new, gather, axis=axis)
    return jnp.where(sel.reshape(shape), src, cache)


def gqa_decode(p, x, cfg, cache_k, cache_v, pos, *, layer_kind="global",
               use_flash=False):
    """One-token decode. x: [B,1,D]; cache_{k,v}: [B,Hkv,Smax,Dh] (KV-major:
    attention-einsum-native layout, no per-step transposes; sequence axis is
    the sharding axis); pos: scalar, or [B] per-row positions (continuous
    batching: each slot of a decode batch sits at its own sequence offset).

    Cache write: a scalar ``pos`` takes the ``dynamic_update_slice`` fast
    path (one-token traffic), a vector ``pos`` the ragged mask-scatter
    fallback; either way positions out of range simply write nothing, and
    the two paths produce bit-identical caches (tested).
    Returns (out [B,1,D], new_cache_k, new_cache_v).
    """
    B = x.shape[0]
    Smax = cache_k.shape[2]
    positions = jnp.broadcast_to(jnp.asarray(pos), (B,))[:, None]
    q, k, v = _proj_qkv(p, x, cfg, positions)       # k,v: [B,1,Hkv,Dh]
    kt = k.transpose(0, 2, 1, 3).astype(cache_k.dtype)   # [B,Hkv,1,Dh]
    vt = v.transpose(0, 2, 1, 3).astype(cache_v.dtype)
    if jnp.ndim(pos) == 0:
        p0 = jnp.asarray(pos, jnp.int32)
        # guard out-of-range like the mask-scatter (write nothing) instead
        # of letting dynamic_update_slice clamp onto the last entry
        cache_k, cache_v = jax.lax.cond(
            p0 < Smax,
            lambda ck, cv: (jax.lax.dynamic_update_slice(ck, kt,
                                                         (0, 0, p0, 0)),
                            jax.lax.dynamic_update_slice(cv, vt,
                                                         (0, 0, p0, 0))),
            lambda ck, cv: (ck, cv), cache_k, cache_v)
    else:
        upd = (jnp.arange(Smax)[None, :] == positions)[:, None, :, None]
        cache_k = jnp.where(upd, kt, cache_k)
        cache_v = jnp.where(upd, vt, cache_v)
    out = _decode_core(q, cache_k, cache_v, positions, cfg, layer_kind,
                       x.dtype, use_flash=use_flash)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache_k, cache_v


def gqa_prefill_step(p, x, cfg, cache_k, cache_v, pos, *, layer_kind="global",
                     use_flash=False):
    """Cached-context chunked prefill over the dense slot cache.

    x: [B,Sq,D] — an Sq-token prompt chunk per row, starting at per-row cache
    position ``pos`` [B]; cache_{k,v}: [B,Hkv,Smax,Dh] (KV-major). The
    chunk's K/V are scattered into the window first, then each query at
    pos+i attends to the pos+i cached prefix (earlier chunks / a shared
    prefix) plus the chunk itself — the primitive behind both chunked
    prefill and batched prefix-cache suffix replay. Rows with ``pos >=
    Smax`` write nothing and their outputs are garbage (the scheduler's
    masked-row convention). An Sq == 1 call is shape-identical to
    :func:`gqa_decode`'s vector-``pos`` path, which is what makes the
    scheduler's final one-token chunk bit-equal to the seed's
    scan-of-decode-steps prefill. Returns (out [B,Sq,D], new caches)."""
    B, Sq, _ = x.shape
    positions = pos[:, None] + jnp.arange(Sq)[None, :]        # [B, Sq]
    q, k, v = _proj_qkv(p, x, cfg, positions)
    kt = k.transpose(0, 2, 1, 3).astype(cache_k.dtype)        # [B,Hkv,Sq,Dh]
    vt = v.transpose(0, 2, 1, 3).astype(cache_v.dtype)
    cache_k = _chunk_scatter(cache_k, kt, pos, axis=2)
    cache_v = _chunk_scatter(cache_v, vt, pos, axis=2)
    out = _decode_core(q, cache_k, cache_v, positions, cfg, layer_kind,
                       x.dtype, use_flash=use_flash)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache_k, cache_v


def _paged_append(pools, layer, news, page_table, pos, *, seq_axis):
    """Write an Sq-token chunk per row into ``layer`` of stacked page pools,
    where the pools lie, and return them.

    pools: a tuple of [L, n_pages, *page] (K and V, or the two latent
    pools), the page's token axis at ``seq_axis`` of ``page``; news: the
    matching [B, Sq, *entry] (``page`` without its token axis); pos: [B]
    chunk starts; page_table: [B, P] (negative entries, entries >= n_pages
    and logical pages past the table are unmapped).

    A row's Sq tokens touch at most ``n_seg`` pages. Those pages are read,
    the row's tokens merged in, and the pages written back whole, by one
    gather and one scatter per pool: only the touched pages move, with no
    per-layer slice of the pool, and a whole-page window leaves XLA no
    reason to relayout the pool (a token-granular scatter does). Rows at
    unmapped positions (the write sentinel) and pages that take none of a
    row's tokens are not written. A page written here belongs to one row
    (pages are shared only read-only, and forked before a write), so no two
    written pages collide."""
    B, Sq = news[0].shape[:2]
    n_pages, ps = pools[0].shape[1], pools[0].shape[2 + seq_axis]
    P = page_table.shape[1]
    n_seg = (Sq + ps - 2) // ps + 1
    logical = pos[:, None] // ps + jnp.arange(n_seg)[None, :]   # [B, n_seg]
    phys = jnp.take_along_axis(page_table, jnp.clip(logical, 0, P - 1),
                               axis=1)
    mapped = (phys >= 0) & (phys < n_pages) & (logical < P)
    tok = ((logical * ps)[..., None] + jnp.arange(ps)
           - pos[:, None, None])                                # [B,n_seg,ps]
    keep = mapped[..., None] & (tok >= 0) & (tok < Sq)
    rows = (jnp.arange(B)[:, None], jnp.clip(tok, 0, Sq - 1).reshape(B, -1))
    dest = jnp.where(keep.any(axis=-1), phys, n_pages)   # n_pages: dropped
    phys = jnp.clip(phys, 0, n_pages - 1)
    out = []
    for pool, new in zip(pools, news):
        src = new[rows].reshape((B, n_seg, ps) + new.shape[2:])
        src = jnp.moveaxis(src.astype(pool.dtype), 2, 2 + seq_axis)
        n_entry = new.ndim - 2
        pages = jnp.where(
            keep.reshape((B, n_seg) + (1,) * seq_axis + (ps,)
                         + (1,) * (n_entry - seq_axis)),
            src, pool[layer, phys])                      # [B, n_seg, *page]
        out.append(pool.at[layer, dest].set(pages, mode="drop"))
    return out


def _gqa_paged(p, x, cfg, k_pages, v_pages, page_table, pos, layer,
               layer_kind, use_flash, chunk):
    """Shared body of the paged GQA decode and chunk steps: append the
    chunk's K/V at ``layer`` of the stacked pools, then attend through the
    page table (the chunked-prefill kernel if ``chunk``, else the decode
    kernel)."""
    B, Sq, _ = x.shape
    n_pages = k_pages.shape[1]
    Dh = k_pages.shape[-1]
    positions = pos[:, None] + jnp.arange(Sq)[None, :]        # [B, Sq]
    q, k, v = _proj_qkv(p, x, cfg, positions)       # k,v: [B,Sq,Hkv,Dh]
    k_pages, v_pages = _paged_append((k_pages, v_pages), layer, (k, v),
                                     page_table, pos, seq_axis=1)
    if use_flash and not cfg.attn_logit_softcap and \
            not (layer_kind == "local" and cfg.local_window):
        from ..kernels import ops as kops
        if chunk:
            out = kops.prefill_attention_paged(q, k_pages, v_pages,
                                               page_table, pos, layer=layer)
        else:
            out = kops.decode_attention_paged(q[:, 0], k_pages, v_pages,
                                              page_table, pos,
                                              layer=layer)[:, None]
        out = out.astype(x.dtype)
    else:
        pt = jnp.clip(page_table, 0, n_pages - 1)
        kd = k_pages[layer, pt]                     # [B,P,Hkv,ps,Dh]
        vd = v_pages[layer, pt]
        P, Hkv, ps = pt.shape[1], kd.shape[2], kd.shape[3]
        kd = kd.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, P * ps, Dh)
        vd = vd.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, P * ps, Dh)
        out = _decode_core(q, kd, vd, positions, cfg, layer_kind, x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), k_pages, v_pages


def gqa_decode_paged(p, x, cfg, k_pages, v_pages, page_table, pos, *,
                     layer, layer_kind="global", use_flash=False):
    """One-token decode against a paged KV cache (serving fast path).

    k_pages/v_pages: [L, n_pages, Hkv, page_size, Dh] — every layer's page
    pool, shared by every slot of the tenant (carved from the ColoredArena
    by ``serving.kv_cache.PagedKVCache``), of which this call reads and
    writes ``layer``; page_table: [B, P] int32 mapping each row's logical
    pages to pool pages (entries >= n_pages are unmapped); pos: scalar or
    [B].

    The append rewrites one page per row, the one that takes its token (no
    copy of the layer's pool), and unmapped rows drop their writes. The read
    side: ``use_flash`` gathers pages inside the kernel's BlockSpec index
    map (no dense copy, per-row early exit — the real-hardware path); the
    jnp fallback materializes a dense [B, P*page_size] window view first,
    so it pays an extra window copy per layer and is a correctness path,
    not a traffic win. Returns (out [B,1,D], new_k_pages, new_v_pages).
    """
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    return _gqa_paged(p, x, cfg, k_pages, v_pages, page_table, pos, layer,
                      layer_kind, use_flash, chunk=False)


def gqa_prefill_paged(p, x, cfg, k_pages, v_pages, page_table, pos, *,
                      layer, layer_kind="global", use_flash=False):
    """Cached-context chunked prefill against a paged KV cache: the paged
    counterpart of :func:`gqa_prefill_step` (and the batched replacement for
    the prefix cache's one-token-per-step suffix replay).

    x: [B,Sq,D]; pools/page_table/layer as in :func:`gqa_decode_paged`;
    pos: [B] chunk start positions. The Sq appends write each row's tokens
    page by page in place (rows with unmapped or out-of-table positions
    drop); the read side gathers the per-row window — through the
    chunked-prefill Pallas kernel's BlockSpec index map under
    ``use_flash``, or a dense window view in the jnp correctness path.
    Returns (out [B,Sq,D], new pools)."""
    return _gqa_paged(p, x, cfg, k_pages, v_pages, page_table,
                      pos.astype(jnp.int32), layer, layer_kind, use_flash,
                      chunk=True)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

def _mla_q(p, x, cfg, positions):
    m = cfg.mla
    ql = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_ln"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", ql, p["wq_b"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p, x, cfg, positions):
    m = cfg.mla
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv = rms_norm(kv[..., : m.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, m.kv_lora_rank:], positions, cfg.rope_theta)
    return c_kv, k_rope[..., 0, :]                  # [B,S,R], [B,S,rope]


def mla_forward(p, x, cfg, *, positions=None, causal=True, **_):
    """Full-sequence MLA with expanded keys/values (training/prefill path).
    The rope sub-dim is folded into per-head keys so the GQA attention cores
    (blocked or grouped) apply unchanged."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_latent(p, x, cfg, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"])
    # fold rope dims: q' = [q_nope | q_rope], k' = [k_nope | k_rope(bcast)]
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    # rescale so the shared 1/sqrt(d) in the attention cores matches MLA's
    d_eff = m.qk_nope_head_dim + m.qk_rope_head_dim
    ratio = (d_eff ** -0.5) / (q_full.shape[-1] ** -0.5)
    if abs(ratio - 1.0) > 1e-9:
        q_full = q_full * ratio
    if S > BLOCKED_THRESHOLD:
        out = blocked_attention(q_full, k_full, v, causal=causal,
                                unroll=not cfg.scan_layers)
    else:
        mask = causal_mask(S, S) if causal else None
        out = grouped_attention(q_full, k_full, v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def _mla_core(p, x, cfg, q_nope, q_rope, cache_ckv, cache_krope, positions):
    """Absorbed-matmul attention over a dense latent window. cache_ckv:
    [B,S,R]; cache_krope: [B,S,rope]; positions: [B,Sq] (Sq == 1 for
    decode, a token chunk for chunked prefill — each query row attends to
    latents at positions <= its own)."""
    m = cfg.mla
    Smax = cache_ckv.shape[1]
    q_eff = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"])
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_eff, cache_ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhk,bsk->bhqs", q_rope, cache_krope,
                           preferred_element_type=jnp.float32)) * scale
    valid = (jnp.arange(Smax)[None, None, :]
             <= positions[:, :, None])[:, None]               # [B,1,Sq,S]
    scores = jnp.where(valid, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    o_latent = jnp.einsum("bhqs,bsr->bqhr", w.astype(cache_ckv.dtype),
                          cache_ckv, preferred_element_type=jnp.float32)
    out = jnp.einsum("bqhr,rhn->bqhn", o_latent.astype(x.dtype), p["wv_b"])
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def mla_decode(p, x, cfg, cache_ckv, cache_krope, pos, **_):
    """Absorbed-matmul MLA decode against the compressed latent cache.

    cache_ckv: [B,Smax,R]; cache_krope: [B,Smax,rope].
    Scores are computed in latent space: q_eff = q_nope @ wk_b (absorbed), and
    the attention output is re-expanded through wv_b afterwards — the cache
    stays at R + rope floats per token (the paper-relevant serving win).
    pos: scalar (``dynamic_update_slice`` one-token write), or [B] per-row
    positions (ragged mask-scatter fallback; continuous batching). Both
    write paths are bit-identical, dropping out-of-range writes.
    """
    B = x.shape[0]
    Smax = cache_ckv.shape[1]
    positions = jnp.broadcast_to(jnp.asarray(pos), (B,))[:, None]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_latent(p, x, cfg, positions)
    if jnp.ndim(pos) == 0:
        p0 = jnp.asarray(pos, jnp.int32)
        cache_ckv, cache_krope = jax.lax.cond(
            p0 < Smax,
            lambda c, r: (jax.lax.dynamic_update_slice(
                              c, c_kv.astype(c.dtype), (0, p0, 0)),
                          jax.lax.dynamic_update_slice(
                              r, k_rope.astype(r.dtype), (0, p0, 0))),
            lambda c, r: (c, r), cache_ckv, cache_krope)
    else:
        upd = (jnp.arange(Smax)[None, :] == positions)[:, :, None]  # [B,S,1]
        cache_ckv = jnp.where(upd, c_kv.astype(cache_ckv.dtype), cache_ckv)
        cache_krope = jnp.where(upd, k_rope.astype(cache_krope.dtype),
                                cache_krope)
    return (_mla_core(p, x, cfg, q_nope, q_rope, cache_ckv, cache_krope,
                      positions),
            cache_ckv, cache_krope)


def mla_prefill_step(p, x, cfg, cache_ckv, cache_krope, pos, **_):
    """Cached-context chunked MLA prefill (absorbed-matmul): the Sq-token
    chunk's latents are scattered into the dense latent window at per-row
    start positions ``pos`` [B], then each query attends to its own latent
    prefix. Returns (out [B,Sq,D], new caches)."""
    B, Sq, _ = x.shape
    positions = pos[:, None] + jnp.arange(Sq)[None, :]        # [B, Sq]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_latent(p, x, cfg, positions)
    cache_ckv = _chunk_scatter(cache_ckv, c_kv.astype(cache_ckv.dtype),
                               pos, axis=1)
    cache_krope = _chunk_scatter(cache_krope,
                                 k_rope.astype(cache_krope.dtype),
                                 pos, axis=1)
    return (_mla_core(p, x, cfg, q_nope, q_rope, cache_ckv, cache_krope,
                      positions),
            cache_ckv, cache_krope)


def _mla_paged(p, x, cfg, ckv_pages, krope_pages, page_table, pos, layer):
    """Append the chunk's latents at ``layer`` of the stacked latent pools
    and attend over each row's gathered window."""
    B, Sq, _ = x.shape
    n_pages = ckv_pages.shape[1]
    positions = pos[:, None] + jnp.arange(Sq)[None, :]        # [B, Sq]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_latent(p, x, cfg, positions)
    ckv_pages, krope_pages = _paged_append(
        (ckv_pages, krope_pages), layer, (c_kv, k_rope), page_table, pos,
        seq_axis=0)
    pt = jnp.clip(page_table, 0, n_pages - 1)
    ckv = ckv_pages[layer, pt].reshape(B, -1, ckv_pages.shape[-1])
    krope = krope_pages[layer, pt].reshape(B, -1, krope_pages.shape[-1])
    return (_mla_core(p, x, cfg, q_nope, q_rope, ckv, krope, positions),
            ckv_pages, krope_pages)


def mla_decode_paged(p, x, cfg, ckv_pages, krope_pages, page_table, pos, *,
                     layer, **_):
    """Paged MLA decode: the latent cache lives in a shared page pool.

    ckv_pages: [L, n_pages, page_size, R]; krope_pages: [L, n_pages,
    page_size, rope] (every layer's pools, of which ``layer`` is read and
    written); page_table: [B, P]
    int32 (entries >= n_pages unmapped). The append rewrites, in place, the
    page that takes each row's new latent; attention runs over the per-row
    gathered window of P * page_size tokens.
    """
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    return _mla_paged(p, x, cfg, ckv_pages, krope_pages, page_table, pos,
                      layer)


def mla_prefill_paged(p, x, cfg, ckv_pages, krope_pages, page_table, pos, *,
                      layer, **_):
    """Cached-context chunked MLA prefill against the paged latent pools:
    each row's Sq latents are appended in place (unmapped positions drop),
    then attention runs over the per-row gathered window. Returns (out
    [B,Sq,D], new pools)."""
    return _mla_paged(p, x, cfg, ckv_pages, krope_pages, page_table,
                      pos.astype(jnp.int32), layer)


# ---------------------------------------------------------------------------
# cross attention (enc-dec / VLM)
# ---------------------------------------------------------------------------

def cross_attn_forward(p, x, kv_feats, cfg, gated=False):
    """x: [B,S,D]; kv_feats: [B,T,kv_dim] (encoder output / patch embeddings)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", kv_feats.astype(x.dtype), p["wk"])
    v = jnp.einsum("btd,dhk->bthk", kv_feats.astype(x.dtype), p["wv"])
    out = grouped_attention(q, k, v, mask=None, cap=cfg.attn_logit_softcap)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if gated:
        out = jnp.tanh(p["gate"].astype(jnp.float32)).astype(out.dtype) * out
    return out
