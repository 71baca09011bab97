"""Unified model: every assigned architecture is an instantiation of this
module (layer kinds: global/local attention, MLA, cross-attention, MoE-MLP,
RWKV6, Mamba2, zamba-style shared blocks; enc-dec for whisper).

Entry points:
    init_params(key, cfg)                  -> params pytree
    forward(params, cfg, batch)            -> (logits, aux)
    loss_fn(params, cfg, batch)            -> (loss, metrics)
    init_cache(cfg, B, max_seq)            -> cache pytree
    init_paged_cache(cfg, n_pages, page)   -> page-pool cache pytree
    decode_step(params, cfg, token, cache, pos, ctx) -> (logits, cache)
    prefill(params, cfg, batch, max_seq)   -> (logits_last, cache)

Layer stacking: an optional dense prefix (deepseek first-dense / zamba ragged
head) followed by the repeating layer pattern (period p) scanned over
(num_layers - prefix)/p periods with stacked params; ``cfg.scan_layers=False``
unrolls (used by the dry-run metric probes, where XLA's cost analysis counts a
scan body only once).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import attention as attn
from . import mlp as mlpm
from . import moe as moem
from . import ssm as ssmm
from .common import dt, embed_init, dense_init, rms_norm, softcap
from ..configs.base import ModelConfig
from ..dist.sharding import hint

SHARED_SUFFIX = "_shared"   # layer kinds ending with this also fire the shared block


def _kind_base(kind: str) -> str:
    return kind[: -len(SHARED_SUFFIX)] if kind.endswith(SHARED_SUFFIX) else kind


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, path, cfg: ModelConfig, kind: str, dtype, moe_layer: bool):
    kind = _kind_base(kind)
    D = cfg.d_model
    p: Dict[str, Any] = {"ln1": jnp.zeros((D,), dtype)}
    if kind in ("global", "local"):
        if cfg.attn_type == "mla":
            p["attn"] = attn.init_mla(key, path + "/attn", cfg, dtype)
        else:
            p["attn"] = attn.init_gqa(key, path + "/attn", cfg, dtype)
    elif kind == "cross":
        p["attn"] = attn.init_cross_attn(key, path + "/attn", cfg,
                                         cfg.vision.vision_dim, dtype)
    elif kind == "rwkv":
        p["rwkv"] = ssmm.init_rwkv_block(key, path + "/rwkv", cfg, dtype)
        p["ln2"] = jnp.zeros((D,), dtype)
        return p
    elif kind == "mamba":
        p["mamba"] = ssmm.init_mamba2_block(key, path + "/mamba", cfg, dtype)
        return p
    else:
        raise ValueError(kind)
    p["ln2"] = jnp.zeros((D,), dtype)
    if moe_layer:
        p["moe"] = moem.init_moe(key, path + "/moe", cfg, dtype)
    else:
        dff = cfg.d_ff
        if cfg.moe and cfg.moe.first_dense_layers and cfg.moe.d_ff_dense:
            dff = cfg.moe.d_ff_dense
        p["mlp"] = mlpm.init_mlp(key, path + "/mlp", D, dff, cfg.mlp_act, dtype)
    if cfg.name.startswith("gemma"):
        p["ln1_post"] = jnp.zeros((D,), dtype)
        p["ln2_post"] = jnp.zeros((D,), dtype)
    return p


def _pattern_segments(cfg: ModelConfig):
    """(n_prefix, prefix_kind, period_kinds, n_periods)."""
    n_prefix = cfg.prefix_layers or (cfg.moe.first_dense_layers if cfg.moe else 0)
    period = tuple(cfg.layer_pattern)
    n_rest = cfg.num_layers - n_prefix
    assert n_rest >= 0 and n_rest % len(period) == 0, \
        (cfg.name, cfg.num_layers, n_prefix, period)
    prefix_kind = _kind_base(period[0])
    return n_prefix, prefix_kind, period, n_rest // len(period)


def _moe_flag(cfg, kind: str) -> bool:
    return bool(cfg.moe) and _kind_base(kind) in ("global", "local", "cross")


def n_shared_invocations(cfg: ModelConfig) -> int:
    _, _, period, n_periods = _pattern_segments(cfg)
    per = sum(1 for k in period if k.endswith(SHARED_SUFFIX))
    return max(1, per * n_periods)


def init_params(key, cfg: ModelConfig):
    dtype = dt(cfg.param_dtype)
    D, V = cfg.d_model, cfg.vocab_size
    params: Dict[str, Any] = {
        "embed": embed_init(key, "embed", (V, D), dtype),
        "final_ln": jnp.zeros((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(key, "unembed", (D, V), dtype)
    if not cfg.use_rope:
        params["pos_embed"] = embed_init(key, "pos_embed",
                                         (cfg.max_position, D), dtype)
    n_prefix, prefix_kind, period, n_periods = _pattern_segments(cfg)
    if n_prefix:
        params["prefix"] = [
            _init_layer(jax.random.fold_in(key, 1000 + i), f"prefix/{i}", cfg,
                        prefix_kind, dtype,
                        moe_layer=False if cfg.moe else _moe_flag(cfg, prefix_kind))
            for i in range(n_prefix)]
    if n_periods:
        def init_one(k):
            return {f"s{j}": _init_layer(k, f"layers/s{j}", cfg, kind, dtype,
                                         _moe_flag(cfg, kind))
                    for j, kind in enumerate(period)}
        keys = jax.random.split(jax.random.fold_in(key, 7), n_periods)
        params["layers"] = jax.vmap(init_one)(keys)
    if cfg.family == "hybrid":
        sk = jax.random.fold_in(key, 77)
        params["shared"] = {
            "ln1": jnp.zeros((D,), dtype),
            "attn": attn.init_gqa(sk, "shared/attn", cfg, dtype),
            "ln2": jnp.zeros((D,), dtype),
            "mlp": mlpm.init_mlp(sk, "shared/mlp", D, cfg.d_ff, cfg.mlp_act, dtype),
            "in_proj": dense_init(sk, "shared/in_proj",
                                  (n_shared_invocations(cfg), 2 * D, D), dtype),
        }
    if cfg.encoder:
        ek = jax.random.fold_in(key, 99)
        enc = {"pos": embed_init(ek, "enc/pos", (cfg.encoder.num_frames, D), dtype),
               "ln_post": jnp.zeros((D,), dtype)}
        if cfg.encoder.num_layers:
            enc_keys = jax.random.split(ek, cfg.encoder.num_layers)
            enc["layers"] = jax.vmap(
                lambda k: _init_layer(k, "enc/layer", cfg, "global", dtype, False)
            )(enc_keys)
        params["encoder"] = enc
        if n_periods:
            dk = jax.random.split(jax.random.fold_in(key, 101), n_periods)
            params["cross"] = jax.vmap(
                lambda k: {"ln": jnp.zeros((D,), dtype),
                           **attn.init_cross_attn(k, "dec/cross", cfg, D, dtype)}
            )(dk)
    return params


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def cast_tree(p, cfg):
    """Cast-on-use mixed precision: fp32 master params enter compute in the
    activation dtype (norm internals re-upcast to fp32 where needed)."""
    dtype = dt(cfg.activation_dtype)
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, p)


def _maybe_post(h, p, name, cfg):
    return rms_norm(h, p[name], cfg.norm_eps) if name in p else h


def _mlp_or_moe(p, h, cfg, aux):
    if "moe" in p:
        out, a = moem.moe_forward(p["moe"], h, cfg)
        aux = {k: aux.get(k, 0.0) + a[k] for k in a}
        return out, aux
    return mlpm.mlp_forward(p["mlp"], h, cfg.mlp_act), aux


def _attn_layer(p, x, cfg, kind, ctx, aux, cache=None, pos=None, layer=None):
    """Pre-norm attention + MLP/MoE block. Returns (x, aux, new_cache).
    With a paged cache, ``layer`` is this layer's index into the stacked
    pools ``cache`` holds (None: ``cache`` holds this layer's pools alone)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    new_cache = cache
    if kind == "cross":
        a = attn.cross_attn_forward(p["attn"], h, ctx["vision"], cfg, gated=True)
    elif cache is None:
        if cfg.attn_type == "mla":
            a = attn.mla_forward(p["attn"], h, cfg, positions=ctx.get("positions"))
        else:
            a = attn.gqa_forward(p["attn"], h, cfg, layer_kind=kind,
                                 positions=ctx.get("positions"),
                                 causal=ctx.get("causal", True))
    elif "page_table" in ctx:
        # paged cache: leaves are shared page pools, addressed through the
        # per-row page table (serving engine fast path); ``ctx["chunk"]``
        # switches one-token decode to the cached-context chunked prefill
        # contract (Sq prompt tokens per row at per-row start positions)
        pt = ctx["page_table"]
        chunk = ctx.get("chunk", False)
        one = layer is None     # this layer's pools alone: a 1-layer stack
        if one:
            cache, layer = jax.tree.map(lambda a: a[None], cache), 0
        if cfg.attn_type == "mla":
            fn = attn.mla_prefill_paged if chunk else attn.mla_decode_paged
            a, ckv, kr = fn(p["attn"], h, cfg, cache["ckv"], cache["krope"],
                            pt, pos, layer=layer)
            new_cache = {"ckv": ckv, "krope": kr}
        else:
            fn = attn.gqa_prefill_paged if chunk else attn.gqa_decode_paged
            a, ck, cv = fn(
                p["attn"], h, cfg, cache["k"], cache["v"], pt, pos,
                layer=layer, layer_kind=kind,
                use_flash=ctx.get("use_flash", False))
            new_cache = {"k": ck, "v": cv}
        if one:
            new_cache = jax.tree.map(lambda a: a[0], new_cache)
    else:
        chunk = ctx.get("chunk", False)
        if cfg.attn_type == "mla":
            fn = attn.mla_prefill_step if chunk else attn.mla_decode
            a, ckv, kr = fn(p["attn"], h, cfg, cache["ckv"],
                            cache["krope"], pos)
            new_cache = {"ckv": ckv, "krope": kr}
        else:
            fn = attn.gqa_prefill_step if chunk else attn.gqa_decode
            a, ck, cv = fn(p["attn"], h, cfg, cache["k"],
                           cache["v"], pos, layer_kind=kind,
                           use_flash=ctx.get("use_flash", False))
            new_cache = {"k": ck, "v": cv}
    x = x + _maybe_post(a, p, "ln1_post", cfg)
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    m, aux = _mlp_or_moe(p, h2, cfg, aux)
    return x + _maybe_post(m, p, "ln2_post", cfg), aux, new_cache


def _whisper_cross(cp, x, cfg, ctx):
    cp = cast_tree(cp, cfg)
    h = rms_norm(x, cp["ln"], cfg.norm_eps)
    a = attn.cross_attn_forward(
        {k: cp[k] for k in ("wq", "wk", "wv", "wo", "gate")}, h,
        ctx["enc_out"], cfg, gated=False)
    return x + a


def _rwkv_layer(p, x, cfg, aux, cache=None):
    rp = p["rwkv"]
    st = cache["state"] if cache is not None else None
    tm_last = cache["tm_shift"] if cache is not None else None
    cm_last = cache["cm_shift"] if cache is not None else None
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    y, new_state, tm_shift = ssmm.rwkv_time_mix(rp, h, cfg, st, tm_last)
    x = x + y
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    y2, cm_shift = ssmm.rwkv_channel_mix(rp, h2, cfg, cm_last)
    new_cache = ({"state": new_state, "tm_shift": tm_shift,
                  "cm_shift": cm_shift} if cache is not None else None)
    return x + y2, aux, new_cache


def _mamba_layer(p, x, cfg, aux, cache=None):
    st = cache["state"] if cache is not None else None
    cv = cache["conv"] if cache is not None else None
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    y, new_state, new_conv = ssmm.mamba2_block(p["mamba"], h, cfg, st, cv)
    new_cache = ({"state": new_state, "conv": new_conv}
                 if cache is not None else None)
    return x + y, aux, new_cache


def _shared_block(sp, x, x0, cfg, inv_idx, aux, ctx, cache=None, pos=None):
    """zamba2 shared attention block: concat(current, original embedding),
    per-invocation input projection, shared attn+MLP; delta added to trunk."""
    sp = cast_tree(sp, cfg)
    w = jnp.take(sp["in_proj"], inv_idx, axis=0)
    h = jnp.concatenate([x, x0.astype(x.dtype)], axis=-1) @ w
    p = {k: sp[k] for k in ("ln1", "attn", "ln2", "mlp")}
    out, aux, new_cache = _attn_layer(p, h, cfg, "global", ctx, aux,
                                      cache=cache, pos=pos)
    return x + (out - h), aux, new_cache


def _apply_one(p, x, cfg, kind, ctx, aux, cache, pos, period_idx, slot,
               layer=None):
    """Apply one pattern slot (possibly + shared block)."""
    p = cast_tree(p, cfg)
    base = _kind_base(kind)
    if base in ("global", "local", "cross"):
        x, aux, nc = _attn_layer(p, x, cfg, base, ctx, aux, cache, pos,
                                 layer)
        if cfg.encoder and base == "global" and "cross_p" in ctx:
            cp = jax.tree.map(lambda a: a[period_idx], ctx["cross_p"])
            x = _whisper_cross(cp, x, cfg, ctx)
    elif base == "rwkv":
        x, aux, nc = _rwkv_layer(p, x, cfg, aux, cache)
    elif base == "mamba":
        x, aux, nc = _mamba_layer(p, x, cfg, aux, cache)
    else:
        raise ValueError(kind)
    return x, aux, nc


# ---------------------------------------------------------------------------
# stack
# ---------------------------------------------------------------------------

def _period_body(cfg, period, ctx, with_cache, paged=False):
    """One period of the layer loop. A dense cache comes in per period as
    a loop input and leaves as a loop output; a paged cache (``paged``)
    rides in the carry as the stacked pools of every period, which each
    attention layer updates in place at its period index — handing the loop
    one period's pools would copy them out of the stack and back in, in
    every layer of every step."""
    n_shared_per = max(1, sum(1 for k in period if k.endswith(SHARED_SUFFIX)))

    def body(carry, inp):
        if paged:
            (x, aux, pos, cache_period), (p_period, idx) = carry, inp
        elif with_cache:
            (x, aux, pos), (p_period, cache_period, idx) = carry, inp
        else:
            (x, aux), (p_period, idx) = carry, inp
            cache_period, pos = None, None
        new_caches = {}
        shared_i = 0
        for j, kind in enumerate(period):
            p = p_period[f"s{j}"]
            c = cache_period[f"s{j}"] if with_cache else None
            x, aux, nc = _apply_one(p, x, cfg, kind, ctx, aux, c, pos, idx, j,
                                    layer=idx if paged else None)
            new_caches[f"s{j}"] = nc
            if kind.endswith(SHARED_SUFFIX):
                inv = idx * n_shared_per + shared_i
                sc = cache_period.get("shared") if with_cache else None
                x, aux, nsc = _shared_block(ctx["shared_p"], x, ctx["x0"], cfg,
                                            inv, aux, ctx, sc, pos)
                if with_cache:
                    new_caches["shared"] = nsc
                shared_i += 1
        if paged:
            return (x, aux, pos, new_caches), None
        if with_cache:
            return (x, aux, pos), new_caches
        return (x, aux), None
    return body


def _aux0(cfg):
    if cfg.moe:
        return {"z_loss": jnp.zeros((), jnp.float32),
                "lb_loss": jnp.zeros((), jnp.float32),
                "dropped_frac": jnp.zeros((), jnp.float32)}
    return {}


def _apply_stack(params, cfg, x, ctx, cache=None, pos=None):
    aux = _aux0(cfg)
    new_cache: Dict[str, Any] = {} if cache is not None else None
    n_prefix, prefix_kind, period, n_periods = _pattern_segments(cfg)
    if n_prefix:
        for i, p in enumerate(params["prefix"]):
            c = cache["prefix"][i] if cache is not None else None
            x, aux, nc = _apply_one(p, x, cfg, prefix_kind, ctx, aux, c, pos,
                                    0, -1 - i)
            if cache is not None:
                new_cache.setdefault("prefix", []).append(nc)
    if n_periods == 0:
        if cache is not None and "layers" in cache:
            new_cache["layers"] = cache["layers"]   # zero-period passthrough
        return x, aux, new_cache
    ctx = dict(ctx)
    if cfg.encoder:
        ctx["cross_p"] = params["cross"]
    if cfg.family == "hybrid":
        ctx["shared_p"] = params["shared"]
    with_cache = cache is not None
    # paged pools (pageable models: no shared block) are loop-carried
    paged = with_cache and "page_table" in ctx
    body = _period_body(cfg, period, ctx, with_cache, paged)
    if cfg.remat != "none":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)
    idxs = jnp.arange(n_periods)
    if paged:
        carry = (x, aux, jnp.asarray(pos, jnp.int32), cache["layers"])
        if cfg.scan_layers:
            carry, _ = jax.lax.scan(body, carry, (params["layers"], idxs))
        else:
            for i in range(n_periods):
                p_i = jax.tree.map(lambda a: a[i], params["layers"])
                carry, _ = body(carry, (p_i, i))
        x, aux, _, new_cache["layers"] = carry
    elif cfg.scan_layers and not with_cache:
        (x, aux), _ = jax.lax.scan(body, (x, aux), (params["layers"], idxs))
    elif cfg.scan_layers:
        (x, aux, _), stack = jax.lax.scan(
            body, (x, aux, jnp.asarray(pos, jnp.int32)),
            (params["layers"], cache["layers"], idxs))
        new_cache["layers"] = stack
    else:
        percell = []
        for i in range(n_periods):
            p_i = jax.tree.map(lambda a: a[i], params["layers"])
            if not with_cache:
                (x, aux), _ = body((x, aux), (p_i, i))
            else:
                c_i = jax.tree.map(lambda a: a[i], cache["layers"])
                (x, aux, _), nc = body(
                    (x, aux, jnp.asarray(pos, jnp.int32)), (p_i, c_i, i))
                percell.append(nc)
        if with_cache:
            new_cache["layers"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *percell)
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _embed_tokens(params, cfg, tokens, positions=None):
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt(cfg.activation_dtype))
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if not cfg.use_rope:
        pos = (jnp.arange(tokens.shape[1])[None, :] if positions is None
               else positions)
        x = x + jnp.take(params["pos_embed"], pos, axis=0).astype(x.dtype)
    return x


def _run_encoder(params, cfg, frames):
    ep = params["encoder"]
    x = frames.astype(dt(cfg.activation_dtype)) + ep["pos"][None].astype(frames.dtype)
    if "layers" in ep:
        def body(x, p_i):
            x, _, _ = _attn_layer(cast_tree(p_i, cfg), x, cfg, "global",
                                  {"causal": False}, {})
            return x, None
        if cfg.scan_layers:
            x, _ = jax.lax.scan(body, x, ep["layers"])
        else:
            for i in range(cfg.encoder.num_layers):
                x, _ = body(x, jax.tree.map(lambda a: a[i], ep["layers"]))
    return rms_norm(x, ep["ln_post"], cfg.norm_eps)


def _logits(params, cfg, x):
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    logits = hint(logits, "data", None, "model")
    if cfg.final_logit_softcap:
        logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits


def _make_ctx(params, cfg, batch, positions):
    ctx = {"positions": positions}
    if cfg.vision:
        ctx["vision"] = batch["vision"]
    if cfg.encoder:
        ctx["enc_out"] = (batch["enc_out"] if "enc_out" in batch
                          else _run_encoder(params, cfg, batch["frames"]))
    return ctx


def forward(params, cfg: ModelConfig, batch, last_only: bool = False):
    tokens = batch["tokens"]
    x = _embed_tokens(params, cfg, tokens)
    x = hint(x, "data", None, None)
    ctx = _make_ctx(params, cfg, batch, jnp.arange(tokens.shape[1])[None, :])
    if cfg.family == "hybrid":
        ctx["x0"] = x
    x, aux, _ = _apply_stack(params, cfg, x, ctx)
    if last_only:   # prefill: only the last position's logits are needed
        x = x[:, -1:]
    return _logits(params, cfg, x), aux


def loss_fn(params, cfg: ModelConfig, batch):
    logits, aux = forward(params, cfg, batch)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(lse - gold)
    loss = nll
    metrics = {"nll": nll}
    for k in ("z_loss", "lb_loss"):
        if k in aux:
            loss = loss + aux[k] / max(cfg.num_layers, 1)
            metrics[k] = aux[k]
    if "dropped_frac" in aux:
        metrics["dropped_frac"] = aux["dropped_frac"] / max(cfg.num_layers, 1)
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# caches / decode
# ---------------------------------------------------------------------------

def _layer_cache(cfg: ModelConfig, kind: str, B: int, S: int, dtype):
    kind = _kind_base(kind)
    D = cfg.d_model
    if kind in ("global", "local"):
        if cfg.attn_type == "mla":
            m = cfg.mla
            return {"ckv": jnp.zeros((B, S, m.kv_lora_rank), dtype),
                    "krope": jnp.zeros((B, S, m.qk_rope_head_dim), dtype)}
        # KV-major layout [B, Hkv, S, Dh]: einsum-native (no per-step
        # transposes) and the sequence axis (dim 2) is the sharding axis
        return {"k": jnp.zeros((B, cfg.num_kv_heads, S, cfg.head_dim), dtype),
                "v": jnp.zeros((B, cfg.num_kv_heads, S, cfg.head_dim), dtype)}
    if kind == "cross":
        return {}
    if kind == "rwkv":
        s = cfg.ssm
        H = D // s.head_dim
        return {"state": jnp.zeros((B, H, s.head_dim, s.head_dim), jnp.float32),
                "tm_shift": jnp.zeros((B, 1, D), dtype),
                "cm_shift": jnp.zeros((B, 1, D), dtype)}
    if kind == "mamba":
        s = cfg.ssm
        d_in = s.expand * D
        H = d_in // s.head_dim
        return {"state": jnp.zeros((B, H, s.state_dim, s.head_dim), jnp.float32),
                "conv": jnp.zeros((B, s.conv_dim - 1, d_in + 2 * s.state_dim),
                                  dtype)}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, B: int, max_seq: int, dtype=None):
    dtype = dtype or dt(cfg.activation_dtype)
    n_prefix, prefix_kind, period, n_periods = _pattern_segments(cfg)
    cache: Dict[str, Any] = {}
    if n_prefix:
        cache["prefix"] = [_layer_cache(cfg, prefix_kind, B, max_seq, dtype)
                           for _ in range(n_prefix)]
    per = {f"s{j}": _layer_cache(cfg, kind, B, max_seq, dtype)
           for j, kind in enumerate(period)}
    if any(k.endswith(SHARED_SUFFIX) for k in period):
        per["shared"] = _layer_cache(cfg, "global", B, max_seq, dtype)
    cache["layers"] = jax.tree.map(
        lambda a: jnp.zeros((n_periods,) + a.shape, a.dtype), per)
    return cache


def pageable(cfg: ModelConfig) -> bool:
    """Paged KV is supported for pure-attention decoders (GQA or MLA,
    global/local layers only — SSM state, encoders, and vision cross-attn
    keep per-slot dense state)."""
    kinds = {_kind_base(k) for k in cfg.layer_pattern}
    return (kinds <= {"global", "local"} and cfg.attn_type in ("gqa", "mla")
            and not cfg.encoder and not cfg.vision
            and cfg.family != "hybrid")


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=None):
    """Page-pool KV cache: same pytree structure as ``init_cache`` but the
    slot-batch axis is a shared page-pool axis and the sequence axis is one
    page ([n_pages, Hkv, page_size, Dh] per layer for GQA; [n_pages,
    page_size, R] for MLA latents). Slots address the pool through the
    [n_slots, P] page table threaded into ``decode_step`` via
    ``ctx_extra={"page_table": ...}``."""
    assert pageable(cfg), (cfg.name, cfg.layer_pattern)
    return init_cache(cfg, n_pages, page_size, dtype)


def decode_step(params, cfg: ModelConfig, token, cache, pos, ctx_extra=None,
                use_flash: bool = False):
    """token: [B,1] int32; pos: scalar int32 OR [B] int32 per-row positions
    (continuous batching: every slot of a decode batch advances at its own
    offset). ``ctx_extra={"page_table": [B,P] int32}`` switches attention
    layers to the paged KV contract (cache built by ``init_paged_cache``);
    ``use_flash`` routes eligible GQA layers through the ragged Pallas
    flash-decode kernel. Returns (logits [B,1,V], cache)."""
    B = token.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(pos), (B,))[:, None]
    x = _embed_tokens(params, cfg, token, positions=positions)
    ctx = {"positions": positions}
    if use_flash:
        ctx["use_flash"] = True
    if ctx_extra:
        ctx.update(ctx_extra)
    if cfg.family == "hybrid":
        ctx["x0"] = x
    x, _, new_cache = _apply_stack(params, cfg, x, ctx, cache=cache, pos=pos)
    return _logits(params, cfg, x), new_cache


def chunkable(cfg: ModelConfig) -> bool:
    """Cached-context chunked prefill (:func:`prefill_step`) is supported
    for pure-attention decoders — the same family as :func:`pageable`
    (SSM state and encoder/vision models would need their recurrent state
    stepped token-by-token, so they keep the scan-of-decode-steps
    :func:`prefill`)."""
    return pageable(cfg)


def prefill_step(params, cfg: ModelConfig, tokens, cache, pos,
                 ctx_extra=None, use_flash: bool = False):
    """One cached-context prefill chunk: ``tokens`` [B,Sq] prompt chunks
    whose rows start at per-row cache position ``pos`` [B]. Each query at
    pos+i attends to the pos+i cached KV (earlier chunks, or a prefix-cache
    hit's shared pages) plus the chunk itself, and the chunk's KV lands in
    the cache — so a prompt prefills across several calls while the cache
    stays decode-compatible, and a cached prefix is never recomputed. Rows
    at an out-of-window sentinel position write nothing (the serving
    engine's masked-row convention for partial batches).

    ``ctx_extra={"page_table": [B,P]}`` switches to the paged pools;
    ``use_flash`` routes eligible layers through the chunked-prefill Pallas
    kernel. Returns (last-position logits [B,1,V], cache) — only the final
    chunk's logits (query at L-1) are meaningful, and the scheduler always
    issues that position as its own one-token chunk, which is
    shape-identical to a decode step: generated tokens are bit-equal across
    chunkings and to the scan-of-decode-steps :func:`prefill` by
    construction."""
    assert chunkable(cfg), (cfg.name, cfg.layer_pattern)
    B, Sq = tokens.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None] + jnp.arange(Sq)[None, :]
    x = _embed_tokens(params, cfg, tokens, positions=positions)
    ctx = {"positions": positions, "chunk": True}
    if use_flash:
        ctx["use_flash"] = True
    if ctx_extra:
        ctx.update(ctx_extra)
    x, _, new_cache = _apply_stack(params, cfg, x, ctx, cache=cache, pos=pos)
    return _logits(params, cfg, x[:, -1:]), new_cache


def prefill(params, cfg: ModelConfig, batch, max_seq: int):
    """Reference prompt-processing: scan decode steps to populate the cache.
    (The dry-run lowers `forward` for the prefill shape — compute-equivalent;
    this helper is for small-scale integration tests and the serving engine.)"""
    tokens = batch["tokens"]
    cache = init_cache(cfg, tokens.shape[0], max_seq)
    ctx_extra = {}
    if cfg.vision:
        ctx_extra["vision"] = batch["vision"]
    if cfg.encoder:
        ctx_extra["enc_out"] = _run_encoder(params, cfg, batch["frames"])

    def step(carry, t):
        cache, pos = carry
        logits, cache = decode_step(params, cfg, t[:, None], cache, pos,
                                    ctx_extra=ctx_extra)
        return (cache, pos + 1), logits[:, 0]

    (cache, _), logits = jax.lax.scan(
        step, (cache, jnp.asarray(0, jnp.int32)), tokens.T)
    return logits[-1][:, None], cache
