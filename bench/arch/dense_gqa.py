"""The dense GQA decoder that ``qwen3-1.7b`` and ``stablelm-1.6b`` are served
as: a pre-norm decoder; RMSNorm with scale ``1 + gamma``; optional RMSNorm
of each query and key head (``qk_norm``) before rotary embedding; rotary
embedding over the whole head, rotating the two halves (``rotate_half``);
causal softmax attention with grouped key/value heads, scaled by
``head_dim ** -0.5``; a SwiGLU MLP; a final RMSNorm and a tied or separate
output matrix.

The sizes are read from a tenant's published ``config`` under the Hugging
Face key names, with the keys of ``served_as`` (where the program departs
from the published architecture) in their place. The parameter layout is
the program's (``repro.models.transformer.init_params``): the layers are
stacked along a leading axis under ``layers/s0``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from benchkit.reference import _linear, _rms, _rope
from benchkit.workcount import itemsize, queries

NORM_SCALE = 0.1


@dataclass(frozen=True)
class Model:
    name: str            # the program's registry name
    d: int               # hidden_size
    layers: int          # num_hidden_layers
    heads: int           # num_attention_heads
    kv_heads: int        # num_key_value_heads
    head_dim: int
    ff: int              # intermediate_size
    vocab: int
    eps: float           # rms_norm_eps
    theta: float         # rope_theta
    qk_norm: bool
    tied: bool           # tie_word_embeddings
    dtype: str           # served parameter and activation type
    rotary: float = 1.0  # partial_rotary_factor
    qkv_bias: bool = False   # use_qkv_bias / attention_bias

    @property
    def layer_matmul_params(self) -> int:
        d, h, kv, dh, f = (self.d, self.heads, self.kv_heads, self.head_dim,
                           self.ff)
        return d * h * dh * 2 + d * kv * dh * 2 + 3 * d * f


def model(group: dict, dtype: str) -> Model:
    """The model as served: the published ``config``, with the keys of
    ``served_as`` in their place."""
    c = {**group["config"], **group.get("served_as", {})}
    return Model(name=group["model"], d=c["hidden_size"],
                 layers=c["num_hidden_layers"],
                 heads=c["num_attention_heads"],
                 kv_heads=c["num_key_value_heads"],
                 head_dim=c.get("head_dim", c["hidden_size"]
                                // c["num_attention_heads"]),
                 ff=c["intermediate_size"], vocab=c["vocab_size"],
                 eps=c["rms_norm_eps"], theta=c["rope_theta"],
                 qk_norm=group.get("qk_norm", False),
                 tied=c["tie_word_embeddings"], dtype=dtype,
                 rotary=c.get("partial_rotary_factor", 1.0),
                 qkv_bias=c.get("use_qkv_bias",
                                c.get("attention_bias", False)))


def mismatches(m: Model, cfg) -> list:
    """Fields in which the program's ``ModelConfig`` differs from the file;
    empty when the program serves the stated model."""
    pairs = {"d_model": m.d, "num_layers": m.layers,
             "num_heads": m.heads, "num_kv_heads": m.kv_heads,
             "head_dim": m.head_dim, "d_ff": m.ff,
             "vocab_size": m.vocab, "norm_eps": m.eps,
             "rope_theta": m.theta, "qk_norm": m.qk_norm,
             "tie_embeddings": m.tied,
             "param_dtype": m.dtype, "activation_dtype": m.dtype,
             "attn_type": "gqa", "layer_pattern": ("global",),
             "mlp_act": "swiglu", "use_rope": True, "moe": None,
             "attn_logit_softcap": None, "final_logit_softcap": None,
             "local_window": None, "prefix_layers": 0}
    bad = [f"{k}: program {getattr(cfg, k)!r} != file {v!r}"
           for k, v in pairs.items() if getattr(cfg, k) != v]
    # the program's block rotates the whole head and has no q/k/v bias
    if m.rotary != 1.0:
        bad.append(f"partial_rotary_factor: program 1.0 != file "
                   f"{m.rotary!r}")
    if m.qkv_bias:
        bad.append("use_qkv_bias: program False != file True")
    return bad


# -- weights ------------------------------------------------------------

def params(draw, m: Model) -> dict:
    """The program's parameter layout, every leaf from ``draw(path, shape,
    scale)``. Norm scales are the program's ``1 + gamma`` with a random
    ``gamma``, so a norm that drops its scale shows in the comparison."""
    L, D, H, K, Dh, F, V = (m.layers, m.d, m.heads, m.kv_heads, m.head_dim,
                            m.ff, m.vocab)
    attn = {"wq": draw("wq", (L, D, H, Dh), D ** -0.5),
            "wk": draw("wk", (L, D, K, Dh), D ** -0.5),
            "wv": draw("wv", (L, D, K, Dh), D ** -0.5),
            "wo": draw("wo", (L, H, Dh, D), (H * Dh) ** -0.5)}
    if m.qk_norm:
        attn["q_gamma"] = draw("q_gamma", (L, Dh), NORM_SCALE)
        attn["k_gamma"] = draw("k_gamma", (L, Dh), NORM_SCALE)
    p = {"embed": draw("embed", (V, D), D ** -0.5),
         "final_ln": draw("final_ln", (D,), NORM_SCALE),
         "layers": {"s0": {
             "ln1": draw("ln1", (L, D), NORM_SCALE), "attn": attn,
             "ln2": draw("ln2", (L, D), NORM_SCALE),
             "mlp": {"w_gate": draw("w_gate", (L, D, F), D ** -0.5),
                     "w_up": draw("w_up", (L, D, F), D ** -0.5),
                     "w_down": draw("w_down", (L, F, D), F ** -0.5)}}}}
    if not m.tied:
        p["unembed"] = draw("unembed", (D, V), D ** -0.5)
    return p


# -- the reference ------------------------------------------------------

def embed(params, tokens, m: Model):
    return params["embed"][tokens].astype(jnp.float32)


def attention(h, a, m: Model, fp8: bool):
    """One layer's attention over the normed input ``h`` [T, D], through
    its output projection."""
    T = h.shape[0]
    q = _linear(h, a["wq"], "td,dhk->thk", 0, fp8)
    k = _linear(h, a["wk"], "td,dhk->thk", 0, fp8)
    v = _linear(h, a["wv"], "td,dhk->thk", 0, fp8)
    if m.qk_norm:
        q = _rms(q, a["q_gamma"], m.eps)
        k = _rms(k, a["k_gamma"], m.eps)
    q, k = _rope(q, m.theta), _rope(k, m.theta)
    g = m.heads // m.kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thk,shk->hts", q, k) * (m.head_dim ** -0.5)
    causal = jnp.arange(T)[None, :, None] >= jnp.arange(T)[None, None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("hts,shk->thk", jax.nn.softmax(s, axis=-1), v)
    return _linear(o, a["wo"], "thk,hkd->td", (0, 1), fp8)


def layer(x, params, i, m: Model, fp8: bool):
    p = jax.tree.map(lambda a: a[i], params["layers"]["s0"])
    x = x + attention(_rms(x, p["ln1"], m.eps), p["attn"], m, fp8)
    h = _rms(x, p["ln2"], m.eps)
    f = p["mlp"]
    up = (jax.nn.silu(_linear(h, f["w_gate"], "td,df->tf", 0, fp8))
          * _linear(h, f["w_up"], "td,df->tf", 0, fp8))
    return x + _linear(up, f["w_down"], "tf,fd->td", 0, fp8)


def head(x, rows, params, m: Model, fp8: bool):
    h = _rms(x[rows], params["final_ln"], m.eps)
    if m.tied:
        return _linear(h, params["embed"], "td,vd->tv", 1, fp8)
    return _linear(h, params["unembed"], "td,dv->tv", 0, fp8)


# -- work counts ----------------------------------------------------------

def attn_flops(m: Model, rows, kind: str) -> float:
    """One layer's attention kernel: QK^T and PV over the causal context,
    2 * 2 * heads * head_dim per (query, key) pair."""
    pairs = sum(n * s + n * (n + 1) // 2 for s, n in queries(rows, kind))
    return 4.0 * m.heads * m.head_dim * pairs


def attn_bytes(m: Model, rows, kind: str) -> float:
    """One layer's attention kernel: each row's keys and values over its
    context read once, its queries read and its outputs written once."""
    b = itemsize(m)
    total = 0
    for s, n in queries(rows, kind):
        total += 2 * m.kv_heads * m.head_dim * (s + n)   # K and V
        total += 2 * n * m.heads * m.head_dim            # Q in, O out
    return float(total * b)


def step_flops(m: Model, rows, kind: str, logit_rows: int) -> float:
    """A whole decode or chunk step: every layer's matmuls for each real
    token, every layer's attention, and the output matrix for the
    ``logit_rows`` rows whose logits are used (a decode row's next token;
    a chunk row that completes its prompt)."""
    tokens = sum(n for _, n in queries(rows, kind))
    return (2.0 * m.layer_matmul_params * m.layers * tokens
            + m.layers * attn_flops(m, rows, kind)
            + 2.0 * m.d * m.vocab * logit_rows)


def _every_layer(m: Model) -> int:
    return m.layers


# the paged attention kernels (``decode_attention_paged``,
# ``prefill_attention_paged``): one layer's operations and bytes, and the
# number of layers of a step that run the kernel
kernels = {"attention_paged": (attn_flops, attn_bytes, _every_layer)}
