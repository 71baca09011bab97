"""The plain reference: the served decoder's forward pass over a whole
sequence in ``jax.numpy``, float32 and matmul precision ``highest``, with no
cache, no batching and no kernel, run layer by layer so that it fits beside
nothing else on the chip. It imports nothing of the program.

The architecture is the one both configurations serve: a pre-norm GQA
decoder; RMSNorm with scale ``1 + gamma``; optional RMSNorm of each query
and key head (``qk_norm``) before rotary embedding; rotary embedding over
the whole head, rotating the two halves (``rotate_half``); causal softmax
attention scaled by ``head_dim ** -0.5``; a SwiGLU MLP; a final RMSNorm and
a tied or separate output matrix.

``precision="fp8"`` is the control: the same pass with every linear layer
(the projections, the MLP and the output matrix) fed float8 e4m3 weights
(one scale per output channel) and float8 e4m3 activations (one scale per
token), accumulating in float32 -- the step below the served bfloat16 that
a later change might be tempted to take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .model import Model

E4M3_MAX = 448.0


def _q8(x, axis):
    """Scaled round trip through float8 e4m3 (``axis``: the reduction
    axes the scale is shared over)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, spec, w_in_axes, fp8):
    """einsum(spec, x, w) in float32; under ``fp8`` both operands are
    quantized first (x per token over its last axis, w per output channel
    over ``w_in_axes``)."""
    w = w.astype(jnp.float32)
    if fp8:
        x = _q8(x, -1)
        w = _q8(w, w_in_axes)
    return jnp.einsum(spec, x, w)


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g.astype(jnp.float32))


def _rope(x, theta):
    """x: [T, heads, Dh] at positions 0..T-1."""
    T, _, dh = x.shape
    half = dh // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(x, layers, i, m: Model, fp8: bool):
    p = jax.tree.map(lambda a: a[i], layers)
    T = x.shape[0]
    h = _rms(x, p["ln1"], m.eps)
    a = p["attn"]
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
    x = x + _linear(o, a["wo"], "thk,hkd->td", (0, 1), fp8)
    h = _rms(x, p["ln2"], m.eps)
    f = p["mlp"]
    up = (jax.nn.silu(_linear(h, f["w_gate"], "td,df->tf", 0, fp8))
          * _linear(h, f["w_up"], "td,df->tf", 0, fp8))
    return x + _linear(up, f["w_down"], "tf,fd->td", 0, fp8)


def _head(x, rows, params, m: Model, fp8: bool):
    h = _rms(x[rows], params["final_ln"], m.eps)
    if m.tied:
        return _linear(h, params["embed"], "td,vd->tv", 1, fp8)
    return _linear(h, params["unembed"], "td,dv->tv", 0, fp8)


@functools.lru_cache(maxsize=None)
def _fns(m: Model, fp8: bool):
    layer = jax.jit(functools.partial(_layer, m=m, fp8=fp8))
    head = jax.jit(functools.partial(_head, m=m, fp8=fp8))
    embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))
    return embed, layer, head


def logits(params, m: Model, tokens, rows, *, pad_to: int,
           precision: str = "f32"):
    """Reference logits [len(rows), vocab] (float32, numpy) of ``tokens``
    at sequence positions ``rows``. The sequence is padded at its end to
    ``pad_to`` tokens, which the causal mask keeps from every real row, so
    that one compiled program serves every length."""
    assert precision in ("f32", "fp8"), precision
    embed, layer, head = _fns(m, precision == "fp8")
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        x = embed(params["embed"], jnp.asarray(toks))
        for i in range(m.layers):
            x = layer(x, params["layers"]["s0"], i)
        out = head(x, jnp.asarray(np.asarray(rows, np.int32)), params)
    return np.asarray(out)


def gaps(ref, chosen) -> np.ndarray:
    """How far below the reference's best logit the chosen token's logit
    lies, per row (0 where the chosen token is the reference's best)."""
    ref = np.asarray(ref, np.float32)
    idx = np.asarray(chosen, np.int64)
    return ref.max(axis=-1) - ref[np.arange(len(idx)), idx]
