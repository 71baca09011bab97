"""The plain reference: a served decoder's forward pass over a whole sequence
in ``jax.numpy``, float32 and matmul precision ``highest``, with no cache,
no batching and no kernel, run layer by layer so that it fits beside
nothing else on the chip. It imports nothing of the program.

The layers are the architecture module's (``bench/arch/<arch>.py``:
``embed``, ``layer`` and ``head``); this module pads the sequence, runs the
layers one compiled program at a time and reads the logits of the rows
asked for. The helpers below (``_q8``, ``_linear``, ``_rms``, ``_rope``)
are shared by the architecture modules.

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


@functools.lru_cache(maxsize=None)
def _fns(arch, m, fp8: bool):
    layer = jax.jit(functools.partial(arch.layer, m=m, fp8=fp8))
    head = jax.jit(functools.partial(arch.head, m=m, fp8=fp8))
    embed = jax.jit(functools.partial(arch.embed, m=m))
    return embed, layer, head


def logits(params, arch, m, tokens, rows, *, pad_to: int,
           precision: str = "f32"):
    """Reference logits [len(rows), vocab] (float32, numpy) of ``tokens``
    at sequence positions ``rows``, by the architecture module ``arch`` for
    its model ``m``. The sequence is padded at its end to ``pad_to``
    tokens, which the causal mask keeps from every real row, so that one
    compiled program serves every length; the layer index is an argument
    of the layer's program, so that one program serves every layer."""
    assert precision in ("f32", "fp8"), precision
    embed, layer, head = _fns(arch, m, precision == "fp8")
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        x = embed(params, jnp.asarray(toks))
        for i in range(m.layers):
            x = layer(x, params, i)
        out = head(x, jnp.asarray(np.asarray(rows, np.int32)), params)
    return np.asarray(out)


def gaps(ref, chosen) -> np.ndarray:
    """How far below the reference's best logit the chosen token's logit
    lies, per row (0 where the chosen token is the reference's best)."""
    ref = np.asarray(ref, np.float32)
    idx = np.asarray(chosen, np.int64)
    return ref.max(axis=-1) - ref[np.arange(len(idx)), idx]
