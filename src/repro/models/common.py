"""Shared model building blocks: parameter init, norms, RoPE, activations.

Parameters are plain nested dicts of jnp arrays (pytrees). Initializers take an
explicit PRNG key; every leaf gets a key derived from its path so init is
order-independent and reproducible across processes (the path is folded in
through CRC-32, never through Python's per-process salted ``hash``). Leaves
are drawn directly in their parameter dtype, so a bfloat16 model never
materializes a float32 copy of its weights.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# dtype helpers
# ---------------------------------------------------------------------------

def dt(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _crc(name: str) -> int:
    return zlib.crc32(name.encode()) % (2**31)


def _fold(key, path: str):
    return jax.random.fold_in(key, np.uint32(_crc(path)))


def name_key(name: str):
    """PRNG key derived from a name (e.g. a serving tenant's), the same in
    every process."""
    return jax.random.key(_crc(name))


def dense_init(key, path: str, shape, dtype, scale: float | None = None):
    """Truncated-normal fan-in init (scale defaults to 1/sqrt(fan_in))."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else fan_in ** -0.5
    return jax.random.truncated_normal(_fold(key, path), -2.0, 2.0, shape,
                                       dtype) * jnp.asarray(s, dtype)


def embed_init(key, path: str, shape, dtype):
    return jax.random.normal(_fold(key, path), shape, dtype) \
        * jnp.asarray(shape[-1] ** -0.5, dtype)


def zeros(shape, dtype):
    return jnp.zeros(shape, dtype)


def ones(shape, dtype):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))


def apply_rope(x, positions, theta: float = 10_000.0):
    """x: [..., S, H, D] (or [..., S, D]); positions: [..., S] int32."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta))              # [d/2]
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., S, d/2]
    # broadcast over the head axis if present
    for _ in range(x.ndim - ang.ndim):
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def sq_relu(x):
    r = jax.nn.relu(x)
    return r * r


ACTIVATIONS = {
    "gelu": jax.nn.gelu,
    "sq_relu": sq_relu,
    "silu": jax.nn.silu,
}


def softcap(x, cap: float):
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def count_params(params) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(params)))


def causal_mask(q_len: int, kv_len: int, q_offset=0):
    """[q_len, kv_len] bool, True = attend."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    return k_pos <= q_pos


def local_mask(q_len: int, kv_len: int, window: int, q_offset=0):
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    return (k_pos <= q_pos) & (k_pos > q_pos - window)
