"""Seeded random weights in the served type, made on the device in one
jitted call, in the parameter layout the program takes
(``repro.models.transformer.init_params``: a dense GQA decoder whose layers
are stacked along a leading axis under ``layers/s0``).

The benchmark makes the weights, not the program: the same function hands
them to the engine and, after the window, to the reference, which so takes
nothing that the program made. Every leaf is drawn in float32 from its own
key (the run's key folded with the CRC-32 of the leaf's path) and rounded
once to the served type. Norm scales are the program's ``1 + gamma`` with a
random ``gamma``, so a norm that drops its scale shows in the comparison.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from .model import Model

NORM_SCALE = 0.1


def run_key(seed: int, tenant: str):
    """A PRNG key from the run's seed (any size) and the tenant's name."""
    word = np.random.SeedSequence(
        [int(seed) % 2**63, zlib.crc32(tenant.encode())]).generate_state(1)
    return jax.random.key(int(word[0]) & 0x7FFFFFFF)


def _draw(key, path, shape, scale, dtype):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def _make(key, m: Model):
    dt = jnp.dtype(m.dtype)
    L, D, H, K, Dh, F, V = (m.layers, m.d, m.heads, m.kv_heads, m.head_dim,
                            m.ff, m.vocab)

    def w(path, shape, scale):
        return _draw(key, path, shape, scale, dt)

    attn = {"wq": w("wq", (L, D, H, Dh), D ** -0.5),
            "wk": w("wk", (L, D, K, Dh), D ** -0.5),
            "wv": w("wv", (L, D, K, Dh), D ** -0.5),
            "wo": w("wo", (L, H, Dh, D), (H * Dh) ** -0.5)}
    if m.qk_norm:
        attn["q_gamma"] = w("q_gamma", (L, Dh), NORM_SCALE)
        attn["k_gamma"] = w("k_gamma", (L, Dh), NORM_SCALE)
    p = {"embed": w("embed", (V, D), D ** -0.5),
         "final_ln": w("final_ln", (D,), NORM_SCALE),
         "layers": {"s0": {
             "ln1": w("ln1", (L, D), NORM_SCALE), "attn": attn,
             "ln2": w("ln2", (L, D), NORM_SCALE),
             "mlp": {"w_gate": w("w_gate", (L, D, F), D ** -0.5),
                     "w_up": w("w_up", (L, D, F), D ** -0.5),
                     "w_down": w("w_down", (L, F, D), F ** -0.5)}}}}
    if not m.tied:
        p["unembed"] = w("unembed", (D, V), D ** -0.5)
    return p


@functools.lru_cache(maxsize=None)
def _maker(m: Model):
    return jax.jit(functools.partial(_make, m=m))


def make(m: Model, seed: int, tenant: str):
    """The tenant's parameters for this seed, on the default device."""
    return _maker(m)(run_key(seed, tenant))
