"""Seeded random weights in the served type, made on the device in one
jitted call, in the parameter layout the program takes: the architecture
module's ``params(draw, m)`` (``bench/arch/<arch>.py``) names each leaf's
path, shape and scale, and ``draw`` makes it.

The benchmark makes the weights, not the program: the same function hands
them to the engine and, after the window, to the reference, which so takes
nothing that the program made. Every leaf is drawn in float32 from its own
key (the run's key folded with the CRC-32 of the leaf's path) and rounded
once to the served type.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def run_key(seed: int, tenant: str):
    """A PRNG key from the run's seed (any size) and the tenant's name."""
    word = np.random.SeedSequence(
        [int(seed) % 2**63, zlib.crc32(tenant.encode())]).generate_state(1)
    return jax.random.key(int(word[0]) & 0x7FFFFFFF)


def _draw(key, path, shape, scale, dtype):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def _make(key, arch, m):
    dt = jnp.dtype(m.dtype)

    def draw(path, shape, scale):
        return _draw(key, path, shape, scale, dt)
    return arch.params(draw, m)


@functools.lru_cache(maxsize=None)
def _maker(arch, m):
    return jax.jit(functools.partial(_make, arch=arch, m=m))


def make(arch, m, seed: int, tenant: str):
    """The tenant's parameters for this seed, on the default device, in the
    layout of the architecture module ``arch`` for its model ``m``."""
    return _maker(arch, m)(run_key(seed, tenant))
