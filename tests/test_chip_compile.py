"""Ahead-of-time compiles of the served Pallas kernels for a described TPU
v5e, at the widths the engine serves (bf16; qwen3-1.7b: H 16, Hkv 8, D 128;
stablelm-1.6b: H 32, Hkv 32, D 64). No chip is needed: the TPU compiler
refuses here what it would refuse on the chip (misaligned tiles, too much
VMEM), which Pallas interpret mode accepts. Each case asserts the kernel is
in the compiled program (``tpu_custom_call``).

The topology is described only inside the module fixture: a process that
describes it loads the TPU library and holds it until it exits, so no call
may happen while test modules are imported or collected.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_paged)
from repro.kernels.prefill_attention import (prefill_attention,
                                             prefill_attention_paged)

B = 4          # decode slots per tenant
SMAX = 2048    # per-slot context
# (H, Hkv, D) of the served tenants
QWEN3 = (16, 8, 128)
STABLELM = (32, 32, 64)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("widths", [QWEN3, STABLELM], ids=["qwen3", "stablelm"])
def test_decode_attention_compiles(one_chip, widths):
    H, Hkv, D = widths
    txt = _compile_text(
        functools.partial(decode_attention, kv_layout="bhsd",
                          interpret=False),
        one_chip, ((B, H, D), jnp.bfloat16),
        ((B, Hkv, SMAX, D), jnp.bfloat16), ((B, Hkv, SMAX, D), jnp.bfloat16),
        ((B,), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("widths,page", [(QWEN3, 16), (QWEN3, 128),
                                         (STABLELM, 128)],
                         ids=["qwen3-p16", "qwen3-p128", "stablelm-p128"])
def test_decode_attention_paged_compiles(one_chip, widths, page):
    H, Hkv, D = widths
    n_pages = B * SMAX // page
    txt = _compile_text(
        functools.partial(decode_attention_paged, interpret=False),
        one_chip, ((B, H, D), jnp.bfloat16),
        ((n_pages, Hkv, page, D), jnp.bfloat16),
        ((n_pages, Hkv, page, D), jnp.bfloat16),
        ((B, SMAX // page), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in txt


def _with_abort(kernel, abort):
    if not abort:
        return functools.partial(kernel, interpret=False)

    def fn(*args):
        cap = jnp.full((B,), 3, jnp.int32)
        return kernel(*args, interpret=False, abort=cap)
    return fn


@pytest.mark.parametrize("abort", [False, True], ids=["full", "abort"])
@pytest.mark.parametrize("widths,sq", [(QWEN3, 64), (QWEN3, 512),
                                       (STABLELM, 256)],
                         ids=["qwen3-sq64", "qwen3-sq512", "stablelm-sq256"])
def test_prefill_attention_compiles(one_chip, widths, sq, abort):
    H, Hkv, D = widths
    txt = _compile_text(
        _with_abort(prefill_attention, abort), one_chip,
        ((B, sq, H, D), jnp.bfloat16), ((B, Hkv, SMAX, D), jnp.bfloat16),
        ((B, Hkv, SMAX, D), jnp.bfloat16), ((B,), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("abort", [False, True], ids=["full", "abort"])
@pytest.mark.parametrize("widths,sq,page", [(QWEN3, 1, 128),
                                            (QWEN3, 256, 16),
                                            (QWEN3, 256, 128),
                                            (STABLELM, 256, 128)],
                         ids=["qwen3-sq1-p128", "qwen3-sq256-p16",
                              "qwen3-sq256-p128", "stablelm-sq256-p128"])
def test_prefill_attention_paged_compiles(one_chip, widths, sq, page, abort):
    H, Hkv, D = widths
    n_pages = B * SMAX // page
    txt = _compile_text(
        _with_abort(prefill_attention_paged, abort), one_chip,
        ((B, sq, H, D), jnp.bfloat16),
        ((n_pages, Hkv, page, D), jnp.bfloat16),
        ((n_pages, Hkv, page, D), jnp.bfloat16),
        ((B, SMAX // page), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_paged_kernels_carry_distinct_names(one_chip):
    """Each paged kernel is named after its own wrapper, so a profile or an
    HLO dump tells the decode kernel from the prefill kernel; both names
    keep ``attention_paged``."""
    H, Hkv, D = QWEN3
    page = 128
    n_pages = B * SMAX // page
    pool = ((n_pages, Hkv, page, D), jnp.bfloat16)
    table = ((B, SMAX // page), jnp.int32)
    texts = {}
    for name, kernel, q in (
            ("decode", decode_attention_paged, (B, H, D)),
            ("prefill", prefill_attention_paged, (B, 256, H, D))):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in ((q, jnp.bfloat16), pool, pool, table,
                                     ((B,), jnp.int32))]
        lowered = jax.jit(functools.partial(kernel, interpret=False)).lower(
            *args)
        texts[name] = lowered.as_text()
        assert "tpu_custom_call" in lowered.compile().as_text()
    assert "_decode_attention_paged_kernel" in texts["decode"]
    assert "_prefill_attention_paged_kernel" not in texts["decode"]
    assert "_prefill_attention_paged_kernel" in texts["prefill"]
    assert "_decode_attention_paged_kernel" not in texts["prefill"]
