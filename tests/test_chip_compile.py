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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_paged,
                                            head_groups)
from repro.kernels.prefill_attention import (prefill_attention,
                                             prefill_attention_paged)

B = 4          # decode slots per tenant
SMAX = 2048    # per-slot context
# (H, Hkv, D) of the served tenants
QWEN3 = (16, 8, 128)
STABLELM = (32, 32, 64)
# a KV block of all heads past the paged decode kernel's budget (4 MB a page)
SPLIT_HEADS = (64, 64, 256)


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


@pytest.mark.parametrize("widths,page,groups", [(QWEN3, 16, 1),
                                                (QWEN3, 128, 1),
                                                (STABLELM, 128, 1),
                                                (SPLIT_HEADS, 128, 4)],
                         ids=["qwen3-p16", "qwen3-p128", "stablelm-p128",
                              "split-heads-p128"])
def test_decode_attention_paged_compiles(one_chip, widths, page, groups):
    """One grid step reads a page of every KV head; a block past the budget
    (64 heads of D 256) splits the heads into groups, which compiles too."""
    H, Hkv, D = widths
    assert head_groups(Hkv, page, D, 2) == groups
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


# (opcode of an instruction, or the name of a fusion) that moves a whole pool
_POOL_COPIES = ("copy", "dynamic-update-slice", "dynamic-slice")
_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "pred": 1, "u8": 1, "s8": 1}


def _pool_copies(hlo_text, min_bytes):
    """Instructions outside the Pallas calls that copy at least
    ``min_bytes``: ``copy`` instructions, and fusions named after a copy or
    a dynamic (update-)slice, whose array result is that large."""
    out = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if not m:
            continue
        name, dtype, dims, opcode = m.groups()
        n = _BYTES.get(dtype, 4)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        if n < min_bytes:
            continue
        if opcode == "copy" or (opcode == "fusion" and any(
                c in name for c in _POOL_COPIES)):
            out.append(f"{name} {dtype}[{dims}]")
    return out


@pytest.mark.parametrize("kind", ["decode", "chunk256"])
@pytest.mark.parametrize("name", ["qwen3-1.7b", "stablelm-1.6b"])
def test_paged_step_updates_pools_in_place(one_chip, monkeypatch, name, kind):
    """The whole served step at published widths (bf16, 8 slots x 2048
    tokens, 128-token pages, the cache donated as the engine donates it)
    updates the KV pools where they lie: no instruction outside the Pallas
    kernels copies a layer's pool or more, and the step's temporary is below
    one layer's K+V pools. Handing the layer loop one layer's pools at a
    time copied them out of the stack and back in every layer (temporaries
    of 1.95e9 bytes for the qwen3 decode step, 4.33e9 for the stablelm
    chunk step)."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import transformer as tf
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    cfg = get_config(name).replace(param_dtype="bfloat16",
                                   activation_dtype="bfloat16")
    slots, page = 8, 128
    n_pages = slots * SMAX // page
    sq = 1 if kind == "decode" else 256

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    params = abstract(jax.eval_shape(
        lambda: tf.init_params(jax.random.key(0), cfg)))
    cache = abstract(jax.eval_shape(
        lambda: tf.init_paged_cache(cfg, n_pages, page, jnp.bfloat16)))
    tokens, pos, table = abstract((
        jax.ShapeDtypeStruct((slots, sq), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots, SMAX // page), jnp.int32)))
    step = tf.decode_step if kind == "decode" else tf.prefill_step

    def fn(p, t, c, q, pt):
        return step(p, cfg, t, c, q, ctx_extra={"page_table": pt},
                    use_flash=True)
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(
        params, tokens, cache, pos, table).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    k_pool = cache["layers"]["s0"]["k"]
    layer_k = k_pool.size // k_pool.shape[0] * k_pool.dtype.itemsize
    assert _pool_copies(txt, layer_k) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * layer_k
