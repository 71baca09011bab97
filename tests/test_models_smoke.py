"""Per-architecture smoke tests: reduced config, one forward + one train-style
grad step on CPU; assert shapes and no NaNs. Plus decode-path consistency.
Configs/params come from the cached ``smoke_model`` conftest factory so the
three tests per arch share one init."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.models import io, transformer as tf

ALL = sorted(ARCHS)


@pytest.mark.parametrize("name", ALL)
def test_forward_shapes_no_nans(name, smoke_model):
    cfg, params = smoke_model(name, 0)
    batch = io.make_batch(cfg, B=2, S=16)
    logits, aux = jax.jit(lambda p, b: tf.forward(p, cfg, b))(params, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits, np.float32)))


@pytest.mark.parametrize("name", ALL)
def test_train_step_grads_finite(name, smoke_model):
    cfg, params = smoke_model(name, 0)
    batch = io.make_batch(cfg, B=2, S=8)

    @jax.jit
    def step(p, b):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p_: tf.loss_fn(p_, cfg, b), has_aux=True)(p)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree_util.tree_leaves(grads)))
        return loss, gnorm

    loss, gnorm = step(params, batch)
    assert np.isfinite(float(loss)), name
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0, name


@pytest.mark.parametrize("name", ALL)
def test_decode_matches_forward(name):
    """Step-by-step decode must reproduce the full forward logits."""
    cfg = smoke_config(name).replace(activation_dtype="float32",
                                     param_dtype="float32")
    params = tf.init_params(jax.random.key(2), cfg)
    S = 8
    batch = io.make_batch(cfg, B=1, S=S)
    full_logits, _ = jax.jit(lambda p, b: tf.forward(p, cfg, b))(params, batch)

    cache = tf.init_cache(cfg, 1, S, dtype=jnp.float32)
    ctx = {}
    if cfg.vision:
        ctx["vision"] = batch["vision"]
    if cfg.encoder:
        ctx["enc_out"] = tf._run_encoder(params, cfg, batch["frames"])
    step = jax.jit(lambda p, t, c, pos: tf.decode_step(p, cfg, t, c, pos, ctx))
    outs = []
    for t in range(S):
        logits, cache = step(params, batch["tokens"][:, t:t + 1], cache,
                             jnp.asarray(t, jnp.int32))
        outs.append(np.asarray(logits[:, 0], np.float32))
    dec = np.stack(outs, axis=1)
    np.testing.assert_allclose(dec, np.asarray(full_logits, np.float32),
                               rtol=2e-3, atol=2e-3)


def test_init_keys_are_stable_across_processes():
    """Leaf and tenant keys come from CRC-32 of their names, not from
    Python's per-process salted ``hash``: the recorded values below hold in
    every process, so a run on one machine can be checked against a
    reference computed in another."""
    from repro.models.common import _fold, name_key
    leaf = _fold(jax.random.key(0), "layers/s0/attn/wq")
    assert jax.random.key_data(leaf).tolist() == [224504919, 1878813183]
    tenant = name_key("ls:qwen3-1.7b")
    assert jax.random.key_data(tenant).tolist() == [0, 1051162302]


def test_bf16_params_are_created_in_bf16():
    """A bfloat16 config's leaves are drawn in bfloat16: no float32 array
    (only the scalar truncation bounds) appears while they are made."""
    cfg = smoke_config("qwen3-1.7b").replace(param_dtype="bfloat16")
    params = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                            jax.random.key(0))
    floats = [l.dtype for l in jax.tree.leaves(params)
              if jnp.issubdtype(l.dtype, jnp.floating)]
    assert floats and all(d == jnp.bfloat16 for d in floats)
    jaxpr = str(jax.make_jaxpr(lambda k: tf.init_params(k, cfg))(
        jax.random.key(0)))
    assert not re.search(r"f32\[\d", jaxpr)
