"""Abstract input/cache/state specs + shardings for dry-run lowering.

Everything here is ShapeDtypeStruct-based: no device allocation ever happens.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from ..dist.sharding import batch_axes, param_pspecs
from ..models import io as model_io
from ..models import transformer as tf
from ..train.optimizer import AdamWConfig, OptState, zero1_pspecs


def _axes_ok(mesh, axes, dim):
    n = int(np.prod([mesh.shape[a] for a in axes]))
    return dim % n == 0 and dim >= n


def batch_pspec(mesh, dim):
    ax = batch_axes(mesh)
    if ax and _axes_ok(mesh, ax, dim):
        return ax if len(ax) > 1 else ax[0]
    return None


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh,
                mode: str) -> Dict:
    """ShapeDtypeStruct stand-ins + NamedShardings for every model input."""
    B = shape.global_batch
    if mode in ("train", "prefill"):
        S = shape.seq_len
        fields = model_io.batch_fields(cfg, B, S, with_labels=(mode == "train"))
        structs, shards = {}, {}
        for name, shp, dtype in fields:
            structs[name] = jax.ShapeDtypeStruct(shp, dtype)
            shards[name] = NamedSharding(mesh, P(batch_pspec(mesh, shp[0])))
        return structs, shards
    # decode: one token + KV cache of shape.seq_len
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_shard = NamedSharding(mesh, P(batch_pspec(mesh, B)))
    cache = jax.eval_shape(lambda: tf.init_cache(cfg, B, shape.seq_len))
    cache_shard = cache_pspecs(cache, mesh)
    extras, extra_shards = {}, {}
    if cfg.vision:
        shp = (B, cfg.vision.num_tokens, cfg.vision.vision_dim)
        extras["vision"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
        extra_shards["vision"] = NamedSharding(mesh, P(batch_pspec(mesh, B)))
    if cfg.encoder:
        shp = (B, cfg.encoder.num_frames, cfg.d_model)
        extras["enc_out"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
        extra_shards["enc_out"] = NamedSharding(mesh, P(batch_pspec(mesh, B)))
    return (token, cache, extras), (tok_shard, cache_shard, extra_shards)


def cache_pspecs(cache, mesh):
    """KV/state cache shardings. Leaves are [L(stacked), B, ...]: batch
    shards over data; the first trailing dim divisible by the model axis
    (the sequence axis for KV-major attention caches [L,B,H,S,D]; heads for
    SSM state) shards over model; the rest replicate."""
    msize = mesh.shape.get("model", 1)

    def spec(leaf):
        shp = leaf.shape
        s = [None] * len(shp)
        if len(shp) >= 2:
            s[1] = batch_pspec(mesh, shp[1])
        if "model" in mesh.axis_names:
            for d in range(2, len(shp)):
                if shp[d] % msize == 0 and shp[d] >= msize:
                    s[d] = "model"
                    break
        return NamedSharding(mesh, P(*s))

    return jax.tree.map(spec, cache)


# ---------------------------------------------------------------------------
# step functions to lower
# ---------------------------------------------------------------------------

def make_steps(cfg: ModelConfig, opt_cfg: AdamWConfig = None):
    from ..train.trainer import make_train_step
    opt_cfg = opt_cfg or AdamWConfig()
    train_step = make_train_step(cfg, opt_cfg)

    def prefill_step(params, batch):
        logits, aux = tf.forward(params, cfg, batch, last_only=True)
        return logits[:, 0]

    def serve_step(params, token, cache, pos, extras):
        logits, new_cache = tf.decode_step(params, cfg, token, cache, pos,
                                           ctx_extra=extras or None)
        return logits[:, 0], new_cache

    return train_step, prefill_step, serve_step


def abstract_state(cfg: ModelConfig, mesh, key=None, zero1: bool = True):
    """Abstract params/opt/err + shardings (no allocation)."""
    if key is None:
        key = jax.random.key(0)
    params = jax.eval_shape(lambda k: tf.init_params(k, cfg), key)
    pspecs = param_pspecs(params, mesh)
    ospecs = zero1_pspecs(params, mesh, zero1)
    opt = OptState(
        jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                     params),
        jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                     params),
        jax.ShapeDtypeStruct((), jnp.int32))
    err = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                       params)
    return params, pspecs, opt, ospecs, err
