"""The plain reference against the program's full-sequence forward, at the
tiny float32 test sizes of both served architectures, on the benchmark's
seeded weights; and the weights' layout against the program's."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtest
from benchkit import reference, weights
from benchkit.model import Model
from repro.configs import smoke_config
from repro.models import transformer as tf


def _tiny(cls):
    with open(os.path.join(benchtest.DATA, "configs",
                           "tiny-colo.json")) as f:
        conf = json.load(f)
    return Model.from_conf(conf[cls], conf["dtype"])


@pytest.mark.parametrize("cls", ["ls", "be"])
def test_weights_have_the_programs_layout(cls):
    m = _tiny(cls)
    cfg = smoke_config(m.name).replace(activation_dtype="float32")
    assert not m.mismatches(cfg)
    mine = jax.eval_shape(lambda: weights.make(m, 5, "t"))
    theirs = jax.eval_shape(lambda: tf.init_params(jax.random.key(0), cfg))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), mine) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), theirs)


def test_weights_follow_the_seed():
    m = _tiny("ls")
    a, b = weights.make(m, 2**31 + 9, "t"), weights.make(m, 2**31 + 9, "t")
    c = weights.make(m, 2**31 + 10, "t")
    assert np.array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])


@pytest.mark.parametrize("cls", ["ls", "be"])
def test_reference_matches_the_programs_forward(cls):
    m = _tiny(cls)
    cfg = smoke_config(m.name).replace(activation_dtype="float32")
    params = weights.make(m, 3, cls)
    toks = np.random.default_rng(0).integers(0, m.vocab, 40).astype(np.int32)
    rows = np.arange(10, 40)
    ref = reference.logits(params, m, toks, rows, pad_to=64)
    with jax.default_matmul_precision("highest"):
        prog, _ = tf.forward(params, cfg, {"tokens": jnp.asarray(toks[None])})
    np.testing.assert_allclose(ref, np.asarray(prog[0, rows]), atol=2e-4,
                               rtol=2e-4)
    assert reference.gaps(ref, np.asarray(prog[0, rows]).argmax(-1)).max() \
        < 1e-3


def test_fp8_control_departs_from_the_reference():
    m = _tiny("ls")
    params = weights.make(m, 3, "ls")
    toks = np.random.default_rng(1).integers(0, m.vocab, 60).astype(np.int32)
    rows = np.arange(60)
    ref = reference.logits(params, m, toks, rows, pad_to=64)
    ctl = reference.logits(params, m, toks, rows, pad_to=64,
                           precision="fp8")
    assert reference.gaps(ref, ref.argmax(-1)).max() == 0.0
    assert np.abs(ctl - ref).max() > 1e-2
    assert reference.gaps(ref, ctl.argmax(-1)).max() > 0.0
