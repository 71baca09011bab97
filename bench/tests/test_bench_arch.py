"""The architecture modules (``bench/arch/<arch>.py``) against what the
harness computed before the dense GQA decoder moved into
``bench/arch/dense_gqa.py``.

``data/before_arch.json`` was recorded from the harness as it stood before
the move, by the computations below written against its API: the weights'
leaf draws (path, shape, scale, type) and tree at the published sizes with
every small leaf's bits, the tiny configurations' whole weights and their
reference and float8-control logits, the work counts of
``test_bench_workcount.py``'s rows, and every per-layer reading of a record
reduced from the recorded chip trace (``benchtest.recorded_record``). The
moved code has to give each of them bit for bit. The published weights are
too large to make on a CPU: each leaf is ``weights._draw`` of its (path,
shape, scale, type), so the draws, the tree and ``_draw`` itself (the tiny
configurations' bits) fix them.
"""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtest
from benchkit import reference, spec, weights

SPEC = spec.Spec()
DG = SPEC.arch("dense_gqa")
with open(os.path.join(benchtest.DATA, "before_arch.json")) as f:
    BEFORE = json.load(f)
SEED, BIG = BEFORE["seed"], BEFORE["big"]


def _conf(path):
    with open(path) as f:
        return json.load(f)


CONFS = {"colo": _conf(f"{benchtest.BENCH}/configs/"
                       "colo-qwen3-1.7b-stablelm-1.6b.json"),
         "solo": _conf(f"{benchtest.BENCH}/configs/solo-qwen3-1.7b.json"),
         "tiny": _conf(f"{benchtest.DATA}/configs/tiny-colo.json")}


def _model(conf, cls):
    c = CONFS[conf]
    assert c[cls]["arch"] == "dense_gqa"
    return DG.model(c[cls], c["dtype"]), f"{cls}:{c[cls]['model']}"


def _sha(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _draws(m):
    out = []

    def draw(path, shape, scale):
        out.append([path, list(shape), float(scale), m.dtype])
        return jax.ShapeDtypeStruct(shape, jnp.dtype(m.dtype))
    DG.params(draw, m)
    return out


@pytest.mark.parametrize("label", ["colo.ls", "colo.be", "solo.ls"])
def test_published_weights_are_drawn_as_before(label):
    want = BEFORE["published"][label]
    m, tenant = _model(*label.split("."))
    assert tenant == want["tenant"]
    draws = _draws(m)
    assert draws == want["draws"]
    shapes = jax.eval_shape(lambda: weights.make(DG, m, SEED, tenant))
    tree = [["/".join(str(k.key) for k in kp), list(a.shape), a.dtype.name]
            for kp, a in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert tree == want["tree"]
    key, dt = weights.run_key(SEED, tenant), jnp.dtype(m.dtype)
    small = [weights._draw(key, p, tuple(s), sc, dt)
             for p, s, sc, _ in draws if int(np.prod(s)) <= BIG]
    assert _sha(small) == want["small_leaves_sha256"]


@pytest.mark.parametrize("cls", ["ls", "be"])
def test_tiny_weights_reference_and_control_are_bit_equal(cls):
    want = BEFORE["tiny"][cls]
    m, tenant = _model("tiny", cls)
    params = weights.make(DG, m, SEED, tenant)
    assert _sha(jax.tree.leaves(params)) == want["weights_sha256"]
    toks = np.random.default_rng(0).integers(0, m.vocab, 40).astype(np.int32)
    rows = np.arange(10, 40)
    ref = reference.logits(params, DG, m, toks, rows, pad_to=64)
    ctl = reference.logits(params, DG, m, toks, rows, pad_to=64,
                           precision="fp8")
    assert _sha([ref]) == want["ref_sha256"]
    assert _sha([ctl]) == want["fp8_sha256"]


ROWS = {"decode": [99, 511], "chunk": [(0, 256), (256, 64)]}


@pytest.mark.parametrize("model,cls", [("qwen3", "ls"), ("stablelm", "be")])
@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_work_counts_are_equal(model, cls, kind):
    m, _ = _model("colo", cls)
    rows, got = ROWS[kind], {}
    for lr in (0, 2):
        got[f"{model}/{kind}/step_flops/{lr}"] = DG.step_flops(m, rows, kind,
                                                               lr)
    flops, nbytes, layers = DG.kernels["attention_paged"]
    got[f"{model}/{kind}/attn_flops"] = flops(m, rows, kind)
    got[f"{model}/{kind}/attn_bytes"] = nbytes(m, rows, kind)
    assert layers(m) == m.layers
    assert got == {k: v for k, v in BEFORE["workcount"].items()
                   if k.startswith(f"{model}/{kind}/")}


def test_readings_on_the_recorded_trace_are_equal(tmp_path):
    want = BEFORE["recorded_trace"]
    rec = benchtest.recorded_record(tmp_path, SPEC, CONFS["tiny"])
    t = rec.traced
    readings = {}
    for m in SPEC.benchmark()["per_layer"]:
        v = SPEC.metric_reader(m["name"])(rec)
        if v is not None:
            readings[m["name"]] = v
    assert readings == want["readings"]
    per = [d for _, d in t["aligned"]]
    assert [d["device_ns"] for d in per] == want["device_ns"]
    assert [d["kernel_ns"] for d in per] == want["kernel_ns"]
    assert (t["busy_s"], t["window_s"], t["n_calls"]) == (
        want["busy_s"], want["window_s"], want["n_calls"])
    assert t["breakdown"] == want["breakdown"]
    # the per-operation map holds every leaf operation of the step once:
    # the kernels among them, and nothing more than the step's time
    for d in per:
        assert d["kernels"] == {"attention_paged": d["kernel_ns"]}
        assert sum(v for k, v in d["ops"].items()
                   if "attention_paged" in k) == d["kernel_ns"]
        assert 0 < sum(d["ops"].values()) <= d["device_ns"]


def test_harness_names_no_architecture():
    """What a model family is lives in ``bench/arch``: the harness's own
    code names none of its keys."""
    keys = ("kv_heads", "intermediate_size", "hidden_size", "head_dim",
            "num_key_value_heads", "wq", "w_gate", "qk_norm", "swiglu",
            "attention_paged", "gqa", "qwen", "stablelm")
    kit = os.path.join(benchtest.BENCH, "benchkit")
    for name in sorted(os.listdir(kit)):
        if name.endswith(".py"):
            with open(os.path.join(kit, name)) as f:
                text = f.read()
            assert not [k for k in keys if k in text], name


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      SPEC.benchmark()["workloads"]])
def test_every_cell_resolves_by_name(workload):
    """Each cell's configuration names architectures that have files and
    build a model of the published sizes, and each metric the cell reports
    has its reader."""
    w = SPEC.workload(workload)
    conf = SPEC.config(w["config"])
    for cls in ("ls", "be"):
        if cls in conf:
            arch = SPEC.arch(conf[cls]["arch"])
            m = arch.model(conf[cls], conf["dtype"])
            assert (m.name, m.d, m.vocab) == (
                conf[cls]["model"], conf[cls]["config"]["hidden_size"],
                conf[cls]["config"]["vocab_size"])
            assert hash(m) == hash(arch.model(conf[cls], conf["dtype"]))
    assert SPEC.traffic(w["traffic"])
    e2e, layer = SPEC.metrics_for(workload)
    assert any(m["name"] == "setup_s" for m in e2e)
    for m in layer:
        assert callable(SPEC.metric_reader(m["name"])), m["name"]
