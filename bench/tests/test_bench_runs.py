"""Whole runs of the tiny CPU cells with the chip check skipped: a new
configuration, mix and metric are picked up from their files alone; a
sound run is correct; the timed path broken underneath, or the float8
control in its place, is not."""
import json

import jax
import jax.numpy as jnp
import pytest

import benchtest

NEW_METRIC = '''"""LS requests due in the window (a test's new metric)."""


def read(rec):
    return float(len(rec.window_ls()))
'''


def test_new_config_mix_and_metric_need_only_new_files(tmp_path):
    with open(f"{benchtest.DATA}/configs/tiny-solo.json") as f:
        conf = json.load(f)
    conf["serve_argv"][conf["serve_argv"].index("--slots") + 1] = "3"
    with open(f"{benchtest.DATA}/traffic/tiny-solo.json") as f:
        mix = json.load(f)
    mix["ls"]["arrivals"] = {"kind": "square_wave", "period_s": 1.0,
                             "on_rate_per_s": 8.0}
    sp = benchtest.tiny_spec(tmp_path, {
        "configs/tiny-solo3.json": json.dumps(conf),
        "traffic/tiny-tide.json": json.dumps(mix),
        "metrics/ls_requests_due.py": NEW_METRIC})
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "tiny.tide", "config": "tiny-solo3",
                            "traffic": "tiny-tide", "chips": 1,
                            "why": "test"})
    bm["per_layer"].append({"name": "ls_requests_due", "unit": "count",
                            "better": "higher", "source": "host_clock",
                            "layer": "load generator (bench)",
                            "moves": "ls_ttft_p90_ms",
                            "workloads": ["tiny.tide"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    res = benchtest.run_tiny(sp, "tiny.tide", seconds=2.0, trace=True)
    assert res["correct"], res["checks"]
    # 8/s over the first half of each 1 s period, for 2 s
    assert res["metrics"]["ls_requests_due"]["value"] == 8.0
    assert list(res)[-1] == "checks"


TOY_ARCH = '''"""A test's architecture: dense_gqa's attention with a non-gated
squared-ReLU MLP, as the program serves ``mlp_act="sq_relu"``."""
from pathlib import Path

import jax
import jax.numpy as jnp

from benchkit.reference import _linear, _rms
from benchkit.spec import load_file
from benchkit.workcount import queries

base = load_file(Path(__file__).with_name("dense_gqa.py"))
model, embed, head, kernels = base.model, base.embed, base.head, base.kernels


def mismatches(m, cfg):
    bad = base.mismatches(m, cfg.replace(mlp_act="swiglu"))
    if cfg.mlp_act != "sq_relu":
        bad.append(f"mlp_act: program {cfg.mlp_act!r} != file 'sq_relu'")
    return bad


def params(draw, m):
    p = base.params(draw, m)
    L, D, F = m.layers, m.d, m.ff
    p["layers"]["s0"]["mlp"] = {"w_up": draw("w_up", (L, D, F), D ** -0.5),
                                "w_down": draw("w_down", (L, F, D),
                                               F ** -0.5)}
    return p


def layer(x, params, i, m, fp8):
    p = jax.tree.map(lambda a: a[i], params["layers"]["s0"])
    x = x + base.attention(_rms(x, p["ln1"], m.eps), p["attn"], m, fp8)
    h = _rms(x, p["ln2"], m.eps)
    up = jnp.square(jax.nn.relu(
        _linear(h, p["mlp"]["w_up"], "td,df->tf", 0, fp8)))
    return x + _linear(up, p["mlp"]["w_down"], "tf,fd->td", 0, fp8)


def step_flops(m, rows, kind, logit_rows):
    tokens = sum(n for _, n in queries(rows, kind))
    return (base.step_flops(m, rows, kind, logit_rows)
            - 2.0 * m.d * m.ff * m.layers * tokens)     # no gate matrix
'''

KERNEL_METRIC = '''"""Device ms per traced decode step of the operations whose names hold
``attention_paged``, from each step's per-operation map."""


def read(rec):
    per = [d for c, d in (rec.traced or {}).get("aligned") or []
           if c.kind == "decode"]
    ns = sum(v for d in per for k, v in d["ops"].items()
             if "attention_paged" in k)
    return ns / 1e6 / len(per) if ns else None
'''

DISPATCH_METRIC = '''"""Mean ``live`` argument of the engine's dispatch spans in the traced
slice."""


def read(rec):
    t = rec.traced or {}
    if "slice" not in t:
        return None
    lo, hi = t["slice"]
    live = [int(e.args["live"]) for e in t["engine_spans"]
            if e.name == "engine.dispatch" and lo <= e.start < hi]
    return sum(live) / len(live) if live else None
'''


def test_new_architecture_config_and_readers_need_only_new_files(tmp_path):
    """A second architecture, a configuration that names it and two
    readers -- one of a kernel by name from the per-operation map, one of
    an ``engine.dispatch`` argument from the kept spans -- are new files
    and nothing else. A CPU profile records no device plane, so the kernel
    reader reads the trace recorded on the chip."""
    with open(f"{benchtest.DATA}/configs/tiny-solo.json") as f:
        conf = json.load(f)
    ls = conf["ls"]
    ls.update(model="nemotron-4-15b", arch="toy_sq_relu", qk_norm=False)
    ls["config"].update(num_key_value_heads=1, rope_theta=10000.0,
                        tie_word_embeddings=False)
    sp = benchtest.tiny_spec(tmp_path / "bench", {
        "arch/toy_sq_relu.py": TOY_ARCH,
        "configs/tiny-sqrelu.json": json.dumps(conf),
        "metrics/toy_attn_kernel_ms.py": KERNEL_METRIC,
        "metrics/toy_dispatch_live.py": DISPATCH_METRIC})
    bm = json.loads((tmp_path / "bench" / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "tiny.sqrelu", "config": "tiny-sqrelu",
                            "traffic": "tiny-solo", "chips": 1,
                            "why": "test"})
    for name, src in (("toy_attn_kernel_ms", "device_trace"),
                      ("toy_dispatch_live", "program_counter")):
        bm["per_layer"].append({"name": name, "unit": "x",
                                "better": "higher", "source": src,
                                "layer": "test", "moves": "ls_tbt_p99_ms",
                                "workloads": ["tiny.sqrelu"]})
    (tmp_path / "bench" / "BENCHMARK.json").write_text(json.dumps(bm))
    res = benchtest.run_tiny(sp, "tiny.sqrelu", seconds=2.0, trace=True)
    assert res["correct"], res["checks"]
    assert res["checks"]["ls_logit_gap"]["tokens"] > 0
    assert 1 <= res["metrics"]["toy_dispatch_live"]["value"] <= 2
    assert "toy_attn_kernel_ms" not in res["metrics"]     # no device plane
    rec = benchtest.recorded_record(tmp_path, sp, sp.config("tiny-colo"))
    assert sp.metric_reader("toy_attn_kernel_ms")(rec) > 0
    # the architecture's own counts: one matrix fewer than dense_gqa's
    arch, base = sp.arch("toy_sq_relu"), sp.arch("dense_gqa")
    m = arch.model(ls, conf["dtype"])
    assert base.step_flops(m, [5], "decode", 0) - arch.step_flops(
        m, [5], "decode", 0) == 2.0 * 128 * 256 * 2


def test_configuration_naming_a_missing_architecture_fails_at_once(
        tmp_path):
    with open(f"{benchtest.DATA}/configs/tiny-solo.json") as f:
        conf = json.load(f)
    conf["ls"]["arch"] = "no_such_arch"
    sp = benchtest.tiny_spec(tmp_path, {
        "configs/tiny-solo.json": json.dumps(conf)})
    with pytest.raises(KeyError, match="arch/no_such_arch.py"):
        benchtest.run_tiny(sp, "tiny.solo")


def test_sound_run_is_correct_and_reports_its_metrics(tmp_path):
    sp = benchtest.tiny_spec(tmp_path)
    res = benchtest.run_tiny(sp, "tiny.colo")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"ls_ttft_p90_ms", "ls_tbt_p99_ms",
                                   "ls_slo_pct", "be_tok_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    # every LS request finished well inside the tiny cell's limits
    assert res["metrics"]["ls_slo_pct"]["value"] == 100.0
    for k in ("ls_logit_gap", "be_logit_gap"):
        assert res["checks"][k]["value"] <= res["checks"][k]["limit"]
    assert res["device"]["platform"] == "cpu"


def _state_unchanged(rt, kind, fn):
    """The step computes but hands back the cache it was given."""
    def f(params, toks, cache, pos, pt):
        keep = jax.tree.map(jnp.copy, cache)
        logits, _ = fn(params, toks, cache, pos, pt)
        return logits, keep
    return f if kind == "chunk" else fn


def _half_batch(rt, kind, fn):
    """Every other live row of a chunk call is left out (its writes
    dropped)."""
    sentinel = rt.kv.pages_per_slot * rt.kv.page_size

    def f(params, toks, cache, pos, pt):
        live = jnp.cumsum(pos < sentinel) % 2 == 0
        return fn(params, toks, cache,
                  jnp.where(live & (pos < sentinel), sentinel, pos), pt)
    return f if kind == "chunk" else fn


def _token_altered(rt, kind, fn):
    """Decode picks token 7, whatever the logits say."""
    def f(params, toks, cache, pos, pt):
        logits, cache = fn(params, toks, cache, pos, pt)
        return logits.at[:, :, 7].add(1e4), cache
    return f if kind == "decode" else fn


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state-unchanged", "half-batch",
                              "token-altered"])
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    res = benchtest.run_tiny(benchtest.tiny_spec(tmp_path), "tiny.colo",
                             fault=fault)
    assert not res["correct"], res["checks"]
    # it fails the comparison, not for want of finished requests
    assert any(v["value"] > v["limit"] and v["tokens"] > 0
               for v in res["checks"].values())


def test_fp8_control_fails_the_limit(tmp_path):
    """The float8 control, judged in the program's place, is not correct,
    while the program's own tokens of the same run are within the limit."""
    res = benchtest.run_tiny(benchtest.tiny_spec(tmp_path), "tiny.colo",
                             control=True)
    assert not res["correct"], res["checks"]
    c = res["checks"]
    assert any(c[f"{k}_control_gap"]["value"] > c[f"{k}_logit_gap"]["limit"]
               for k in ("ls", "be"))
    assert all(c[f"{k}_logit_gap"]["value"] <= c[f"{k}_logit_gap"]["limit"]
               for k in ("ls", "be"))


def test_no_chip_no_result(tmp_path):
    from benchkit import cell as c
    sp = benchtest.tiny_spec(tmp_path)
    with pytest.raises(c.NoChip):
        c.run(sp, "tiny.colo", 1, 1.0, False, t_start=0.0,
              require_chip=True, compile_cache=False)
