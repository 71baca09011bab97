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
