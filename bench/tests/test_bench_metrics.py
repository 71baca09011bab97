"""End-to-end metric definitions and the per-layer readers, on records made
by hand."""
import types

import pytest

import benchtest
from benchkit import cell, spec, stats, traffic
from benchkit import tracing as tr

SPEC = spec.Spec()
DG = SPEC.arch("dense_gqa")
LIM = {"ttft_ms": 1000.0, "tbt_ms": 100.0}


def _sent(due, first=None, gaps=(), done=True, failed=False, cls="LS",
          in_window=True, admit=None):
    s = traffic.Sent(cls, 0, 65, 8, due=due, in_window=in_window)
    s.t_due, s.t_submit, s.t_first, s.gaps = due, due, first, list(gaps)
    s.t_admit = admit
    s.req = types.SimpleNamespace(t_done=1.0 if done else None,
                                  failed=failed, t_admit=admit, output=[1])
    traffic.detach(s)        # as the harness does once the run is over
    return s


def _rec(sent, **kw):
    rec = cell.RunRecord("w", 10.0, {}, {"LS": "ls:x", **kw.pop(
        "tenants", {})}, {"LS": LIM})
    rec.sent, rec.t0, rec.t1, rec.t_stop = sent, 0.0, 10.0, 12.0
    for k, v in kw.items():
        setattr(rec, k, v)
    return rec


def test_unfinished_request_enters_the_ttft_tail_with_its_age():
    s = _sent(due=11.5)                     # no first token by t_stop=12
    assert cell.ttft_s(s, 12.0) == pytest.approx(0.5)
    sent = [_sent(due=0.1 * i, first=0.1 * i + 0.2) for i in range(9)]
    sent.append(_sent(due=2.0, done=False))  # never got a token: age 10 s
    rec = _rec(sent)
    e2e = cell.end_to_end(rec, 3.0)
    assert e2e["ls_ttft_p90_ms"] == pytest.approx(200.0)   # 9 of 10 fast
    sent.append(_sent(due=3.0, done=False))
    assert cell.end_to_end(_rec(sent), 3.0)["ls_ttft_p90_ms"] == \
        pytest.approx(9000.0)


def test_slo_needs_finish_ttft_and_mean_gap():
    ok = _sent(0.0, first=0.5, gaps=[0.05, 0.12])          # mean 85 ms
    slow_first = _sent(0.0, first=1.5, gaps=[0.01])
    slow_gaps = _sent(0.0, first=0.5, gaps=[0.2, 0.1])
    failed = _sent(0.0, first=0.5, failed=True)
    unfinished = _sent(0.0, first=0.5, done=False)
    assert cell.met_slo(ok, LIM)
    assert not any(cell.met_slo(s, LIM) for s in
                   (slow_first, slow_gaps, failed, unfinished))
    rec = _rec([ok, slow_first, slow_gaps, failed, unfinished])
    assert cell.end_to_end(rec, 1.0)["ls_slo_pct"] == pytest.approx(20.0)


def test_window_only_and_be_rate():
    sent = [_sent(0.0, first=0.1), _sent(11.0, first=11.1, in_window=False)]
    rec = _rec(sent, ls_gaps=[0.01] * 99 + [0.5], be_tokens=250,
               tenants={"BE": "be:y"})
    e2e = cell.end_to_end(rec, 2.5)
    assert e2e["ls_tbt_p99_ms"] == pytest.approx(10.0)
    assert e2e["be_tok_per_s"] == pytest.approx(25.0)
    assert e2e["setup_s"] == 2.5
    assert len(rec.window_ls()) == 1


def test_nearest_rank_percentile():
    assert stats.percentile(list(range(1, 11)), 99) == 10
    assert stats.percentile(list(range(1, 11)), 90) == 9
    assert stats.percentile([], 50) is None
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(
        (4.5 - 1.5) / 3)


def test_host_readers():
    sent = [_sent(0.0, first=0.3, admit=0.1), _sent(1.0, first=1.2,
                                                     admit=1.05)]
    sent[1].t_submit = 1.004
    steps = [cell.StepRec(True, True, "BE"), cell.StepRec(True, True, "LS"),
             cell.StepRec(True, True, "LS"), cell.StepRec(False, True, "BE"),
             cell.StepRec(True, False, "LS")]
    calls = [cell.Call(0, "ls:x", "decode", 1, 6, [5, 9, 30], 3),
             cell.Call(0, "ls:x", "chunk", 256, 6, [(0, 256)], 0),
             cell.Call(0, "ls:x", "chunk", 64, 6, [(256, 64), (0, 64)], 1)]
    rec = _rec(sent, steps=steps, calls=calls)
    read = SPEC.metric_reader
    assert read("gen_lag_p99_ms")(rec) == pytest.approx(4.0)
    assert read("ls_admit_wait_p90_ms")(rec) == pytest.approx(100.0)
    assert read("be_quanta_pct")(rec) == pytest.approx(100 / 3)
    assert read("decode_rows_live_pct")(rec) == pytest.approx(50.0)
    assert read("chunk_rows_useful_pct")(rec) == pytest.approx(
        100 * (256 + 128) / (6 * 256 + 6 * 64))
    # nothing traced: device readers stay silent rather than read 0
    for m in ("device_idle_pct", "step_mfu_pct.decode",
              "step_mfu_pct.prefill", "decode_attn_roofline",
              "prefill_attn_roofline", "idle_engine_pct",
              "step_host_ms_p50"):
        assert read(m)(rec) is None


def test_device_readers_on_a_traced_record():
    m = DG.Model("m", 64, 2, 4, 2, 16, 128, 1000, 1e-6, 1e4, False, True,
                 "bfloat16")
    dec = cell.Call(0, "ls:x", "decode", 1, 4, [10, 20], 2)
    chk = cell.Call(0, "ls:x", "chunk", 8, 4, [(0, 8)], 0)
    rec = _rec([], models={"ls:x": m}, archs={"ls:x": DG})
    rec.peak = {"flops": 1e12, "hbm_bytes_per_s": 1e11}
    rec.traced = {"busy_s": 0.75, "window_s": 1.0, "aligned": [
        (dec, {"device_ns": 1000, "kernel_ns": 100,
               "kernels": {"attention_paged": 100}}),
        (chk, {"device_ns": 2000, "kernel_ns": 400,
               "kernels": {"attention_paged": 400}})]}
    read = SPEC.metric_reader
    assert read("device_idle_pct")(rec) == pytest.approx(25.0)
    from benchkit import workcount as wc
    flops = DG.step_flops(m, [10, 20], "decode", 2)
    assert read("step_mfu_pct.decode")(rec) == pytest.approx(
        100 * flops / (1e-6 * 1e12))
    need = 2 * wc.roofline_s(DG.attn_flops(m, [(0, 8)], "chunk"),
                             DG.attn_bytes(m, [(0, 8)], "chunk"), rec.peak)
    assert read("prefill_attn_roofline")(rec) == pytest.approx(
        100 * need / 400e-9)
    # the kept engine spans: one step of 100 ms that waits 40 ms on its
    # argmax, with the device idle for its first 30 ms
    E = tr.Event
    rec.traced.update(slice=(0, 120_000_000), idle=[(0, 30_000_000)],
                      engine_spans=[E("engine.step", 0, 100_000_000),
                                    E("engine.sync", 40_000_000,
                                      80_000_000)])
    assert read("idle_engine_pct")(rec) == pytest.approx(25.0)
    assert read("step_host_ms_p50")(rec) == pytest.approx(60.0)


def test_tail_and_colocated_readers_match_their_definitions():
    """In a colocated record the tails are the LS requests' alone, and the
    slot-pool readers count both tenants' calls."""
    sent = [_sent(0.1 * i, first=0.1 * i + 0.3, gaps=[0.05, 0.2])
            for i in range(10)]
    sent.append(_sent(0.0, first=5.0, gaps=[1.0], cls="BE"))
    calls = [cell.Call(0, "ls:x", "decode", 1, 6, [5, 9, 30], 3),
             cell.Call(0, "be:y", "decode", 1, 6, list(range(6)), 6),
             cell.Call(0, "ls:x", "chunk", 64, 6, [(256, 64)], 1),
             cell.Call(0, "be:y", "chunk", 256, 6, [(0, 256)] * 3, 3)]
    rec = _rec(sent, calls=calls, ls_gaps=[0.05] * 50 + [0.2] * 50,
               tenants={"BE": "be:y"})
    e2e = cell.end_to_end(rec, 0.0)
    assert e2e["ls_ttft_p90_ms"] == pytest.approx(300.0)
    assert e2e["ls_tbt_p99_ms"] == pytest.approx(200.0)
    assert e2e["ls_slo_pct"] == pytest.approx(0.0)  # mean gap 125 > 100 ms
    read = SPEC.metric_reader
    assert read("decode_rows_live_pct")(rec) == pytest.approx(75.0)
    assert read("chunk_rows_useful_pct")(rec) == pytest.approx(
        100 * (64 + 3 * 256) / (6 * 64 + 6 * 256))
    for m in ("step_mfu_pct.decode", "step_mfu_pct.prefill",
              "decode_attn_roofline", "prefill_attn_roofline",
              "device_idle_pct", "idle_engine_pct", "step_host_ms_p50"):
        assert read(m)(rec) is None      # nothing traced


def test_slo_reads_the_request_once_detached():
    """The SLO share is read after the engine's requests are dropped: it
    takes what ``detach`` copied, not the request."""
    s = traffic.Sent("LS", 0, 65, 8, due=0.0, in_window=True)
    s.t_due, s.t_first, s.gaps = 0.0, 0.5, [0.05]
    s.req = types.SimpleNamespace(t_done=2.0, failed=False, t_admit=0.1,
                                  output=[3, 4])
    traffic.detach(s)
    assert s.req is None and s.finished and s.output == [3, 4]
    assert cell.met_slo(s, LIM)
    assert cell.end_to_end(_rec([s]), 0.0)["ls_slo_pct"] == 100.0
