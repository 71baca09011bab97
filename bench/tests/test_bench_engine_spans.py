"""The engine's own spans in a profiler trace (``benchkit.spans``): on
events made by hand, on the tiny cell served on the CPU under the profiler,
and on the trace recorded on one TPU v5e before the engine wrote any."""
import glob
import gzip

import jax
import numpy as np

import benchtest
import split_idle
from benchkit import cell, spans
from benchkit import tracing as tr

E = tr.Event
MS = 1_000_000     # ns


def _program():
    # one step: the scheduler, a dispatch that uploads a page table, the
    # argmax sync, token bookkeeping; then a step outside the slice
    return [E("engine.step", 0, 100 * MS, {"step": 4}),
            E("engine.sched", 5 * MS, 15 * MS),
            E("engine.dispatch", 20 * MS, 40 * MS),
            E("engine.pages", 25 * MS, 30 * MS),
            E("engine.sync", 40 * MS, 80 * MS),
            E("engine.emit", 80 * MS, 95 * MS),
            E("engine.step", 130 * MS, 150 * MS),
            E("engine.sync", 135 * MS, 140 * MS)]


def _ops():
    return [E("%fusion.1", 30 * MS, 45 * MS), E("%fusion.2", 85 * MS,
                                                 90 * MS)]


def test_innermost_span_segments_cover_the_slice():
    segs = spans.segments(_program(), 0, 120 * MS)
    assert [(s // MS, e // MS, lab) for s, e, lab in segs] == [
        (0, 5, "step"), (5, 15, "sched"), (15, 20, "step"),
        (20, 25, "dispatch"), (25, 30, "pages"), (30, 40, "dispatch"),
        (40, 80, "sync"), (80, 95, "emit"), (95, 100, "step"),
        (100, 120, "outside")]


def test_idle_split_is_the_exact_intersection_with_self_time():
    lo, hi = 0, 120 * MS
    idle = spans.idle_intervals(_ops(), lo, hi)
    assert idle == [(0, 30 * MS), (45 * MS, 85 * MS), (90 * MS, 120 * MS)]
    split = spans.idle_split(_program(), idle, lo, hi)
    assert {k: v / MS for k, v in split.items()} == {
        "step": 15, "sched": 10, "dispatch": 5, "pages": 5, "sync": 35,
        "emit": 10, "outside": 20}
    assert sum(split.values()) == sum(e - s for s, e in idle)


def test_idle_engine_pct_leaves_out_the_sync_and_outside():
    lo, hi = 0, 120 * MS
    idle = spans.idle_intervals(_ops(), lo, hi)
    # step 15 + sched 10 + dispatch 5 + pages 5 + emit 10 of 120
    assert spans.idle_engine_pct(_program(), idle, lo, hi) == 37.5
    assert spans.idle_engine_pct([], idle, lo, hi) is None


def test_step_host_ms_takes_the_sync_out_of_each_step():
    assert spans.step_host_ms(_program(), 0, 200 * MS) == [60.0, 15.0]
    # a step that starts before the slice is not in it
    assert spans.step_host_ms(_program(), 1, 200 * MS) == [15.0]
    assert spans.step_host_ms_p50(_program(), 0, 200 * MS) == 37.5
    assert spans.step_host_ms_p50([], 0, 200 * MS) is None


def test_trace_from_before_engine_spans_reads_nothing(tmp_path):
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(f"{benchtest.DATA}/tiny-colo.xplane.pb.gz") as src:
        path.write_bytes(src.read())
    assert spans.load_program(path) == []
    t = tr.load(path)
    w = tr.window(t)
    idle = spans.idle_intervals(t.ops, w.start, w.end)
    assert spans.idle_engine_pct([], idle, w.start, w.end) is None
    assert spans.step_host_ms_p50([], w.start, w.end) is None


def test_tiny_cell_spans_nest_in_steps_and_count_the_calls(tmp_path):
    sp = benchtest.tiny_spec(tmp_path / "bench")
    conf = sp.config("tiny-colo")
    models, tenants, _ = cell._tenants(conf)
    eng = cell.build(conf, 2**31 + 5, models)
    calls = []
    cell.instrument(eng, calls)
    rng = np.random.default_rng(3)
    for cls, L, n in (("LS", 41, 3), ("BE", 49, 4), ("LS", 17, 2)):
        t = tenants[cls]
        eng.submit(t, rng.integers(0, eng.tenants[t].cfg.vocab_size, L),
                   max_new=n)
    logdir = tmp_path / "prof"
    jax.profiler.start_trace(str(logdir))
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            for _ in range(200):
                with jax.profiler.TraceAnnotation("bench.step"):
                    if not eng.step():
                        break
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    program = spans.load_program(path)
    names = {e.name for e in program}
    assert {"engine.step", "engine.sched", "engine.dispatch",
            "engine.pages", "engine.sync", "engine.emit"} <= names
    steps = [e for e in tr.load(path).host if e.name == "bench.step"]
    for e in program:
        assert any(s.start <= e.start and e.end <= s.end for s in steps)
    # the dispatch spans count, where the work happens, what the harness
    # records of the same calls from outside
    disp = [e.args for e in program if e.name == "engine.dispatch"]
    assert len(disp) == len(calls) > 0
    assert {c.kind for c in calls} == {"decode", "chunk"}
    for a, c in zip(disp, calls):
        assert (a["tenant"], a["kind"]) == (c.tenant, c.kind)
        assert a["sq"] == c.sq and a["slots"] == c.n_slots
        assert a["live"] == len(c.rows)
        assert a["tokens"] == (len(c.rows) if c.kind == "decode"
                               else sum(n for _, n in c.rows))
        assert len(str(a["rids"]).split()) == a["live"]
    # the split of the slice's idle time adds up to that idle time (on the
    # CPU no device plane is recorded, so the whole slice reads idle)
    red = split_idle.reduce(path)
    assert red["split_sum_s"] == red["idle_s"] == red["window_s"]
    assert red["engine_steps"] == red["bench_steps"] > 0
    assert sum(d["calls"] for d in red["dispatch"].values()) == len(calls)


def test_tenant_programs_keep_the_step_program_names(tmp_path):
    sp = benchtest.tiny_spec(tmp_path / "bench")
    conf = sp.config("tiny-colo")
    models, _, _ = cell._tenants(conf)
    eng = cell.build(conf, 2**31 + 5, models)
    modules = set()
    for t, rt in eng.tenants.items():
        pt = rt.kv.device_page_table()
        pos = jax.numpy.zeros((rt.n_slots,), jax.numpy.int32)
        for fn, sq in ((rt.decode_fn, 1), (rt.chunk_fn, 16)):
            toks = jax.numpy.zeros((rt.n_slots, sq), jax.numpy.int32)
            text = fn.lower(rt.params, toks, rt.cache, pos, pt).as_text()
            modules.add(text.split("module @")[1].split()[0])
    assert modules == {"jit__decode_paged__ls_qwen3_1_7b",
                       "jit__chunk_paged__ls_qwen3_1_7b",
                       "jit__decode_paged__be_stablelm_1_6b",
                       "jit__chunk_paged__be_stablelm_1_6b"}
    # the reduction still finds every one of them as a step program
    mods = [E(m, 0, 1) for m in sorted(modules)]
    assert tr.step_modules(tr.Trace([], mods, []), 0, 1) == mods
