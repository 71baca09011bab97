"""Engine spans on the profiler's clock (``repro.obs.span``).

A run recorded by ``jax.profiler`` carries one ``engine.*`` span per seam of
the engine's host work, nested on the host thread inside ``engine.step``;
the JSON tracer's stream is byte-identical whether a profile records or
not, and so are the tokens.
"""
import glob

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.controller import (OnlineController, PlanFrontier,
                                   ResourcePlan)
from repro.core.tenancy import TenantSpec
from repro.serving import ServingEngine

MAX_SEQ = 32
PAGE = 4
SEAMS = {"engine.step", "engine.control", "engine.sched", "engine.dispatch",
         "engine.pages", "engine.sync", "engine.emit"}


@pytest.fixture(scope="module")
def tiny():
    from repro.configs import smoke_config
    from repro.models import transformer as tf
    cfg = smoke_config("stablelm-1.6b").replace(num_layers=1,
                                                activation_dtype="float32")
    return cfg, tf.init_params(jax.random.key(7), cfg)


def _serve(cfg, params, tracer=None):
    """Two LS and two BE requests through a paged engine with page growth,
    swap, the prefix cache and an online controller (as ``test_obs``)."""
    lend = ResourcePlan(1.0, 1.0, 0.5, (), (), 2.0)
    cons = ResourcePlan(0.1, 1 / 6, 0.5, (), (), 2.0, prefill_budget=8)
    state = {"t": 0.0}
    eng = ServingEngine(max_seq=MAX_SEQ, paged=True, page_size=PAGE,
                        chunk_size=PAGE, slots_ls=2, slots_be=2,
                        kv_pages=10, grow_pages=True, swap=True,
                        cold_dtype="fp16",
                        controller=OnlineController(
                            PlanFrontier([(0.0, lend), (1.0, cons)]),
                            idle_patience=1),
                        control_interval=2, prefix_cache=True,
                        now_fn=lambda: state["t"], tracer=tracer)
    eng.add_tenant(TenantSpec("ls0", "LS"), cfg, params=params)
    eng.add_tenant(TenantSpec("be0", "BE"), cfg, params=params)
    rng = np.random.default_rng(11)
    reqs = [eng.submit("ls0", rng.integers(0, 100, n).astype(np.int32),
                       max_new=3) for n in (10, 11)]
    reqs += [eng.submit("be0", rng.integers(0, 100, 8).astype(np.int32),
                        max_new=6) for _ in range(2)]
    for _ in range(2000):
        state["t"] += 1.0
        if not eng.step() and not any(rt.has_work()
                                      for rt in eng.tenants.values()):
            break
    return [[int(x) for x in (r.output or [])] for r in reqs]


def _engine_spans(logdir):
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
                   for p in pd.planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.startswith("engine.")), key=lambda e: e[1])


def test_span_is_a_profiler_annotation():
    sp = obs.span("sched", tenant="ls0")
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp:                          # no profile recording: writes nothing
        pass


def test_profiled_run_records_every_seam_and_changes_no_output(tiny,
                                                               tmp_path):
    cfg, params = tiny
    plain = obs.Tracer("info")
    base = _serve(cfg, params, plain)
    traced = obs.Tracer("info")
    jax.profiler.start_trace(str(tmp_path))
    try:
        outs = _serve(cfg, params, traced)
    finally:
        jax.profiler.stop_trace()
    assert outs == base
    assert traced.jsonl() == plain.jsonl()      # byte-identical JSONL
    spans = _engine_spans(tmp_path)
    assert {name for name, *_ in spans} == SEAMS
    steps = [(s, e) for name, s, e, _ in spans if name == "engine.step"]
    # every other span nests inside one engine.step on the host thread
    for name, s, e, _ in spans:
        if name != "engine.step":
            assert any(a <= s and e <= b for a, b in steps), name
    for name, _, _, args in spans:
        if name == "engine.dispatch":
            assert args["kind"] in ("decode", "chunk")
            assert args["slots"] == 2 and 0 < args["live"] <= 2
            assert args["tokens"] == args["live"] * args["sq"]
            assert len(str(args["rids"]).split()) == args["live"]
        if name == "engine.emit":
            assert 0 <= args["finished"] <= args["tokens"]

