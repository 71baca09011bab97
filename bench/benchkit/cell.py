"""One run of one cell: build the program's engine from the configuration
file, warm up exactly the step shapes the cell's traffic uses, drive the
engine open-loop for the measured window, let the window's own requests
finish under the same load, and check the served tokens against the plain
reference once the engine is freed.

Clocks: every end-to-end number is the harness's own host clock
(``time.perf_counter``), read as a client would see the engine: a request
falls due at its scheduled time, and a token exists once the ``step()``
that produced it has returned. The program's own timestamps feed only the
per-layer metric of admission wait.
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import reference, spans, stats, traffic, weights, workcount
from . import tracing as tr
from .spec import REPO_DIR

# JAX's persistent compilation cache, inside the checkout at a fixed path
COMPILE_CACHE_DIR = REPO_DIR / ".jax_cache"

# the window's requests may finish under continued load for this long after
# the window closes; one that has not by then counts as failed
DRAIN_CAP_S = 120.0
# open-loop arrivals keep coming for this long after the window
TAIL_S = 60.0
# the traced run profiles the window's last seconds (a whole window's
# trace is too large to read within a run's time)
TRACE_S = 10.0
# served tokens the correctness check compares per tenant class, at least
CHECK_TOKENS = 256
CHECK_MAX_REQUESTS = 12


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


@dataclass
class Call:
    """One dispatch of a tenant's decode or chunk program, as the harness
    saw its inputs."""
    t: float
    tenant: str
    kind: str            # "decode" | "chunk"
    sq: int              # tokens per row
    n_slots: int         # rows computed
    rows: list           # decode: [pos]; chunk: [(start, sq)] (live rows)
    logit_rows: int      # live rows whose logits the engine uses


@dataclass
class StepRec:
    ls_work: bool
    be_work: bool
    ran: Optional[str]   # class of the tenant whose quantum ran


@dataclass
class RunRecord:
    """What one run saw, for the metric readers (``bench/metrics``)."""
    workload: str
    seconds: float
    models: Dict[str, Any]             # tenant -> its architecture's model
    tenants: Dict[str, str]            # class -> tenant name
    limits: Dict[str, dict]            # class -> {"ttft_ms", "tbt_ms"}
    archs: Dict[str, Any] = field(default_factory=dict)  # tenant -> module
    sent: List[traffic.Sent] = field(default_factory=list)
    calls: List[Call] = field(default_factory=list)      # in the window
    steps: List[StepRec] = field(default_factory=list)   # in the window
    ls_gaps: List[float] = field(default_factory=list)   # in the window
    be_tokens: int = 0                                   # in the window
    ls_queue_at_close: int = 0      # LS requests waiting for a slot then
    t0: float = 0.0
    t1: float = 0.0
    t_stop: float = 0.0
    peak: Optional[dict] = None
    traced: Optional[dict] = None   # reduction of the traced window
    t_start: float = 0.0            # process start (host clock)
    setup_s: float = 0.0
    # (time, LS requests waiting for a slot) after every step of the run
    ls_queue: List[tuple] = field(default_factory=list)

    def window_ls(self) -> List[traffic.Sent]:
        return [s for s in self.sent if s.cls == "LS" and s.in_window]


# -- end-to-end metrics -------------------------------------------------

def ttft_s(s: traffic.Sent, t_stop: float) -> float:
    """Time to first token from the due time; a request that has none by
    the end of the run enters with its age then."""
    return (s.t_first if s.t_first is not None else t_stop) - s.t_due


def met_slo(s: traffic.Sent, limits: dict) -> bool:
    """Finished, within the TTFT limit, and within the limit on the mean
    gap between its tokens (read from the request once
    :func:`traffic.detach` has copied what the client saw)."""
    if not s.finished or s.t_first is None:
        return False
    if (s.t_first - s.t_due) * 1e3 > limits["ttft_ms"]:
        return False
    return not s.gaps or np.mean(s.gaps) * 1e3 <= limits["tbt_ms"]


def end_to_end(rec: RunRecord, setup_s: float) -> dict:
    ls = rec.window_ls()
    out = {"setup_s": setup_s}
    if ls:
        out["ls_ttft_p90_ms"] = stats.percentile(
            [ttft_s(s, rec.t_stop) for s in ls], 90) * 1e3
        out["ls_slo_pct"] = 100.0 * sum(
            met_slo(s, rec.limits["LS"]) for s in ls) / len(ls)
    if rec.ls_gaps:
        out["ls_tbt_p99_ms"] = stats.percentile(rec.ls_gaps, 99) * 1e3
    if "BE" in rec.tenants:
        out["be_tok_per_s"] = rec.be_tokens / rec.seconds
    return out


# -- engine construction ------------------------------------------------

def build(conf: dict, seed: int, models: dict, archs: dict):
    """The engine, built by the program's own launcher from the argv the
    configuration file states, serving the benchmark's seeded weights
    (``models`` and ``archs``: each tenant's model and its architecture
    module)."""
    from repro.launch import serve
    argv = ["--ls", conf["ls"]["model"]]
    if "be" in conf:
        argv += ["--be", conf["be"]["model"]]
    args = serve.build_parser().parse_args(argv + conf["serve_argv"])
    if "be" not in conf:
        args.be = []      # the launcher's flag takes at least one name
    params = {t: weights.make(archs[t], m, seed, t)
              for t, m in models.items()}
    with contextlib.redirect_stdout(sys.stderr):
        eng = serve.build_engine(args, params=params)
    for t, m in models.items():
        bad = archs[t].mismatches(m, eng.tenants[t].cfg)
        if bad:
            raise ValueError(f"{t}: the program does not serve the "
                             f"configuration file's model: {bad}")
    return eng


def _record_calls(log: list, rt, kind: str, fn, sentinel: int):
    import jax
    name = rt.spec.name

    def call(params, toks, cache, pos, pt):
        p = np.asarray(pos)
        sq = int(toks.shape[1])
        live = np.nonzero(p < sentinel)[0]
        if kind == "decode":
            rows = [int(p[b]) for b in live]
            logit_rows, label = len(rows), "decode"
        else:
            rows = [(int(p[b]), sq) for b in live]
            logit_rows = sum(
                1 for b in live if rt.active[b] is not None
                and p[b] + sq >= len(rt.active[b].tokens))
            label = f"chunk{sq}"
        log.append(Call(time.perf_counter(), name, kind, sq, rt.n_slots,
                        rows, logit_rows))
        with jax.profiler.TraceAnnotation("bench.call", tenant=name,
                                          kind=label):
            return fn(params, toks, cache, pos, pt)
    return call


def instrument(eng, calls: list, fault=None):
    """Record every decode and chunk dispatch (and, in tests, put a fault
    under the timed path)."""
    for rt in eng.tenants.values():
        sentinel = rt.kv.pages_per_slot * rt.kv.page_size
        for kind, attr in (("decode", "decode_fn"), ("chunk", "chunk_fn")):
            fn = getattr(rt, attr)
            if fault is not None:
                fn = fault(rt, kind, fn)
            setattr(rt, attr, _record_calls(calls, rt, kind, fn, sentinel))


def warm_up(eng, streams: dict, tenants: dict):
    """Compile every step shape the traffic will use, and no other: the
    decode step, and one chunk program per chunk length that the streams'
    prompt lengths produce (a prompt of Sq + 1 tokens runs as Sq, 1)."""
    chunk = eng.chunk_size
    rng = np.random.default_rng(0)
    for cls, st in streams.items():
        t = tenants[cls]
        vocab = eng.tenants[t].cfg.vocab_size
        sqs = {c for L in set(st.prompt)
               for c in traffic.chunk_lengths(L, chunk)} - {1}
        for sq in sorted(sqs) or [1]:
            eng.submit(t, rng.integers(0, vocab, sq + 1), max_new=2)
    eng.run_until_idle()


# -- the window -----------------------------------------------------------

class Observer:
    """Reads new output tokens after each step, as a client would."""

    def __init__(self, rec: RunRecord):
        self.rec = rec
        self.inflight: List[traffic.Sent] = []

    def __call__(self, now: float, window_open: bool):
        keep = []
        for s in self.inflight:
            r = s.req
            n = len(r.output) if r.output else 0
            if n > s.n_seen:
                if s.n_seen == 0:
                    s.t_first = now
                    new_gaps = [0.0] * (n - 1)
                else:
                    new_gaps = [now - s.t_last] + [0.0] * (n - s.n_seen - 1)
                s.gaps += new_gaps
                if window_open:
                    if s.cls == "LS":
                        self.rec.ls_gaps += new_gaps
                    else:
                        self.rec.be_tokens += n - s.n_seen
                s.t_last, s.n_seen = now, n
            if r.t_done is None:
                keep.append(s)
        self.inflight = keep


def serve_window(eng, rec: RunRecord, streams: dict, seconds: float,
                 calls: list, trace_dir: Optional[str] = None):
    """Drive the engine open-loop: submit each LS request when it falls due
    (``submit(..., at=due)``), keep the BE backlog ``depth`` deep, step the
    engine, and read new tokens after every step. The window closes after
    ``seconds``; the load goes on until every request due in the window
    has finished (at most ``DRAIN_CAP_S`` more). With
    ``trace_dir`` the profiler records the window's last ``TRACE_S``
    seconds, marked by a ``bench.traced`` span; it stops after the window
    closes, so its stall falls outside the window. ``calls`` is the list
    :func:`instrument` records into; returns the [start, stop) indices of
    the calls made while the profiler ran."""
    import jax
    span = jax.profiler.TraceAnnotation
    ls_t = rec.tenants["LS"]
    be_t = rec.tenants.get("BE")
    ls_rt = eng.tenants[ls_t]
    be_rt = eng.tenants[be_t] if be_t else None
    ls_stream, be_stream = streams["LS"], streams.get("BE")
    ls_vocab = ls_rt.cfg.vocab_size
    ls_reqs = [ls_stream.make(i, ls_vocab) for i in range(len(ls_stream))]
    be_next = 0
    obs = Observer(rec)
    window_span, traced = None, [None, None]
    t0 = time.perf_counter()
    t1 = t0 + seconds
    tr_lo = t1 - min(TRACE_S, seconds / 2)
    hard_stop = t1 + DRAIN_CAP_S
    rec.t0, rec.t1 = t0, t1
    nxt = 0
    window_open = True
    while True:
        now = time.perf_counter()
        if trace_dir and traced[0] is None and now >= tr_lo:
            _sync(eng)
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options())
            window_span = span("bench.traced")
            window_span.__enter__()
            traced[0] = len(calls)
        closing = window_open and now >= t1
        if closing:
            window_open = False
            rec.ls_queue_at_close = len(ls_rt.queue)
        if not window_open and (now >= hard_stop or all(
                s.req is not None and s.req.t_done is not None
                for s in ls_reqs if s.in_window)):
            break
        with span("bench.submit"):
            while nxt < len(ls_reqs) and t0 + ls_reqs[nxt].due <= now:
                s = ls_reqs[nxt]
                s.t_due = t0 + s.due
                s.t_submit = time.perf_counter()
                s.req = eng.submit(ls_t, s.tokens, max_new=s.max_new,
                                   at=s.t_due)
                rec.sent.append(s)
                obs.inflight.append(s)
                nxt += 1
            while be_rt is not None and len(be_rt.queue) < be_stream.depth:
                s = be_stream.make(be_next, be_rt.cfg.vocab_size)
                s.t_due = s.t_submit = time.perf_counter()
                s.in_window = window_open
                s.req = eng.submit(be_t, s.tokens, max_new=s.max_new)
                rec.sent.append(s)
                obs.inflight.append(s)
                be_next += 1
        if closing and window_span is not None:
            # after the window's last requests were handed over, so that
            # the profiler's stall delays none of their submissions
            window_span.__exit__(None, None, None)
            _sync(eng)
            jax.profiler.stop_trace()
            traced[1] = len(calls)
        ls_work = ls_rt.has_work()
        be_work = be_rt is not None and be_rt.has_work()
        n_ev = len(eng.events)
        with span("bench.step"):
            progressed = eng.step()
        if window_open:
            rec.steps.append(StepRec(ls_work, be_work,
                                     eng.events[-1][2]
                                     if len(eng.events) > n_ev else None))
        with span("bench.client"):
            now = time.perf_counter()
            obs(now, window_open)
            rec.ls_queue.append((now - t0, len(ls_rt.queue)))
        if not progressed:
            due = t0 + ls_reqs[nxt].due if nxt < len(ls_reqs) else hard_stop
            until = min(due, t1 if window_open else hard_stop)
            with span("bench.idle"):
                time.sleep(max(0.0, until - time.perf_counter()))
    rec.t_stop = time.perf_counter()
    if traced[0] is not None and traced[1] is None:   # ended as it closed
        window_span.__exit__(None, None, None)
        _sync(eng)
        jax.profiler.stop_trace()
        traced[1] = len(calls)
    return traced


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _sync(eng):
    import jax
    for rt in eng.tenants.values():
        jax.block_until_ready(rt.cache)


def window_calls(rec: RunRecord, calls: list):
    """The dispatches made while the window was open."""
    rec.calls = [c for c in calls if rec.t0 <= c.t < rec.t1]


def reduce_trace(rec: RunRecord, path, calls: list) -> dict:
    """Device numbers of the traced slice of the window: busy time and the
    slice's length, the breakdown, and each call made while the profiler
    ran paired with its step program's device time, its kernels' device
    time (the markers of the served architectures' ``kernels``) and its
    device time by operation name. Calls and step programs are paired in
    the order the device ran them; a count that differs leaves the pairs
    out (and the metrics that read them silent). Also the slice's bounds
    on the profiler's clock (``slice``), the engine's spans of the profile
    (``engine_spans``, with their arguments; readers clip them to the
    slice) and, where the device ran, the slice's intervals with no device
    operation (``idle``)."""
    trace = tr.load(path)
    span = tr.window(trace)
    if span is None:
        return {}
    lo, hi = span.start, span.end
    kept = {"slice": (lo, hi), "engine_spans": trace.program}
    if not trace.ops:
        return kept
    markers = sorted({k for a in rec.archs.values() for k in a.kernels})
    mods = tr.step_modules(trace, float("-inf"), float("inf"))
    aligned = len(mods) == len(calls)
    labels = [f"{c.tenant}/{c.kind}{c.sq if c.kind == 'chunk' else ''}"
              for c in calls] if aligned else ["step program"] * len(mods)
    gaps = tr.idle_gaps(trace, lo, hi)
    out = {"busy_s": tr.busy_ns(trace, lo, hi) / 1e9,
           "window_s": (hi - lo) / 1e9, "n_devices": trace.n_devices,
           "n_modules": len(mods), "n_calls": len(calls), "aligned": None,
           "breakdown": {
               "device_ops": tr.top_ops(trace, mods, labels, lo, hi),
               "idle_gaps": [[k, v / 1e9] for k, v in sorted(
                   gaps.items(), key=lambda kv: -kv[1])[:10]]},
           "idle": spans.idle_intervals(trace.ops, lo, hi), **kept}
    if aligned:
        out["aligned"] = list(zip(calls, tr.per_module(trace, mods,
                                                       markers)))
    return out


# -- correctness -----------------------------------------------------------

def pick_checked(sent: List[traffic.Sent], cls: str, seed: int) -> list:
    """The finished requests of one class that the check compares: the one
    with the most output tokens, then others in an order drawn from the
    seed, until ``CHECK_TOKENS`` served tokens are covered."""
    done = [s for s in sent if s.cls == cls and s.finished and s.output]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.output))
    rest = [s for s in done if s is not longest]
    order = np.random.default_rng([int(seed) % 2**63, 11]).permutation(
        len(rest))
    out, n = [longest], len(longest.output)
    for i in order:
        if n >= CHECK_TOKENS or len(out) >= CHECK_MAX_REQUESTS:
            break
        out.append(rest[i])
        n += len(rest[i].output)
    return out


def check(samples: list, arch, m, seed: int, tenant: str, max_seq: int,
          control: bool) -> dict:
    """Widest gap, over the sampled requests' served tokens, by which a
    served token's reference logit lies below the reference's best; with
    ``control`` also the same for the fp8 control's own first choice at
    each of those positions."""
    params = weights.make(arch, m, seed, tenant)
    worst, worst_c, n = 0.0, None, 0
    for prompt, out in samples:
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        ref = reference.logits(params, arch, m, seq, rows, pad_to=max_seq)
        worst = max(worst, float(reference.gaps(ref, out).max()))
        n += len(out)
        if control:
            ctl = reference.logits(params, arch, m, seq, rows,
                                   pad_to=max_seq, precision="fp8")
            g = float(reference.gaps(ref, ctl.argmax(-1)).max())
            worst_c = g if worst_c is None else max(worst_c, g)
    del params
    return {"gap": worst, "control_gap": worst_c, "tokens": n,
            "requests": len(samples)}


# -- one run -----------------------------------------------------------------

def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_chip and (d.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX finds "
                     f"{len(devs)} {d.platform} device(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def compile_counters():
    """Counts of JAX's compile and compilation-cache events (as
    ``chip_smoke.py`` counts them)."""
    import collections
    import jax
    events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: events.update([event]))
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: events.update([event]))
    return events


def use_checkout_cache() -> str:
    """Keep every compiled program in the checkout's own cache (the
    environment's ``JAX_COMPILATION_CACHE_DIR`` is overridden, so that two
    checkouts share nothing), however short its compile."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return str(COMPILE_CACHE_DIR)


COMPILES = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"


def _tenants(spec, conf: dict):
    """({tenant: model}, {tenant: architecture module}, {class: tenant},
    {class: LS limits}) of a configuration file; each tenant group names
    its architecture (``bench/arch/<arch>.py``)."""
    models, archs, tenants, limits = {}, {}, {}, {}
    for cls in ("ls", "be"):
        if cls in conf:
            t = f"{cls}:{conf[cls]['model']}"
            archs[t] = spec.arch(conf[cls]["arch"])
            models[t] = archs[t].model(conf[cls], conf["dtype"])
            tenants[cls.upper()] = t
            if "limits" in conf[cls]:
                limits[cls.upper()] = conf[cls]["limits"]
    return models, archs, tenants, limits


def _measure(eng, rec, streams, calls, trace, keep_trace, log):
    """The window, and with ``trace`` the reduction of its traced slice;
    returns the compile events counted inside the window."""
    import jax
    events = compile_counters()
    tmp = None
    if trace:
        # a first start of the profiler is slow: pay it here, in set-up
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(os.path.join(tmp, "warm"),
                                 profiler_options=_profile_options())
        jax.profiler.stop_trace()
    before = events.copy()
    rec.setup_s = time.perf_counter() - rec.t_start
    try:
        traced = serve_window(eng, rec, streams, rec.seconds, calls,
                              trace_dir=os.path.join(tmp, "run") if trace
                              else None)
        in_window = {k: events[k] - before[k] for k in (COMPILES, CACHE_HITS)}
        window_calls(rec, calls)
        if trace:
            files = [os.path.join(d, f) for d, _, fs in
                     os.walk(os.path.join(tmp, "run"))
                     for f in fs if f.endswith(".xplane.pb")]
            if keep_trace and files:
                shutil.copy(files[0], keep_trace)
            t_red = time.perf_counter()
            rec.traced = (reduce_trace(rec, files[0],
                                       calls[traced[0]:traced[1]])
                          if files and traced[1] is not None else {})
            log(f"info: trace reduction {time.perf_counter() - t_red:.1f} s "
                f"({os.path.getsize(files[0]) if files else 0} bytes)")
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    return in_window


def _verify(conf, rec, samples, seed, max_seq, control) -> tuple:
    """(correct, checks): each tenant's widest logit gap against the limit
    its configuration states; with ``control``, the float8 control's gap
    is what is judged."""
    checks, correct = {}, True
    for cls, t in rec.tenants.items():
        lim = conf[cls.lower()]["logit_gap_limit"]
        res = check(samples[cls], rec.archs[t], rec.models[t], seed, t,
                    max_seq, control)
        checks[f"{cls.lower()}_logit_gap"] = {
            "value": res["gap"], "limit": lim, "tokens": res["tokens"]}
        if control:
            # the control's own choices stand in the program's place and
            # are judged by the same limit
            checks[f"{cls.lower()}_control_gap"] = {
                "value": res["control_gap"], "limit": lim,
                "tokens": res["tokens"]}
        judged = res["control_gap"] if control else res["gap"]
        correct &= res["requests"] > 0 and judged <= lim
    return bool(correct), checks


def run(spec, workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, control: bool = False,
        fault: Optional[Callable] = None, compile_cache: bool = True,
        keep_trace: Optional[str] = None, log=None) -> dict:
    """One run of ``workload``; returns the result line's object (the
    ``checks`` key last)."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    w = spec.workload(workload)
    conf = spec.config(w["config"])
    device = device_info(w["chips"], require_chip)
    import jax
    cache_dir = use_checkout_cache() if compile_cache else None
    log(f"info: device {device['kind']} x{device['count']} "
        f"({device['platform']}); compile cache {cache_dir}")
    models, archs, tenants, limits = _tenants(spec, conf)
    streams = traffic.streams(spec, spec.traffic(w["traffic"]), seed,
                              seconds, TAIL_S)
    if set(streams) - set(tenants):
        raise ValueError(f"mix {w['traffic']} sends traffic that "
                         f"configuration {w['config']} does not serve")
    rec = RunRecord(workload, seconds, models, tenants, limits, archs,
                    t_start=t_start)
    if device["platform"] == "tpu":
        rec.peak = workcount.peaks(device["kind"])

    eng = build(conf, seed, models, archs)
    max_seq = eng.max_seq
    calls: list = []
    instrument(eng, calls, fault)
    warm_up(eng, streams, tenants)
    calls.clear()
    in_window = _measure(eng, rec, streams, calls, trace, keep_trace, log)
    device["memory_peak_bytes"] = int((jax.devices()[0].memory_stats()
                                       or {}).get("peak_bytes_in_use", 0))
    log(f"info: window {seconds} s: {len(rec.window_ls())} LS requests due, "
        f"{sum(1 for s in rec.sent if s.cls == 'BE' and s.in_window)} BE "
        f"requests sent; {len(rec.ls_gaps)} LS token gaps; drain "
        f"{rec.t_stop - rec.t1:.3f} s; {rec.ls_queue_at_close} LS requests "
        f"waiting for a slot at the close")
    log(f"info: compiles inside the window: {in_window[COMPILES]} "
        f"(persistent-cache loads {in_window[CACHE_HITS]})")
    log(f"info: peak device memory {device['memory_peak_bytes']} bytes")

    for s in rec.sent:
        traffic.detach(s)
    # a window LS request counts as failed when it failed or had not
    # finished by the end of the run; a BE request only when it failed
    failed = sum(1 for s in rec.sent if s.in_window and (
        s.failed or (s.cls == "LS" and not s.finished)))
    attempted = sum(1 for s in rec.sent if s.in_window)
    e2e = end_to_end(rec, rec.setup_s)
    samples = {cls: [(s.tokens, s.output)
                     for s in pick_checked(rec.sent, cls, seed)]
               for cls in tenants}
    del eng            # the reference runs on a chip the engine has left
    gc.collect()
    t_check = time.perf_counter()
    correct, checks = _verify(conf, rec, samples, seed, max_seq, control)
    log(f"info: correctness check {time.perf_counter() - t_check:.1f} s "
        f"(reference{' and control' if control else ''})")

    result = {"correct": correct, "attempted": attempted, "failed": failed}
    e2e_spec, layer_spec = spec.metrics_for(workload)
    if trace:
        metrics = {}
        for m in layer_spec:
            v = spec.metric_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        t = rec.traced or {}
        if t.get("window_s"):
            device["busy_s"] = t["busy_s"]
            device["window_s"] = t["window_s"]
            result["breakdown"] = t["breakdown"]
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in e2e_spec if m["name"] in e2e}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result
