#!/usr/bin/env python3
"""Split a cell's device idle time by the engine's own host spans.

    python3 bench/split_idle.py --workload colo.chat --seed 7 --seconds 51

runs the cell once with ``--trace 1`` (as ``bench/run.py`` does, and prints
the same result line), keeps the profiler trace of the window's last
seconds, and reduces it with :mod:`benchkit.spans`. Standard error gets one
``info:`` line: the slice's idle seconds by the innermost ``engine.*`` span
open in them (``sched``, ``dispatch``, ``pages``, ``sync``, ``emit``,
``control``, ``step``, and ``outside`` any engine span). The last line of
standard output is one JSON object: that split, ``idle_engine_pct``,
``step_host_ms_p50``, the median ``bench.step`` length, the engine spans per
``engine.step`` and the slot-pool counts the ``engine.dispatch`` spans carry.
An engine that writes no ``engine.*`` span gives ``null`` for what reads
them. ``--keep <file.xplane.pb>`` keeps the run's trace there;
``--span-cost 1`` also times one span's entry and exit with and without a
recording profiler.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def reduce(path) -> dict:
    from benchkit import spans, tracing
    trace = tracing.load(path)
    program = trace.program
    w = tracing.window(trace)
    if w is None:
        return {}
    lo, hi = w.start, w.end
    idle = spans.idle_intervals(trace.ops, lo, hi)
    idle_ns = sum(e - s for s, e in idle)
    split = spans.idle_split(program, idle, lo, hi)
    print(spans.describe_split(split, lo, hi, idle_ns), file=sys.stderr,
          flush=True)
    steps = [(e.end - e.start) / 1e6 for e in trace.host
             if e.name == "bench.step" and lo <= e.start < hi]
    inside = [e for e in program if lo <= e.start < hi]
    n_step = sum(e.name == "engine.step" for e in inside)
    disp = [e.args for e in inside if e.name == "engine.dispatch"]
    pool = {}
    for kind in ("decode", "chunk"):
        d = [a for a in disp if a.get("kind") == kind]
        rows = sum(int(a["slots"]) * int(a["sq"]) for a in d)
        pool[kind] = {"calls": len(d),
                      "live": sum(int(a["live"]) for a in d),
                      "tokens": sum(int(a["tokens"]) for a in d),
                      "row_tokens": rows}
    return {"window_s": (hi - lo) / 1e9, "idle_s": idle_ns / 1e9,
            "split_s": {k: v / 1e9 for k, v in split.items()},
            "split_sum_s": sum(split.values()) / 1e9,
            "idle_engine_pct": spans.idle_engine_pct(program, idle, lo, hi),
            "step_host_ms_p50": spans.step_host_ms_p50(program, lo, hi),
            "bench_step_ms_p50": (statistics.median(steps) if steps
                                  else None),
            "bench_steps": len(steps), "engine_steps": n_step,
            "engine_spans_per_step": (len(inside) / n_step if n_step
                                      else None),
            "dispatch": pool}


def span_cost(n: int = 20000) -> dict:
    """Seconds per entry and exit of one span carrying a dispatch span's
    arguments, with no profiler recording and with one recording."""
    import jax

    def one():
        with jax.profiler.TraceAnnotation(
                "engine.dispatch", tenant="ls:qwen3-1.7b", kind="decode",
                sq=1, slots=8, live=6, tokens=6, rids="1 2 3 4 5 6"):
            pass

    def per(fn):
        t = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t) / n

    off = per(one)
    d = tempfile.mkdtemp(prefix="span-cost-")
    try:
        jax.profiler.start_trace(d)
        on = per(one)
        jax.profiler.stop_trace()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return {"span_us_off": off * 1e6, "span_us_on": on * 1e6}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    ap.add_argument("--span-cost", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchkit import cell, spec
    tmp = tempfile.mkdtemp(prefix="split-idle-")
    kept = args.keep or os.path.join(tmp, "run.xplane.pb")
    try:
        try:
            result = cell.run(spec.Spec(), args.workload, args.seed,
                              args.seconds, True, t_start=T_START,
                              keep_trace=kept)
        except cell.NoChip as e:
            print(f"bench: {e}", file=sys.stderr)
            return 2
        print(json.dumps(result), flush=True)
        out = reduce(kept) if os.path.exists(kept) else {}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if args.span_cost:
        out.update(span_cost())
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
