#!/usr/bin/env python3
"""Record a profiler trace of one cell on the chip and print its structure.

    python3 bench/tests/record_trace.py --bench-dir bench/tests/data \\
        --workload tiny.colo --seconds 2 --out chiprun_out/tiny.xplane.pb

The trace that ``test_bench_trace.py`` reduces was recorded so, from the
tiny CPU-test cell (``bench/tests/data``) served on one TPU v5e.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench-dir", default=None)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from benchkit import cell, spec, tracing
    if args.bench_dir:
        # a data-only directory: borrow the benchmark's kinds, readers and
        # architectures
        d = tempfile.mkdtemp()
        for sub in ("traffic", "metrics", "arch"):
            shutil.copytree(os.path.join(BENCH, sub), os.path.join(d, sub))
        for sub in ("configs", "traffic"):
            shutil.copytree(os.path.join(args.bench_dir, sub),
                            os.path.join(d, sub), dirs_exist_ok=True)
        sp = spec.Spec(d, os.path.join(args.bench_dir, "BENCHMARK.json"))
    else:
        sp = spec.Spec()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    res = cell.run(sp, args.workload, args.seed, args.seconds, True,
                   t_start=T_START, keep_trace=args.out)
    print(tracing.describe(args.out), file=sys.stderr)
    print(json.dumps(res, default=str))


if __name__ == "__main__":
    main()
