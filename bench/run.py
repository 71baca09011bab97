#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload colo.chat --seed 7 --seconds 40 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
under ``bench/configs`` and a traffic mix under ``bench/traffic``. With
``--trace 0`` the result reports the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window's last seconds and from the harness's own records. ``--control 1``
puts the float8 control in the program's place: ``correct`` then judges
the control's logit gaps, and comes out false (how the correctness limits
were checked; the benchmark's own runs leave it off).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, optionally
``breakdown``, and ``checks`` last); the last lines of standard error give
each compared number beside its limit. With no TPU, or fewer chips than
the cell needs, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchkit import cell, spec
    try:
        result = cell.run(spec.Spec(), args.workload, args.seed,
                          args.seconds, bool(args.trace), t_start=T_START,
                          control=bool(args.control))
    except cell.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, v in result["checks"].items():
        print(f"check: {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
