"""Test helpers: put ``bench/`` and ``src/`` on the path, and lay out a
benchmark directory for the tiny CPU cells of ``bench/tests/data``."""
import os
import shutil
import sys
import time

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
DATA = os.path.join(TESTS, "data")
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_spec(root, extra=None):
    """A benchmark directory under ``root``: the benchmark's own traffic
    kinds and metric readers, the tiny test configurations and mixes, and
    ``extra`` ({relative path: text}) as new files."""
    from benchkit import spec
    root = str(root)
    for sub in ("traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(root, sub),
                        dirs_exist_ok=True)
    for sub in ("configs", "traffic"):
        shutil.copytree(os.path.join(DATA, sub), os.path.join(root, sub),
                        dirs_exist_ok=True)
    shutil.copy(os.path.join(DATA, "BENCHMARK.json"),
                os.path.join(root, "BENCHMARK.json"))
    for rel, text in (extra or {}).items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    return spec.Spec(root, os.path.join(root, "BENCHMARK.json"))


def run_tiny(sp, workload="tiny.colo", seed=2**31 + 17, seconds=1.5,
             trace=False, **kw):
    """One CPU run of a tiny cell, with the chip check skipped."""
    from benchkit import cell
    return cell.run(sp, workload, seed, seconds, trace,
                    t_start=time.perf_counter(), require_chip=False,
                    compile_cache=False, log=lambda msg: None, **kw)
