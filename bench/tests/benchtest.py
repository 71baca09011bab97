"""Test helpers: put ``bench/`` and ``src/`` on the path, and lay out a
benchmark directory for the tiny CPU cells of ``bench/tests/data``."""
import gzip
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
    kinds, metric readers and architectures, the tiny test configurations
    and mixes, and ``extra`` ({relative path: text}) as new files."""
    from benchkit import spec
    root = str(root)
    for sub in ("traffic", "metrics", "arch"):
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


def calls_of(path):
    """One call per ``bench.call`` span of a trace, with live rows made from
    its index (the recorded trace keeps no rows)."""
    from benchkit import cell
    from benchkit import tracing as tr
    out = []
    spans = (e for e in tr.load(path).host if e.name == "bench.call")
    for i, e in enumerate(spans):
        kind = e.args["kind"]
        if kind == "decode":
            rows = [(7 * i) % 97, (13 * i) % 89 + 1][: 1 + i % 2]
            out.append(cell.Call(0.0, e.args["tenant"], "decode", 1, 2, rows,
                                 len(rows)))
        else:
            sq = int(kind[len("chunk"):])
            rows = [((i % 3) * sq, sq)] + ([(0, sq)] if i % 4 == 0 else [])
            out.append(cell.Call(0.0, e.args["tenant"], "chunk", sq, 2, rows,
                                 i % 2))
    return out


def recorded_record(tmp_path, sp, conf):
    """The tiny colocated cell's record (``conf``: its configuration, with
    architectures from ``sp``), reduced from the trace recorded on the chip
    (``data/tiny-colo.xplane.pb.gz``) with the calls of :func:`calls_of`."""
    from benchkit import cell, workcount
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(f"{DATA}/tiny-colo.xplane.pb.gz") as src:
        path.write_bytes(src.read())
    models, archs, tenants, limits = cell._tenants(sp, conf)
    rec = cell.RunRecord("tiny.colo", 2.0, models, tenants, limits, archs)
    rec.peak = workcount.peaks("TPU v5 lite")
    rec.calls = calls_of(path)
    rec.traced = cell.reduce_trace(rec, path, rec.calls)
    return rec
