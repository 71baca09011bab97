"""Finds everything a cell is made of by name, so that a new configuration,
traffic mix or per-layer metric is a new file and never an edit:

* ``BENCHMARK.json`` at the repository root lists the cells;
* ``bench/configs/<config>.json`` holds a configuration as it is run;
* ``bench/traffic/<mix>.json`` holds a traffic mix's parameters, read by the
  one generator in :mod:`benchkit.traffic`, whose distribution and arrival
  kinds are the modules ``bench/traffic/<kind>.py``;
* ``bench/metrics/<metric>.py`` is the reader of one per-layer metric: a
  function ``read(rec)`` over the run's :class:`benchkit.cell.RunRecord`
  that returns the value, or ``None`` where the run has nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_DIR = BENCH_DIR.parent


class Spec:
    """The benchmark's files under one root (``bench/`` by default; tests
    point it at a copy)."""

    def __init__(self, bench_dir: Path = BENCH_DIR,
                 benchmark_json: Path | None = None):
        self.dir = Path(bench_dir)
        self.benchmark_json = Path(benchmark_json or
                                   self.dir.parent / "BENCHMARK.json")
        self._kinds: dict = {}

    def benchmark(self) -> dict:
        return json.loads(self.benchmark_json.read_text())

    def workload(self, name: str) -> dict:
        for w in self.benchmark()["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.benchmark_json}")

    def config(self, name: str) -> dict:
        return json.loads((self.dir / "configs" / f"{name}.json").read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def _module(self, path: Path, label: str):
        if label not in self._kinds:
            if not path.is_file():
                raise KeyError(f"no file {path} for {label!r}")
            spec = importlib.util.spec_from_file_location(
                f"bench_{label.replace('.', '_').replace('/', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._kinds[label] = mod
        return self._kinds[label]

    def kind(self, kind: str):
        """The traffic kind module ``bench/traffic/<kind>.py``."""
        return self._module(self.dir / "traffic" / f"{kind}.py",
                            f"traffic/{kind}")

    def metric_reader(self, metric: str):
        """``read(rec)`` of the per-layer metric ``metric``."""
        return self._module(self.dir / "metrics" / f"{metric}.py",
                            f"metrics/{metric}").read

    def metrics_for(self, workload: str) -> tuple[list, list]:
        """(end-to-end, per-layer) metric entries that ``workload``
        reports: those without a ``workloads`` key, and those that list
        it."""
        bm = self.benchmark()

        def mine(m):
            return "workloads" not in m or workload in m["workloads"]
        return ([m for m in bm["end_to_end"] if mine(m)],
                [m for m in bm["per_layer"] if mine(m)])
