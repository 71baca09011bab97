"""Finds everything a cell is made of by name, so that a new configuration,
traffic mix or per-layer metric is a new file and never an edit:

* ``BENCHMARK.json`` at the repository root lists the cells;
* ``bench/configs/<config>.json`` holds a configuration as it is run;
* ``bench/traffic/<mix>.json`` holds a traffic mix's parameters, read by the
  one generator in :mod:`benchkit.traffic`, whose distribution and arrival
  kinds are the modules ``bench/traffic/<kind>.py``;
* ``bench/metrics/<metric>.py`` is the reader of one per-layer metric: a
  function ``read(rec)`` over the run's :class:`benchkit.cell.RunRecord`
  that returns the value, or ``None`` where the run has nothing to read;
* ``bench/arch/<arch>.py`` is the architecture that a tenant group of a
  configuration file names under ``"arch"``.

An architecture module provides:

* ``model(group, dtype)``: a frozen, hashable description of the model as
  served, read from the group's published ``config`` and its ``served_as``.
  The harness reads its ``name`` (the program's registry name), ``layers``,
  ``vocab``, ``d`` and ``dtype``;
* ``mismatches(m, cfg)``: where the program's ``ModelConfig`` departs from
  ``m`` (empty when the program serves what the file states);
* ``params(draw, m)``: the program's parameter tree, every leaf made by
  ``draw(path, shape, scale)`` (:mod:`benchkit.weights`);
* ``embed(params, tokens, m)``, ``layer(x, params, i, m, fp8)`` and
  ``head(x, rows, params, m, fp8)``: the plain reference in float32, run by
  :mod:`benchkit.reference` one layer at a time (``i`` is traced, so that
  one program serves every layer; ``fp8`` is the control), built from that
  module's ``_linear``, ``_rms``, ``_rope`` and ``_q8``;
* ``step_flops(m, rows, kind, logit_rows)``: the model FLOPs of one decode
  or chunk call (:mod:`benchkit.workcount` says what ``rows`` hold);
* ``kernels``: ``{marker: (flops, bytes, layers)}``, one entry for each
  kernel whose wrapper's name in the device trace contains ``marker``:
  one layer's operations and bytes of a call, functions of ``(m, rows,
  kind)``, and ``layers(m)``, how many layers of one step run it.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_DIR = BENCH_DIR.parent


def load_file(path) -> object:
    """The module in the file ``path`` (``<dir>/<name>.py``), registered as
    ``bench_<dir>_<name>`` while it runs, as an import would be (a
    dataclass looks its module up by name). An architecture module that
    builds on another loads it so: ``load_file(Path(__file__).with_name(
    "<other>.py"))``."""
    path = Path(path)
    name = f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


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
            self._kinds[label] = load_file(path)
        return self._kinds[label]

    def kind(self, kind: str):
        """The traffic kind module ``bench/traffic/<kind>.py``."""
        return self._module(self.dir / "traffic" / f"{kind}.py",
                            f"traffic/{kind}")

    def arch(self, name: str):
        """The architecture module ``bench/arch/<name>.py``."""
        return self._module(self.dir / "arch" / f"{name}.py", f"arch/{name}")

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
