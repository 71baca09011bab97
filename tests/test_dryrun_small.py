"""Dry-run machinery on a small fake-device mesh (subprocess so the forced
device count never leaks into other tests): lower+compile smoke-scale cells
on (data=2, model=4) and (pod=2, data=2, model=2), parse collectives."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.configs import smoke_config
from repro.configs.base import ShapeConfig
from repro.launch.dryrun import lower_cell, tune_config
from repro.launch.hlo_metrics import compiled_metrics
from repro.launch.mesh import make_mesh

out = {}
for mesh_name, dims, axes in [("single", (2, 4), ("data", "model")),
                              ("multi", (2, 2, 2), ("pod", "data", "model"))]:
    mesh = make_mesh(dims, axes)
    for arch, shape in [("qwen3-1.7b", ShapeConfig("t", 64, 8, "train")),
                        ("deepseek-v2-236b", ShapeConfig("d", 64, 8, "decode")),
                        ("zamba2-1.2b", ShapeConfig("p", 64, 8, "prefill"))]:
        cfg = tune_config(smoke_config(arch), {"train": "train",
                                               "decode": "decode",
                                               "prefill": "prefill"}[shape.kind])
        mode = shape.kind
        compiled = lower_cell(cfg, shape, mesh, mode)
        m = compiled_metrics(compiled, mesh.size)
        out[f"{mesh_name}/{arch}/{mode}"] = {
            "flops": m["flops"],
            "colls": sum(m["collectives"]["counts"].values())}
print(json.dumps(out))
"""


@pytest.mark.slow
def test_small_mesh_dryrun_all_modes():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(out) == 6
    for k, v in out.items():
        assert v["flops"] > 0, k
        # the multi-pod mesh must actually communicate
    assert any(v["colls"] > 0 for k, v in out.items() if k.startswith("multi"))


def test_launch_imports_touch_no_device_or_flags():
    """Importing the dry-run and spec modules starts no backend and leaves
    ``XLA_FLAGS`` as the caller set it (fresh interpreter: the test process
    has long since started its backend)."""
    script = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_dump_to=/dev/null'\n"
        "import repro.launch.dryrun, repro.launch.specs, "
        "repro.launch.compile_cache\n"
        "from jax._src import xla_bridge\n"
        "assert os.environ['XLA_FLAGS'] == '--xla_dump_to=/dev/null'\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
