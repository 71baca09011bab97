DOC = """Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes (16x16 single-pod, 2x16x16 multi-pod), record
memory/cost/collective metrics, and lower small unrolled probes to recover
per-layer metrics that XLA's scan-counts-body-once cost analysis hides.

Usage:
    python -m repro.launch.dryrun                      # all cells, both meshes
    python -m repro.launch.dryrun --arch qwen3-1.7b --shape decode_32k
    python -m repro.launch.dryrun --mesh multi --force
Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>.json  (resumable)
"""

import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, dryrun_cells, get_config
from ..configs.base import ModelConfig, ShapeConfig
from ..dist.sharding import use_mesh
from ..train.optimizer import AdamWConfig
from .hlo_metrics import compiled_metrics
from .mesh import make_production_mesh
from .specs import abstract_state, input_specs, make_steps

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")


def cell_mode(shape: ShapeConfig) -> str:
    return {"train": "train", "prefill": "prefill", "decode": "decode"}[
        shape.kind]


def tune_config(cfg: ModelConfig, mode: str, *, probe: bool = False,
                overrides: dict | None = None) -> ModelConfig:
    import dataclasses as _dc
    from ..core.costmodel import param_count
    big = param_count(cfg) > 2e10
    kw = dict(scan_layers=not probe)
    if mode == "train":
        kw.update(param_dtype="float32", activation_dtype="bfloat16",
                  remat="full" if big else "dots")
    else:
        kw.update(param_dtype="bfloat16", activation_dtype="bfloat16",
                  remat="none")
    if overrides:
        ov = dict(overrides)
        if "capacity_factor" in ov and cfg.moe:
            kw["moe"] = _dc.replace(cfg.moe,
                                    capacity_factor=float(ov.pop("capacity_factor")))
        kw.update(ov)
    return cfg.replace(**kw)


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, mode: str):
    """Returns the compiled executable for the cell's step function."""
    train_step, prefill_step, serve_step = make_steps(cfg, AdamWConfig())
    with use_mesh(mesh):
        if mode == "train":
            params, pspecs, opt, ospecs, err = abstract_state(cfg, mesh)
            batch, bshards = input_specs(cfg, shape, mesh, "train")
            fn = jax.jit(train_step,
                         in_shardings=(pspecs, ospecs, pspecs, bshards),
                         out_shardings=(pspecs, ospecs, pspecs, None),
                         donate_argnums=(0, 1, 2))
            lowered = fn.lower(params, opt, err, batch)
        elif mode == "prefill":
            params, pspecs, *_ = abstract_state(cfg, mesh)
            batch, bshards = input_specs(cfg, shape, mesh, "prefill")
            fn = jax.jit(prefill_step, in_shardings=(pspecs, bshards))
            lowered = fn.lower(params, batch)
        else:
            params, pspecs, *_ = abstract_state(cfg, mesh)
            (token, cache, extras), (tsh, csh, esh) = input_specs(
                cfg, shape, mesh, "decode")
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            fn = jax.jit(serve_step,
                         in_shardings=(pspecs, tsh, csh,
                                       NamedSharding(mesh, P()), esh),
                         out_shardings=(None, csh),
                         donate_argnums=(2,))
            lowered = fn.lower(params, token, cache, pos, extras)
        compiled = lowered.compile()
    return compiled


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             probes: bool = True, out_dir: str = ART_DIR,
             force: bool = False, overrides: dict | None = None,
             tag: str = "") -> dict:
    os.makedirs(out_dir, exist_ok=True)
    fname = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}{tag}.json")
    if os.path.exists(fname) and not force:
        with open(fname) as f:
            return json.load(f)
    shape = SHAPES[shape_name]
    base = get_config(arch)
    mode = cell_mode(shape)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.size
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "mode": mode, "n_devices": n_dev, "ok": False, "tag": tag}
    t0 = time.time()
    try:
        cfg = tune_config(base, mode, overrides=overrides)
        compiled = lower_cell(cfg, shape, mesh, mode)
        rec["main"] = compiled_metrics(compiled, n_dev)
        del compiled
        if probes and mesh_kind == "single":
            period = base.probe_period
            metrics = []
            for n in (0, period):
                pcfg = tune_config(base.with_layers(n), mode, probe=True,
                                   overrides=overrides)
                c = lower_cell(pcfg, shape, mesh, mode)
                metrics.append(compiled_metrics(c, n_dev))
                del c
            rec["probe0"], rec["probe1"] = metrics
            n_periods = (base.num_layers - base.n_prefix) / period
            rec["n_periods"] = n_periods
            rec["scaled"] = _scale(metrics[0], metrics[1], n_periods)
            corr = _ssm_scan_correction(base, shape, mode, n_dev)
            if corr:
                rec["scaled"]["flops"] += corr["flops"]
                rec["scaled"]["bytes_accessed"] += corr["bytes"]
                rec["ssm_correction"] = corr
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["elapsed_s"] = round(time.time() - t0, 1)
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def _ssm_scan_correction(cfg: ModelConfig, shape: ShapeConfig, mode: str,
                         n_dev: int):
    """When the SSM chunk scan exceeds the probe unroll cap, its body is
    counted once by cost analysis; add the analytic residual (costmodel) for
    the remaining trip count. Mixed measured+analytic accounting, recorded in
    the artifact."""
    from ..core.costmodel import ssm_costs
    from ..models.ssm import MAX_UNROLL_CHUNKS
    if not cfg.ssm or mode == "decode":
        return None
    n_chunks = shape.seq_len // cfg.ssm.chunk
    if n_chunks <= MAX_UNROLL_CHUNKS:
        return None
    kind = "rwkv" if cfg.ssm.kind == "rwkv6" else "mamba"
    ops = ssm_costs(cfg, shape.global_batch, shape.seq_len, kind)
    scan_ops = [o for o in ops if o.name.endswith("_scan")]
    n_ssm_layers = sum(1 for k in cfg.pattern
                       if k.replace("_shared", "") in ("rwkv", "mamba"))
    frac = (n_chunks - 1) / n_chunks
    mult = 3.0 if mode == "train" else 1.0
    return {
        "flops": mult * frac * n_ssm_layers
        * sum(o.flops for o in scan_ops) / n_dev,
        "bytes": mult * frac * n_ssm_layers
        * sum(o.bytes for o in scan_ops) / n_dev,
        "n_chunks": n_chunks, "n_ssm_layers": n_ssm_layers,
    }


def _scale(m0: dict, m1: dict, n: float) -> dict:
    """total = probe0 + n * (probe1 - probe0), per metric."""
    out = {
        "flops": m0["flops"] + n * (m1["flops"] - m0["flops"]),
        "bytes_accessed": m0["bytes_accessed"]
        + n * (m1["bytes_accessed"] - m0["bytes_accessed"]),
    }
    w0 = m0["collectives"]["total_wire_bytes"]
    w1 = m1["collectives"]["total_wire_bytes"]
    out["collective_wire_bytes"] = w0 + n * (w1 - w0)
    per_kind = {}
    kinds = set(m0["collectives"]["wire_bytes"]) | \
        set(m1["collectives"]["wire_bytes"])
    for k in kinds:
        a = m0["collectives"]["wire_bytes"].get(k, 0.0)
        b = m1["collectives"]["wire_bytes"].get(k, 0.0)
        per_kind[k] = a + n * (b - a)
    out["collective_wire_bytes_by_kind"] = per_kind
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default=ART_DIR)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--tag", default="", help="artifact suffix (perf iters)")
    ap.add_argument("--attn-fallback", default="headdim",
                    choices=["headdim", "replicate"])
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override k=v (e.g. remat=dots)")
    args = ap.parse_args()
    # the production meshes need 512 host devices; set before the backend
    # starts, keeping any flags the caller already passed
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")).strip()

    from ..dist.sharding import set_attn_fallback
    set_attn_fallback(args.attn_fallback)
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = {"true": True, "false": False}.get(
            v.lower(), int(v) if v.isdigit() else v)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    results = []
    for cfg, shape, ok, why in dryrun_cells():
        if args.arch and cfg.name != args.arch:
            continue
        if args.shape and shape.name != args.shape:
            continue
        if not ok:
            print(f"SKIP  {cfg.name:24s} {shape.name:12s} -- {why}")
            continue
        for mk in meshes:
            t0 = time.time()
            rec = run_cell(cfg.name, shape.name, mk,
                           probes=not args.no_probes, out_dir=args.out,
                           force=args.force, overrides=overrides or None,
                           tag=args.tag)
            status = "ok" if rec["ok"] else "FAIL"
            mem = rec.get("main", {}).get("memory", {})
            print(f"{status:5s} {cfg.name:24s} {shape.name:12s} {mk:6s} "
                  f"args={mem.get('argument_bytes', 0)/2**30:8.2f}GiB/dev "
                  f"temp={mem.get('temp_bytes', 0)/2**30:7.2f}GiB/dev "
                  f"({time.time()-t0:6.1f}s)", flush=True)
            if not rec["ok"]:
                print("      " + rec["error"].splitlines()[0][:160], flush=True)
            results.append(rec)
    n_ok = sum(r["ok"] for r in results)
    print(f"\n{n_ok}/{len(results)} cells compiled")


if __name__ == "__main__":
    main()
