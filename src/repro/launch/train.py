"""Training launcher.

    python -m repro.launch.train --arch qwen3-1.7b --smoke --steps 20
    python -m repro.launch.train --arch nemotron-4-15b --mesh 2,4 --steps 2

--smoke uses the reduced config (CPU-runnable); otherwise the full config is
launched on the requested mesh (on real TPU hosts; on this CPU container use
--devices to fake a small mesh). Auto-resumes from --ckpt-dir.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--mesh", default=None,
                    help="comma dims, e.g. 2,4 = (data=2, model=4)")
    ap.add_argument("--devices", type=int, default=0,
                    help="fake host devices (CPU testing only)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    from .compile_cache import use_compile_cache
    use_compile_cache()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    from ..configs import get_config, smoke_config
    from ..train import AdamWConfig, DataConfig, Trainer, TrainerConfig
    from .mesh import make_mesh

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        axes = ("data", "model")[:len(dims)] if len(dims) <= 2 else \
            ("pod", "data", "model")
        mesh = make_mesh(dims, axes)

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.batch, mode="pattern")
    tr = Trainer(cfg, dc,
                 AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                             total_steps=args.steps),
                 TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                               compress_grads=args.compress_grads),
                 mesh=mesh)
    hist = tr.run()
    for h in hist[:: max(1, len(hist) // 10)]:
        print(f"step {h['step']:5d}  loss {h['loss']:.4f}  {h['time_s']*1e3:.0f} ms")
    print(f"final loss {hist[-1]['loss']:.4f}; "
          f"stragglers flagged: {len(tr.straggler_events)}")


if __name__ == "__main__":
    main()
