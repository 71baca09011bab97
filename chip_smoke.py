#!/usr/bin/env python3
"""Bring-up check on one TPU chip: serve an LS and a BE tenant at their
published widths through the serving engine's normal construction path.

    python chip_smoke.py

An LS ``qwen3-1.7b`` and a BE ``stablelm-1.6b`` share one ServingEngine,
built by ``repro.launch.serve.build_engine`` exactly as
``python -m repro.launch.serve`` builds it: bfloat16 weights from each
tenant's seeded initialisation, paged KV (4 slots of 2048 tokens per
tenant), chunked prefill through the Pallas kernels (``--use-flash``), and a
grid-searched ResourcePlan that gives BE a quantum share (sm_be > 0). Each
tenant serves 4 prompts of 513 tokens and 32 new tokens.

It checks, and raises on any failure:
  * a TPU is present, and the kernels are compiled for it, not interpreted:
    each tenant's compiled decode and chunk steps contain ``tpu_custom_call``;
  * every request finishes with its 32 tokens, and every logits row behind
    an emitted token is finite;
  * the first-token logits of each request agree with the same engine built
    with ``--use-flash`` off (the jnp attention path) on the same
    parameters, within ``LOGIT_REL_TOL``.

Lines starting ``info:`` (compile counts and seconds, wall time, peak device
memory) are informational, not benchmark numbers. The last line of standard
output is one JSON object naming the device. With no TPU the script exits
non-zero and prints no result; there is no CPU fallback.
"""
import collections
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SERVE_ARGV = ["--ls", "qwen3-1.7b", "--be", "stablelm-1.6b",
              "--paged", "--page-size", "128", "--max-seq", "2048",
              "--slots", "4", "--chunk-size", "256",
              "--grid-search", "--gpu", "tpu-v5e",
              "--requests", "4", "--prompt-len", "513"]
MAX_NEW = 32
CHUNK_LENGTHS = (256, 1)   # a 513-token prompt runs as 256 + 256 + 1
# Relative L2 distance allowed between the first-token logits of the kernel
# path and of the jnp path. Both compute in bfloat16 with float32
# accumulation; they differ in where attention probabilities are rounded to
# bfloat16 (unit roundoff 2**-8 ~ 0.004). That drift compounds over 24-28
# layers to roughly 0.01-0.02; 0.05 leaves room for it, while a wrong mask,
# a wrong page or a dropped block moves the logits by tens of percent.
LOGIT_REL_TOL = 0.05


def _require_tpu(jax):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{dev.platform!r}); this check runs only on a TPU chip")
    return dev


COMPILE = "/jax/core/compile/backend_compile_duration"


def _compile_counters(jax):
    """Counts of JAX monitoring events and of timed events, and the
    seconds of the timed ones."""
    events = collections.Counter()
    seconds = collections.Counter()

    def timed(event, duration, **kw):
        events[event] += 1
        seconds[event] += duration

    jax.monitoring.register_event_listener(
        lambda event, **kw: events.update([event]))
    jax.monitoring.register_event_duration_secs_listener(timed)
    return events, seconds


def _report_compiles(tag, events, seconds):
    print(f"info: {tag}: backend compiles {events[COMPILE]} "
          f"({seconds[COMPILE]:.1f} s), persistent-cache hits "
          f"{events['/jax/compilation_cache/cache_hits']}, misses "
          f"{events['/jax/compilation_cache/cache_misses']}", flush=True)


def _serve(serve, argv, params=None):
    """Build the engine through the launcher, serve its requests, and
    return (engine, requests, first-token logits by rid, finite flags)."""
    args = serve.build_parser().parse_args(argv)
    eng = serve.build_engine(args, params=params)
    first, finite = {}, []

    def observe(rt, req, row):
        if not req.output:
            first[req.rid] = row
        finite.append(row)

    eng.logits_hook = observe
    reqs = serve.submit_requests(eng, args)
    serve.run(eng, args)
    return eng, reqs, first, finite


def _kernels_in_steps(jnp, eng):
    """Compile each tenant's decode and chunk steps for the shapes served
    and return the ones whose program lacks a Pallas kernel."""
    missing = []
    for name, rt in eng.tenants.items():
        pt = rt.kv.device_page_table()
        pos = jnp.zeros(rt.n_slots, jnp.int32)
        steps = [("decode", rt.decode_fn, 1)]
        steps += [(f"chunk{n}", rt.chunk_fn, n) for n in CHUNK_LENGTHS]
        for label, fn, n in steps:
            toks = jnp.zeros((rt.n_slots, n), jnp.int32)
            text = fn.lower(rt.params, toks, rt.cache, pos, pt) \
                .compile().as_text()
            if "tpu_custom_call" not in text:
                missing.append(f"{name}/{label}")
    return missing


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = _require_tpu(jax)
    from repro.kernels.pallas_compat import interpret_default
    from repro.launch import serve
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    print(f"info: device {dev.device_kind} x{jax.device_count()}; "
          f"compile cache {cache_dir}", flush=True)
    events, seconds = _compile_counters(jax)
    if interpret_default():
        raise RuntimeError("Pallas kernels would run in interpret mode")

    t0 = time.perf_counter()
    eng, reqs, first, finite = _serve(
        serve, SERVE_ARGV + ["--use-flash", "--max-new", str(MAX_NEW)])
    wall = time.perf_counter() - t0
    print(f"info: plan sm_be={eng.sm_be:.2f}; kernel-path serve wall "
          f"{wall:.1f} s including compiles", flush=True)
    _report_compiles("kernel-path serve", events, seconds)
    if not eng.sm_be > 0:
        raise RuntimeError(f"plan gives BE no quantum share: {eng.sm_be}")
    if not all(rt.flash for rt in eng.tenants.values()):
        raise RuntimeError("a tenant left the kernel path mid-run")

    short = [(r.tenant, r.rid, len(r.output)) for r in reqs
             if r.failed or len(r.output) != MAX_NEW]
    if short:
        raise RuntimeError(f"requests without {MAX_NEW} tokens: {short}")
    if len(finite) != MAX_NEW * len(reqs):
        raise RuntimeError(f"observed {len(finite)} logits rows, expected "
                           f"{MAX_NEW * len(reqs)}")
    if not bool(jnp.all(jnp.stack([jnp.isfinite(r).all() for r in finite]))):
        raise RuntimeError("non-finite logits on the kernel path")

    missing = _kernels_in_steps(jnp, eng)
    if missing:
        raise RuntimeError(f"no tpu_custom_call in compiled steps: {missing}")
    print("info: tpu_custom_call in every tenant's decode and chunk "
          f"{'/'.join(map(str, CHUNK_LENGTHS))} steps", flush=True)

    params = {name: rt.params for name, rt in eng.tenants.items()}
    kernel_first = {rid: np.asarray(row, np.float32)
                    for rid, row in first.items()}
    tokens = {r.rid: r.tokens for r in reqs}
    del eng, reqs, first, finite
    gc.collect()

    _, ref_reqs, ref_first, _ = _serve(
        serve, SERVE_ARGV + ["--max-new", "1"], params=params)
    _report_compiles("after jnp-path reference", events, seconds)
    worst = 0.0
    for r in ref_reqs:
        if not np.array_equal(r.tokens, tokens[r.rid]):
            raise RuntimeError(f"request {r.rid}: prompts differ between runs")
        a = kernel_first[r.rid]
        b = np.asarray(ref_first[r.rid], np.float32)
        rel = float(np.linalg.norm(a - b) / np.linalg.norm(b))
        print(f"info: {r.tenant} rid {r.rid}: first-token logits rel L2 "
              f"{rel:.3e}, argmax {int(a.argmax())} vs {int(b.argmax())}",
              flush=True)
        worst = max(worst, rel)
    if not worst <= LOGIT_REL_TOL:
        raise RuntimeError(f"kernel and jnp first-token logits differ by "
                           f"rel L2 {worst:.3e} > {LOGIT_REL_TOL}")
    print(f"info: worst first-token logits rel L2 {worst:.3e} "
          f"(limit {LOGIT_REL_TOL})", flush=True)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"info: peak device memory "
              f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB", flush=True)
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
