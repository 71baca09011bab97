#!/usr/bin/env python3
"""Find a configuration's knee once, on the chip: serve its traffic mix at
a list of LS arrival rates in one process (one build, one warm-up) and
write, per rate, every LS request's time to first token and mean gap
between tokens, the end-to-end numbers a cell reports (the SLO share under
the configuration's LS limits), the LS requests waiting for a slot at the
end of each tenth of the window (a queue that grows marks a rate past the
knee) and how late the generator ran. The benchmark's cells then offer
load at fixed rates (four fifths of the knee); this tool is how those
rates and the LS limits were chosen, not part of a cell's run.

    python3 bench/sweep.py --config solo-qwen3-1.7b --traffic chat-solo \\
        --rates 0.25,1,2,4 --seconds 20 --seed 1 --out chiprun_out/solo.json
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--drain", type=float, default=None,
                    help="cap on the time the window's requests may take "
                    "to finish after it (default: a cell's cap)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from benchkit import cell, spec, stats, traffic
    dev = cell.device_info(1, True)
    cell.use_checkout_cache()
    if args.drain is not None:
        cell.DRAIN_CAP_S = args.drain
    sp = spec.Spec()
    conf = sp.config(args.config)
    mix = sp.traffic(args.traffic)
    models, tenants, limits = cell._tenants(conf)
    eng = cell.build(conf, args.seed, models)
    calls = []
    cell.instrument(eng, calls)
    rates = [float(r) for r in args.rates.split(",")]
    streams0 = traffic.streams(sp, mix, args.seed, args.seconds, 0.0)
    cell.warm_up(eng, streams0, tenants)
    out = {"device": dev, "config": args.config, "traffic": args.traffic,
           "seconds": args.seconds, "setup_s": time.perf_counter() - T_START,
           "plan_sm_be": eng.sm_be, "limits": limits["LS"], "rates": []}
    print(f"info: setup {out['setup_s']:.1f} s; plan sm_be {eng.sm_be}",
          file=sys.stderr, flush=True)
    for rate in rates:
        m = copy.deepcopy(mix)
        m["ls"]["arrivals"]["rate_per_s"] = rate
        streams = traffic.streams(sp, m, args.seed, args.seconds,
                                  cell.TAIL_S)
        rec = cell.RunRecord("sweep", args.seconds, models, tenants, limits)
        calls.clear()
        cell.serve_window(eng, rec, streams, args.seconds, calls)
        for s in rec.sent:
            traffic.detach(s)
        ls = rec.window_ls()
        e2e = cell.end_to_end(rec, 0.0)
        # LS requests waiting for a slot at the end of each tenth of the
        # window
        tenths = [([q for t, q in rec.ls_queue if t <= args.seconds * k
                    / 10] or [0])[-1] for k in range(1, 11)]
        row = {"rate": rate, "n_ls": len(ls),
               "drain_s": rec.t_stop - rec.t1,
               "ttft_ms": [cell.ttft_s(s, rec.t_stop) * 1e3 for s in ls],
               "mean_tbt_ms": [float(np.mean(s.gaps)) * 1e3 if s.gaps
                               else None for s in ls],
               "prompt": [s.prompt_len for s in ls],
               "out": [s.max_new for s in ls],
               "finished": [s.finished for s in ls],
               "due": [s.due for s in ls],
               "gen_lag_p99_ms": (stats.percentile(
                   [s.t_submit - s.t_due for s in ls], 99) or 0) * 1e3,
               "tbt_p50_ms": (stats.percentile(rec.ls_gaps, 50) or 0) * 1e3,
               "ttft_p50_ms": (stats.percentile(
                   [cell.ttft_s(s, rec.t_stop) for s in ls], 50) or 0) * 1e3,
               **{k: e2e.get(k) for k in ("ls_ttft_p90_ms", "ls_tbt_p99_ms",
                                          "ls_slo_pct", "be_tok_per_s")},
               "ls_queue_by_tenth": tenths,
               "ls_queue_at_close": rec.ls_queue_at_close,
               "calls": {k: sum(1 for c in calls if c.t < rec.t1 and
                                f"{c.tenant}/{c.kind}{c.sq}" == k)
                         for k in sorted({f"{c.tenant}/{c.kind}{c.sq}"
                                          for c in calls})}}
        out["rates"].append(row)
        print(f"rate {rate}: n {row['n_ls']} ttft p50/p90 "
              f"{row['ttft_p50_ms']:.0f}/{row['ls_ttft_p90_ms'] or 0:.0f} "
              f"ms, tbt p50/p99 {row['tbt_p50_ms']:.1f}/"
              f"{row['ls_tbt_p99_ms'] or 0:.1f} ms, slo "
              f"{row['ls_slo_pct'] or 0:.1f}%, be "
              f"{row['be_tok_per_s'] or 0:.1f} tok/s, LS queue by tenth "
              f"{tenths}, drain {row['drain_s']:.1f} s",
              file=sys.stderr, flush=True)
        for rt in eng.tenants.values():      # start the next rate empty
            rt.queue.clear()
        eng.run_until_idle()
    out["memory_peak_bytes"] = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(json.dumps({k: v for k, v in out.items() if k != "rates"}))


if __name__ == "__main__":
    main()
