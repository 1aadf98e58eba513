"""Find a serving cell's knee once, on the chip: the same traffic at a ladder
of fixed rates in one process, each for a short window, with the latency from
due times and whether the backlog grew over the window.

    python benchmarks/tools/sweep.py --workload kaggle_serve_ranking \
        --rates 200,400,600 --seconds 8

It is not part of a benchmark run. Prints one JSON object a rate.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=2_100_000_000)
    args = ap.parse_args(argv)

    from benchmarks.families import system
    from benchmarks.lib import manifest
    cell = manifest.Cell(args.workload)
    system.ensure_compile_cache()
    import numpy as np
    from benchmarks.lib import serve, traffic

    fam, cfg, tr = cell.family, cell.config, cell.traffic
    built = fam.build(cfg, tr, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    mean_size = float(np.mean(traffic.request_sizes(tr, 1000)))
    # admission as the cell sets it, sized for the highest rate swept
    params = dict(tr["serve"], max_queue=max(int(max(rates) * mean_size),
                                             int(tr["serve"]["rungs"][-1])))
    rt = fam.serving_runtime(built, params)
    for k, rate in enumerate(rates):
        schedule = fam.serve_schedule(cfg, dict(tr, rate_per_s=rate),
                                      args.seed + k, args.seconds)
        if k == 0:
            rt.warmup(schedule.request(0))
        before = rt.stats()["flushes"]
        results, t_sub, t_last = serve.open_loop(rt, schedule,
                                                 fam.requests_of(schedule))
        lat = serve.latencies_ms(schedule.due_s, t_sub, results)
        q = len(lat) // 4
        sizes = np.diff(schedule.offsets)
        served = [i for i, r in results.items() if not serve.failed(r)]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "failed": len(lat) - len(served),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "first_quarter_mean_ms": float(lat[:q].mean()),
            "last_quarter_mean_ms": float(lat[-q:].mean()),
            "drain_s": float(t_last - schedule.due_s[-1]),
            "offered_samples_per_s": float(sizes.sum() / args.seconds),
            "served_samples_per_s": float(sizes[served].sum() / t_last),
            "flushes": rt.stats()["flushes"] - before,
            "gen_late_p99_ms": float(np.percentile(
                (t_sub - schedule.due_s) * 1e3, 99)),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
