#!/usr/bin/env python
"""Fixed-QPS Zipfian serving benchmark on the world-8 virtual CPU mesh.

The bench ``serving`` section's body, run in a CHILD process pinned to
the 8-virtual-device CPU mesh while the bench process holds the chip
(like ``schedule`` / ``phase_profile`` / ``pipeline``):

* builds an 8-table DLRM-shaped model on a world-8 CPU mesh and a
  :class:`~distributed_embeddings_tpu.parallel.serving.ServingRuntime`
  around the donated-input no-grad forward (padded-batch ladder warmed
  up front),
* drives a seeded Zipfian request stream (variable 1..max samples per
  request, power-law ids) at a FIXED target QPS through the shared
  :func:`~distributed_embeddings_tpu.parallel.serving.drive` loop,
* reports p50/p95/p99 latency over served requests, the shed and
  deadline-missed counts, the aggregate padding fraction, the achieved
  QPS, and the steady-state recompile count (0 required — a ladder that
  retraces per request mix poisons its own latencies),
* embeds the jax-free int8-rows-with-per-row-scales serving-table
  pricing (``analysis.plan_audit.price_int8_serving``) — the capacity
  case for the future quantized-serving PR, recorded next to the
  latencies it would improve.

``tools/compare_bench.py::check_serving`` gates the section: p95
regression beyond 10%, a nonzero recompile count, or the section
disappearing versus the baseline fails the diff.

    python tools/serve_bench.py --json -          # the bench child
    python tools/serve_bench.py --qps 100 --duration 5

Exit codes: 0 ok; 2 usable-environment failure.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

try:  # imported as tools.serve_bench (tests)
    from tools._profcommon import cpu_mesh, force_cpu  # noqa: F401
except ImportError:  # run as a script: tools/ itself is sys.path[0]
    from _profcommon import cpu_mesh, force_cpu  # noqa: F401

WORLD = 8
#: 8 tables (>= world), DLRM-ish widths — big enough that the forward
#: is a real exchange+gather program, small enough that the whole
#: ladder compiles in seconds on the CPU proxy
TABLE_SIZES = (100_000, 50_000, 50_000, 20_000, 20_000, 10_000, 10_000,
               5_000)
DIM = 32
NUMERICAL = 4


def run_qps(qps: float, duration_s: float, max_batch: int,
            max_samples: int, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_embeddings_tpu.analysis.plan_audit import (
        price_int8_serving)
    from distributed_embeddings_tpu.parallel import (
        DistributedEmbedding, ServeConfig, ServingRuntime, SparseSGD,
        init_hybrid_state)
    from distributed_embeddings_tpu.parallel import serving as sv

    mesh = cpu_mesh(WORLD)
    de = DistributedEmbedding(
        [{"input_dim": v, "output_dim": DIM} for v in TABLE_SIZES],
        world_size=WORLD)
    tx = optax.sgd(0.05)
    dense_params = {"w": jnp.asarray(
        np.random.default_rng(0).normal(
            size=(len(TABLE_SIZES) * DIM + NUMERICAL, 1)) * 0.05,
        jnp.float32)}
    state = init_hybrid_state(de, SparseSGD(), dense_params, tx,
                              jax.random.key(1), mesh=mesh)

    def pred_fn(dp, outs, batch):
        x = jnp.concatenate(list(outs) + [batch], axis=-1)
        return jax.nn.sigmoid(x @ dp["w"])[:, 0]

    cfg = ServeConfig(max_batch=max_batch)
    rt = ServingRuntime(de, pred_fn, state, mesh=mesh, config=cfg)
    tmpl_rng = np.random.default_rng(seed)
    tmpl = sv.synthetic_request(tmpl_rng, TABLE_SIZES, 2,
                                numerical=NUMERICAL)
    rt.warmup((tmpl.cats, tmpl.batch))

    rng = np.random.default_rng(seed + 1)

    def make_request(i):
        n = int(rng.integers(1, max_samples + 1))
        return sv.synthetic_request(rng, TABLE_SIZES, n,
                                    numerical=NUMERICAL)

    results = sv.drive(rt, make_request, qps, duration_s,
                       burst_positions=())
    s = rt.stats()
    served = [r for r in results if isinstance(r, sv.Served)]
    rec = {
        "world": WORLD,
        "tables": len(TABLE_SIZES),
        "dim": DIM,
        "qps_target": qps,
        "duration_s": duration_s,
        "rungs": list(rt.rungs),
        "requests_submitted": s["served"] + s["shed"] + s["expired"],
        "served": s["served"],
        "served_samples": s["served_samples"],
        "qps_achieved": round(len(served) / duration_s, 1),
        "latency_p50_ms": round(s["latency_p50_ms"] or 0.0, 3),
        "latency_p95_ms": round(s["latency_p95_ms"] or 0.0, 3),
        "latency_p99_ms": round(s["latency_p99_ms"] or 0.0, 3),
        "shed": s["shed"],
        "shed_frac": round(s["shed_frac_of_submitted"], 4),
        "deadline_missed": s["deadline_missed"],
        "pad_fraction": round(s["pad_fraction"], 4),
        "queue_depth_p95": round(s["queue_depth_p95"], 1),
        "flushes": s["flushes"],
        "warmup_compiles": s["warmup_compiles"],
        "steady_state_recompiles": s["steady_state_recompiles"],
        # pricing only: the int8 serving-table variant this latency
        # record would ride (future quantized-serving PR; also feeds
        # the ROADMAP-1 hot-row cache sizing)
        "int8_serving": price_int8_serving(
            de, rt.rungs[-1], param_dtype="float32",
            label=f"serving/world{WORLD}"),
    }
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--qps", type=float, default=150.0,
                    help="target request arrival rate (default 150)")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="seconds of load (default 10)")
    ap.add_argument("--max-batch", type=int, default=64,
                    help="largest padded-batch rung (default 64)")
    ap.add_argument("--max-samples", type=int, default=8,
                    help="largest request size in samples (default 8)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="3s at 60 QPS (the DETPU_BENCH_SMOKE shape)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the record as JSON (- for stdout)")
    args = ap.parse_args(argv)

    force_cpu(WORLD)
    sys.path.insert(0, REPO)
    from distributed_embeddings_tpu.utils import runtime

    runtime.ensure_compile_cache()
    if args.smoke:
        args.qps, args.duration = 60.0, 3.0
    try:
        rec = run_qps(args.qps, args.duration, args.max_batch,
                      args.max_samples, args.seed)
    except Exception as e:  # noqa: BLE001 - child tool: readable env-fail
        print(f"serve_bench: errored: {e}", file=sys.stderr)
        return 2
    print(f"serve_bench: world={rec['world']} qps={rec['qps_target']:.0f} "
          f"(achieved {rec['qps_achieved']:.0f}) p50/p95/p99 = "
          f"{rec['latency_p50_ms']:.1f}/{rec['latency_p95_ms']:.1f}/"
          f"{rec['latency_p99_ms']:.1f} ms, shed={rec['shed']}, "
          f"pad={rec['pad_fraction']:.2f}, recompiles="
          f"{rec['steady_state_recompiles']}")
    if args.json:
        payload = json.dumps(rec, indent=2)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w", encoding="utf-8") as fh:
                fh.write(payload + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
