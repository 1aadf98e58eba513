#!/usr/bin/env python
"""Pipelined-vs-serialized hybrid-step A/B on the virtual-device CPU mesh.

The pipelined step (``parallel/schedule.py::pipelined_schedule``) exists
to hide the all-to-all exchanges under dense compute — a property that
only *exists* at world > 1 (the single-chip headline step has no
exchange to hide, so bench.py's world-1 sections are structurally unable
to show it). This tool is the bench's ``pipeline`` section body, run in
a CHILD process pinned to the 8-virtual-device CPU mesh (the bench
process holds the chip):

* builds the capped Criteo-Kaggle DLRM shapes on a world-8 CPU mesh,
* times the SAME model/config under the serialized baseline schedule and
  under ``pipelined_schedule(K)`` (``DETPU_MICROBATCH_BENCH``, default
  2),
* rides the steady-state recompile gate (a pipelined step that retraces
  per step poisons its own numbers exactly like any other section),
* emits one JSON record: both ms/step figures, the speedup fraction, and
  the recompile count.

Honesty note: on the CPU mesh the exchange is a shared-memory copy
priced at ~nothing and the CPU thunk scheduler does not overlap across
chains, so the wall-clock delta is noise-level and says nothing about a
chip; the certified wins are the schedule auditor's modeled fraction
(0.99 → 0.00) and critical path. Whether the pipelined step wins on
four real chips has not been measured (ROADMAP Design item 3).

    python tools/pipeline_bench.py --json -          # the bench child
    python tools/pipeline_bench.py --iters 4 --batch 4096

Exit codes: 0 ok; 2 usable-environment failure.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

try:  # imported as tools.pipeline_bench (tests)
    from tools._profcommon import (CAP_SIZES, cpu_mesh,  # noqa: F401
                                   force_cpu)
except ImportError:  # run as a script: tools/ itself is sys.path[0]
    from _profcommon import CAP_SIZES, cpu_mesh, force_cpu  # noqa: F401

WORLD = 8
#: vocab cap of the A/B tables — the capped Criteo-Kaggle vector shrunk
#: so a world-8 CPU host holds both variants' slabs comfortably; the
#: shapes stay 26-table/dim-128 DLRM-like so the exchange layout (and
#: therefore what the pipeline hides) matches the headline's structure
TABLE_CAP = 200_000


def run_ab(batch: int, iters: int, k: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_embeddings_tpu.models.dlrm import (DLRMConfig,
                                                        DLRMDense,
                                                        bce_with_logits)
    from distributed_embeddings_tpu.parallel import (
        DistributedEmbedding, SparseSGD, init_hybrid_state,
        make_hybrid_train_step)
    from distributed_embeddings_tpu.parallel.schedule import (
        pipelined_schedule)
    from distributed_embeddings_tpu.utils import obs, power_law_ids

    sizes = [min(s, TABLE_CAP) for s in CAP_SIZES]
    mesh = cpu_mesh(WORLD)
    cfg = DLRMConfig(table_sizes=sizes, embedding_dim=128,
                     num_numerical_features=13,
                     bottom_mlp_dims=(512, 256, 128),
                     top_mlp_dims=(1024, 1024, 512, 256, 1),
                     compute_dtype=jnp.bfloat16)
    obs.install_compile_listener()

    def time_variant(schedule):
        de = DistributedEmbedding(cfg.embedding_configs(),
                                  world_size=WORLD,
                                  compute_dtype=jnp.bfloat16,
                                  schedule=schedule)
        dense = DLRMDense(cfg)
        emb_opt = SparseSGD()
        tx = optax.sgd(0.005)
        rng = np.random.default_rng(0)
        cats = [jnp.asarray(power_law_ids(rng, s, (batch,)), jnp.int32)
                for s in sizes]
        num = jnp.asarray(rng.normal(size=(batch, 13)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 2, size=(batch, 1)),
                             jnp.float32)
        dense_params = dense.init(
            jax.random.key(0), num[:2],
            [jnp.zeros((2, 128), jnp.float32) for _ in sizes])

        def loss_fn(dp, emb_outs, b):
            n, y = b
            return bce_with_logits(dense.apply(dp, n, emb_outs), y)

        state = init_hybrid_state(de, emb_opt, dense_params, tx,
                                  jax.random.key(1), mesh=mesh,
                                  dtype=jnp.bfloat16)
        step = make_hybrid_train_step(de, loss_fn, tx, emb_opt, mesh=mesh,
                                      lr_schedule=0.005,
                                      with_metrics=False, nan_guard=False,
                                      telemetry=False)
        loss = None
        for _ in range(2):
            loss, state = step(state, cats, (num, labels))
        float(jnp.asarray(loss).reshape(-1)[-1])
        compiles0 = obs.counters().get("recompiles", 0)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss, state = step(state, cats, (num, labels))
        float(jnp.asarray(loss).reshape(-1)[-1])
        dt = (time.perf_counter() - t0) / iters
        recompiles = obs.counters().get("recompiles", 0) - compiles0
        del state
        return dt, recompiles

    ser_s, ser_rc = time_variant(None)
    pip_s, pip_rc = time_variant(pipelined_schedule(k))
    return {
        "world": WORLD,
        "batch": batch,
        "iters": iters,
        "microbatches": k,
        "table_cap": TABLE_CAP,
        "serialized_ms_per_step": round(ser_s * 1e3, 3),
        "pipelined_ms_per_step": round(pip_s * 1e3, 3),
        "serialized_samples_per_sec": round(batch / ser_s, 1),
        "pipeline_samples_per_sec": round(batch / pip_s, 1),
        "pipeline_speedup_frac": round(ser_s / pip_s - 1.0, 4),
        "steady_state_recompiles": ser_rc + pip_rc,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=8192,
                    help="global batch of the A/B (default 8192)")
    ap.add_argument("--iters", type=int, default=8,
                    help="timed steps per variant (default 8)")
    ap.add_argument("--microbatches", type=int, default=None,
                    help="pipeline K (default DETPU_MICROBATCH_BENCH)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the record as JSON (- for stdout)")
    args = ap.parse_args(argv)

    force_cpu(WORLD)
    sys.path.insert(0, REPO)
    from distributed_embeddings_tpu.utils import runtime

    runtime.ensure_compile_cache()
    from distributed_embeddings_tpu.utils import envvars

    k = (args.microbatches if args.microbatches is not None
         else envvars.get_int("DETPU_MICROBATCH_BENCH"))
    if k < 1:
        print(f"pipeline_bench: microbatches must be >= 1, got {k}",
              file=sys.stderr)
        return 2
    try:
        rec = run_ab(args.batch, args.iters, k)
    except Exception as e:  # noqa: BLE001 - child tool: readable env-fail
        print(f"pipeline_bench: errored: {e}", file=sys.stderr)
        return 2
    print(f"pipeline_bench: world={rec['world']} K={k} "
          f"serialized {rec['serialized_ms_per_step']:.1f} ms/step vs "
          f"pipelined {rec['pipelined_ms_per_step']:.1f} ms/step "
          f"({rec['pipeline_speedup_frac']:+.1%}); recompiles="
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
