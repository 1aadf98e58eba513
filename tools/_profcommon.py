"""Shared setup for the static audit tools (``tools/*_audit.py``,
``tools/phase_profile.py``) and ``chip_smoke.py``: the ``sys.path``
insert, the two published vocabulary vectors, the reference
``DistributedEmbedding`` cases (:func:`build_case`) and the CPU pinning
(:func:`force_cpu`, :func:`cpu_mesh`).

``tests/test_tree_records.py`` holds both vectors to ``table_sizes`` of
``benchmarks/configs/<name>.json``: the smoke test, the auditors and the
benchmark's cells price the same vectors.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# Criteo-Kaggle (MLPerf DLRM) vocab sizes, uncapped: 26 tables, 33.8M rows
# (chip_smoke.py caps them at 2M rows; dlrm-kaggle runs them whole)
CRITEO_KAGGLE_SIZES = [
    1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683,
    8351593, 3194, 27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18, 15,
    286181, 105, 142572,
]

# Criteo-1TB (MLPerf DLRM) vocab sizes + the reference's "+1" convention
# (its examples/dlrm/main.py loads model_size.json and adds 1): 26 tables,
# ~187.8M rows total — the real shapes behind the ≥2M samples/s v5e-16
# north star. Shared here so the capacity auditor and the
# dress-rehearsal tooling price the SAME vector (they used to drift).
CRITEO_1TB_SIZES = [s + 1 for s in [
    39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951,
    2953546, 403346, 10, 2208, 11938, 155, 4, 976, 14, 39979771, 25641295,
    39664984, 585935, 12972, 108, 36,
]]
# Column-slice threshold (elements) of the criteo1tb reference case: the
# five ~25-40M-row tables (3.3-5.1e9 elements at dim 128) split 4-way into
# width-32 slices, putting every per-rank apply slab under the measured
# scatter cliff at world=16 bf16 (analysis/plan_audit.py enforces this);
# the <=1.4e9-element tables stay whole.
CRITEO1TB_COL_SLICE = 1_400_000_000
CRITEO1TB_DIM = 128
CRITEO1TB_BATCH = 65536
CRITEO1TB_WORLD = 16


def build_case(name: str, world: int, batch: int):
    """One reference DistributedEmbedding configuration: ``(de, cat_inputs,
    batch_tree, dense_params, loss_fn)`` with abstract (ShapeDtypeStruct)
    inputs — the shapes the static tools audit. Shared by
    ``tools/audit_step.py`` (jaxpr-level SPMD contract) and
    ``tools/hlo_audit.py`` (optimized-HLO pass budgets) so the gates
    cannot drift apart.

    Cases: ``dense`` / ``ragged`` / ``row_sliced`` (the tier-1 shapes),
    ``pipelined`` — the dense shapes under ``pipelined_schedule(2)``,
    the K-microbatch case the schedule auditor certifies declared
    overlaps on and the phase profiler measures —
    ``bigvocab`` — vocab rows >> the id stream, so stateful sparse
    optimizers compile their sort-dedup path instead of the dense-apply
    regime (the configuration the dedup pass budget is pinned on) —
    ``streaming`` — the dense shapes plus one dynamic-vocab table
    (``"streaming"`` config entry), so the static gates can audit the
    slot-map remap/commit phases too (build its step with
    ``dynamic=StreamingConfig(...)``) — and
    ``criteo1tb`` — the REAL 26-table Criteo-1TB vocab vector at dim 128
    with the reference column-slice threshold (``CRITEO1TB_COL_SLICE``),
    the shapes the plan-time capacity auditor (``tools/plan_audit.py``)
    enforces its HBM/cliff contracts at. Building it materializes
    nothing (plans are host metadata; inputs are ShapeDtypeStructs), but
    only the static tools should ask for it — ``de.init`` at these
    shapes is 48 GB of bf16.
    """
    import jax
    import jax.numpy as jnp

    from distributed_embeddings_tpu.ops.embedding_lookup import Ragged
    from distributed_embeddings_tpu.parallel import DistributedEmbedding

    def loss_fn(dp, emb_outs, b):
        n, y = b
        x = jnp.concatenate([e.reshape(e.shape[0], -1) for e in emb_outs],
                            axis=1)
        return jnp.mean((x @ dp["w"] + n @ dp["v"] - y) ** 2)

    def dense_cats(configs):
        cats = []
        for cfg in configs:
            hot = 1 if cfg["combiner"] is None else 3
            shape = (batch,) if hot == 1 else (batch, hot)
            cats.append(jax.ShapeDtypeStruct(shape, jnp.int32))
        return cats

    if name == "dense":
        configs = [{"input_dim": 20 + 6 * i, "output_dim": 4,
                    "combiner": ["sum", None, "mean"][i % 3]}
                   for i in range(10)]
        de = DistributedEmbedding(configs, world_size=world)
        cats = dense_cats(configs)
    elif name == "pipelined":
        # the dense shapes under the K=2 software-pipelined schedule
        # (parallel/schedule.py): the case the schedule auditor certifies
        # the DECLARED microbatch overlaps on, the HLO census pins the
        # per-microbatch pass budgets on, and the measured phase profile
        # confirms on the clock (ROADMAP item 2)
        from distributed_embeddings_tpu.parallel.schedule import (
            pipelined_schedule)

        configs = [{"input_dim": 20 + 6 * i, "output_dim": 4,
                    "combiner": ["sum", None, "mean"][i % 3]}
                   for i in range(10)]
        de = DistributedEmbedding(configs, world_size=world,
                                  schedule=pipelined_schedule(2))
        cats = dense_cats(configs)
    elif name == "bigvocab":
        # stream << rows: SparseAdagrad's dense_apply_ratio cost model
        # (stream * ratio > slab rows) cannot trigger, so the compiled
        # program holds the sort + segment-sum dedup passes the census
        # budgets; under SparseSGD the same shapes must compile dedup-free
        configs = [{"input_dim": 5000 + 400 * i, "output_dim": 8,
                    "combiner": ["sum", None, "mean"][i % 3]}
                   for i in range(10)]
        de = DistributedEmbedding(configs, world_size=world)
        cats = dense_cats(configs)
    elif name == "streaming":
        # the dense shapes plus ONE dynamic-vocab table: capacity slots +
        # shared bucket rows (input_dim = capacity + buckets, the
        # streaming slab contract) — the shapes the schedule auditor's
        # streaming case certifies
        configs = [{"input_dim": 20 + 6 * i, "output_dim": 4,
                    "combiner": ["sum", None, "mean"][i % 3]}
                   for i in range(9)]
        configs.append({"input_dim": 4096 + 64, "output_dim": 4,
                        "combiner": "sum",
                        "streaming": {"capacity": 4096, "buckets": 64}})
        de = DistributedEmbedding(configs, world_size=world)
        cats = dense_cats(configs)
    elif name == "criteo1tb":
        # mp input + comm_balanced: the ROADMAP item-4 deployment shape
        # (the dlrm example's defaults at scale). dp_input stays True in
        # the returned layer so the case also traces on the generic
        # harnesses; the capacity audit prices the mp-input variant via
        # audit_plan(dp_input=False).
        configs = [{"input_dim": int(s), "output_dim": CRITEO1TB_DIM,
                    "combiner": None} for s in CRITEO_1TB_SIZES]
        de = DistributedEmbedding(configs, world_size=world,
                                  strategy="comm_balanced",
                                  column_slice_threshold=CRITEO1TB_COL_SLICE)
        cats = dense_cats(configs)
    elif name == "ragged":
        configs = [{"input_dim": 40 + 7 * i, "output_dim": 8,
                    "combiner": "sum" if i % 2 else "mean"}
                   for i in range(8)]
        de = DistributedEmbedding(configs, world_size=world)
        local_b = batch // max(world, 1)
        cap = local_b * 4
        cats = [Ragged(values=jax.ShapeDtypeStruct((world * cap,),
                                                   jnp.int32),
                       row_splits=jax.ShapeDtypeStruct(
                           (world * (local_b + 1),), jnp.int32))
                for _ in configs]
    elif name == "row_sliced":
        configs = [
            {"input_dim": 100, "output_dim": 8, "combiner": None},
            {"input_dim": 30, "output_dim": 8, "combiner": "sum"},
            {"input_dim": 100, "output_dim": 8, "combiner": "mean"},
            {"input_dim": 40, "output_dim": 8, "combiner": None},
            {"input_dim": 26, "output_dim": 8, "combiner": "sum"},
            {"input_dim": 100, "output_dim": 4, "combiner": "sum"},
            {"input_dim": 22, "output_dim": 8, "combiner": None},
            {"input_dim": 24, "output_dim": 8, "combiner": None},
        ]
        # the 100-row tables split into 4 row-range slices
        de = DistributedEmbedding(configs, world_size=world,
                                  row_slice=100 * 8 // 4 + 1)
        cats = dense_cats(configs)
    else:
        raise ValueError(f"unknown config {name!r}")

    cols = sum(int(c["output_dim"]) for c in configs)
    dense_params = {"w": jax.ShapeDtypeStruct((cols, 1), jnp.float32),
                    "v": jax.ShapeDtypeStruct((3, 1), jnp.float32)}
    batch_tree = (jax.ShapeDtypeStruct((batch, 3), jnp.float32),
                  jax.ShapeDtypeStruct((batch, 1), jnp.float32))
    return de, cats, batch_tree, dense_params, loss_fn


def force_cpu(devices: int) -> None:
    """Pin the static audit tools to an N-virtual-device CPU backend.

    Must run before the process's first jax import: the auditors are pure
    static tools and must not take a chip another process may be using.
    Shared by ``tools/audit_step.py`` and ``tools/hlo_audit.py``
    so the two gates cannot drift in WHICH program they audit: an
    inherited ``DETPU_OBS=1`` / ``DETPU_TELEMETRY=1`` would flip the
    audited step to an instrumented variant, and an exported
    ``DETPU_SGD_DEDUP=1`` would force the dedup pass back into the SGD
    builds — both gates audit the default program; the variants are
    audited explicitly (``--with-metrics``/``--with-telemetry``,
    ``--sgd-dedup``, tests)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}")
    for knob in ("DETPU_OBS", "DETPU_TELEMETRY", "DETPU_SGD_DEDUP"):
        os.environ.pop(knob, None)


def cpu_mesh(world: int):
    """A ``("data",)`` Mesh over the first ``world`` host-platform devices
    (``None`` for world <= 1). :func:`force_cpu` must have run first."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if world <= 1:
        return None
    devs = jax.devices()
    if len(devs) < world:
        raise RuntimeError(
            f"host platform exposes {len(devs)} devices < {world}")
    return Mesh(np.array(devs[:world]), ("data",))
