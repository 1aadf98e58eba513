"""Plan-time capacity auditor (analysis/plan_audit.py, ISSUE 8).

Three layers of teeth:

* **mirror parity** — the jax-free pack/slab arithmetic must agree with
  ``ops/packed_slab.py`` and with a real ``DistributedEmbedding``'s
  layout for every reference configuration, and the byte totals must
  agree EXACTLY with ``analysis/memory.py``'s ``eval_shape`` accounting
  (the calibration contract ``tools/plan_audit.py --strict`` enforces);
* **measured validation** — the predicted per-step all-to-all payloads
  must equal the on-device ``*_a2a_bytes`` step metrics on the
  8-virtual-device mesh (the predictor is validated, not decorative);
* **contract drills** — a seeded over-HBM plan and a seeded empty rank
  must each FAIL with a violation naming the rank, an unsliced 9.5 GB slab
  is priced and not refused, and the
  real Criteo-1TB deployment plan (world=16, bf16, column-sliced) must
  pass, all without materializing a single array.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_embeddings_tpu.analysis import memory as dmem
from distributed_embeddings_tpu.analysis import plan_audit as pa
from distributed_embeddings_tpu.ops import packed_slab as ps
from distributed_embeddings_tpu.ops.embedding_lookup import Ragged
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseAdagrad, SparseAdam, SparseSGD,
    init_hybrid_state, make_hybrid_train_step)
from distributed_embeddings_tpu.parallel.strategy import DistEmbeddingStrategy
from tools._profcommon import (CRITEO1TB_BATCH, CRITEO1TB_COL_SLICE,
                               CRITEO1TB_DIM, CRITEO1TB_WORLD,
                               CRITEO_1TB_SIZES, build_case)

WORLD = 8

C1TB_CONFIGS = [{"input_dim": int(s), "output_dim": CRITEO1TB_DIM,
                 "combiner": None} for s in CRITEO_1TB_SIZES]


# ------------------------------------------------------ mirror parity


@pytest.mark.parametrize("width", [1, 2, 3, 4, 8, 16, 21, 32, 64, 127,
                                   128, 130, 256])
def test_pack_arithmetic_matches_packed_slab(width):
    """The jax-free mirrors cannot drift from ops/packed_slab.py."""
    assert pa._pack_factor(width) == ps.pack_factor(width)
    assert pa._phys_width(width) == ps.phys_width(width)
    for rows in (1, 7, 100, 1001):
        assert pa._align_rows(rows, width) == ps.align_rows(rows, width)
    assert pa.LANES == ps.LANES


@pytest.mark.parametrize("case", ["dense", "ragged", "row_sliced",
                                  "bigvocab", "criteo1tb"])
def test_slab_geometry_matches_distributed_embedding(case):
    """slab_geometry reproduces the layer's width grouping, row offsets
    and physical capacities exactly — for every shared reference case,
    including the real Criteo-1TB shapes (pure metadata, nothing
    materialized)."""
    world = CRITEO1TB_WORLD if case == "criteo1tb" else WORLD
    de, _, _, _, _ = build_case(case, world, 16)
    g = pa.slab_geometry(de.strategy)
    assert list(g.widths) == de.widths
    assert dict(g.phys_cap) == de.phys_cap
    assert dict(g.phys_w) == de.phys_w
    assert dict(g.rows_cap) == de.rows_cap
    assert [list(o) for o in g.row_offsets_list] == de.row_offsets_list


@pytest.mark.parametrize("opt,name", [(SparseSGD(), "sgd"),
                                      (SparseAdagrad(), "adagrad"),
                                      (SparseAdam(), "adam")])
def test_byte_model_matches_memory_eval_shape(opt, name):
    """The calibration contract: zero drift against
    analysis/memory.py's eval_shape accounting for every optimizer
    family (the state models price init() exactly)."""
    de, cats, _, _, _ = build_case("dense", WORLD, 16)
    rep = pa.audit_plan(de, 16, optimizer=opt, cat_inputs=cats)
    assert rep.optimizer == name
    mem = dmem.table_memory_report(de, opt)
    drift = pa.compare_with_memory(rep, mem)
    assert drift["max_abs_drift"] == 0.0, drift
    # per-rank division agrees with memory.py's new per-rank totals
    assert (rep.per_rank[0].alloc_param_bytes
            == mem["totals"]["param_bytes_allocated_per_rank"])
    assert (rep.per_rank[0].opt_state_bytes
            == mem["totals"]["opt_state_bytes_per_rank"])


def test_dtype_pricing_bf16_halves_param_bytes():
    de, cats, _, _, _ = build_case("dense", WORLD, 16)
    f32 = pa.audit_plan(de, 16, cat_inputs=cats, param_dtype="float32")
    bf16 = pa.audit_plan(de, 16, cat_inputs=cats, param_dtype=jnp.bfloat16)
    assert bf16.param_dtype == "bfloat16"
    assert (bf16.per_rank[0].alloc_param_bytes * 2
            == f32.per_rank[0].alloc_param_bytes)


# ------------------------------------------- measured a2a validation


def test_a2a_prediction_matches_step_metrics_on_mesh():
    """Predicted per-step exchange payloads equal the on-device
    ``*_a2a_bytes`` metrics exactly — dense + ragged mixed inputs on the
    8-virtual-device mesh."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    configs = ([{"input_dim": 50, "output_dim": 16, "combiner": "sum"}]
               + [{"input_dim": 30 + i, "output_dim": 16}
                  for i in range(WORLD + 1)])
    de = DistributedEmbedding(configs, world_size=WORLD,
                              strategy="memory_balanced")
    tx = optax.sgd(0.01)
    emb_opt = SparseSGD()

    def loss_fn(dp, outs, batch):
        del batch
        return sum(jnp.mean(o.astype(jnp.float32) ** 2)
                   for o in outs) * dp["w"]

    state = init_hybrid_state(de, emb_opt, {"w": jnp.float32(0.5)}, tx,
                              jax.random.key(0), mesh=mesh)
    step = make_hybrid_train_step(de, loss_fn, tx, emb_opt, mesh=mesh,
                                  with_metrics=True)
    rng = np.random.default_rng(0)
    b, cap = 4, 8
    vals = np.concatenate([rng.integers(0, 50, cap).astype(np.int32)
                           for _ in range(WORLD)])
    splits = np.concatenate([np.arange(0, 2 * (b + 1), 2, dtype=np.int32)
                             for _ in range(WORLD)])
    rag = Ragged(values=jnp.asarray(vals), row_splits=jnp.asarray(splits))
    cats = [rag] + [jnp.asarray(rng.integers(0, 30, WORLD * b), jnp.int32)
                    for _ in range(WORLD + 1)]
    _, _, m = step(state, cats, None)

    rep = pa.audit_plan(de, WORLD * b, cat_inputs=cats, optimizer="sgd")
    assert rep.local_batch == b
    assert rep.id_a2a_bytes_per_step == int(np.asarray(m["id_a2a_bytes"])[0])
    assert rep.out_a2a_bytes_per_step == int(
        np.asarray(m["out_a2a_bytes"])[0])
    assert rep.grad_a2a_bytes_per_step == int(
        np.asarray(m["grad_a2a_bytes"])[0])
    # padding fraction is the same plan-derived figure the metric reports
    np.testing.assert_allclose(rep.out_pad_frac,
                               float(np.asarray(m["out_pad_frac"]).mean()),
                               atol=1e-6)


def test_mp_input_prices_zero_id_exchange():
    de, cats, _, _, _ = build_case("dense", WORLD, 16)
    dp = pa.audit_plan(de, 16, cat_inputs=cats, dp_input=True)
    mp = pa.audit_plan(de, 16, cat_inputs=cats, dp_input=False)
    assert dp.id_a2a_bytes_per_step > 0
    assert mp.id_a2a_bytes_per_step == 0
    assert mp.out_a2a_bytes_per_step == dp.out_a2a_bytes_per_step


# ------------------------------------------------- contract drills


def test_criteo1tb_deployment_plan_passes():
    """The north-star plan — real 26-table / ~188M-row vocab vector,
    world=16, bf16, the reference column-slice threshold — holds the
    default v5e contract: fits HBM, no slab past the cliff, every rank
    populated. Pure metadata; building the strategy at 188M rows costs
    microseconds and zero array bytes."""
    st = DistEmbeddingStrategy(C1TB_CONFIGS, CRITEO1TB_WORLD,
                               strategy="comm_balanced",
                               column_slice_threshold=CRITEO1TB_COL_SLICE)
    rep = pa.audit_plan(st, CRITEO1TB_BATCH, optimizer="sgd",
                        param_dtype="bfloat16", dp_input=False,
                        contract=pa.default_contract())
    assert rep.ok, rep.violations
    assert rep.n_sliced_tables >= CRITEO1TB_WORLD
    # every slab's scatter is priced by the step's own rule
    assert all(s.stream_rows > 0 and s.scatter_form and s.scatter_ms > 0
               for s in rep.slabs)
    # the whole point of the threshold: the ~40M-row tables split
    assert rep.n_sliced_tables > len(C1TB_CONFIGS)
    rep.raise_on_violations()  # no-op when clean


def test_seeded_over_hbm_plan_fails_naming_rank():
    """Criteo-1TB fp32 + Adam on 8 ranks (~57 GB/rank) must be rejected
    with the rank named."""
    st = DistEmbeddingStrategy(C1TB_CONFIGS, 8, strategy="memory_balanced")
    rep = pa.audit_plan(st, CRITEO1TB_BATCH, optimizer="adam",
                        param_dtype="float32",
                        contract=pa.default_contract())
    assert not rep.ok
    assert any(v.startswith("rank ") and "exceeds the per-rank HBM" in v
               for v in rep.violations), rep.violations
    with pytest.raises(pa.PlanAuditError, match="rank "):
        rep.raise_on_violations()


def test_online_snapshot_billing_and_seeded_over_hbm():
    """The online runtime's RCU double-buffer is contract-checked:
    ``online=True`` bills 2x params + 1x opt (frozen, shared) + 2x
    streaming state per rank as ``snapshot_bytes``, and a plan that
    fits offline can exceed HBM the moment serving runs beside
    training — rejected with the snapshot component named."""
    st = DistEmbeddingStrategy(C1TB_CONFIGS, CRITEO1TB_WORLD,
                               strategy="comm_balanced",
                               column_slice_threshold=CRITEO1TB_COL_SLICE)
    kw = dict(optimizer="adagrad", param_dtype="bfloat16",
              dp_input=False)
    off = pa.audit_plan(st, CRITEO1TB_BATCH,
                        contract=pa.default_contract(), **kw)
    assert off.ok, off.violations
    assert all(r.snapshot_bytes == 0 for r in off.per_rank)
    on = pa.audit_plan(st, CRITEO1TB_BATCH, online=True,
                       contract=pa.default_contract(), **kw)
    r0, o0 = on.per_rank[0], off.per_rank[0]
    # the publisher keeps exactly: published + in-flight params, one
    # frozen opt slab, two streaming-state copies (zero here)
    assert r0.snapshot_bytes == (2 * o0.alloc_param_bytes
                                 + o0.opt_state_bytes
                                 + 2 * o0.streaming_state_bytes)
    assert r0.total_bytes == o0.total_bytes + r0.snapshot_bytes
    # ~6.6 GB/rank offline fits v5e; +2x params +1x opt does not
    assert not on.ok
    assert any("online snapshots" in v and "exceeds the per-rank HBM" in v
               for v in on.violations), on.violations


def test_online_snapshot_bills_streaming_state_twice():
    cfgs = [{"input_dim": 4096 + 256, "output_dim": 16,
             "streaming": {"capacity": 4096, "buckets": 256}},
            {"input_dim": 1000, "output_dim": 16}]
    st = DistEmbeddingStrategy(cfgs, 2)

    class _S:  # duck-typed StreamingConfig (this module stays jax-free)
        depth, buckets = 3, 512

    off = pa.audit_plan(st, 16, streaming_config=_S)
    on = pa.audit_plan(st, 16, streaming_config=_S, online=True)
    o0, r0 = off.per_rank[0], on.per_rank[0]
    assert o0.streaming_state_bytes > 0
    assert r0.snapshot_bytes == (2 * o0.alloc_param_bytes
                                 + o0.opt_state_bytes
                                 + 2 * o0.streaming_state_bytes)


def test_isolated_serving_bills_shm_region():
    """``isolated=True`` prices the supervisor's double-buffered shm
    transport (utils/shm.py's exact region arithmetic over the global
    host-pickled payload) — host RAM, reported per rank but never
    counted against the HBM contract."""
    from distributed_embeddings_tpu.utils import shm

    cfgs = [{"input_dim": 4096 + 256, "output_dim": 16,
             "streaming": {"capacity": 4096, "buckets": 256}},
            {"input_dim": 1000, "output_dim": 16}]
    st = DistEmbeddingStrategy(cfgs, 2)

    class _S:  # duck-typed StreamingConfig
        depth, buckets = 3, 512

    off = pa.audit_plan(st, 16, streaming_config=_S)
    iso = pa.audit_plan(st, 16, streaming_config=_S, isolated=True)
    o0, r0 = off.per_rank[0], iso.per_rank[0]
    assert o0.shm_region_bytes == 0
    payload = 2 * (o0.alloc_param_bytes + o0.streaming_state_bytes)
    assert r0.shm_region_bytes == shm.region_bytes(
        shm.slack_capacity(payload))
    assert r0.shm_region_bytes > 2 * payload  # 2 buffers + slack + headers
    # host RAM, not HBM: totals and the contract are untouched
    assert r0.total_bytes == o0.total_bytes
    assert r0.hbm_frac == o0.hbm_frac
    assert "shm serving region" in iso.markdown()
    assert "shm serving region" not in off.markdown()


def test_unsliced_slab_is_priced_not_refused():
    """Criteo-1TB bf16 on 16 ranks WITHOUT column slicing stacks the
    ~40M-row tables into a ~9.5 GB apply slab. Older notes read a rate
    cliff between 2.7 and 8.65 GB and refused it; on the v5e that was the
    sweep's pass over the slab (``PERF.md`` section 6, PR 31), which the
    step now avoids, so the slab is priced by the step's own rule."""
    st = DistEmbeddingStrategy(C1TB_CONFIGS, CRITEO1TB_WORLD,
                               strategy="comm_balanced")
    rep = pa.audit_plan(st, CRITEO1TB_BATCH, optimizer="sgd",
                        param_dtype="bfloat16", dp_input=False,
                        contract=pa.default_contract())
    assert not any("slab w" in v for v in rep.violations), rep.violations
    from distributed_embeddings_tpu.parallel import optimizers as opt
    (slab,) = [s for s in rep.slabs if s.width == 128]
    assert slab.rank_bytes > 9e9 and slab.stream_rows > 0
    assert slab.scatter_form == opt.scatter_form(slab.stream_rows,
                                                 slab.rank_bytes)
    assert slab.scatter_ms == pytest.approx(opt.scatter_ns(
        slab.scatter_form, slab.stream_rows, slab.rank_bytes) / 1e6)
    # the pass over the slab alone would cost more than the whole stream
    assert slab.scatter_ms < opt._SWEEP_NS[1] * slab.rank_bytes / 1e6
    assert "scatter" in rep.markdown() and slab.scatter_form in rep.markdown()


def test_empty_rank_flagged():
    st = DistEmbeddingStrategy([{"input_dim": 100, "output_dim": 8}] * 4, 6)
    rep = pa.audit_plan(st, 12, contract=pa.default_contract())
    assert any("own no table slice" in v for v in rep.violations)


def test_group_and_a2a_ceilings():
    de, cats, _, _, _ = build_case("dense", WORLD, 16)
    tight = pa.PlanContract(max_groups=1, max_a2a_bytes_per_step=1)
    rep = pa.audit_plan(de, 16, cat_inputs=cats, contract=tight)
    assert any("padded group shapes" in v for v in rep.violations)
    assert any("a2a payload" in v for v in rep.violations)


# ------------------------------------ spec audit, ranking, cost hook


def test_audit_plan_spec_matches_full_audit():
    """A bare plan_spec() dict (the checkpoint meta.json fingerprint)
    prices capacity identically to the full audit — the path that vets a
    checkpoint's plan before a restore."""
    st = DistEmbeddingStrategy(C1TB_CONFIGS, CRITEO1TB_WORLD,
                               strategy="comm_balanced",
                               column_slice_threshold=CRITEO1TB_COL_SLICE)
    full = pa.audit_plan(st, CRITEO1TB_BATCH, optimizer="adagrad",
                         param_dtype="bfloat16", dp_input=False)
    spec = pa.audit_plan_spec(st.plan_spec(), optimizer="adagrad",
                              param_dtype="bfloat16",
                              contract=pa.default_contract())
    assert spec.ok, spec.violations
    for a, b in zip(full.per_rank, spec.per_rank):
        assert a.alloc_param_bytes == b.alloc_param_bytes
        assert a.live_param_bytes == b.live_param_bytes
        assert a.opt_state_bytes == b.opt_state_bytes
    assert [ (s.width, s.rank_bytes) for s in full.slabs ] == \
           [ (s.width, s.rank_bytes) for s in spec.slabs ]


def test_rank_strategies_orders_fitting_plans_first():
    """The planner cost hook: a strategy whose plan violates the
    contract sorts after every fitting one; among fitting plans the
    lighter max-rank wins."""
    configs = [{"input_dim": 1000 * (i + 1), "output_dim": 16}
               for i in range(8)]
    ranked = pa.rank_strategies(configs, 4, 16,
                                contract=pa.PlanContract(
                                    max_rank_bytes=10**12))
    assert [n for n, _ in ranked][0] in ("memory_optimized",
                                         "memory_balanced",
                                         "comm_balanced")
    basic_rank = [n for n, _ in ranked].index("basic")
    best = ranked[0][1].max_rank_bytes
    assert ranked[basic_rank][1].max_rank_bytes >= best
    assert all(r.ok for _, r in ranked)


def test_strategy_predicted_cost_hook():
    st = DistEmbeddingStrategy([{"input_dim": 64, "output_dim": 8}] * 8, 4)
    rep = st.predicted_cost(16, optimizer="adagrad")
    assert isinstance(rep, pa.PlanReport)
    assert rep.world == 4 and rep.optimizer == "adagrad"
    assert rep.max_rank_bytes > 0


def test_encodings_from_inputs_errors():
    st = DistEmbeddingStrategy([{"input_dim": 64, "output_dim": 8}] * 8, 4)
    with pytest.raises(ValueError, match="not divisible"):
        pa.encodings_from_inputs(
            st, [jax.ShapeDtypeStruct((10,), jnp.int32)] * 8, 4)
    with pytest.raises(ValueError, match="not divisible"):
        pa.audit_plan(st, 10)
    with pytest.raises(ValueError, match="unknown optimizer"):
        pa.audit_plan(st, 16, optimizer="rmsprop")


def test_price_int8_serving_pricing_only():
    """The ISSUE-15 serving-table variant: int8 rows + per-row scales
    price at ~4x less HBM than fp32 (~2x vs bf16, minus the scale tax)
    and shrink the out-a2a payload by the same code/scale arithmetic —
    pricing only, nothing materializes, no jax touched."""
    st = DistEmbeddingStrategy(
        [{"input_dim": 10_000, "output_dim": 32}] * 8, 8)
    rec = pa.price_int8_serving(st, 64, param_dtype="float32")
    # fp32 dim-32: 128 B/row -> 36 B/row = 3.56x
    assert rec["table_bytes_ratio"] == pytest.approx(128 / 36)
    assert rec["int8_table_bytes_per_rank"] < rec["table_bytes_per_rank"]
    assert rec["int8_hbm_frac"] < rec["hbm_frac"]
    assert rec["out_a2a_bytes_per_step"] > 0
    assert rec["int8_out_a2a_bytes_per_step"] \
        < rec["out_a2a_bytes_per_step"]
    assert rec["out_a2a_ratio"] > 1.0
    # bf16 baseline halves the win but the variant still wins
    rec16 = pa.price_int8_serving(st, 64, param_dtype="bfloat16")
    assert 1.0 < rec16["table_bytes_ratio"] < rec["table_bytes_ratio"]
    # json-able (rides the bench serving section)
    import json
    json.dumps(rec)


def test_report_json_roundtrip():
    de, cats, _, _, _ = build_case("ragged", WORLD, 16)
    rep = pa.audit_plan(de, 16, cat_inputs=cats,
                        contract=pa.default_contract())
    import json
    doc = json.loads(pa.report_to_jsonl(rep))
    assert doc["world"] == WORLD
    assert len(doc["per_rank"]) == WORLD
    assert doc["violations"] == []
    assert "| rank |" in rep.markdown()
