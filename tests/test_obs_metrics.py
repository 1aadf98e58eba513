"""Observability layer (ISSUE 2): on-device step metrics, ragged
capacity-overflow counters, process counters/recompile listener, the
metrics sidecar, and `utils.metrics.binary_auc` edge cases.

The overflow tests are the acceptance teeth: a ragged batch engineered to
claim more ids than its static capacity must report a NONZERO truncation
count instead of passing silently (the failure mode the ISSUE motivation
names)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_embeddings_tpu.ops.embedding_lookup import Ragged
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseRows, SparseSGD, init_hybrid_state,
    make_hybrid_train_loop, make_hybrid_train_step, sparse_grad_metrics)
from distributed_embeddings_tpu.utils import metrics as umetrics
from distributed_embeddings_tpu.utils import obs, runtime

WORLD = 8


# ------------------------------------------------------ binary_auc edges


def _auc_pairwise(labels, preds):
    """O(P*N) literal definition: P(score_pos > score_neg) + 0.5 ties."""
    labels = np.asarray(labels).reshape(-1)
    preds = np.asarray(preds).reshape(-1)
    pos = preds[labels > 0.5]
    neg = preds[labels <= 0.5]
    if len(pos) == 0 or len(neg) == 0:
        return float("nan")
    wins = (pos[:, None] > neg[None, :]).sum()
    ties = (pos[:, None] == neg[None, :]).sum()
    return float((wins + 0.5 * ties) / (len(pos) * len(neg)))


def test_binary_auc_matches_pairwise_reference():
    rng = np.random.default_rng(0)
    labels = (rng.random(500) < 0.3).astype(np.float32)
    preds = rng.normal(size=500)
    np.testing.assert_allclose(umetrics.binary_auc(labels, preds),
                               _auc_pairwise(labels, preds), atol=1e-12)


def test_binary_auc_tied_scores():
    # heavy ties (quantized scores): the rank statistic must average tied
    # ranks, matching the 0.5-credit pairwise definition
    rng = np.random.default_rng(1)
    labels = (rng.random(400) < 0.5).astype(np.float32)
    preds = rng.integers(0, 4, size=400).astype(np.float64)  # 4 levels
    np.testing.assert_allclose(umetrics.binary_auc(labels, preds),
                               _auc_pairwise(labels, preds), atol=1e-12)


def test_binary_auc_all_tied_is_half():
    labels = np.array([0, 1, 0, 1, 1], np.float32)
    preds = np.full(5, 0.7)
    np.testing.assert_allclose(umetrics.binary_auc(labels, preds), 0.5,
                               atol=1e-12)


def test_binary_auc_single_class_is_nan():
    preds = np.array([0.1, 0.9, 0.5])
    assert np.isnan(umetrics.binary_auc(np.ones(3), preds))
    assert np.isnan(umetrics.binary_auc(np.zeros(3), preds))


def test_binary_auc_empty_batch_is_nan():
    assert np.isnan(umetrics.binary_auc(np.zeros(0), np.zeros(0)))


def test_binary_auc_perfect_and_inverted():
    labels = np.array([0, 0, 1, 1], np.float32)
    np.testing.assert_allclose(
        umetrics.binary_auc(labels, np.array([0.1, 0.2, 0.8, 0.9])), 1.0)
    np.testing.assert_allclose(
        umetrics.binary_auc(labels, np.array([0.9, 0.8, 0.2, 0.1])), 0.0)


# ----------------------------------------------- step metrics, world == 1


def _loss_fn_factory():
    def loss_fn(dp, outs, batch):
        del batch
        return sum(jnp.mean(o.astype(jnp.float32) ** 2) for o in outs) \
            * dp["w"]
    return loss_fn


def _single_worker_setup(combiner="sum"):
    configs = [{"input_dim": 50, "output_dim": 8, "combiner": combiner},
               {"input_dim": 40, "output_dim": 8}]
    de = DistributedEmbedding(configs, world_size=1)
    tx = optax.sgd(0.01)
    emb_opt = SparseSGD()
    state = init_hybrid_state(de, emb_opt, {"w": jnp.float32(0.5)}, tx,
                              jax.random.key(0))
    step = make_hybrid_train_step(de, _loss_fn_factory(), tx, emb_opt,
                                  with_metrics=True)
    return de, state, step


def test_single_worker_metrics_schema_and_counts():
    de, state, step = _single_worker_setup()
    rng = np.random.default_rng(0)
    rag = Ragged(values=jnp.asarray(rng.integers(0, 50, 12), jnp.int32),
                 row_splits=jnp.asarray([0, 3, 6, 9, 12], jnp.int32))
    dense_ids = jnp.asarray(rng.integers(0, 40, 4), jnp.int32)
    loss, state, m = step(state, [rag, dense_ids], None)
    assert set(m) == set(obs.STEP_METRIC_KEYS)
    assert int(m["ids_routed"][0]) == 12 + 4
    assert int(m["id_overflow"][0]) == 0
    # single worker: nothing leaves the chip
    assert float(m["id_a2a_bytes"][0]) == 0.0
    assert float(m["out_a2a_bytes"][0]) == 0.0
    assert float(m["loss"][0]) == pytest.approx(float(loss))
    assert int(m["step"][0]) == 0
    assert float(m["emb_grad_norm"][0]) > 0
    # every value JSON-serializes (the sidecar contract)
    assert obs._selftest_json_roundtrip(m)


def test_single_worker_overflow_counter_nonzero():
    """A ragged batch whose row lengths claim more ids than the static
    capacity holds must report the truncated count, not pass silently."""
    de, state, step = _single_worker_setup()
    rng = np.random.default_rng(0)
    cap = 8
    # lengths claim 3 ids per row * 4 rows = 12 > cap = 8 -> 4 truncated
    rag = Ragged(values=jnp.asarray(rng.integers(0, 50, cap), jnp.int32),
                 row_splits=jnp.asarray([0, 3, 6, 9, 12], jnp.int32))
    dense_ids = jnp.asarray(rng.integers(0, 40, 4), jnp.int32)
    _, _, m = step(state, [rag, dense_ids], None)
    assert int(m["id_overflow"][0]) == 4
    # routed counts clamp at capacity: 8 ragged + 4 dense
    assert int(m["ids_routed"][0]) == cap + 4


def test_metrics_disabled_keeps_two_tuple_contract():
    configs = [{"input_dim": 40, "output_dim": 8}]
    de = DistributedEmbedding(configs, world_size=1)
    tx = optax.sgd(0.01)
    emb_opt = SparseSGD()
    state = init_hybrid_state(de, emb_opt, {"w": jnp.float32(0.5)}, tx,
                              jax.random.key(0))
    step = make_hybrid_train_step(de, _loss_fn_factory(), tx, emb_opt,
                                  with_metrics=False)
    out = step(state, [jnp.zeros((4,), jnp.int32)], None)
    assert len(out) == 2


def test_env_flag_enables_metrics(monkeypatch):
    monkeypatch.setenv(obs.OBS_ENV, "1")
    assert obs.metrics_enabled()
    de, state, _ = _single_worker_setup()
    # with_metrics=None follows the env
    step = make_hybrid_train_step(de, _loss_fn_factory(), optax.sgd(0.01),
                                  SparseSGD())
    rng = np.random.default_rng(0)
    rag = Ragged(values=jnp.asarray(rng.integers(0, 50, 12), jnp.int32),
                 row_splits=jnp.asarray([0, 3, 6, 9, 12], jnp.int32))
    out = step(state, [rag, jnp.zeros((4,), jnp.int32)], None)
    assert len(out) == 3
    monkeypatch.setenv(obs.OBS_ENV, "0")
    assert not obs.metrics_enabled()


def test_train_loop_stacks_metrics_over_steps():
    de, state, _ = _single_worker_setup(combiner=None)
    # dense-only inputs for an easy [K, ...] stack
    loop = make_hybrid_train_loop(de, _loss_fn_factory(), optax.sgd(0.01),
                                  SparseSGD(), with_metrics=True)
    K, b = 3, 4
    rng = np.random.default_rng(0)
    cats = [jnp.asarray(rng.integers(0, 50, (K, b)), jnp.int32),
            jnp.asarray(rng.integers(0, 40, (K, b)), jnp.int32)]
    losses, state, m = loop(state, cats, None)
    assert losses.shape == (K,)
    assert m["ids_routed"].shape == (K, 1)
    np.testing.assert_array_equal(np.asarray(m["step"]).reshape(-1),
                                  [0, 1, 2])


# ----------------------------------------------- step metrics, world == 8


def _dist_setup():
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
    configs = ([{"input_dim": 50, "output_dim": 16, "combiner": "sum"}]
               + [{"input_dim": 30 + i, "output_dim": 16}
                  for i in range(WORLD + 1)])
    de = DistributedEmbedding(configs, world_size=WORLD,
                              strategy="memory_balanced")
    tx = optax.sgd(0.01)
    emb_opt = SparseSGD()
    state = init_hybrid_state(de, emb_opt, {"w": jnp.float32(0.5)}, tx,
                              jax.random.key(0), mesh=mesh)
    step = make_hybrid_train_step(de, _loss_fn_factory(), tx, emb_opt,
                                  mesh=mesh, with_metrics=True)
    return de, state, step


def _stacked_ragged(rng, cap, b, lens_per_shard):
    """Per-shard CSR blocks stacked in the distributed Ragged convention:
    values [WORLD*cap], row_splits [WORLD*(b+1)]; ``lens_per_shard[s]`` is
    shard s's uniform per-row length."""
    vals, splits = [], []
    for s in range(WORLD):
        vals.append(rng.integers(0, 50, cap).astype(np.int32))
        ln = lens_per_shard[s]
        splits.append(np.arange(0, ln * (b + 1), ln, dtype=np.int32))
    return Ragged(values=jnp.asarray(np.concatenate(vals)),
                  row_splits=jnp.asarray(np.concatenate(splits)))


def test_distributed_overflow_is_per_rank():
    de, state, step = _dist_setup()
    rng = np.random.default_rng(0)
    b, cap = 4, 8
    # shard 0 claims 3*4=12 > cap=8 (4 truncated); others claim 2*4=8 (fit)
    rag = _stacked_ragged(rng, cap, b, [3] + [2] * (WORLD - 1))
    cats = [rag] + [jnp.asarray(rng.integers(0, 30, WORLD * b), jnp.int32)
                    for _ in range(WORLD + 1)]
    _, _, m = step(state, cats, None)
    overflow = np.asarray(m["id_overflow"])
    assert overflow.shape == (WORLD,)
    assert overflow.sum() == 4
    # the overflow lands on the rank OWNING the ragged table, localizing
    # the truncation to a placement, not just a boolean
    assert (overflow > 0).sum() == 1
    # exchange byte metrics are nonzero on a real mesh and identical
    # across ranks (uniform padded layout)
    ida2a = np.asarray(m["id_a2a_bytes"])
    assert (ida2a > 0).all() and len(set(ida2a.tolist())) == 1
    assert (np.asarray(m["out_a2a_bytes"]) > 0).all()
    # per-rank routed counts sum to >= the dense id volume
    assert np.asarray(m["ids_routed"]).sum() > 0


def test_distributed_healthy_batch_zero_overflow():
    de, state, step = _dist_setup()
    rng = np.random.default_rng(0)
    b, cap = 4, 8
    rag = _stacked_ragged(rng, cap, b, [2] * WORLD)
    cats = [rag] + [jnp.asarray(rng.integers(0, 30, WORLD * b), jnp.int32)
                    for _ in range(WORLD + 1)]
    _, _, m = step(state, cats, None)
    assert np.asarray(m["id_overflow"]).sum() == 0


# ------------------------------------------------- counters and listeners


def test_counters_inc_and_reset():
    obs.reset_counters()
    assert obs.counter_inc("x") == 1
    assert obs.counter_inc("x", 4) == 5
    assert obs.counters() == {"x": 5}
    obs.reset_counters()
    assert obs.counters() == {}


def test_retry_increments_counter():
    obs.reset_counters()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ValueError("transient")
        return "ok"

    assert runtime.retry(flaky, max_attempts=5, base_delay_s=0.01,
                         max_delay_s=0.02, describe="obs test") == "ok"
    assert obs.counters()["runtime_retries"] == 2
    assert obs.counters()["runtime_retries.obs_test"] == 2


def test_fault_point_increments_counter(monkeypatch):
    obs.reset_counters()
    runtime.reset_fault_counts()
    monkeypatch.setenv(runtime.FAULT_ENV, "raise:obs_probe:1")
    with pytest.raises(runtime.FaultInjected):
        runtime.fault_point("obs_probe")
    assert obs.counters()["fault_injections"] == 1
    assert obs.counters()["fault_injections.obs_probe"] == 1


def test_compile_listener_counts_fresh_compiles():
    assert obs.install_compile_listener()
    obs.reset_counters()
    shape = (17,)  # unlikely to be cached from another test

    @jax.jit
    def f(x):
        return x * 2 + 1

    f(jnp.zeros(shape)).block_until_ready()
    first = obs.counters().get("recompiles", 0)
    assert first >= 1
    f(jnp.ones(shape)).block_until_ready()  # cache hit: no new compile
    assert obs.counters().get("recompiles", 0) == first


# -------------------------------------------------------- metrics logger


def test_metrics_logger_roundtrip(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    log = obs.MetricsLogger(path)
    m = {"ids_routed": jnp.asarray([7], jnp.int32),
         "id_overflow": np.asarray([0])}
    log.log_step(m, step=3, variant="test")
    obs.reset_counters()
    obs.counter_inc("recompiles", 2)
    log.log_counters(final=True)
    recs = obs.MetricsLogger.load(path)
    assert [r["section"] for r in recs] == ["step_metrics", "counters"]
    assert recs[0]["step"] == 3 and recs[0]["variant"] == "test"
    assert recs[0]["metrics"]["ids_routed"] == [7]
    assert recs[1]["counters"]["recompiles"] == 2
    # every line is independently parseable JSON (fsynced JSONL contract)
    with open(path, encoding="utf-8") as f:
        for line in f:
            json.loads(line)


def test_metrics_logger_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    log = obs.MetricsLogger(path)
    log.log_step({"ids_routed": [1]}, step=0)
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"section": "step_metrics", "metr')  # killed mid-write
    recs = obs.MetricsLogger.load(path)
    assert len(recs) == 1 and recs[0]["step"] == 0


def test_summarize_reduces_per_rank_vectors():
    m = {"ids_routed": np.asarray([4, 6]),
         "id_overflow": np.asarray([0, 3]),
         "id_a2a_bytes": np.asarray([10.0, 10.0]),
         "out_pad_frac": np.asarray([0.25, 0.5]),
         "loss": np.asarray([1.5, 1.5])}
    s = obs.summarize(m)
    assert s["ids_routed"] == 10.0
    assert s["id_overflow"] == 3.0
    assert s["id_a2a_bytes"] == 20.0
    assert s["out_pad_frac"] == 0.5
    assert s["loss"] == 1.5


def test_summarize_percentiles_of_per_rank_vectors():
    # 8-rank vector: p50/p95 ride alongside the scalar aggregation
    ids = np.asarray([10.0, 10, 10, 10, 10, 10, 10, 94])
    s = obs.summarize({"ids_routed": ids, "loss": np.asarray([1.0])})
    assert s["ids_routed"] == float(ids.sum())
    assert s["ids_routed_p50"] == pytest.approx(np.percentile(ids, 50))
    assert s["ids_routed_p95"] == pytest.approx(np.percentile(ids, 95))
    # scalar ([1]-shaped) metrics carry no percentile keys
    assert "loss_p50" not in s and "loss_p95" not in s


def test_metrics_logger_rotation_caps_growth(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    cap = 2000
    log = obs.MetricsLogger(path, max_bytes=cap)
    for s in range(60):
        log.log_step({"ids_routed": list(range(8))}, step=s)
    # the live file stays bounded by ~one record past the cap, and the
    # rotated generation holds the earlier records
    assert os.path.getsize(path) <= cap + 200
    assert os.path.exists(path + ".1")
    live = obs.MetricsLogger.load(path)
    rotated = obs.MetricsLogger.load(path + ".1")
    assert live and rotated
    # one generation kept: the retained tail is contiguous, ordered,
    # and ends at the newest record
    steps = [r["step"] for r in rotated + live]
    assert steps == list(range(steps[0], 60))


def test_metrics_logger_unbounded_by_default(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    log = obs.MetricsLogger(path)  # DETPU_OBS_MAX_BYTES default 0
    for s in range(30):
        log.log_counters(step=s)
    assert not os.path.exists(path + ".1")
    assert len(obs.MetricsLogger.load(path)) == 30


def test_metrics_logger_n_generation_rotation(tmp_path):
    # max_files=3: .1/.2/.3 ride behind the live file (the checkpoint
    # ring idiom); the record stream across ALL generations is the
    # contiguous, ordered tail of everything logged
    path = str(tmp_path / "metrics.jsonl")
    cap = 1000
    log = obs.MetricsLogger(path, max_bytes=cap, max_files=3)
    for s in range(120):
        log.log_step({"ids_routed": list(range(8))}, step=s)
    gens = [p for p in (f"{path}.{i}" for i in range(1, 5))
            if os.path.exists(p)]
    assert gens == [f"{path}.{i}" for i in (1, 2, 3)]  # never a .4
    assert os.path.getsize(path) <= cap + 200
    recs = []
    for p in reversed(gens):  # .3 oldest ... .1 newest rotated
        recs.extend(obs.MetricsLogger.load(p))
    recs.extend(obs.MetricsLogger.load(path))
    steps = [r["step"] for r in recs]
    assert steps == list(range(steps[0], 120))


def test_metrics_logger_rotation_drops_oldest_generation(tmp_path):
    # with max_files=1 every rotation REPLACES .1 — the oldest records
    # fall off instead of a .2 appearing
    path = str(tmp_path / "metrics.jsonl")
    log = obs.MetricsLogger(path, max_bytes=500, max_files=1)
    for s in range(80):
        log.log_step({"ids_routed": list(range(8))}, step=s)
    assert os.path.exists(path + ".1")
    assert not os.path.exists(path + ".2")
    tail = [r["step"] for r in obs.MetricsLogger.load(path + ".1")
            + obs.MetricsLogger.load(path)]
    assert tail == list(range(tail[0], 80))
    assert tail[0] > 0  # something WAS dropped


def test_metrics_logger_max_files_env_default(tmp_path, monkeypatch):
    monkeypatch.setenv("DETPU_OBS_MAX_FILES", "4")
    log = obs.MetricsLogger(str(tmp_path / "m.jsonl"), max_bytes=100)
    assert log.max_files == 4
    monkeypatch.delenv("DETPU_OBS_MAX_FILES")
    log = obs.MetricsLogger(str(tmp_path / "m2.jsonl"), max_bytes=100)
    assert log.max_files == 2  # registry default


# ------------------------------------------------- sparse_optax metrics


def test_sparse_grad_metrics_counts_live_rows():
    vocab = 10
    g = SparseRows(ids=jnp.asarray([0, 3, vocab, vocab], jnp.int32),
                   rows=jnp.asarray([[3.0, 4.0], [0.0, 0.0],
                                     [9.0, 9.0], [9.0, 9.0]]),
                   vocab=vocab)
    out = sparse_grad_metrics([g])
    assert int(out["touched_rows"][0]) == 2  # the two in-vocab rows
    # pad rows' values are excluded from the norm: |(3,4)| = 5
    assert float(out["sparse_grad_norm"][0]) == pytest.approx(5.0)


# ------------------------------------------------------- tracing helpers


def test_scope_and_profile_trace_noop(tmp_path, monkeypatch):
    with obs.scope("unit_test"):
        pass  # named_scope outside a trace is a no-op context
    monkeypatch.delenv(obs.PROFILE_DIR_ENV, raising=False)
    with obs.profile_trace("nothing"):
        pass  # disabled: transparent
    d = str(tmp_path / "prof")
    monkeypatch.setenv(obs.PROFILE_DIR_ENV, d)
    with obs.profile_trace("lbl"):
        jnp.zeros((2,)).block_until_ready()
    # a capture directory was created for the label
    assert os.path.isdir(os.path.join(d, "lbl"))


def test_span_is_inert_outside_a_capture():
    """obs.span is the host twin of obs.scope: a context manager that
    records nothing while no profiler session runs, takes its metadata
    at the call or later, and brings no import of jax to module scope
    (the package path imports jax anyway, so the rule is the static
    one)."""
    from tools import detlint

    with obs.span("unit_test", flush=3, rung=8) as sp:
        sp.set_metadata(rid=1)         # inert too
        with obs.span("unit_test/child"):
            pass
    assert obs.is_span_event("detpu/serve/flush")
    assert not obs.is_span_event("fusion.4")
    assert not obs.is_span_event("req/flush")
    assert not obs.is_span_event(None)
    assert [f for f in detlint.run(rule_names=["module-scope-jax"])
            if f.path.endswith("utils/obs.py")] == []
