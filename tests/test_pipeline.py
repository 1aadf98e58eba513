"""Pipelined hybrid step: schedule declarations, microbatch slicing, and
trajectory equivalence against the serialized baseline.

The K-microbatch software-pipelined step (``parallel/schedule.py::
pipelined_schedule`` + ``parallel/trainer.py::_pipelined_local_step``)
promises three things, each pinned here:

* **K=1 is the serialized program, bitwise** — ``pipelined_schedule(1)``
  degenerates to the serialized schedule and the traced step is
  byte-identical;
* **K>1 is trajectory-equivalent** — losses and final parameters match
  the serialized step within float-accumulation-order tolerance across
  the PR 12 A/B matrix configurations (dense / ragged / row-sliced /
  streaming+telemetry, world 1 and 8, SGD/Adagrad/Adam, metrics on and
  off), with the discrete state (streaming slot maps, admission
  sketches, telemetry sketches, metric counters) BITWISE equal — the
  staging concatenation must reproduce the serialized decisions exactly;
* **the declared overlaps exist** — the schedule auditor certifies the
  pipelined program's DAG independence and the serialized fraction
  collapses (the ROADMAP item 2 acceptance).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from distributed_embeddings_tpu.ops.embedding_lookup import Ragged
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding,
    SparseAdagrad,
    SparseAdam,
    SparseSGD,
    init_hybrid_state,
    make_hybrid_train_step,
)
from distributed_embeddings_tpu.parallel import schedule as schedule_mod
from distributed_embeddings_tpu.parallel.schedule import (
    PHASE_DENSE,
    PHASE_GRAD_EXCHANGE,
    PHASE_ID_EXCHANGE,
    ScheduleError,
    default_schedule,
    pipelined_schedule,
    resolve_schedule,
    streaming_schedule,
)
from distributed_embeddings_tpu.utils import envvars

WORLD = 8


# ------------------------------------------------------------- schedules


def test_pipelined_schedule_declares_per_microbatch_phases():
    sched = pipelined_schedule(2)
    assert sched.microbatches == 2
    names = [p.name for p in sched.phases]
    assert "id_all_to_all_mb0" in names and "id_all_to_all_mb1" in names
    assert "sparse_apply*" in names
    # every collective declares an overlap with the OTHER microbatch's
    # lookup/dense chain
    for p in sched.phases:
        if p.kind == "collective":
            assert p.overlaps, p.name
            assert all("_mb" in q for q in p.overlaps)


def test_pipelined_schedule_k1_is_serialized_baseline():
    assert pipelined_schedule(1).name == default_schedule().name
    assert pipelined_schedule(1).microbatches == 1
    assert (pipelined_schedule(1, streaming=True).name
            == streaming_schedule().name)


def test_pipelined_schedule_env_default(monkeypatch):
    monkeypatch.setenv("DETPU_MICROBATCH", "4")
    assert pipelined_schedule().microbatches == 4
    monkeypatch.setenv("DETPU_MICROBATCH", "1")
    assert pipelined_schedule().microbatches == 1
    monkeypatch.setenv("DETPU_MICROBATCH", "0")
    with pytest.raises(ScheduleError):
        pipelined_schedule()


def test_resolve_schedule_forms():
    assert resolve_schedule(None).name == "serialized-v1"
    assert resolve_schedule("serialized",
                            streaming=True).name == "streaming-serialized-v1"
    sched = pipelined_schedule(2)
    assert resolve_schedule(sched) is sched
    with pytest.raises(ScheduleError):
        resolve_schedule("bogus")


def test_streaming_schedule_declares_admit_overlap():
    sched = streaming_schedule()
    by = sched.by_name()
    assert by["out_all_to_all"].overlaps == ("streaming_admit_*",)
    assert by["grad_all_to_all"].overlaps == ("streaming_admit_*",)
    assert sched.microbatches == 1


def test_mb_phase_glob_suffix():
    assert schedule_mod.mb_phase("lookup_*", 0) == "lookup_*_mb0"
    assert schedule_mod.mb_phase(PHASE_ID_EXCHANGE, 3) == "id_all_to_all_mb3"
    import fnmatch
    assert fnmatch.fnmatchcase("lookup_w8_d_mb0", "lookup_*_mb0")
    assert not fnmatch.fnmatchcase("lookup_w8_d_mb10", "lookup_*_mb1")


def test_microbatch_knobs_registered():
    reg = envvars.registered()
    # default 2: asking for schedule="pipelined" without pinning K must
    # actually build a pipeline (the serialized baseline is the DEFAULT
    # schedule, not a pipelined_schedule degenerate)
    assert reg["DETPU_MICROBATCH"].default == "2"


def test_schedule_pipelined_string_actually_pipelines(monkeypatch):
    monkeypatch.delenv("DETPU_MICROBATCH", raising=False)
    configs = [{"input_dim": 32, "output_dim": 4, "combiner": "sum"}
               for _ in range(8)]
    de = DistributedEmbedding(configs, world_size=WORLD,
                              schedule="pipelined")
    assert de.schedule.microbatches == 2
    # and the plain default stays serialized regardless of the env knob
    monkeypatch.setenv("DETPU_MICROBATCH", "4")
    de2 = DistributedEmbedding(configs, world_size=WORLD)
    assert de2.schedule.microbatches == 1


def test_expected_collectives_scale_with_microbatches():
    from distributed_embeddings_tpu.analysis import expected_collectives

    configs = [{"input_dim": 32, "output_dim": 4, "combiner": "sum"}
               for _ in range(8)]
    de = DistributedEmbedding(configs, world_size=WORLD,
                              schedule=pipelined_schedule(2))
    exp = expected_collectives(de, nan_guard=True, n_dense_leaves=2)
    assert exp["all_to_all_roles"] == {"id_exchange_fwd": 2,
                                       "out_exchange_fwd": 2,
                                       "grad_exchange_bwd": 2}
    # the psum census is K-invariant: accumulate locally, resolve once
    assert exp["psum"] == 1 + 2 + 1


# ------------------------------------------------------ microbatch slicing


def test_microbatch_inputs_ragged_slices_rows_exactly():
    from distributed_embeddings_tpu.parallel.trainer import (
        _microbatch_inputs)

    splits = jnp.asarray([0, 2, 3, 3, 6], jnp.int32)
    values = jnp.asarray([10, 11, 20, 30, 31, 32, 0, 0], jnp.int32)
    r = Ragged(values=values, row_splits=splits)
    dense = jnp.arange(4, dtype=jnp.int32)
    batch = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
    mbs = _microbatch_inputs([r, dense], batch, 2)
    assert len(mbs) == 2
    (r0, d0), b0 = mbs[0]
    (r1, d1), b1 = mbs[1]
    np.testing.assert_array_equal(r0.row_splits, [0, 2, 3])
    np.testing.assert_array_equal(r0.values[:3], [10, 11, 20])
    np.testing.assert_array_equal(r1.row_splits, [0, 0, 3])
    np.testing.assert_array_equal(r1.values[:3], [30, 31, 32])
    np.testing.assert_array_equal(d0, [0, 1])
    np.testing.assert_array_equal(d1, [2, 3])
    np.testing.assert_array_equal(b1, batch[2:])
    with pytest.raises(ValueError):
        _microbatch_inputs([dense], batch, 3)


# --------------------------------------------------------- the A/B harness


def _build_case(name, world, rng):
    """One A/B matrix configuration: ``(de_kwargs, configs, streaming)``."""
    if name == "dense":
        configs = [{"input_dim": 20 + 6 * i, "output_dim": 4,
                    "combiner": ["sum", None, "mean"][i % 3]}
                   for i in range(10)]
        return {}, configs, False
    if name == "ragged":
        configs = [{"input_dim": 40 + 7 * i, "output_dim": 8,
                    "combiner": "sum" if i % 2 else "mean"}
                   for i in range(8)]
        return {}, configs, False
    if name == "row_sliced":
        configs = [{"input_dim": 100 if i % 3 == 0 else 20 + i,
                    "output_dim": 8,
                    "combiner": [None, "sum", "mean"][i % 3]}
                   for i in range(9)]
        return {"row_slice": 100 * 8 // 4 + 1}, configs, False
    if name == "streaming":
        configs = [{"input_dim": 20 + 6 * i, "output_dim": 4,
                    "combiner": ["sum", None, "mean"][i % 3]}
                   for i in range(9)]
        configs.append({"input_dim": 512 + 64, "output_dim": 4,
                        "combiner": "sum",
                        "streaming": {"capacity": 512, "buckets": 64}})
        return {}, configs, True
    raise ValueError(name)


def _make_inputs(rng, configs, batch, world, ragged):
    local_b = batch // max(world, 1)
    cats = []
    for cfg in configs:
        if ragged:
            vals_all, splits_all = [], []
            cap = local_b * 4
            for _ in range(max(world, 1)):
                hots = rng.integers(0, 5, size=local_b)
                splits = np.zeros(local_b + 1, np.int32)
                np.cumsum(hots, out=splits[1:])
                vals = np.zeros(cap, np.int32)
                nnz = int(splits[-1])
                vals[:nnz] = rng.integers(0, cfg["input_dim"], size=nnz)
                vals_all.append(vals)
                splits_all.append(splits)
            cats.append(Ragged(values=jnp.asarray(np.concatenate(vals_all)),
                               row_splits=jnp.asarray(
                                   np.concatenate(splits_all))))
            continue
        hot = 1 if cfg["combiner"] is None else 3
        shape = (batch,) if hot == 1 else (batch, hot)
        hi = (16 * cfg["streaming"]["capacity"] if "streaming" in cfg
              else cfg["input_dim"])
        cats.append(jnp.asarray(rng.integers(0, hi, size=shape), jnp.int32))
    n = jnp.asarray(rng.normal(size=(batch, 13)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(batch, 1)), jnp.float32)
    cols = sum(c["output_dim"] for c in configs)
    dp = {"w": jnp.asarray(rng.normal(size=(cols, 1)), jnp.float32) * 0.1,
          "v": jnp.asarray(rng.normal(size=(13, 1)), jnp.float32) * 0.1}
    return cats, (n, y), dp


def _loss_fn(dp, emb_outs, b):
    n, y = b
    x = jnp.concatenate([e.reshape(e.shape[0], -1) for e in emb_outs],
                        axis=1)
    return jnp.mean((x @ dp["w"] + n @ dp["v"] - y) ** 2)


def _opt(name):
    return {"sgd": SparseSGD, "adagrad": SparseAdagrad,
            "adam": SparseAdam}[name]()


def _run(name, world, opt_name, metrics, sched, steps=3, batch=64,
         telemetry=False):
    from distributed_embeddings_tpu.analysis import telemetry as tel
    from distributed_embeddings_tpu.parallel import (StreamingConfig,
                                                     init_streaming)
    from distributed_embeddings_tpu.analysis.telemetry import init_telemetry

    kwargs, configs, streaming = _build_case(name, world,
                                             np.random.default_rng(0))
    de = DistributedEmbedding(configs, world_size=world, schedule=sched,
                              **kwargs)
    mesh = (Mesh(np.array(jax.devices()[:world]), ("data",))
            if world > 1 else None)
    rng = np.random.default_rng(7)
    cats, bt, dp = _make_inputs(rng, configs, batch, world,
                                ragged=(name == "ragged"))
    tx = optax.sgd(0.5)
    opt = _opt(opt_name)
    scfg = StreamingConfig(admit_min_count=1) if streaming else None
    tcfg = tel.TelemetryConfig() if telemetry else None
    state = init_hybrid_state(de, opt, dp, tx, jax.random.key(0),
                              mesh=mesh)
    step = make_hybrid_train_step(
        de, _loss_fn, tx, opt, mesh=mesh, lr_schedule=0.3,
        with_metrics=metrics, nan_guard=True,
        telemetry=tcfg if tcfg else False,
        dynamic=scfg if scfg else False)
    aux = []
    if tcfg:
        aux.append(init_telemetry(de, tcfg, mesh=mesh))
    if scfg:
        aux.append(init_streaming(de, scfg, mesh=mesh))
    losses = []
    last_metrics = None
    for _ in range(steps):
        out = step(state, cats, bt, *aux)
        loss, state = out[0], out[1]
        rest = list(out[2:])
        if metrics:
            last_metrics = rest.pop(0)
        aux = rest
        losses.append(float(loss))
    return losses, state, aux, last_metrics


def _assert_equivalent(name, world, opt_name, metrics, telemetry=False,
                       steps=3):
    l0, s0, aux0, m0 = _run(name, world, opt_name, metrics, None,
                            steps=steps, telemetry=telemetry)
    l2, s2, aux2, m2 = _run(name, world, opt_name, metrics,
                            pipelined_schedule(
                                2, streaming=(name == "streaming")),
                            steps=steps, telemetry=telemetry)
    np.testing.assert_allclose(l0, l2, rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(s0.emb_params),
                    jax.tree_util.tree_leaves(s2.emb_params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-5, atol=2e-6)
    # the discrete aux state (slot maps, sketches, counters) must be
    # BITWISE equal: the pipelined staging reproduces the serialized
    # decisions exactly, not approximately
    for a, b in zip(jax.tree_util.tree_leaves(aux0),
                    jax.tree_util.tree_leaves(aux2)):
        if jnp.issubdtype(a.dtype, jnp.integer):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
    if metrics:
        for k in ("ids_routed", "invalid_id_count", "id_overflow",
                  "skipped_steps"):
            np.testing.assert_array_equal(np.asarray(m0[k]),
                                          np.asarray(m2[k]))


# ------------------------------------------- the PR 12 six-config matrix
# Each configuration pairs with a distinct (world, optimizer, metrics)
# assignment so the set covers world 1 and 8, all three optimizer
# families, and metrics on/off without the full 48-way product; the
# cross combinations ride the slow tier.

def test_ab_dense_world8_adagrad_metrics_on():
    _assert_equivalent("dense", WORLD, "adagrad", True)


def test_ab_ragged_world1_sgd_metrics_off():
    _assert_equivalent("ragged", 1, "sgd", False)


def test_ab_row_sliced_world8_adam_metrics_off():
    _assert_equivalent("row_sliced", WORLD, "adam", False)


def test_ab_streaming_world8_adagrad_metrics_on_with_telemetry():
    _assert_equivalent("streaming", WORLD, "adagrad", True,
                       telemetry=True)


@pytest.mark.parametrize("name,world,opt_name,metrics,telemetry", [
    ("dense", 1, "adam", False, False),
    ("dense", WORLD, "sgd", False, False),
    ("ragged", WORLD, "adagrad", True, False),
    ("row_sliced", 1, "adagrad", True, False),
    ("streaming", 1, "sgd", False, False),
    ("streaming", WORLD, "adam", False, True),
])
def test_ab_matrix_cross(name, world, opt_name, metrics, telemetry):
    _assert_equivalent(name, world, opt_name, metrics,
                       telemetry=telemetry)


# ------------------------------------------------------- exact arithmetic


def test_grad_accumulation_order_exact_bitwise():
    """With exactly-representable values (integer embeddings and
    cotangents, power-of-two batch and K), the K=2 step must reproduce
    the serialized step BITWISE — duplicate ids crossing the microbatch
    boundary land in the merged per-width stream and the single scatter
    accumulates the same per-row total regardless of segment order."""
    configs = [{"input_dim": 16, "output_dim": 4, "combiner": "sum"}
               for _ in range(2)]

    def int_init(key, shape, dtype):
        del key
        return (jnp.arange(np.prod(shape), dtype=jnp.float32)
                .reshape(shape) % 8).astype(dtype)

    for c in configs:
        c["embeddings_initializer"] = int_init

    def run(sched):
        de = DistributedEmbedding(configs, world_size=1, schedule=sched)
        # duplicate ids straddling the microbatch boundary
        cats = [jnp.asarray([[1, 1], [2, 3], [1, 2], [3, 3]], jnp.int32),
                jnp.asarray([[0, 5], [5, 5], [5, 0], [2, 2]], jnp.int32)]
        y = jnp.asarray([[1.0], [-2.0], [4.0], [-8.0]], jnp.float32)
        n = jnp.zeros((4, 13), jnp.float32)
        dp = {"w": jnp.ones((8, 1), jnp.float32),
              "v": jnp.zeros((13, 1), jnp.float32)}
        tx = optax.sgd(0.0)  # dense frozen: the sparse path is the test
        opt = SparseSGD()
        state = init_hybrid_state(de, opt, dp, tx, jax.random.key(0))
        step = make_hybrid_train_step(de, _loss_fn, tx, opt,
                                      lr_schedule=0.5,
                                      with_metrics=False, nan_guard=False)
        for _ in range(2):
            loss, state = step(state, cats, (n, y))
        return loss, state

    l0, s0 = run(None)
    l2, s2 = run(pipelined_schedule(2))
    assert float(l0) == float(l2)
    for a, b in zip(jax.tree_util.tree_leaves(s0.emb_params),
                    jax.tree_util.tree_leaves(s2.emb_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ K=1 bitwise


def test_k1_pipelined_step_bitwise_identical_to_serialized():
    configs = [{"input_dim": 24 + i, "output_dim": 4, "combiner": "sum"}
               for i in range(2)]

    def lower_text(sched):
        de = DistributedEmbedding(configs, world_size=1, schedule=sched)
        cats = [jax.ShapeDtypeStruct((8, 2), jnp.int32) for _ in configs]
        bt = (jax.ShapeDtypeStruct((8, 13), jnp.float32),
              jax.ShapeDtypeStruct((8, 1), jnp.float32))
        dp = {"w": jax.ShapeDtypeStruct((8, 1), jnp.float32),
              "v": jax.ShapeDtypeStruct((13, 1), jnp.float32)}
        tx = optax.sgd(0.1)
        opt = SparseSGD()
        state = jax.eval_shape(
            lambda k, d: init_hybrid_state(de, opt, d, tx, k),
            jax.random.key(0), dp)
        step = make_hybrid_train_step(de, _loss_fn, tx, opt,
                                      lr_schedule=0.1,
                                      with_metrics=False, nan_guard=True)
        return step.lower(state, cats, bt).as_text()

    assert lower_text(pipelined_schedule(1)) == lower_text(None)


# --------------------------------------------- schedule-audit acceptance


def test_pipelined_schedule_certifies_and_fraction_collapses():
    """The ROADMAP item 2 acceptance, in-suite: the compiled K=2 program
    must contain every declared overlap (declaration check), classify
    every declaring exchange overlappable, and collapse the modeled
    serialized fraction from the ~0.99 baseline to <= 0.7."""
    import sys
    sys.path.insert(0, __import__("os").path.dirname(
        __import__("os").path.dirname(__import__("os").path.abspath(
            __file__))))
    from tools._profcommon import build_case
    from distributed_embeddings_tpu.analysis import schedule_audit as sa

    de, cats, batch_tree, dense_params, loss_fn = build_case(
        "pipelined", WORLD, 256)
    mesh = Mesh(np.array(jax.devices()[:WORLD]), ("data",))
    rep = sa.audit_train_step(
        de, loss_fn, optax.sgd(0.5), SparseAdagrad(), cats, batch_tree,
        mesh=mesh, lr_schedule=0.3, dense_params=dense_params,
        contracts=sa.declared_overlap_contracts(de.schedule),
        label="pipelined-acceptance")
    assert rep.ok, rep.violations
    assert rep.serialized_collective_fraction <= 0.7
    a2a_phases = {c.phase_leaf for c in rep.collectives
                  if "all_to_all" in c.phase_leaf}
    assert {f"{r}_mb{k}" for r in ("id_all_to_all", "out_all_to_all",
                                   "grad_all_to_all")
            for k in range(2)} <= a2a_phases


def test_pipelined_fake_overlap_still_rejected():
    """A pipelined-SHAPED schedule declared against the SERIALIZED
    program must fail the declaration check: _mb phases match nothing
    compiled, which is itself the lie the auditor reports."""
    from distributed_embeddings_tpu.analysis import schedule_audit as sa
    from tools._profcommon import build_case

    de, cats, batch_tree, dense_params, loss_fn = build_case(
        "dense", WORLD, 256)
    mesh = Mesh(np.array(jax.devices()[:WORLD]), ("data",))
    rep = sa.audit_train_step(
        de, loss_fn, optax.sgd(0.5), SparseAdagrad(), cats, batch_tree,
        mesh=mesh, lr_schedule=0.3, dense_params=dense_params,
        schedule=pipelined_schedule(2), contracts=[],
        label="fake-pipelined")
    assert not rep.ok
    assert any("matches no compiled collective" in v
               for v in rep.violations)


# ------------------------------------------------------------ guard rails


def test_pipelined_rejects_mp_input():
    configs = [{"input_dim": 32, "output_dim": 4, "combiner": "sum"}
               for _ in range(8)]
    de = DistributedEmbedding(configs, world_size=WORLD, dp_input=False,
                              schedule=pipelined_schedule(2))
    mesh = Mesh(np.array(jax.devices()[:WORLD]), ("data",))
    step = make_hybrid_train_step(de, _loss_fn, optax.sgd(0.1),
                                  SparseSGD(), mesh=mesh)
    packed = de.pack_mp_inputs(
        [np.zeros((16, 3), np.int32) for _ in configs], mesh=mesh)
    bt = (jnp.zeros((16, 13), jnp.float32), jnp.zeros((16, 1),
                                                      jnp.float32))
    dp = {"w": jnp.zeros((32, 1), jnp.float32),
          "v": jnp.zeros((13, 1), jnp.float32)}
    state = init_hybrid_state(de, SparseSGD(), dp, optax.sgd(0.1),
                              jax.random.key(0), mesh=mesh)
    with pytest.raises(NotImplementedError, match="pipelined"):
        step(state, packed, bt)
