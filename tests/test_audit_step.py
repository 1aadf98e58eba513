"""SPMD invariant auditor (analysis/audit.py) against the hybrid step.

The acceptance contract: the collective census is EXACT — one id
all-to-all + one output all-to-all forward, one cotangent all-to-all
backward per step on a multi-device mesh (dense, ragged, and row-sliced
configs), zero collectives on a single worker — and seeded violations
(an extra psum, an all_gather, an f64 leak, a host callback) are flagged.
Everything here is abstract tracing under JAX_PLATFORMS=cpu (conftest):
no TPU, no execution of the audited program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_embeddings_tpu.analysis import (
    AuditError, audit_step_fn, audit_train_step, expected_collectives)
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseAdagrad, SparseSGD, init_hybrid_state,
    make_hybrid_train_step)
from tools.audit_step import build_case

WORLD = 8
B = 16

FULL_CENSUS = {"id_exchange_fwd": 1, "out_exchange_fwd": 1,
               "grad_exchange_bwd": 1}


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    assert len(devs) >= WORLD, "conftest should force 8 CPU devices"
    return Mesh(np.array(devs[:WORLD]), ("data",))


def _audit(name, world, mesh=None, loss_fn=None, **kw):
    de, cats, batch_tree, dense_params, default_loss = build_case(
        name, world, B)
    return audit_train_step(
        de, loss_fn or default_loss, optax.sgd(0.5), SparseAdagrad(),
        cats, batch_tree, mesh=mesh, lr_schedule=0.3,
        dense_params=dense_params, **kw)


@pytest.mark.parametrize("config", ["dense", "ragged", "row_sliced"])
def test_census_exact_8dev(config, mesh):
    """Acceptance: exactly 2 forward + 1 backward all-to-all on an
    8-device mesh for dense, ragged, and row-sliced configs; no
    all_gather/reduce_scatter; every donation intact."""
    rep = _audit(config, WORLD, mesh=mesh)
    assert rep.ok, rep.violations
    assert rep.a2a_census() == FULL_CENSUS
    assert rep.collective_counts.get("all_gather", 0) == 0
    assert rep.collective_counts.get("reduce_scatter", 0) == 0
    assert rep.donation["dropped"] == 0
    assert rep.donation["donated"] == rep.donation["expected"]


@pytest.mark.parametrize("config", ["dense", "ragged"])
def test_census_single_worker(config):
    """world_size == 1 runs the plan executor without any exchange: the
    census must be empty (a collective here would mean the single-worker
    path touches a mesh axis that does not exist)."""
    rep = _audit(config, 1)
    assert rep.ok, rep.violations
    assert rep.a2a_census() == {}
    assert rep.collective_counts.get("psum", 0) == 0


def test_instrumented_step_same_census(mesh):
    """with_metrics=True adds on-device metrics but must not add any
    collective: the instrumented and bare steps share one exchange
    contract (otherwise DETPU_OBS=1 would change what it measures)."""
    rep = _audit("dense", WORLD, mesh=mesh, with_metrics=True)
    assert rep.ok, rep.violations
    assert rep.a2a_census() == FULL_CENSUS


def test_mp_input_skips_id_exchange(mesh):
    """dp_input=False (MpInputs) skips the id all-to-all: census is one
    forward (outputs) + one backward (cotangents)."""
    configs = [{"input_dim": 20 + 6 * i, "output_dim": 4,
                "combiner": ["sum", None, "mean"][i % 3]}
               for i in range(10)]
    de = DistributedEmbedding(configs, world_size=WORLD, dp_input=False)
    rng = np.random.default_rng(0)
    inputs = []
    for cfg in configs:
        hot = 1 if cfg["combiner"] is None else 3
        shape = (B,) if hot == 1 else (B, hot)
        inputs.append(rng.integers(0, cfg["input_dim"], size=shape
                                   ).astype(np.int32))
    mp = de.pack_mp_inputs(inputs)

    def loss_fn(dp, emb_outs, batch):
        n, y = batch
        x = jnp.concatenate([e.reshape(e.shape[0], -1) for e in emb_outs],
                            axis=1)
        return jnp.mean((x @ dp["w"] + n @ dp["v"] - y) ** 2)

    cols = sum(int(c["output_dim"]) for c in configs)
    dense_params = {"w": jax.ShapeDtypeStruct((cols, 1), jnp.float32),
                    "v": jax.ShapeDtypeStruct((3, 1), jnp.float32)}
    batch_tree = (jax.ShapeDtypeStruct((B, 3), jnp.float32),
                  jax.ShapeDtypeStruct((B, 1), jnp.float32))
    rep = audit_train_step(de, loss_fn, optax.sgd(0.5), SparseAdagrad(),
                           mp, batch_tree, mesh=mesh,
                           dense_params=dense_params)
    assert rep.ok, rep.violations
    assert rep.a2a_census() == {"out_exchange_fwd": 1,
                                "grad_exchange_bwd": 1}


def test_extra_psum_flagged(mesh):
    """A deliberately broken step — one extra psum smuggled into the loss
    — must fail the census (the ISSUE acceptance seeding)."""
    _, _, _, _, base_loss = build_case("dense", WORLD, B)

    def bad_loss(dp, emb_outs, batch):
        loss = base_loss(dp, emb_outs, batch)
        return loss + 0.0 * lax.psum(jnp.sum(emb_outs[0]), "data")

    rep = _audit("dense", WORLD, mesh=mesh, loss_fn=bad_loss)
    assert not rep.ok
    assert any("psum census" in v for v in rep.violations), rep.violations
    with pytest.raises(AuditError):
        rep.raise_on_violations()


def test_extra_all_gather_flagged(mesh):
    """An all_gather anywhere in the step is the paper's forbidden
    failure mode (a slab/batch-sized collective the layout exists to
    avoid) — flagged regardless of where it hides."""
    _, _, _, _, base_loss = build_case("dense", WORLD, B)

    def bad_loss(dp, emb_outs, batch):
        g = lax.all_gather(emb_outs[0], "data")
        return base_loss(dp, emb_outs, batch) + 0.0 * jnp.sum(g)

    rep = _audit("dense", WORLD, mesh=mesh, loss_fn=bad_loss)
    assert not rep.ok
    assert any("all_gather" in v for v in rep.violations), rep.violations


def test_dtype_leak_flagged():
    """An x64 leak (f64 value inside the step) is flagged. Seeded by
    tracing under enable_x64 with a loss that upcasts — without x64 the
    cast is a silent no-op, which is exactly why only the auditor can see
    the difference."""
    with jax.enable_x64(True):
        _, _, _, _, base_loss = build_case("dense", 1, B)

        def leaky_loss(dp, emb_outs, batch):
            return base_loss(dp, emb_outs, batch).astype(jnp.float64)

        rep = _audit("dense", 1, loss_fn=leaky_loss)
    assert not rep.ok
    assert any("f64" in v for v in rep.violations), rep.violations
    assert rep.dtype_leaks


def test_host_interop_flagged():
    """A host callback inside the jitted step (a device->host sync per
    step) is flagged by the host-interop audit."""

    def chatty_loss(dp, emb_outs, batch):
        loss = jnp.mean(emb_outs[0])
        jax.debug.callback(lambda x: None, loss)
        return loss

    rep = _audit("dense", 1, loss_fn=chatty_loss)
    assert not rep.ok
    assert any("host interop" in v for v in rep.violations), rep.violations
    assert rep.host_interop


def test_weak_scalar_arg_flagged():
    """A Python scalar riding the jitted signature is a recompile hazard
    (weak->strong flips retrace); the scan flags it."""
    f = jax.jit(lambda x, s: x * s)
    rep = audit_step_fn(f, (jax.ShapeDtypeStruct((4,), jnp.float32), 2.0),
                        check_donation=False)
    assert rep.recompile_hazards
    assert not rep.ok


def test_expected_collectives_shape():
    """The contract generator matches the layer's configuration."""
    configs = [{"input_dim": 32, "output_dim": 8} for _ in range(8)]
    de = DistributedEmbedding(configs, world_size=WORLD)
    exp = expected_collectives(de, nan_guard=True, n_dense_leaves=2)
    assert exp["all_to_all"] == 3
    assert exp["psum"] == 4  # loss + 2 dense leaves + nanguard
    assert exp["all_gather"] == 0
    de1 = DistributedEmbedding(configs, world_size=1)
    assert expected_collectives(de1, nan_guard=True,
                                n_dense_leaves=2)["all_to_all"] == 0


def test_step_runs_under_transfer_guard(mesh, transfer_guard_compiled):
    """Run-time twin of the static audit: a compiled hybrid step
    dispatched under jax.transfer_guard('disallow') performs no implicit
    host<->device transfer (fixture compiles outside the guard, then the
    steady-state dispatches run inside it)."""
    step, state, cats, batch = transfer_guard_compiled
    with jax.transfer_guard("disallow"):
        for _ in range(2):
            loss, state = step(state, cats, batch)
    assert np.isfinite(float(np.asarray(loss)))


@pytest.fixture
def transfer_guard_compiled(mesh):
    """A compiled (warmed-up) 8-device hybrid step with explicitly staged
    inputs — what a production steady state looks like."""
    configs = [{"input_dim": 24 + i, "output_dim": 4, "combiner": None}
               for i in range(8)]
    de = DistributedEmbedding(configs, world_size=WORLD)
    rng = np.random.default_rng(0)
    shard = NamedSharding(mesh, P("data"))
    cats = [jax.device_put(
        rng.integers(0, c["input_dim"], size=(B,)).astype(np.int32), shard)
        for c in configs]
    num = jax.device_put(rng.normal(size=(B, 3)).astype(np.float32), shard)
    y = jax.device_put(rng.normal(size=(B, 1)).astype(np.float32), shard)

    def loss_fn(dp, emb_outs, batch):
        n, yy = batch
        x = jnp.concatenate([e.reshape(e.shape[0], -1) for e in emb_outs],
                            axis=1)
        return jnp.mean((x @ dp["w"] + n @ dp["v"] - yy) ** 2)

    tx = optax.sgd(0.5)
    emb_opt = SparseSGD()
    dense_params = {"w": jnp.zeros((8 * 4, 1)), "v": jnp.zeros((3, 1))}
    state = init_hybrid_state(de, emb_opt, dense_params, tx,
                              jax.random.key(0), mesh=mesh)
    step = make_hybrid_train_step(de, loss_fn, tx, emb_opt, mesh=mesh,
                                  lr_schedule=0.1)
    # compile + first transfer of baked constants happens OUTSIDE the
    # guard; the guarded dispatches then prove the steady state clean
    loss, state = step(state, cats, (num, y))
    jax.block_until_ready(loss)
    return step, state, cats, (num, y)
