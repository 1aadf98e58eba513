"""The forms of the sparse apply's one scatter-add (``parallel/optimizers.py``).

Every form is the same mathematics: what ``slab.at[ids].add(vals,
mode="drop")`` gives. Which form a slab gets is a cost comparison in the
stream's rows and the slab's bytes, whose constants were read on the chip
inside the benchmark's cells (``PERF.md`` section 6, PR 31); here the rule is
held to the forms those readings chose, and the form's name to the scope the
scatter runs under.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from distributed_embeddings_tpu.ops import packed_slab as ps
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding,
    SparseSGD,
    init_hybrid_state,
    make_hybrid_train_step,
)
from distributed_embeddings_tpu.parallel import optimizers as opt

GIB = 2 ** 30


def _stream(case, rng):
    """``(slab, ids, vals)`` of one case. Values are small integers, so every
    order of accumulation gives the same sum to the bit, in bf16 too."""
    if case == "packed_w32":
        # a width-32 slab, four logical rows to a physical row: the scatter
        # gets physical ids and lane-placed rows from expand_update_rows
        logical_rows, w = 64, 32
        slab = jnp.asarray(rng.integers(-8, 8, size=(logical_rows // 4, 128)),
                           jnp.bfloat16)
        lids = jnp.asarray(
            np.concatenate([rng.integers(0, logical_rows, size=90),
                            np.full(6, logical_rows)]), jnp.int32)
        lvals = jnp.asarray(rng.integers(-4, 4, size=(96, w)), jnp.bfloat16)
        ids, vals = ps.expand_update_rows(lvals, lids, w)
        return slab, ids, vals
    rows = 40
    slab = jnp.asarray(rng.integers(-8, 8, size=(rows, 128)), jnp.float32)
    if case == "disabled":
        # what the guard's enable=False path hands over: every id the sentinel
        ids = jnp.full((200,), rows, jnp.int32)
    else:
        # duplicates (200 ids over 40 rows), the sentinel and ids past it,
        # negative ids (jnp's .at wraps those in range, drops the rest)
        ids = jnp.asarray(
            np.concatenate([rng.integers(0, rows, size=170),
                            np.full(10, rows), rng.integers(rows, 3 * rows, 10),
                            rng.integers(-2 * rows, 0, size=10)]), jnp.int32)
        ids = jnp.asarray(rng.permutation(np.asarray(ids)))
    vals = jnp.asarray(rng.integers(-4, 4, size=(200, 128)), jnp.float32)
    return slab, ids, vals


@pytest.mark.parametrize("case", ["duplicates_sentinel_negative",
                                  "packed_w32", "disabled"])
@pytest.mark.parametrize("form", opt.SCATTER_FORMS)
def test_every_form_is_the_same_scatter_add(form, case):
    slab, ids, vals = _stream(case, np.random.default_rng(7))
    want = np.asarray(slab.at[ids].add(vals, mode="drop"))
    got = jax.jit(opt._scatter_add_as, static_argnums=0)(form, slab, ids,
                                                         vals)
    assert got.dtype == slab.dtype
    np.testing.assert_array_equal(np.asarray(got), want)
    if case == "disabled":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(slab))
    else:
        assert (np.asarray(got) != np.asarray(slab)).any()


def test_packed_scatter_is_the_logical_scatter():
    """Through ``expand_update_rows`` a width-32 stream lands where the same
    stream lands in the unpacked ``[rows, 32]`` table, under the form the rule
    picks for it."""
    rng = np.random.default_rng(11)
    rows, w = 64, 32
    table = rng.integers(-8, 8, size=(rows, w)).astype(np.float32)
    lids = rng.integers(0, rows + 1, size=120).astype(np.int32)
    lvals = rng.integers(-4, 4, size=(120, w)).astype(np.float32)
    ids, vals = ps.expand_update_rows(jnp.asarray(lvals), jnp.asarray(lids), w)
    got = opt._sorted_scatter_add(jnp.asarray(ps.pack_rows_np(table, w)),
                                  ids, vals)
    want = jnp.asarray(table).at[jnp.asarray(lids)].add(jnp.asarray(lvals),
                                                        mode="drop")
    np.testing.assert_array_equal(ps.unpack_rows_np(np.asarray(got), w),
                                  np.asarray(want))


# the benchmark's four (stream rows, slab bytes) pairs and the form PERF.md
# section 6 (PR 31) records for each
RECORDED = [
    ("kaggle_train_onehot", 1_703_936, 8.05 * GIB, "sort_fused"),
    ("kaggle_train_multihot", 6_815_744, 8.05 * GIB, "unsorted"),
    ("criteo1tb_train_x4.w32", 327_680, 10.95 * GIB, "dedup_rows"),
    ("criteo1tb_train_x4.w128", 344_064, 0.71 * GIB, "sort_fused"),
]


@pytest.mark.parametrize("cell,rows,slab_bytes,form", RECORDED,
                         ids=[r[0] for r in RECORDED])
def test_rule_returns_the_recorded_form(cell, rows, slab_bytes, form):
    assert opt.scatter_form(rows, int(slab_bytes)) == form


def test_rule_is_a_cost_comparison_in_rows_and_bytes():
    """Every form has a rate a row and a rate a byte; the pick is the
    cheapest admitted form, and ``unsorted`` is admitted only where XLA's
    own lowering has been read as the sweep."""
    assert set(opt._SCATTER_NS) == set(opt.SCATTER_FORMS)
    for rows in (1, 10 ** 3, 10 ** 5, 10 ** 7):
        for slab_bytes in (2 ** k for k in range(8, 40)):
            form = opt.scatter_form(rows, slab_bytes)
            cost = {f: opt.scatter_ns(f, rows, slab_bytes)
                    for f in opt.SCATTER_FORMS}
            if slab_bytes > opt._XLA_SWEEPS_BELOW_BYTES_A_ROW * rows:
                assert form != "unsorted"
                del cost["unsorted"]
            assert cost[form] == min(cost.values())
        # a slab large enough is never swept for a stream this short
        assert opt.scatter_form(rows, 2 ** 40) == "dedup_rows"


def test_row_at_a_time_loop_covers_every_distinct_row(monkeypatch):
    """More distinct rows than one step of the loop holds: the trip count
    follows the stream, and the padding past it is never read as a row."""
    monkeypatch.setattr(opt, "_RMW_CHUNK", 16)
    rng = np.random.default_rng(5)
    slab = jnp.asarray(rng.integers(-8, 8, size=(300, 128)), jnp.float32)
    for distinct in (1, 16, 17, 100):
        ids = jnp.asarray(rng.permutation(np.repeat(
            rng.choice(300, size=distinct, replace=False), 3))[:-1],
            jnp.int32)
        vals = jnp.asarray(rng.integers(-4, 4, size=(len(ids), 128)),
                           jnp.float32)
        got = jax.jit(opt._scatter_add_as, static_argnums=0)(
            "dedup_rows", slab, ids, vals)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(slab.at[ids].add(vals, mode="drop")))


def test_form_is_in_the_lowered_steps_scope_names():
    """The choice is static, so the scope says it: the step's scatter runs
    under ``sparse_apply_w{k}/scatter_<form>``."""
    world = 2
    configs = [{"input_dim": 50, "output_dim": 128, "combiner": None},
               {"input_dim": 30, "output_dim": 128, "combiner": None}]
    de = DistributedEmbedding(configs, world_size=world)
    mesh = Mesh(np.array(jax.devices()[:world]), ("data",))

    def loss_fn(dense, emb_outs, batch):
        x = jnp.concatenate([e.reshape(e.shape[0], -1) for e in emb_outs], 1)
        return jnp.mean((x @ dense["w"] - batch) ** 2)

    dense = {"w": jnp.ones((256, 1), jnp.float32)}
    tx = optax.sgd(0.1)
    state = init_hybrid_state(de, SparseSGD(), dense, tx,
                              jax.random.PRNGKey(0), mesh=mesh)
    step = make_hybrid_train_step(de, loss_fn, tx, SparseSGD(), mesh=mesh,
                                  lr_schedule=0.1)
    batch = 8
    cats = [jnp.zeros((batch, 1), jnp.int32) for _ in configs]
    text = step.lower(state, cats, jnp.zeros((batch, 1), jnp.float32)
                      ).as_text(debug_info=True)
    slab = state.emb_params["w128"]
    plan, = de._plan_cache.values()
    assert plan.stream_rows() == {128: batch * len(configs) // world}
    form = opt.scatter_form(plan.stream_rows()[128],
                            slab[0].size * slab.dtype.itemsize)
    assert f"detpu/sparse_apply_w128/detpu/scatter_{form}" in text
    others = [f for f in opt.SCATTER_FORMS if f != form]
    assert not any(f"scatter_{f}" in text for f in others)
