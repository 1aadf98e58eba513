"""Small-table slots leave the scatter's stream (``parallel/plan.py`` size
class, ``parallel/apply.py:small_table_sums``, the cost rule
``parallel/optimizers.py:sums_densely``).

A dense slot whose table is small sends the width's stream one dense block,
``onehot(ids)^T @ cotangents`` over the table's rows, in place of a row an
id. That is the same update: every test here holds the step with the blocks
to the step with every slot on the stream (the rule switched off), to the bit,
on cotangents whose per-row sums are exact in any order (small integers,
power-of-two scales), for every sparse optimizer and every state component.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from distributed_embeddings_tpu.analysis.hlo_census import (
    apply_contracts, census_step_fn, lookup_contracts)
from distributed_embeddings_tpu.analysis.plan_audit import audit_plan
from distributed_embeddings_tpu.parallel import (
    DistributedEmbedding, SparseAdagrad, SparseAdam, SparseMomentum,
    SparseSGD, init_hybrid_state, make_hybrid_train_step)
from distributed_embeddings_tpu.parallel import optimizers as opt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B = 512     # global batch: a 128-row block pays from some 130 ids a slot
WORLD = 8

# (rows, width, combiner, hotness): small and large tables at a full-tile
# width and a lane-packed one, a ``mean`` slot (hotness 4: its divisor is
# exact) and a ``sum`` slot of hotness 2
TABLES = [(40, 128, None, 1), (300, 128, None, 1), (7, 128, "mean", 4),
          (129, 128, "sum", 2), (6000, 128, None, 1), (9000, 128, None, 1),
          (8000, 32, None, 1), (5000, 32, None, 1),
          (100, 128, None, 1), (64, 128, None, 1), (33, 128, None, 1),
          (50, 128, None, 1), (77, 128, None, 1), (90, 128, None, 1),
          (64, 32, None, 1), (200, 32, None, 1),
          (20, 128, None, 1), (21, 128, None, 1), (22, 128, None, 1),
          (23, 128, None, 1), (24, 128, None, 1), (25, 128, None, 1),
          (11, 32, None, 1)]
OPTIMIZERS = {
    "sgd": SparseSGD,
    "adagrad_dense_regime": SparseAdagrad,
    "adagrad_sparse_regime": lambda: SparseAdagrad(dense_apply_ratio=None),
    # a power-of-two decay keeps the trace exact, so that whether the
    # compiler contracts ``g + m * trace`` into one rounding does not show
    "momentum": lambda: SparseMomentum(momentum=0.5),
    "adam": SparseAdam,
}


def _all_stream(monkeypatch):
    monkeypatch.setattr(opt, "sums_densely", lambda *a, **k: False)


def _whole_numbers(key, shape, dtype):
    # with integer cotangents and power-of-two rates every partial sum into
    # a row is exact, so the order of SGD's adds does not show
    return jnp.round(jax.random.uniform(key, shape, minval=-8, maxval=8)
                     ).astype(dtype)


def _embedding(world, tables=TABLES, **kw):
    return DistributedEmbedding(
        [{"input_dim": r, "output_dim": w, "combiner": c,
          "embeddings_initializer": _whole_numbers}
         for r, w, c, _ in tables], world_size=world, **kw)


def _loss(dense, emb_outs, coef):
    # linear in the activations: the cotangents ARE the coefficients
    x = jnp.concatenate([e.reshape(e.shape[0], -1) for e in emb_outs], 1)
    return jnp.sum(x * coef) + 0.0 * jnp.sum(dense["w"])


def _batches(rng, tables=TABLES, steps=3, poison=None):
    """Ids with duplicates (a dozen hot rows a table), rows that no step
    touches, and ids out of range on both sides; integer coefficients."""
    cols = sum(w for _, w, _, _ in tables)
    out = []
    for s in range(steps):
        cats = []
        for rows, _, comb, hot in tables:
            ids = rng.integers(0, min(rows, 12), size=(B, hot))
            ids[rng.random(size=ids.shape) < 0.05] = rows + 3   # past it
            ids[rng.random(size=ids.shape) < 0.05] = -2         # before it
            cats.append(jnp.asarray(ids if comb else ids[:, 0], jnp.int32))
        coef = rng.integers(-4, 5, size=(B, cols)).astype(np.float32)
        if poison == s:
            coef[0, 0] = np.nan
        out.append((cats, jnp.asarray(coef)))
    return out


def _train(world, optimizer, batches, tables=TABLES, **kw):
    """Final ``(state, plan, state before the last step)`` after the
    batches, from a fixed key."""
    de = _embedding(world, tables, **kw)
    mesh = (Mesh(np.array(jax.devices()[:world]), ("data",))
            if world > 1 else None)
    tx = optax.sgd(0.5)
    state = init_hybrid_state(de, optimizer, {"w": jnp.ones((1,))}, tx,
                              jax.random.PRNGKey(3), mesh=mesh)
    step = make_hybrid_train_step(de, _loss, tx, optimizer, mesh=mesh,
                                  lr_schedule=0.5, with_metrics=False)
    before = None
    for cats, coef in batches:
        before = jax.tree.map(np.asarray, (state.emb_params,
                                           state.emb_opt_state))
        _, state = step(state, cats, coef)[:2]
    plan = next(iter(de._plan_cache.values()), None)
    return jax.tree.map(np.asarray, (state.emb_params, state.emb_opt_state)
                        ), plan, before


def _assert_same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb) and la
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


def _assert_only_rows_under_12_moved(got, fresh, plan):
    """Of every width-128 block slot's table, in the slab and in every state
    component of its shape: rows 12 and up are ``fresh``'s bits, and some
    row under 12 is not."""
    slab = got[0]["w128"]
    moved = False
    for gi, g in enumerate(plan.groups):
        if not (g.block and g.width == 128):
            continue
        for rows, roff in zip(plan.rows[gi][0], plan.roff[gi][0]):
            for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(fresh)):
                if x.shape != slab.shape:
                    continue
                np.testing.assert_array_equal(x[roff + 12:roff + rows],
                                              y[roff + 12:roff + rows])
                moved |= bool((x[roff:roff + 12] != y[roff:roff + 12]).any())
    assert moved


@pytest.mark.parametrize("world", [1, WORLD])
@pytest.mark.parametrize("name", list(OPTIMIZERS))
def test_blocks_train_what_the_stream_trains(name, world, monkeypatch):
    batches = _batches(np.random.default_rng(5))
    got, plan, _ = _train(world, OPTIMIZERS[name](), batches)
    # the mechanism engaged, at both widths, with the mean slot among them
    small = [g for g in plan.groups if g.block]
    assert {g.width for g in small} == {128, 32}
    assert any(g.hot == 4 for g in small)
    assert plan.dense_slots == sum(len(g.block) for g in small) > 0
    if world > 1:   # a dead slot among the blocks
        assert any((plan.valid[gi] == 0).any()
                   for gi, g in enumerate(plan.groups) if g.block)
    _all_stream(monkeypatch)
    want, stream_plan, _ = _train(world, OPTIMIZERS[name](), batches)
    assert stream_plan.dense_slots == 0
    _assert_same(got, want)


@pytest.mark.parametrize("name", ["sgd", "momentum"])
def test_row_sliced_small_table(name, monkeypatch):
    """A table cut into row ranges whose slices are small: each slice's block
    takes the ids of its own range (``rbase``) and drops the others."""
    tables = [(40000, 128, None, 1), (600, 128, None, 1),
              (300, 128, None, 1), (64, 128, None, 1)] + [
                  (20 + i, 128, None, 1) for i in range(6)]
    thr = 600 * 128 // 2 + 1      # the 600-row table goes in two ranges
    rng = np.random.default_rng(9)
    batches = _batches(rng, tables)
    # ids over the whole sliced table, so both ranges see some
    batches = [([c if i != 1 else jnp.asarray(
        rng.integers(0, 600, size=B), jnp.int32) for i, c in enumerate(cats)],
        coef) for cats, coef in batches]
    got, plan, _ = _train(WORLD, OPTIMIZERS[name](), batches, tables,
                          row_slice=thr)
    assert any(g.block and plan.rsliced[gi].any()
               for gi, g in enumerate(plan.groups))
    _all_stream(monkeypatch)
    want, _, _ = _train(WORLD, OPTIMIZERS[name](), batches, tables,
                        row_slice=thr)
    _assert_same(got, want)


@pytest.mark.parametrize("name", ["momentum", "adam", "adagrad_sparse_regime"])
def test_untouched_rows_and_their_state_stay(name):
    """Lazy semantics survive the block: a row no id of any step named (ids
    stay under 12) keeps its value and every state component, to the bit."""
    optimizer = OPTIMIZERS[name]()
    got, plan, _ = _train(1, optimizer, _batches(np.random.default_rng(5)))
    fresh, _, _ = _train(1, optimizer, [])
    _assert_only_rows_under_12_moved(got, fresh, plan)


@pytest.mark.parametrize("world", [1, WORLD])
def test_a_skipped_step_changes_nothing(world):
    """``enable=False`` (the guard's verdict on a non-finite batch): every
    block row goes to the sentinel with the stream's, so slabs and state are
    the bits they were."""
    batches = _batches(np.random.default_rng(5), poison=2)
    got, plan, before = _train(world, SparseAdagrad(), batches)
    assert plan.dense_slots
    _assert_same(got, before)


# ------------------------------------------------------------------ the plan


def _plan_of(name, b, enc=("d", 1), combiner=None):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        cfg = json.load(f)
    plan = cfg.get("plan", {})
    de = DistributedEmbedding(
        [{"input_dim": s, "output_dim": cfg["embedding_dim"],
          "combiner": combiner} for s in cfg["table_sizes"]],
        world_size=cfg["chips"],
        **({"strategy": plan["strategy"]} if "strategy" in plan else {}),
        **({"column_slice_threshold": int(plan["column_slice_threshold"])}
           if plan.get("column_slice_threshold") else {}))
    return cfg, de, de._get_plan([enc] * len(cfg["table_sizes"]), b)


def test_rule_on_the_benchmarks_tables():
    """At the cells' batch the rule takes the Kaggle tables of 3 to 5 683
    rows (the 14 under 3 200 and the two of 5 652 and 5 683: a block of
    5 760 rows costs 0.84 of what its ids cost the sweep) and the 11
    Criteo-1TB tables under 2 300 rows; 7 121 and 7 421 rows stay rows of
    the stream (1.08)."""
    cfg, _, plan = _plan_of("dlrm-kaggle", 65536)
    small = sorted(s for s in cfg["table_sizes"]
                   if opt.sums_densely(s, 65536))
    assert small == sorted([1460, 583, 305, 24, 633, 3, 3194, 27, 10, 2173,
                            4, 18, 15, 105, 5652, 5683])
    assert plan.dense_slots == len(small)
    assert plan.dense_rows == len(small) * 65536
    assert plan.stream_rows() == {
        128: (26 - len(small)) * 65536 + sum(opt.block_rows(s)
                                             for s in small)}
    cfg, _, plan = _plan_of("dlrm-criteo1tb", 16384)
    small = sorted(s for s in cfg["table_sizes"]
                   if opt.sums_densely(s, 65536))
    assert small == sorted([4, 1544, 64, 11, 2209, 156, 5, 977, 15, 109, 37])
    assert (plan.dense_slots, plan.dense_rows) == (3, 3 * 65536)
    # more ids make no table of 12 000 rows and up small
    assert not opt.sums_densely(11939, 2 ** 30)
    assert not opt.sums_densely(64, 2 ** 20, hot=257)   # bf16 counts to 256


MULTIHOT_CAPACITY = 262144   # benchmarks/traffic/train_multihot_b16384.json


def test_rule_on_the_benchmarks_multihot_signature():
    """The multi-hot cell: every feature ragged in a capacity of 262 144. A
    slot sends the sweep a row a POSITION, so the rule is the one-hot cell's
    at four times the ids: the same 16 tables (a block of 7 450 rows would
    cost what its positions cost; 5 683 and 12 517 are the nearest), 21 120
    block rows, and the width's stream is short enough for ``sort_fused``."""
    cfg, _, plan = _plan_of("dlrm-kaggle", 16384,
                            ("r", MULTIHOT_CAPACITY), "sum")
    small = sorted((s for s in cfg["table_sizes"]
                    if opt.sums_densely(s, MULTIHOT_CAPACITY)), reverse=True)
    assert small == sorted((s for s in cfg["table_sizes"] if s <= 5683),
                           reverse=True) and len(small) == 16
    assert opt.sums_densely(7400, MULTIHOT_CAPACITY)
    assert not opt.sums_densely(7500, MULTIHOT_CAPACITY)
    large, blocks = plan.groups
    assert (large.kind, large.n, large.block) == ("r", 10, ())
    assert (blocks.kind, blocks.n) == ("r", 16)
    assert blocks.block == tuple(opt.block_rows(s) for s in small)
    assert sum(blocks.block) == 21_120
    assert (plan.dense_slots, plan.dense_rows) == (16, 16 * MULTIHOT_CAPACITY)
    stream = plan.stream_rows()[128]
    assert stream == 10 * MULTIHOT_CAPACITY + 21_120 == 2_642_560
    slab_bytes = sum(cfg["table_sizes"]) * 128 * 2
    assert opt.scatter_form(stream, slab_bytes) == "sort_fused"
    assert opt.scatter_form(26 * MULTIHOT_CAPACITY,
                            slab_bytes) == "unsorted"


def _digest(plan):
    h = hashlib.sha256(repr((plan.b, plan.groups, plan.instances, plan.l_max,
                             plan.s_max)).encode())
    for arrays in (plan.rows, plan.roff, plan.valid, plan.mean, plan.rbase,
                   plan.rsliced):
        for a in arrays:
            h.update(a.tobytes() + str(a.dtype).encode())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,b,enc,combiner,digest", [
    ("dlrm-kaggle", 65536, ("d", 1), None, "dd39f94af4ea0673"),
    ("dlrm-kaggle", 4096, ("d", 1), None, "230be8307d516250"),
    ("dlrm-kaggle", 256, ("d", 1), None, "bdbcb9f518e02ac2"),
    ("dlrm-criteo1tb", 16384, ("d", 1), None, "ca85017b1ebc4b5a"),
    ("dlrm-kaggle", 512, ("d", 3), "sum", "b85cb57830ed1493"),
])
def test_dense_signatures_plan_as_before_the_ragged_class(name, b, enc,
                                                          combiner, digest):
    """The ragged size class moved no dense signature's plan: groups,
    instances, offsets and every plan tensor hash to what the tree before it
    gave (``fc65086``; the digests were taken there)."""
    assert _digest(_plan_of(name, b, enc, combiner)[2]) == digest


def test_size_class_is_rank_uniform():
    """One layout for every rank: the small slots meet in a group of their
    own behind the large ones, each rank's largest first, a slot's block the
    largest table any rank has there in whole tiles; the large group keeps
    worker order."""
    _, de, plan = _plan_of("dlrm-criteo1tb", 16384)
    kinds = [(g.width, bool(g.block), g.n) for g in plan.groups]
    assert kinds == [(32, False, 5), (128, False, 3), (128, True, 3)]
    assert plan.groups[2].block == (2304, 128, 128)
    gi = 2
    g = plan.groups[gi]
    rows = np.where(plan.valid[gi] > 0, plan.rows[gi], 0)
    assert (np.diff(rows, axis=1) <= 0).all()          # largest first
    assert g.block == tuple(opt.block_rows(int(v)) for v in rows.max(axis=0))
    assert all(v % 128 == 0 for v in g.block)
    # regions tile the id block and the output row as before
    goff = col = 0
    for h in plan.groups:
        assert (h.goff, h.col) == (goff, col)
        goff, col = goff + h.n * h.blen, col + h.n * h.width
    assert (plan.l_max, plan.s_max) == (goff, col)
    # every instance lies in the group of its table's class
    for inst in plan.instances:
        tid = de.strategy.input_table_map[inst.input_id]
        r = plan.rows[inst.group][inst.rank, inst.slot0]
        assert bool(plan.groups[inst.group].block) == (
            plan.groups[inst.group].width == 128
            and opt.sums_densely(int(r), 65536)), tid
    # instances stay in worker order
    assert [(i.rank, i.input_id) for i in plan.instances] == [
        (r, i) for r in range(4) for i in de.strategy.input_ids_list[r]]


def test_a_class_that_only_pads_is_not_made():
    """Two classes pad each to its fullest rank. Where every rank has one
    table, half of them small, a class of their own would double the slots:
    the rule keeps one group, in worker order, and the plan is the one the
    all-stream rule gives."""
    tables = [(40, 128, None, 1), (9000, 128, None, 1)] * 4
    de = _embedding(WORLD, tables)
    plan = de._get_plan([("d", 1)] * len(tables), B // WORLD)
    assert opt.sums_densely(40, B)
    assert plan.dense_slots == 0 and len(plan.groups) == 1
    assert plan.stream_rows() == {128: B}


@pytest.mark.parametrize("kind", ["r", "rw"])
def test_a_ragged_class_that_only_pads_is_not_made(kind):
    """As above for ragged slots, whose padding is priced by the capacity: a
    dead ragged slot is gathered and swept a row a position like a live one."""
    tables = [(40, 128, "sum", 1), (9000, 128, "sum", 1)] * 4
    de = _embedding(WORLD, tables)
    cap = 4 * B // WORLD
    assert opt.sums_densely(40, WORLD * cap)
    plan = de._get_plan([(kind, cap)] * len(tables), B // WORLD)
    assert plan.dense_slots == plan.dense_rows == 0 and len(plan.groups) == 1
    assert plan.stream_rows() == {128: WORLD * cap}
    g, = plan.groups
    assert (g.kind, g.hot, g.n, g.block) == (kind, cap, 1, ())


def test_plan_audit_counts_ragged_blocks():
    """The multi-hot cell's report: the 16 ragged slots and the rows a step
    they no longer send, and the scatter priced over the shorter stream."""
    cfg, de, plan = _plan_of("dlrm-kaggle", 16384,
                             ("r", MULTIHOT_CAPACITY), "sum")
    rep = audit_plan(de, 16384, param_dtype="bfloat16",
                     encodings=[("r", MULTIHOT_CAPACITY)] * 26)
    slab, = rep.slabs
    assert slab.stream_rows == 2_642_560
    assert (rep.dense_slots, rep.dense_rows) == (16, 16 * MULTIHOT_CAPACITY)
    assert slab.scatter_form == "sort_fused"
    assert slab.scatter_ms == pytest.approx(opt.scatter_ns(
        "sort_fused", 2_642_560, slab.rank_bytes) / 1e6)
    assert "16 small-table slot(s)" in rep.markdown()
    assert [g["kind"] for g in rep.to_json()["groups"]] == ["r", "r"]
    assert rep.to_json()["groups"][1]["block_rows"] == list(
        plan.groups[1].block)


def test_plan_audit_prices_the_shorter_stream():
    _, de, plan = _plan_of("dlrm-kaggle", 65536)
    rep = audit_plan(de, 65536, param_dtype="bfloat16")
    slab, = rep.slabs
    assert slab.stream_rows == plan.stream_rows()[128] < 26 * 65536
    assert (rep.dense_slots, rep.dense_rows) == (plan.dense_slots,
                                                 plan.dense_rows)
    assert slab.scatter_form == "sort_fused"
    assert "small-table slot(s)" in rep.markdown()
    assert rep.to_json()["groups"][-1]["block_rows"] == list(
        plan.groups[-1].block)


def test_kaggle_step_scatters_the_shorter_stream(monkeypatch):
    """The one-hot cell's step at its real shapes, abstract: ONE scatter into
    the width-128 slab, of the plan's stream rows where it was 1 703 936, and
    the sums hold no row operation."""
    cfg, de, plan = _plan_of("dlrm-kaggle", 65536)
    calls = []
    real = opt._sorted_scatter_add

    def spy(slab, ids, vals):
        calls.append((slab.shape[0], ids.shape[0], vals.shape))
        return real(slab, ids, vals)

    monkeypatch.setattr(opt, "_sorted_scatter_add", spy)
    n = len(cfg["table_sizes"])
    tx = optax.sgd(0.1)
    state = jax.eval_shape(
        lambda k: init_hybrid_state(de, SparseSGD(), {"w": jnp.ones((1,))},
                                    tx, k, dtype=jnp.bfloat16),
        jax.random.key(0))
    step = make_hybrid_train_step(de, _loss, tx, SparseSGD(),
                                  lr_schedule=0.5, with_metrics=False)
    args = (state, [jax.ShapeDtypeStruct((65536,), jnp.int32)] * n,
            jax.ShapeDtypeStruct((65536, 128 * n), jnp.float32))
    rep = census_step_fn(step, args, contracts=apply_contracts())
    assert rep.ok, rep.violations
    stream = plan.stream_rows()[128]
    assert calls == [(sum(cfg["table_sizes"]), stream, (stream, 128))]
    assert stream == 10 * 65536 + sum(plan.groups[-1].block) == 676_480
    assert rep.passes("*scatter_sort_fused", "scatter") == 1
    assert rep.passes("*small_sum", "fusion") + rep.phases[
        "sparse_apply/sparse_apply_w128/small_sum"].instructions > 0


def test_multihot_step_scatters_the_shorter_stream(monkeypatch):
    """The multi-hot cell's step at its real shapes, abstract: ONE scatter
    into the width-128 slab, of 2 642 560 rows where it was 6 815 744, in the
    form ``sort_fused``; the ragged sums hold no row operation (the ``take``
    that expands the cotangents is the stream path's and lies outside), and
    the forward's bags of the same 16 slots are prefix differences: the
    ``segment_prefix`` scope is there and holds no scatter and no sort."""
    from distributed_embeddings_tpu import Ragged

    cfg, de, plan = _plan_of("dlrm-kaggle", 16384,
                             ("r", MULTIHOT_CAPACITY), "sum")
    calls = []
    real = opt._sorted_scatter_add

    def spy(slab, ids, vals):
        calls.append((slab.shape[0], ids.shape[0], vals.shape))
        return real(slab, ids, vals)

    monkeypatch.setattr(opt, "_sorted_scatter_add", spy)
    n = len(cfg["table_sizes"])
    tx = optax.sgd(0.1)
    state = jax.eval_shape(
        lambda k: init_hybrid_state(de, SparseSGD(), {"w": jnp.ones((1,))},
                                    tx, k, dtype=jnp.bfloat16),
        jax.random.key(0))
    step = make_hybrid_train_step(de, _loss, tx, SparseSGD(),
                                  lr_schedule=0.5, with_metrics=False)
    rag = Ragged(values=jax.ShapeDtypeStruct((MULTIHOT_CAPACITY,), jnp.int32),
                 row_splits=jax.ShapeDtypeStruct((16385,), jnp.int32))
    args = (state, [rag] * n,
            jax.ShapeDtypeStruct((16384, 128 * n), jnp.float32))
    rep = census_step_fn(step, args,
                         contracts=apply_contracts() + lookup_contracts())
    assert rep.ok, rep.violations
    assert calls == [(sum(cfg["table_sizes"]), 2_642_560, (2_642_560, 128))]
    assert rep.passes("*scatter_sort_fused", "scatter") == 1
    assert not rep.passes("*scatter_unsorted", "scatter")
    sums = rep.phases["sparse_apply/sparse_apply_w128/ragged_sum"]
    assert sums.fusions + sums.instructions > 0
    assert not any(sums.counts.get(k) for k in ("sort", "scatter", "cumsum",
                                                "gather"))
    bags = rep.phases["embedding_forward/lookup_w128_r/segment_prefix"]
    assert bags.fusions > 0 and bags.counts.get("gather")
    assert rep.passes("*segment_prefix", "scatter") == 0
    assert rep.passes("*segment_prefix", "sort") == 0
