"""Small-table RAGGED slots leave the scatter's stream (``parallel/apply.py``:
the ragged branch of ``cotangent_width_streams`` hands ``small_table_sums``
the rows its ``take`` expanded, one column of the one-hot a position of the
capacity, under ``sparse_apply_w{k}/ragged_sum``).

As in ``test_small_table_sums.py``, whose helpers these tests share (a file
of their own so that the two spread over the suite's workers): the step with
the blocks against the step with every slot on the stream (the rule switched
off), to the bit, on cotangents, weights and ``mean`` divisors whose sums are
exact in any order.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_embeddings_tpu import Ragged
from distributed_embeddings_tpu.parallel import SparseAdagrad
from test_small_table_sums import (B, OPTIMIZERS, _all_stream,
                                   _assert_only_rows_under_12_moved,
                                   _assert_same, _train)

# (rows, width, combiner, kind): ragged features over small and large tables,
# plain and with per-id weights (a group of their own), at both widths
RAGGED = [(40, 128, "sum", "r"), (300, 128, "mean", "r"),
          (100, 128, "sum", "r"), (64, 128, "sum", "r"),
          (33, 128, "mean", "r"), (50, 128, "sum", "r"),
          (77, 128, "sum", "r"), (6000, 128, "sum", "r"),
          (9000, 128, "mean", "r"),
          (7, 128, "mean", "rw"), (129, 128, "sum", "rw"),
          (20, 128, "sum", "rw"), (21, 128, "mean", "rw"),
          (22, 128, "sum", "rw"), (23, 128, "sum", "rw"),
          (24, 128, "mean", "rw"), (25, 128, "sum", "rw"),
          (26, 128, "sum", "rw"), (5000, 128, "sum", "rw"),
          (64, 32, "sum", "r"), (200, 32, "sum", "r"),
          (11, 32, "mean", "r"), (12, 32, "sum", "r"),
          (13, 32, "sum", "r"), (8000, 32, "sum", "r")]
RAGGED_WORLD = 4
HOT = 4     # capacity a sample


def _ragged_batches(rng, world, tables=RAGGED, steps=3, poison=None):
    """Per shard a CSR of ``B / world`` segments in a capacity of ``HOT`` a
    sample: empty segments, an id twice in one segment (ids stay under 12),
    ids out of range on both sides, and past the lengths positions that hold
    ids no segment owns. Lengths and weights are powers of two, so ``mean``
    and the weighted rows stay exact."""
    b = B // world
    cols = sum(w for _, w, _, _ in tables)
    out = []
    for s in range(steps):
        cats = []
        for rows, _, _, kind in tables:
            vals, splits, wts = [], [], []
            for _ in range(world):
                lens = rng.choice([0, 1, 2, 4], size=b)
                ids = rng.integers(0, min(rows, 12), size=HOT * b)
                ids[rng.random(size=ids.shape) < 0.05] = rows + 3
                ids[rng.random(size=ids.shape) < 0.05] = -2
                vals.append(ids)
                splits.append(np.concatenate([[0], np.cumsum(lens)]))
                wts.append(rng.choice([0.5, 1.0, 2.0], size=HOT * b))
            cats.append(Ragged(
                values=jnp.asarray(np.concatenate(vals), jnp.int32),
                row_splits=jnp.asarray(np.concatenate(splits), jnp.int32),
                weights=(jnp.asarray(np.concatenate(wts), jnp.float32)
                         if kind == "rw" else None)))
        coef = rng.integers(-4, 5, size=(B, cols)).astype(np.float32)
        if poison == s:
            coef[0, 0] = np.nan
        out.append((cats, jnp.asarray(coef)))
    return out


@pytest.mark.parametrize("name,world", [("sgd", 1), ("adam", 1)] + [
    (name, RAGGED_WORLD) for name in OPTIMIZERS])
def test_ragged_blocks_train_what_the_stream_trains(name, world, monkeypatch):
    """A ragged group's blocks against the same slots on the stream, to the
    bit: ``"r"`` and ``"rw"``, ``sum`` and ``mean``, both widths."""
    batches = _ragged_batches(np.random.default_rng(7), world)
    got, plan, _ = _train(world, OPTIMIZERS[name](), batches, RAGGED)
    small = [gi for gi, g in enumerate(plan.groups) if g.block]
    assert {(plan.groups[gi].kind, plan.groups[gi].width) for gi in small
            } >= {("r", 128), ("rw", 128)}
    assert any(0 < plan.mean[gi].sum() < plan.mean[gi].size for gi in small)
    assert plan.dense_slots == sum(plan.groups[gi].n for gi in small)
    # a ragged slot would have sent a row a position of every source
    assert plan.dense_rows == plan.dense_slots * HOT * B
    if world > 1:   # a dead slot among the blocks
        assert any((plan.valid[gi] == 0).any() for gi in small)
    else:
        assert ("r", 32) in {(plan.groups[gi].kind, plan.groups[gi].width)
                             for gi in small}
    _all_stream(monkeypatch)
    want, stream_plan, _ = _train(world, OPTIMIZERS[name](), batches, RAGGED)
    assert stream_plan.dense_slots == 0
    _assert_same(got, want)


@pytest.mark.parametrize("name", ["momentum", "adam"])
def test_ragged_untouched_rows_and_their_state_stay(name):
    """Lazy semantics survive the ragged block: ids stay under 12, so a row
    past them keeps its value and every state component, to the bit."""
    optimizer = OPTIMIZERS[name]()
    got, plan, _ = _train(1, optimizer,
                          _ragged_batches(np.random.default_rng(7), 1), RAGGED)
    fresh, _, _ = _train(1, optimizer, [], RAGGED)
    _assert_only_rows_under_12_moved(got, fresh, plan)


@pytest.mark.parametrize("world", [1, RAGGED_WORLD])
def test_a_skipped_step_changes_nothing_ragged(world):
    """The guard's verdict on a non-finite batch reaches the ragged blocks'
    rows with the stream's."""
    batches = _ragged_batches(np.random.default_rng(7), world, poison=2)
    got, plan, before = _train(world, SparseAdagrad(), batches, RAGGED)
    assert any(g.block and g.kind != "d" for g in plan.groups)
    _assert_same(got, before)


def test_the_pipelined_step_merges_streams_that_hold_ragged_blocks(
        monkeypatch):
    """Two microbatches, each with its own blocks of the same table rows: the
    one scatter a width slab takes both, and the trajectory is the
    serialized step's (which sums each block over the whole batch)."""
    import test_pipeline as tp
    from distributed_embeddings_tpu.parallel import plan as plan_mod

    plans = []
    real = plan_mod.build_plan

    def spy(*args, **kw):
        plans.append(real(*args, **kw))
        return plans[-1]

    monkeypatch.setattr(plan_mod, "build_plan", spy)
    tp._assert_equivalent("ragged", 1, "adam", False)
    assert {p.b for p in plans} == {64, 32}     # serialized, a microbatch
    assert all(p.dense_slots == len(p.groups[0].block) == 8
               and p.groups[0].kind == "r" for p in plans)
