"""The bags of small-table RAGGED slots are summed with no scatter
(``parallel/lookup.py:block_bag_sums``, scope ``lookup_w{k}_r/segment_prefix``):
each position's row is ``onehot(id) @ block`` on the MXU, the rows' float32
prefix within each tile of 128 positions is a lower-triangular matmul, and a
bag is read at its splits as a difference of prefixes, with the tile totals
where it crosses tiles.

Every test holds that forward to the scatter-add form the other ragged
groups keep (the size-class rule switched off, as in
``test_small_table_sums.py``) and to a float64 oracle: to the bit where the
sums are exact in any order (whole-number tables, power-of-two weights), and,
on a bfloat16 table, no bag further from float64 than the scatter-add's
bfloat16 sum.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_embeddings_tpu import Ragged
from distributed_embeddings_tpu.parallel import DistributedEmbedding
from distributed_embeddings_tpu.parallel import lookup
from distributed_embeddings_tpu.parallel import optimizers as opt

T = lookup._PREFIX_TILE
# (rows, width, combiner, kind): small tables of both widths, with ``mean``
# and per-id weights, beside a large one that keeps the scatter
TABLES = [(40, 128, "sum", "r"), (300, 128, "mean", "r"), (3, 128, "sum", "r"),
          (129, 128, "sum", "r"), (100, 32, "sum", "r"), (9, 32, "mean", "r"),
          (20, 128, "sum", "rw"), (33, 128, "mean", "rw"),
          (9000, 128, "sum", "r")]
# lengths that put bag ends on and across tile boundaries: empty, exactly
# the first tile, one starting on a boundary, one ending on one, an empty
# bag at a boundary, one id, one over three tiles (two boundaries crossed)
EDGES = [0, T, 2, T - 2, 0, 1, 300, 5]


def _whole_numbers(key, shape, dtype):
    return jnp.round(jax.random.uniform(key, shape, minval=-8, maxval=8)
                     ).astype(dtype)


def _csr(rng, rows, b, cap, edges=()):
    """One source's CSR: ``edges`` first, then lengths of 0-5 while they fit;
    ids in range with one past it and one before it among them; past the last
    split ids no bag owns, out of range too; weights powers of two."""
    lens = list(edges)
    while len(lens) < b:
        lens.append(int(rng.integers(0, 6)))
    lens = np.asarray(lens)
    lens[np.cumsum(lens) > cap] = 0
    ids = rng.integers(0, rows, size=cap)
    ids[rng.integers(0, cap, size=3)] = rows + 7
    ids[rng.integers(0, cap, size=3)] = -4
    ids[int(lens.sum()):] = rng.integers(-50, rows + 50,
                                         size=cap - int(lens.sum()))
    wts = rng.choice([0.5, 1.0, 2.0], size=cap)
    return ids, np.concatenate([[0], np.cumsum(lens)]), wts


def _inputs(rng, world, b, cap, tables=TABLES, edges=()):
    cats, srcs = [], []
    for rows, _, _, kind in tables:
        parts = [_csr(rng, rows, b, cap, edges) for _ in range(world)]
        srcs.append(parts)
        cats.append(Ragged(
            values=jnp.asarray(np.concatenate([p[0] for p in parts]),
                               jnp.int32),
            row_splits=jnp.asarray(np.concatenate([p[1] for p in parts]),
                                   jnp.int32),
            weights=(jnp.asarray(np.concatenate([p[2] for p in parts]),
                                 jnp.float32) if kind == "rw" else None)))
    return cats, srcs


def _forward(world, cats, tables=TABLES, init=_whole_numbers,
             dtype=jnp.float32, **kw):
    """Outputs (host arrays), the plan, and the host tables."""
    de = DistributedEmbedding(
        [{"input_dim": r, "output_dim": w, "combiner": c,
          "embeddings_initializer": init} for r, w, c, _ in tables],
        world_size=world, **kw)
    if world == 1:
        params = de.init(jax.random.key(1), dtype=dtype)
        outs = jax.jit(lambda p, c: de(p, c))(params, cats)
    else:
        mesh = Mesh(np.array(jax.devices()[:world]), ("data",))
        params = de.init(jax.random.key(1), dtype=dtype, mesh=mesh)
        outs = jax.jit(jax.shard_map(
            lambda p, c: tuple(de(p, list(c))), mesh=mesh,
            in_specs=(P("data"), P("data")), out_specs=P("data")))(
                params, tuple(cats))
    plan = next(iter(de._plan_cache.values()))
    return ([np.asarray(o) for o in outs], plan,
            [np.asarray(t, np.float64) for t in de.get_weights(params)])


def _oracle(tables_host, srcs, tables=TABLES, sliced=()):
    """float64 bags: an id out of range reads the table's last (or first)
    row, or zero on a row-sliced table; ``mean`` divides by the id count."""
    outs = []
    for i, ((rows, _, comb, kind), parts) in enumerate(zip(tables, srcs)):
        tab = tables_host[i]
        bags = []
        for ids, splits, wts in parts:
            for s, e in zip(splits[:-1], splits[1:]):
                x = ids[s:e]
                if i in sliced:
                    rows_of = np.where(((x >= 0) & (x < rows))[:, None],
                                       tab[np.clip(x, 0, rows - 1)], 0.0)
                else:
                    rows_of = tab[np.clip(x, 0, rows - 1)]
                if kind == "rw":
                    rows_of = rows_of * wts[s:e, None]
                bag = rows_of.sum(axis=0)
                bags.append(bag / max(e - s, 1) if comb == "mean" else bag)
        outs.append(np.stack(bags))
    return outs


def _all_stream(monkeypatch):
    monkeypatch.setattr(opt, "sums_densely", lambda *a, **k: False)


@pytest.mark.parametrize("world,masked_reads", [(1, False), (1, True),
                                                (4, False)])
def test_bags_match_the_scatter_form_and_float64(world, masked_reads,
                                                 monkeypatch):
    """Against the scatter-add form and float64, to the bit: bags on and
    across tile boundaries, empty bags, ids out of range, garbage past the
    last split, ``mean``, ``"rw"`` weights, both widths, a capacity that is
    not a multiple of the tile; on four ranks dead slots too."""
    b, cap = (64, 700) if world == 1 else (16, 300)
    cats, srcs = _inputs(np.random.default_rng(3), world, b, cap,
                         edges=EDGES if world == 1 else EDGES[:6])
    got, plan, host = _forward(world, cats, masked_reads=masked_reads)
    small = [gi for gi, g in enumerate(plan.groups) if g.block]
    assert {(plan.groups[gi].kind, plan.groups[gi].width) for gi in small
            } == {("r", 128), ("r", 32), ("rw", 128)}
    assert all(plan.groups[gi].hot % T for gi in small)
    if world > 1:
        assert any((plan.valid[gi] == 0).any() for gi in small)
    _all_stream(monkeypatch)
    want, stream_plan, _ = _forward(world, cats, masked_reads=masked_reads)
    assert stream_plan.dense_slots == 0
    ref = _oracle(host, srcs)
    for i, (x, y) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(x, y, err_msg=f"input {i}")
        if masked_reads:   # out-of-range ids read zero: the oracle's clip
            continue       # does not hold, the scatter form above does
        # exact sums; a ``mean`` quotient rounds once either way
        np.testing.assert_array_equal(x, ref[i].astype(x.dtype),
                                      err_msg=f"input {i}")


def test_a_row_sliced_slot(monkeypatch):
    """A table cut into row ranges: each slice's slot reads its own range
    and zero outside it, and the slices' bags sum to the table's."""
    tables = [(300, 128, "sum", "r"), (40, 128, "mean", "r"),
              (20, 128, "sum", "r"), (9000, 128, "sum", "r")]
    thr = 300 * 128 // 2 + 1
    cats, srcs = _inputs(np.random.default_rng(4), 4, 16, 300, tables,
                         edges=EDGES[:6])
    got, plan, host = _forward(4, cats, tables, row_slice=thr)
    assert any(g.block and plan.rsliced[gi].any()
               for gi, g in enumerate(plan.groups))
    _all_stream(monkeypatch)
    want, _, _ = _forward(4, cats, tables, row_slice=thr)
    ref = _oracle(host, srcs, tables, sliced=(0, 3))   # both over thr
    for i, (x, y) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(x, y, err_msg=f"input {i}")
        np.testing.assert_array_equal(x, ref[i].astype(x.dtype),
                                      err_msg=f"input {i}")


def _same_sign(key, shape, dtype):
    return jax.random.uniform(key, shape, minval=0.01, maxval=1.0
                              ).astype(dtype)


def test_float32_bags_are_no_worse_than_the_bfloat16_scatter(monkeypatch):
    """A bfloat16 table whose values share a sign, at the multi-hot cell's
    capacity of 262 144 and its 1-30 ids a bag: every bag read through the
    prefixes is, element by element, no further from float64 than the
    scatter-add's bfloat16 sum of the same rows, and a bag of one id at the
    end of the capacity reads its row exactly."""
    cap = 262144
    rng = np.random.default_rng(5)
    lens = []
    while sum(lens) < cap - 1:
        lens.append(int(min(rng.integers(1, 31), cap - 1 - sum(lens))))
    lens.append(1)
    ids = rng.integers(0, 100, size=cap)
    splits = np.concatenate([[0], np.cumsum(lens)])
    assert splits[-1] == cap
    tables = [(100, 128, "sum", "r")]
    cats = [Ragged(values=jnp.asarray(ids, jnp.int32),
                   row_splits=jnp.asarray(splits, jnp.int32))]
    (got,), plan, (tab,) = _forward(1, cats, tables, init=_same_sign,
                                    dtype=jnp.bfloat16)
    assert plan.groups[0].block == (128,) and got.dtype == jnp.bfloat16
    _all_stream(monkeypatch)
    (want,), _, _ = _forward(1, cats, tables, init=_same_sign,
                             dtype=jnp.bfloat16)
    ref = _oracle([tab], [[(ids, splits, None)]], tables)[0]
    err = np.abs(got.astype(np.float64) - ref)
    err_scatter = np.abs(want.astype(np.float64) - ref)
    assert (err <= err_scatter).all()
    assert err.mean() < err_scatter.mean()
    np.testing.assert_array_equal(got[-1].astype(np.float64), tab[ids[-1]])
