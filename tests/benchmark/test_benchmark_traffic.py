"""The generator: the same seed gives the same bytes, another seed other
bytes, and every seed the same amount of work."""

import numpy as np
import pytest

from benchmarks.lib import manifest
from benchmarks.lib import traffic as draws

traffic = manifest.load_family("dlrm").traffic
weights = manifest.load_family("dlrm").weights

SIZES = [1000, 3, 50000, 200]
ONE = {"kind": "train", "global_batch": 64, "id_alpha": 1.05,
       "hotness": {"kind": "one"}, "distinct_batches": 3}
MULTI = dict(ONE, hotness={"kind": "uniform", "min": 1, "max": 30,
                           "capacity": 64 * 16})
SERVE = {"kind": "serve", "rate_per_s": 50.0, "id_alpha": 1.05,
         "size_quantiles": {"p": [0, 0.5, 1.0], "samples": [100, 200, 700]}}


def _train_bytes(tr, seed):
    out = []
    for b in traffic.train_batches(tr, SIZES, 13, seed):
        out += [i.tobytes() for i in b.ids]
        out += [s.tobytes() for s in (b.splits or [])]
        out += [b.numerical.tobytes(), b.labels.tobytes()]
    return out


@pytest.mark.parametrize("tr", [ONE, MULTI], ids=["one_hot", "multi_hot"])
def test_stager_is_a_function_of_the_seed(tr):
    big = 2**31 + 12345
    assert _train_bytes(tr, big) == _train_bytes(tr, big)
    assert _train_bytes(tr, big) != _train_bytes(tr, big + 1)


def test_multi_hot_rows_fit_and_hold_the_same_lengths():
    a = traffic.train_batches(MULTI, SIZES, 13, 1)[0]
    b = traffic.train_batches(MULTI, SIZES, 13, 2)[0]
    for sa, sb, ids, size in zip(a.splits, b.splits, a.ids, SIZES):
        la, lb = np.diff(sa), np.diff(sb)
        assert sorted(la) == sorted(lb) and la.min() >= 1 and la.max() <= 30
        assert sa[-1] <= MULTI["hotness"]["capacity"]
        assert ids.max() < size and ids.min() >= 0


def test_row_lengths_trim_to_the_capacity():
    rng = np.random.default_rng(0)
    lens = draws.row_lengths(rng, 64, 1, 30, capacity=500)
    assert lens.sum() <= 500 and lens.min() >= 1


def test_requests_are_a_function_of_the_seed():
    def b(seed):
        s = traffic.serve_schedule(SERVE, SIZES, 13, seed, 4.0)
        return [s.due_s.tobytes(), s.offsets.tobytes(), s.numerical.tobytes()
                ] + [i.tobytes() for i in s.ids]
    assert b(7) == b(7) and b(7) != b(8)


def test_every_seed_offers_the_same_work():
    a = traffic.serve_schedule(SERVE, SIZES, 13, 1, 4.0)
    b = traffic.serve_schedule(SERVE, SIZES, 13, 2**31 + 5, 4.0)
    assert len(a) == len(b) == 200
    assert sorted(np.diff(a.offsets)) == sorted(np.diff(b.offsets))
    gaps = lambda s: sorted(np.diff(np.append(s.due_s, 4.0)))  # noqa: E731
    assert np.allclose(gaps(a), gaps(b)) and min(gaps(a)) > 0
    assert a.due_s[0] == 0 and a.due_s[-1] < 4.0
    sizes = np.diff(a.offsets)
    assert sizes.min() >= 100 and sizes.max() <= 700
    cats, num = a.request(3)
    assert len(cats) == len(SIZES) and len(cats[0]) == sizes[3] == len(num)


def test_burst_keeps_the_mean_rate_and_crowds_one_second_in_ten():
    tr = dict(SERVE, rate_per_s=100.0, burst={"every_s": 10.0, "factor": 4.0})
    s = traffic.serve_schedule(tr, SIZES, 13, 3, 20.0)
    assert len(s) == 2000 and np.all(np.diff(s.due_s) >= 0)
    in_burst = np.sum((s.due_s % 10.0) < 1.0)
    assert abs(in_burst - 800) < 40      # 4 x 100/s for 2 of 20 seconds
    assert s.due_s[-1] < 20.5


def test_weights_depend_on_table_row_column_and_seed():
    import jax.numpy as jnp
    w = jnp.asarray(weights.seed_words(2**31 + 9))
    a = np.asarray(weights.table_rows(0, 1000, np.arange(8), 128,
                                      jnp.bfloat16, w), np.float32)
    again = np.asarray(weights.table_rows(0, 1000, np.arange(8), 128,
                                          jnp.bfloat16, w), np.float32)
    other_table = np.asarray(weights.table_rows(1, 1000, np.arange(8), 128,
                                                jnp.bfloat16, w), np.float32)
    other_seed = np.asarray(weights.table_rows(
        0, 1000, np.arange(8), 128, jnp.bfloat16,
        jnp.asarray(weights.seed_words(2**31 + 10))), np.float32)
    assert np.array_equal(a, again)
    assert not np.array_equal(a, other_table)
    assert not np.array_equal(a, other_seed)
    assert len(np.unique(a)) > 300 and np.abs(a).max() <= 1.25 / np.sqrt(1000)
    # a column slice is the same columns of the whole row
    part = np.asarray(weights.base_rows(0, 1000, np.arange(8), 32, 32,
                                        jnp.bfloat16), np.float32)
    whole = np.asarray(weights.base_rows(0, 1000, np.arange(8), 0, 128,
                                         jnp.bfloat16), np.float32)
    assert np.array_equal(part, whole[:, 32:64])
