"""The comparison: gaps of norms by the worst leaf, limits, and what a missing
number does."""

from benchmarks.lib import check, manifest

train = manifest.load_family("dlrm").train


def test_worst_leaf_is_measured_against_its_norm_or_the_median_leaf():
    want = [1.0, 2.0, 1e-9]
    got = [1.1, 2.0, 2e-9]
    # the tiny leaf's gap is measured against the median leaf, not itself
    assert abs(check.worst_leaf_gap(got, want, want) - 0.1) < 1e-9
    assert check.worst_leaf_gap([0.0, 2.0, 0.0], want, want) == 1.0
    assert check.worst_leaf_gap([2.0, 2.0, 0.0], want, want) == 1.0
    assert check.worst_leaf_gap(got, want, want, skip=[True, False, False]) \
        < 1e-6


def _numbers(scale=1.0):
    leaf = [1.0, 2.0, 3.0]
    side = {"losses": [0.7, 0.69, 0.68]}
    for k in ("grad1_dense", "grad1_tables", "delta3_dense", "delta3_tables"):
        side[k] = [x * scale for x in leaf]
    return side


def test_train_numbers_and_the_verdict():
    ref = _numbers()
    same = train.train_numbers(_numbers(), ref)
    assert set(same) == {"loss1", "loss2", "loss3", "grad1_dense",
                         "grad1_tables", "delta3_dense", "delta3_tables"}
    assert max(same.values()) == 0.0
    limits = {k: 0.05 for k in same}
    assert check.verdict(same, limits)[0]
    unmoved = train.train_numbers(_numbers(0.0), ref)
    assert unmoved["delta3_dense"] == 1.0 and not check.verdict(unmoved,
                                                                limits)[0]
    ok, compared = check.verdict({"loss1": 0.0, "extra": 3.0}, {"loss1": 0.1,
                                                                "loss2": 0.1})
    assert not ok                      # loss2 has a limit and no number
    assert compared["extra"]["limit"] is None
    assert not check.verdict({"loss1": float("nan")}, {"loss1": 0.1})[0]


def test_a_leaf_with_no_gradient_is_left_out_of_the_change():
    ref, prog = _numbers(), _numbers()
    ref["grad1_dense"][0] = 1e-9        # nought to rounding
    prog["delta3_dense"][0] = 50.0      # moved by round-off alone
    assert train.train_numbers(prog, ref)["delta3_dense"] == 0.0


def test_half_rows_are_two_leaves_over_all_tables():
    """One table's few rows move by rounding alone; the leaf is every
    table's rows that one half of the batch touches alone."""
    per_table = [3.0, 4.0, 0.0, 1e-5] + [0.0, 0.0, 6.0, 8.0]
    first, second = train._two_halves(per_table)
    assert abs(first - 5.0) < 1e-9 and second == 10.0
    ref = dict(_numbers(), grad1_half_rows=[first, second])
    # the tiny table reads double on one side: by the table it would be the
    # worst leaf, over all tables it is nothing
    noisy = dict(_numbers(), grad1_half_rows=train._two_halves(
        [3.0, 4.0, 0.0, 2e-5] + [0.0, 0.0, 6.0, 8.0]))
    assert train.train_numbers(noisy, ref)["grad1_half_rows"] < 1e-9
    left_out = dict(_numbers(), grad1_half_rows=[2 * first, 0.0])
    assert train.train_numbers(left_out, ref)["grad1_half_rows"] == 1.0
