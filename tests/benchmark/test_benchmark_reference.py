"""The plain reference against the hybrid step at a small size, the control
that has to fail, and the run driven with the timed path broken underneath."""

import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import check, manifest, runner

import benchmark_tiny

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 8}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchmark_tiny.make(str(tmp_path_factory.mktemp("bench")))


def _first_steps(cell, seed):
    fam, cfg, tr = cell.family, cell.config, cell.traffic
    built = fam.build(cfg, tr, seed)
    batches = fam.train_batches(cfg, tr, seed)
    staged = [fam.stage(built, b) for b in batches]
    step = fam.train_step(built, tr)
    prog, _ = fam.first_steps(built, tr, step, staged, batches, seed)
    return prog, batches


@pytest.fixture(scope="module")
def one_hot(root):
    cell = manifest.Cell("kaggle_train_onehot", root)
    seed = 2**31 + 77
    prog, batches = _first_steps(cell, seed)
    ref = cell.family.reference_numbers(cell.config, cell.traffic, batches,
                                        seed)
    return cell, seed, prog, batches, ref


def test_the_hybrid_step_follows_the_plain_reference(one_hot):
    cell, seed, prog, batches, ref = one_hot
    numbers = cell.family.train_numbers(prog, ref)
    ok, compared = check.verdict(numbers, cell.own["limits"])
    assert ok, compared
    assert ref["losses"][0] != ref["losses"][1]


@pytest.mark.parametrize("wrong", ["float8", "half_batch", "state_unchanged"])
def test_the_control_and_the_planted_faults_fail(one_hot, wrong):
    cell, seed, prog, batches, ref = one_hot
    kw = {"precision": "float8"} if wrong == "float8" else {"fault": wrong}
    bad = cell.family.reference_numbers(cell.config, cell.traffic, batches,
                                        seed, **kw)
    numbers = cell.family.train_numbers(bad, ref)
    ok, compared = check.verdict(numbers, cell.own["limits"])
    assert not ok, compared
    if wrong == "state_unchanged":
        assert numbers["delta3_dense"] == pytest.approx(1.0)
        assert numbers["delta3_tables"] == pytest.approx(1.0)


def test_half_of_a_multi_hot_batch_left_out_fails(root):
    cell = manifest.Cell("kaggle_train_multihot", root)
    fam, seed = cell.family, 2**31 + 78
    batches = fam.train_batches(cell.config, cell.traffic, seed)
    ref = fam.reference_numbers(cell.config, cell.traffic, batches, seed)
    bad = fam.reference_numbers(cell.config, cell.traffic, batches, seed,
                                fault="half_batch")
    # held to the one-hot cell's toy limits: the multi-hot cell's own leave
    # out every number past the first forward and its dense gradient
    limits = manifest.Cell("kaggle_train_onehot", root).own["limits"]
    ok, compared = check.verdict(fam.train_numbers(bad, ref), limits)
    assert not ok, compared


def _run(root, name, hooks, seed=2**31 + 5):
    cell = manifest.Cell(name, root)
    return runner.run(cell, seed, 0.5, False, DEVICE, rehearse=True,
                      t_start=time.perf_counter(), hooks=hooks)


def _state_unchanged(step):
    def broken(state, cats, batch):
        keep = jax.tree.map(jnp.copy, state)
        loss, _ = step(state, cats, batch)
        return loss, keep
    return broken


def _half_batch(step):
    def broken(state, cats, batch):
        h = cats[0].shape[0] // 2
        return step(state, [c[:h] for c in cats],
                    jax.tree.map(lambda x: x[:h], batch))
    return broken


def _altered_answer(results):
    first = min(results)
    results[first].predictions = results[first].predictions + 0.5
    for r in results.values():
        r.predictions = r.predictions + 0.5


@pytest.mark.parametrize("name,hooks", [
    ("kaggle_train_onehot", {"step": _state_unchanged}),
    ("kaggle_train_onehot", {"step": _half_batch}),
    ("kaggle_serve_ranking", {"results": _altered_answer}),
], ids=["state_unchanged", "half_batch", "answer_altered"])
def test_a_run_over_a_broken_timed_path_is_not_correct(root, name, hooks):
    assert _run(root, name, {})["correct"] is True
    out = _run(root, name, hooks)
    assert out["correct"] is False, out["compared"]


def test_a_run_without_the_exchange_is_not_correct(root):
    assert _run(root, "criteo1tb_train_x4", {})["correct"] is True
    fam = manifest.Cell("criteo1tb_train_x4", root).family
    with fam.exchange_left_out():
        out = _run(root, "criteo1tb_train_x4", {}, seed=2**31 + 6)
    assert out["correct"] is False, out["compared"]


def test_narrow_rows_on_one_chip_hold_the_seeds_weights(root):
    """A width that packs several rows into a physical row gets its values
    from ``program._fill_packed`` and not through the initializer hook: what
    the program's own lookup then returns is the seed's rows, on one device
    with no mesh too."""
    cell = manifest.Cell("kaggle_train_onehot", root)
    program, train, weights = (cell.family.program, cell.family.train,
                               cell.family.weights)
    cfg = dict(cell.config, embedding_dim=32, bottom_mlp=[64, 32])
    seed = 2**31 + 79
    built = program.build(cfg, seed)
    assert built.mesh is None and built.de.phys_w[32] == 128
    watch = train.RowWatch(built, jnp.asarray(
        weights.seed_words(seed)))
    every = [jnp.arange(int(s)) for s in cfg["table_sizes"]]
    assert max(watch.change_norms_of(built.state, every)) == 0.0
    # and they are not all alike: another seed's rows differ
    other = train.RowWatch(built, jnp.asarray(
        weights.seed_words(seed + 1)))
    assert min(other.change_norms_of(built.state, every)) > 0.0
