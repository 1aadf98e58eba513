"""DLRM example script smoke: mid-training eval cadence + AUC early stop
(VERDICT r3 Missing #3) driven end-to-end through ``examples/dlrm/main.py``
on an 8-virtual-device CPU mesh (via the script's DETPU_FORCE_CPU_DEVICES
test hook)."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_REPO, "examples", "dlrm", "main.py")


def _run(tmp_path, extra):
    env = dict(os.environ)
    env["DETPU_FORCE_CPU_DEVICES"] = "8"
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO] + env.get("PYTHONPATH", "").split(os.pathsep))
    cmd = [
        sys.executable, _SCRIPT,
        "--batch_size", "64",
        "--table_sizes", ",".join(["50"] * 10),
        "--embedding_dim", "8",
        "--bottom_mlp_dims", "16,8",
        "--top_mlp_dims", "16,1",
        "--num_numerical_features", "4",
        "--learning_rate", "0.1",
        "--checkpoint_out", str(tmp_path / "ckpt"),
    ] + extra
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_eval_interval_and_early_stop(tmp_path):
    out = _run(tmp_path, [
        "--num_batches", "8",
        "--eval_interval", "3",
        "--eval_batches", "2",
        "--auc_threshold", "0.0",  # any AUC satisfies: must stop at step 3
    ])
    assert "eval step: 3 AUC:" in out, out
    assert "threshold 0.0 reached at step 3" in out, out
    # early stop means the end-of-training eval must NOT run
    assert "Evaluation completed" not in out, out


@pytest.mark.slow
def test_final_eval_and_checkpoint(tmp_path):
    out = _run(tmp_path, [
        "--num_batches", "4",
        "--eval_interval", "0",
        "--eval_batches", "2",
    ])
    assert "Evaluation completed, AUC:" in out, out
    assert "saved 10 tables" in out, out


def _write_dataset(root, n, sizes, numf):
    """Tiny Criteo raw-binary dataset (reader layout, utils/data.py)."""
    import json

    import numpy as np

    rng = np.random.default_rng(0)
    for split, rows in (("train", n), ("test", n // 2)):
        d = root / split
        d.mkdir(parents=True, exist_ok=True)
        (rng.random(rows) < 0.3).astype(np.bool_).tofile(d / "label.bin")
        rng.normal(size=(rows, numf)).astype(np.float16).tofile(
            d / "numerical.bin")
        from distributed_embeddings_tpu.utils.data import (
            get_categorical_feature_type)
        for i, s in enumerate(sizes):
            rng.integers(0, s, size=rows).astype(
                get_categorical_feature_type(s)).tofile(d / f"cat_{i}.bin")
    (root / "model_size.json").write_text(
        json.dumps({f"c{i}": s - 1 for i, s in enumerate(sizes)}))


@pytest.mark.slow
def test_save_restore_resumes_data_stream(tmp_path):
    """--restore_state continues the dataset at the checkpointed step with
    globally numbered steps; resuming a COMPLETED run trains nothing
    extra (ADVICE r4 / r5 review findings)."""
    sizes = [50] * 10
    _write_dataset(tmp_path / "ds", 64 * 6, sizes, 4)
    common = ["--dataset_path", str(tmp_path / "ds"),
              "--eval_batches", "0", "--eval_interval", "0"]
    out1 = _run(tmp_path, common + [
        "--save_state", str(tmp_path / "state")])
    assert "saved full train state" in out1, out1
    out2 = _run(tmp_path, common + [
        "--restore_state", str(tmp_path / "state"),
        "--save_state", str(tmp_path / "state2")])
    assert "restored train state at step 6" in out2, out2
    # the 6-batch epoch was finished: the resumed run must yield NO new
    # training steps (an empty stream, not a silent extra epoch) — the
    # loop's per-step loss line never fires on an empty stream
    assert " loss:" not in out2, out2
    # and the re-saved state's step counter must still be 6
    from flax import serialization
    import numpy as np
    blob = (tmp_path / "state2" / "dense.msgpack").read_bytes()
    assert int(np.asarray(
        serialization.msgpack_restore(blob)["step"])) == 6


def test_obs_sidecar_comes_from_the_example(tmp_path, monkeypatch):
    """What ``tools/check_obs.py`` (``make check-obs``) runs: the toy-size
    example under ``DETPU_OBS=1`` with ``--metrics_out`` writes the
    step-metrics sidecar of the ISSUE 2 acceptance criterion."""
    import json

    from tools import check_obs

    side = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("DETPU_OBS", "1")
    _run(tmp_path, list(check_obs.EXAMPLE_ARGS) + ["--metrics_out", str(side)])
    assert check_obs.sidecar_errors(str(side)) == []
    recs = [json.loads(line) for line in side.read_text().splitlines()]
    steps = [r for r in recs if r["section"] == "step_metrics"]
    assert len(steps) == 4  # --num_batches 4 --metrics_interval 1
    for field in check_obs.REQUIRED_METRIC_FIELDS:
        assert len(steps[0]["metrics"][field]) == 8  # one entry a rank
    assert recs[-1]["section"] == "counters" and recs[-1]["final"]
    assert recs[-1]["counters"]["recompiles"] > 0
    # the check names what a sidecar lacks
    bare = tmp_path / "bare.jsonl"
    bare.write_text('{"section": "step_metrics", "metrics": {}}\n')
    assert len(check_obs.sidecar_errors(str(bare))) == 4
