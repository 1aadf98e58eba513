"""Each cell end to end as the driver starts it, on the CPU at toy sizes (the
four-chip cell on four virtual devices): exit code, the last line's keys, and
the refusals that must print no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import manifest

import benchmark_tiny

CELLS = [w["name"] for w in manifest.manifest()["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchmark_tiny.make(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(root, cell):
    BM = manifest.manifest(root)
    chips = next(w["chips"] for w in BM["workloads"] if w["name"] == cell)
    for trace in (0, 1):
        rc, last, err = benchmark_tiny.run_cell(root, cell, 2**31 + 11 + trace,
                                                trace)
        assert rc == 0, err[-3000:]
        assert list(last)[-1] == "compared"
        assert {"correct", "attempted", "failed", "metrics", "device"} \
            <= set(last)
        assert last["correct"] is True and last["failed"] == 0
        assert last["attempted"] > 0
        assert last["device"]["platform"] == "cpu"
        assert last["device"]["count"] == chips
        tail = [ln for ln in err.splitlines() if ln.startswith("check ")]
        assert tail[-1] == "check correct True"
        assert len(tail) == len(last["compared"]) + 1
        names = set(last["metrics"])
        wanted = {m["name"] for m in (BM["per_layer"] if trace
                                      else BM["end_to_end"])
                  if cell in m.get("workloads", CELLS)}
        assert names <= wanted
        if trace:
            assert {"busy_s", "window_s"} <= set(last["device"])
            assert "breakdown" in last
            # a rehearsal has no device: nothing read from a device trace
            assert not any(m["source"] == "device_trace"
                           for m in BM["per_layer"] if m["name"] in names)
        else:
            assert names == wanted
            # no number from a CPU run under a device metric's name
            assert all(v["value"] is None for v in last["metrics"].values())


def test_no_accelerator_prints_no_result(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_checkout_without_the_program_prints_no_result(root, tmp_path):
    bare = tmp_path / "bare"
    shutil.copytree(os.path.join(root, "benchmarks"), bare / "benchmarks")
    shutil.copy(os.path.join(root, "BENCHMARK.json"), bare)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse-cpu"],
        cwd=bare, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert json.loads(open(bare / "BENCHMARK.json").read())["command"]
