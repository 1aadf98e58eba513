"""A checkout of the benchmark at toy sizes in a temporary directory: the
harness and its data files copied, the program linked in, and every
configuration and traffic file shrunk to what a CPU test can hold."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _edit(path, fn):
    with open(path) as f:
        data = json.load(f)
    fn(data)
    with open(path, "w") as f:
        json.dump(data, f)


FOUR_CHIP = "criteo1tb_train_x4"
TINY_LIMITS = {"loss1": 2e-3, "loss2": 2e-3, "loss3": 2e-3,
               "grad1_dense": 0.05, "delta3_dense": 0.05,
               "grad1_half_rows": 0.2, "logit_gap": 0.05, "misshapen": 0,
               "unanswered": 0}


def _add_four_chip_cell(path):
    """Play the PR that adds the four-chip cell: its files are in
    ``benchmarks/`` already, its entries in ``four_chip_entries.json``."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "four_chip_entries.json")) as f:
        add = json.load(f)

    def edit(bm):
        if any(w["name"] == FOUR_CHIP for w in bm["workloads"]):
            return
        bm["configs"].append(add["config"])
        bm["workloads"].append(add["workload"])
        for m in bm["end_to_end"] + bm["per_layer"]:
            if "kaggle_train_onehot" in m.get("workloads", ()):
                m["workloads"].append(FOUR_CHIP)
        bm["per_layer"].extend(add["per_layer"])
    _edit(path, edit)


def make(tmp: str) -> str:
    root = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    _add_four_chip_cell(os.path.join(root, "BENCHMARK.json"))
    os.symlink(os.path.join(ROOT, "distributed_embeddings_tpu"),
               os.path.join(root, "distributed_embeddings_tpu"))
    bench = os.path.join(root, "benchmarks")
    for name in os.listdir(os.path.join(bench, "configs")):
        def shrink(c):
            c["table_sizes"] = [min(s, 2000) for s in c["table_sizes"]]
            if c["plan"].get("column_slice_threshold"):
                c["plan"]["column_slice_threshold"] = 100_000
        _edit(os.path.join(bench, "configs", name), shrink)
    # the cells' limits are read on the chip at the cells' sizes; toy tables on
    # the CPU read wider, the table numbers most of all
    for name in os.listdir(os.path.join(bench, "workloads")):
        def loosen(w, name=name):
            w["limits"] = {k: TINY_LIMITS[k] for k in w["limits"]
                           if k in TINY_LIMITS}
            if "multihot" in name:
                # 64 samples of up to 30 ids over 2000 rows: the program sums
                # duplicates in bfloat16, and nothing past the first forward
                # and its dense gradient agrees to a percent
                w["limits"] = {"loss1": 0.02, "grad1_dense": 0.2}
        _edit(os.path.join(bench, "workloads", name), loosen)
    for name in os.listdir(os.path.join(bench, "traffic")):
        def shrink(t):
            if t["kind"] == "train":
                t["global_batch"] = 64 if t["hotness"]["kind"] != "one" else 256
                t["distinct_batches"] = 4
                if "capacity" in t["hotness"]:
                    t["hotness"]["capacity"] = t["global_batch"] * 16
            else:
                t["rate_per_s"] = 20
                t["serve"]["rungs"] = [256, 1024]
                t["serve"]["max_queue"] = 8000
        _edit(os.path.join(bench, "traffic", name), shrink)
    return root


def run_cell(root: str, workload: str, seed: int, trace: int,
             seconds: float = 1.0, timeout: float = 600):
    """``benchmarks/run.py --rehearse-cpu`` of the checkout as the driver
    would start it. Returns ``(returncode, last stdout line parsed, stderr)``."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse-cpu"],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    last = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    return p.returncode, last, p.stderr
