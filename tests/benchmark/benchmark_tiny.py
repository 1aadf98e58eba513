"""A checkout of the benchmark at toy sizes in a temporary directory: the
harness and its data files copied, the program linked in, and every
configuration, traffic and workload file that has an overlay under
``benchmarks/tiny/<kind>/<name>.json`` (the keys to replace for a CPU
rehearsal) rewritten with it. A file without an overlay is copied as it is."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


KINDS = ("configs", "traffic", "workloads")


def apply_overlays(root: str) -> None:
    """Replace, in every data file of the checkout at ``root`` that has an
    overlay, the keys that the overlay holds. A file that already reads as
    its overlay says is left alone, so a second call touches only what was
    added since the first."""
    bench = os.path.join(root, "benchmarks")
    for kind in KINDS:
        tiny = os.path.join(bench, "tiny", kind)
        for name in sorted(os.listdir(tiny)) if os.path.isdir(tiny) else ():
            with open(os.path.join(tiny, name)) as f:
                over = json.load(f)
            path = os.path.join(bench, kind, name)
            with open(path) as f:
                data = json.load(f)
            if any(data.get(k) != v for k, v in over.items()):
                with open(path, "w") as f:
                    json.dump(dict(data, **over), f)


def make(tmp: str) -> str:
    root = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "distributed_embeddings_tpu"),
               os.path.join(root, "distributed_embeddings_tpu"))
    apply_overlays(root)
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
