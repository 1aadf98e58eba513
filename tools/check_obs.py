#!/usr/bin/env python
"""Verify gate for the observability layer (run by ``make verify``).

Two checks, both in clean subprocesses so they test what a user's process
actually does:

1. ``utils.obs`` imports cleanly under ``JAX_PLATFORMS=cpu`` and — like
   ``utils.runtime`` — without pulling jax in at module scope (importing
   the obs/counters half must never risk a backend touch).
2. ``DETPU_OBS=1 python examples/dlrm/main.py`` at toy size
   (:data:`EXAMPLE_ARGS`) with ``--metrics_out <file>`` emits a parseable
   step-metrics sidecar containing the acceptance fields: exchange bytes,
   per-rank routed-id counts, capacity-overflow counters, and a recompile
   count (the ISSUE 2 acceptance criterion, kept green by CI).

Exit 0 when both pass; 1 with a readable reason otherwise.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REQUIRED_METRIC_FIELDS = ("id_a2a_bytes", "ids_routed", "id_overflow")

EXAMPLE = os.path.join(REPO, "examples", "dlrm", "main.py")
#: toy size: 10 tables of 50 rows x 8, four steps on 8 virtual CPU devices,
#: a step-metrics record every step
EXAMPLE_ARGS = (
    "--batch_size", "64", "--table_sizes", ",".join(["50"] * 10),
    "--embedding_dim", "8", "--bottom_mlp_dims", "16,8",
    "--top_mlp_dims", "16,1", "--num_numerical_features", "4",
    "--learning_rate", "0.1", "--num_batches", "4", "--eval_batches", "0",
    "--eval_interval", "0", "--metrics_interval", "1")


def check_import() -> list:
    """obs must import (and count) cleanly under ``JAX_PLATFORMS=cpu`` in
    a fresh process, and the module source must not import jax at module
    scope (the :mod:`utils.runtime` never-touch-a-backend-at-import
    contract; the *package* path unavoidably imports jax via compat). The
    static half is the detlint ``module-scope-jax`` rule — shared here so
    the AST walking lives in exactly one place."""
    sys.path.insert(0, REPO)
    from tools import detlint

    errors = [
        f"{f.path}:{f.line}: {f.message}"
        for f in detlint.run(rule_names=["module-scope-jax"])]
    code = (
        "import distributed_embeddings_tpu.utils.obs as obs\n"
        "obs.counter_inc('selftest'); assert obs.counters()['selftest'] == 1\n"
        "print('obs import OK')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("DETPU_OBS", None)
    try:
        r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                           capture_output=True, text=True, timeout=120)
    except subprocess.TimeoutExpired:
        errors.append("obs import check timed out after 120s")
        return errors
    if r.returncode != 0:
        errors.append(f"obs import check failed (rc={r.returncode}): "
                      f"{(r.stderr or r.stdout).strip()[-500:]}")
    return errors


def sidecar_errors(path: str) -> list:
    """What a step-metrics sidecar lacks of the acceptance fields."""
    try:
        with open(path, encoding="utf-8") as f:
            recs = [json.loads(line) for line in f if line.strip()]
    except (OSError, json.JSONDecodeError) as e:
        return [f"metrics sidecar unreadable: {e}"]
    errors = []
    steps = [x for x in recs if x.get("section") == "step_metrics"]
    if not steps:
        errors.append("sidecar has no step_metrics record")
    for field in REQUIRED_METRIC_FIELDS:
        if not any(field in x.get("metrics", {}) for x in steps):
            errors.append(f"no step_metrics record carries {field!r}")
    counter_recs = [x for x in recs if x.get("section") == "counters"]
    if not any("recompiles" in x.get("counters", {})
               for x in counter_recs):
        errors.append("sidecar has no recompile count")
    return errors


def check_example_sidecar() -> list:
    """A DETPU_OBS=1 run of the example must write a metrics sidecar whose
    records carry the acceptance fields."""
    with tempfile.TemporaryDirectory(prefix="detpu_check_obs_") as tmp:
        side = os.path.join(tmp, "metrics.jsonl")
        env = dict(os.environ, JAX_PLATFORMS="cpu", DETPU_OBS="1",
                   DETPU_FORCE_CPU_DEVICES="8", PYTHONPATH=REPO)
        cmd = [sys.executable, EXAMPLE, *EXAMPLE_ARGS, "--metrics_out", side,
               "--checkpoint_out", os.path.join(tmp, "ckpt")]
        try:
            r = subprocess.run(cmd, env=env, cwd=tmp, capture_output=True,
                               text=True, timeout=600)
        except subprocess.TimeoutExpired:
            return ["the example timed out after 600s — wedged backend or "
                    "grossly overloaded machine; re-run `DETPU_OBS=1 "
                    "DETPU_FORCE_CPU_DEVICES=8 python examples/dlrm/main.py "
                    f"{' '.join(EXAMPLE_ARGS)} --metrics_out <file>` to "
                    "see where"]
        if r.returncode != 0:
            return [f"the example failed (rc={r.returncode}): "
                    f"{(r.stderr or r.stdout).strip()[-500:]}"]
        return sidecar_errors(side)


def main() -> int:
    errors = check_import()
    if not errors:  # a broken import would make the example's check noise
        errors += check_example_sidecar()
    for e in errors:
        print(f"check_obs: {e}", file=sys.stderr)
    if not errors:
        print("check_obs: OK (obs imports cleanly; a DETPU_OBS=1 run of "
              "examples/dlrm/main.py emits a parseable metrics sidecar with "
              f"{', '.join(REQUIRED_METRIC_FIELDS)} + recompiles)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
