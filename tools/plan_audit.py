#!/usr/bin/env python
"""Enforce the plan-time HBM/comms capacity contracts — before anything
is built, traced, or compiled.

The third static gate (jaxpr auditor → what we ask for; HLO census →
what XLA emits; THIS → what the plan costs before either exists): for
every shared reference configuration (``tools/_profcommon.build_case``)
**plus the real Criteo-1TB vocab vector** it prices the placement plan
with :mod:`distributed_embeddings_tpu.analysis.plan_audit` — per-rank
param+optimizer+exchange-buffer bytes, per-step all-to-all payloads,
what the apply's scatter pays for each slab (the step's own rule), padded
group-shape count — and enforces the default :class:`PlanContract`.

Strict mode additionally

* calibrates the jax-free byte model against
  ``analysis.memory.table_memory_report``'s ``eval_shape`` accounting
  (drift beyond ``--calibration-tol`` fails: the mirror broke);
* runs two seeded NEGATIVE drills — an over-HBM plan (Criteo-1TB fp32 +
  Adam on 8 ranks) and a plan that leaves ranks empty (4 tables on 6
  ranks) — and fails unless each is rejected with a violation naming
  the offending rank (a gate that cannot catch a seeded violation is
  not a gate).

Nothing executes on any backend: plans are host metadata, inputs are
``ShapeDtypeStruct``s, and the only jax use is ``eval_shape`` inside the
calibration target.

    python tools/plan_audit.py --strict           # make verify's gate
    python tools/plan_audit.py --case criteo1tb --markdown
    python tools/plan_audit.py --json report.json

Exit codes: 0 clean; 1 violations / calibration drift / failed drill
(only with ``--strict``); 2 unusable environment.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

try:  # imported as tools.plan_audit (tests)
    from tools import _profcommon as pc
except ImportError:  # run as a script: tools/ itself is sys.path[0]
    import _profcommon as pc

#: (case, world, global batch, param dtype, optimizer, dp_input) —
#: the tier-1 shapes at the 8-position mesh the other static gates use,
#: plus the Criteo-1TB deployment shape (world 16, bf16, mp input: the
#: dlrm example's defaults at the north-star scale).
CASES = (
    ("dense", 8, 16, "float32", "adagrad", True),
    ("ragged", 8, 16, "float32", "adagrad", True),
    ("row_sliced", 8, 16, "float32", "adagrad", True),
    ("bigvocab", 8, 16, "float32", "sgd", True),
    ("criteo1tb", pc.CRITEO1TB_WORLD, pc.CRITEO1TB_BATCH, "bfloat16",
     "sgd", False),
)


def audit_case(name, world, batch, param_dtype, opt_name, dp_input,
               chip="v5e"):
    """Build one shared reference case and audit its plan + calibration."""
    from distributed_embeddings_tpu.analysis import (
        compare_with_memory, default_contract, memory as dmem, plan_audit)
    from distributed_embeddings_tpu.parallel import (
        SparseAdagrad, SparseAdam, SparseMomentum, SparseSGD)

    opt = {"sgd": SparseSGD, "adagrad": SparseAdagrad,
           "momentum": SparseMomentum, "adam": SparseAdam}[opt_name]()
    de, cats, _batch_tree, _dp, _loss = pc.build_case(name, world, batch)
    rep = plan_audit.audit_plan(
        de, batch, optimizer=opt, param_dtype=param_dtype,
        cat_inputs=cats, dp_input=dp_input, chip=chip,
        label=f"{name}/world{world}/{opt_name}/{param_dtype}",
        contract=default_contract(chip))
    mem = dmem.table_memory_report(de, opt, param_dtype=param_dtype)
    calib = compare_with_memory(rep, mem)
    return rep, calib


def seeded_drills():
    """The negative self-tests: each returns ``(label, violations,
    expect_substring)`` and MUST produce at least one violation whose
    text names the offending rank / slab."""
    from distributed_embeddings_tpu.analysis import (default_contract,
                                                     plan_audit)
    from distributed_embeddings_tpu.parallel.strategy import (
        DistEmbeddingStrategy)

    configs = [{"input_dim": int(s), "output_dim": pc.CRITEO1TB_DIM,
                "combiner": None} for s in pc.CRITEO_1TB_SIZES]
    # drill 1: fp32 + Adam (2 state slots) on 8 ranks — ~57 GB/rank,
    # nearly 4x over the v5e budget; must fail naming a rank
    st8 = DistEmbeddingStrategy(configs, 8, strategy="memory_balanced")
    over = plan_audit.audit_plan(
        st8, pc.CRITEO1TB_BATCH, optimizer="adam", param_dtype="float32",
        label="drill_over_hbm", contract=default_contract())
    # drill 2: four tables on six ranks — two ranks own no table slice;
    # must fail naming them
    st6 = DistEmbeddingStrategy(
        [{"input_dim": 100, "output_dim": 8}] * 4, 6)
    empty = plan_audit.audit_plan(
        st6, 12, label="drill_empty_rank", contract=default_contract())
    return [("over_hbm", over.violations, "rank "),
            ("empty_rank", empty.violations, "own no table slice")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--case",
                    choices=("dense", "ragged", "row_sliced", "bigvocab",
                             "criteo1tb", "all"),
                    default="all")
    ap.add_argument("--chip", default="v5e",
                    help="capacity-registry chip the contracts bind to")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on any violation, calibration drift, or "
                         "failed seeded drill (the make verify gate)")
    ap.add_argument("--calibration-tol", type=float, default=0.001,
                    help="max |drift| of the jax-free byte model vs the "
                         "eval_shape accounting (default 0.1%%)")
    ap.add_argument("--markdown", action="store_true",
                    help="print each case's per-rank budget table")
    ap.add_argument("--json", metavar="PATH",
                    help="dump the full reports as JSON (- for stdout)")
    args = ap.parse_args(argv)

    # pure-host tool: pin an inert CPU backend exactly like the other
    # static auditors (nothing is dispatched; the jax import — for
    # eval_shape calibration — must not take a chip another process uses)
    pc.force_cpu(1)
    sys.path.insert(0, REPO)

    cases = [c for c in CASES
             if args.case in ("all", c[0])]
    failed = 0
    reports = []
    for name, world, batch, dt, opt_name, dp in cases:
        try:
            rep, calib = audit_case(name, world, batch, dt, opt_name, dp,
                                    chip=args.chip)
        except Exception as e:  # noqa: BLE001 - report, then fail the gate
            print(f"plan_audit: {name}: audit errored: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return 2
        reports.append(rep)
        status = "OK" if rep.ok else "FAIL"
        print(f"plan_audit: {rep.label}: {status} "
              f"max_rank={rep.max_rank_bytes / 2**30:.2f}GB "
              f"a2a={rep.total_a2a_bytes_per_step / 1e6:.1f}MB/step "
              f"groups={rep.n_groups} imbalance={rep.imbalance_ratio:.2f} "
              f"calib_drift={calib['max_abs_drift']:.2e}")
        if args.markdown:
            print(rep.markdown())
        for v in rep.violations:
            print(f"plan_audit:   violation: {v}", file=sys.stderr)
            failed += 1
        if calib["max_abs_drift"] > args.calibration_tol:
            print(f"plan_audit:   CALIBRATION DRIFT {calib} — the jax-free "
                  "byte model disagrees with analysis.memory's eval_shape "
                  "accounting; one of the two mirrors broke",
                  file=sys.stderr)
            failed += 1

    # the negative self-test runs for the full sweep AND for any strict
    # invocation — a strict gate that skipped its seeded drills because
    # the case list was narrowed would no longer prove it can reject
    if args.case == "all" or args.strict:
        for label, violations, expect in seeded_drills():
            if any(expect in v for v in violations):
                print(f"plan_audit: drill {label}: correctly rejected "
                      f"({len(violations)} violation(s))")
            else:
                print(f"plan_audit: drill {label}: NOT rejected — the "
                      f"contract failed to catch a seeded violation "
                      f"(wanted {expect!r} in {violations})",
                      file=sys.stderr)
                failed += 1

    if args.json:
        payload = json.dumps([r.to_json() for r in reports], indent=2)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w", encoding="utf-8") as fh:
                fh.write(payload + "\n")
    if failed and args.strict:
        print(f"plan_audit: {failed} failure(s)", file=sys.stderr)
        return 1
    if not failed:
        print(f"plan_audit: OK ({len(reports)} case(s) hold their capacity "
              "contracts; byte model calibrated)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
