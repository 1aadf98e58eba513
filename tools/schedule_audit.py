#!/usr/bin/env python
"""Audit the compiled hybrid step's schedule graph on a CPU mesh.

The jaxpr auditor checks which collectives we ask for, the HLO census
counts what XLA emits; this gate sees the DEPENDENCY STRUCTURE between
them. It builds the shared reference configurations
(``tools/_profcommon.build_case`` — the same shapes every static gate
uses, plus the ``streaming`` dynamic-vocab case and the real Criteo-1TB
vector), compiles each hybrid train step abstractly, parses the
optimized HLO into the full dependency DAG
(:mod:`distributed_embeddings_tpu.analysis.schedule_audit`), prices it
under the v5e cost model, and enforces:

* the **baseline contracts** — the id / out / grad all-to-alls exist,
  sit on the modeled critical path, and are SERIALIZED against dense
  compute (today's unpipelined step, the documented starting line the
  pipelined step has to beat);
* the layer's declared :class:`StepSchedule` — every overlap a schedule
  claims must exist in the compiled DAG;
* a **seeded drill**: a fake overlap-declaring schedule (claiming the
  id exchange hides under dense compute) is checked against the real
  serialized program and MUST fail — if the auditor ever lets that lie
  through, this gate fails itself.

Nothing executes on any backend — ``lower().compile()`` only.

    python tools/schedule_audit.py --strict          # make verify's gate
    python tools/schedule_audit.py --json report.json --config dense
    python tools/schedule_audit.py --markdown        # per-case tables

Exit codes: 0 clean; 1 violations found or drill not caught (only with
``--strict``); 2 usable-environment failure.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

try:  # imported as tools.schedule_audit (tests)
    from tools._profcommon import build_case, cpu_mesh, force_cpu  # noqa: F401
except ImportError:  # run as a script: tools/ itself is sys.path[0]
    from _profcommon import build_case, cpu_mesh, force_cpu  # noqa: F401

#: (case, world, global batch, optimizer) sweep. Batches are large
#: enough that the a2a payloads dominate the toy dense-update branch —
#: at production shapes they dominate by orders of magnitude, and the
#: serialized-baseline classification must not flip on the audit shapes.
CASES = (
    ("dense", 8, 256, "adagrad"),
    ("pipelined", 8, 256, "adagrad"),
    ("ragged", 8, 256, "adagrad"),
    ("row_sliced", 8, 256, "adagrad"),
    ("bigvocab", 8, 256, "sgd"),
    ("streaming", 8, 256, "adagrad"),
    ("criteo1tb", 16, 4096, "adagrad"),
)


def audit_case(name: str, world: int, batch: int, opt_name: str):
    """Audit one (config, optimizer) pair against the baseline."""
    import optax

    from distributed_embeddings_tpu.analysis import schedule_audit as sa
    from distributed_embeddings_tpu.parallel import (SparseAdagrad,
                                                     SparseSGD,
                                                     StreamingConfig)

    opt = SparseSGD() if opt_name == "sgd" else SparseAdagrad()
    de, cats, batch_tree, dense_params, loss_fn = build_case(
        name, world, batch)
    dynamic = StreamingConfig() if name == "streaming" else None
    contracts = None  # baseline_contracts(): all three a2as serialized
    if name == "pipelined":
        # the K=2 software-pipelined step: every declared microbatch
        # overlap must EXIST in the compiled DAG (the declaration check
        # runs via de.schedule) AND every declaring exchange must
        # classify overlappable — the ROADMAP item 2 acceptance this
        # gate certifies
        contracts = sa.declared_overlap_contracts(de.schedule)
    elif name in ("streaming", "dense", "ragged", "row_sliced"):
        # Chains that branch off the received ids and meet the step again
        # only at the apply or the commit: genuine independent compute
        # next to the activation/cotangent exchanges, which on the audit
        # shapes outweighs their toy payloads. The id exchange stays
        # serialized (everything downstream depends on it) but in the
        # ragged case (below).
        if name == "streaming":
            # the auditor's first real finding: the staged slot-map/sketch
            # transitions are consumed only at commit
            why = ("streaming admission staging (slot-map/sketch "
                   "transitions) is independent of this exchange — the "
                   "overlap candidate a pipelined step can exploit")
        else:
            # every table of these cases is small at this batch (the
            # ragged case's at its capacity), so the backward sums each
            # slot's cotangents into a dense block
            # (parallel/apply.py:small_table_sums); which block rows an id
            # touched is read off the received ids alone
            why = ("the small-table sums' touched-rows reduce reads only "
                   "the received ids and is independent of this exchange")
        contracts = [
            sa.ScheduleContract("id_all_to_all", expect="serialized",
                                on_critical_path=True,
                                reason="unpipelined baseline")
            if name != "ragged" else
            # the forward fetches the small tables' block rows from the slab
            # (parallel/lookup.py:block_bag_sums), which reads no id
            sa.ScheduleContract("id_all_to_all", expect="overlappable",
                                reason="the small tables' block rows are "
                                       "fetched from the slab alone"),
            sa.ScheduleContract("out_all_to_all", expect="overlappable",
                                reason=why),
            sa.ScheduleContract("grad_all_to_all", expect="overlappable",
                                reason=why),
        ]
    return sa.audit_train_step(
        de, loss_fn, optax.sgd(0.5), opt, cats, batch_tree,
        mesh=cpu_mesh(world), lr_schedule=0.3, dynamic=dynamic,
        dense_params=dense_params, contracts=contracts,
        label=f"{name}/world{world}/{opt_name}")


def seeded_drill(world: int, batch: int) -> int:
    """The self-check: a schedule CLAIMING the id exchange overlaps the
    dense compute, audited against the real (serialized) program, must
    produce violations. Returns 0 when the drill fired, 1 when the fake
    overlap slipped through."""
    import optax

    from distributed_embeddings_tpu.analysis import schedule_audit as sa
    from distributed_embeddings_tpu.parallel import SparseAdagrad
    from distributed_embeddings_tpu.parallel.schedule import (
        PHASE_APPLY, PHASE_DENSE, PHASE_GRAD_EXCHANGE, PHASE_ID_EXCHANGE,
        PHASE_LOOKUP, PHASE_OUT_EXCHANGE, PhaseDecl, StepSchedule)

    # a "pipelined" schedule nobody implemented: microbatch k+1's id
    # exchange supposedly hides under microbatch k's dense compute, so
    # no `after` chain ties them and the overlap claim is declarable
    fake = StepSchedule(
        name="fake-pipelined-drill",
        phases=(
            PhaseDecl(PHASE_ID_EXCHANGE, kind="collective",
                      overlaps=(PHASE_DENSE,)),
            PhaseDecl(PHASE_LOOKUP, kind="compute",
                      after=(PHASE_ID_EXCHANGE,)),
            PhaseDecl(PHASE_OUT_EXCHANGE, kind="collective",
                      after=(PHASE_LOOKUP,)),
            PhaseDecl(PHASE_DENSE, kind="compute"),
            PhaseDecl(PHASE_GRAD_EXCHANGE, kind="collective",
                      after=(PHASE_DENSE,)),
            PhaseDecl(PHASE_APPLY, kind="compute",
                      after=(PHASE_GRAD_EXCHANGE,)),
        ))
    de, cats, batch_tree, dense_params, loss_fn = build_case(
        "dense", world, batch)
    rep = sa.audit_train_step(
        de, loss_fn, optax.sgd(0.5), SparseAdagrad(), cats, batch_tree,
        mesh=cpu_mesh(world), lr_schedule=0.3, dense_params=dense_params,
        schedule=fake, contracts=[], label="drill/fake-overlap")
    if rep.ok:
        print("schedule_audit: DRILL FAILED — the fake overlap-declaring "
              "schedule passed against the serialized program; the "
              "overlap check is not checking", file=sys.stderr)
        return 1
    print("schedule_audit: drill OK (fake overlap-declaring schedule "
          f"rejected: {rep.violations[0]})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config",
                    choices=("dense", "pipelined", "ragged", "row_sliced",
                             "bigvocab", "streaming", "criteo1tb", "all"),
                    default="all")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on any violation (the make verify gate)")
    ap.add_argument("--markdown", action="store_true",
                    help="print each case's collective table")
    ap.add_argument("--json", metavar="PATH",
                    help="dump the full reports as JSON (- for stdout)")
    ap.add_argument("--no-drill", action="store_true",
                    help="skip the seeded fake-overlap drill")
    args = ap.parse_args(argv)

    cases = [c for c in CASES
             if args.config == "all" or c[0] == args.config]
    force_cpu(max(c[1] for c in cases))
    sys.path.insert(0, REPO)

    reports = []
    failed = 0
    for name, world, batch, opt_name in cases:
        try:
            rep = audit_case(name, world, batch, opt_name)
        except Exception as e:  # noqa: BLE001 - report, then fail the gate
            print(f"schedule_audit: {name}/{opt_name}: audit errored: {e}",
                  file=sys.stderr)
            return 2
        reports.append(rep)
        status = "OK" if rep.ok else "FAIL"
        n_ser = sum(c.classification == "serialized"
                    for c in rep.collectives)
        print(f"schedule_audit: {rep.label}: {status} "
              f"nodes={rep.nodes} edges={rep.edges} "
              f"collectives={len(rep.collectives)} "
              f"serialized={n_ser} "
              f"frac={rep.serialized_collective_fraction:.3f} "
              f"critical_path={rep.critical_path_ns / 1e3:.1f}us")
        if args.markdown:
            print(rep.markdown())
        for v in rep.violations:
            print(f"schedule_audit:   violation: {v}", file=sys.stderr)
            failed += 1
    if not args.no_drill:
        failed += seeded_drill(8, 256)
    if args.json:
        payload = json.dumps([r.to_json() for r in reports], indent=2)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w", encoding="utf-8") as fh:
                fh.write(payload + "\n")
    if failed and args.strict:
        print(f"schedule_audit: {failed} violation(s)", file=sys.stderr)
        return 1
    if not failed:
        print(f"schedule_audit: OK ({len(reports)} case(s) certify the "
              "serialized baseline; drill caught the fake overlap)"
              if not args.no_drill else
              f"schedule_audit: OK ({len(reports)} case(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
