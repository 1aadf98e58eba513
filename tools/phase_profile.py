#!/usr/bin/env python
"""Measure where the compiled hybrid step's milliseconds actually go —
and calibrate the schedule auditor's cost model against the clock.

``make schedule-audit`` proves the step's dependency STRUCTURE and
prices it from CHIP_SPECS byte arithmetic; this gate is its measured
twin (= ``make phase-profile``). For each reference case it

1. builds the hybrid train step EXACTLY as shipped (default metrics /
   nan-guard policy, the program the static gates audit) on the
   8-virtual-device CPU mesh, with concrete inputs;
2. times N unprofiled steps, then N steps each under its own
   ``jax.profiler.trace`` capture into a temp ``DETPU_PROFILE_DIR``-style
   directory (``DETPU_PHASE_PROFILE_DIR`` keeps the captures);
3. parses every capture (``utils/traceparse.py``), joins bare-name
   events against the compiled module's own ``metadata.op_name`` text
   (:class:`~distributed_embeddings_tpu.analysis.phase_profile.HloPhaseIndex`),
   and reduces them to a ``PhaseProfile``: per-phase p50/p95 ms, the
   exchange/lookup/apply/dense breakdown, measured a2a fraction,
   measured overlap, and a measured serialized/overlapped verdict per
   exchange — where "overlap" only credits DAG-independent compute, so
   lockstep skew across virtual devices cannot fake a win;
4. audits the SAME compiled text with ``analysis/schedule_audit.py`` and
   (a) cross-checks measured vs modeled classification
   (:func:`check_agreement` — the strict gate: a modeled-serialized
   exchange that measures overlapped means the model lies), and
   (b) renders the calibration drift table (:func:`calibrate`:
   measured/modeled ratio per phase, normalized so the CPU-proxy-vs-v5e
   speed factor cancels; >2x relative drift is flagged).

Profiling is strictly opt-in: the step program is untouched, unprofiled
steps are bitwise the shipped program, and the reported
``profile_overhead_frac`` prices what turning the profiler on costs.

    python tools/phase_profile.py --strict            # the full gate
    python tools/phase_profile.py --smoke --strict    # make verify's smoke
    python tools/phase_profile.py --json out.json --case dense

Exit codes: 0 clean; 1 agreement violations or unusable captures (with
``--strict``; add ``--fail-on-drift`` to also fail on calibration
flags); 2 usable-environment failure.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

try:  # imported as tools.phase_profile (tests)
    from tools._profcommon import build_case, cpu_mesh, force_cpu  # noqa: F401
except ImportError:  # run as a script: tools/ itself is sys.path[0]
    from _profcommon import build_case, cpu_mesh, force_cpu  # noqa: F401

#: (case, world, global batch, optimizer) — the measured twin of the
#: schedule auditor's sweep, restricted to the two cases the acceptance
#: pins: the serialized dense baseline and the streaming case whose
#: out/grad exchanges the auditor already classifies overlappable
CASES = (
    ("dense", 8, 256, "adagrad"),
    ("pipelined", 8, 256, "adagrad"),
    ("streaming", 8, 256, "adagrad"),
)
SMOKE_STEPS = 2


def concretize_case(name, world, batch):
    """``build_case``'s abstract shapes -> concrete arrays: categorical
    ids drawn inside each table's vocab (the streaming table draws from
    a 16x-capacity external space so admissions genuinely fire), floats
    from a fixed-seed normal."""
    import jax.numpy as jnp
    import numpy as np

    de, cats_abs, batch_abs, dp_abs, loss_fn = build_case(
        name, world, batch)
    rng = np.random.default_rng(0)
    configs = de.strategy.global_configs
    cats = []
    for cfg, a in zip(configs, cats_abs):
        stream = cfg.get("streaming")
        hi = (16 * int(stream["capacity"]) if stream
              else int(cfg["input_dim"]))
        cats.append(jnp.asarray(rng.integers(0, hi, size=a.shape),
                                jnp.int32))
    def conc(a):
        return jnp.asarray(rng.normal(size=a.shape), a.dtype)
    batch_tree = (conc(batch_abs[0]), conc(batch_abs[1]))
    dense_params = {k: conc(v) for k, v in dp_abs.items()}
    return de, cats, batch_tree, dense_params, loss_fn


def run_case(name: str, world: int, batch: int, opt_name: str,
             steps: int):
    """Profile one case; returns the JSON-able case record."""
    import optax

    from distributed_embeddings_tpu.analysis import (
        phase_profile as pp, schedule_audit as sa)
    from distributed_embeddings_tpu.parallel import (
        SparseAdagrad, SparseSGD, StreamingConfig, init_hybrid_state,
        init_streaming, make_hybrid_train_step)
    import jax

    emb_opt = SparseSGD() if opt_name == "sgd" else SparseAdagrad()
    tx = optax.sgd(0.5)
    de, cats, batch_tree, dense_params, loss_fn = concretize_case(
        name, world, batch)
    mesh = cpu_mesh(world)
    dynamic = StreamingConfig() if name == "streaming" else None
    state = init_hybrid_state(de, emb_opt, dense_params, tx,
                              jax.random.key(0), mesh=mesh)
    # the SHIPPED program: default metrics policy (env popped by
    # force_cpu -> off) and default nan-guard — the same defaults
    # build_abstract_step gives the static gates
    step = make_hybrid_train_step(de, loss_fn, tx, emb_opt, mesh=mesh,
                                  lr_schedule=0.3, dynamic=dynamic)
    sstate = init_streaming(de, dynamic) if dynamic else None
    args = (state, cats, batch_tree) + ((sstate,) if dynamic else ())
    txt = step.lower(*args).compile().as_text()
    index = pp.HloPhaseIndex(txt, world=world)
    label = f"{name}/world{world}/{opt_name}"
    sched = sa.audit_text(
        txt, label=label, world=world,
        backend=jax.default_backend())

    holder = {"state": state, "sstate": sstate}

    def run_one():
        if dynamic:
            loss, s, ss = step(holder["state"], cats, batch_tree,
                               holder["sstate"])
            holder["state"], holder["sstate"] = s, ss
        else:
            loss, s = step(holder["state"], cats, batch_tree)
            holder["state"] = s
        jax.block_until_ready((loss, holder["state"]))

    for _ in range(2):  # compile + reach steady state before any clock
        run_one()
    t0 = time.perf_counter()
    for _ in range(steps):
        run_one()
    plain_s = (time.perf_counter() - t0) / steps

    profile = pp.profile_steps(run_one, steps=steps, index=index,
                               world=world, label=label)
    # the profiler's cost ON the step (capture only; parsing happens off
    # the training path and is priced separately as parse_s)
    profiled_s = profile.capture_s or plain_s

    calib = pp.calibrate(profile, sched)
    agreement = pp.check_agreement(profile, sched)
    return {
        "label": label,
        "profile": profile.summary(),
        "phase_ms": profile.phase_ms,
        "modeled": {
            "serialized_collective_fraction":
                sched.serialized_collective_fraction,
            "collectives": [
                {"phase": c.phase, "classification": c.classification}
                for c in sched.collectives],
        },
        "calibration": calib.to_json(),
        "agreement_violations": agreement,
        "plain_step_ms": round(plain_s * 1e3, 3),
        "profiled_step_ms": round(profiled_s * 1e3, 3),
        "parse_ms_per_step": (round(profile.parse_s * 1e3, 3)
                              if profile.parse_s else None),
        "profile_overhead_frac": round(profiled_s / plain_s - 1.0, 4)
        if plain_s > 0 else None,
        "steps": steps,
    }, profile, calib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--case",
                    choices=("dense", "pipelined", "streaming", "all"),
                    default="all")
    ap.add_argument("--steps", type=int, default=None,
                    help="profiled steps per case (default "
                         "DETPU_PHASE_PROFILE_STEPS)")
    ap.add_argument("--smoke", action="store_true",
                    help="dense case only, 2 steps — the make verify "
                         "smoke")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on measured-vs-modeled classification "
                         "disagreement (the gate)")
    ap.add_argument("--fail-on-drift", action="store_true",
                    help="with --strict, also fail on calibration drift "
                         "flags (off by default: the CPU proxy "
                         "legitimately misprices phases the v5e model "
                         "prices for ICI)")
    ap.add_argument("--markdown", action="store_true",
                    help="print the full per-phase tables")
    ap.add_argument("--json", metavar="PATH",
                    help="dump the case records as JSON (- for stdout)")
    args = ap.parse_args(argv)

    cases = [c for c in CASES
             if args.case == "all" or c[0] == args.case]
    if args.smoke and args.case == "all":
        # smoke narrows the DEFAULT sweep to the dense case; an explicit
        # --case selection is honored (smoke then only shrinks steps)
        cases = [c for c in CASES if c[0] == "dense"]
    force_cpu(max(c[1] for c in cases))
    sys.path.insert(0, REPO)

    from distributed_embeddings_tpu.analysis.phase_profile import (
        PhaseProfileError, default_profile_steps)

    steps = args.steps or (SMOKE_STEPS if args.smoke
                           else default_profile_steps())
    records = []
    failed = 0
    for name, world, batch, opt_name in cases:
        try:
            rec, profile, calib = run_case(name, world, batch, opt_name,
                                           steps)
        except PhaseProfileError as e:
            print(f"phase_profile: {name}: {e}", file=sys.stderr)
            failed += 1
            continue
        except Exception as e:  # noqa: BLE001 - report, then env-fail
            print(f"phase_profile: {name}: errored: {e}", file=sys.stderr)
            return 2
        records.append(rec)
        prof = rec["profile"]
        print(f"phase_profile: {rec['label']}: wall p50 "
              f"{prof['step_wall_ms_p50']:.1f} ms | a2a in flight "
              f"{prof['a2a_frac'] * 100:.1f}% | concurrency "
              f"x{prof['concurrency']:.2f} | measured serialized frac "
              f"{prof['measured_serialized_fraction']} (modeled "
              f"{rec['modeled']['serialized_collective_fraction']:.3f}) | "
              f"overhead {rec['profile_overhead_frac']:+.1%} | "
              f"attribution {prof['resolved_frac'] * 100:.1f}%")
        if args.markdown:
            print(profile.markdown())
            print()
        print(calib.markdown())
        for v in rec["agreement_violations"]:
            print(f"phase_profile:   violation: {v}", file=sys.stderr)
            failed += 1
        if args.fail_on_drift:
            for f in rec["calibration"]["flagged"]:
                print(f"phase_profile:   drift: {f}", file=sys.stderr)
                failed += 1
    if args.json:
        payload = json.dumps(records, indent=2)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w", encoding="utf-8") as fh:
                fh.write(payload + "\n")
    if failed and args.strict:
        print(f"phase_profile: {failed} violation(s)", file=sys.stderr)
        return 1
    if not failed:
        print(f"phase_profile: OK ({len(records)} case(s): measured "
              "classification agrees with the schedule auditor's model)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
