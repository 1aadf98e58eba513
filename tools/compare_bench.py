#!/usr/bin/env python
"""Diff two BENCH records and fail on throughput regressions.

Two records of ``bench.py`` (both named on the command line — there is
no default baseline) are diffed metric by metric. This tool is the
regression gate:

    python tools/compare_bench.py OLD.json NEW.json [--threshold 0.10]

Accepts either the driver's wrapper format (``{"rc": ..., "parsed":
{...}}``) or bench.py's raw one-line JSON. Exit codes:

* 0 — every comparable metric within the threshold;
* 1 — at least one regression beyond the threshold (throughput metrics
  dropping, or ms-per-iter metrics rising, by more than ``--threshold``,
  default 10%), a nonzero steady-state recompile count, a per-phase
  HLO pass-count regression / contract violation in the candidate's
  ``phase_budget`` census (:func:`check_phase_budget`), a
  ``plan_audit`` capacity failure — contract violation or a
  predicted-vs-measured byte drift beyond ±15%
  (:func:`check_plan_audit`) — a ``schedule`` overlap regression:
  ``serialized_collective_fraction`` or modeled critical-path bytes
  growing versus the baseline (:func:`check_schedule`) — or a MEASURED
  overlap regression: the trace-parsed ``phase_profile`` section's
  measured serialized fraction growing, or its measured-vs-modeled
  classification disagreeing (:func:`check_phase_profile`) — both the
  schedule and phase-profile gates run twice, once for the serialized
  headline and once for the pipelined twins (``schedule_pipelined`` /
  ``phase_profile_pipelined``), so the K-microbatch step's won overlap
  ratchets independently — or a ``serving`` regression: fixed-QPS p95
  latency growing beyond 10%, a recompiling padded-batch ladder, or
  the section missing versus the baseline (:func:`check_serving`);
* 2 — unusable inputs (missing file, no parseable payload).

Metrics present in only one record are reported but never fail the gate
(rounds legitimately add sections). Records from DIFFERENT backends or
device counts (the top-level device stamp, falling back to the PR 2
``env`` block) are REFUSED outright — a CPU record must never gate a
TPU one; ``--allow-env-mismatch`` downgrades that to a loud
warning when cross-backend reading is deliberate. Wired as ``make
bench-diff`` (``OLD=... NEW=... make bench-diff``).

No jax import: this must run anywhere, instantly.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Optional

# higher is better
THROUGHPUT_KEYS = (
    "value",
    "fp32_samples_per_sec",
    "bf16_samples_per_sec",
    "bf16_params_samples_per_sec",
    "bf16_per_dispatch_samples_per_sec",
    "uncapped_bf16_samples_per_sec",
    "multihot_ragged_samples_per_sec",
    "criteo1tb_shard_samples_per_sec",
    "input_pipeline_samples_per_sec",
    "nanguard_samples_per_sec",
    "resilient_samples_per_sec",
    "sentinel_samples_per_sec",
    "telemetry_samples_per_sec",
    "streaming_samples_per_sec",
    "pipeline_samples_per_sec",
    "online_train_samples_per_sec",
)
# lower is better (ms-per-iter timings and byte budgets: a >threshold
# rise in per-step peak HBM is a regression exactly like a slower step)
MS_KEYS = (
    "tiny_zoo_adagrad_ms_per_iter",
    "tiny_zoo_sgd_ms_per_iter",
    "tiny_zoo_adagrad_bf16_ms_per_iter",
    "criteo1tb_v5e16_step_ms",
    "peak_hbm_mb",
)
ENV_KEYS = ("backend", "device_count", "jax_version", "smoke")
# per-phase HLO pass kinds gated round over round (keep in sync with
# analysis/hlo_census.py GATED_KINDS; convert/transpose counts are
# reported in the record but move with benign layout choices)
PHASE_GATE_KINDS = ("gather", "scatter", "sort", "cumsum", "all_to_all")


def load_bench(path: str) -> Optional[Dict[str, Any]]:
    """Extract the bench payload from either the driver wrapper or a raw
    bench.py JSON line (last parseable JSON object wins for line files)."""
    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    except OSError as e:
        print(f"compare_bench: cannot read {path}: {e}", file=sys.stderr)
        return None
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        # maybe a JSONL tail (e.g. a sidecar) — take the last object line
        doc = None
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
            except json.JSONDecodeError:
                continue
        if doc is None:
            print(f"compare_bench: no JSON payload in {path}",
                  file=sys.stderr)
            return None
    if isinstance(doc, dict) and "parsed" in doc and "rc" in doc:
        if doc["parsed"] is None:
            print(f"compare_bench: {path} is a driver record whose bench "
                  f"run failed (rc={doc.get('rc')}); nothing to compare",
                  file=sys.stderr)
            return None
        doc = doc["parsed"]
    if isinstance(doc, dict) and "section" in doc:
        # SectionRecorder sidecar (BENCH.partial.jsonl): the bench payload
        # of the "final" record is nested under "value"
        if doc.get("section") == "final" and isinstance(doc.get("value"),
                                                        dict):
            doc = doc["value"]
        else:
            print(f"compare_bench: {path} is a sidecar without a completed "
                  "'final' record (run killed mid-way?); nothing to compare",
                  file=sys.stderr)
            return None
    if not isinstance(doc, dict) or "metric" not in doc:
        print(f"compare_bench: {path} does not look like a bench record",
              file=sys.stderr)
        return None
    return doc


def _stamp(rec: Dict[str, Any], key: str):
    """A record's backend-identity field: the top-level device stamp
    (written since the phase-profile round), falling back to the PR 2
    ``env`` block for older records."""
    if key in rec:
        return rec[key]
    env = rec.get("env")
    return env.get(key) if isinstance(env, dict) else None


def check_env(old: Dict[str, Any], new: Dict[str, Any],
              allow_mismatch: bool = False) -> int:
    """Backend honesty gate: records from DIFFERENT backends or device
    counts are REFUSED, not silently diffed (a run that quietly landed
    on the CPU must never pass a gate calibrated on TPU numbers, nor
    vice versa). ``--allow-env-mismatch`` downgrades the refusal to the old
    loud warning for deliberate cross-backend reading. Softer stamps
    (jax version, smoke flag) always warn only. Records carrying no
    stamp on either side (pre-PR-2) compare as before."""
    failures = 0
    for k in ("backend", "device_count"):
        ov, nv = _stamp(old, k), _stamp(new, k)
        if ov is not None and nv is not None and ov != nv:
            if allow_mismatch:
                print(f"compare_bench: WARNING {k} mismatch "
                      f"({ov!r} vs {nv!r}) overridden by "
                      "--allow-env-mismatch — numbers are not "
                      "apples-to-apples", file=sys.stderr)
            else:
                print(f"compare_bench: REFUSING to compare: {k} "
                      f"{ov!r} (baseline) vs {nv!r} (candidate) — "
                      "records from different backends measure "
                      "different machines; pass --allow-env-mismatch "
                      "to diff them anyway", file=sys.stderr)
                failures += 1
    oenv, nenv = old.get("env"), new.get("env")
    if isinstance(oenv, dict) and isinstance(nenv, dict):
        for k in ENV_KEYS:
            if k in ("backend", "device_count"):
                continue  # hard-gated above
            if k in oenv and k in nenv and oenv[k] != nenv[k]:
                print(f"compare_bench: WARNING env mismatch on {k!r}: "
                      f"{oenv[k]!r} vs {nenv[k]!r} — numbers are not "
                      "apples-to-apples", file=sys.stderr)
    return failures


def check_steady_state(new: Dict[str, Any]) -> int:
    """The recompile gate: a candidate record carrying the PR 4
    ``steady_state_recompiles`` field (compiles observed inside bench.py's
    TIMED loops, warmup excluded; obs compile-listener counter) must show
    zero — a nonzero count means some section retraces per step, which
    poisons every throughput number in the same record. Absolute property
    of the NEW record, no baseline needed; absent field (pre-PR-4 records,
    runs without DETPU_OBS) passes."""
    n = new.get("steady_state_recompiles")
    if isinstance(n, (int, float)) and n > 0:
        print(f"compare_bench: steady_state_recompiles={int(n)} — the "
              "candidate bench retraced inside a timed loop; its "
              "throughput numbers measure compiles, not steps",
              file=sys.stderr)
        return 1
    return 0


def check_phase_budget(old: Dict[str, Any], new: Dict[str, Any]) -> int:
    """The PR 7 pass-budget gate, the static analogue of the recompile
    gate: the bench record embeds the per-phase HLO pass census of the
    headline step (``phase_budget.phases``: gather/scatter/sort/cumsum/
    all-to-all passes per ``obs.scope`` phase). Two absolute checks and
    one diff:

    * a candidate whose census VIOLATES its own contracts (e.g. a dedup
      pass compiled into the SparseSGD headline) fails outright;
    * a candidate whose gated pass count GROWS in any phase both records
      share fails — an extra gather/sort in the hot path is a regression
      even before it shows up as milliseconds. Counts dropping, phases
      disappearing, or brand-new phases are fine (pass cuts and new
      instrumentation are the point).

    Records without a ``phase_budget`` section (pre-PR-7) pass the diff.
    """
    failures = 0
    nb = new.get("phase_budget")
    if not isinstance(nb, dict):
        if isinstance(old.get("phase_budget"), dict):
            # the baseline proves the section used to exist: a candidate
            # without one means the census crashed or was skipped, and a
            # silent pass here would hide exactly the regressions the
            # gate exists to catch
            print("compare_bench: candidate record has no phase_budget "
                  "section but the baseline does — the census failed or "
                  "was skipped; the pass-budget gate cannot run",
                  file=sys.stderr)
            return 1
        return 0  # both pre-PR-7 records: nothing to compare
    for v in nb.get("violations") or []:
        print(f"compare_bench: phase_budget contract violation in the "
              f"candidate record: {v}", file=sys.stderr)
        failures += 1
    ob = old.get("phase_budget")
    ophases = ob.get("phases") if isinstance(ob, dict) else None
    nphases = nb.get("phases")
    if not isinstance(ophases, dict) or not isinstance(nphases, dict):
        return failures
    for phase, orow in ophases.items():
        nrow = nphases.get(phase)
        if not isinstance(orow, dict) or not isinstance(nrow, dict):
            continue
        for kind in PHASE_GATE_KINDS:
            ov, nv = orow.get(kind, 0) or 0, nrow.get(kind, 0) or 0
            if nv > ov:
                print(f"compare_bench: phase_budget REGRESSION: phase "
                      f"{phase!r} {kind} passes {ov} -> {nv} — a new "
                      "row-op pass entered the hot path",
                      file=sys.stderr)
                failures += 1
    return failures


#: max tolerated |predicted - measured| / measured byte drift of the
#: bench's plan_audit section (the plan-time capacity model must stay
#: validated against XLA's own accounting, not decorative)
PLAN_AUDIT_DRIFT_TOL = 0.15


def check_plan_audit(old: Dict[str, Any], new: Dict[str, Any]) -> int:
    """The PR 8 capacity gate: the bench record embeds the plan-time
    byte model's self-check (``plan_audit``: predicted argument bytes of
    the compiled headline step vs XLA ``memory_analysis``, plus the
    contract audit of the headline and Criteo-1TB plans). Three absolute
    checks on the candidate:

    * any contract violation (headline or the criteo1tb deployment
      plan) fails outright — an over-HBM or past-cliff plan must never
      ride a green bench record;
    * ``byte_drift_frac`` beyond ±15% fails — the predictor drifted
      from what XLA actually allocates and can no longer be trusted as
      a pre-pod gate;
    * a candidate missing the section while the baseline has it fails
      (the audit crashed or was skipped — silence would hide exactly
      the regressions the gate exists to catch).
    """
    nb = new.get("plan_audit")
    if not isinstance(nb, dict):
        if isinstance(old.get("plan_audit"), dict):
            print("compare_bench: candidate record has no plan_audit "
                  "section but the baseline does — the capacity audit "
                  "failed or was skipped; the plan gate cannot run",
                  file=sys.stderr)
            return 1
        return 0
    failures = 0
    for v in nb.get("violations") or []:
        print(f"compare_bench: plan_audit contract violation in the "
              f"candidate record: {v}", file=sys.stderr)
        failures += 1
    c1tb = nb.get("criteo1tb")
    if isinstance(c1tb, dict):
        for v in c1tb.get("violations") or []:
            print(f"compare_bench: plan_audit criteo1tb violation in the "
                  f"candidate record: {v}", file=sys.stderr)
            failures += 1
    drift = nb.get("byte_drift_frac")
    if drift is None:
        # the predictor was never validated this round (compile or
        # memory_analysis failed) — that is a gate failure whenever the
        # baseline shows validation used to work, not a silent pass
        ob = old.get("plan_audit")
        if isinstance(ob, dict) and ob.get("byte_drift_frac") is not None:
            print("compare_bench: plan_audit byte_drift_frac is null in "
                  "the candidate (compile_error="
                  f"{nb.get('compile_error')!r}) but the baseline had a "
                  "measured drift — the capacity predictor went "
                  "unvalidated", file=sys.stderr)
            failures += 1
    elif isinstance(drift, (int, float)) and abs(drift) > PLAN_AUDIT_DRIFT_TOL:
        print(f"compare_bench: plan_audit byte drift {drift:+.1%} exceeds "
              f"±{PLAN_AUDIT_DRIFT_TOL:.0%}: predicted "
              f"{nb.get('predicted_argument_mb')} MB vs measured "
              f"{nb.get('measured_argument_mb')} MB — the plan-time "
              "capacity model no longer matches XLA's accounting",
              file=sys.stderr)
        failures += 1
    return failures


#: tolerated growth of the schedule section's modeled critical-path
#: bytes (layout jitter between jax/XLA versions moves a few operand
#: shapes; structural regressions move megabytes)
SCHEDULE_BYTES_TOL = 0.02
#: tolerated growth of serialized_collective_fraction (float noise only
#: — any real re-serialization moves whole collectives, not epsilons)
SCHEDULE_FRACTION_TOL = 0.005


def check_schedule(old: Dict[str, Any], new: Dict[str, Any],
                   key: str = "schedule") -> int:
    """The schedule-graph gate (the overlap ratchet): the bench record
    embeds the schedule auditor's baseline report (``schedule``:
    serialized_collective_fraction, modeled critical-path bytes, and the
    per-collective classification of the headline step's dependency
    DAG) — and, since the pipelined round, the K=2 pipelined twin
    (``schedule_pipelined``, checked by a second call with ``key=``).
    Four checks:

    * any contract / declaration violation in the candidate's own
      report fails outright;
    * ``serialized_collective_fraction`` GROWING beyond float tolerance
      fails — overlap, once won, can never silently regress back to a
      serialized exchange;
    * a collective PHASE the baseline classified overlappable that the
      candidate classifies serialized fails, even when the fraction
      math would forgive it (one re-serialized exchange among many
      cheap ones moves the fraction little but loses the win);
    * modeled ``critical_path_bytes`` growing beyond
      :data:`SCHEDULE_BYTES_TOL` fails — a longer dependency chain is a
      structural regression even before it shows up as milliseconds;
    * a candidate missing the section while the baseline has it fails
      (the audit crashed or was skipped — silence would hide exactly
      the regressions the gate exists to catch).
    """
    sec = new.get(key)
    if not isinstance(sec, dict):
        if isinstance(old.get(key), dict):
            print(f"compare_bench: candidate record has no {key} "
                  "section but the baseline does — the schedule audit "
                  "failed or was skipped; the overlap gate cannot run",
                  file=sys.stderr)
            return 1
        return 0
    failures = 0
    for v in sec.get("violations") or []:
        print(f"compare_bench: {key} contract violation in the "
              f"candidate record: {v}", file=sys.stderr)
        failures += 1
    osec = old.get(key)
    if not isinstance(osec, dict):
        return failures
    of = osec.get("serialized_collective_fraction")
    nf = sec.get("serialized_collective_fraction")
    if isinstance(of, (int, float)) and isinstance(nf, (int, float)) \
            and nf > of + SCHEDULE_FRACTION_TOL:
        print(f"compare_bench: {key} REGRESSION: "
              f"serialized_collective_fraction {of:.3f} -> {nf:.3f} — "
              "a collective that used to overlap dense compute is "
              "serialized again", file=sys.stderr)
        failures += 1
    def classifications(s):
        """(scope, phase) -> classification over the section's own
        collectives list AND every per-case list (the headline section
        keeps its lists under ``cases``; the pipelined twin is flat)."""
        out = {}
        for c in s.get("collectives") or []:
            if isinstance(c, dict):
                out[("", c.get("phase"))] = c.get("classification")
        cases = s.get("cases")
        if isinstance(cases, dict):
            for label, case in cases.items():
                if not isinstance(case, dict):
                    continue
                for c in case.get("collectives") or []:
                    if isinstance(c, dict):
                        out[(label, c.get("phase"))] = c.get(
                            "classification")
        return out

    ocls = classifications(osec)
    for (scope, phase), cls in classifications(sec).items():
        if ocls.get((scope, phase)) == "overlappable" \
                and cls == "serialized":
            where = f" (case {scope!r})" if scope else ""
            print(f"compare_bench: {key} REGRESSION: collective phase "
                  f"{phase!r}{where} was overlappable in the baseline "
                  "but the candidate serializes it — an exchange lost "
                  "its independent compute", file=sys.stderr)
            failures += 1
    ob = osec.get("critical_path_bytes")
    nb2 = sec.get("critical_path_bytes")
    if isinstance(ob, (int, float)) and isinstance(nb2, (int, float)) \
            and ob > 0 and nb2 > ob * (1.0 + SCHEDULE_BYTES_TOL):
        print(f"compare_bench: {key} REGRESSION: modeled "
              f"critical-path bytes {int(ob)} -> {int(nb2)} "
              f"(+{(nb2 / ob - 1) * 100:.1f}%) — the step's dependency "
              "chain got longer", file=sys.stderr)
        failures += 1
    return failures


#: tolerated growth of the MEASURED serialized-collective fraction
#: (trace captures are noisier than the static model: thread scheduling
#: moves a few percent between runs; a real re-serialization moves the
#: whole collective, i.e. tens of points)
PHASE_PROFILE_FRACTION_TOL = 0.10


def check_phase_profile(old: Dict[str, Any], new: Dict[str, Any],
                        key: str = "phase_profile") -> int:
    """The measured half of the overlap ratchet: the bench record embeds
    the trace-parsed phase profile of the headline step
    (``phase_profile``: per-phase measured ms, measured a2a fraction,
    measured serialized-collective fraction, capture overhead,
    measured-vs-modeled agreement) — and, since the pipelined round,
    the K=2 pipelined twin (``phase_profile_pipelined``, checked by a
    second call with ``key=``). Three checks:

    * any agreement violation in the candidate (a modeled-serialized
      exchange that MEASURED overlapped, or a join failure) fails
      outright — the cost model and the clock disagree;
    * ``measured_serialized_fraction`` GROWING beyond
      :data:`PHASE_PROFILE_FRACTION_TOL` fails — measured overlap, once
      won by the pipelined step, can never silently regress (the
      measured twin of :func:`check_schedule`'s modeled ratchet);
    * a candidate missing the section while the baseline has it fails
      (the capture crashed or was skipped — silence would hide exactly
      the regressions the gate exists to catch).
    """
    sec = new.get(key)
    if not isinstance(sec, dict):
        if isinstance(old.get(key), dict):
            print(f"compare_bench: candidate record has no {key} "
                  "section but the baseline does — the measured capture "
                  "failed or was skipped; the measured overlap gate "
                  "cannot run", file=sys.stderr)
            return 1
        return 0
    failures = 0
    for v in sec.get("violations") or []:
        print(f"compare_bench: {key} agreement violation in the "
              f"candidate record: {v}", file=sys.stderr)
        failures += 1
    osec = old.get(key)
    if not isinstance(osec, dict):
        return failures
    of = osec.get("measured_serialized_fraction")
    nf = sec.get("measured_serialized_fraction")
    if isinstance(of, (int, float)) and isinstance(nf, (int, float)) \
            and nf > of + PHASE_PROFILE_FRACTION_TOL:
        print(f"compare_bench: {key} REGRESSION: measured "
              f"serialized fraction {of:.3f} -> {nf:.3f} — an exchange "
              "that used to measure hidden under compute is exposed "
              "again on the clock", file=sys.stderr)
        failures += 1
    return failures


#: streaming section contract: the capacity-bounded dynamic table must
#: keep TRACKING the static-vocab AUC on the day-k/day-k+1 replay (and
#: actually exercise its admission machinery) — the scenario's whole
#: point is matching quality at a fraction of the HBM
STREAMING_MAX_AUC_DROP = 0.02


def check_streaming(old: Dict[str, Any], new: Dict[str, Any]) -> int:
    """Gate the ``streaming`` section: a candidate carrying it must show
    a dynamic-vs-static AUC delta within :data:`STREAMING_MAX_AUC_DROP`,
    nonzero admissions, and a dynamic HBM footprint genuinely below the
    static plan's; a candidate MISSING the section while the baseline
    has it fails (the scenario silently disappeared)."""
    sec = new.get("streaming")
    if not isinstance(sec, dict):
        if isinstance(old.get("streaming"), dict):
            print("compare_bench: candidate has no 'streaming' section "
                  "but the baseline does — the streaming scenario failed "
                  "or was dropped", file=sys.stderr)
            return 1
        return 0
    failures = 0
    delta = sec.get("auc_delta_vs_static")
    if isinstance(delta, (int, float)) and delta < -STREAMING_MAX_AUC_DROP:
        print(f"compare_bench: streaming dynamic table trails the static "
              f"vocab by {-delta:.4f} AUC on the day-k+1 eval (> "
              f"{STREAMING_MAX_AUC_DROP} allowed)", file=sys.stderr)
        failures += 1
    if not sec.get("admitted"):
        print("compare_bench: streaming section reports zero admissions "
              "— the frequency gate never fired", file=sys.stderr)
        failures += 1
    frac = sec.get("hbm_frac_of_static")
    if isinstance(frac, (int, float)) and frac >= 1.0:
        print(f"compare_bench: streaming plan prices at {frac:.2f}x the "
              "static plan's HBM — the capacity bound is not bounding",
              file=sys.stderr)
        failures += 1
    return failures


#: max tolerated growth of the serving section's p95 latency (the
#: latency twin of the 10% throughput gate: at a FIXED target QPS and
#: fixed shapes, p95 rising faster than this is a served-path
#: regression, not load)
SERVING_P95_TOL = 0.10


def check_serving(old: Dict[str, Any], new: Dict[str, Any]) -> int:
    """Gate the ``serving`` section (ISSUE 15): three checks.

    * a nonzero ``steady_state_recompiles`` inside the section fails
      outright — a padded-batch ladder that retraces per request mix
      measures compiles, not latencies (the section's count also folds
      into the record-wide recompile gate, but a candidate diffed
      against a pre-serving baseline must not escape it);
    * ``latency_p95_ms`` growing beyond :data:`SERVING_P95_TOL` versus
      the baseline fails — the fixed-QPS latency ratchet;
    * a candidate missing the section while the baseline has it fails
      (the serving scenario failed or was dropped — silence would hide
      exactly the regressions the gate exists to catch).
    """
    sec = new.get("serving")
    if not isinstance(sec, dict):
        if isinstance(old.get("serving"), dict):
            print("compare_bench: candidate has no 'serving' section "
                  "but the baseline does — the serving scenario failed "
                  "or was dropped", file=sys.stderr)
            return 1
        return 0
    failures = 0
    rc = sec.get("steady_state_recompiles")
    if isinstance(rc, (int, float)) and rc > 0:
        print(f"compare_bench: serving section recompiled {int(rc)} "
              "time(s) at steady state — the compiled ladder retraces "
              "under the benched request mix; its latencies measure "
              "compiles", file=sys.stderr)
        failures += 1
    osec = old.get("serving")
    if isinstance(osec, dict):
        op, np_ = osec.get("latency_p95_ms"), sec.get("latency_p95_ms")
        if isinstance(op, (int, float)) and isinstance(np_, (int, float)) \
                and op > 0 and np_ > op * (1.0 + SERVING_P95_TOL):
            print(f"compare_bench: serving REGRESSION: p95 latency "
                  f"{op:.1f} -> {np_:.1f} ms "
                  f"(+{(np_ / op - 1) * 100:.1f}%) at fixed QPS — the "
                  "served path got slower", file=sys.stderr)
            failures += 1
    return failures


#: max tolerated growth of the online section's serve p95 (same latency
#: ratchet as the standalone serving section — fixed step-paced load)
ONLINE_P95_TOL = 0.10
#: max tolerated |AUC(online) - AUC(offline replay)|: the RCU snapshots
#: are COPIES, so concurrent serving must not move the trajectory — a
#: nonzero delta here means the publisher leaked aliased buffers or the
#: serve path wrote into live tables (the statistical twin of
#: check_online's checkpoint-CRC identity)
ONLINE_MAX_AUC_DELTA = 0.002


def check_online(old: Dict[str, Any], new: Dict[str, Any]) -> int:
    """Gate the ``online`` section (ISSUE 16): concurrent train-and-serve
    at fixed staleness.

    * nonzero ``steady_state_recompiles`` fails outright — any mix of
      training, publication and serving that retraces poisons both the
      joint throughput and the latencies;
    * ``freshness_p95_steps`` above the section's own
      ``freshness_slo_steps`` fails — the publisher fell behind the
      staleness budget the section claims to hold;
    * ``auc_delta_vs_replay`` beyond :data:`ONLINE_MAX_AUC_DELTA` fails
      — serving perturbed the training trajectory;
    * serve ``latency_p95_ms`` growing beyond :data:`ONLINE_P95_TOL`
      versus the baseline fails;
    * a candidate missing the section while the baseline has it fails
      (the online scenario crashed or was dropped — absence would hide
      exactly what this gate watches).
    """
    sec = new.get("online")
    if not isinstance(sec, dict):
        if isinstance(old.get("online"), dict):
            print("compare_bench: candidate has no 'online' section "
                  "but the baseline does — the online train-and-serve "
                  "scenario failed or was dropped", file=sys.stderr)
            return 1
        return 0
    failures = 0
    rc = sec.get("steady_state_recompiles")
    if isinstance(rc, (int, float)) and rc > 0:
        print(f"compare_bench: online section recompiled {int(rc)} "
              "time(s) at steady state — training, publication or "
              "serving retraced under the fixed joint load",
              file=sys.stderr)
        failures += 1
    fresh = sec.get("freshness_p95_steps")
    slo = sec.get("freshness_slo_steps")
    if isinstance(fresh, (int, float)) and isinstance(slo, (int, float)) \
            and fresh > slo:
        print(f"compare_bench: online freshness p95 {fresh} steps "
              f"exceeds the section's own SLO {slo} — snapshot "
              "publication fell behind training", file=sys.stderr)
        failures += 1
    delta = sec.get("auc_delta_vs_replay")
    if isinstance(delta, (int, float)) \
            and abs(delta) > ONLINE_MAX_AUC_DELTA:
        print(f"compare_bench: online AUC is {delta:+.4f} off the "
              "offline replay of the identical stream (tolerance "
              f"{ONLINE_MAX_AUC_DELTA}) — concurrent serving moved the "
              "training trajectory", file=sys.stderr)
        failures += 1
    osec = old.get("online")
    if isinstance(osec, dict):
        op, np_ = osec.get("latency_p95_ms"), sec.get("latency_p95_ms")
        if isinstance(op, (int, float)) and isinstance(np_, (int, float)) \
                and op > 0 and np_ > op * (1.0 + ONLINE_P95_TOL):
            print(f"compare_bench: online serve REGRESSION: p95 latency "
                  f"{op:.1f} -> {np_:.1f} ms "
                  f"(+{(np_ / op - 1) * 100:.1f}%) at fixed step-paced "
                  "load — the snapshot-serving path got slower",
                  file=sys.stderr)
            failures += 1
    return failures


#: out-of-process serve latency may cost a socket + pickle round-trip
#: over the in-process floor, but not a structural multiple of it: p99
#: beyond FACTOR x inproc + SLACK ms means the boundary grew a stall
#: (lock convoy, nagle, shm retry storm), not just overhead
ISOLATED_OOP_FACTOR = 5.0
ISOLATED_OOP_SLACK_MS = 10.0
#: ceiling on restart-to-first-served — a reborn worker re-ingests the
#: latest shm snapshot BEFORE answering, so first service after the
#: ready handshake is bounded host work, not a recompile
ISOLATED_RESTART_MS = 30_000.0


def check_isolated_serving(old: Dict[str, Any],
                           new: Dict[str, Any]) -> int:
    """Gate the ``isolated_serving`` section (ISSUE 18): process-isolated
    serving with crash containment.

    * a record whose worker never crashed+restarted fails — the section
      EXISTS to measure supervision under a real kill; zero restarts
      means the drill fizzled;
    * ``budget_ok`` != 1 fails — the restart exhausted its backoff
      budget;
    * ``conserved`` != 1 fails — a request future was lost, duplicated
      or left hanging across the crash;
    * nonzero ``steady_state_recompiles`` fails — the reborn worker
      retraced its serve ladder;
    * out-of-process p99 beyond :data:`ISOLATED_OOP_FACTOR` x the
      in-process floor (+ :data:`ISOLATED_OOP_SLACK_MS`) fails — the
      boundary grew a structural stall;
    * ``restart_to_first_served_ms`` beyond
      :data:`ISOLATED_RESTART_MS` fails;
    * a candidate missing the section while the baseline has it fails.
    """
    sec = new.get("isolated_serving")
    if not isinstance(sec, dict):
        if isinstance(old.get("isolated_serving"), dict):
            print("compare_bench: candidate has no 'isolated_serving' "
                  "section but the baseline does — the process-isolation "
                  "scenario failed or was dropped", file=sys.stderr)
            return 1
        return 0
    failures = 0
    if not sec.get("crashes") or not sec.get("restarts"):
        print(f"compare_bench: isolated_serving recorded crashes="
              f"{sec.get('crashes')} restarts={sec.get('restarts')} — "
              "the mid-stream kill never happened or the supervisor "
              "never restarted the worker", file=sys.stderr)
        failures += 1
    if sec.get("budget_ok") != 1:
        print("compare_bench: isolated_serving restart budget exhausted "
              "— the worker could not be brought back within the "
              "backoff budget", file=sys.stderr)
        failures += 1
    if sec.get("conserved") != 1:
        print("compare_bench: isolated_serving request conservation "
              "broken — a future was lost, duplicated or left hanging "
              "across the worker crash", file=sys.stderr)
        failures += 1
    rc = sec.get("steady_state_recompiles")
    if isinstance(rc, (int, float)) and rc > 0:
        print(f"compare_bench: isolated_serving recompiled {int(rc)} "
              "time(s) at steady state — the reborn worker retraced its "
              "serve ladder", file=sys.stderr)
        failures += 1
    ip, op = sec.get("inproc_p99_ms"), sec.get("oop_p99_ms")
    if isinstance(ip, (int, float)) and isinstance(op, (int, float)) \
            and op > ip * ISOLATED_OOP_FACTOR + ISOLATED_OOP_SLACK_MS:
        print(f"compare_bench: isolated_serving boundary overhead: "
              f"out-of-process p99 {op:.1f} ms vs in-process floor "
              f"{ip:.1f} ms — beyond {ISOLATED_OOP_FACTOR:.0f}x + "
              f"{ISOLATED_OOP_SLACK_MS:.0f} ms, the socket/shm path "
              "grew a structural stall", file=sys.stderr)
        failures += 1
    rtfs = sec.get("restart_to_first_served_ms")
    if isinstance(rtfs, (int, float)) and rtfs > ISOLATED_RESTART_MS:
        print(f"compare_bench: isolated_serving restart-to-first-served "
              f"{rtfs:.0f} ms exceeds {ISOLATED_RESTART_MS:.0f} ms — "
              "the reborn worker did not resume service promptly",
              file=sys.stderr)
        failures += 1
    return failures


#: max tolerated growth of the observability plane's own costs
#: (stats() wall time, HTTP scrape round-trip, black-box dump). These
#: are microsecond/millisecond-scale host measurements with real
#: scheduler noise, so the ratchet is deliberately looser than the 10%
#: throughput gate: a 2x jump is a structural regression (the plane
#: grew a sort, a lock convoy, or an O(window) path back), not jitter.
OBS_PLANE_COST_TOL = 1.0
#: per-metric noise floors: below these absolute baselines the ratchet
#: is skipped — doubling a 3us stats() call is timer noise, doubling a
#: 300us one is a regression
OBS_PLANE_FLOORS = {
    "stats_wall_us": 20.0,
    "scrape_ms": 0.25,
    "dump_ms": 0.25,
}


def check_obs_plane(old: Dict[str, Any], new: Dict[str, Any]) -> int:
    """Gate the ``obs_plane`` section (ISSUE 17): the observability
    plane must stay an instrument, not a workload.

    * a failed scrape (``scrape_ok`` != 1) fails outright — the
      Prometheus endpoint served garbage or nothing while the section
      ran;
    * nonzero ``steady_state_recompiles`` fails — observing a warmed
      serving ladder must never retrace it;
    * each cost in :data:`OBS_PLANE_FLOORS` growing beyond
      :data:`OBS_PLANE_COST_TOL` versus a baseline above its noise
      floor fails — the cost ratchet on the plane's own read, scrape,
      and crash-dump paths;
    * a candidate missing the section while the baseline has it fails
      (the cost measurement crashed or was dropped — absence would hide
      exactly the regressions this gate watches).

    The serving latencies the plane *measures* are gated separately by
    :func:`check_serving`; this gate prices the measuring itself.
    """
    sec = new.get("obs_plane")
    if not isinstance(sec, dict):
        if isinstance(old.get("obs_plane"), dict):
            print("compare_bench: candidate has no 'obs_plane' section "
                  "but the baseline does — the observability-plane cost "
                  "measurement failed or was dropped", file=sys.stderr)
            return 1
        return 0
    failures = 0
    if sec.get("scrape_ok") != 1:
        print("compare_bench: obs_plane scrape_ok != 1 — the Prometheus "
              "scrape endpoint failed while the section ran",
              file=sys.stderr)
        failures += 1
    rc = sec.get("steady_state_recompiles")
    if isinstance(rc, (int, float)) and rc > 0:
        print(f"compare_bench: obs_plane section recompiled {int(rc)} "
              "time(s) at steady state — observing the serving ladder "
              "retraced it", file=sys.stderr)
        failures += 1
    osec = old.get("obs_plane")
    if isinstance(osec, dict):
        for key, floor in OBS_PLANE_FLOORS.items():
            ov, nv = osec.get(key), sec.get(key)
            if not isinstance(ov, (int, float)) \
                    or not isinstance(nv, (int, float)):
                continue
            if ov >= floor and nv > ov * (1.0 + OBS_PLANE_COST_TOL):
                print(f"compare_bench: obs_plane REGRESSION: {key} "
                      f"{ov:.2f} -> {nv:.2f} "
                      f"(+{(nv / ov - 1) * 100:.0f}%) — the plane's own "
                      "cost grew past the "
                      f"{OBS_PLANE_COST_TOL * 100:.0f}% ratchet",
                      file=sys.stderr)
                failures += 1
    return failures


#: tracing-on throughput must stay within this fraction of tracing-off
#: WITHIN the same record (retain-everything is the tracer's worst case)
TRACING_ON_MIN_FRAC = 0.7

#: tracing-off throughput may not drop below this fraction of the
#: baseline's (the "tracing off costs nothing" ratchet; loose enough
#: for shared-CPU noise, tight enough to catch a hot-path tax)
TRACING_OFF_MIN_FRAC = 0.6


def check_tracing(old: Dict[str, Any], new: Dict[str, Any]) -> int:
    """Gate the ``tracing`` section (ISSUE 20): request tracing must be
    free when off, bounded when on, and exact always.

    * ``span_sum_ok`` != 1 fails — a retained trace whose stage spans
      do not sum to its ``latency_ms`` is a lying instrument;
    * ``trace_off_disabled`` != 1 fails — the off run actually traced;
    * nonzero ``steady_state_recompiles`` fails — tracing perturbed the
      serve ladder's compile cache;
    * ``retained`` must be positive and bounded by ``ring_capacity``;
    * ``tracing_on_rps`` below :data:`TRACING_ON_MIN_FRAC` x
      ``tracing_off_rps`` (same record) fails — the tracer's
      retain-everything worst case grew into a workload;
    * ``tracing_off_rps`` below :data:`TRACING_OFF_MIN_FRAC` x the
      baseline's fails — the disabled path grew a tax;
    * a candidate missing the section while the baseline has it fails.
    """
    sec = new.get("tracing")
    if not isinstance(sec, dict):
        if isinstance(old.get("tracing"), dict):
            print("compare_bench: candidate has no 'tracing' section "
                  "but the baseline does — the tracing cost measurement "
                  "failed or was dropped", file=sys.stderr)
            return 1
        return 0
    failures = 0
    if sec.get("span_sum_ok") != 1:
        print("compare_bench: tracing span_sum_ok != 1 — a retained "
              "trace's stage spans do not sum to its latency_ms within "
              "tolerance", file=sys.stderr)
        failures += 1
    if sec.get("trace_off_disabled") != 1:
        print("compare_bench: tracing trace_off_disabled != 1 — the "
              "tracing-off baseline run was actually tracing",
              file=sys.stderr)
        failures += 1
    rc = sec.get("steady_state_recompiles")
    if isinstance(rc, (int, float)) and rc > 0:
        print(f"compare_bench: tracing section recompiled {int(rc)} "
              "time(s) at steady state — tracing perturbed the serve "
              "ladder", file=sys.stderr)
        failures += 1
    retained, cap = sec.get("retained"), sec.get("ring_capacity")
    if not isinstance(retained, (int, float)) or retained < 1 \
            or (isinstance(cap, (int, float)) and retained > cap):
        print(f"compare_bench: tracing retained={retained!r} of "
              f"capacity={cap!r} — retention is empty or unbounded",
              file=sys.stderr)
        failures += 1
    off, on = sec.get("tracing_off_rps"), sec.get("tracing_on_rps")
    if isinstance(off, (int, float)) and isinstance(on, (int, float)) \
            and off > 0 and on < off * TRACING_ON_MIN_FRAC:
        print(f"compare_bench: tracing-on throughput {on:.0f} rps < "
              f"{TRACING_ON_MIN_FRAC:.0%} of tracing-off {off:.0f} rps "
              "— the retain-everything worst case costs too much",
              file=sys.stderr)
        failures += 1
    osec = old.get("tracing")
    if isinstance(osec, dict):
        o_off = osec.get("tracing_off_rps")
        if isinstance(o_off, (int, float)) and o_off > 0 \
                and isinstance(off, (int, float)) \
                and off < o_off * TRACING_OFF_MIN_FRAC:
            print(f"compare_bench: tracing-off throughput REGRESSION: "
                  f"{o_off:.0f} -> {off:.0f} rps (below the "
                  f"{TRACING_OFF_MIN_FRAC:.0%} ratchet) — the disabled "
                  "tracer grew a hot-path tax", file=sys.stderr)
            failures += 1
    return failures


def compare(old: Dict[str, Any], new: Dict[str, Any],
            threshold: float) -> int:
    steady_failures = check_steady_state(new)
    steady_failures += check_phase_budget(old, new)
    steady_failures += check_plan_audit(old, new)
    steady_failures += check_schedule(old, new)
    steady_failures += check_schedule(old, new, key="schedule_pipelined")
    steady_failures += check_phase_profile(old, new)
    steady_failures += check_phase_profile(old, new,
                                           key="phase_profile_pipelined")
    steady_failures += check_streaming(old, new)
    steady_failures += check_serving(old, new)
    steady_failures += check_online(old, new)
    steady_failures += check_isolated_serving(old, new)
    steady_failures += check_obs_plane(old, new)
    steady_failures += check_tracing(old, new)
    regressions = 0
    rows = []
    for keys, higher_better in ((THROUGHPUT_KEYS, True), (MS_KEYS, False)):
        for k in keys:
            ov, nv = old.get(k), new.get(k)
            if not isinstance(ov, (int, float)) or not isinstance(
                    nv, (int, float)):
                if (ov is None) != (nv is None):
                    rows.append((k, ov, nv, None, "only-one-side"))
                continue
            if not ov:
                # a failed section records 0.0 (bench _guard default):
                # not comparable, but NEVER silently dropped — a section
                # flipping between failed and healthy must stay visible
                rows.append((k, ov, nv, None, "baseline-zero"))
                continue
            change = (nv - ov) / ov
            regressed = (change < -threshold if higher_better
                         else change > threshold)
            rows.append((k, ov, nv, change,
                         "REGRESSION" if regressed else "ok"))
            regressions += bool(regressed)
    width = max((len(r[0]) for r in rows), default=10)
    for k, ov, nv, change, verdict in rows:
        pct = "" if change is None else f"{change * 100:+7.1f}%  "
        print(f"{k:<{width}}  {ov!s:>12} -> {nv!s:>12}  {pct}{verdict}")
    if regressions:
        print(f"compare_bench: {regressions} metric(s) regressed beyond "
              f"{threshold * 100:.0f}%", file=sys.stderr)
    if regressions or steady_failures:
        return 1
    print(f"compare_bench: OK ({len(rows)} metric(s) compared, none beyond "
          f"{threshold * 100:.0f}%; steady-state recompiles clean)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("old", help="baseline BENCH json (driver wrapper or "
                                "raw bench.py line)")
    ap.add_argument("new", help="candidate BENCH json")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="max tolerated fractional regression "
                         "(default 0.10 = 10%%)")
    ap.add_argument("--allow-env-mismatch", action="store_true",
                    help="downgrade the cross-backend refusal to a "
                         "warning (deliberate CPU-vs-TPU reading only)")
    args = ap.parse_args(argv)
    old, new = load_bench(args.old), load_bench(args.new)
    if old is None or new is None:
        return 2
    if check_env(old, new, allow_mismatch=args.allow_env_mismatch):
        return 1
    return compare(old, new, args.threshold)


if __name__ == "__main__":
    sys.exit(main())
