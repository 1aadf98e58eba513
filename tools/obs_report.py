#!/usr/bin/env python
"""The embedding telemetry observatory report: fuse step-metrics
sidecars, jit-carried access telemetry, and static HBM/FLOP accounting
into one run summary.

Three ways in:

* ``python tools/obs_report.py`` (= ``make obs-report``) — the live
  demo/acceptance run: an 8-virtual-device CPU mesh trains a small
  hybrid model on Zipfian synthetic inputs with PLANTED heavy hitters
  and an engineered per-rank load skew, with metrics + telemetry on.
  The report must recover the planted hot rows in the per-table top-k,
  show the planted imbalance in the per-rank load ratios, and carry the
  abstract-lowering HBM/FLOP budget — and the run verifies the
  telemetry is genuinely jit-carried: zero steady-state recompiles
  (``obs.install_compile_listener`` delta over the post-warmup steps)
  and zero host callbacks in the audited jaxpr. Nonzero exit when any
  of that fails, so the target doubles as a gate.
* ``python tools/obs_report.py --metrics run.metrics.jsonl
  [--telemetry run.telemetry.json] [--phases phase_profile.json]`` —
  fuse existing artifacts (a step-metrics sidecar, a resilient run's
  checkpoint-side telemetry flush, a ``tools/phase_profile.py --json``
  measured-phase artifact — or a raw ``DETPU_PROFILE_DIR`` trace
  capture, parsed jax-free) without running anything.
* ``python tools/obs_report.py --selftest`` (wired into ``make
  verify``) — synthetic metrics JSONL + telemetry summary + the
  checked-in miniature trace (``tests/data/mini.trace.json.gz``)
  through the full fusion + render path, no jax, sub-second.

Output: a human-readable report on stdout (``--json PATH`` for the
machine-readable version): per-table top-k hot rows with Zipf-skew
exponents, per-rank routed-id imbalance ratio time series, the a2a byte
breakdown, and the per-table/slab HBM budget table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEMO_WORLD = 8
DEMO_TABLES = 16
DEMO_VOCAB = 1000
DEMO_BATCH = 128
#: (table, row, fraction-of-batch) heavy hitters the demo plants — and
#: the acceptance check then requires in the per-table top-k
PLANTED = ((0, 5, 0.25), (3, 17, 0.20), (9, 250, 0.15))
#: the demo's skewed ragged feature rides table 15, whose owning rank
#: receives ~RAGGED_HOT x the dense per-slot load
RAGGED_TABLE = 15
RAGGED_HOT = 12


def _force_cpu(devices: int) -> None:
    """Before the first jax import: the observatory's live demo is a CPU
    harness tool and must never wait on an accelerator backend."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}")
    os.environ.pop("DETPU_OBS", None)


# ------------------------------------------------------------------ fusion


def load_metrics(path: str) -> List[Dict[str, Any]]:
    """step_metrics records of a MetricsLogger sidecar (rotated ``.1``
    generation included, oldest first; torn lines tolerated)."""
    from distributed_embeddings_tpu.utils.obs import MetricsLogger

    recs: List[Dict[str, Any]] = []
    for p in (path + ".1", path):
        if os.path.exists(p):
            recs.extend(r for r in MetricsLogger.load(p)
                        if r.get("section") == "step_metrics")
    return recs


def metrics_digest(records: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """Fold step-metrics records into the observatory's exchange view:
    per-record per-rank imbalance ratios (the time series), a2a byte
    breakdown, overflow/invalid totals."""
    if not records:
        return None
    series = []
    a2a = {"id_a2a_bytes": 0.0, "out_a2a_bytes": 0.0, "grad_a2a_bytes": 0.0}
    overflow = invalid = 0.0
    for rec in records:
        m = rec.get("metrics", {})
        ids = m.get("ids_routed")
        flat = _flatten(ids) if ids is not None else []
        if flat:
            mean = sum(flat) / len(flat)
            series.append({
                "step": rec.get("step"),
                "ratio": (max(flat) / mean) if mean > 0 else 1.0,
            })
        for k in a2a:
            v = m.get(k)
            if v is not None:
                a2a[k] += sum(_flatten(v))
        for k, acc in (("id_overflow", "o"), ("invalid_id_count", "i")):
            v = m.get(k)
            if v is None:
                continue
            s = sum(_flatten(v))
            if acc == "o":
                overflow += s
            else:
                invalid += s
    ratios = [s["ratio"] for s in series]
    return {
        "records": len(records),
        "imbalance_series": series,
        "imbalance_max": max(ratios) if ratios else None,
        "a2a_bytes": dict(a2a, total=sum(a2a.values())),
        "id_overflow_total": overflow,
        "invalid_id_total": invalid,
    }


def _flatten(v) -> List[float]:
    if hasattr(v, "tolist"):  # numpy / jax arrays (fetch_metrics output)
        v = v.tolist()
    if isinstance(v, (list, tuple)):
        out: List[float] = []
        for x in v:
            out.extend(_flatten(x))
        return out
    return [float(v)]


def fuse_report(metrics: Optional[Dict[str, Any]],
                telemetry: Optional[Dict[str, Any]],
                hbm: Optional[Dict[str, Any]],
                verified: Optional[Dict[str, Any]] = None,
                phases: Optional[List[Dict[str, Any]]] = None,
                traces: Optional[Dict[str, Any]] = None
                ) -> Dict[str, Any]:
    """One observatory record from whichever inputs exist."""
    return {"metric": "obs_report", "metrics": metrics,
            "telemetry": telemetry, "hbm": hbm, "verified": verified,
            "phases": phases, "traces": traces}


def load_request_traces(path: str) -> Dict[str, Any]:
    """A request-trace export (``TraceBuffer.export``'s Chrome trace
    document, ``.gz`` fine) -> the report's trace digest: outcome
    histogram, span-partition violations (``sum(stages) != latency``
    beyond the writer's tolerance), restart-crossing traces, and the
    slowest retained requests with their dominant stage."""
    from distributed_embeddings_tpu.utils import reqtrace, traceparse

    recs = traceparse.parse_request_traces(path)
    outcomes: Dict[str, int] = {}
    bad_sum = 0
    crossing: List[str] = []
    for t in recs:
        outcomes[t["outcome"]] = outcomes.get(t["outcome"], 0) + 1
        lat = t.get("latency_ms")
        if isinstance(lat, (int, float)) and t["stages_ms"] and \
                abs(sum(t["stages_ms"].values()) - lat) \
                > reqtrace.SPAN_SUM_TOL_MS:
            bad_sum += 1
        if t["attrs"].get("restart_crossed"):
            crossing.append(t["trace_id"])
    slow = sorted(
        (t for t in recs if isinstance(t.get("latency_ms"), (int, float))),
        key=lambda t: -t["latency_ms"])[:5]
    return {
        "traces": len(recs),
        "outcomes": dict(sorted(outcomes.items())),
        "span_sum_violations": bad_sum,
        "restart_crossing": crossing,
        "slowest": [{
            "trace_id": t["trace_id"], "outcome": t["outcome"],
            "latency_ms": round(t["latency_ms"], 3),
            "dominant_stage": (max(t["stages_ms"],
                                   key=t["stages_ms"].get)
                               if t["stages_ms"] else None),
            "marks": [e["name"] for e in t["events"]],
        } for t in slow],
    }


def load_phases(path: str) -> List[Dict[str, Any]]:
    """Measured-phase cases from either artifact shape:

    * a ``tools/phase_profile.py --json`` dump (list of case records) —
      passed through with calibration/violations intact;
    * a raw trace capture — a ``DETPU_PROFILE_DIR`` directory or one
      ``.trace.json[.gz]`` file — parsed with the jax-free
      ``utils/traceparse.py`` (metadata-tier attribution only: no
      compiled HLO to join bare names against) and reduced to the same
      summary shape.
    """
    from distributed_embeddings_tpu.utils import traceparse

    if os.path.isdir(path) or ".trace.json" in os.path.basename(path):
        events = traceparse.parse_capture(path)
        if not events:
            raise ValueError(f"no op events parsed from trace {path!r}")
        m = traceparse.measure_events(events)
        return [{
            "label": os.path.basename(path.rstrip(os.sep)),
            "profile": {
                "step_wall_ms_p50": m["wall_ms"],
                "group_ms": m["group_ms"],
                "a2a_frac": m["a2a_frac"],
                "concurrency": m["concurrency"],
                "measured_serialized_fraction":
                    m["measured_serialized_fraction"],
                "collectives": m["collectives"],
                "resolved_frac": (m["events_resolved"] / m["events"]
                                  if m["events"] else 0.0),
            },
            "phase_ms": {k: {"p50": v} for k, v in m["phase_ms"].items()},
        }]
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        doc = [doc]
    return doc


def _fmt_bytes(n: Optional[float]) -> str:
    if n is None:
        return "?"
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} GiB"


def render(report: Dict[str, Any]) -> str:
    """Human-readable observatory report."""
    lines: List[str] = ["== embedding telemetry observatory =="]
    tel = report.get("telemetry")
    if tel:
        lines.append(f"-- access telemetry ({tel.get('steps', '?')} steps)")
        lines.append(
            "   per-rank routed ids: "
            + ", ".join(f"{x:.0f}" for x in tel.get("per_rank_ids", []))
            + f"  (imbalance ratio {tel.get('imbalance_ratio', 0):.3f})")
        for t in tel.get("tables", []):
            alpha = t.get("zipf_alpha")
            top = ", ".join(f"row {r}~{c}" for r, c in t["top_rows"][:5])
            lines.append(
                f"   table {t['table_id']:>3} ({t['rows']}x{t['width']}): "
                f"{top}"
                + (f"  zipf~{alpha:.2f}" if alpha is not None else ""))
    m = report.get("metrics")
    if m:
        lines.append(f"-- step metrics ({m['records']} records)")
        a2a = m["a2a_bytes"]
        lines.append(
            f"   a2a bytes: id {_fmt_bytes(a2a['id_a2a_bytes'])} | out "
            f"{_fmt_bytes(a2a['out_a2a_bytes'])} | grad "
            f"{_fmt_bytes(a2a['grad_a2a_bytes'])} | total "
            f"{_fmt_bytes(a2a['total'])}")
        if m.get("imbalance_max") is not None:
            lines.append(
                f"   routed-id imbalance ratio: max {m['imbalance_max']:.3f}"
                f" over {len(m['imbalance_series'])} sampled steps")
        lines.append(
            f"   overflow ids {m['id_overflow_total']:.0f} | invalid ids "
            f"{m['invalid_id_total']:.0f}")
    hbm = report.get("hbm")
    if hbm:
        tot = hbm["layout"]["totals"]
        lines.append("-- HBM budget (static, abstract lowering)")
        lines.append(
            f"   params {_fmt_bytes(tot['param_bytes_allocated'])} "
            f"allocated / {_fmt_bytes(tot['param_bytes_live'])} live "
            f"(padding {tot['padding_frac'] * 100:.1f}%) | opt state "
            f"{_fmt_bytes(tot['opt_state_bytes'])}")
        for key, slab in sorted(hbm["layout"]["slabs"].items()):
            lines.append(
                f"   slab {key}: {slab['shape']} "
                f"{_fmt_bytes(slab['param_bytes'])} "
                f"(live {_fmt_bytes(slab['live_bytes'])}, opt "
                f"{_fmt_bytes(slab['opt_state_bytes'])})")
        comp = hbm.get("compiled") or {}
        if comp.get("error"):
            lines.append(f"   compiled-step analysis unavailable: "
                         f"{comp['error']}")
        else:
            lines.append(
                f"   compiled step [{comp.get('backend')}]: peak est "
                f"{_fmt_bytes(comp.get('peak_bytes_est'))} (args "
                f"{_fmt_bytes(comp.get('argument_bytes'))}, temps "
                f"{_fmt_bytes(comp.get('temp_bytes'))}, aliased "
                f"{_fmt_bytes(comp.get('alias_bytes'))}) | "
                f"flops {comp.get('flops')} | bytes accessed "
                f"{_fmt_bytes(comp.get('bytes_accessed'))}")
        traffic = hbm.get("per_table_traffic") or []
        heavy = sorted(traffic, key=lambda t: -t["est_hbm_bytes_per_step"])
        for t in heavy[:5]:
            lines.append(
                f"   table {t['table_id']:>3}: ~{t['ids_per_step']} "
                f"ids/step, est {_fmt_bytes(t['est_hbm_bytes_per_step'])}"
                f"/step, {t['est_flops_per_step']} flops/step")
    phases = report.get("phases")
    if phases:
        lines.append(f"-- measured phase profile ({len(phases)} case(s))")
        for case in phases:
            prof = case.get("profile") or {}
            frac = prof.get("measured_serialized_fraction")
            lines.append(
                f"   {case.get('label', '?')}: wall p50 "
                f"{prof.get('step_wall_ms_p50', 0):.2f} ms | a2a in "
                f"flight {prof.get('a2a_frac', 0) * 100:.1f}% | "
                f"concurrency x{prof.get('concurrency', 0):.2f} | "
                "measured serialized frac "
                + (f"{frac:.3f}" if isinstance(frac, (int, float))
                   else "n/a"))
            groups = prof.get("group_ms") or {}
            if groups:
                lines.append("      breakdown ms: " + " | ".join(
                    f"{g} {groups[g]:.2f}" for g in
                    ("exchange", "lookup", "dense", "apply", "streaming",
                     "other") if g in groups))
            for c in prof.get("collectives") or []:
                lines.append(
                    f"      {c['phase']}: {c['classification']} "
                    f"(hidden {c.get('hidden_frac', 0) * 100:.0f}%)")
            calib = case.get("calibration") or {}
            flagged = calib.get("flagged")
            if flagged is not None:
                lines.append(
                    f"      calibration: x"
                    f"{calib.get('scale_measured_over_modeled', 0):.0f} "
                    "backend scale, "
                    + (f"{len(flagged)} phase(s) DRIFT beyond "
                       f"{calib.get('drift_max')}x" if flagged
                       else "no phase drifts beyond "
                            f"{calib.get('drift_max')}x"))
            for v in case.get("agreement_violations") or []:
                lines.append(f"      VIOLATION: {v}")
    tr = report.get("traces")
    if tr:
        lines.append(f"-- request traces ({tr['traces']} retained)")
        lines.append("   outcomes: " + (", ".join(
            f"{k} {v}" for k, v in tr["outcomes"].items()) or "none"))
        lines.append(
            f"   span-partition violations: {tr['span_sum_violations']}"
            + ("  !!" if tr["span_sum_violations"] else ""))
        if tr["restart_crossing"]:
            lines.append("   restart-crossing: "
                         + ", ".join(tr["restart_crossing"]))
        for s in tr["slowest"]:
            marks = f"  [{', '.join(s['marks'])}]" if s["marks"] else ""
            lines.append(
                f"   {s['trace_id']}: {s['outcome']} "
                f"{s['latency_ms']:.3f} ms, dominant stage "
                f"{s['dominant_stage'] or 'n/a'}{marks}")
    ver = report.get("verified")
    if ver:
        lines.append("-- verification")
        for k, v in ver.items():
            lines.append(f"   {k}: {v}")
    return "\n".join(lines)


# ---------------------------------------------------------------- demo run


def run_demo(world: int, steps: int, batch: int,
             metrics_path: Optional[str] = None) -> Dict[str, Any]:
    """The acceptance run (see module docstring): train `steps` steps of
    a small hybrid model with planted heavy hitters + skewed ragged
    load, metrics and telemetry on, then fuse + verify."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from distributed_embeddings_tpu.analysis import (
        audit_step_fn, step_memory_report, telemetry as tel)
    from distributed_embeddings_tpu.ops.embedding_lookup import Ragged
    from distributed_embeddings_tpu.parallel import (
        DistributedEmbedding, SparseAdagrad, init_hybrid_state,
        make_hybrid_train_step)
    from distributed_embeddings_tpu.utils import obs, power_law_ids

    devs = jax.devices()
    if len(devs) < world:
        raise RuntimeError(
            f"host platform exposes {len(devs)} devices < {world}")
    mesh = Mesh(np.array(devs[:world]), ("data",))

    configs = [{"input_dim": DEMO_VOCAB, "output_dim": 8,
                "combiner": "sum" if i == RAGGED_TABLE else None}
               for i in range(DEMO_TABLES)]
    de = DistributedEmbedding(configs, world_size=world)
    tx = optax.sgd(0.01)
    emb_opt = SparseAdagrad()

    def loss_fn(dp, outs, _batch):
        x = jnp.concatenate([o.reshape(o.shape[0], -1) for o in outs],
                            axis=1)
        return jnp.mean((x @ dp["w"]) ** 2)

    dense_params = {"w": jnp.full((8 * DEMO_TABLES, 1), 0.1, jnp.float32)}
    state = init_hybrid_state(de, emb_opt, dense_params, tx,
                              jax.random.key(0), mesh=mesh)
    tel_cfg = tel.config_from_env()
    telem = tel.init_telemetry(de, tel_cfg, mesh=mesh)
    step = make_hybrid_train_step(de, loss_fn, tx, emb_opt, mesh=mesh,
                                  with_metrics=True, nan_guard=False,
                                  telemetry=tel_cfg)

    obs.install_compile_listener()
    logger = obs.MetricsLogger(metrics_path) if metrics_path else None
    rng = np.random.default_rng(0)
    local_b = batch // world
    cap = local_b * RAGGED_HOT  # per-shard static capacity

    def make_batch():
        cats: List[Any] = []
        for t in range(DEMO_TABLES):
            if t == RAGGED_TABLE:
                # the skew plant: every row of the ragged feature claims
                # RAGGED_HOT ids, so table 15's rank routes ~12x the ids
                # of a 1-hot dense slot. dp-sharded ragged layout: one
                # (values[cap], row_splits[local_b+1]) block per shard
                values = power_law_ids(rng, DEMO_VOCAB, (world * cap,))
                splits = np.tile(
                    np.arange(local_b + 1, dtype=np.int32) * RAGGED_HOT,
                    world)
                cats.append(Ragged(values=jnp.asarray(values, jnp.int32),
                                   row_splits=jnp.asarray(splits)))
                continue
            ids = power_law_ids(rng, DEMO_VOCAB, (batch,)).astype(np.int32)
            for tid, row, frac in PLANTED:
                if tid == t:
                    k = int(batch * frac)
                    pos = rng.permutation(batch)[:k]
                    ids[pos] = row
            cats.append(jnp.asarray(ids))
        return cats

    from distributed_embeddings_tpu.utils import envvars

    warmup = 2
    # metrics-log cadence (DETPU_TELEMETRY_INTERVAL, scaled down to the
    # demo's short run so a default-100 interval still samples it)
    interval = max(1, min(envvars.get_int("DETPU_TELEMETRY_INTERVAL"),
                          max(steps // 4, 1)))
    compiles_after_warmup = None
    loss = metrics = None
    for i in range(steps):
        loss, state, metrics, telem = step(state, make_batch(), None, telem)
        if i == warmup - 1:
            float(np.asarray(loss))  # drain, then mark the steady state
            compiles_after_warmup = obs.counters().get("recompiles", 0)
        if logger is not None and i % interval == 0:
            logger.log_step(obs.fetch_metrics(metrics), step=i,
                            summary=obs.summarize(metrics))
    float(np.asarray(loss))
    steady_recompiles = (obs.counters().get("recompiles", 0)
                         - (compiles_after_warmup or 0))

    # host-interop audit of the exact program (abstract, no execution)
    abs_args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") else a,
        (state, make_batch(), None, telem))
    audit = audit_step_fn(step, abs_args, world=world,
                          label="obs_report_demo")

    summary = tel.summarize_telemetry(de, telem, topk=tel_cfg.topk)

    # planted-heavy-hitter recovery check
    recovered = {}
    for tid, row, _frac in PLANTED:
        tab = next((t for t in summary["tables"]
                    if t["table_id"] == tid), None)
        recovered[f"table{tid}/row{row}"] = bool(
            tab and any(r == row for r, _ in tab["top_rows"]))

    hbm = step_memory_report(
        de, loss_fn, tx, emb_opt,
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
                     if hasattr(a, "shape") else a, make_batch()),
        None, mesh=mesh, with_metrics=True, nan_guard=False,
        telemetry=tel_cfg, dense_params=dense_params)

    verified = {
        "steady_state_recompiles": int(steady_recompiles),
        "host_interop_in_step": list(audit.host_interop),
        "planted_hot_rows_recovered": recovered,
        "imbalance_ratio": summary["imbalance_ratio"],
        "imbalance_skew_detected": summary["imbalance_ratio"] > 1.5,
    }
    metrics_digest_v = (metrics_digest(load_metrics(metrics_path))
                        if metrics_path else
                        metrics_digest([{"metrics": obs.fetch_metrics(
                            metrics), "step": steps - 1}]))
    return fuse_report(metrics_digest_v, summary, hbm, verified)


def demo_ok(report: Dict[str, Any]) -> bool:
    ver = report.get("verified") or {}
    return (ver.get("steady_state_recompiles") == 0
            and not ver.get("host_interop_in_step")
            and all((ver.get("planted_hot_rows_recovered") or {}).values())
            and bool(ver.get("imbalance_skew_detected")))


# ---------------------------------------------------------------- selftest


def _synth_metrics(path: str, steps: int = 6, world: int = 8) -> None:
    """Synthetic step-metrics JSONL in MetricsLogger's exact schema."""
    from distributed_embeddings_tpu.utils.obs import MetricsLogger

    logger = MetricsLogger(path)
    for s in range(steps):
        per_rank = [100.0 + 40.0 * (r == 0) + s for r in range(world)]
        logger.log_step({
            "ids_routed": per_rank,
            "id_overflow": [0.0] * world,
            "invalid_id_count": [0.0] * world,
            "id_a2a_bytes": [4096.0] * world,
            "out_a2a_bytes": [65536.0] * world,
            "grad_a2a_bytes": [65536.0] * world,
            "out_pad_frac": [0.1] * world,
            "loss": [0.5] * world,
        }, step=s)


#: the checked-in miniature TPU-style trace the no-jax selftest parses
#: (2 device lanes, metadata-embedded op_names, one fused event, one
#: event missing op_name, one host frame that must be dropped)
MINI_TRACE = os.path.join(REPO, "tests", "data", "mini.trace.json.gz")


def _synth_request_trace(tmp: str) -> str:
    """A two-trace request export through the REAL writer (one served
    with the full stage partition, one unavailable crossing a restart)
    — exercises the export -> parse -> digest path end to end."""
    from distributed_embeddings_tpu.utils import reqtrace

    buf = reqtrace.TraceBuffer(capacity=16, sample=1.0, seed=7,
                               enabled=True, process="selftest")
    buf.begin(0, 100.0)
    buf.finish(0, "served", 5.0, 100.005,
               {"queue_wait": 1.0, "coalesce": 0.5, "dispatch": 0.5,
                "device_compute": 2.5, "reply_slice": 0.5},
               flush=1, coalesced=2, flush_t0=100.001)
    buf.begin(1, 100.1)
    buf.event(1, "outage", t=100.2, reason="worker_crash")
    tr = buf.finish(1, "unavailable", 100.0, 100.2,
                    {"queue_wait": 100.0}, stranded=True)
    buf.append_event(tr["trace_id"], "worker_restarted", t=100.9)
    buf.annotate(tr["trace_id"], restart_crossed=True)
    path = os.path.join(tmp, "req.trace.json.gz")
    buf.export(path)
    return path


def _selftest_phases() -> List[str]:
    """Parse the checked-in miniature trace through the jax-free parser
    and check the hand-computable numbers; returns failure strings."""
    from distributed_embeddings_tpu.utils import traceparse

    bad: List[str] = []
    events = traceparse.parse_events(traceparse.load_trace(MINI_TRACE))
    if len(events) != 8:  # 9 X events minus the $python host frame
        bad.append(f"mini trace: expected 8 op events, got {len(events)}")
    m = traceparse.measure_events(events)
    want_phases = {
        "embedding_forward/id_all_to_all",
        "embedding_forward/lookup_w8_d/packed_gather",
        "sparse_apply/sparse_apply_w8",
    }
    missing_ph = want_phases - set(m["phase_ms"])
    if missing_ph:
        bad.append(f"mini trace: phases not recovered: {missing_ph}")
    # a2a spans [0,100)+[10,110) us -> union exactly 110 us
    if abs(m["a2a_union_ms"] - 0.11) > 1e-9:
        bad.append(f"mini trace: a2a union {m['a2a_union_ms']} != 0.11")
    # compute in flight during the a2a: [50,60) copy + [95,110) of the
    # pid2 gather/dot chain = 25 us -> serialized frac (110-25)/110
    frac = m["measured_serialized_fraction"]
    if frac is None or abs(frac - 85.0 / 110.0) > 1e-3:
        bad.append(f"mini trace: serialized fraction {frac} != "
                   f"{85.0 / 110.0:.4f}")
    if m["events_resolved"] != 7:  # copy.3 carries no op_name anywhere
        bad.append(f"mini trace: resolved {m['events_resolved']} != 7")
    if not any(c["classification"] == "serialized"
               for c in m["collectives"]):
        bad.append("mini trace: a2a not classified serialized")
    return bad


def selftest() -> int:
    """Synthetic metrics JSONL + telemetry summary + the checked-in
    miniature trace -> full fusion + render; asserts every report
    section materializes. No jax."""
    with tempfile.TemporaryDirectory(prefix="detpu_obs_report_") as tmp:
        side = os.path.join(tmp, "metrics.jsonl")
        _synth_metrics(side)
        m = metrics_digest(load_metrics(side))
        telemetry = {
            "steps": 6, "per_rank_ids": [840.0] + [600.0] * 7,
            "imbalance_ratio": 840.0 / 630.0,
            "tables": [{"table_id": 0, "rows": 1000, "width": 8,
                        "top_rows": [[5, 150], [17, 90], [2, 30],
                                     [40, 12]],
                        "zipf_alpha": 1.2}],
            "per_width_ids": {"w8": [840.0] + [600.0] * 7},
        }
        hbm = {
            "layout": {
                "totals": {"param_bytes_allocated": 1 << 20,
                           "param_bytes_live": 900 * 1024,
                           "padding_frac": 0.12,
                           "opt_state_bytes": 1 << 20},
                "slabs": {"w8": {"shape": [8, 1024, 128],
                                 "param_bytes": 1 << 20,
                                 "live_bytes": 900 * 1024,
                                 "opt_state_bytes": 1 << 20}},
            },
            "compiled": {"backend": "cpu", "peak_bytes_est": 5 << 20,
                         "argument_bytes": 4 << 20, "temp_bytes": 1 << 20,
                         "alias_bytes": 3 << 20, "flops": 1e6,
                         "bytes_accessed": 8e6, "error": None},
            "per_table_traffic": [{"table_id": 0, "ids_per_step": 128,
                                   "est_hbm_bytes_per_step": 12288,
                                   "est_flops_per_step": 4096}],
        }
        phases = load_phases(MINI_TRACE)
        req_traces = load_request_traces(_synth_request_trace(tmp))
        report = fuse_report(m, telemetry, hbm,
                             {"selftest": True}, phases=phases,
                             traces=req_traces)
        text = render(report)
        required = ("access telemetry", "step metrics", "HBM budget",
                    "imbalance ratio", "a2a bytes", "zipf", "slab w8",
                    "compiled step", "measured phase profile",
                    "id_all_to_all: serialized",
                    "request traces (2 retained)", "restart-crossing",
                    "span-partition violations: 0")
        missing = [r for r in required if r not in text]
        json.dumps(report)  # must round-trip
        if m is None or m["records"] != 6:
            missing.append("metrics records")
        # per-rank loads at step 0 are [140, 100 x7]: mean 105, max 140
        elif abs(m["imbalance_max"] - 140.0 / 105.0) > 1e-9:
            missing.append("imbalance math")
        missing.extend(_selftest_phases())
        if missing:
            print(text)
            for x in missing:
                print(f"obs_report selftest: missing {x!r}",
                      file=sys.stderr)
            return 1
    print("obs_report selftest: OK (synthetic metrics + telemetry + HBM "
          "budget + miniature measured trace fused and rendered)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--metrics", metavar="PATH",
                    help="fuse an existing step-metrics JSONL sidecar")
    ap.add_argument("--telemetry", metavar="PATH",
                    help="fuse an existing telemetry summary JSON (e.g. "
                         "a resilient run's <ckpt>.telemetry.json)")
    ap.add_argument("--phases", metavar="PATH",
                    help="fuse a measured phase-profile artifact: a "
                         "tools/phase_profile.py --json dump, or a raw "
                         "DETPU_PROFILE_DIR trace capture (dir or "
                         ".trace.json[.gz] file, parsed jax-free)")
    ap.add_argument("--traces", metavar="PATH",
                    help="fuse a request-trace export (the Chrome trace "
                         "document utils/reqtrace.py TraceBuffer.export "
                         "writes, .gz fine)")
    ap.add_argument("--run", action="store_true",
                    help="force the live demo run even with --metrics")
    ap.add_argument("--world", type=int, default=DEMO_WORLD)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--batch", type=int, default=DEMO_BATCH)
    ap.add_argument("--json", metavar="PATH",
                    help="also dump the fused report as JSON (- = stdout)")
    ap.add_argument("--selftest", action="store_true",
                    help="synthetic end-to-end render check (make verify)")
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    if args.selftest:
        return selftest()

    if args.metrics or args.telemetry or args.phases or args.traces:
        if not args.run:
            metrics = telemetry = phases = req_traces = None
            if args.metrics:
                if not os.path.exists(args.metrics) and \
                        not os.path.exists(args.metrics + ".1"):
                    print(f"obs_report: no metrics sidecar at "
                          f"{args.metrics}", file=sys.stderr)
                    return 2
                metrics = metrics_digest(load_metrics(args.metrics))
            if args.telemetry:
                try:
                    with open(args.telemetry, encoding="utf-8") as f:
                        telemetry = json.load(f)
                except (OSError, json.JSONDecodeError) as e:
                    print(f"obs_report: cannot read {args.telemetry}: {e}",
                          file=sys.stderr)
                    return 2
            if args.phases:
                try:
                    phases = load_phases(args.phases)
                except (OSError, ValueError,
                        json.JSONDecodeError) as e:
                    print(f"obs_report: cannot read {args.phases}: {e}",
                          file=sys.stderr)
                    return 2
            if args.traces:
                try:
                    req_traces = load_request_traces(args.traces)
                except (OSError, ValueError,
                        json.JSONDecodeError) as e:
                    print(f"obs_report: cannot read {args.traces}: {e}",
                          file=sys.stderr)
                    return 2
            report = fuse_report(metrics, telemetry, None, phases=phases,
                                 traces=req_traces)
            print(render(report))
            _maybe_json(report, args.json)
            return 0

    _force_cpu(max(args.world, 1))
    with tempfile.TemporaryDirectory(prefix="detpu_obs_demo_") as tmp:
        report = run_demo(args.world, args.steps, args.batch,
                          metrics_path=os.path.join(tmp, "metrics.jsonl"))
    print(render(report))
    _maybe_json(report, args.json)
    if not demo_ok(report):
        print("obs_report: verification FAILED (see the verification "
              "section above)", file=sys.stderr)
        return 1
    print("obs_report: OK (planted hot rows recovered, skew detected, "
          "telemetry jit-carried: 0 steady-state recompiles, no host "
          "callbacks)")
    return 0


def _maybe_json(report: Dict[str, Any], path: Optional[str]) -> None:
    if not path:
        return
    payload = json.dumps(report, indent=2)
    if path == "-":
        print(payload)
    else:
        with open(path, "w", encoding="utf-8") as f:
            f.write(payload + "\n")


if __name__ == "__main__":
    sys.exit(main())
