"""Single registry of every ``DETPU_*`` environment variable.

The knob surface grew one env read at a time (``DETPU_OBS``,
``DETPU_FAULT``, ``DETPU_NANGUARD``, ...) with no one place that says
what exists, what the default is, or what a value means — and nothing
stopping a typo'd ``os.environ.get("DETPU_OBSS")`` from shipping as a
silently-dead knob. This module is that place: every ``DETPU_*`` variable
is :func:`declare`'d here with its default and one-line meaning, and the
``env-registry`` detlint rule (``tools/detlint/rules/env_registry.py``)
fails the build on any ``DETPU_*`` env read whose name is not registered.

Reads may keep using ``os.environ`` directly with a registered name (the
lint rule resolves literals and module-level ``X_ENV = "DETPU_X"``
constants), or go through :func:`get`/:func:`enabled`/:func:`get_float`,
which also raise loudly on an undeclared name at run time.

Like the rest of :mod:`..utils`'s host-side layer, this module never
imports jax: the registry must be readable by pure-AST tooling and by
processes that never load a backend.
"""

from __future__ import annotations

import os
from typing import Dict, NamedTuple, Optional


class EnvVar(NamedTuple):
    """One registered knob: its default (``None`` = unset) and meaning."""
    name: str
    default: Optional[str]
    doc: str


_REGISTRY: Dict[str, EnvVar] = {}


def declare(name: str, default: Optional[str] = None, doc: str = "") -> str:
    """Register one ``DETPU_*`` variable; returns the name so call sites
    can do ``FOO_ENV = declare("DETPU_FOO", ...)``. Declarations live in
    this module (below) so the detlint rule can extract the full set from
    the AST without importing anything."""
    _REGISTRY[name] = EnvVar(name, default, doc)
    return name


def registered() -> Dict[str, EnvVar]:
    """Snapshot of the full registry (name -> :class:`EnvVar`)."""
    return dict(_REGISTRY)


def _require(name: str) -> EnvVar:
    spec = _REGISTRY.get(name)
    if spec is None:
        raise KeyError(
            f"{name!r} is not a registered DETPU env var — declare it in "
            "distributed_embeddings_tpu/utils/envvars.py (the env-registry "
            "lint rule would reject the read anyway)")
    return spec


def get(name: str, default: Optional[str] = None) -> Optional[str]:
    """Read a registered variable; ``default`` overrides the declared
    default for this one call (tests and shims occasionally need that)."""
    spec = _require(name)
    fallback = spec.default if default is None else default
    return os.environ.get(name, fallback)


def enabled(name: str) -> bool:
    """Truthy read with the repo-wide convention: unset-with-falsy-default,
    empty, and ``"0"`` are off; anything else is on."""
    v = get(name)
    return v not in (None, "", "0")


def get_float(name: str, default: Optional[float] = None) -> float:
    """Float read of a registered variable; a malformed value falls back
    to the default instead of crashing a training run over a typo."""
    spec = _require(name)
    fb = default if default is not None else float(spec.default or 0.0)
    try:
        return float(os.environ.get(name, fb))
    except (TypeError, ValueError):
        return fb


def get_int(name: str, default: Optional[int] = None) -> int:
    """Int read of a registered variable (same fallback policy as
    :func:`get_float`)."""
    spec = _require(name)
    fb = default if default is not None else int(spec.default or 0)
    try:
        return int(os.environ.get(name, fb))
    except (TypeError, ValueError):
        return fb


# --------------------------------------------------------------------------
# The registry. One declare() per knob, literal names only (the lint rule
# reads these calls from the AST). Keep alphabetical within each block.
# --------------------------------------------------------------------------

# observability (utils/obs.py + utils/mplane.py)
declare("DETPU_BLACKBOX", default="1",
        doc="0 = disable the flight recorder (utils/mplane.py): no "
            "black-box ring is installed and no <dir>.blackbox.json "
            "post-mortem is dumped on NaN escalation / rollback "
            "exhaustion / freshness breach / preemption / crash")
declare("DETPU_BLACKBOX_RING", default="64",
        doc="flight-recorder ring capacity: how many recent step-metric "
            "summaries, events, and stats snapshots (each kind "
            "separately) the black-box dump carries")
declare("DETPU_METRICS_PORT", default=None,
        doc="opt-in Prometheus scrape endpoint port (utils/mplane.py "
            "start_http_exporter serves GET /metrics as text "
            "exposition); unset = no endpoint, 0 = ephemeral port "
            "(tests/drills read it back from the exporter handle)")
declare("DETPU_OBS", default="",
        doc="1 = build train steps with on-device step metrics (3-tuple "
            "return) and emit metrics sidecars")
declare("DETPU_OBS_MAX_BYTES", default="0",
        doc="MetricsLogger sidecar size cap in bytes; on overflow the "
            "file rotates through <path>.1..<path>.N "
            "(DETPU_OBS_MAX_FILES generations kept). 0 = unbounded "
            "(the historical behavior)")
declare("DETPU_OBS_MAX_FILES", default="2",
        doc="rotated MetricsLogger generations kept beyond the live "
            "sidecar (<path>.1 newest .. <path>.N oldest — the "
            "checkpoint-ring idiom); total disk is bounded by "
            "(N + 1) * DETPU_OBS_MAX_BYTES")

# access telemetry (analysis/telemetry.py; carried through train steps
# built by parallel/trainer.py when enabled)
declare("DETPU_TELEMETRY", default="",
        doc="1 = telemetry-aware entry points (examples/dlrm, "
            "tools/obs_report.py) build their "
            "steps with jit-carried access telemetry. Plain step "
            "builders need the explicit telemetry= opt-in (it changes "
            "the step's call arity)")
declare("DETPU_TELEMETRY_CANDIDATES", default="0",
        doc="per-step unique-id candidates merged into the hot-row "
            "top-k; 0 = 4 * DETPU_TELEMETRY_TOPK")
declare("DETPU_TELEMETRY_INTERVAL", default="100",
        doc="metrics-log cadence (steps) of tools/obs_report.py's demo "
            "run (clamped to sample short runs)")
declare("DETPU_TELEMETRY_SKETCH_DEPTH", default="4",
        doc="count-min sketch rows (independent hashes) per width slab")
declare("DETPU_TELEMETRY_SKETCH_WIDTH", default="2048",
        doc="count-min sketch buckets per row; estimate error ~ "
            "total_ids/buckets")
declare("DETPU_TELEMETRY_TOPK", default="32",
        doc="hot-row slots tracked per width slab per rank")
declare("DETPU_PROFILE_DIR", default=None,
        doc="directory for XLA profile captures (obs.profile_trace); "
            "unset = no capture")
declare("DETPU_PROFILE_PORT", default=None,
        doc="port for a live jax profiler server (obs.maybe_start_server); "
            "unset = no server")

# measured phase-time observatory (analysis/phase_profile.py +
# tools/phase_profile.py = make phase-profile)
declare("DETPU_PHASE_PROFILE_STEPS", default="5",
        doc="timed steps captured per case by the measured phase profile "
            "(each step gets its own jax.profiler.trace so per-phase "
            "numbers carry real p50/p95 spread)")
declare("DETPU_PHASE_PROFILE_DIR", default=None,
        doc="keep the phase-profile trace captures (TensorBoard-loadable) "
            "under this directory instead of a deleted temp dir")
declare("DETPU_PHASE_DRIFT_MAX", default="2.0",
        doc="calibration flag threshold: a phase whose measured/modeled "
            "cost ratio exceeds this factor (or falls below its inverse) "
            "relative to the step's cost-weighted median ratio is "
            "reported as model drift (analysis.phase_profile.calibrate)")

# streaming vocab: frequency-gated admission + approximate-LFU eviction
# (parallel/streaming.py; carried through train steps built by
# parallel/trainer.py with dynamic=)
declare("DETPU_ADMIT_MIN_COUNT", default="2",
        doc="count-min estimate an external id needs before it may claim "
            "a dynamic-table slot; below it the id is served from its "
            "shared hash bucket")
declare("DETPU_ADMIT_SKETCH_DEPTH", default="4",
        doc="admission count-min sketch rows (independent hashes) per "
            "streaming width slab")
declare("DETPU_ADMIT_SKETCH_WIDTH", default="4096",
        doc="admission count-min sketch buckets per row; estimate error "
            "~ total_ids/buckets")
declare("DETPU_EVICT_MARGIN", default="1",
        doc="approximate-LFU eviction margin: a claim on an occupied "
            "slot succeeds only when the incoming estimate >= occupant "
            "frequency + margin (0 = ties evict)")

# pipelined hybrid step (parallel/schedule.py + parallel/trainer.py):
# K-microbatch software pipelining that hides the all-to-all exchange
# under dense compute (ROADMAP item 2)
declare("DETPU_MICROBATCH", default="2",
        doc="microbatch count K of steps built with a pipelined schedule "
            "(parallel.schedule.pipelined_schedule(K=None) resolves K "
            "here — only schedule='pipelined' opt-ins read it; the "
            "default schedule stays serialized regardless). The global "
            "batch splits into K chains inside ONE jitted step — "
            "microbatch k+1's id all-to-all is data-independent of "
            "microbatch k's dense fwd/bwd, so XLA can overlap them — "
            "with gradients accumulated so the applied update matches "
            "the serialized step (K=1 IS the serialized baseline, "
            "bitwise — the opt-in default is 2 so asking for a pipeline "
            "actually builds one). The per-device batch must divide by K")

# deadline-bounded serving runtime (parallel/serving.py +
# tools/check_serving.py = make check-serving)
declare("DETPU_SERVE_BURST_X", default="8",
        doc="arrival-rate multiplier of the burst@<pos> QPS-spike drill "
            "(the serving load generator applies it during each burst "
            "second; the admission controller must absorb the spike)")
declare("DETPU_SERVE_DEADLINE_MS", default="100",
        doc="default per-request deadline (ms, from submit): the "
            "scheduler flushes early to make it, drops requests already "
            "past it (typed Expired, counted deadline_missed) instead "
            "of wasting a rung on answers nobody is waiting for; "
            "requests may pin their own deadline_ms")
declare("DETPU_SERVE_MAX_BATCH", default="256",
        doc="largest padded-batch rung (global samples per flush) of "
            "the serving coalescer's compiled-executable ladder")
declare("DETPU_SERVE_MAX_QUEUE", default="1024",
        doc="hard admission bound (queued samples): a submit that would "
            "exceed it is shed with a typed Overloaded response — queue "
            "growth is bounded by construction, whatever the QPS")
declare("DETPU_SERVE_MAX_WAIT_MS", default="5",
        doc="batching delay: a queued request is flushed no later than "
            "this many ms after submit even when the batch is not full "
            "(the degradation ladder shrinks it to 0 under pressure)")
declare("DETPU_SERVE_RUNGS", default="",
        doc="comma-separated explicit padded-batch ladder (global "
            "samples, ascending, each divisible by the world size) "
            "overriding the power-of-two default; one compiled "
            "executable per rung, warmed up front so steady-state "
            "serving never recompiles")
declare("DETPU_SERVE_SHED_FRAC", default="0.5",
        doc="queue fraction of DETPU_SERVE_MAX_QUEUE at which the "
            "admission controller enters its shed level: new lowest-"
            "priority (<= 0) requests are refused with a typed "
            "Overloaded response while higher-priority traffic keeps "
            "being served")
declare("DETPU_SERVE_SLO_MS", default="2000",
        doc="p99 latency bound (ms) the make check-serving overload "
            "drill enforces on served requests — generous on the CPU "
            "proxy (flushes are injected 20+ ms slow there); tighten "
            "per deployment for a real SLO")

# online learning runtime: concurrent train-and-serve with RCU snapshot
# publication and a freshness SLO (parallel/online.py +
# tools/check_online.py = make check-online)
declare("DETPU_FRESHNESS_MAX_S", default="0",
        doc="wall-clock half of the freshness SLO (seconds): when the "
            "installed serving snapshot's age exceeds it the runtime "
            "enters its freshness shed rung, like the step half below. "
            "0 = disabled (step SLO only)")
declare("DETPU_FRESHNESS_MAX_STEPS", default="8",
        doc="staleness SLO in train steps: when snapshot publication "
            "falls more than this many completed steps behind training, "
            "serving enters its shed rung (new priority<=0 requests are "
            "refused with a typed Overloaded reason='stale_snapshot', a "
            "snapshot_lagging event fires) — load is shed serve-side "
            "before training is ever blocked on publication; the next "
            "publication recovers. <=0 disables the step SLO")
declare("DETPU_ONLINE_PUBLISH_STEPS", default="1",
        doc="publication cadence (train steps) of the online runtime's "
            "RCU snapshot publisher: every N completed steps the "
            "training tables are copied into fresh buffers and installed "
            "atomically as one monotonically-versioned serving view "
            "(rollback-and-replay republishes immediately, whatever the "
            "cadence)")

# process-isolated serving: shared-memory snapshot transport + the
# serving-worker supervisor (utils/shm.py + parallel/supervisor.py +
# tools/check_isolation.py = make check-isolation)
declare("DETPU_SHM_READ_RETRIES", default="8",
        doc="seqlock read attempts per SnapshotShm.read_latest() call: a "
            "reader that keeps catching the writer mid-publish (sequence "
            "stamps disagree or the CRC32 fails) retries this many times, "
            "then returns None and keeps serving its previous snapshot — "
            "a torn cross-process read is impossible by construction, "
            "only a missed refresh")
declare("DETPU_SHM_SLACK", default="1.25",
        doc="sizing multiplier for the shared-memory snapshot region: "
            "each of the two seqlock buffers holds slack * the template "
            "payload's serialized bytes (pickle framing varies a little "
            "run to run; shapes/dtypes never do). A later payload that "
            "exceeds the buffer raises — the region is sized once, "
            "before the worker attaches")
declare("DETPU_SUPERVISE_BACKOFF_BASE_S", default="0.1",
        doc="base delay of the supervisor's jittered exponential backoff "
            "between serving-worker restart attempts (the runtime.retry "
            "idiom: doubles per attempt, jittered in [0.5x, 1.5x))")
declare("DETPU_SUPERVISE_BACKOFF_MAX_S", default="2",
        doc="cap on the supervisor's restart backoff delay (seconds)")
declare("DETPU_SUPERVISE_DEADLINE_S", default="5",
        doc="heartbeat deadline: a serving worker whose last pong is "
            "older than this is declared HUNG, killed (SIGKILL — hang "
            "detection never depends on the child cooperating) and "
            "restarted under the restart budget")
declare("DETPU_SUPERVISE_HEARTBEAT_S", default="0.25",
        doc="interval between supervisor heartbeat pings to the serving "
            "worker; pongs carry the worker's live stats subset")
declare("DETPU_SUPERVISE_MAX_RESTARTS", default="3",
        doc="restart budget per Supervisor lifetime: after this many "
            "worker deaths the supervisor stays down (every request "
            "answers typed Unavailable) instead of crash-looping — "
            "training is never taken down with it")
declare("DETPU_SUPERVISE_START_TIMEOUT_S", default="300",
        doc="deadline for a (re)started serving worker to finish its "
            "warmup and report ready; a worker that blows it is treated "
            "as crashed (kill + backoff + next attempt)")

# cross-process request tracing: per-request causal spans with
# tail-based sampling and a bounded retained ring (utils/reqtrace.py +
# tools/check_tracing.py = make check-tracing)
declare("DETPU_TRACE", default="1",
        doc="request tracing master switch: when enabled every "
            "ServingRuntime/Supervisor submit mints a trace whose stage "
            "spans partition the request's life (sum == latency_ms); "
            "the per-request cost is a dict and a hash. Empty/0 "
            "disables minting entirely")
declare("DETPU_TRACE_RING", default="256",
        doc="capacity of the retained-trace ring per TraceBuffer: "
            "tail-sampled traces beyond this evict oldest-first, so "
            "trace memory is bounded no matter the burst (the 10x-burst "
            "property tests/test_reqtrace.py pins)")
declare("DETPU_TRACE_SAMPLE", default="0.02",
        doc="retention probability for HEALTHY served traces that miss "
            "the latency top decile; applied as a deterministic hash of "
            "(DETPU_TRACE_SEED, trace_id), never a random draw. "
            "Unhealthy outcomes (expired/failed/overloaded/unavailable) "
            "and top-decile latencies are always retained — that is the "
            "tail-based half of the policy")
declare("DETPU_TRACE_SEED", default="0",
        doc="seed of the deterministic sampling hash (and of minted "
            "trace ids): pin it and the same request stream replays the "
            "same retention decisions run-to-run, which is what makes "
            "sampled traces reproducible in drills and tests")

# concurrency auditor: lock-discipline analysis + interleaving model
# checker over the serving plane (analysis/concurrency_audit.py +
# tools/concurrency_audit.py = make concurrency-audit)
declare("DETPU_CONCURRENCY_DEPTH", default="8",
        doc="virtual-clock tick bound of the supervisor heartbeat model "
            "explored by make concurrency-audit: larger values widen "
            "the interleaving space (more crash/restart phases per "
            "proof) at exponential state cost; 8 covers two full "
            "fault -> detect -> restart -> re-ingest cycles")
declare("DETPU_CONCURRENCY_WORDS", default="2",
        doc="payload words in the seqlock interleaving model: each word "
            "is an independently-timed copy step, so more words = more "
            "distinct torn prefixes the explorer must prove detected; "
            "2 already exhibits every mix class (old/new, new/old)")

# non-finite guard (utils/obs.py + parallel/trainer.py + resilient.py)
declare("DETPU_NANGUARD", default="1",
        doc="on-device non-finite guard in the hybrid step; 0 = build the "
            "unguarded step")
declare("DETPU_NANGUARD_K", default="3",
        doc="consecutive guard-skipped steps before the resilient driver "
            "enters rollback-and-replay recovery (and, once the rollback "
            "budget is exhausted, escalates NonFiniteLossError)")

# rollback-and-replay recovery (parallel/resilient.py + utils/checkpoint.py)
declare("DETPU_CKPT_RING", default="2",
        doc="ring size of last-good checkpoints kept BEYOND <dir> and "
            "<dir>.prev (utils.checkpoint.save_train_state keep_last_n): "
            "each save archives the displaced .prev under <dir>.ring/ and "
            "prunes to this many entries; the rollback-and-replay recovery "
            "restores the newest healthy entry predating the poisoned "
            "window. 0 = no ring (the pre-ring layout)")
declare("DETPU_ROLLBACK_MAX", default="2",
        doc="rollback-and-replay attempts per resilient run before the "
            "NaN escalation turns terminal (NonFiniteLossError with the "
            "quarantine ledger attached); persisted in the ledger so the "
            "budget survives preemption/resume")
declare("DETPU_QUARANTINE_MAX", default="8",
        doc="max batches the recovery may quarantine (total, across "
            "rollbacks) before declaring the stream poisoned and raising "
            "terminally — a transient bad window is quarantinable, a "
            "fully-poisoned stream is not")

# per-table numerical health sentinels (parallel/trainer.py + utils/obs.py)
declare("DETPU_HEALTH_GRAD_NORM", default="0",
        doc="per-table sparse-gradient L2-norm threshold for the health "
            "contract (obs.TableHealthContract): a table whose "
            "table_grad_norm exceeds it is named unhealthy in recovery "
            "logs/events. <= 0 = disabled (non-finite counts are always "
            "checked)")
declare("DETPU_HEALTH_UPDATE_MAXABS", default="0",
        doc="per-table row-update max-abs threshold for the health "
            "contract; <= 0 = disabled")

# fault injection + runtime probes (utils/runtime.py)
declare("DETPU_FAULT", default="",
        doc="comma-separated fault injections: hang|slow|raise|die:<point>, "
            "preempt@<step> (driver self-SIGTERM drill), corrupt@ckpt "
            "(flip bytes in each just-committed checkpoint shard so the "
            "CRC manifest + .prev fallback are exercisable end to end), "
            "nan@<step> (poison one rank's loss at that batch — the NaN-"
            "storm drill the rollback-and-replay recovery quarantines), or "
            "badbatch@<step> (corrupt that input batch's categorical ids — "
            "exercises the invalid-input policies end to end), or "
            "oovflood@<pos> (replace that batch's categorical ids with a "
            "burst of never-before-seen ids — the non-stationary-traffic "
            "drill the streaming-vocab admission/bucket machinery must "
            "absorb without recompiles or crashes), or burst@<pos> (QPS "
            "spike: the serving load generator multiplies the arrival "
            "rate by DETPU_SERVE_BURST_X during that second of the "
            "stream — the overload drill the serving runtime's "
            "degradation ladder must absorb with clean typed shedding, "
            "bounded p99, and post-burst recovery). Specs comma-combine: "
            "oovflood@P,burst@P is the joint online-learning chaos drill "
            "(a traffic spike of never-seen ids while serving, make "
            "check-online); in the online runtime burst@ positions are "
            "train-step ordinals (requests-per-step multiply by "
            "DETPU_SERVE_BURST_X at those steps). die@<pos> / hang@<pos> "
            "target a SUPERVISED serving worker (parallel/supervisor.py): "
            "at that arrival ordinal the worker hard-exits (die@, the "
            "SIGKILL/OOM equivalent) or stops answering (hang@, the "
            "wedged-process equivalent) — the supervisor must detect "
            "either, answer in-flight requests typed Unavailable, dump "
            "the black box on the child's behalf, and restart within its "
            "budget (make check-isolation)")
declare("DETPU_ON_MISMATCH", default="reshard",
        doc="resilient-driver restore policy when a checkpoint's recorded "
            "sharding plan/world size differs from the model's: 'reshard' "
            "= re-slice the logical tables under the current plan and "
            "continue (elastic resume; degradation logged), 'error' = "
            "raise CheckpointMismatch (the strict pre-elastic behavior)")
declare("DETPU_PROBE_TIMEOUT_S", default="120",
        doc="time box (seconds) for the subprocess backend probe")
declare("DETPU_DRYRUN_TIMEOUT_S", default="600",
        doc="time box (seconds) for the __graft_entry__ dryrun child")
declare("_DETPU_DRYRUN_CHILD", default=None,
        doc="internal: set in the dryrun child's environment so it knows "
            "to touch the backend directly")

# sparse optimizer paths (parallel/optimizers.py, parallel/sparse_optax.py)
declare("DETPU_SGD_DEDUP", default="",
        doc="1 = force the sort/segment-sum dedup pass back INTO the "
            "SGD sparse paths that statically skip it (SparseSGD declares "
            "needs_dedup=False; sparse_value_and_grad(dedup=False)) — the "
            "A/B escape hatch for the ROADMAP 3(a) pass cut. Read at step "
            "BUILD time; trajectories are mathematically identical either "
            "way (SGD is linear in the gradient)")

# debug / test harness
declare("DETPU_DEBUG_LANE_EXTRACT", default="0",
        doc="1 = swap the packed-slab lane extraction for the reference "
            "gather (ops/packed_slab.py divergence debugging)")
declare("DETPU_FORCE_CPU_DEVICES", default=None,
        doc="N = examples force JAX_PLATFORMS=cpu with N virtual host "
            "devices (test harness for the example mains)")
