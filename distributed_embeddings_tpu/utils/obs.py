"""Step-level observability: named-scope tracing, on-device step metrics,
process counters, and a crash-surviving metrics sidecar.

PR 1's runtime layer (:mod:`.runtime`) made failures *survivable* — a
killed process leaves parseable records. This module
makes runs *explainable*: when throughput drops, or ragged ids silently
overflow their static capacity, there is something to look at. Every later
perf PR is measured against the instrumentation here.

Three layers, all off by default and <1% overhead when disabled:

* **Named-scope tracing** — :func:`scope` wraps the hybrid step's phases
  (id all-to-all, per-width lookups, ragged decode, output exchange,
  sparse apply) in ``jax.named_scope`` so a captured XLA profile
  attributes device time to phases instead of one opaque jit blob.
  Scopes are trace-time-only metadata: they cost nothing at run time and
  are therefore always on. :func:`profile_trace` (gated by
  ``DETPU_PROFILE_DIR``) and :func:`maybe_start_server` (gated by
  ``DETPU_PROFILE_PORT``) capture the profiles the scopes annotate.
  :func:`span` is the host twin: a ``jax.profiler.TraceAnnotation`` on
  the calling thread's line of the same capture, on the device ops'
  clock (the serving runtime's flush is opened with it); inert while no
  capture runs.
* **On-device step metrics** — a plain-dict pytree (keys
  :data:`STEP_METRIC_KEYS`) computed *inside* the jitted step by
  ``DistributedEmbedding.step_metrics`` + ``trainer.make_hybrid_train_step
  (with_metrics=True)``: ids routed per rank, exchange bytes per
  direction, ragged capacity-overflow counts, output-exchange padding
  fraction, dense/embedding grad norms. A handful of sums over tensors the
  step already holds — near-zero cost, and only built when
  ``DETPU_OBS=1`` (or ``with_metrics=True`` is passed explicitly).
* **Host-side collection** — :class:`MetricsLogger` drains step-metric
  pytrees into an fsynced JSONL sidecar (same crash-surviving mechanics as
  :class:`.runtime.SectionRecorder`, which it rides), and module-level
  :func:`counter_inc`/:func:`counters` track process events: recompiles
  (:func:`install_compile_listener`, a ``jax.monitoring`` backend-compile
  listener), runtime retries, fault injections, bootstrap retries.

Like :mod:`.runtime`, this module never imports jax at module scope:
importing it must never risk touching an accelerator backend, and the
counter/logger half works in processes that never load jax at all.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import os
import re
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from . import envvars
from . import runtime as _runtime

logger = logging.getLogger(__name__)

# names stay importable as module constants; the knobs themselves are
# declared (default + meaning) in utils/envvars.py, the single registry
# the env-registry lint rule enforces
OBS_ENV = "DETPU_OBS"
PROFILE_DIR_ENV = "DETPU_PROFILE_DIR"
PROFILE_PORT_ENV = "DETPU_PROFILE_PORT"
NANGUARD_ENV = "DETPU_NANGUARD"
NANGUARD_K_ENV = "DETPU_NANGUARD_K"

#: Keys of the on-device step-metrics dict (a plain dict so it is a pytree
#: without any registration, and JSON-serializable after a host fetch).
#: Every value is a per-device ``[1]``-shaped array — except the three
#: per-table health sentinels (``table_*``), which are ``[1, n_tables]``.
#: Under ``shard_map`` with ``out_specs=P(axis)`` the per-device rows
#: concatenate into a ``[world]`` per-rank vector (rank ``r``'s entry
#: describes rank ``r``); the sentinels become ``[world, n_tables]``.
STEP_METRIC_KEYS = (
    "ids_routed",        # live (non-padding) ids this rank received
    "id_overflow",       # ragged ids lost to static-capacity truncation
    "invalid_id_count",  # negative / out-of-vocab ids among the live ids
    "id_a2a_bytes",      # id-exchange bytes leaving this chip (dp->mp)
    "out_a2a_bytes",     # activation-exchange bytes leaving (mp->dp fwd)
    "grad_a2a_bytes",    # cotangent-exchange bytes leaving (dp->mp bwd)
    "out_pad_frac",      # dead-column fraction of this rank's output rows
    "loss",              # per-device loss (post-pmean: identical rows)
    "emb_grad_norm",     # L2 norm of this device's embedding cotangents
    "dense_grad_norm",   # L2 norm of the (averaged) dense gradient
    "skipped_steps",     # 1 when the non-finite guard skipped this step
    "step",              # step counter at the START of the step
    # -- per-table numerical health sentinels ([1, n_tables] per device):
    # computed from this device's per-table embedding cotangents inside
    # the jitted step, so a recovery log can name WHICH table went
    # unhealthy, not just the step (see TableHealthContract)
    "table_grad_norm",      # per-table L2 norm of the sparse cotangents
    "table_update_maxabs",  # per-table max |row update| (lr/world scaled)
    "table_nonfinite",      # per-table count of non-finite cotangents
)

#: The per-table health-sentinel subset of :data:`STEP_METRIC_KEYS`.
TABLE_HEALTH_KEYS = ("table_grad_norm", "table_update_maxabs",
                     "table_nonfinite")

#: Extra step-metric keys of streaming-vocab (dynamic-table) steps —
#: present only when the step was built with ``dynamic=`` on
#: (``parallel/streaming.py``). Per-device ``[1]`` counts of THIS step's
#: slot-map transitions, gated by the non-finite guard like the updates
#: they describe (a skipped step reports zeros).
STREAMING_METRIC_KEYS = (
    "stream_admitted",    # external ids admitted to a real slot
    "stream_evicted",     # slot occupants evicted back to their bucket
    "stream_bucket_ids",  # live ids served from a shared hash bucket
    "stream_hit_ids",     # live ids served from their admitted slot
)


def metrics_enabled() -> bool:
    """Whether ``DETPU_OBS`` asks for step metrics (read per call so tests
    can flip it at runtime; an env read is nanoseconds against a train
    step)."""
    return envvars.enabled(OBS_ENV)


def nanguard_enabled() -> bool:
    """Whether the on-device non-finite guard is on. Default ON
    (``DETPU_NANGUARD`` unset or truthy): a NaN/Inf batch must never
    corrupt the sharded tables silently. Set ``DETPU_NANGUARD=0`` to build
    the unguarded step. Read at step-build time (trace-time static), like
    ``with_metrics``."""
    return envvars.enabled(NANGUARD_ENV)


def nanguard_escalation_k(default: int = 3) -> int:
    """Consecutive guard-skipped steps before the host driver escalates
    with :class:`~.runtime.NonFiniteLossError` (``DETPU_NANGUARD_K``)."""
    return envvars.get_int(NANGUARD_K_ENV, default)


# ------------------------------------------------------------- named scopes

#: Prefix every :func:`scope` stamps on its ``jax.named_scope`` — the one
#: identifier that threads a phase through the jaxpr auditor, the HLO
#: census, the schedule-graph auditor, and the measured trace parser.
SCOPE_PREFIX = "detpu"

#: The phase-name extractor every consumer of ``metadata.op_name`` shares
#: (``analysis/hlo_census.py`` compiled-HLO attribution, the schedule
#: auditor's DAG nodes, ``utils/traceparse.py``'s profiler events): each
#: match is one ``detpu/<component>`` scope level. Lives HERE — next to
#: :func:`scope`, which mints the names, and derived from the same
#: :data:`SCOPE_PREFIX` — so the writer and every reader agree by
#: construction.
SCOPE_RE = re.compile(re.escape(SCOPE_PREFIX) + r"/([\w.\-]+)")


def phase_path(op_name: Optional[str]) -> str:
    """Full ``detpu`` scope path embedded in an XLA ``op_name`` (or a
    profiler event's metadata), e.g.
    ``"jit(step)/.../detpu/embedding_forward/detpu/id_all_to_all/..."``
    -> ``"embedding_forward/id_all_to_all"``. Empty string when the name
    carries no detpu scope."""
    return "/".join(SCOPE_RE.findall(op_name or ""))


def phase_leaf(path: str) -> str:
    """Last component of a phase path (census convention: contracts match
    the full path OR the leaf)."""
    return path.rsplit("/", 1)[-1] if path else ""


#: Event-name namespace for per-REQUEST trace events (utils/reqtrace.py
#: emits them, utils/traceparse.py reads them back). Lives here, next to
#: :data:`SCOPE_RE`, because obs.py owns the naming conventions that keep
#: a mixed capture directory separable: ``detpu/...`` scopes mark device
#: op events, ``req/...`` names mark request spans — phase tooling skips
#: the latter, request-trace tooling keys on them.
REQ_EVENT_PREFIX = "req/"


def is_request_event(name: Optional[str]) -> bool:
    """Whether a trace-event name belongs to the request-tracing
    namespace (vs a device/profiler op event)."""
    return bool(name) and str(name).startswith(REQ_EVENT_PREFIX)


def scope(name: str):
    """``jax.named_scope("detpu/<name>")`` — phase attribution for XLA
    profiles. Trace-time-only metadata (zero run-time cost), so call sites
    use it unconditionally."""
    import jax

    return jax.named_scope(f"{SCOPE_PREFIX}/{name}")


def span(name: str, **args: Any):
    """``jax.profiler.TraceAnnotation("detpu/<name>", **args)`` — the host
    twin of :func:`scope`. While a profiler session runs
    (:func:`profile_trace`, the profiler server, a benchmark's traced
    run) the span lands on the calling thread's line of the same trace
    as the device ops, on their clock, with ``args`` as its metadata;
    nesting on a thread gives each span its parent. While none runs it
    records nothing (half a microsecond), so call sites use it
    unconditionally. A span times the HOST's stay in the block: around
    an asynchronous call (a transfer, a dispatch) that is the call, not
    the device work it starts."""
    import jax

    return jax.profiler.TraceAnnotation(f"{SCOPE_PREFIX}/{name}", **args)


def is_span_event(name: Optional[str]) -> bool:
    """Whether a trace-event NAME is a host :func:`span`. A device op
    carries its ``detpu/...`` scopes inside its metadata (``tf_op`` /
    ``op_name``), never at the start of its name, so trace readers skip
    these before attributing device time."""
    return bool(name) and str(name).startswith(SCOPE_PREFIX + "/")


@contextlib.contextmanager
def profile_trace(label: Optional[str] = None) -> Iterator[None]:
    """Capture an XLA profile of the enclosed block into
    ``$DETPU_PROFILE_DIR`` (a TensorBoard-loadable trace directory); a
    transparent no-op when the variable is unset.

    ``label`` names a subdirectory so successive captures (e.g. one per
    phase) do not overwrite each other.
    """
    base = envvars.get(PROFILE_DIR_ENV)
    if not base:
        yield
        return
    import jax

    path = os.path.join(base, label) if label else base
    os.makedirs(path, exist_ok=True)
    with jax.profiler.trace(path):
        yield


_server_started = False
_server_lock = threading.Lock()


def maybe_start_server() -> bool:
    """Start ``jax.profiler.start_server($DETPU_PROFILE_PORT)`` once per
    process (for live TensorBoard capture); no-op without the variable.
    Returns whether a server is running after the call."""
    global _server_started
    port = envvars.get(PROFILE_PORT_ENV)
    if not port:
        return _server_started
    with _server_lock:
        if not _server_started:
            import jax

            jax.profiler.start_server(int(port))
            _server_started = True
            logger.info("obs: profiler server listening on port %s", port)
    return _server_started


# -------------------------------------------------------- process counters

_counters: Dict[str, int] = {}
_counters_lock = threading.Lock()


def counter_inc(name: str, n: int = 1) -> int:
    """Bump a process-level counter (``recompiles``, ``runtime_retries``,
    ``fault_injections``, ``bootstrap_retries``, ...); returns the new
    value. Thread-safe; always on (a dict bump is free)."""
    with _counters_lock:
        v = _counters.get(name, 0) + n
        _counters[name] = v
    return v


def counters() -> Dict[str, int]:
    """Snapshot of every process counter."""
    with _counters_lock:
        return dict(_counters)


def reset_counters() -> None:
    """Forget counter state (test isolation helper)."""
    with _counters_lock:
        _counters.clear()


# ---------------------------------------------------------- process events

# Structured one-shot events (e.g. a checkpoint re-shard on elastic
# resume): producers deep in library code record them here; the driver
# layer drains and routes them to its MetricsLogger / log output. Unlike
# counters these carry a payload; like counters they are process-global
# so a utils-level producer needs no logger plumbed through.
_events: List[Dict[str, Any]] = []

# observability-plane taps: callbacks that see every record_event() as it
# happens, WITHOUT consuming it (drain_events stays the at-most-once
# delivery path for drivers). The flight recorder (utils/mplane.py) rides
# here so its black-box ring holds recent events with nobody polling.
_event_taps: List[Any] = []


def add_event_tap(fn) -> None:
    """Register ``fn(kind, payload_dict)`` to observe every recorded
    event (idempotent per function object). Taps must not raise; a
    failing tap is dropped from the chain rather than poisoning every
    later producer."""
    with _counters_lock:
        if fn not in _event_taps:
            _event_taps.append(fn)


def record_event(kind: str, **payload: Any) -> Dict[str, Any]:
    """Record one structured event (also bumps the ``event_<kind>``
    counter); returns the stored record."""
    rec = {"event": kind, "time": time.time(), **payload}
    with _counters_lock:
        _events.append(rec)
        taps = list(_event_taps)
    counter_inc(f"event_{kind}")
    for fn in taps:
        try:
            fn(kind, dict(payload))
        except Exception:  # noqa: BLE001 - a broken tap must not poison
            # every event producer in the process
            logger.exception("obs: event tap failed; removing it")
            with _counters_lock:
                if fn in _event_taps:
                    _event_taps.remove(fn)
    return rec


def drain_events(kind: Optional[str] = None) -> List[Dict[str, Any]]:
    """Pop (and return) recorded events — all of them, or only ``kind``.
    Draining is the consumer's acknowledgment; events are delivered at
    most once."""
    with _counters_lock:
        if kind is None:
            out, _events[:] = list(_events), []
            return out
        out = [e for e in _events if e["event"] == kind]
        _events[:] = [e for e in _events if e["event"] != kind]
        return out


_compile_listener_installed = False
# guards the install check-then-act: two threads warming two serving
# runtimes (the online drill's trainer + server) could otherwise both
# pass the installed check and double-register the listener — every
# recompile would then count twice and the 0-steady-state-recompiles
# gates would flag phantom retraces
_compile_lock = threading.Lock()

# fires once per jitted-signature miss, around compile-or-load: an
# in-process jit cache hit does not fire it, a persistent-cache load does
# (the program was still traced, lowered and handed to the backend)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# fires when that miss was answered from the persistent compilation cache
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def install_compile_listener() -> bool:
    """Count program builds into the ``recompiles`` counter.

    Registers a ``jax.monitoring`` duration listener for the
    backend-compile event, which fires exactly once per jit cache miss —
    whether XLA then compiles the program or loads it from the persistent
    compilation cache (``persistent_cache_hits`` counts the latter, so
    ``recompiles - persistent_cache_hits`` is the number of real XLA
    compiles). A zero-recompile window therefore means no retrace at all,
    with or without a warm persistent cache. Idempotent; returns True.
    """
    global _compile_listener_installed
    with _compile_lock:
        if _compile_listener_installed:
            return True
        import jax.monitoring

        def _on_duration(event: str, duration: float,
                         **kwargs: Any) -> None:
            del duration, kwargs
            if event == _COMPILE_EVENT:
                counter_inc("recompiles")

        def _on_event(event: str, **kwargs: Any) -> None:
            del kwargs
            if event == _CACHE_HIT_EVENT:
                counter_inc("persistent_cache_hits")

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _compile_listener_installed = True
        return True


# --------------------------------------------------------- host collection


class MetricsLogger:
    """Fsynced JSONL sidecar of step metrics and counters.

    Rides :class:`.runtime.SectionRecorder` (append one JSON line, flush,
    fsync), so a process killed at any point leaves every previously
    logged record parseable — what a run that dies at its time limit
    (rc=124) leaves behind. Records:

    * ``{"section": "step_metrics", "step": N, "metrics": {...}, ...}``
      from :meth:`log_step` — device arrays are fetched and listified
      (``[world]``-shaped per-rank vectors stay vectors);
    * ``{"section": "counters", "counters": {...}}`` from
      :meth:`log_counters` — the process counters, recompiles included.

    ``max_bytes`` (default ``DETPU_OBS_MAX_BYTES``; 0 = unbounded)
    bounds the sidecar for long resilient runs: when the file would
    exceed the cap, it rotates through ``<path>.1`` .. ``<path>.N``
    (``max_files`` generations, default ``DETPU_OBS_MAX_FILES`` = 2 —
    the checkpoint-ring idiom: ``.1`` is the newest rotated generation,
    ``.N`` the oldest, and the one past ``.N`` is dropped) and logging
    continues into a fresh file. Total disk is therefore bounded by
    ``(max_files + 1) * max_bytes`` however long the run lives.
    Rotation happens between records, so every generation stays
    line-parseable.
    """

    def __init__(self, path: str, max_bytes: Optional[int] = None,
                 max_files: Optional[int] = None):
        self.path = path
        self.max_bytes = (envvars.get_int("DETPU_OBS_MAX_BYTES")
                          if max_bytes is None else int(max_bytes))
        self.max_files = max(1, envvars.get_int("DETPU_OBS_MAX_FILES")
                             if max_files is None else int(max_files))
        self._rec = _runtime.SectionRecorder(path)

    def _maybe_rotate(self) -> None:
        if self.max_bytes <= 0:
            return
        try:
            size = os.path.getsize(self.path)
        except OSError:
            return
        if size < self.max_bytes:
            return
        # shift the ring up one generation, oldest out first (same
        # newest-first numbering as the checkpoint ring): .N drops,
        # .i -> .(i+1), live -> .1
        oldest = f"{self.path}.{self.max_files}"
        if os.path.exists(oldest):
            os.remove(oldest)
        for i in range(self.max_files - 1, 0, -1):
            src = f"{self.path}.{i}"
            if os.path.exists(src):
                os.replace(src, f"{self.path}.{i + 1}")
        os.replace(self.path, self.path + ".1")
        logger.info("obs: rotated metrics sidecar %s (> %d bytes; %d "
                    "generation(s) kept)", self.path, self.max_bytes,
                    self.max_files)

    def log_step(self, metrics: Dict[str, Any], step: Optional[int] = None,
                 **extra: Any) -> Dict[str, Any]:
        """Append one step-metrics record. ``metrics`` is the dict the
        instrumented train step returned (device arrays or numpy); fetching
        the values here is the ONE host readback the caller opted into by
        logging."""
        host = {}
        for k, v in metrics.items():
            host[k] = v.tolist() if hasattr(v, "tolist") else v
        rec = dict(extra)
        if step is not None:
            rec["step"] = int(step)
        self._maybe_rotate()
        return self._rec.record("step_metrics", metrics=host, **rec)

    def log_counters(self, **extra: Any) -> Dict[str, Any]:
        """Append the current process-counter snapshot."""
        self._maybe_rotate()
        return self._rec.record("counters", counters=counters(), **extra)

    def log_event(self, event: str, **fields: Any) -> Dict[str, Any]:
        """Append one structured one-shot record (e.g. a
        ``checkpoint_reshard`` degradation on elastic resume) under its
        own section name."""
        self._maybe_rotate()
        return self._rec.record(event, **fields)

    @staticmethod
    def load(path: str):
        """Parse a metrics sidecar (torn trailing line tolerated)."""
        return _runtime.SectionRecorder.load(path)


def fetch_metrics(metrics: Dict[str, Any]) -> Dict[str, Any]:
    """Host numpy copy of a step-metrics dict, multi-host safe.

    Under ``shard_map`` with ``out_specs=P(axis)`` on a pod, each
    ``[world]`` metrics vector spans devices of EVERY process — a bare
    ``tolist()`` on one process raises (non-addressable shards). This
    gathers such arrays with ``process_allgather``, which is a
    COLLECTIVE: on a multi-process job every process must call
    :func:`fetch_metrics` (even the ones that then drop the result), and
    only the chief hands it to :class:`MetricsLogger`. Single-process:
    a plain device fetch.
    """
    import numpy as np

    out: Dict[str, Any] = {}
    for k, v in metrics.items():
        if getattr(v, "is_fully_addressable", True):
            out[k] = np.asarray(v) if hasattr(v, "tolist") else v
        else:
            from jax.experimental import multihost_utils

            out[k] = np.asarray(
                multihost_utils.process_allgather(v, tiled=True))
    return out


def summarize(metrics: Dict[str, Any]) -> Dict[str, Any]:
    """Host-side scalar summary of one step-metrics dict: per-rank vectors
    reduce to totals (sums for counts/bytes, max for overflow — the rank
    that truncated is the one to look at), norms/fractions to their max.
    Per-rank vectors with more than one entry additionally report their
    p50/p95 (``<key>_p50`` / ``<key>_p95``) — the distribution view the
    imbalance analyses in ``tools/obs_report.py`` read."""
    import numpy as np

    out: Dict[str, Any] = {}
    for k in STEP_METRIC_KEYS + STREAMING_METRIC_KEYS:
        if k not in metrics:
            continue
        v = np.asarray(metrics[k]).reshape(-1)
        if v.size == 0:
            continue
        if k in ("ids_routed", "invalid_id_count", "id_a2a_bytes",
                 "out_a2a_bytes", "grad_a2a_bytes"
                 ) or k in STREAMING_METRIC_KEYS:
            out[k] = float(v.sum())
        elif k in ("id_overflow", "out_pad_frac", "emb_grad_norm",
                   "skipped_steps") or k in TABLE_HEALTH_KEYS:
            # table sentinels reduce to their worst (max) entry here;
            # the per-table view stays available via
            # TableHealthContract.violations_by_table / unhealthy_tables
            out[k] = float(v.max())
        else:
            out[k] = float(v[0])
        if v.size > 1:
            out[f"{k}_p50"] = float(np.percentile(v, 50))
            out[f"{k}_p95"] = float(np.percentile(v, 95))
    return out


# ------------------------------------------- per-table health contracts


@dataclasses.dataclass(frozen=True)
class TableHealthContract:
    """Declarative per-table numerical-health thresholds, audited against
    the ``table_*`` step-metric sentinels the trainer computes inside the
    jitted step — the recovery analogue of the plan-audit
    ``PlanContract``: the contract is data, :meth:`check` returns
    violations naming the offending table, and the resilient driver logs
    them in every skip/rollback event so a NaN storm at step 400k names
    *which table* went unhealthy, not just the step.

    ``max_nonfinite`` is the hard contract (default 0: any non-finite
    cotangent entry is unhealthy). The two magnitude thresholds default
    from ``DETPU_HEALTH_GRAD_NORM`` / ``DETPU_HEALTH_UPDATE_MAXABS`` and
    are disabled at ``<= 0`` — magnitude is workload-dependent, finiteness
    is not."""

    max_grad_norm: float = 0.0       # per-table L2; <= 0 disables
    max_update_maxabs: float = 0.0   # per-table max |update|; <= 0 disables
    max_nonfinite: int = 0           # per-table non-finite entry budget

    def violations_by_table(self, metrics: Dict[str, Any]
                            ) -> Dict[int, List[str]]:
        """Structured contract check of one step-metrics dict (device
        arrays or numpy; each sentinel ``[..., n_tables]``, reduced over
        ranks here): ``{table_id: [violation message, ...]}``. Empty
        dict = every table healthy. Metrics dicts without the sentinels
        (pre-sentinel steps) report nothing. This is the machine-read
        form (recovery events, :func:`unhealthy_tables`);
        :meth:`check` renders it for logs."""
        import numpy as np

        out: Dict[int, List[str]] = {}

        def per_table(key):
            v = metrics.get(key)
            if v is None:
                return None
            arr = np.asarray(v)
            if arr.ndim == 0 or arr.size == 0:
                return None
            return arr.reshape(-1, arr.shape[-1])

        nf = per_table("table_nonfinite")
        if nf is not None:
            for t, n in enumerate(nf.sum(axis=0)):
                if n > self.max_nonfinite:
                    out.setdefault(t, []).append(
                        f"{int(n)} non-finite sparse-gradient "
                        f"entr{'y' if int(n) == 1 else 'ies'} (budget "
                        f"{self.max_nonfinite})")
        for key, cap, what in (
                ("table_grad_norm", self.max_grad_norm, "grad L2 norm"),
                ("table_update_maxabs", self.max_update_maxabs,
                 "row-update max-abs")):
            if cap is None or cap <= 0:
                continue
            v = per_table(key)
            if v is None:
                continue
            for t, x in enumerate(v.max(axis=0)):
                if not np.isfinite(x) or x > cap:
                    out.setdefault(t, []).append(
                        f"{what} {float(x):g} exceeds the {cap:g} "
                        "contract")
        return out

    def check(self, metrics: Dict[str, Any]) -> List[str]:
        """Human-readable violations (``"table <t>: <message>"``), table
        order. Empty list = every table healthy."""
        by_table = self.violations_by_table(metrics)
        return [f"table {t}: {msg}"
                for t in sorted(by_table) for msg in by_table[t]]


def default_health_contract() -> TableHealthContract:
    """The env-configured contract (``DETPU_HEALTH_GRAD_NORM`` /
    ``DETPU_HEALTH_UPDATE_MAXABS``; non-finite budget always 0)."""
    return TableHealthContract(
        max_grad_norm=envvars.get_float("DETPU_HEALTH_GRAD_NORM"),
        max_update_maxabs=envvars.get_float("DETPU_HEALTH_UPDATE_MAXABS"))


def unhealthy_tables(metrics: Dict[str, Any],
                     contract: Optional[TableHealthContract] = None
                     ) -> List[int]:
    """Sorted table ids the contract names unhealthy — the compact form
    recovery events carry (structured, not parsed from log strings)."""
    contract = contract or default_health_contract()
    return sorted(contract.violations_by_table(metrics))


def record_fault(point: str) -> None:
    """Counter hook for :func:`.runtime.fault_point` — one bump per fired
    injection, keyed globally and per point."""
    counter_inc("fault_injections")
    counter_inc(f"fault_injections.{point}")


def record_retry(describe: str) -> None:
    """Counter hook for :func:`.runtime.retry` — one bump per retried
    attempt (the success that needed no retry bumps nothing)."""
    counter_inc("runtime_retries")
    counter_inc(f"runtime_retries.{describe.replace(' ', '_')}")


def env_stamp() -> Dict[str, Any]:
    """Process/environment identity for stamping benchmark records:
    backend platform, device kind and count are NOT read here (the caller
    adds them from its own ``jax.devices()``); this returns what is
    knowable without touching a backend."""
    stamp: Dict[str, Any] = {
        "unix_time": time.time(),
        "obs_enabled": metrics_enabled(),
    }
    try:
        import jax

        stamp["jax_version"] = jax.__version__
    except Exception:  # noqa: BLE001 - stamp is best-effort
        stamp["jax_version"] = None
    return stamp


def _selftest_json_roundtrip(metrics: Dict[str, Any]) -> bool:
    """Whether a metrics dict survives a json round trip after host
    fetch — used by the verify gate to fail fast on an unserializable
    field sneaking into :data:`STEP_METRIC_KEYS` payloads."""
    try:
        json.dumps({k: (v.tolist() if hasattr(v, "tolist") else v)
                    for k, v in metrics.items()})
        return True
    except (TypeError, ValueError):
        return False
