"""Process-isolated serving: the supervised out-of-process worker.

ISSUE 18's tentpole piece 2. PR 16 proved train-and-serve correctness
with both halves in ONE process — which means a serving crash is a
training crash and serve latency rides the trainer's scheduler. This
module separates the revenue path (serving) from the state path
(training) with a real process boundary and a supervision loop over it:

* :class:`ServingWorker` — a child process (ALWAYS the ``spawn`` start
  method: forking after the jax backend initialises deadlocks in the
  runtime's internal threads) that builds its own model + compiled
  ladder from a picklable factory spec, attaches the
  :class:`~..utils.shm.SnapshotShm` region, runs its own
  :class:`~.serving.ServingRuntime` and mplane HTTP exporter, and
  answers requests over a local AF_UNIX socket.
* :class:`Supervisor` — the trainer-side handle. It mirrors the
  runtime's ``submit``/``poll``/``install_snapshot``/``stats`` surface,
  so the :class:`~.online.SnapshotPublisher` and
  :class:`~.serving.RealtimeDriver` work against it UNCHANGED; under
  the surface it heartbeats the worker on a deadline, detects crashes
  (dead pid, socket EOF) and hangs (missed pongs), kills and restarts
  with jittered exponential backoff under a restart budget, answers
  every request caught in an outage with a typed
  :class:`~.serving.Unavailable` (a rung BELOW ``stale_snapshot``:
  a stale server still answers, a dead one answers typed), and dumps
  the crash flight-recorder black box ON BEHALF of the SIGKILLed child
  — the child cannot dump its own.

The isolation contract, drilled by ``make check-isolation``: training
never blocks on the worker (snapshot publication is a seqlock write
into shared memory; socket sends ride a dedicated sender thread) and
never dies with it; the training trajectory is checkpoint-CRC-identical
to a serving-free run even across worker kills.

Fault injection: ``DETPU_FAULT=die@<pos>`` / ``hang@<pos>`` fire INSIDE
the worker at global request-stream ordinals (the supervisor's request
counter, monotone across restarts — each position fires at most once,
so a drill kill is followed by a clean recovery, not a crash loop).
``die@`` hard-exits with no cleanup (the SIGKILL/OOM-kill equivalent);
``hang@`` stops answering (the wedged-process equivalent) and must be
caught by the heartbeat deadline, never by worker cooperation.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
import logging
import multiprocessing
import os
import pickle
import queue
import random
import threading
import time
import traceback
from multiprocessing.connection import Client, Listener
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..utils import envvars, mplane, obs, reqtrace
from ..utils import runtime as runtime_mod
from ..utils import shm as shm_mod
from .serving import ServeResult, Served, Unavailable

logger = logging.getLogger(__name__)

HEARTBEAT_ENV = "DETPU_SUPERVISE_HEARTBEAT_S"
DEADLINE_ENV = "DETPU_SUPERVISE_DEADLINE_S"
MAX_RESTARTS_ENV = "DETPU_SUPERVISE_MAX_RESTARTS"
BACKOFF_BASE_ENV = "DETPU_SUPERVISE_BACKOFF_BASE_S"
BACKOFF_MAX_ENV = "DETPU_SUPERVISE_BACKOFF_MAX_S"
START_TIMEOUT_ENV = "DETPU_SUPERVISE_START_TIMEOUT_S"

# the spawn context, requested ONCE at import: fork after jax backend
# init deadlocks, and a supervisor lives in a process that has
# necessarily initialised jax (it trains)  # spawn-ok: module policy
_SPAWN = multiprocessing.get_context("spawn")

#: metrics-federation cadence: the worker attaches its registry's
#: ``to_dict`` document to at most one pong per this many seconds (the
#: document is a few KB of counters + sketch buckets — cheap, but not
#: per-heartbeat cheap), so the supervisor's merged ``/metrics`` view
#: lags the worker by at most this plus one heartbeat
_FED_EVERY_S = 0.5


# ------------------------------------------------- snapshot serialization


def snapshot_payload(state, streaming_state=None) -> bytes:
    """Serialize the SERVABLE view of a train state for the wire: the
    embedding + dense parameter leaves (as host numpy, in tree order)
    plus the streaming-table state. Optimizer slots never cross the
    boundary — eval does not read them, exactly the frozen-opt idiom of
    the in-process :class:`~.online.SnapshotPublisher`."""
    import jax

    params = jax.tree_util.tree_leaves(
        (state.emb_params, state.dense_params))
    stream = (jax.tree_util.tree_leaves(streaming_state)
              if streaming_state is not None else None)
    doc = {
        "step": int(jax.device_get(state.step)),  # host-ok: snapshot export
        "params": [np.asarray(jax.device_get(x))  # host-ok: snapshot export
                   for x in params],
        "stream": ([np.asarray(jax.device_get(x))  # host-ok: snapshot export
                    for x in stream]
                   if stream is not None else None),
    }
    return pickle.dumps(doc, protocol=pickle.HIGHEST_PROTOCOL)


def install_payload(payload: bytes, template_state,
                    template_streaming=None) -> Tuple[Any, Any, int]:
    """Rebuild a served state from :func:`snapshot_payload` bytes onto
    the WORKER's own templates: leaves are ``device_put`` with the
    template leaf's sharding so the compiled ladder's jit cache keys
    stay bitwise-in-spec — 0 steady-state recompiles per install, the
    same contract the in-process path pins."""
    import jax

    doc = pickle.loads(payload)
    tmpl = (template_state.emb_params, template_state.dense_params)
    leaves, treedef = jax.tree_util.tree_flatten(tmpl)
    if len(doc["params"]) != len(leaves):
        raise ValueError(
            f"snapshot has {len(doc['params'])} param leaves, worker "
            f"template has {len(leaves)} — trainer and worker must "
            f"build the SAME model at the SAME world size")

    from jax.sharding import NamedSharding

    def _put(arr, like):
        if arr.shape != like.shape or arr.dtype != like.dtype:
            raise ValueError(
                f"snapshot leaf {arr.shape}/{arr.dtype} does not match "
                f"worker template {like.shape}/{like.dtype}")
        sh = getattr(like, "sharding", None)
        if isinstance(sh, NamedSharding):
            # mesh-sharded template leaf: rebuild the global array with
            # the SAME sharding so the jit cache key matches the ladder
            return jax.device_put(arr, sh)
        # single-device leaf: stay host-side and UNCOMMITTED, exactly
        # like the template jit staged — a committed device_put here
        # changes the cache key and retraces (1 recompile per install)
        return arr

    put = [_put(a, l) for a, l in zip(doc["params"], leaves)]
    emb_params, dense_params = jax.tree_util.tree_unflatten(treedef, put)
    state = template_state._replace(
        emb_params=emb_params, dense_params=dense_params,
        step=np.asarray(doc["step"],
                        np.asarray(template_state.step).dtype))
    streaming_state = None
    if doc["stream"] is not None:
        if template_streaming is None:
            raise ValueError("snapshot carries streaming state but the "
                             "worker serves none")
        sleaves, sdef = jax.tree_util.tree_flatten(template_streaming)
        sput = [_put(a, l) for a, l in zip(doc["stream"], sleaves)]
        streaming_state = jax.tree_util.tree_unflatten(sdef, sput)
    return state, streaming_state, doc["step"]


# ------------------------------------------------------------- the config


@dataclasses.dataclass
class SuperviseConfig:
    """Supervision policy. ``None`` fields resolve from the registered
    ``DETPU_SUPERVISE_*`` knobs at construction."""

    heartbeat_s: Optional[float] = None
    deadline_s: Optional[float] = None
    max_restarts: Optional[int] = None
    backoff_base_s: Optional[float] = None
    backoff_max_s: Optional[float] = None
    start_timeout_s: Optional[float] = None
    # the supervisor-side crash black box (None disables)
    blackbox_path: Optional[str] = None
    # worker-side mplane scrape port (None -> worker env decides)
    metrics_port: Optional[int] = None
    # extra environment for the worker process (applied around spawn)
    env: Dict[str, str] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.heartbeat_s is None:
            self.heartbeat_s = envvars.get_float(HEARTBEAT_ENV)
        if self.deadline_s is None:
            self.deadline_s = envvars.get_float(DEADLINE_ENV)
        if self.max_restarts is None:
            self.max_restarts = envvars.get_int(MAX_RESTARTS_ENV)
        if self.backoff_base_s is None:
            self.backoff_base_s = envvars.get_float(BACKOFF_BASE_ENV)
        if self.backoff_max_s is None:
            self.backoff_max_s = envvars.get_float(BACKOFF_MAX_ENV)
        if self.start_timeout_s is None:
            self.start_timeout_s = envvars.get_float(START_TIMEOUT_ENV)
        if self.heartbeat_s <= 0 or self.deadline_s <= self.heartbeat_s:
            raise ValueError(
                f"need 0 < heartbeat_s ({self.heartbeat_s}) < deadline_s "
                f"({self.deadline_s}) — a deadline the heartbeat cannot "
                f"beat declares every worker hung")


# ------------------------------------------------------------- the worker


def _resolve_factory(spec: str) -> Callable:
    mod_name, _, attr = spec.partition(":")
    if not attr:
        raise ValueError(
            f"worker factory must be 'module:attr', got {spec!r}")
    return getattr(importlib.import_module(mod_name), attr)


def _worker_main(spec: Dict[str, Any]) -> None:
    """Entry point of the serving worker child (module-level: spawn
    pickles the target by reference). Builds the model via the factory,
    warms the ladder, attaches shared memory, then serves until told to
    shut down — or until a ``die@``/``hang@`` drill takes it out."""
    conn = Client(spec["address"], authkey=spec["authkey"])
    try:
        _worker_body(conn, spec)
    except SystemExit:
        raise
    except BaseException:  # noqa: BLE001 - last-chance telemetry: the
        # supervisor turns the EOF into a crash either way, but the
        # traceback makes the black box actionable
        try:
            conn.send(("worker_error", traceback.format_exc()))
        except Exception:  # noqa: BLE001 - conn may be the casualty
            pass
        raise
    finally:
        try:
            conn.close()
        except Exception:  # noqa: BLE001 - already torn down
            pass


def _worker_body(conn, spec: Dict[str, Any]) -> None:
    runtime_mod.ensure_compile_cache()
    import jax  # deferred to the child

    from .serving import ServingRuntime

    # first backend touch, on purpose and alone: a chip belongs to one
    # process at a time, and the usual reason a worker cannot start is
    # that the supervisor's process (which trains) holds the only one
    try:
        jax.devices()
    except RuntimeError as e:
        raise RuntimeError(
            "serving worker found no free device — a chip belongs to one "
            "process at a time and the supervisor's process holds it. Give "
            "the worker a chip of its own, or pin it to the CPU "
            "(SuperviseConfig(env={'JAX_PLATFORMS': 'cpu'})). Backend said: "
            f"{e}") from e
    factory = _resolve_factory(spec["factory"])
    built = factory(**spec.get("kwargs", {}))
    rt = ServingRuntime(
        built["de"], built["pred_fn"], built["state"],
        mesh=built.get("mesh"), config=built.get("config"),
        streaming=built.get("streaming"))
    template_state = built["state"]
    template_streaming = (built["streaming"][1]
                          if built.get("streaming") else None)
    rt.warmup(built["template"])
    if spec.get("slo") is not None:
        rt.set_freshness_slo(steps=spec["slo"][0], seconds=spec["slo"][1])
    exporter = mplane.start_http_exporter(rt.metrics,
                                          port=spec.get("metrics_port"))
    region = None
    if spec.get("shm_name"):
        region = shm_mod.SnapshotShm.attach(spec["shm_name"])
    installed_seq = 0
    die_at = set(runtime_mod.die_steps())
    hang_at = set(runtime_mod.hang_steps())
    ridmap: Dict[int, int] = {}  # runtime rid -> supervisor rid
    last_fed = 0.0  # last metrics-federation send (worker monotonic)
    conn.send(("ready", {"pid": os.getpid(),
                         "warmup_compiles": rt.warmup_compiles,
                         "metrics_port": exporter.port if exporter else None}))

    def _ingest() -> None:
        nonlocal installed_seq
        if region is None:
            return
        snap = region.read_latest()
        if snap is None or snap.seq <= installed_seq:
            return
        state, streaming_state, _ = install_payload(
            snap.payload, template_state, template_streaming)
        rt.install_snapshot(state, streaming_state, version=snap.version,
                            train_step=snap.train_step,
                            published_t=snap.wall_ts)
        installed_seq = snap.seq

    def _emit(res: ServeResult) -> None:
        sup_rid = ridmap.pop(res.rid, None)
        if sup_rid is None:
            return
        res.rid = sup_rid
        if isinstance(res, Served) and res.predictions is not None:
            res.predictions = np.asarray(res.predictions)
        conn.send(("result", res))

    while True:
        _ingest()
        while conn.poll(0.001):
            msg = conn.recv()
            kind = msg[0]
            if kind == "ping":
                # metrics federation rides the heartbeat it already
                # pays for: at most one registry snapshot per
                # _FED_EVERY_S, so the supervisor's /metrics can serve
                # the worker's families without a second channel
                fed = None
                wnow = time.monotonic()
                if wnow - last_fed >= _FED_EVERY_S:
                    last_fed = wnow
                    fed = rt.metrics.to_dict()
                conn.send(("pong", msg[1], fed))
            elif kind == "request":
                sup_rid, ordinal, req = msg[1], msg[2], msg[3]
                if ordinal in die_at:
                    # the SIGKILL/OOM equivalent: no cleanup, no goodbye
                    os._exit(17)
                if ordinal in hang_at:
                    # the wedged-process equivalent: stop answering
                    # EVERYTHING (heartbeats included) without exiting —
                    # detection must never depend on our cooperation
                    while True:
                        time.sleep(3600)
                rej = rt.submit(req)
                if rej is not None:
                    rej.rid = sup_rid
                    conn.send(("result", rej))
                else:
                    ridmap[req.rid] = sup_rid
            elif kind == "train_step":
                rt.note_train_step(msg[1])
            elif kind == "shm":
                region = shm_mod.SnapshotShm.attach(msg[1])
            elif kind == "slo":
                rt.set_freshness_slo(steps=msg[1], seconds=msg[2])
            elif kind == "flush":
                for res in rt.flush():
                    _emit(res)
            elif kind == "stats":
                conn.send(("stats_reply", rt.stats()))
            elif kind == "shutdown":
                for res in rt.flush():
                    _emit(res)
                conn.send(("bye",))
                if exporter:
                    exporter.stop()
                if region is not None:
                    region.close()
                return
        for res in rt.poll():
            _emit(res)


class ServingWorker:
    """Handle on one worker incarnation: the spawn-context process plus
    its connection. Thin — policy lives in :class:`Supervisor`."""

    def __init__(self, process, conn, info: Dict[str, Any]):
        self.process = process
        self.conn = conn
        self.info = info

    @property
    def pid(self) -> int:
        return self.process.pid

    def alive(self) -> bool:
        return self.process.is_alive()

    def kill(self) -> None:
        """SIGKILL — the worker may be wedged; SIGTERM would trust it."""
        try:
            self.process.kill()
        except Exception:  # noqa: BLE001 - already gone
            pass
        self.process.join(timeout=10)

    def close(self) -> None:
        try:
            self.conn.close()
        except Exception:  # noqa: BLE001 - already closed
            pass


# --------------------------------------------------------- the supervisor


class Supervisor:
    """Trainer-side handle on a supervised out-of-process serving
    worker; presents the :class:`~.serving.ServingRuntime` surface.

    Usage::

        sup = Supervisor("tools.isolation_common:worker_factory",
                         kwargs={"world": 8},
                         config=SuperviseConfig(blackbox_path=...))
        sup.start()                       # blocks until worker warm
        sup.install_snapshot(state, streaming_state,
                             version=1, train_step=0)
        rej = sup.submit(req)             # None | Overloaded | Unavailable
        results = sup.poll()
        ...
        sup.close()

    Thread model: the caller's threads only touch in-memory state and
    the send QUEUE (training never blocks on a slow/hung worker); one
    monitor thread owns the socket (heartbeats, receive, crash/hang
    detection, restart); one sender thread drains the queue into the
    socket. Snapshot publication bypasses the socket entirely — it is a
    seqlock write into shared memory, crash-proof by construction.
    """

    # state the caller / monitor / sender threads share (detlint
    # thread-shared): every mutation holds self._lock, or carries a
    # thread-local-ok waiver at the site explaining why it is safe
    # (pre-thread construction, post-join teardown, atomic reference
    # swap by a sole writer)
    _THREAD_SHARED = (
        "_alive", "_closing", "_counts", "_down_reason", "_down_since",
        "_fed_archive", "_fed_latest", "_inflight", "_last_pong",
        "_last_train_step", "_last_version", "_next_rid", "_outage_trace",
        "_restarts", "_results", "_shm", "_slo", "_warm", "_worker",
        "_worker_stats", "restart_budget_exhausted",
    )

    def __init__(self, factory: str, kwargs: Optional[Dict[str, Any]] = None,
                 *, config: Optional[SuperviseConfig] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.cfg = config or SuperviseConfig()
        self._factory = factory
        self._kwargs = dict(kwargs or {})
        self._clock = clock
        self._listener = Listener(family="AF_UNIX",
                                  authkey=_SPAWN.current_process().authkey)
        self._worker: Optional[ServingWorker] = None
        self._shm: Optional[shm_mod.SnapshotShm] = None
        self._slo: Optional[Tuple[Optional[float], Optional[float]]] = None
        self._lock = threading.Lock()
        self._results: collections.deque = collections.deque()
        self._inflight: Dict[int, float] = {}
        self._next_rid = 0
        self._alive = False
        self._warm = False
        self._closing = False
        self._down_since = self._clock()
        self._down_reason = "never_started"
        self._last_pong = 0.0
        self._restarts = 0
        self.restart_budget_exhausted = False
        self._counts = collections.Counter()
        self._worker_stats: Dict[str, Any] = {}
        self._stats_event = threading.Event()
        self._last_train_step: Optional[int] = None
        self._last_version = 0
        self._publish_ms = mplane.QuantileSketch()
        self._restart_to_serve_ms: List[float] = []
        self._awaiting_first_served: Optional[float] = None
        self._recorder = (mplane.FlightRecorder(self.cfg.blackbox_path)
                          if self.cfg.blackbox_path else None)
        self._send_q: "queue.Queue" = queue.Queue()
        self._monitor: Optional[threading.Thread] = None
        self._sender: Optional[threading.Thread] = None
        # ---- request tracing (utils/reqtrace.py): the supervisor MINTS
        # each trace at submit; the context rides the request over the
        # socket and the worker's runtime adopts it, so its stage spans
        # re-parent under this id — across die@ restarts too. The trace
        # the outage touched LAST (newest stranded rid, then each
        # refused-during-outage rid in turn — the one the bounded ring
        # cannot have evicted) is remembered in _outage_trace; when the
        # reborn worker serves its first request, worker_restarted /
        # served_after_restart marks are appended to it: ONE retained
        # trace shows submit -> outage -> Unavailable -> restart ->
        # served (what make check-tracing asserts)
        self._e2e_ms = mplane.QuantileSketch()  # end-to-end, this side
        self.traces = reqtrace.TraceBuffer(process="supervisor",
                                           top_fn=self._trace_top_decile)
        self._outage_trace: Optional[str] = None
        # ---- metrics federation: the worker's registry documents
        # arrive on pongs (_fed_latest); a dead incarnation's last
        # document is absorbed into _fed_archive (sketch-merged), so
        # counts survive restarts. The supervisor's own registry serves
        # ONE merged /metrics view over both plus its own families
        self._fed_latest: Optional[Dict[str, Any]] = None
        self._fed_archive: Optional[Dict[str, Any]] = None
        self.metrics = mplane.MetricsRegistry()
        self.metrics.register_collector(self._collect_metrics)
        self.metrics.add_federated(self._federated_doc)

    def _trace_top_decile(self) -> Optional[float]:
        """Tail-retention threshold: q90 of the end-to-end latency the
        supervisor itself observed (None while cold)."""
        return (self._e2e_ms.quantile(0.9) if self._e2e_ms.count >= 20
                else None)

    def _collect_metrics(self) -> None:
        """Scrape-time adapter for the supervisor's OWN families (the
        worker's arrive via federation)."""
        with self._lock:
            alive = self._alive
            restarts = self._restarts
            outage = 0.0 if alive else self._clock() - self._down_since
            exhausted = self.restart_budget_exhausted
            counts = dict(self._counts)
        mplane.sync_counters(self.metrics, counts,
                             name="detpu_supervisor_total", label="outcome")
        g = self.metrics.gauge
        g("detpu_supervisor_worker_alive",
          "1 while the serving worker is up").set(int(alive))
        g("detpu_supervisor_restarts",
          "supervised worker restarts spent").set(restarts)
        g("detpu_supervisor_outage_s",
          "current outage age (0 while the worker is up)").set(outage)
        g("detpu_supervisor_restart_budget_exhausted",
          "1 once the restart budget is spent").set(int(exhausted))
        g("detpu_supervisor_trace_ring",
          "retained supervisor-side request traces").set(
            self.traces.stats()["retained"])
        if self._publish_ms.count:
            g("detpu_supervisor_shm_publish_p95_ms",
              "seqlock snapshot publish latency p95 (ms)").set(
                self._publish_ms.quantile(0.95))

    def _federated_doc(self) -> Optional[Dict[str, Any]]:
        """The worker-side registry document for the merged scrape: the
        live incarnation's latest, sketch-merged over every dead
        incarnation's final document."""
        with self._lock:
            docs = [d for d in (self._fed_archive, self._fed_latest) if d]
        if not docs:
            return None
        # merge outside the lock: the documents are immutable once
        # stored (swaps replace the reference, merge copies)
        return (mplane.merge_registry_docs(docs) if len(docs) > 1
                else docs[0])

    # ------------------------------------------------------------ spawn

    def _spawn_spec(self) -> Dict[str, Any]:
        return {
            "address": self._listener.address,
            "authkey": bytes(_SPAWN.current_process().authkey),
            "factory": self._factory,
            "kwargs": self._kwargs,
            "shm_name": self._shm.name if self._shm else None,
            "slo": self._slo,
            "metrics_port": self.cfg.metrics_port,
        }

    def _spawn_worker(self) -> ServingWorker:
        spec = self._spawn_spec()
        proc = _SPAWN.Process(target=_worker_main, args=(spec,),
                              name="detpu-serving-worker", daemon=True)
        saved = {k: os.environ.get(k) for k in self.cfg.env}
        os.environ.update(self.cfg.env)
        try:
            proc.start()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        conn_box: List[Any] = []
        accept = threading.Thread(
            target=lambda: conn_box.append(self._listener.accept()),
            daemon=True)
        accept.start()
        accept.join(self.cfg.start_timeout_s)
        if not conn_box:
            proc.kill()
            proc.join(timeout=10)
            raise TimeoutError(
                f"serving worker did not connect within "
                f"{self.cfg.start_timeout_s}s")
        conn = conn_box[0]
        deadline = self._clock() + self.cfg.start_timeout_s
        while True:
            if conn.poll(max(0.0, min(1.0, deadline - self._clock()))):
                msg = conn.recv()
                if msg[0] == "ready":
                    return ServingWorker(proc, conn, msg[1])
                if msg[0] == "worker_error":
                    proc.kill()
                    proc.join(timeout=10)
                    raise RuntimeError(
                        f"serving worker failed to build:\n{msg[1]}")
                continue  # unrelated early chatter
            if self._clock() >= deadline or not proc.is_alive():
                proc.kill()
                proc.join(timeout=10)
                raise TimeoutError("serving worker never became ready")

    def start(self) -> "Supervisor":
        """Spawn the first worker and block until it is warm (compiled
        ladder + attached shm); then supervision runs in the
        background."""
        if self._monitor is not None:
            raise RuntimeError("supervisor already started")
        self._worker = self._spawn_worker()  # thread-local-ok: runs before the monitor/sender threads exist
        self._on_worker_up()
        self._sender = threading.Thread(target=self._send_loop,
                                        name="detpu-supervise-send",
                                        daemon=True)
        self._sender.start()
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         name="detpu-supervise",
                                         daemon=True)
        self._monitor.start()
        return self

    def _on_worker_up(self) -> None:
        now = self._clock()
        with self._lock:
            self._alive = True
            self._warm = True
            self._last_pong = now
            if self._restarts:
                self._awaiting_first_served = now
            step = self._last_train_step
        if step is not None:
            self._send_q.put(("train_step", step))

    # ----------------------------------------------------- wire plumbing

    def _send_loop(self) -> None:
        while not self._closing:
            try:
                item = self._send_q.get(timeout=0.1)
            except queue.Empty:
                continue
            worker = self._worker
            if worker is None or not self._alive:
                continue  # outage: the crash path answers for us
            try:
                worker.conn.send(item)
            except Exception:  # noqa: BLE001 - a broken pipe IS the
                # crash signal; the monitor thread makes it official
                pass

    def _handle_msg(self, msg: Tuple) -> None:
        now = self._clock()
        with self._lock:
            self._last_pong = now
        kind = msg[0]
        if kind == "result":
            res = msg[1]
            first_after_restart = False
            with self._lock:
                t_sub = self._inflight.pop(res.rid, None)
                if t_sub is None:
                    # already answered Unavailable at crash detection —
                    # a late duplicate would break request conservation
                    return
                self._results.append(res)
                if (isinstance(res, Served)
                        and self._awaiting_first_served is not None):
                    self._restart_to_serve_ms.append(
                        (now - self._awaiting_first_served) * 1e3)
                    self._awaiting_first_served = None
                    first_after_restart = True
                outage_trace = self._outage_trace
                restarts = self._restarts
            # supervisor-side trace: the worker's stage spans verbatim
            # (their sum equals res.latency_ms exactly — the partition
            # crossed the boundary intact); the socket/queue overhead
            # this side observed on top is a boundary mark, outside the
            # partition by design
            spans = getattr(res, "spans", None)
            stages = ({k[:-3]: v for k, v in spans.items()} if spans
                      else {"queue_wait": res.latency_ms})
            boundary_ms = max(0.0, (now - t_sub) * 1e3 - res.latency_ms)
            self._e2e_ms.observe((now - t_sub) * 1e3)
            self.traces.event(res.rid, "boundary", t=now,
                              dur_ms=boundary_ms)
            self.traces.finish(res.rid, res.status, res.latency_ms, now,
                               stages, boundary_ms=boundary_ms,
                               restarts=restarts)
            if first_after_restart and outage_trace is not None:
                # the restart-crossing evidence: the outage's first
                # stranded trace now carries the full arc
                self.traces.append_event(outage_trace, "worker_restarted",
                                         t=now, restarts=restarts)
                self.traces.append_event(outage_trace,
                                         "served_after_restart", t=now,
                                         dur_ms=res.latency_ms,
                                         served_rid=res.rid)
                self.traces.annotate(outage_trace, restart_crossed=True,
                                     restarts_at_serve=restarts)
                with self._lock:
                    self._outage_trace = None
        elif kind == "pong":
            # liveness (handled above) + the piggybacked federation doc
            if len(msg) > 2 and msg[2]:
                with self._lock:
                    self._fed_latest = msg[2]
        elif kind == "stats_reply":
            with self._lock:
                self._worker_stats = msg[1]
            self._stats_event.set()
        elif kind == "worker_error":
            logger.error("serving worker raised:\n%s", msg[1])
            if self._recorder:
                self._recorder.note_event("serve_worker_error",
                                          traceback=msg[1])
        # "bye" carries nothing beyond liveness

    def _monitor_loop(self) -> None:
        last_ping = 0.0
        while not self._closing:
            worker = self._worker
            if not self._alive or worker is None:
                time.sleep(0.01)
                continue
            now = self._clock()
            if now - last_ping >= self.cfg.heartbeat_s:
                self._send_q.put(("ping", now))
                last_ping = now
            try:
                while worker.conn.poll(self.cfg.heartbeat_s / 4):
                    self._handle_msg(worker.conn.recv())
            except (EOFError, OSError):
                self._on_worker_down("crash")
                continue
            if not worker.alive():
                self._on_worker_down("crash")
            elif self._clock() - self._last_pong > self.cfg.deadline_s:
                worker.kill()  # SIGKILL: a wedged worker won't cooperate
                self._on_worker_down("hang")

    # ------------------------------------------------------ crash path

    def _on_worker_down(self, reason: str) -> None:
        now = self._clock()
        down_reason = f"worker_{reason}"
        with self._lock:
            worker, self._worker = self._worker, None
            self._alive = False
            self._down_since = now
            self._down_reason = down_reason
            self._counts[reason] += 1
            stranded = list(self._inflight.items())
            self._inflight.clear()
            restarts = self._restarts
            for rid, t_sub in stranded:
                self._counts["unavailable"] += 1
                self._results.append(Unavailable(
                    rid=rid, latency_ms=0.0, reason=down_reason,
                    outage_s=0.0, restarts=restarts,
                    spans={"queue_wait_ms":
                           max(0.0, (now - t_sub) * 1e3)}))
            # absorb the dead incarnation's final federation document:
            # its counters and sketch buckets keep merging under the
            # reborn worker's, so the scrape never forgets an outage
            if self._fed_latest:
                self._fed_archive = mplane.merge_registry_docs(
                    [d for d in (self._fed_archive, self._fed_latest)
                     if d])
                self._fed_latest = None
        # stranded traces finish Unavailable with the wait they actually
        # spent (an outage mark annotates the death); the newest one
        # becomes the outage trace the restart-crossing marks land on —
        # later refusals during the outage keep moving the pointer
        # forward so the bounded ring can never evict it first
        last_tid = None
        for rid, t_sub in stranded:
            wait_ms = max(0.0, (now - t_sub) * 1e3)
            self.traces.event(rid, "outage", t=now, reason=down_reason)
            tr = self.traces.finish(rid, "unavailable", wait_ms, now,
                                    {"queue_wait": wait_ms},
                                    reason=down_reason, stranded=True,
                                    restarts=restarts)
            if tr is not None:
                last_tid = tr["trace_id"]
        if last_tid is not None:
            with self._lock:
                self._outage_trace = last_tid
        # purge queued sends: the reborn worker must not receive
        # requests whose rids were just answered Unavailable
        try:
            while True:
                self._send_q.get_nowait()
        except queue.Empty:
            pass
        pid = worker.pid if worker else -1
        if worker:
            worker.kill()
            worker.close()
        logger.warning("serving worker pid=%s down (%s); %d in-flight "
                       "answered Unavailable", pid, reason, len(stranded))
        obs.counter_inc("serve_worker_crash")
        obs.record_event("serve_worker_crash", reason=reason, pid=pid,
                         stranded=len(stranded), restarts=self._restarts)
        if self._recorder:
            # the black box the child can no longer write: the
            # supervisor dumps on its behalf
            self._recorder.note_event("serve_worker_crash", reason=reason,
                                      pid=pid, stranded=len(stranded),
                                      restarts=self._restarts)
            if self._worker_stats:
                self._recorder.note_stats(self._worker_stats)
            for tr in self.traces.drain_new():
                self._recorder.note_trace(tr)
            self._recorder.dump("serve_worker_crash", reason=reason,
                                pid=pid)
        self._restart()

    def _restart(self) -> None:
        """Kill-and-restart under the budget, jittered exponential
        backoff (the ``runtime.retry`` idiom: ``base * 2^k``, capped,
        x(0.5 + rand) jitter so a fleet of supervisors never thunders)."""
        attempt = 0
        while not self._closing:
            if self._restarts >= self.cfg.max_restarts:
                with self._lock:
                    self.restart_budget_exhausted = True
                    self._down_reason = "restart_budget_exhausted"
                logger.error("serving worker restart budget (%d) "
                             "exhausted; serving stays Unavailable",
                             self.cfg.max_restarts)
                obs.record_event("serve_worker_budget_exhausted",
                                 restarts=self._restarts)
                return
            delay = min(self.cfg.backoff_base_s * (2.0 ** attempt),
                        self.cfg.backoff_max_s)
            delay *= 0.5 + random.random()
            time.sleep(delay)
            attempt += 1
            with self._lock:
                self._restarts += 1
            try:
                # spawn outside the lock (blocks on fork + accept +
                # worker warmup); the reference swap itself is atomic
                self._worker = self._spawn_worker()  # thread-local-ok: reference swap by the monitor thread, the sole writer while supervision runs
            except Exception as e:  # noqa: BLE001 - spawn/ready failure
                # burns budget and backs off further, never raises into
                # the trainer
                logger.warning("serving worker restart %d failed: %s",
                               self._restarts, e)
                obs.record_retry(f"serve_worker_restart:{e}")
                continue
            self._on_worker_up()
            obs.counter_inc("serve_worker_restart")
            obs.record_event("serve_worker_restart",
                             restarts=self._restarts,
                             pid=self._worker.pid)
            if self._recorder:
                self._recorder.note_event("serve_worker_restart",
                                          restarts=self._restarts,
                                          pid=self._worker.pid)
            return

    # ------------------------------------- the ServingRuntime surface

    def install_snapshot(self, state, streaming_state=None, *,
                         version: int, train_step: int,
                         published_t: Optional[float] = None,
                         now: Optional[float] = None) -> None:
        """Publish one snapshot INTO SHARED MEMORY (seqlock write, no
        socket, no lock shared with the worker): a crashed, hung, or
        restarting worker can never block the trainer here. A reborn
        worker reads the latest snapshot on attach, so publishing
        during an outage is not just safe but the recovery path."""
        if version <= self._last_version:
            raise ValueError(
                f"snapshot version must be monotonic: got {version}, "
                f"published {self._last_version}")
        t0 = self._clock()
        payload = snapshot_payload(state, streaming_state)
        created = None
        with self._lock:
            # lazy region creation is a check-then-act; _spawn_spec
            # reads _shm from the monitor thread on every restart
            if self._shm is None:
                self._shm = shm_mod.SnapshotShm.create(
                    shm_mod.slack_capacity(len(payload)))
                created = self._shm.name
        if created is not None:
            self._send_q.put(("shm", created))
        wall = time.monotonic() if published_t is None else published_t
        self._shm.publish_bytes(payload, version=int(version),
                                train_step=int(train_step), wall_ts=wall)
        self._publish_ms.observe((self._clock() - t0) * 1e3)
        with self._lock:
            self._last_version = int(version)
            self._last_train_step = int(train_step)

    def note_train_step(self, step: int) -> None:
        with self._lock:
            self._last_train_step = int(step)
        self._send_q.put(("train_step", int(step)))

    def set_freshness_slo(self, steps: Optional[float] = None,
                          seconds: Optional[float] = None) -> None:
        with self._lock:
            self._slo = (steps, seconds)
        self._send_q.put(("slo", steps, seconds))

    def warmup(self, template=None) -> None:
        """No-op: the worker warms its own ladder from its factory's
        template before reporting ready (``_warm`` flips then)."""

    @property
    def queued_samples(self) -> int:
        """In-flight requests (submitted, not yet answered) — the
        drain condition for :class:`~.serving.RealtimeDriver`."""
        with self._lock:
            return len(self._inflight)

    def submit(self, req) -> Optional[ServeResult]:
        now = self._clock()
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            alive = self._alive
            restarts = self._restarts
            if alive:
                self._inflight[rid] = now
        # mint (or adopt) the trace here, at the FRONT DOOR: the worker
        # re-parents under this context, so one trace id survives the
        # pickle boundary and any worker rebirth in between
        ctx = self.traces.begin(rid, now,
                                ctx=getattr(req, "trace", None),
                                priority=getattr(req, "priority", 0),
                                incarnation=restarts)
        if not alive:
            with self._lock:
                self._counts["unavailable"] += 1
                outage = now - self._down_since
                reason = self._down_reason
            tr = self.traces.finish(rid, "unavailable", 0.0, now,
                                    {"queue_wait": 0.0}, reason=reason,
                                    outage_s=outage, restarts=restarts)
            if tr is not None:
                # keep pointing at the NEWEST outage trace: every
                # refusal is retained ("outcome"), so under a long
                # outage the oldest ones are exactly what the bounded
                # ring evicts first — the newest is the one guaranteed
                # to still be retained when the restart marks land
                with self._lock:
                    self._outage_trace = tr["trace_id"]
            return Unavailable(rid=rid, latency_ms=0.0, reason=reason,
                               outage_s=outage, restarts=restarts,
                               spans={"queue_wait_ms": 0.0})
        req.rid = rid
        req.trace = ctx
        # the rid doubles as the GLOBAL stream ordinal die@/hang@ key on
        self._send_q.put(("request", rid, rid, req))
        return None

    def poll(self, now=None) -> List[ServeResult]:
        out: List[ServeResult] = []
        with self._lock:
            while self._results:
                out.append(self._results.popleft())
        return out

    def flush(self) -> List[ServeResult]:
        """Ask the worker to flush sub-rung batches, then return what
        has arrived (socket round-trip: poll again for stragglers)."""
        self._send_q.put(("flush",))
        time.sleep(self.cfg.heartbeat_s)
        return self.poll()

    def stats(self, sync: bool = True,
              timeout_s: float = 5.0) -> Dict[str, Any]:
        """The worker's ``ServingRuntime.stats()`` (fresh over the
        socket when ``sync`` and the worker is alive; otherwise the
        last received) plus the ``"supervisor"`` block: restarts,
        outage bookkeeping, shm publish latency, restart-to-first-served
        — the isolation-layer stats the drills read."""
        if sync and self._alive:
            self._stats_event.clear()
            self._send_q.put(("stats",))
            self._stats_event.wait(timeout_s)
        out = dict(self._worker_stats)
        with self._lock:
            out["supervisor"] = {
                "worker_alive": self._alive,
                "restarts": self._restarts,
                "crashes": self._counts["crash"],
                "hangs": self._counts["hang"],
                "unavailable": self._counts["unavailable"],
                "restart_budget_exhausted": self.restart_budget_exhausted,
                "outage_s": (0.0 if self._alive
                             else self._clock() - self._down_since),
                "shm_region_bytes": self._shm.size if self._shm else 0,
                "shm_publish_p95_ms": (self._publish_ms.quantile(0.95)
                                       if self._publish_ms.count else None),
                "restart_to_first_served_ms": (
                    self._restart_to_serve_ms[-1]
                    if self._restart_to_serve_ms else None),
                "e2e_p99_ms": (self._e2e_ms.quantile(0.99)
                               if self._e2e_ms.count else None),
            }
        # the supervisor's OWN trace ring (end-to-end spans, boundary
        # marks) — distinct from the worker's in-process ring above
        out["supervisor"]["trace"] = self.traces.stats()
        out["supervisor"]["p99_exemplars"] = self.traces.exemplars(5)
        return out

    # ---------------------------------------------------------- teardown

    def close(self) -> None:
        """Orderly shutdown: ask the worker to exit, then escalate;
        tear down the socket and UNLINK the shm region (the supervisor
        owns it — last one out)."""
        # stop supervision FIRST: the monitor must not read the orderly
        # exit below as a crash (and burn a restart + a black box on it)
        self._closing = True  # thread-local-ok: atomic stop flag, sole writer; the loops poll it
        if self._monitor is not None:
            self._monitor.join(timeout=5)
        if self._sender is not None:
            self._sender.join(timeout=5)
        worker = self._worker
        if worker is not None and self._alive:
            try:
                worker.conn.send(("shutdown",))
            except Exception:  # noqa: BLE001 - dying anyway
                pass
            worker.process.join(timeout=5)
        if worker is not None:
            worker.kill()
            worker.close()
        self._worker = None  # thread-local-ok: monitor/sender joined above, no other thread of control remains
        self._alive = False  # thread-local-ok: monitor/sender joined above, no other thread of control remains
        try:
            self._listener.close()
        except Exception:  # noqa: BLE001 - already closed
            pass
        if self._shm is not None:
            self._shm.unlink()
            self._shm = None  # thread-local-ok: monitor/sender joined above, no other thread of control remains
