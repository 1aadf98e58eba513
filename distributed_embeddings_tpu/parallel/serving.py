"""Deadline-bounded serving runtime: request coalescing, overload
admission control, and graceful degradation at QPS.

Every robustness subsystem before this one protects *training*
(preemption, rollback, elastic resume, streaming degradation); this
module is the inference half of "millions of users": answering
variable-size lookup requests under a latency SLO without recompiling
and without falling over when traffic spikes (ROADMAP item 4's serving
scenario). Three pieces, all host-side around ONE compiled program
family:

* **The compiled forward** — a no-grad step built from
  :func:`~.trainer.make_hybrid_eval_step` with ``donate_inputs=True``
  (each flush's freshly packed input buffer is dead the moment the
  step consumes it) and frozen tables. Every input leaf of a flush
  travels in ONE staging buffer and one host-to-device transfer
  (:class:`PackLayout`); the program's first stage unpacks it on the
  device by static slices. Streaming tables serve
  READ-ONLY: admitted ids read their slots, cold/evicted ids degrade to
  their shared hash-bucket rows, and no admission/eviction runs at
  serve time — the slot map, sketch and counters are bitwise-unchanged
  by any amount of serving. The program family is a small fixed
  **ladder** of padded batch shapes (one compiled executable per rung,
  warmed up front), so steady-state serving is pinned to ZERO
  recompiles by the compile-listener counter (``steady_recompiles``).
* **The request coalescer** — variable-size requests (1..n samples
  each, single-hot, fixed multi-hot, or ragged-hotness inputs) are
  packed FIFO into the smallest rung that holds them; padding samples
  are whole fake rows (id 0, zero features) whose predictions are
  sliced off, and the padding fraction is a first-class metric (every
  padded slot is latency and exchange bytes spent on nobody).
* **The robustness core** — a deadline scheduler (flush on ``max_batch``
  OR ``max_wait_ms``, with per-request deadline propagation: the flush
  happens early when the oldest deadline demands it, and requests
  already past their deadline are dropped with a typed
  :class:`Expired` instead of wasting a rung) and an overload admission
  controller with an explicit DEGRADATION LADDER:

  - **level 0 (healthy)** — batch up to ``max_wait_ms`` for efficiency;
  - **level 1 (pressure)** — a full rung is queued: the batching delay
    shrinks to zero and the queue drains flush-after-flush;
  - **level 2 (shed)** — the queue passed ``shed_frac x max_queue``:
    new lowest-priority (``priority <= 0``) requests are refused with a
    typed :class:`Overloaded` response while higher-priority traffic
    keeps being served; at ``max_queue`` everything incoming is shed.
    Queue growth is bounded by construction — there is no input rate at
    which memory grows without bound.

  Every level transition is surfaced via
  :func:`~..utils.obs.record_event` (``serve_degraded`` /
  ``serve_recovered``) and the served/shed/deadline-missed counts bump
  the process counters next to the recompile counter.

Under the online-learning runtime (``parallel/online.py``) the frozen
tables become *published snapshots*: :meth:`ServingRuntime.
install_snapshot` atomically swaps in monotonically-versioned table
copies between polls (every flush reads the installed view exactly once
— no torn reads), per-response staleness is tracked next to latency
(``freshness_p95_steps`` / ``freshness_p95_s`` in :meth:`stats`), and a
FRESHNESS rung joins the ladder: when publication falls behind
``DETPU_FRESHNESS_MAX_STEPS`` (or ages past ``DETPU_FRESHNESS_MAX_S``)
the server sheds low-priority load (typed ``Overloaded``,
``reason="stale_snapshot"``; ``snapshot_lagging`` event) instead of
ever blocking training.

Drills: ``DETPU_FAULT=slow:serve_step`` injects latency into every
flush (the degraded-backend drill) and ``DETPU_FAULT=burst@<pos>``
makes :func:`drive` spike the arrival rate during second ``<pos>`` of
the stream (the QPS-spike drill). ``tools/check_serving.py`` (= ``make
check-serving``) runs both against the ladder in CI and requires
bounded p99, clean typed shedding, zero steady-state recompiles, and
post-burst recovery; the benchmark's ``kaggle_serve_ranking`` cell
(``benchmarks/run.py``) measures the latency tails on the chip.

The runtime is single-threaded and clock-injectable: callers own the
loop (``submit`` + ``poll``), tests drive a manual clock, and
:func:`drive` is the shared real-time load loop the tools use. Nothing
here imports a backend beyond what the compiled forward already needs.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple)

import jax
import numpy as np

from ..utils import envvars, mplane, obs, reqtrace
from ..utils import runtime as runtime_mod
from ..ops.embedding_lookup import Ragged
from . import streaming as streaming_mod
from .trainer import make_hybrid_eval_step

logger = logging.getLogger(__name__)

#: degradation-ladder levels (index = level)
LEVELS = ("healthy", "pressure", "shed")

#: the longest :meth:`ServingRuntime.poll` stays away when requests are
#: queued and none is due: a caller that loops on ``poll()`` through the
#: batching delay gets its core back in slices of this length instead of
#: making a quarter of a million empty calls a second, and an arrival
#: waits at most this long for its ``submit``
POLL_IDLE_S = 2e-4

#: per-request latency decomposition stages, in pipeline order: the time
#: between submit and the reply splits EXACTLY into these five spans
#: (queue wait is per request; the other four are per flush), each rolled
#: into its own registry sketch so :meth:`ServingRuntime.stats` can
#: attribute the p99 tail to a stage — the instrument behind ROADMAP
#: item 1's "the p99 tail is exchange-bound" claim
STAGES = ("queue_wait", "coalesce", "dispatch", "device_compute",
          "reply_slice")


class ServeConfig:
    """Static serving policy (ladder, deadlines, admission bounds).

    A plain attribute bag (hashable not required — the runtime closes
    over it host-side only). ``rungs`` overrides the power-of-two
    ladder; every rung must be divisible by the world size (the
    shard_map splits the padded batch evenly over ranks).
    """

    def __init__(self,
                 max_batch: Optional[int] = None,
                 rungs: Optional[Sequence[int]] = None,
                 max_wait_ms: Optional[float] = None,
                 deadline_ms: Optional[float] = None,
                 max_queue: Optional[int] = None,
                 shed_frac: Optional[float] = None,
                 ragged_hotness: int = 0):
        env_rungs = envvars.get("DETPU_SERVE_RUNGS") or ""
        if rungs is None and env_rungs.strip():
            rungs = [int(x) for x in env_rungs.split(",") if x.strip()]
        self.rungs = tuple(int(r) for r in rungs) if rungs else None
        self.max_batch = int(
            max_batch if max_batch is not None
            else (self.rungs[-1] if self.rungs
                  else envvars.get_int("DETPU_SERVE_MAX_BATCH")))
        self.max_wait_ms = float(
            max_wait_ms if max_wait_ms is not None
            else envvars.get_float("DETPU_SERVE_MAX_WAIT_MS"))
        self.deadline_ms = float(
            deadline_ms if deadline_ms is not None
            else envvars.get_float("DETPU_SERVE_DEADLINE_MS"))
        self.max_queue = int(
            max_queue if max_queue is not None
            else envvars.get_int("DETPU_SERVE_MAX_QUEUE"))
        self.shed_frac = float(
            shed_frac if shed_frac is not None
            else envvars.get_float("DETPU_SERVE_SHED_FRAC"))
        #: per-sample id budget of ragged (list-of-lists) inputs; the
        #: rung's static value capacity is ``rung x ragged_hotness``.
        #: 0 = no ragged inputs accepted
        self.ragged_hotness = int(ragged_hotness)
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if not (0.0 < self.shed_frac <= 1.0):
            raise ValueError("shed_frac must be in (0, 1]")
        if self.max_queue < self.max_batch:
            raise ValueError(
                f"max_queue ({self.max_queue}) must hold at least one "
                f"full batch ({self.max_batch}) — a queue smaller than "
                "a rung sheds healthy traffic")


def resolve_rungs(config: ServeConfig, world: int) -> Tuple[int, ...]:
    """The padded-batch ladder: explicit ``config.rungs`` validated, or
    powers of two from ``max(8, world)`` up to ``max_batch`` (each
    rounded up to a ``world`` multiple). One compiled executable per
    rung — keep the ladder small; every rung is a warmup compile."""
    if config.rungs:
        rungs = list(config.rungs)
        if sorted(rungs) != rungs or len(set(rungs)) != len(rungs):
            raise ValueError(f"rungs must be strictly ascending: {rungs}")
        for r in rungs:
            if r < 1 or r % world:
                raise ValueError(
                    f"rung {r} is not a positive multiple of world "
                    f"{world}")
        return tuple(rungs)

    def up(x: int) -> int:
        return ((x + world - 1) // world) * world

    lo = up(max(8, world))
    # the TOP rung rounds DOWN to a world multiple (never past the
    # configured max_batch — admission and the max_queue validation
    # bind against it), except when max_batch < world, where one
    # world-sized rung is the minimum viable ladder
    hi = max(world, (config.max_batch // world) * world)
    rungs = []
    r = lo
    while r < hi:
        rungs.append(r)
        r *= 2
    rungs.append(hi)
    return tuple(sorted(set(rungs)))


# ---------------------------------------------------------------- requests


@dataclasses.dataclass
class Request:
    """One inference request: ``n`` samples of categorical ids (+ the
    dense ``batch`` pytree the ``pred_fn`` consumes).

    ``cats`` holds one entry per model input: an int array ``[n]``
    (single-hot), ``[n, h]`` (fixed multi-hot), or a length-``n`` list
    of id lists (ragged hotness — per-sample lists longer than the
    configured ``ragged_hotness`` budget are clipped and counted).
    Higher ``priority`` survives longer under overload; ``deadline_ms``
    (from submit time) defaults to the config's."""

    cats: Sequence[Any]
    batch: Any = None
    priority: int = 0
    deadline_ms: Optional[float] = None
    # filled in by submit():
    rid: int = -1
    n: int = 0
    t_submit: float = 0.0
    deadline: float = 0.0
    # span context (utils/reqtrace.py): minted at submit, or provided by
    # an upstream minter (the supervisor) — it pickles across the worker
    # socket with the rest of the request, which is HOW one trace id
    # spans the process boundary: the worker's runtime adopts it in
    # _normalize and its stage spans re-parent under the upstream trace
    trace: Optional[Dict[str, Any]] = None
    # a language model's turn (``parallel/lm_serving.py``): the session it
    # continues, the tokens to generate (its ``n``) and the generated
    # positions whose logits come back; ``cats[0]`` holds the prompt's ids
    session: Optional[int] = None
    max_new_tokens: int = 0
    logits_at: Sequence[int] = ()


@dataclasses.dataclass
class ServeResult:
    """Base of the typed responses (``isinstance`` IS the status)."""

    rid: int
    latency_ms: float

    @property
    def status(self) -> str:
        return type(self).__name__.lower()


@dataclasses.dataclass
class Served(ServeResult):
    """Predictions for one request, sliced from its flush."""

    predictions: Any = None
    rung: int = 0
    deadline_missed: bool = False  # completed, but after the deadline
    # online-learning provenance: which published table snapshot answered
    # (the whole flush observed exactly this one version — never a
    # mid-publish mix), and how stale it was at flush time. -1 / None =
    # no snapshot installed (the classic frozen-table server)
    version: int = -1
    staleness_steps: Optional[float] = None
    staleness_s: Optional[float] = None
    # latency decomposition: one ``<stage>_ms`` entry per :data:`STAGES`
    # member; the five spans sum to ``latency_ms`` by construction
    # (queue wait is this request's own, the rest are its flush's)
    spans: Optional[Dict[str, float]] = None
    # a language model's turn: the generated ids (``predictions`` then holds
    # the logits of the positions ``Request.logits_at`` named)
    tokens: Any = None


@dataclasses.dataclass
class Overloaded(ServeResult):
    """Typed load-shed rejection: the admission controller refused the
    request (full queue, or shed level + low priority). The caller can
    retry after backing off — nothing about the request was wrong."""

    reason: str = "queue_full"
    level: int = 0
    queue_samples: int = 0
    # minimal decomposition: everything a shed request spent was queue
    # admission time (0 — refused at the door), kept span-shaped so the
    # unhealthy tail reads like the healthy one
    spans: Optional[Dict[str, float]] = None


@dataclasses.dataclass
class Expired(ServeResult):
    """The request's deadline passed while it was still queued — the
    scheduler dropped it instead of spending a rung on an answer nobody
    is waiting for. Counted ``deadline_missed``."""

    deadline_ms: float = 0.0
    # minimal decomposition: an expired request's whole life was queue
    # wait — ``{"queue_wait_ms": latency_ms}`` by construction
    spans: Optional[Dict[str, float]] = None


@dataclasses.dataclass
class Failed(ServeResult):
    """The flush this request was coalesced into raised (injected
    fault, transient backend error, a pred_fn bug): the request is
    consumed and answered TYPED instead of the exception escaping
    ``poll()`` and silently losing every co-batched request — one bad
    flush must never kill the serving loop. Counted ``failed``;
    recorded as a ``serve_flush_error`` event."""

    reason: str = ""
    # minimal decomposition: time from submit to the flush failure,
    # booked as queue wait (the flush's own spans died with it)
    spans: Optional[Dict[str, float]] = None


@dataclasses.dataclass
class Unavailable(ServeResult):
    """The serving WORKER is down: it crashed or hung and its
    supervisor is restarting it (or has exhausted the restart budget).
    One rung below ``stale_snapshot`` on the degradation ladder — a
    stale server still answers, a dead one answers TYPED: every request
    arriving during the outage (and every request that was in flight
    inside the dead worker) gets this instead of being lost or hanging
    a caller forever. ``outage_s`` is how long the worker had been down
    when this request arrived; ``restarts`` how many supervised
    restarts have been spent. Emitted only by the trainer-side
    ``parallel.supervisor.Supervisor`` — the in-process runtime cannot
    be "down" while it runs."""

    reason: str = "worker_down"
    outage_s: float = 0.0
    restarts: int = 0
    # minimal decomposition: how long the request waited before the
    # supervisor answered for the dead worker (0 when refused on
    # arrival, the stranded wait when answered by _on_worker_down)
    spans: Optional[Dict[str, float]] = None


# ------------------------------------------------- the packed input layout


@dataclasses.dataclass(frozen=True)
class _Leaf:
    """One input leaf's section of a shard's row of the staging buffer."""

    offset: int                 # 32-bit words from the row's start
    words: int                  # whole words: a narrower dtype is padded up
    shape: Tuple[int, ...]      # the leaf as one shard of the batch sees it
    size: int                   # its elements
    dtype: np.dtype

    def host_view(self, row: np.ndarray) -> np.ndarray:
        """The leaf's writable view into one shard's row (host side)."""
        return row[self.offset:self.offset + self.words].view(
            self.dtype)[:self.size].reshape(self.shape)

    def device_view(self, row):
        """The leaf rebuilt from one shard's row inside the compiled
        program: a static slice, a bitcast and a reshape — the bytes the
        request carried, never a conversion."""
        w = jax.lax.slice(row, (self.offset,), (self.offset + self.words,))
        # bool has no bitcast: its bytes travel as uint8 (0 / 1)
        carrier = np.dtype(np.uint8) if self.dtype == np.bool_ else self.dtype
        if carrier.itemsize > 4:
            w = w.reshape(self.size, carrier.itemsize // 4)
        if carrier != np.int32:
            w = jax.lax.bitcast_convert_type(w, carrier)
        if carrier.itemsize < 4:
            w = w.reshape(-1)[:self.size]
        if carrier != self.dtype:
            w = w.astype(self.dtype)
        return w.reshape(self.shape)


class PackLayout:
    """Where every input leaf of one rung lies in the ONE staging buffer
    a flush sends: ``int32[world x words]``, shard ``s``'s row of
    ``words`` holding its ``rung // world`` rows of every leaf, each leaf
    a contiguous section at a static offset. The buffer is flat so that
    the mesh axis hands a device its row as it lies, with no reshape
    ahead of the slices.

    Leaves, in order: per categorical input the ids (``[b]`` or
    ``[b, hot]`` int32) or, for a ragged input, the shard's CSR pair
    (``values[b x hot]``, ``row_splits[b + 1]``); then the batch
    tree's leaves with their trailing shapes and dtypes. 4-byte leaves
    take their words as they are, narrower ones are padded to whole
    words, and an 8-byte leaf (x64 mode) takes two a value. The cost of
    a host-to-device transfer is the call, not the bytes, so one buffer
    a flush replaces one transfer a leaf; :meth:`unpack` is the compiled
    forward's first stage and undoes it by static slices."""

    def __init__(self, input_spec: Sequence[tuple], batch_spec: Any,
                 rung: int, world: int):
        b = rung // world
        self.rung, self.world = rung, world
        off = 0

        def leaf(shape, dtype) -> _Leaf:
            nonlocal off
            # what jnp.asarray made of the leaf: x64 off narrows 8 bytes
            dtype = np.dtype(jax.dtypes.canonicalize_dtype(np.dtype(dtype)))
            size = int(np.prod(shape))
            words = (size * dtype.itemsize + 3) // 4
            out = _Leaf(off, words, tuple(shape), size, dtype)
            off += words
            return out

        #: per categorical input ("d", ids) or ("r", values, row_splits)
        self.cats = [
            ("d", leaf((b,) if hot == 1 else (b, hot), np.int32))
            if kind == "d" else
            ("r", leaf((b * hot,), np.int32), leaf((b + 1,), np.int32))
            for kind, hot in input_spec]
        flat, self.batch_tree = jax.tree_util.tree_flatten(
            batch_spec, is_leaf=lambda x: isinstance(x, tuple)
            and len(x) == 2 and isinstance(x[1], str))
        self.batch = [leaf((b,) + tuple(trailing), dtype)
                      for trailing, dtype in flat]
        self.words = off

    @property
    def nbytes(self) -> int:
        """Bytes of the whole buffer: what the flush's one transfer
        carries."""
        return 4 * self.world * self.words

    def staging(self) -> np.ndarray:
        """A fresh zeroed staging buffer: all padding (id 0, zero
        features, zero-length ragged rows) until a request is written.
        Fresh every flush, never reused: ``jax.device_put`` returns
        before it has read the host memory (on the v5e an overwrite
        right after the call reached the device in 200 trials of 200)."""
        return np.zeros((self.world * self.words,), np.int32)

    def unpack(self, row):
        """``(cat_inputs, batch)`` of one shard from its row of the
        packed buffer (the ``[words]`` block that the mesh axis hands
        one device)."""
        cats = [c[1].device_view(row) if c[0] == "d" else
                Ragged(values=c[1].device_view(row),
                       row_splits=c[2].device_view(row))
                for c in self.cats]
        batch = jax.tree_util.tree_unflatten(
            self.batch_tree, [l.device_view(row) for l in self.batch])
        return cats, batch


# ----------------------------------------------------------- the runtime


class ServingRuntime:
    """Single-threaded deadline-bounded server around one compiled
    forward family.

    Usage::

        rt = ServingRuntime(de, pred_fn, state, mesh=mesh,
                            config=ServeConfig(max_batch=128))
        rt.warmup((template_cats, template_batch))
        rej = rt.submit(Request(cats=..., batch=...))  # None or Overloaded
        results += rt.poll()                           # flushes when due

    ``streaming=(StreamingConfig, streaming_state)`` serves dynamic
    tables read-only (cold ids degrade to their buckets; the state is
    never donated, never mutated). ``clock`` is injectable for
    deterministic tests; ``poll(now=...)`` accepts explicit time.
    """

    def __init__(self, de, pred_fn: Callable, state, mesh=None,
                 config: Optional[ServeConfig] = None,
                 streaming: Optional[tuple] = None,
                 clock: Callable[[], float] = time.monotonic,
                 trace: Optional[bool] = None):
        self.de = de
        self.config = config or ServeConfig()
        self.world = int(de.world_size)
        if self.world > 1 and mesh is None:
            raise ValueError("mesh is required for world_size > 1")
        if not de.dp_input:
            raise ValueError(
                "ServingRuntime requires dp_input=True: requests arrive "
                "as data-parallel id shards and ride the id exchange "
                "(pre-packed MpInputs cannot be coalesced per request)")
        self.rungs = resolve_rungs(self.config, self.world)
        # the installed (state, streaming_state, snapshot-meta) triple.
        # ONE reference, swapped atomically by install_snapshot() and read
        # ONCE per flush — a flush can never observe a mid-publish mix of
        # versions (the online runtime's no-torn-read contract). meta is
        # None for the classic frozen-table server, else
        # (version, train_step, published_t)
        self._published: Tuple[Any, Any, Optional[Tuple[int, int, float]]] \
            = (None, None, None)
        self.state = state
        self._clock = clock
        self._streaming_cfg = None
        self.streaming_state = None
        if streaming is not None:
            cfg, sstate = streaming
            self._streaming_cfg = streaming_mod.resolve_config(cfg)
            self.streaming_state = sstate
        self._pred_fn = pred_fn
        self._mesh = mesh
        # where the one packed buffer of a flush goes: split by rows over
        # the mesh axis, as the program's P(axis) in-spec expects it (no
        # reshard follows the transfer); the default device without a mesh
        self._packed_sharding = (
            None if mesh is None
            else jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(de.axis_name)))
        # rung -> (its PackLayout, its compiled forward); built from the
        # template's spec in warmup (or at the first flush of a rung)
        self._programs: Dict[int, Tuple[PackLayout, Callable]] = {}
        # writer-side state lock (reentrant: the staleness/level helpers
        # re-acquire it from already-locked callers). In realtime mode
        # ONE runtime is driven from three threads of control — the
        # RealtimeDriver submits/polls, the trainer installs snapshots,
        # the mplane exporter scrapes _collect — so every host-side
        # mutable (queue, outcome counters, freshness, ladder level)
        # mutates under this lock. The flush device path deliberately
        # stays OUTSIDE it: _published is read once per flush (RCU), so
        # publication never waits on device compute and vice versa
        self._state_lock = threading.RLock()
        self._queue: List[Request] = []
        self._queued_samples = 0
        self._level = 0
        self._next_rid = 0
        self._flush_seq = 0         # flush ordinals, minted as one starts
        self._input_spec: Optional[List[tuple]] = None
        self._batch_spec: Optional[Any] = None
        self._est_s = 0.0           # EMA of flush wall seconds
        self._warm = False
        self.warmup_compiles = 0
        self._compiles_at_steady = 0
        # ---- observability plane (utils/mplane.py): every latency /
        # depth / freshness signal folds into a mergeable log-bucketed
        # sketch — O(buckets) memory however long the server lives (the
        # former raw lists grew to 2x STATS_WINDOW floats per signal and
        # full-sorted per stats() call), quantiles within the sketch's
        # guaranteed relative error, and per-rank sketches merge
        # associatively for a fleet view. stats() stays a VIEW over
        # these; the registry also renders the Prometheus scrape text
        self.metrics = mplane.MetricsRegistry()
        self._lat_sketch = self.metrics.sketch(
            "detpu_serve_latency_ms",
            "end-to-end served-request latency (ms)").child()
        stage_fam = self.metrics.sketch(
            "detpu_serve_stage_ms",
            "served-request latency decomposition by stage (ms)")
        # the plain (outcome-less) children below are the SERVED-only
        # partition stats() sums against end-to-end latency; terminal
        # non-served outcomes observe into outcome-labeled siblings via
        # _terminal_spans so the unhealthy tail is counted without
        # skewing that sum
        self._stage_fam = stage_fam
        self._stage_sketch = {s: stage_fam.child(stage=s) for s in STAGES}
        self._qdepth_sketch = self.metrics.sketch(
            "detpu_serve_queue_depth",
            "queued samples observed at each admitted submit").child()
        self._fresh_steps_sketch = self.metrics.sketch(
            "detpu_serve_staleness_steps",
            "per-response snapshot staleness (train steps)").child()
        self._fresh_s_sketch = self.metrics.sketch(
            "detpu_serve_staleness_s",
            "per-response snapshot age (seconds)").child()
        self.metrics.register_collector(self._collect)
        self._pad_slots = 0
        self._total_slots = 0
        self._rung_flushes: Dict[int, int] = {r: 0 for r in self.rungs}
        self._counts = {"served": 0, "shed": 0, "deadline_missed": 0,
                        "expired": 0, "failed": 0, "flushes": 0,
                        "served_samples": 0, "ragged_clipped": 0,
                        "degraded": 0, "recovered": 0,
                        "snapshots_installed": 0, "stale_shed": 0}
        # freshness SLO state (online learning, parallel/online.py): the
        # trainer's newest completed step vs the installed snapshot's.
        # Inert (stale never trips) until a snapshot is installed
        self._latest_train_step: Optional[int] = None
        self._stale = False
        self._freshness_max_steps = envvars.get_int(
            "DETPU_FRESHNESS_MAX_STEPS")
        self._freshness_max_s = envvars.get_float("DETPU_FRESHNESS_MAX_S")
        # ---- request tracing (utils/reqtrace.py): a trace per rid,
        # minted in _normalize (or adopted from Request.trace when an
        # upstream supervisor minted it), finished with the five-stage
        # partition in _run_flush or the minimal queue_wait span on a
        # terminal outcome. ``trace=None`` defers to DETPU_TRACE; an
        # explicit False/True overrides it (tests measure the delta)
        self.traces = reqtrace.TraceBuffer(
            enabled=trace, process="serve", top_fn=self._trace_top_decile)

    def _trace_top_decile(self) -> Optional[float]:
        """Tail-retention threshold: the latency sketch's q90 once it
        has enough samples to mean something (None while cold — a cold
        threshold would retain everything and drown the sample)."""
        sk = self._lat_sketch
        return sk.quantile(0.9) if sk.count >= 20 else None

    def _terminal_spans(self, rid: int, outcome: str, latency_ms: float,
                        t_end: float, **attrs: Any) -> Dict[str, float]:
        """Book one terminal non-served outcome: observe its queue wait
        into the outcome-labeled stage sketch (the unhealthy tail stops
        under-counting) and finish its trace with the minimal
        ``{"queue_wait": latency_ms}`` partition — always retained, by
        the tail-sampling policy. Returns the ``spans`` dict the typed
        result carries (same ``<stage>_ms`` key shape as Served)."""
        lat = max(0.0, float(latency_ms))
        self._stage_fam.child(stage="queue_wait",
                              outcome=outcome).observe(lat)
        self.traces.finish(rid, outcome, latency_ms, t_end,
                           {"queue_wait": latency_ms}, **attrs)
        return {"queue_wait_ms": latency_ms}

    def _collect(self) -> None:
        """Scrape-time adapter: mirror the host counts and point-in-time
        gauges into the runtime's registry. The sketches observe inline
        on the hot path; everything countable syncs lazily, exactly when
        someone renders — scraping is the only cost of being scrapable."""
        mplane.sync_counters(self.metrics, self._counts,
                             name="detpu_serve_total", label="outcome")
        mplane.sync_counters(self.metrics, obs.counters())
        g = self.metrics.gauge
        g("detpu_serve_level",
          "degradation-ladder level (0 healthy, 1 pressure, 2 shed)"
          ).set(self._level)
        g("detpu_serve_queued_samples",
          "samples queued right now").set(self._queued_samples)
        g("detpu_serve_pad_fraction",
          "aggregate padded-slot fraction across flushes").set(
            self._pad_slots / self._total_slots if self._total_slots
            else 0.0)
        g("detpu_serve_steady_state_recompiles",
          "compiles since warmup (the 0-recompile contract)").set(
            self.steady_recompiles())
        g("detpu_serve_freshness_stale",
          "1 while the freshness SLO is breached").set(int(self._stale))
        g("detpu_serve_trace_ring",
          "tail-sampled request traces retained in the ring").set(
            self.traces.stats()["retained"])

    def _count(self, key: str, n: int = 1) -> None:
        """Bump one outcome counter under the state lock. A bare dict
        ``+=`` is a read-modify-write: concurrent bumps from the driver
        and trainer threads can lose increments (the concurrency
        auditor's first real finding in this file)."""
        with self._state_lock:
            self._counts[key] += n

    # --------------------------------------------- published table views

    @property
    def state(self):
        """The train state the compiled forward reads — the currently
        installed table view (a published snapshot under the online
        runtime, the construction-time state otherwise)."""
        return self._published[0]

    @state.setter
    def state(self, value) -> None:
        _, ss, meta = self._published
        # thread-local-ok: RCU — single-reference swap; construction /
        # checkpoint-restore path, before any concurrent serving
        self._published = (value, ss, meta)  # thread-local-ok: RCU swap

    @property
    def streaming_state(self):
        """Read-only streaming-vocab state of the installed view."""
        return self._published[1]

    @streaming_state.setter
    def streaming_state(self, value) -> None:
        st, _, meta = self._published
        # thread-local-ok: RCU — single-reference swap; construction /
        # checkpoint-restore path, before any concurrent serving
        self._published = (st, value, meta)  # thread-local-ok: RCU swap

    def install_snapshot(self, state, streaming_state=None, *,
                         version: int, train_step: int,
                         published_t: Optional[float] = None,
                         now: Optional[float] = None) -> None:
        """Atomically swap in one published table view (RCU reader side).

        The online runtime's :class:`~.online.SnapshotPublisher` calls
        this between polls with freshly copied buffers; ``version`` must
        be strictly monotonic (a regression raises — the versioning
        contract, not a recoverable condition). The swap is a single
        reference assignment and every flush reads the triple exactly
        once, so a flush observes exactly one version. The arrays must
        match the warmed-up state's structure/shapes/dtypes bitwise-in-
        spec, or the compiled ladder would retrace (the 0-steady-state-
        recompiles contract ``make check-online`` drills)."""
        now = self._clock() if now is None else now
        published_t = now if published_t is None else float(published_t)
        if self._streaming_cfg is not None and streaming_state is None:
            raise ValueError(
                "this runtime serves streaming tables: install_snapshot "
                "needs the matching streaming_state copy")
        with self._state_lock:
            # the version check is a check-then-act: it and the swap
            # must be one atom or two racing publishers could both pass
            meta = self._published[2]
            if meta is not None and version <= meta[0]:
                raise ValueError(
                    f"snapshot version must be monotonic: got {version}, "
                    f"installed {meta[0]}")
            self._published = (state, streaming_state,
                               (int(version), int(train_step),
                                published_t))
            # the snapshot IS the freshest trained view at publish time
            self._latest_train_step = int(train_step)
            self._counts["snapshots_installed"] += 1
            obs.counter_inc("snapshot_published")
            obs.record_event("snapshot_published", version=int(version),
                             train_step=int(train_step))
            self._refresh_staleness(now)

    def note_train_step(self, step: int, now: Optional[float] = None) -> None:
        """Tell the server how far training has advanced (the freshness
        reference point). When the installed snapshot falls more than
        ``DETPU_FRESHNESS_MAX_STEPS`` behind (or ages past
        ``DETPU_FRESHNESS_MAX_S``), the runtime enters its shed rung —
        load is refused serve-side (typed, ``reason="stale_snapshot"``)
        before the trainer is ever blocked on publication."""
        now = self._clock() if now is None else now
        with self._state_lock:
            if (self._latest_train_step is None
                    or step > self._latest_train_step):
                self._latest_train_step = int(step)
            self._refresh_staleness(now)

    def set_freshness_slo(self, max_steps: Optional[int] = None,
                          max_s: Optional[float] = None) -> None:
        """Override the env-default freshness SLO (the online runtime
        pushes its :class:`~.online.OnlineConfig` through here so one
        config governs publisher and server)."""
        with self._state_lock:
            if max_steps is not None:
                self._freshness_max_steps = int(max_steps)
            if max_s is not None:
                self._freshness_max_s = float(max_s)

    def _staleness(self, now: float) -> Optional[Tuple[int, float, float]]:
        """(version, lag_steps, age_s) of the installed snapshot, or
        ``None`` when no snapshot was ever installed."""
        meta = self._published[2]
        if meta is None:
            return None
        version, snap_step, pub_t = meta
        latest = (self._latest_train_step if self._latest_train_step
                  is not None else snap_step)
        return version, max(0, latest - snap_step), max(0.0, now - pub_t)

    def _refresh_staleness(self, now: float) -> None:
        # reentrant: install_snapshot/note_train_step call this with
        # the state lock already held; poll() calls it bare
        with self._state_lock:
            st = self._staleness(now)
            if st is None:
                return
            version, lag_steps, age_s = st
            stale = ((self._freshness_max_steps > 0
                      and lag_steps > self._freshness_max_steps)
                     or (self._freshness_max_s > 0
                         and age_s > self._freshness_max_s))
            if stale and not self._stale:
                obs.counter_inc("snapshot_lagging")
                obs.record_event("snapshot_lagging", version=version,
                                 lag_steps=int(lag_steps),
                                 age_s=float(age_s),
                                 max_steps=self._freshness_max_steps,
                                 max_s=self._freshness_max_s)
                logger.warning(
                    "serving snapshot v%d is STALE (%d step(s) / %.3f s "
                    "behind training) — entering the shed rung", version,
                    lag_steps, age_s)
                rec = mplane.flight_recorder()
                if rec is not None:
                    # freshness/SLO breach: park a post-mortem while the
                    # breach is live (the black box names the lagging
                    # version and carries the recent stats ring, plus
                    # the exemplar requests that led up to the breach)
                    rec.note_stats(self.stats())
                    for tr in self.traces.drain_new():
                        rec.note_trace(tr)
                    rec.dump("freshness_breach", version=int(version),
                             lag_steps=int(lag_steps), age_s=float(age_s))
            self._stale = stale
            self._update_level()

    @property
    def freshness_stale(self) -> bool:
        """Whether the freshness SLO is currently violated (the shed
        rung is forced on until the next publication)."""
        return self._stale

    # ------------------------------------------------------------ intake

    def _normalize(self, req: Request, now: float) -> Request:
        """Derive ``n``, validate shapes against the (template-derived)
        input spec, clip over-budget ragged rows, stamp the deadline."""
        if len(req.cats) != len(self.de.strategy.input_table_map):
            raise ValueError(
                f"request has {len(req.cats)} categorical inputs, the "
                f"model takes {len(self.de.strategy.input_table_map)}")
        spec = self._spec_of(req.cats, req.batch)
        with self._state_lock:
            # first-submit initialization is a check-then-act
            if self._input_spec is None:
                self._input_spec, self._batch_spec = spec
        if spec[0] != self._input_spec:
            raise ValueError(
                f"request input spec {spec[0]} does not match the "
                f"warmed-up spec {self._input_spec} — one compiled "
                "ladder serves one input layout")
        elif spec[1] != self._batch_spec:
            # reject HERE, while nothing is queued: a malformed batch
            # that only failed at pack time would crash the flush and
            # lose every healthy request coalesced with it
            raise ValueError(
                f"request batch spec {spec[1]} does not match the "
                f"warmed-up spec {self._batch_spec}")
        n = None
        for i, c in enumerate(req.cats):
            ni = len(c) if isinstance(c, (list, tuple)) \
                else int(np.asarray(c).shape[0])
            if n is None:
                n = ni
            elif n != ni:
                raise ValueError(
                    f"input {i} has {ni} samples, input 0 has {n}")
        if not n:
            raise ValueError("empty request")
        if n > self.rungs[-1]:
            raise ValueError(
                f"request of {n} samples exceeds the largest rung "
                f"{self.rungs[-1]} — split it client-side")
        hot = self.config.ragged_hotness
        cats = []
        for i, c in enumerate(req.cats):
            if isinstance(c, (list, tuple)):
                rows = []
                for row in c:
                    row = list(row)
                    if len(row) > hot:
                        self._count("ragged_clipped", len(row) - hot)
                        row = row[:hot]
                    rows.append(row)
                cats.append(rows)
            else:
                cats.append(np.asarray(c))
        req.cats = cats
        req.n = int(n)
        with self._state_lock:
            # rid assignment must be atomic or two racing submits can
            # share a rid (the result-matching key)
            req.rid = self._next_rid
            self._next_rid += 1
        req.t_submit = now
        dl = (req.deadline_ms if req.deadline_ms is not None
              else self.config.deadline_ms)
        req.deadline_ms = float(dl)
        req.deadline = now + dl / 1e3
        # trace mint point: every admitted-or-shed rid gets a span
        # context here; a context already on the request (the supervisor
        # minted upstream) is adopted, re-parenting this runtime's spans
        req.trace = self.traces.begin(req.rid, now, ctx=req.trace,
                                      priority=req.priority, n=req.n)
        return req

    def _spec_of(self, cats, batch) -> tuple:
        spec = []
        for c in cats:
            if isinstance(c, (list, tuple)):
                if self.config.ragged_hotness < 1:
                    raise ValueError(
                        "ragged (list-of-lists) input needs "
                        "ServeConfig(ragged_hotness=...) > 0")
                spec.append(("r", self.config.ragged_hotness))
            else:
                a = np.asarray(c)
                if a.ndim == 1:
                    spec.append(("d", 1))
                elif a.ndim == 2:
                    spec.append(("d", int(a.shape[1])))
                else:
                    raise ValueError(
                        f"categorical input rank {a.ndim} unsupported")
        bspec = jax.tree.map(
            # the dtype by name: a bfloat16 leaf's ``str`` is a bare "<V2"
            lambda a: (tuple(np.asarray(a).shape[1:]),
                       np.asarray(a).dtype.name), batch)
        return spec, bspec

    def submit(self, req: Request,
               now: Optional[float] = None) -> Optional[Overloaded]:
        """Admit one request. Returns ``None`` (queued — the answer
        arrives from a later :meth:`poll`) or a typed
        :class:`Overloaded` when the admission controller sheds it."""
        with obs.span("serve/submit") as sp:
            now = self._clock() if now is None else now
            req = self._normalize(req, now)
            sp.set_metadata(rid=req.rid, n=req.n)
            q = self._queued_samples
            shed_at = self.config.shed_frac * self.config.max_queue
            reason = None
            if q + req.n > self.config.max_queue:
                reason = "queue_full"
            elif self._stale and req.priority <= 0:
                # freshness rung: publication fell behind the SLO — refuse
                # low-priority load rather than serve ever-staler answers
                # (or block training to catch up)
                reason = "stale_snapshot"
            elif q >= shed_at and req.priority <= 0:
                reason = "load_shed"
            if reason is not None:
                self._count("shed")
                if reason == "stale_snapshot":
                    self._count("stale_shed")
                obs.counter_inc("serve_shed")
                self._update_level()
                spans = self._terminal_spans(req.rid, "overloaded", 0.0, now,
                                             reason=reason, level=self._level,
                                             queue_samples=q)
                return Overloaded(rid=req.rid, latency_ms=0.0, reason=reason,
                                  level=self._level, queue_samples=q,
                                  spans=spans)
            with self._state_lock:
                self._queue.append(req)
                self._queued_samples += req.n
            self._qdepth_sketch.observe(self._queued_samples)
            self._update_level()
            return None

    @property
    def queued_samples(self) -> int:
        return self._queued_samples

    @property
    def level(self) -> int:
        """Current degradation-ladder level (0 healthy, 1 pressure,
        2 shed)."""
        return self._level

    # ------------------------------------------------- degradation ladder

    def _target_level(self, q: int) -> int:
        if self._stale:
            # the freshness rung rides the same ladder as queue pressure:
            # serve_degraded/serve_recovered events fire on the
            # transitions, and recovery is the next publication
            return 2
        if q >= self.config.shed_frac * self.config.max_queue:
            return 2
        if q >= self.rungs[-1]:
            return 1
        return 0

    def _set_level(self, new: int, q: int) -> None:
        # reentrant: reads-then-writes _level and fires the transition
        # event exactly once, however many threads race the transition
        with self._state_lock:
            old = self._level
            if new == old:
                return
            self._level = new
            if new > old:
                self._counts["degraded"] += 1
                obs.record_event("serve_degraded", level=new,
                                 from_level=old, level_name=LEVELS[new],
                                 queue_samples=q)
                logger.warning("serving degraded to %s (queue %d "
                               "samples)", LEVELS[new], q)
            else:
                self._counts["recovered"] += 1
                obs.record_event("serve_recovered", level=new,
                                 from_level=old, level_name=LEVELS[new],
                                 queue_samples=q)
                logger.info("serving recovered to %s (queue %d samples)",
                            LEVELS[new], q)

    def _update_level(self) -> None:
        with self._state_lock:
            self._set_level(self._target_level(self._queued_samples),
                            self._queued_samples)

    # ----------------------------------------------------------- packing

    def _rung_for(self, n: int) -> int:
        for r in self.rungs:
            if r >= n:
                return r
        return self.rungs[-1]

    def _program(self, rung: int) -> Tuple[PackLayout, Callable]:
        """The rung's static input layout and the forward compiled
        against it (``eval(state, packed[, stream])``: the packed buffer
        donated, the state never)."""
        prog = self._programs.get(rung)
        if prog is None:
            if self._input_spec is None:
                raise RuntimeError(
                    "call warmup(template) first — the input layout comes "
                    "from the template request")
            layout = PackLayout(self._input_spec, self._batch_spec, rung,
                                self.world)
            prog = (layout, make_hybrid_eval_step(
                self.de, self._pred_fn, mesh=self._mesh,
                dynamic=self._streaming_cfg, donate_inputs=True,
                unpack=layout.unpack))
            with self._state_lock:
                prog = self._programs.setdefault(rung, prog)
        return prog

    def _zero_inputs(self, rung: int):
        """The packed all-padding input of one rung (warmup / audit)."""
        return self._pack([], rung)[0]

    def _h2d(self, buf: np.ndarray):
        """The flush's one host-to-device transfer under its ``serve/h2d``
        span: the host's time in the call, not the DMA's."""
        with obs.span("serve/h2d", bytes=buf.nbytes):
            return jax.device_put(buf, self._packed_sharding)

    def _pack(self, reqs: List[Request], rung: int):
        """Coalesce ``reqs`` (total samples <= rung) into the rung's one
        staging buffer (:class:`PackLayout`) and send it: every request's
        leaves are copied into their sections of a fresh zeroed buffer
        under one ``serve/pack`` span, then ONE transfer under one
        ``serve/h2d`` span carries the lot, whatever the number of input
        leaves (a flush's pack + h2d spans sum to its ``coalesce_ms``).
        Padding samples are whole fake rows: id 0 everywhere, zero dense
        features, zero-length ragged rows — what the zeroed buffer holds
        where nothing is written; their predictions are sliced off
        below. Returns the device buffer and each request's row offset."""
        layout, _ = self._program(rung)
        with obs.span("serve/pack"):
            buf = layout.staging()
            rows = buf.reshape(self.world, layout.words)
            b_local = rung // self.world
            # request r's rows o..o+n of the global batch lie in shard
            # o // b_local onwards: one copy a request and leaf, one more
            # for each shard boundary the request crosses
            offsets, pieces = [], []
            off = 0
            for ri, r in enumerate(reqs):
                offsets.append(off)
                lo = 0
                while lo < r.n:
                    s, at = divmod(off + lo, b_local)
                    k = min(r.n - lo, b_local - at)
                    pieces.append((ri, s, slice(at, at + k),
                                   slice(lo, lo + k)))
                    lo += k
                off += r.n

            def fill(leaf: _Leaf, per_request) -> None:
                views = [leaf.host_view(row) for row in rows]
                for ri, s, dst, src in pieces:
                    a = np.asarray(per_request[ri])
                    if a.ndim != len(leaf.shape):  # [n, 1] ids, one-hot
                        a = a.reshape((-1,) + leaf.shape[1:])
                    views[s][dst] = a[src]

            for i, c in enumerate(layout.cats):
                if c[0] == "d":
                    fill(c[1], [r.cats[i] for r in reqs])
                    continue
                # ragged: each shard's own CSR pair over its b_local rows
                lists = [ids for r in reqs for ids in r.cats[i]]
                for s in range(self.world):
                    mine = lists[s * b_local:(s + 1) * b_local]
                    if not mine:
                        break
                    ends = np.cumsum([len(ids) for ids in mine])
                    c[1].host_view(rows[s])[:ends[-1]] = [
                        v for ids in mine for v in ids]
                    splits = c[2].host_view(rows[s])
                    splits[1:len(ends) + 1] = ends
                    splits[len(ends) + 1:] = ends[-1]
            req_leaves = [jax.tree.leaves(r.batch) for r in reqs]
            for li, leaf in enumerate(layout.batch):
                fill(leaf, [rl[li] for rl in req_leaves])
        return self._h2d(buf), offsets

    # ----------------------------------------------------------- serving

    def warmup(self, template) -> int:
        """Compile the whole ladder up front from a ``(cats, batch)``
        template (one representative request's inputs). Installs the
        compile listener and records the warmup compile count; after
        this, :meth:`steady_recompiles` must stay 0 whatever mix of
        request sizes arrives — the property ``make check-serving``
        drills. Returns the number of warmup compiles."""
        import warnings

        obs.install_compile_listener()
        cats, batch = template
        # thread-local-ok: warmup precedes serving — the driver/trainer
        # threads only start once the ladder is compiled
        self._input_spec, self._batch_spec = self._spec_of(cats, batch)  # thread-local-ok: warmup precedes serving
        self._programs = {}  # thread-local-ok: warmup precedes serving
        before = obs.counters().get("recompiles", 0)
        for rung in self.rungs:
            packed = self._zero_inputs(rung)
            with warnings.catch_warnings():
                # input donation is best-effort: a backend that cannot
                # alias the int32 staging buffer into the f32 predictions
                # warns per compile — expected here, not actionable
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not")
                out = self._dispatch(packed, rung)
            np.asarray(out)  # block: the compile must finish inside warmup
        self.warmup_compiles = obs.counters().get("recompiles", 0) - before  # thread-local-ok: warmup precedes serving
        self._compiles_at_steady = obs.counters().get("recompiles", 0)  # thread-local-ok: warmup precedes serving
        self._warm = True  # thread-local-ok: warmup precedes serving
        return self.warmup_compiles

    def steady_recompiles(self) -> int:
        """Compiles observed since :meth:`warmup` finished — the serving
        analogue of a train window's ``compiles_in_window`` (must be 0)."""
        if not self._warm:
            return 0
        return obs.counters().get("recompiles", 0) - self._compiles_at_steady

    def _dispatch(self, packed, rung: int, published=None):
        state, sstate, _ = (self._published if published is None
                            else published)
        _, forward = self._program(rung)
        if sstate is not None:
            return forward(state, packed, sstate)
        return forward(state, packed)

    def _run_flush(self, reqs: List[Request],
                   rung: int) -> List[Served]:
        """One flush, opened for a profile by :func:`~.obs.span`: a
        ``serve/flush`` span (args: the flush ordinal the request traces
        carry, the rung, requests and samples) whose children on this
        thread are, in order, one ``serve/pack`` and one ``serve/h2d``
        (:meth:`_pack`: every input in one buffer, sent once),
        ``serve/dispatch`` (the call into the rung's compiled forward,
        which unpacks the buffer on the device), ``serve/fetch`` (device
        compute + the copy back) and ``serve/reply``. The spans record
        only while a profiler session runs; ``Served.spans`` and
        :data:`STAGES` are :meth:`_flush`'s own clock reads, as ever."""
        with self._state_lock:
            # the flush ordinal doubles as the coalesce-span id linking
            # the N request traces that shared this flush: minted as
            # the flush starts, so its serve/flush span carries it too
            self._flush_seq += 1
            flush_id = self._flush_seq
        with obs.span("serve/flush", flush=flush_id, rung=rung,
                      requests=len(reqs), samples=sum(r.n for r in reqs)):
            return self._flush(reqs, rung, flush_id)

    def _flush(self, reqs: List[Request], rung: int,
               flush_id: int) -> List[Served]:
        runtime_mod.fault_point("serve_step")
        t0 = self._clock()
        # read the published triple ONCE: the whole flush — tables,
        # streaming state, version stamp — observes exactly this view,
        # however the publisher interleaves (the no-torn-read contract)
        published = self._published
        packed, offsets = self._pack(reqs, rung)
        t_pack = self._clock()
        with obs.span("serve/dispatch"):
            pending = self._dispatch(packed, rung, published)
        t_disp = self._clock()
        with obs.span("serve/fetch"):
            preds = np.asarray(pending)  # device compute + host fetch
        t_dev = self._clock()
        with obs.span("serve/reply"):
            slices = [preds[o:o + r.n] for r, o in zip(reqs, offsets)]
            t1 = self._clock()
            with self._state_lock:
                # flush accounting only — the device work above ran
                # lock-free against the RCU-read published triple
                self._est_s = (t_dev - t0 if not self._est_s
                               else 0.7 * self._est_s + 0.3 * (t_dev - t0))
                n = sum(r.n for r in reqs)
                self._pad_slots += rung - n
                self._total_slots += rung
                self._counts["flushes"] += 1
                self._rung_flushes[rung] = \
                    self._rung_flushes.get(rung, 0) + 1
            # latency decomposition: the flush-level spans are shared by
            # every coalesced request (they waited on the SAME pack /
            # dispatch / device / slice work); queue wait is per request.
            # The five spans sum to each request's latency by construction
            coalesce_ms = (t_pack - t0) * 1e3
            dispatch_ms = (t_disp - t_pack) * 1e3
            device_ms = (t_dev - t_disp) * 1e3
            reply_ms = (t1 - t_dev) * 1e3
            # per-response freshness: how stale the answering snapshot was at
            # flush time, in steps (vs the trainer's newest completed step)
            # and seconds (snapshot age) — the freshness SLO's raw samples
            meta = published[2]
            version = -1
            stale_steps: Optional[float] = None
            stale_s: Optional[float] = None
            if meta is not None:
                version, snap_step, pub_t = meta
                latest = (self._latest_train_step if self._latest_train_step
                          is not None else snap_step)
                stale_steps = float(max(0, latest - snap_step))
                stale_s = float(max(0.0, t_dev - pub_t))
            out = []
            for r, pred in zip(reqs, slices):
                lat = (t1 - r.t_submit) * 1e3
                queue_wait_ms = (t0 - r.t_submit) * 1e3
                missed = t1 > r.deadline
                spans = {"queue_wait_ms": queue_wait_ms,
                         "coalesce_ms": coalesce_ms,
                         "dispatch_ms": dispatch_ms,
                         "device_compute_ms": device_ms,
                         "reply_slice_ms": reply_ms}
                self._lat_sketch.observe(lat)
                for stage, v in zip(STAGES, spans.values()):
                    self._stage_sketch[stage].observe(max(0.0, v))
                if meta is not None:
                    self._fresh_steps_sketch.observe(stale_steps)
                    self._fresh_s_sketch.observe(stale_s)
                self._count("served")
                self._count("served_samples", r.n)
                if missed:
                    self._count("deadline_missed")
                    obs.counter_inc("serve_deadline_missed")
                obs.counter_inc("serve_served")
                # the trace's stage partition is exactly the spans dict
                # (bare stage names): sum == latency_ms by the telescoping
                # construction above — the 1e-6 invariant check-tracing
                # asserts on every retained trace
                self.traces.finish(r.rid, "served", lat, t1,
                                   dict(zip(STAGES, spans.values())),
                                   flush=flush_id, coalesced=len(reqs),
                                   rung=rung, flush_t0=t0, version=version,
                                   deadline_missed=missed)
                out.append(Served(rid=r.rid, latency_ms=lat,
                                  predictions=pred, rung=rung,
                                  deadline_missed=missed, version=version,
                                  staleness_steps=stale_steps,
                                  staleness_s=stale_s, spans=spans))
            return out

    def poll(self, now: Optional[float] = None) -> List[ServeResult]:
        """Run the scheduler once: expire dead requests, flush every due
        batch, update the degradation level. Returns the completed
        results (:class:`Served` / :class:`Expired`); call it often —
        with nothing queued it returns at once, and with requests queued
        and none due it first sleeps until one is, :data:`POLL_IDLE_S`
        at most (never under an explicit ``now``: that caller owns the
        time)."""
        out: List[ServeResult] = []
        explicit = now is not None
        # the seconds half of the freshness SLO can trip between
        # publications with no train-step notification — re-evaluate it
        # on the scheduler tick. Guarded so the classic (no-snapshot)
        # server keeps its exact clock-read sequence
        if self._published[2] is not None:
            self._refresh_staleness(now if explicit else self._clock())
        while True:
            t = now if explicit else self._clock()
            # deadline propagation, part 1: requests already past their
            # deadline are dead weight — drop them (typed) rather than
            # spend rung slots on them (strictly past: at exactly the
            # deadline the flush below still gets its chance)
            keep = []
            expired_now: List[Request] = []
            with self._state_lock:
                for r in self._queue:
                    if r.deadline < t:
                        self._queued_samples -= r.n
                        self._counts["expired"] += 1
                        self._counts["deadline_missed"] += 1
                        obs.counter_inc("serve_deadline_missed")
                        expired_now.append(r)
                    else:
                        keep.append(r)
                self._queue = keep
            # span booking outside the state lock (sketch + trace locks
            # are leaves; no reason to nest them under the queue's)
            for r in expired_now:
                lat = (t - r.t_submit) * 1e3
                spans = self._terminal_spans(r.rid, "expired", lat, t,
                                             deadline_ms=r.deadline_ms)
                out.append(Expired(rid=r.rid, latency_ms=lat,
                                   deadline_ms=r.deadline_ms,
                                   spans=spans))
            if not self._queue:
                break
            oldest = self._queue[0]
            full = self._queued_samples >= self.rungs[-1]
            # degradation ladder, level 1: under pressure the batching
            # delay shrinks to zero — latency is spent on compute only
            wait_s = (0.0 if self._level >= 1
                      else self.config.max_wait_ms / 1e3)
            timed_out = t >= oldest.t_submit + wait_s
            # deadline propagation, part 2: flush early when the
            # TIGHTEST queued deadline (not necessarily the oldest
            # request's) would be missed by waiting any longer (the
            # flush itself costs ~est_s)
            tightest = min(r.deadline for r in self._queue)
            deadline_due = t + self._est_s >= tightest
            if not (full or timed_out or deadline_due):
                if not explicit:
                    due_in = min(oldest.t_submit + wait_s,
                                 tightest - self._est_s) - t
                    time.sleep(max(0.0, min(POLL_IDLE_S, due_in)))
                break
            out.extend(self._flush_picked())
        self._update_level()
        return out

    def _flush_picked(self) -> List[ServeResult]:
        """Pop one rung's worth of requests FIFO and run the flush.
        Shared by :meth:`poll` and :meth:`flush` (ONE packing policy);
        a flush that raises answers its requests with typed
        :class:`Failed` instead of letting the exception escape and
        lose every co-batched request."""
        picked: List[Request] = []
        total = 0
        with self._state_lock:
            while (self._queue
                   and total + self._queue[0].n <= self.rungs[-1]):
                r = self._queue.pop(0)
                picked.append(r)
                total += r.n
            self._queued_samples -= total
        try:
            return self._run_flush(picked, self._rung_for(total))
        except Exception as e:  # noqa: BLE001 - typed failure, see Failed
            self._count("failed", len(picked))
            obs.counter_inc("serve_failed", len(picked))
            obs.record_event("serve_flush_error", error=repr(e),
                             requests=len(picked))
            logger.exception("serve flush failed (%d request(s) answered "
                             "Failed)", len(picked))
            t = self._clock()
            return [Failed(rid=r.rid,
                           latency_ms=(t - r.t_submit) * 1e3,
                           reason=repr(e),
                           spans=self._terminal_spans(
                               r.rid, "failed",
                               (t - r.t_submit) * 1e3, t,
                               reason=repr(e))) for r in picked]

    def flush(self, now: Optional[float] = None) -> List[ServeResult]:
        """Force every queued request out (drain), regardless of the
        batching delay — shutdown / test helper."""
        del now  # kept for signature symmetry with poll()
        out: List[ServeResult] = []
        while self._queue:
            out.extend(self._flush_picked())
        self._update_level()
        return out

    # ------------------------------------------------------------- stats

    def stats(self) -> Dict[str, Any]:
        """Host summary: counts, latency percentiles over served
        requests, aggregate pad fraction, queue-depth p95, recompile
        verdicts — the dict the benchmark's cell and the check drill
        read. Percentiles come from the registry's mergeable
        log-bucketed sketches (bounded memory, no full sort); every
        key that predates the sketch migration is preserved as a view,
        plus ``latency_stages_ms`` / ``p99_dominant_stage`` — the
        p99-attribution instrument."""
        lat = self._lat_sketch
        pct = ((lambda p: lat.quantile(p / 100.0)) if lat.count
               else (lambda p: None))
        stages: Dict[str, Dict[str, float]] = {}
        for stage in STAGES:
            sk = self._stage_sketch[stage]
            if not sk.count:
                continue
            stages[stage] = {
                "p50": sk.quantile(0.50), "p95": sk.quantile(0.95),
                "p99": sk.quantile(0.99), "mean": sk.mean,
                "sum": sk.sum, "count": sk.count,
            }
        dominant = (max(stages, key=lambda s: stages[s]["p99"])
                    if stages else None)
        # the unhealthy tail, by outcome: the outcome-labeled siblings
        # _terminal_spans observes (kept OUT of latency_stages_ms so the
        # served partition still sums against served latency)
        unhealthy: Dict[str, Dict[str, float]] = {}
        for key, sk in self._stage_fam.items():
            oc = dict(key).get("outcome")
            if oc and sk.count:
                unhealthy[oc] = {"p95": sk.quantile(0.95),
                                 "p99": sk.quantile(0.99),
                                 "sum": sk.sum, "count": sk.count}
        meta = self._published[2]
        return {
            **self._counts,
            "level": self._level,
            "level_name": LEVELS[self._level],
            "queued_samples": self._queued_samples,
            "latency_p50_ms": pct(50),
            "latency_p95_ms": pct(95),
            "latency_p99_ms": pct(99),
            "latency_stages_ms": stages,
            "p99_dominant_stage": dominant,
            "latency_stages_unhealthy_ms": unhealthy,
            # exemplar join: the slowest retained traces with their
            # per-stage breakdowns — the p99 is no longer just a number,
            # it names requests
            "p99_exemplars": self.traces.exemplars(5),
            "trace": self.traces.stats(),
            "pad_fraction": (self._pad_slots / self._total_slots
                             if self._total_slots else 0.0),
            "queue_depth_p95": (self._qdepth_sketch.quantile(0.95)
                                if self._qdepth_sketch.count else 0.0),
            "rung_flushes": {str(k): v
                             for k, v in sorted(self._rung_flushes.items())
                             if v},
            "warmup_compiles": self.warmup_compiles,
            "steady_state_recompiles": self.steady_recompiles(),
            "est_flush_ms": self._est_s * 1e3,
            "shed_frac_of_submitted": (self._counts["shed"] / self._next_rid
                                       if self._next_rid else 0.0),
            # freshness SLO, next to p99 (None until a snapshot serves)
            "freshness_p95_steps": (self._fresh_steps_sketch.quantile(0.95)
                                    if self._fresh_steps_sketch.count
                                    else None),
            "freshness_p95_s": (self._fresh_s_sketch.quantile(0.95)
                                if self._fresh_s_sketch.count else None),
            "snapshot_version": meta[0] if meta is not None else None,
            "snapshot_train_step": meta[1] if meta is not None else None,
            "freshness_stale": bool(self._stale),
        }


# ------------------------------------------------------------------ audit


def audit_serve_program(rt: ServingRuntime, rung: Optional[int] = None,
                        expected: Optional[Dict[str, Any]] = None,
                        expected_donated: Optional[int] = None):
    """Static census of the compiled serve program (one rung): traces
    the forward abstractly and enforces the forward-only contract —
    id + output exchange and NOTHING else (no grad exchange, no psum,
    never an all_gather), no host interop, no f64. The serving twin of
    ``audit_train_step``; ``tests/test_serving.py`` and the check drill
    run it so a pred_fn that quietly pays training-shaped communication
    per request cannot ship.

    Input donation is reported but not required by default
    (``expected_donated=None``): it is best-effort — a backend that
    cannot alias an int32 id buffer into the f32 predictions drops the
    marker at lowering (the CPU proxy always does), which is a missed
    optimization, not a correctness hole. Pass the donated leaf count
    to enforce it on a backend where aliasing is expected to stick."""
    from ..analysis import audit as audit_mod

    rung = rung or rt.rungs[0]
    args: tuple = (rt.state, rt._zero_inputs(rung))
    if rt.streaming_state is not None:
        args = args + (rt.streaming_state,)
    if expected is None:
        expected = audit_mod.expected_eval_collectives(rt.de)
    return audit_mod.audit_step_fn(
        rt._program(rung)[1], args, world=rt.world,
        dp_input=rt.de.dp_input,
        expected=expected, expected_donated=expected_donated,
        label=f"serve_rung{rung}")


# ---------------------------------------------------- load gen + driving


def synthetic_request(rng: np.random.Generator, table_sizes: Sequence[int],
                      n: int, *, numerical: int = 0,
                      ragged: Sequence[int] = (),
                      ragged_hotness: int = 4,
                      alpha: float = 1.05,
                      id_offset: int = 0,
                      priority: int = 0) -> Request:
    """One seeded Zipfian request: ``n`` samples of power-law ids per
    table (``ragged`` table indices get variable-length id lists up to
    ``ragged_hotness``), plus an ``[n, numerical]`` dense block when
    ``numerical`` > 0. ``id_offset`` shifts ids (streaming-table
    drills feed external-id spaces through it)."""
    from ..utils.data import power_law_ids

    cats: List[Any] = []
    for i, v in enumerate(table_sizes):
        if i in ragged:
            lens = rng.integers(0, ragged_hotness + 1, size=n)
            cats.append([
                list(power_law_ids(rng, v, (int(k),), alpha=alpha)
                     + id_offset) for k in lens])
        else:
            cats.append(np.asarray(
                power_law_ids(rng, v, (n,), alpha=alpha) + id_offset,
                np.int32))
    batch = (np.asarray(rng.normal(size=(n, numerical)), np.float32)
             if numerical else None)
    return Request(cats=cats, batch=batch, priority=priority)


class RealtimeDriver:
    """Wall-clock open-loop load driver on its OWN thread of control.

    The process-isolation layer (ISSUE 18) needs serving load that is
    concurrent with the trainer — not step-paced pumping interleaved
    with train steps — so that ``freshness_p95_s`` measures TRUE
    wall-clock staleness: the driver thread submits and polls in real
    time while the trainer thread publishes snapshots whenever ITS loop
    gets there. Works against anything with the ``submit``/``poll``
    surface: the in-process :class:`ServingRuntime` or the trainer-side
    ``parallel.supervisor.Supervisor`` proxy for an out-of-process
    worker.

    Arrival generation matches :func:`drive` (fixed ``qps``; whole
    seconds named in ``burst_positions`` multiply the rate by
    ``burst_x``; open-loop, so a slow backend piles real pressure onto
    the admission controller instead of stalling the generator).
    ``duration_s=None`` runs until :meth:`stop` — the supervised-outage
    drill kills and restarts the worker mid-stream and needs load that
    simply keeps arriving.

    Usage::

        drv = RealtimeDriver(rt, make_request, qps=200, duration_s=2.0)
        drv.start()
        ...                      # trainer keeps training + publishing
        drv.join()               # waits for the stream + drain
        results = drv.results()
    """

    # state the driver thread and its caller both touch (detlint
    # thread-shared): _results is guarded by _lock; submitted is
    # written once by the driver thread at stream end and read by the
    # caller only after join()
    _THREAD_SHARED = ("_results", "submitted")

    def __init__(self, server, make_request: Callable[[int], Request],
                 qps: float, *, duration_s: Optional[float] = None,
                 burst_positions: Optional[Sequence[int]] = None,
                 burst_x: Optional[float] = None, drain_s: float = 10.0,
                 clock: Callable[[], float] = time.monotonic):
        if burst_positions is None:
            burst_positions = runtime_mod.burst_steps()
        if burst_x is None:
            burst_x = envvars.get_float("DETPU_SERVE_BURST_X")
        self._server = server
        self._make_request = make_request
        self._qps = float(qps)
        self._duration_s = duration_s
        self._burst = set(int(p) for p in burst_positions)
        self._burst_x = float(burst_x)
        self._drain_s = float(drain_s)
        self._clock = clock
        self._results: List[ServeResult] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.submitted = 0

    # ------------------------------------------------------------ control

    def start(self) -> "RealtimeDriver":
        if self._thread is not None:
            raise RuntimeError("driver already started")
        self._thread = threading.Thread(
            target=self._run, name="detpu-serve-driver", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        """Stop generating arrivals; the loop still drains the queue
        (in-flight requests get real answers, not silence)."""
        self._stop.set()

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is None:
            raise RuntimeError("driver never started")
        self._thread.join(timeout)

    def results(self) -> List[ServeResult]:
        """Everything collected so far (the full stream after
        :meth:`join`); safe to call from any thread."""
        with self._lock:
            return list(self._results)

    # --------------------------------------------------------- the loop

    def _collect(self, out: Sequence[ServeResult]) -> None:
        if out:
            with self._lock:
                self._results.extend(out)

    def _run(self) -> None:
        start = self._clock()
        next_t, i = 0.0, 0
        while not self._stop.is_set() and (
                self._duration_s is None or next_t < self._duration_s):
            now = self._clock() - start
            while next_t <= now and (
                    self._duration_s is None or next_t < self._duration_s):
                rej = self._server.submit(self._make_request(i))
                if rej is not None:
                    self._collect([rej])
                i += 1
                rate = self._qps * (self._burst_x
                                    if int(next_t) in self._burst else 1.0)
                next_t += 1.0 / rate
                if self._stop.is_set():
                    break
            self._collect(self._server.poll())
            wait = next_t - (self._clock() - start)
            if wait > 0:
                time.sleep(min(0.0005, wait))  # poll tick, 0.5 ms cap
        self.submitted = i  # thread-local-ok: single write by the driver thread at stream end; callers read after join()
        deadline = self._clock() + self._drain_s
        while (getattr(self._server, "queued_samples", 0)
               and self._clock() < deadline):
            self._collect(self._server.poll())
            time.sleep(0.0005)
        self._collect(self._server.poll())


def drive(rt: ServingRuntime, make_request: Callable[[int], Request],
          qps: float, duration_s: float, *,
          burst_positions: Optional[Sequence[int]] = None,
          burst_x: Optional[float] = None,
          drain_s: float = 10.0) -> List[ServeResult]:
    """Real-time load loop the tools share: submit ``make_request(i)``
    at a fixed ``qps`` for ``duration_s`` seconds, polling the runtime
    between arrivals, then drain.

    ``burst_positions`` (default: :func:`~..utils.runtime.burst_steps`
    — the ``DETPU_FAULT=burst@<pos>`` drill) names whole seconds of the
    stream during which the arrival rate multiplies by ``burst_x``
    (default ``DETPU_SERVE_BURST_X``) — the QPS-spike injection,
    deterministic per position: the same positions always spike, only
    wall-clock jitter differs run to run.

    Since ISSUE 18 this is a thin synchronous wrapper over
    :class:`RealtimeDriver` — ONE arrival/poll loop serves both the
    blocking tools and the concurrent train-while-serve drills — so the
    load runs on the driver's own thread even here (the calling thread
    just waits)."""
    drv = RealtimeDriver(rt, make_request, qps, duration_s=duration_s,
                         burst_positions=burst_positions, burst_x=burst_x,
                         drain_s=drain_s, clock=rt._clock)
    drv.start()
    drv.join()
    return drv.results()
