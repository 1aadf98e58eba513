"""Online learning runtime: concurrent train-and-serve in ONE process
against ONE set of tables, bridged by RCU snapshot publication.

Production recommenders read the model WHILE clicks train it. Before
this module the two halves existed separately — the resilient training
loop (``parallel/resilient.py``: streaming-vocab tables, nan-guard,
rollback-and-replay, preemption/auto-resume) and the deadline-bounded
coalescer (``parallel/serving.py``) — but serving only ever answered
from frozen snapshots. :class:`OnlineRuntime` runs both interleaved in
one process, connected by a snapshot/versioning layer::

       train step t  ──donates──▶  state_{t+1} ──┐
            ▲                                    │ SnapshotPublisher
            │ resilient loop                     │ (RCU copy, version v)
            │ (rollback / preempt /              ▼
            │  quarantine / resume)     ┌─ published view v ─┐
            │                           │ params (copy)      │
       on_step_aux pump ───────────────▶│ streaming (copy)   │
        publish → submit → poll         │ frozen opt shapes  │
                                        └────────┬───────────┘
                                                 │ install_snapshot
                                                 ▼  (atomic swap)
                                    ServingRuntime compiled ladder
                                    (flush reads ONE view: no torn
                                     reads; same shapes: 0 recompiles)

**Why RCU double-buffering, not a checkpoint-ring handoff.** The train
step donates its state every step (``donate_argnums=(0, ...)``), so any
view that outlives the step must be a genuine copy — and a device-side
elementwise copy (:func:`~.trainer.clone_pytree`) is orders of
magnitude cheaper than a disk round-trip through the checkpoint ring,
preserves shardings bitwise (the serving ladder's jit cache keys match
across versions → 0 steady-state recompiles), and decouples publication
cadence from checkpoint cadence. The serving view never reads optimizer
slots (the eval forward DCEs them, but its shard_map specs still
require the full :class:`~.trainer.HybridTrainState`), so the publisher
clones the optimizer state ONCE and shares those frozen buffers across
every version: steady-state footprint is two param copies (published +
in-flight during a publish) plus one opt-shaped slab —
``analysis/plan_audit.py`` bills exactly this as
``RankBudget.snapshot_bytes``.

**Consistency contracts** (drilled by ``tools/check_online.py`` =
``make check-online`` and pinned bitwise in ``tests/test_online.py``):

* *No torn reads* — a serve flush observes exactly one version, never a
  mid-publish mix: the publisher swaps one reference between polls and
  the flush reads it once.
* *Monotone versions* — versions only grow, across publication,
  preemption/resume (the ``<ckpt>.online.json`` sidecar persists the
  counter) and rollback (train_step may rewind; the version never
  does).
* *Freshness SLO* — per-response staleness in steps and seconds rides
  :meth:`~.serving.ServingRuntime.stats` next to p99; when publication
  falls behind ``DETPU_FRESHNESS_MAX_STEPS`` the server sheds
  serve-side load (typed, via the existing degradation ladder) before
  training is ever blocked on publication.
* *Training unperturbed* — the training trajectory is
  checkpoint-CRC-identical to the same run WITHOUT concurrent serving:
  publishes copy, serves read copies, and the published-version record
  lives in a sidecar BESIDE the checkpoint directory (never inside —
  ``meta.json`` manifests aux keys, so an in-checkpoint record would
  break CRC identity).
* *Robustness composition* — preemption mid-serve checkpoints the
  training state while the sidecar holds the published version
  (a consistent pair: the sidecar's step never exceeds the saved
  step's publish point); auto-resume restores the state, continues the
  version counter, and republishes immediately; rollback-and-replay
  rewinds the publisher with the ring candidate (the next
  ``maybe_publish`` sees ``state.step`` behind the published step and
  republishes at once).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional

from ..utils import envvars, mplane, obs
from ..utils import runtime as runtime_mod
from .resilient import ResilientResult, _atomic_json, run_resilient
from .serving import RealtimeDriver, Request, ServeResult, ServingRuntime
from .trainer import HybridTrainState, clone_pytree

logger = logging.getLogger(__name__)

#: Every Nth pump rings a serving ``stats()`` snapshot into the flight
#: recorder — cheap (sketch reads, no sorts) but not free, so not every
#: step.
_STATS_RING_EVERY = 10


def online_sidecar_path(checkpoint_dir: str) -> str:
    """The publisher's version-record sidecar, BESIDE the checkpoint
    directory (``<dir>.online.json``). Never inside it: the checkpoint
    manifest must stay byte-identical to a run without concurrent
    serving (the CRC-identity contract ``make check-online`` compares),
    and ``meta.json`` records every aux key it carries."""
    return checkpoint_dir.rstrip(os.sep) + ".online.json"


class OnlineConfig:
    """Static online-runtime policy: publication cadence + freshness
    SLO. Defaults come from the ``DETPU_ONLINE_*`` /
    ``DETPU_FRESHNESS_*`` env knobs."""

    def __init__(self, publish_every_steps: Optional[int] = None,
                 freshness_max_steps: Optional[int] = None,
                 freshness_max_s: Optional[float] = None):
        self.publish_every_steps = int(
            publish_every_steps if publish_every_steps is not None
            else envvars.get_int("DETPU_ONLINE_PUBLISH_STEPS"))
        if self.publish_every_steps < 1:
            raise ValueError("publish_every_steps must be >= 1")
        self.freshness_max_steps = int(
            freshness_max_steps if freshness_max_steps is not None
            else envvars.get_int("DETPU_FRESHNESS_MAX_STEPS"))
        self.freshness_max_s = float(
            freshness_max_s if freshness_max_s is not None
            else envvars.get_float("DETPU_FRESHNESS_MAX_S"))


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """One published, immutable table view: fresh buffers, one version.

    ``state`` is a full :class:`~.trainer.HybridTrainState` (the eval
    shard_map specs require it) whose param leaves are copies of the
    training state at ``train_step`` and whose optimizer leaves are the
    publisher's shared frozen buffers (never read by the forward)."""

    version: int
    train_step: int
    published_t: float
    state: Any
    streaming_state: Any = None


class SnapshotPublisher:
    """RCU writer side: copy the live (donated-every-step) training
    state into fresh buffers and install the copy atomically into a
    :class:`~.serving.ServingRuntime`.

    Single-threaded like the server itself: publishes happen between
    polls (the online runtime's step pump), so the atomic-swap +
    read-once discipline in :meth:`~.serving.ServingRuntime
    .install_snapshot` / ``_run_flush`` makes torn reads impossible by
    construction. At most two param copies are ever live (the published
    view and the in-flight one during a publish) — the double-buffer
    footprint ``plan_audit`` bills as ``snapshot_bytes``.

    ``sidecar_path`` (``<ckpt>.online.json``) persists the version
    counter and last-published step across preemption/resume so
    versions stay monotone for the lifetime of the checkpointed run;
    ``resume=False`` starts a fresh lineage (a stale sidecar in a dirty
    directory is deleted, mirroring the quarantine-ledger policy)."""

    def __init__(self, serving: ServingRuntime, *,
                 config: Optional[OnlineConfig] = None,
                 sidecar_path: Optional[str] = None,
                 resume: bool = True,
                 clock: Callable[[], float] = time.monotonic):
        self.serving = serving
        self.config = config or OnlineConfig()
        self.sidecar_path = sidecar_path
        self._clock = clock
        self._version = 0
        self._last_step: Optional[int] = None
        self._opt_frozen = None
        self.published: Optional[Snapshot] = None
        serving.set_freshness_slo(self.config.freshness_max_steps,
                                  self.config.freshness_max_s)
        if sidecar_path and os.path.isfile(sidecar_path):
            if resume:
                try:
                    with open(sidecar_path, encoding="utf-8") as f:
                        doc = json.load(f)
                    self._version = int(doc.get("version", 0))
                    logger.info(
                        "online publisher: resumed version counter at %d "
                        "(last published step %s) from %s", self._version,
                        doc.get("train_step"), sidecar_path)
                except (OSError, ValueError):
                    logger.warning(
                        "online publisher: unreadable sidecar %s — "
                        "version counter restarts (versions stay "
                        "monotone within this run only)", sidecar_path)
            else:
                # fresh lineage over a dead run's record: a later resume
                # of THIS run must not inherit the old run's counter
                os.remove(sidecar_path)

    @property
    def version(self) -> int:
        """Last published version (0 = nothing published yet)."""
        return self._version

    def _frozen_opt(self, state: HybridTrainState):
        # the serve forward never reads optimizer slots (DCE'd), but the
        # eval shard_map specs require the full state — clone them ONCE
        # and share the buffers across every published version: RCU
        # footprint stays at 2x params + 1x opt instead of 2x (params+opt)
        if self._opt_frozen is None:
            self._opt_frozen = clone_pytree(
                (state.emb_opt_state, state.dense_opt_state))
        return self._opt_frozen

    def warm(self, state: HybridTrainState, streaming_state=None) -> None:
        """Compile the copy programs against (template-shaped) state and
        discard the result — so the publisher's one-time compiles land
        BEFORE :meth:`~.serving.ServingRuntime.warmup` marks the
        steady-state recompile baseline."""
        self._frozen_opt(state)
        clone_pytree((state.emb_params, state.dense_params, state.step))
        if streaming_state is not None:
            clone_pytree(streaming_state)

    def publish(self, state: HybridTrainState, streaming_state=None, *,
                train_step: Optional[int] = None,
                now: Optional[float] = None) -> Snapshot:
        """Copy + install one new version unconditionally. The copies
        are real device buffers (:func:`~.trainer.clone_pytree`), so the
        training step may donate the sources immediately after."""
        now = self._clock() if now is None else now
        step = int(state.step) if train_step is None else int(train_step)
        emb_opt, dense_opt = self._frozen_opt(state)
        emb_p, dense_p, step_a = clone_pytree(
            (state.emb_params, state.dense_params, state.step))
        snap_state = HybridTrainState(
            emb_params=emb_p, emb_opt_state=emb_opt,
            dense_params=dense_p, dense_opt_state=dense_opt, step=step_a)
        stream_copy = (clone_pytree(streaming_state)
                       if streaming_state is not None else None)
        snap = Snapshot(version=self._version + 1, train_step=step,
                        published_t=now, state=snap_state,
                        streaming_state=stream_copy)
        self.serving.install_snapshot(
            snap_state, stream_copy, version=snap.version,
            train_step=step, published_t=now, now=now)
        # the retired view's buffers free when the last reference drops
        # (served predictions are already materialized numpy slices)
        self._version = snap.version
        self._last_step = step
        self.published = snap
        if self.sidecar_path:
            _atomic_json(self.sidecar_path, {
                "version": snap.version, "train_step": step,
                "published_t": now, "time": time.time()})
        return snap

    def maybe_publish(self, state: HybridTrainState, streaming_state=None,
                      *, now: Optional[float] = None) -> Optional[Snapshot]:
        """Cadence-gated publish; also the rollback rewind point: when
        ``state.step`` is BEHIND the published step, training rolled
        back to a ring candidate underneath the published view —
        republish immediately (version still advances; versions are
        monotone even when train_step rewinds) so serving never answers
        from a future the trainer abandoned. Off-cadence calls still
        notify the server of training progress (the freshness clock)."""
        step = int(state.step)
        if self._last_step is not None and step < self._last_step:
            logger.warning(
                "online publisher: training rewound under the published "
                "view (step %d < published %d) — republishing the ring-"
                "candidate state as v%d", step, self._last_step,
                self._version + 1)
            obs.record_event("snapshot_rewound", from_step=self._last_step,
                             to_step=step, version=self._version + 1)
            return self.publish(state, streaming_state, now=now)
        if (self.published is None
                or step - self._last_step >= self.config.publish_every_steps):
            return self.publish(state, streaming_state, now=now)
        self.serving.note_train_step(step, now=now)
        return None


def warm_checkpoint_io(de, state, streaming_state=None) -> None:
    """Compile the checkpoint writer's device->host fetch programs (and
    the streaming encoder's gathers) without writing anything.

    The resilient loop's FIRST ring save jit-compiles
    ``DistributedEmbedding.get_table``'s chunked row fetches — one
    program per slab component — and that save lands steps AFTER the
    serving ladder's warmup marks the steady-state recompile baseline.
    Those are one-time compiles, not retraces; the online runtime warms
    them up front so ``steady_state_recompiles == 0`` keeps meaning
    "nothing retraced", with checkpointing running concurrently."""
    from ..utils.checkpoint import _components

    n_tables = len(de.strategy.global_configs)
    for t in range(n_tables):
        de.get_table(state.emb_params, t, all_ranks=False)
    slabs, _ = _components(state.emb_opt_state, state.emb_params)
    for comp in slabs.values():
        for t in range(n_tables):
            de.get_table(comp, t, all_ranks=False)
    if streaming_state is not None:
        from . import streaming as streaming_mod
        streaming_mod.encode_state(de, streaming_state)


@dataclasses.dataclass
class OnlineResult:
    """What one :meth:`OnlineRuntime.run` produced: the training result,
    every typed serve response, the server's final stats, and where
    publication ended."""

    train: ResilientResult
    serve_results: List[ServeResult]
    serve_stats: Dict[str, Any]
    published_version: int
    published_train_step: Optional[int]


class OnlineRuntime:
    """Concurrent train-and-serve: the resilient training loop and the
    serving coalescer interleaved in one process, one set of tables.

    Usage::

        rt = ServingRuntime(de, pred_fn, state, mesh=mesh,
                            streaming=(scfg, sstate), config=...)
        online = OnlineRuntime(rt, config=OnlineConfig(),
                               checkpoint_dir=ckpt)
        res = online.run(step_fn, state, data, de=de, until_step=100,
                         warmup_template=(tmpl_cats, tmpl_batch),
                         make_request=gen, requests_per_step=4,
                         streaming_state=sstate, emb_optimizer=...,
                         dense_tx=...)

    The serve side is pumped from the training loop's ``on_step_aux``
    hook, once per completed step: publish when due →
    (first call only) warm the serving ladder → submit this step's
    arrivals → poll. Ordering matters twice over: the publisher's copy
    compiles and the ladder warmup both land AFTER the train step's own
    compile and BEFORE the steady-state baseline, so
    ``steady_state_recompiles`` stays 0 across any mix of training,
    publication, rollback and serving; and no flush ever runs before
    the first publication, so every response carries a version.

    Serve arrivals come in one of two modes. **Step-paced** (default):
    ``make_request(i)`` is submitted ``requests_per_step`` times per
    train step — multiplied by ``burst_x`` (default
    ``DETPU_SERVE_BURST_X``) at the ``DETPU_FAULT=burst@<step>`` drill
    positions — which keeps chaos drills and CRC-identity comparisons
    reproducible. **Real-time** (``realtime_qps=...``): a
    :class:`~.serving.RealtimeDriver` on its own thread of control
    submits and polls an open-loop Poisson-free arrival schedule
    against the live publisher while training runs, so
    ``freshness_p95_s`` measures WALL-CLOCK staleness under true
    concurrency instead of step-paced pumping. In real-time mode the
    pump only publishes (the driver owns submit/poll), the driver
    starts after the first publication + ladder warmup (every response
    carries a version; the steady baseline predates traffic), and
    burst drill positions are seconds of stream, not step ordinals.

    Training never blocks on serving: the pump is strictly post-step
    host work, publication is a bounded device copy, and when it still
    falls behind the freshness SLO the SERVER sheds load (typed,
    ``reason="stale_snapshot"``) rather than the trainer waiting."""

    def __init__(self, serving: ServingRuntime, *,
                 config: Optional[OnlineConfig] = None,
                 checkpoint_dir: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.serving = serving
        self.config = config or OnlineConfig()
        self.checkpoint_dir = checkpoint_dir
        self.sidecar_path = (online_sidecar_path(checkpoint_dir)
                             if checkpoint_dir else None)
        self._clock = clock
        self.publisher: Optional[SnapshotPublisher] = None

    def run(self, step_fn: Callable, state, data, *, de,
            warmup_template=None,
            make_request: Optional[Callable[[int], Request]] = None,
            requests_per_step: int = 0,
            realtime_qps: Optional[float] = None,
            realtime_drain_s: float = 30.0,
            burst_x: Optional[float] = None,
            resume: bool = True,
            on_step: Optional[Callable] = None,
            **resilient_kwargs) -> OnlineResult:
        """Train ``step_fn`` over ``data`` under the resilient driver
        while publishing snapshots and serving between steps.

        ``warmup_template``: a ``(cats, batch)`` template request
        compiled into the serving ladder on the first pump (skip it if
        the runtime was already warmed). ``**resilient_kwargs`` pass
        through to :func:`~.resilient.run_resilient` (``until_step``,
        ``emb_optimizer``, ``dense_tx``, ``streaming_state``,
        ``checkpoint_every_steps``, ...); ``checkpoint_dir`` and
        ``resume`` come from this runtime so the publisher sidecar and
        the checkpoint agree on lineage.

        ``realtime_qps`` switches serve load to the wall-clock open
        loop (see the class docstring); it is mutually exclusive with
        ``requests_per_step`` and requires ``make_request``. The driver
        is stopped and drained (up to ``realtime_drain_s``) after
        training returns, and its typed responses land in
        ``serve_results`` alongside any shed submissions."""
        if "checkpoint_dir" in resilient_kwargs:
            raise ValueError(
                "pass checkpoint_dir to OnlineRuntime(...), not run() — "
                "the publisher sidecar must share the checkpoint lineage")
        if realtime_qps is not None:
            if requests_per_step:
                raise ValueError(
                    "pick ONE load mode: step-paced requests_per_step "
                    "or wall-clock realtime_qps, not both")
            if make_request is None:
                raise ValueError("realtime_qps requires make_request")
            if realtime_qps <= 0:
                raise ValueError("realtime_qps must be positive")
        self.publisher = SnapshotPublisher(
            self.serving, config=self.config,
            sidecar_path=self.sidecar_path, resume=resume,
            clock=self._clock)
        burst = set(runtime_mod.burst_steps())
        bx = (float(burst_x) if burst_x is not None
              else envvars.get_float("DETPU_SERVE_BURST_X"))
        results: List[ServeResult] = []
        seq = {"i": 0}
        driver: Dict[str, Optional[RealtimeDriver]] = {"drv": None}

        def _pump(cur, loss, metrics, state_now, telem, stream):
            now = self._clock()
            self.publisher.maybe_publish(state_now, stream, now=now)
            rec = mplane.flight_recorder()
            if rec is not None and cur % _STATS_RING_EVERY == 0:
                # ring a serving-stats snapshot so a post-mortem shows
                # the serve plane's recent history, not just training's
                rec.note_stats(self.serving.stats())
                # ... and the newly retained request traces, so a crash
                # dump carries the exact slow/failed requests that led
                # up to it (drain_new is an exactly-once cursor)
                traces = getattr(self.serving, "traces", None)
                if traces is not None:
                    for tr in traces.drain_new():
                        rec.note_trace(tr)
            if warmup_template is not None and not self.serving._warm:
                # after the train step's compile, before any traffic:
                # the steady-state recompile baseline includes every
                # one-time compile in the process
                self.serving.warmup(warmup_template)
            if realtime_qps is not None:
                if (driver["drv"] is None
                        and self.publisher.published is not None):
                    # first pump: a snapshot exists and the ladder is
                    # warm — hand the serve plane its own thread of
                    # control; from here on the pump only publishes
                    drv = RealtimeDriver(
                        self.serving, make_request, realtime_qps,
                        duration_s=None, burst_x=bx,
                        drain_s=realtime_drain_s, clock=self._clock)
                    driver["drv"] = drv
                    drv.start()
            elif make_request is not None and requests_per_step > 0:
                n = int(round(requests_per_step
                              * (bx if cur in burst else 1.0)))
                for _ in range(n):
                    req = make_request(seq["i"])
                    seq["i"] += 1
                    rej = self.serving.submit(req)
                    if rej is not None:
                        results.append(rej)
            if realtime_qps is None:
                results.extend(self.serving.poll())
            if on_step is not None:
                return on_step(cur, loss, metrics, state_now)
            return None

        # publisher copy programs and checkpoint-writer fetch programs
        # compile against the entry state's shapes (identical to the
        # restored state's — restore is shape-preserving), before the
        # steady baseline exists at all
        self.publisher.warm(state,
                            resilient_kwargs.get("streaming_state"))
        if self.checkpoint_dir is not None:
            warm_checkpoint_io(de, state,
                               resilient_kwargs.get("streaming_state"))
        try:
            train = run_resilient(
                step_fn, state, data, de=de,
                checkpoint_dir=self.checkpoint_dir, resume=resume,
                on_step_aux=_pump, **resilient_kwargs)
        except BaseException:
            drv = driver["drv"]
            if drv is not None:
                drv.stop()
                drv.join(timeout=realtime_drain_s + 5.0)
            raise
        if not train.preempted:
            # final publish + drain: the freshest completed state serves
            # the tail (so a served AUC tracks the offline
            # final model)
            if self.publisher._last_step != train.step:
                self.publisher.publish(train.state, train.streaming)
        drv = driver["drv"]
        if drv is not None:
            # stop AFTER the final publish so the driver's drain serves
            # the tail from the freshest completed state
            drv.stop()
            drv.join(timeout=realtime_drain_s + 5.0)
            results.extend(drv.results())
        if not train.preempted and realtime_qps is None:
            results.extend(self.serving.flush())
        return OnlineResult(
            train=train, serve_results=results,
            serve_stats=self.serving.stats(),
            published_version=self.publisher.version,
            published_train_step=self.publisher._last_step)
