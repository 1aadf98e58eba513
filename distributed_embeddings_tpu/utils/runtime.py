"""Fault-tolerant runtime layer: process set-up, backend probing from a
child, retries, deadlines, fault injection, and crash-surviving section
records.

Production training stacks treat a killed process mid-checkpoint and a
slow coordinator as normal operating conditions, not fatal errors. The
reference library assumes a healthy NCCL/Horovod world and dies (or
hangs) otherwise; this module is the TPU-native reproduction's answer.

Pieces, all composable and CPU-testable:

* :func:`ensure_compile_cache` — where the persistent compilation cache
  lives; every entry point calls it first.
* :func:`probe_backend` — what the default backend has, asked from a
  watched child process so that the CALLER never touches (and so never
  holds) the accelerator: a chip belongs to one process at a time. For a
  parent that goes on to spawn the child that will use the chip
  (``__graft_entry__.dryrun_multichip``). A process that uses the chip
  itself just calls ``jax.devices()``.
* :func:`retry` — jittered exponential backoff under a deadline and/or an
  attempt budget.
* :func:`deadline` — best-effort wall-clock bound on a code block
  (``SIGALRM``; main thread, Unix). A section stuck inside a C call is
  interrupted when it next returns to Python — pair with
  :class:`SectionRecorder` for hard hangs.
* :func:`fault_point` — env-driven fault injection
  (``DETPU_FAULT=hang:backend,slow:coordinator,die:checkpoint_write``)
  so every failure mode above is exercisable in CPU-only tests.
* :class:`SectionRecorder` — append-only, fsynced JSONL sidecar of
  per-section results, so a process killed mid-run (OOM, SIGKILL, driver
  timeout) leaves every completed section's record parseable on disk
  (``obs.MetricsLogger`` rides this).

This module deliberately does NOT import jax at module scope: importing it
must never touch an accelerator backend.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import os
import random
import re
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import envvars

logger = logging.getLogger(__name__)

FAULT_ENV = "DETPU_FAULT"
_PROBE_MARKER = "DETPU_PROBE "
# repo root: runtime.py -> utils -> distributed_embeddings_tpu -> root
_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ----------------------------------------------------------- compile cache

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


#: jax flags :func:`ensure_compile_cache` sets beside the directory: the
#: cache key then holds each op's metadata, with this checkout's own path
#: taken off the file names so that a checkout that moves still hits
CACHE_KEY_FLAGS = {
    "jax_compilation_cache_include_metadata_in_key": True,
    "jax_hlo_source_file_canonicalization_regex":
        re.escape(_PKG_ROOT + os.sep),
}


def ensure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory;
    every entry point calls this first. Returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, the operator placed the
    cache and the directory is left alone. Otherwise the cache goes to
    ``<checkout>/.jax_cache`` — one fixed path, so the next process finds
    what this one compiled — and the variable is exported so child
    processes inherit the same directory.
    jax reads the variable at import; a jax imported earlier is told
    through its config (the cache opens lazily at the first compile).

    Wherever the cache lies, its key is made to hold the ops' metadata
    (:data:`CACHE_KEY_FLAGS`). jax leaves it out by default, and an
    executable loaded from the cache keeps the metadata it was compiled
    with: after a change to the ``obs.scope`` names alone, a profile
    would show the device ops under the scopes of whichever commit
    filled the cache first, and every reader of scopes would attribute
    by them. A flag the operator's environment sets is left alone."""
    for flag, value in CACHE_KEY_FLAGS.items():
        if flag.upper() not in os.environ:
            os.environ[flag.upper()] = str(value)
            _tell_jax(flag, value)
    path = os.environ.get(COMPILE_CACHE_ENV)
    if path:
        return path
    path = os.path.join(_PKG_ROOT, ".jax_cache")
    os.environ[COMPILE_CACHE_ENV] = path
    _tell_jax("jax_compilation_cache_dir", path)
    return path


def _tell_jax(flag: str, value) -> None:
    """jax reads its flags from the environment at import; one imported
    earlier is told through its config."""
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update(flag, value)


# ------------------------------------------------------------------ errors


class RuntimeFault(RuntimeError):
    """Base class for the fault layer's own errors."""


class BackendUnavailable(RuntimeFault):
    """The accelerator backend could not be probed within its deadline."""

    def __init__(self, msg: str, probe: Optional["BackendProbe"] = None):
        super().__init__(msg)
        self.probe = probe


class DeadlineExceeded(RuntimeFault):
    """A :func:`deadline`-bounded block (or :func:`retry`) ran out of time."""


class CoordinatorUnreachable(RuntimeFault):
    """A multi-process job was expected but the coordinator join kept
    failing — raised by ``bootstrap.initialize`` after its retry budget."""


class CheckpointCorrupt(RuntimeFault):
    """A checkpoint failed validation (missing file, CRC mismatch, torn
    manifest) and no fallback was available."""


class CheckpointMismatch(RuntimeFault):
    """A (whole, CRC-valid) checkpoint does not match the model it is being
    restored into — wrong table count, or a table whose saved vocab/dim
    disagrees with ``de.strategy.global_configs``. Raised by
    ``utils.checkpoint.restore_train_state`` BEFORE any data streams, so a
    config drift surfaces as one clear error instead of a scatter-shape
    traceback deep inside ``set_weights``."""


class InvalidInputError(RuntimeFault):
    """An input batch violated the id contract (negative / out-of-vocab ids,
    or a ragged batch whose claimed lengths overflow its static capacity)
    under the ``'raise'`` invalid-id policy or the opt-in
    ``ragged_overflow_raise`` escalation."""


class NonFiniteLossError(RuntimeFault):
    """The training loss stayed non-finite for K consecutive steps — the
    on-device guard kept skipping updates (params untouched), and the host
    driver escalates instead of spinning on a poisoned stream. The message
    names the last good step."""


class FaultInjected(RuntimeFault):
    """Raised by :func:`fault_point` under ``DETPU_FAULT=raise:<point>``."""


# --------------------------------------------------------- fault injection

# per-process fire counts, keyed by (mode, point): lets a spec carry a
# budget ("fail the first N calls, then pass") for retry-then-succeed tests
_fire_counts: Dict[Tuple[str, str], int] = {}


def reset_fault_counts() -> None:
    """Forget fire-count state (test isolation helper)."""
    _fire_counts.clear()


def _fault_specs() -> List[Tuple[str, str, Optional[str]]]:
    """Parse ``DETPU_FAULT`` (read at every call so tests can flip it at
    runtime): comma-separated ``mode:point[:arg]`` entries."""
    out = []
    for item in (envvars.get(FAULT_ENV) or "").split(","):
        item = item.strip()
        if not item:
            continue
        if (item.startswith(("preempt@", "nan@", "badbatch@", "oovflood@",
                             "burst@", "die@", "hang@"))
                or item == "corrupt@ckpt"):
            continue  # driver/checkpoint-level drills: see preempt_step(),
            # nan_steps(), badbatch_steps(), oovflood_steps(),
            # burst_steps(), die_steps(), hang_steps() and
            # corrupt_ckpt_requested()
        parts = item.split(":", 2)
        if len(parts) < 2:
            logger.warning("ignoring malformed %s entry %r", FAULT_ENV, item)
            continue
        out.append((parts[0], parts[1], parts[2] if len(parts) > 2 else None))
    return out


def preempt_step() -> Optional[int]:
    """Step index of a ``DETPU_FAULT=preempt@<step>`` preemption drill, or
    ``None``. At that step boundary the resilient driver
    (``parallel.resilient.run_resilient``) delivers itself a real SIGTERM —
    exercising the full preemption path (handler, finish the in-flight
    step, checkpoint, resume sentinel) deterministically on CPU. Parsed per
    call like the other fault specs, so tests can flip it at runtime."""
    for item in (envvars.get(FAULT_ENV) or "").split(","):
        item = item.strip()
        if not item.startswith("preempt@"):
            continue
        try:
            return int(item.split("@", 1)[1])
        except ValueError:
            logger.warning("ignoring malformed %s entry %r", FAULT_ENV, item)
    return None


def _at_steps(prefix: str) -> Tuple[int, ...]:
    """Step indices of every ``<prefix>@<step>`` entry in ``DETPU_FAULT``
    (parsed per call like the other fault specs, so tests can flip the
    variable at runtime). Malformed entries warn and are dropped."""
    out = []
    for item in (envvars.get(FAULT_ENV) or "").split(","):
        item = item.strip()
        if not item.startswith(prefix + "@"):
            continue
        try:
            out.append(int(item.split("@", 1)[1]))
        except ValueError:
            logger.warning("ignoring malformed %s entry %r", FAULT_ENV, item)
    return tuple(out)


def nan_steps() -> Tuple[int, ...]:
    """Batch indices of ``DETPU_FAULT=nan@<step>`` drills: at each of
    those stream positions the resilient driver poisons ONE rank's slice
    of the dense batch with a NaN before dispatch, so the poison flows
    through the real forward into the loss and the on-device guard (and,
    after ``DETPU_NANGUARD_K`` in a row, the rollback-and-replay
    recovery) sees an organic non-finite step — the NaN-storm chaos
    drill, deterministic on CPU."""
    return _at_steps("nan")


def badbatch_steps() -> Tuple[int, ...]:
    """Batch indices of ``DETPU_FAULT=badbatch@<step>`` drills: at each
    of those stream positions the resilient driver corrupts the batch's
    categorical ids (scrambled negative/out-of-vocab values) before
    dispatch — the garbled-input chaos drill the ``invalid_id_policy``
    machinery (clamp / drop / raise + ``invalid_id_count``) must absorb
    or escalate."""
    return _at_steps("badbatch")


def oovflood_steps() -> Tuple[int, ...]:
    """Batch indices of ``DETPU_FAULT=oovflood@<pos>`` drills: at each of
    those stream positions the resilient driver replaces the batch's
    categorical ids with a burst of NEVER-BEFORE-SEEN ids before
    dispatch — the non-stationary-traffic chaos drill. A streaming-vocab
    run (``parallel/streaming.py``) must absorb the flood gracefully:
    the novel ids land in their shared hash buckets (no crash, no
    recompile, no hot-row eviction until the sketch gate passes); a
    static-vocab run sees them as out-of-vocab ids the
    ``invalid_id_policy`` machinery clamps/drops/escalates. Targets
    STREAM positions (like ``nan@``/``badbatch@``) so rollback replays
    re-inject deterministically."""
    return _at_steps("oovflood")


def burst_steps() -> Tuple[int, ...]:
    """Positions of ``DETPU_FAULT=burst@<pos>`` drills: at each of those
    positions of a serving request stream (whole seconds since the stream
    started) the load generator multiplies the arrival rate by
    ``DETPU_SERVE_BURST_X`` — the QPS-spike chaos drill the serving
    runtime's admission controller (``parallel/serving.py``) must absorb
    by walking its degradation ladder: shrink the batching delay, then
    shed lowest-priority requests with a typed ``Overloaded`` response —
    never unbounded queue growth, never a crash, and normal service must
    resume once the burst passes. Deterministic per position (the drill
    decides WHEN the spike hits; the stream contents stay the seeded
    Zipfian draw), parsed per call like the other fault specs."""
    return _at_steps("burst")


def die_steps() -> Tuple[int, ...]:
    """Positions of ``DETPU_FAULT=die@<pos>`` drills: at each of those
    positions of a supervised serving worker's request stream (GLOBAL
    ordinals — the supervisor's request counter, monotone across
    restarts, so each position fires at most once and a drill kill is
    followed by clean recovery, not a crash loop) the worker hard-exits
    (``os._exit``, no cleanup handlers — the SIGKILL/OOM-kill
    equivalent). The trainer-side :class:`~..parallel.supervisor
    .Supervisor` must detect the death, answer every in-flight request
    with a typed ``Unavailable``, dump the crash black box on the
    child's behalf, and restart the worker under its backoff budget —
    the crash-containment drill ``make check-isolation`` runs. Parsed
    per call like the other fault specs."""
    return _at_steps("die")


def hang_steps() -> Tuple[int, ...]:
    """Positions of ``DETPU_FAULT=hang@<pos>`` drills: at each of those
    positions of a supervised serving worker's request stream the worker
    stops answering (a long sleep on its control loop — the wedged-
    process equivalent of ``die@``). Heartbeats stop, the supervisor's
    deadline trips, and the worker is killed and restarted exactly like
    a crash — hang detection must never depend on the child
    cooperating. Parsed per call like the other fault specs."""
    return _at_steps("hang")


def corrupt_ckpt_requested() -> bool:
    """True when ``DETPU_FAULT=corrupt@ckpt`` asks the checkpoint layer to
    flip bytes in a just-committed shard file — simulated silent on-disk
    corruption (bit rot, torn external copy) that the CRC manifest must
    catch on the next restore. Parsed per call like the other fault specs,
    so tests can flip it at runtime and corrupt exactly the save they
    choreograph."""
    return any(item.strip() == "corrupt@ckpt"
               for item in (envvars.get(FAULT_ENV) or "").split(","))


def fault_point(point: str) -> None:
    """Named fault-injection hook. No-op unless ``DETPU_FAULT`` targets
    ``point``. Modes:

    * ``hang:<point>[:secs]`` — sleep (default 3600 s): an unreachable
      service that never errors out.
    * ``slow:<point>[:secs]`` — sleep (default 5 s): a degraded service
      that eventually responds.
    * ``raise:<point>[:count]`` — raise :class:`FaultInjected`; with a
      count, only the first ``count`` calls raise (then the point passes) —
      the retry-then-succeed scenario.
    * ``die:<point>`` — ``os._exit(17)``: hard process death (SIGKILL /
      OOM-kill equivalent), no cleanup handlers run.
    """
    for mode, p, arg in _fault_specs():
        if p != point:
            continue
        key = (mode, p)
        n = _fire_counts.get(key, 0)
        if mode == "raise" and arg is not None and n >= int(arg):
            continue  # budget exhausted: the point now passes
        _fire_counts[key] = n + 1
        from . import obs  # lazy: obs imports this module at its top

        obs.record_fault(point)
        if mode == "hang":
            time.sleep(float(arg) if arg else 3600.0)
        elif mode == "slow":
            time.sleep(float(arg) if arg else 5.0)
        elif mode == "raise":
            raise FaultInjected(f"injected fault at {point!r}")
        elif mode == "die":
            logger.error("DETPU_FAULT: dying at %r", point)
            os._exit(17)
        else:
            logger.warning("ignoring unknown %s mode %r", FAULT_ENV, mode)


# ------------------------------------------------------------------- retry


def retry(fn: Callable[[], Any], *,
          deadline_s: Optional[float] = None,
          max_attempts: Optional[int] = None,
          base_delay_s: float = 0.5,
          max_delay_s: float = 8.0,
          retry_on: Tuple[type, ...] = (Exception,),
          describe: str = "operation") -> Any:
    """Call ``fn()`` until it succeeds, with jittered exponential backoff.

    Stops when either budget runs out: ``deadline_s`` (wall clock over all
    attempts, including backoff sleeps) or ``max_attempts``. At least one
    attempt always runs. On exhaustion re-raises the last error (wrapped in
    :class:`DeadlineExceeded` when the deadline was the binding budget).
    """
    if deadline_s is None and max_attempts is None:
        max_attempts = 3
    start = time.monotonic()
    attempt = 0
    while True:
        attempt += 1
        try:
            return fn()
        except retry_on as e:  # noqa: PERF203 - retry loop
            if max_attempts is not None and attempt >= max_attempts:
                raise
            delay = min(max_delay_s, base_delay_s * (2 ** (attempt - 1)))
            delay *= 0.5 + random.random()  # jitter in [0.5x, 1.5x)
            if deadline_s is not None:
                elapsed = time.monotonic() - start
                if elapsed + delay >= deadline_s:
                    raise DeadlineExceeded(
                        f"{describe} still failing after {attempt} attempt(s)"
                        f" / {elapsed:.1f}s (deadline {deadline_s}s): "
                        f"{e!r}") from e
            logger.warning("%s failed (attempt %d): %r — retrying in %.2fs",
                           describe, attempt, e, delay)
            from . import obs  # lazy: obs imports this module at its top

            obs.record_retry(describe)
            time.sleep(delay)


# ---------------------------------------------------------------- deadline


@contextlib.contextmanager
def deadline(seconds: Optional[float], label: str = "block"):
    """Best-effort wall-clock bound: raises :class:`DeadlineExceeded` from
    inside the block after ``seconds``.

    Implemented with ``SIGALRM`` (``setitimer``), so it only engages on the
    main thread of a Unix process; elsewhere (or with ``seconds`` falsy) it
    is a transparent no-op. The alarm interrupts Python bytecode and most
    blocking syscalls (``time.sleep``, socket waits); code stuck inside a
    non-signal-aware C call (e.g. a wedged XLA compile) is only interrupted
    when it returns to Python — the layer above should pair this with
    crash-surviving records (:class:`SectionRecorder`) for those.
    """
    if (not seconds
            or not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def _alarm(signum, frame):
        raise DeadlineExceeded(f"{label} exceeded {seconds}s deadline")

    old_handler = signal.signal(signal.SIGALRM, _alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, old_handler)


# ----------------------------------------------------------- backend probe


@dataclasses.dataclass(frozen=True)
class BackendProbe:
    """Verdict of one time-boxed backend probe."""

    ok: bool
    platform: Optional[str]
    device_count: int
    elapsed_s: float
    error: Optional[str] = None

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _probe_child() -> None:
    """Body of the probe subprocess: the actual first backend touch.

    ``fault_point('backend')`` runs BEFORE jax initializes any backend, so
    ``DETPU_FAULT=hang:backend`` simulates a backend that never comes up.
    """
    fault_point("backend")
    import jax

    out = {"platform": jax.default_backend(),
           "device_count": jax.device_count()}
    sys.stdout.write(_PROBE_MARKER + json.dumps(out) + "\n")
    sys.stdout.flush()


def probe_backend(timeout_s: float = 120.0,
                  platform: Optional[str] = None) -> BackendProbe:
    """What the default backend has, asked from a watched child process
    with a hard timeout — the caller's own backend stays untouched.

    Returns a :class:`BackendProbe` — never raises and never hangs past
    ``timeout_s`` (plus child-kill slack). ``platform`` forces the child's
    ``JAX_PLATFORMS`` (e.g. ``"cpu"``); by default the child inherits this
    process's environment and probes whatever backend a bare ``import jax;
    jax.device_count()`` would have touched here.
    """
    env = dict(os.environ)
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    code = (f"import sys; sys.path.insert(0, {_PKG_ROOT!r}); "
            "from distributed_embeddings_tpu.utils.runtime import "
            "_probe_child; _probe_child()")
    start = time.monotonic()
    # own session/process group: an accelerator runtime may fork helpers
    # that inherit the stdout/stderr pipes — killing only the direct child
    # would leave communicate() blocked on the open pipe (the exact hang
    # this function exists to prevent), so on timeout the whole group dies
    proc = subprocess.Popen(
        [sys.executable, "-c", code], env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            proc.kill()
        try:  # reap; bounded in case a grandchild survived the killpg
            proc.communicate(timeout=5)
        except subprocess.TimeoutExpired:
            pass
        elapsed = time.monotonic() - start
        logger.warning("backend probe timed out after %.1fs", elapsed)
        return BackendProbe(ok=False, platform=None, device_count=0,
                            elapsed_s=elapsed,
                            error=f"probe timed out after {timeout_s}s")
    elapsed = time.monotonic() - start
    for line in reversed((stdout or "").splitlines()):
        if line.startswith(_PROBE_MARKER):
            info = json.loads(line[len(_PROBE_MARKER):])
            return BackendProbe(ok=True, platform=info["platform"],
                                device_count=int(info["device_count"]),
                                elapsed_s=elapsed)
    tail = (stderr or stdout or "").strip()[-500:]
    return BackendProbe(ok=False, platform=None, device_count=0,
                        elapsed_s=elapsed,
                        error=f"probe child rc={proc.returncode}: {tail}")


# ------------------------------------------- crash-surviving section records


class SectionRecorder:
    """Append-only JSONL sidecar of per-section results.

    Every :meth:`record` appends one JSON line and fsyncs it, so a process
    killed at ANY later point (SIGKILL, OOM, driver timeout) leaves every
    previously completed section's record intact and parseable. A torn
    final line (killed mid-write) is skipped by :meth:`load`.
    """

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)

    def record(self, section: str, **fields: Any) -> Dict[str, Any]:
        rec: Dict[str, Any] = {"section": section, **fields}
        line = json.dumps(rec, default=_jsonable)
        with open(self.path, "a", encoding="utf-8") as f:
            f.write(line + "\n")
            f.flush()
            os.fsync(f.fileno())
        return rec

    @staticmethod
    def load(path: str) -> List[Dict[str, Any]]:
        """Parse a sidecar, tolerating a torn trailing line."""
        out: List[Dict[str, Any]] = []
        if not os.path.exists(path):
            return out
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    logger.warning("skipping torn sidecar line in %s", path)
        return out


def _jsonable(x: Any) -> Any:
    """Best-effort JSON coercion for section payloads (numpy scalars AND
    arrays, tuples of floats, dataclasses)."""
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        return dataclasses.asdict(x)
    if hasattr(x, "tolist"):  # numpy/jax scalar or array, any shape
        return x.tolist()
    if isinstance(x, (set, tuple)):
        return list(x)
    return repr(x)
