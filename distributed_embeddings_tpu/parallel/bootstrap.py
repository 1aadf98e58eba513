"""Multi-host process bootstrap — the ``hvd.init`` equivalent.

The reference bootstraps one Horovod process per GPU and reads
``hvd.size/rank/local_rank`` everywhere (``dist_model_parallel.py:238-241``;
``examples/dlrm/main.py:152-157``). The TPU-native shape is different: one
process per *host*, all hosts joined into a single JAX runtime by
``jax.distributed.initialize``, after which every process sees the global
device list and SPMD programs span the pod — collectives ride ICI within a
slice and DCN across slices with no further involvement from this layer.

Launch recipe (v5e-16, 4 hosts x 4 chips):

    # on every host, same binary:
    import distributed_embeddings_tpu.parallel.bootstrap as bootstrap
    bootstrap.initialize()          # TPU pods: auto-detected, no args
    mesh = bootstrap.global_mesh()  # 16 devices, axis "data"

On clusters without TPU metadata (or for CPU multi-process tests), pass
``coordinator_address="host0:port", num_processes=N, process_id=i``
explicitly, mirroring ``jax.distributed.initialize``.
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Sequence

import jax
import numpy as np

logger = logging.getLogger(__name__)


def _cluster_expected() -> bool:
    """True when the environment clearly describes a multi-process job — in
    that case a failed join must raise, not silently degrade into N
    independent single-host runs (each believing it is chief)."""
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if hosts and len(hosts.split(",")) > 1:
        return True
    for var in ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
                "MEGASCALE_COORDINATOR_ADDRESS"):
        if os.environ.get(var):
            return True
    for var in ("SLURM_NTASKS", "OMPI_COMM_WORLD_SIZE", "PMI_SIZE"):
        v = os.environ.get(var)
        if v and v.isdigit() and int(v) > 1:
            return True
    return False


def _join_runtime(coordinator_address: Optional[str],
                  num_processes: Optional[int],
                  process_id: Optional[int],
                  local_device_ids: Optional[Sequence[int]]) -> None:
    """One join attempt (separated out so tests can stub it and
    ``DETPU_FAULT=slow:coordinator`` / ``raise:coordinator`` can target
    it without a real cluster)."""
    from ..utils import runtime

    runtime.fault_point("coordinator")
    if jax.distributed.is_initialized():
        # an earlier attempt that "failed" late (e.g. deadline fired on the
        # way out) actually completed — initialize() is not idempotent, so
        # re-invoking it would burn the whole retry budget on its
        # already-initialized guard
        return
    try:
        if coordinator_address is None and num_processes is None:
            jax.distributed.initialize()
        else:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                local_device_ids=local_device_ids)
    except Exception:
        # clear any partially-set global state so the NEXT attempt really
        # rejoins instead of tripping the only-called-once guard. Bounded
        # by its own fresh deadline: the outer per-attempt alarm has
        # already fired by the time we get here, and a shutdown tearing
        # down a half-established connection can itself block
        try:
            with runtime.deadline(10.0, label="distributed shutdown"):
                jax.distributed.shutdown()
        except Exception:  # noqa: BLE001 - nothing (usable) was set up
            pass
        raise


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids: Optional[Sequence[int]] = None,
               timeout_s: Optional[float] = None,
               retries: int = 2) -> bool:
    """Join the multi-process JAX runtime; safe to call more than once.

    With no arguments, a join is attempted only when the environment
    announces a multi-process job (:func:`_cluster_expected`: TPU pod
    worker hostnames, a coordinator address, Slurm/MPI task counts); it
    then relies on ``jax.distributed.initialize``'s cluster auto-detection.
    A plain single-process run returns False WITHOUT touching
    ``jax.distributed`` — on a sealed single-host TPU machine the
    auto-detection has nothing to find and may wait on a metadata service.
    Returns True if this call performed the initialization, False if it
    was already done or no cluster is expected.

    Fault tolerance (``utils.runtime``): each join attempt is bounded by
    ``timeout_s`` (best-effort ``SIGALRM`` deadline; ``None`` = no bound)
    and a failed attempt is retried up to ``retries`` times with jittered
    backoff — a *slow* coordinator is a normal operating condition. After
    the retry budget the error is re-raised as
    :class:`~..utils.runtime.CoordinatorUnreachable` — a pod must never
    silently fall apart into independent single-host trainings (each
    believing it is chief).
    """
    if jax.distributed.is_initialized():
        return False
    if not (coordinator_address is not None or num_processes is not None
            or _cluster_expected()):
        return False
    from ..utils import runtime

    def join_once():
        with runtime.deadline(timeout_s, label="coordinator join"):
            _join_runtime(coordinator_address, num_processes, process_id,
                          local_device_ids)

    import time

    from ..utils import obs

    t0 = time.monotonic()
    retries_before = obs.counters().get("runtime_retries", 0)
    try:
        runtime.retry(join_once, max_attempts=retries + 1,
                      describe="coordinator join")
    except Exception as e:
        raise runtime.CoordinatorUnreachable(
            f"cluster expected (coordinator={coordinator_address!r}, "
            f"num_processes={num_processes!r}, detected="
            f"{_cluster_expected()}) but the runtime join kept failing "
            f"after {retries + 1} attempt(s): {e!r}") from e
    # runtime.retry already bumped the global retry counter per attempt;
    # mirror the delta into a bootstrap-specific counter so a metrics
    # record can distinguish "the coordinator was slow" from other retries
    delta = obs.counters().get("runtime_retries", 0) - retries_before
    if delta:
        obs.counter_inc("bootstrap_retries", delta)
    _log_join_success(coordinator_address, time.monotonic() - t0)
    return True


def _log_join_success(coordinator_address: Optional[str],
                      elapsed_s: float) -> None:
    """One INFO line on the success path (the failure paths already log):
    which coordinator, which process slot, how long the join took. Called
    once per successful :func:`initialize`, never per retry attempt."""
    addr = (coordinator_address
            or os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("COORDINATOR_ADDRESS")
            or "auto-detected")
    try:
        pidx, pcnt = jax.process_index(), jax.process_count()
    except Exception:  # noqa: BLE001 - logging must never fail the join
        pidx, pcnt = -1, -1
    logger.info(
        "bootstrap: joined runtime as process %d/%d (coordinator %s) "
        "in %.2fs", pidx, pcnt, addr, elapsed_s)


def process_count() -> int:
    """Number of participating processes (``hvd.size`` is device count in the
    reference; here processes and devices are distinct — see :func:`world`)."""
    return jax.process_count()


def process_index() -> int:
    """This process's index (the reference's ``hvd.rank`` per-GPU analogue is
    a mesh position, not a process)."""
    return jax.process_index()


def world() -> int:
    """Total device count = the ``world_size`` to build
    :class:`~distributed_embeddings_tpu.parallel.DistributedEmbedding` with."""
    return jax.device_count()


def global_mesh(axis_name: str = "data") -> jax.sharding.Mesh:
    """One-axis mesh over every device in the job — the layout the hybrid
    trainer expects (mp positions == dp positions, like the reference)."""
    return jax.sharding.Mesh(np.array(jax.devices()), (axis_name,))


def shard_batch(mesh: jax.sharding.Mesh, tree, axis_name: str = "data"):
    """Assemble global batch arrays from *process-local* shards.

    Each process passes the rows its own data pipeline loaded (the
    reference's per-rank dataset slicing, ``examples/dlrm/main.py:166-190``);
    the result is a pytree of global ``jax.Array`` whose leading axis is
    sharded over ``axis_name``, ready for the hybrid train step.
    """
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(axis_name))
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(
            sharding, np.asarray(x)), tree)


def to_host(x) -> np.ndarray:
    """Full host copy of a (possibly process-spanning) array on every process
    — the reference's ``hvd.allgather`` eval-prediction gather
    (``examples/dlrm/main.py:230-243`` there)."""
    if isinstance(x, np.ndarray) or x.is_fully_addressable:
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def broadcast_seed(seed: int) -> int:
    """Agree on one seed across processes (the reference's
    ``hvd.broadcast_object(seed)``, ``dist_model_parallel_test.py:92-93``)."""
    from jax.experimental import multihost_utils

    arr = multihost_utils.broadcast_one_to_all(
        np.asarray(seed, dtype=np.int64))
    return int(arr)
