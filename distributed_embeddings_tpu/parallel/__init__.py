"""Hybrid model/data parallelism over a TPU mesh.

TPU-native re-design of ``distributed_embeddings/python/layers/dist_model_parallel.py``:
the placement planner is pure Python (carried over algorithmically), while the
runtime communication (Horovod all-to-all/allreduce in the reference) becomes
``jax.lax`` collectives inside ``jax.shard_map`` over a named mesh axis.
"""

from . import bootstrap
from .strategy import DistEmbeddingStrategy
from .dist_embedding import DistributedEmbedding, MpInputs
from .grads import (
    broadcast_variables,
    hybrid_gradients,
    hybrid_value_and_grad,
    resolve_dp_gradient,
    split_mp_dp,
)
from .optimizers import SparseAdagrad, SparseAdam, SparseMomentum, SparseSGD
from .lm_serving import LMServeState, SessionConfig, SessionRuntime
from .sparse_optax import (
    SparseRows,
    apply_sparse_updates,
    sparse_grad_metrics,
    sparse_rows_adagrad,
    sparse_rows_adam,
    sparse_rows_momentum,
    sparse_rows_sgd,
    sparse_value_and_grad,
    unique_ids_static,
)
from .online import (
    OnlineConfig,
    OnlineResult,
    OnlineRuntime,
    Snapshot,
    SnapshotPublisher,
    online_sidecar_path,
)
from .resilient import (
    PREEMPT_EXIT_CODE,
    ResilientResult,
    quarantine_ledger_path,
    resume_sentinel_path,
    run_resilient,
)
from .schedule import (
    PhaseDecl,
    ScheduleError,
    StepSchedule,
    default_schedule,
    pipelined_schedule,
    resolve_schedule,
    streaming_schedule,
)
from .serving import (
    Expired,
    Failed,
    Overloaded,
    RealtimeDriver,
    Request,
    ServeConfig,
    Served,
    ServingRuntime,
    Unavailable,
)
from .streaming import (
    StreamingConfig,
    init_streaming,
)
from .supervisor import (
    SuperviseConfig,
    Supervisor,
)
from .trainer import (
    HybridTrainState,
    clone_pytree,
    init_hybrid_state,
    make_hybrid_eval_step,
    make_hybrid_train_loop,
    make_hybrid_train_step,
)
