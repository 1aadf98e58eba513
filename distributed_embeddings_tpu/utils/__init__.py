"""Data pipelines, metrics, checkpointing, the fault-tolerant runtime
layer, and the step-level observability layer for the example models and
entry points."""

from . import obs, runtime
from .checkpoint import (previous_checkpoint_path, reshard_checkpoint,
                         restore_train_state, ring_dir, ring_entries,
                         rollback_candidates, save_train_state,
                         validate_checkpoint_model, verify_checkpoint)
from .data import DummyDataset, RawBinaryDataset, fast_forward, power_law_ids
from .metrics import binary_auc
from .obs import (MetricsLogger, counter_inc, counters,
                  fetch_metrics, install_compile_listener,
                  maybe_start_server, metrics_enabled, nanguard_enabled,
                  nanguard_escalation_k, profile_trace, reset_counters,
                  scope, span)
from .runtime import (BackendProbe, BackendUnavailable, CheckpointCorrupt,
                      CheckpointMismatch, CoordinatorUnreachable,
                      DeadlineExceeded, FaultInjected, InvalidInputError,
                      NonFiniteLossError, SectionRecorder, deadline,
                      ensure_compile_cache, fault_point, preempt_step,
                      probe_backend, retry)
