"""distributed_embeddings_tpu — TPU-native distributed embedding framework.

A JAX/XLA re-design of the capability surface of NVIDIA's
``distributed-embeddings`` (reference: ``distributed_embeddings/__init__.py:17-18``,
which exports ``embedding_lookup`` and ``__version__``): large-embedding
recommender training with hybrid model/data parallelism over a TPU mesh.
"""

from .version import __version__
from .ops.embedding_lookup import (
    Ragged,
    SparseIds,
    embedding_lookup,
    row_to_split,
)

__all__ = [
    "__version__",
    "embedding_lookup",
    "row_to_split",
    "Ragged",
    "SparseIds",
    "AuditReport",
    "audit_train_step",
]

_ANALYSIS_EXPORTS = ("AuditReport", "audit_train_step")


def __getattr__(name):
    # the step auditor pulls in the whole parallel stack (flax/optax);
    # loaded lazily so `import distributed_embeddings_tpu` stays light
    if name in _ANALYSIS_EXPORTS:
        from . import analysis

        return getattr(analysis, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
