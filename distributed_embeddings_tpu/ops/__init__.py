"""Embedding lookup ops (TPU-native equivalents of the reference custom-op layer).

The reference implements these as TensorFlow custom ops backed by CUDA kernels
(``distributed_embeddings/cc/ops/embedding_lookup_ops.cc:24-88``); here they
are pure XLA (gather + segment-reduce, which XLA fuses well on TPU) — there
are no hand-written kernels in the tree.
"""

from .embedding_lookup import (
    Ragged,
    SparseIds,
    embedding_lookup,
    row_to_split,
    ragged_row_ids,
)
from .sparse_grad import (
    combiner_grad_values,
    dedup_sparse_grad,
)
