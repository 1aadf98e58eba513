"""Sparse (IndexedSlices-style) embedding gradients, TPU-native.

The reference's backward kernel (``cc/kernels/embedding_lookup_kernels.cu:457-629``)
turns per-output-row gradients into ``(unique_ids, unique_grad)`` via CUB
radix-sort + unique-by-key, wrapped as ``tf.IndexedSlices``
(``python/ops/embedding_lookup_ops.py:105-122``). On TPU we reproduce the same
dataflow with static shapes:

* :func:`combiner_grad_values` — expand a ``[batch, width]`` output cotangent
  to per-id row gradients (the ``OffsetToWeightsAndRowId`` + weighted-reuse
  trick of the reference backward, ``.cu:493-494,539-627``).
* :func:`dedup_sparse_grad` — sort ids, segment-sum duplicate rows; output
  buffers keep the input capacity (the dynamic ``num_unique`` of the reference,
  ``.cu:519-528``, becomes a pad-id sentinel + ``mode='drop'`` scatters).

Deduplication is only *required* by optimizers whose update is nonlinear in the
gradient (Adagrad/Adam); plain SGD can scatter-add duplicates directly — the
sparse optimizers declare that via ``needs_dedup`` (:mod:`..parallel.optimizers`)
and the SGD paths skip this pass entirely (``DETPU_SGD_DEDUP=1`` forces it
back on for A/B). :func:`dedup_sparse_grad` runs under the ``detpu/dedup``
named scope so the HLO pass census (:mod:`..analysis.hlo_census`) can
attribute — and budget — its sort/segment-sum passes per compiled program.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .embedding_lookup import ragged_row_ids


def combiner_grad_values(out_grad: jax.Array, row_splits: jax.Array,
                         capacity: int, combiner: str) -> jax.Array:
    """Per-id gradient rows for a CSR lookup-with-combiner.

    Args:
      out_grad: ``[batch, width]`` cotangent of the combined output.
      row_splits: ``[batch+1]`` CSR offsets of the forward input.
      capacity: static id capacity of the forward input.
      combiner: ``'sum'`` or ``'mean'``.

    Returns:
      ``[capacity, width]`` gradient for each id position (zeros at padding).
    """
    seg = ragged_row_ids(row_splits, capacity)
    vals = jnp.take(out_grad, seg, axis=0, mode="fill", fill_value=0)
    if combiner == "mean":
        counts = (row_splits[1:] - row_splits[:-1]).astype(out_grad.dtype)
        inv = 1.0 / jnp.maximum(counts, 1)
        per_id = jnp.take(inv, seg, mode="fill", fill_value=0)
        vals = vals * per_id[:, None]
    return vals


def dedup_sparse_grad(ids: jax.Array, grads: jax.Array, *,
                      pad_id: int,
                      valid: Optional[jax.Array] = None,
                      max_unique: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Sort ids and sum gradient rows of duplicates.

    Args:
      ids: ``[n]`` int row ids; entries equal to (or marked invalid via
        ``valid=False``) are treated as padding.
      grads: ``[n, width]`` per-id gradient rows.
      pad_id: sentinel for padding/unused output slots. Must be >= vocab so
        that ``.at[ids].op(..., mode='drop')`` ignores those rows.
      valid: optional ``[n]`` bool mask; invalid entries are replaced by
        ``pad_id`` before sorting.
      max_unique: optional static bound on the number of distinct values in
        ``ids`` (including the sentinel) — the **vocab bound**: distinct row
        ids can never exceed the table's row capacity + 1. Output buffers
        shrink to ``U = min(n, max_unique)``, shrinking every downstream
        per-unique-row op with them — a multiplicative win whenever the
        batch id stream is much longer than the vocab (small tables under
        power-law traffic: tiny-zoo w=8 is a 2.7M-id stream over ~60k rows).
        Passing a bound smaller than the true distinct count silently drops
        the largest ids' gradients — callers must guarantee it.

    Returns:
      ``(unique_ids [U], unique_grads [U, width])``: position
      ``k < num_unique`` holds the k-th smallest unique id and the sum of
      its gradient rows; positions past that hold ``pad_id`` and garbage
      (callers scatter with ``mode='drop'``).
    """
    with jax.named_scope("detpu/dedup"):
        return _dedup_sparse_grad(ids, grads, pad_id, valid, max_unique)


def _dedup_sparse_grad(ids, grads, pad_id, valid, max_unique,
                       sum_dtype=None):
    """``sum_dtype``: the dtype the duplicates' rows are summed (and
    returned) in; the rows' own when None."""
    n = ids.shape[0]
    u = n if max_unique is None else min(n, int(max_unique))
    if valid is not None:
        ids = jnp.where(valid, ids, pad_id)
    sorted_ids, perm = jax.lax.sort_key_val(ids, jnp.arange(n, dtype=jnp.int32))
    sorted_grads = jnp.take(grads, perm, axis=0)
    boundary = jnp.concatenate(
        [jnp.ones((1,), jnp.int32),
         (sorted_ids[1:] != sorted_ids[:-1]).astype(jnp.int32)])
    seg = jnp.cumsum(boundary) - 1  # [n], segment index per sorted row
    # seg ascends by construction; declaring it buys the sorted-scatter fast
    # path (measured 1.8x on v5e, docs/perf_tpu.md)
    sum_dtype = sum_dtype or grads.dtype
    unique_grads = jnp.zeros((u,) + grads.shape[1:], sum_dtype
                             ).at[seg].add(sorted_grads.astype(sum_dtype),
                                           mode="drop",
                                           indices_are_sorted=True)
    unique_ids = jnp.full((u,), pad_id, dtype=ids.dtype
                          ).at[seg].set(sorted_ids, mode="drop",
                                        indices_are_sorted=True)
    # Padding ids sort last and get their own segment(s) holding pad_id:
    # either past u (dropped here) or dropped downstream by the same
    # out-of-range rule the scatters rely on.
    return unique_ids, unique_grads
