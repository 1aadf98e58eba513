"""Hybrid-parallel gradient glue.

TPU equivalent of the reference's tape/broadcast monkey-patches
(``dist_model_parallel.py:509-567``): one backward pass produces two gradient
families —

* **dp** (dense/replicated) gradients are averaged across the mesh axis
  (``hvd.allreduce(op=Average)`` per var → ``lax.pmean`` over the pytree);
* **mp** (model-parallel embedding) gradients stay local, scaled by
  ``1/world_size`` so loss-mean-over-local-batch semantics match the averaged
  dp gradients (``dist_model_parallel.py:542-546``).

Instead of tagging variables with ``VariableSynchronization.NONE``
(``:258``), partitioning is expressed as a pytree mask: JAX params are plain
arrays, so callers say which subtree is model-parallel (for
:class:`.DistributedEmbedding` that is its width-grouped slab dict).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


def _map_by_mask(fn_mp: Callable, fn_dp: Callable, mask: Any, tree: Any) -> Any:
    """Map ``fn_mp``/``fn_dp`` over ``tree`` leaves according to a boolean mask
    that may be a *prefix* of the tree (optax-style): mapping over the mask
    first lets each mask leaf own a whole subtree."""
    return jax.tree.map(
        lambda m, sub: jax.tree.map(fn_mp if m else fn_dp, sub),
        mask, tree)


def split_mp_dp(tree: Any, mp_mask: Any):
    """Split a pytree into (mp_part, dp_part) by a boolean mask pytree
    (prefix-broadcastable like optax masks); the two parts keep the full
    structure with ``None`` at the other family's leaves."""
    mp = _map_by_mask(lambda g: g, lambda g: None, mp_mask, tree)
    dp = _map_by_mask(lambda g: None, lambda g: g, mp_mask, tree)
    return mp, dp


def resolve_dp_gradient(g: jax.Array, axis_name: str) -> jax.Array:
    """Average a data-parallel gradient across the mesh axis, accounting for
    shard_map's varying-manual-axes (VMA) autodiff semantics.

    Inside ``shard_map`` with replication checking, differentiating a
    device-varying loss w.r.t. an *unvarying* (replicated, ``P()``-spec)
    parameter already inserts the cross-device ``psum`` — the transpose of the
    implicit broadcast — so the raw gradient equals the sum of per-device
    contributions and a further ``pmean`` would be an identity. A gradient
    that is still device-varying needs the explicit ``pmean``. Distinguish by
    the gradient's vma type.

    Requires shard_map's default replication checking (``check_vma=True``):
    under ``check_vma=False`` every value reports an empty vma set, the
    auto-psum does not happen, and this helper cannot tell the two cases
    apart.
    """
    if axis_name in jax.typeof(g).vma:
        return lax.pmean(g, axis_name)
    return g / lax.axis_size(axis_name)


def hybrid_gradients(grads: Any, mp_mask: Any, axis_name: str) -> Any:
    """Resolve a raw gradient pytree into hybrid-parallel gradients.

    Must run inside ``shard_map``/``pjit`` with ``axis_name`` bound. dp leaves
    are averaged over the axis (see :func:`resolve_dp_gradient`); mp leaves
    are divided by the axis size.
    """
    world = lax.axis_size(axis_name)
    return _map_by_mask(
        lambda g: None if g is None else g / world,
        lambda g: None if g is None else resolve_dp_gradient(g, axis_name),
        mp_mask, grads)


def broadcast_variables(params: Any, mp_mask: Any, axis_name: str,
                        root_rank: int = 0) -> Any:
    """Broadcast dp leaves from ``root_rank``; mp leaves pass through
    untouched (reference ``broadcast_variables``, ``:509-523``).

    Under JAX SPMD replicated arrays are identical by construction, so this is
    only needed when per-device state was deliberately diverged (e.g. seeded
    per-rank init); provided for capability parity and tests.
    """

    def bcast(p):
        if p is None:
            return p
        # psum of the root-masked value: broadcasts without materializing a
        # world-sized all_gather intermediate.
        root = lax.axis_index(axis_name) == root_rank
        return lax.psum(jnp.where(root, p, jnp.zeros_like(p)), axis_name)

    return _map_by_mask(lambda p: p, bcast, mp_mask, params)


def hybrid_value_and_grad(loss_fn: Callable, mp_mask: Any, axis_name: str):
    """``jax.value_and_grad`` wrapper applying :func:`hybrid_gradients` —
    the drop-in analogue of the reference's ``DistributedGradientTape``
    (``dist_model_parallel.py:526-567``)."""
    vg = jax.value_and_grad(loss_fn)

    def wrapped(params, *args, **kwargs):
        value, grads = vg(params, *args, **kwargs)
        return value, hybrid_gradients(grads, mp_mask, axis_name)

    return wrapped
