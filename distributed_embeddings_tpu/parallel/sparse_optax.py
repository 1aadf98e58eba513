"""O(touched-rows) embedding training under plain optax — the op-layer
IndexedSlices pipeline.

The reference registers a gradient for its lookup op that returns
``tf.IndexedSlices(unique_grad, unique_ids)`` even on ONE device
(``distributed_embeddings/python/ops/embedding_lookup_ops.py:105-122``), so
any Keras optimizer's sparse path updates only the looked-up rows. JAX
autodiff cannot return a sparse cotangent (cotangents must match primal
shapes), so differentiating through :func:`...ops.embedding_lookup`
materializes a dense ``[vocab, width]`` gradient and optax updates every
row — O(all rows) per step where the reference is O(touched rows).

This module restores the sparse pipeline without the hybrid trainer
(:func:`~.trainer.make_hybrid_train_step`), in three composable pieces:

* :func:`unique_ids_static` — static-shape sort/unique of an id stream
  (the CUB ``SortPairs`` + ``UniqueByKey`` of the reference backward,
  ``cc/kernels/embedding_lookup_kernels.cu:499-515``) returning the unique
  ids and each position's index into them.
* :func:`sparse_value_and_grad` — wraps a ``loss_fn(dense_params,
  emb_outs, *args)`` so that one backward produces dense-parameter grads
  AND per-table :class:`SparseRows` ``(unique_ids, unique_grad)``. The
  mechanism is a basis split, not a custom cotangent type: each table's id
  stream is deduped up front, the ``[U, width]`` unique rows are gathered
  once, and the loss is differentiated w.r.t. those *gathered rows* — so
  the table-side cotangent has U rows, never ``vocab``. Forward values are
  bitwise what direct lookups produce (same gather + combine).
* :func:`sparse_rows_sgd` / :func:`sparse_rows_adagrad` /
  :func:`sparse_rows_momentum` / :func:`sparse_rows_adam` — optax
  ``GradientTransformation``s whose ``update`` consumes :class:`SparseRows`
  leaves and touches only those rows of the (dense, ``[vocab, width]``)
  optimizer state; :func:`apply_sparse_updates` is the matching
  ``optax.apply_updates``. Numerics follow the package's sparse-optimizer
  semantics (:mod:`.optimizers`): optax-equal when every row is touched,
  lazy moments otherwise.

Padding/out-of-range contract: ids ``>= vocab`` read the clipped last row
in the forward (like the op layer) and are DROPPED by the update scatters
(like the hybrid path) — a bad id trains nothing. NEGATIVE ids clamp to 0
on both sides: the forward reads row 0 (``jnp.take(mode="clip")``, the op
layer's read) and the update trains row 0 — symmetric with the read,
instead of letting JAX's negative-index normalization wrap the scatter to
unrelated tail rows (ADVICE r5).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax import lax

from ..ops.embedding_lookup import IdsLike, Ragged, SparseIds, embedding_lookup
from ..utils import obs
from .optimizers import declare_sorted, sgd_dedup_forced


def _sorted_decl(n: int, slab: jax.Array) -> bool:
    """Whether a scatter of ``n`` (truly sorted, distinct) ids into ``slab``
    should DECLARE them sorted.

    The declaration picks XLA's TPU scatter: declared, one sweep over the
    whole slab with the rows merged in; undeclared and short for the slab,
    row at a time, nothing paid for the slab (``optimizers.SCATTER_FORMS``).
    So a small stream into a huge slab wants it dropped (the old "3x WORSE"
    note, and a 16M-row table step here that went ~100 GB/s -> full-rate,
    were the sweep's pass over the slab: ``PERF.md`` section 6, PR 31, has
    the readings), and the costs of :mod:`.optimizers` say where."""
    return declare_sorted(int(n), slab.size * slab.dtype.itemsize)


@struct.dataclass
class SparseRows:
    """IndexedSlices analogue: ``rows[k]`` is the gradient (or update) for
    table row ``ids[k]``; unused capacity is marked ``>= vocab`` (dropped
    by scatters). ``unique=True`` (the default, what
    :func:`sparse_value_and_grad` builds under ``dedup=True``) additionally
    guarantees the ids are sorted and duplicate-free — stateful
    (read-modify-write) optimizers require that; the linear SGD transform
    and :func:`apply_sparse_updates` accept ``unique=False`` rows (the
    dedup-skipped path) and simply scatter-add the repeats."""

    ids: jax.Array  # [U] int32
    rows: jax.Array  # [U, width]
    vocab: int = struct.field(pytree_node=False)
    unique: bool = struct.field(pytree_node=False, default=True)


def unique_ids_static(ids: jax.Array, vocab: int,
                      max_unique: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Sorted-unique of a flat id stream with static output capacity.

    Returns ``(uids [U], inv [n])`` with ``U = min(n, vocab + 1)`` (distinct
    ids can never exceed the vocab; one extra slot absorbs out-of-range
    sentinels): ``uids`` holds the distinct ids ascending, padded with
    ``vocab``; ``inv[k]`` is the index of ``ids[k]`` in ``uids``. The
    static-shape form of the reference backward's CUB sort + unique-by-key
    (``cc/kernels/embedding_lookup_kernels.cu:499-515``)."""
    n = ids.shape[0]
    u = min(n, int(vocab) + 1) if max_unique is None else int(max_unique)
    return _unique_ids_static(ids, int(vocab), n, u)


@jax.named_scope("detpu/unique_ids")
def _unique_ids_static(ids, vocab: int, n: int, u: int):
    # clamp BOTH ends BEFORE sorting. Above: ids > vocab would otherwise
    # sort past the pad slots (which hold exactly ``vocab``) and break the
    # ascending-uids property the scatters later declare; clamping merges
    # every bad id into the one dropped sentinel entry while keeping the
    # clipped-last-row forward read identical. Below: a negative id
    # surviving into uids would read row 0 in the forward (take
    # mode="clip") but WRAP to a tail row in the update scatters (JAX
    # negative-index normalization), training an unrelated row — clamping
    # to 0 makes invalid ids train row 0, symmetric with the read
    # (module docstring "Padding/out-of-range contract"; ADVICE r5).
    ids = jnp.clip(ids.astype(jnp.int32), 0, jnp.int32(vocab))
    sorted_ids, perm = lax.sort_key_val(
        ids, jnp.arange(n, dtype=jnp.int32))
    boundary = jnp.concatenate(
        [jnp.ones((1,), jnp.int32),
         (sorted_ids[1:] != sorted_ids[:-1]).astype(jnp.int32)])
    seg = jnp.cumsum(boundary) - 1  # ascending
    uids = jnp.full((u,), vocab, jnp.int32).at[seg].set(
        sorted_ids, mode="drop", indices_are_sorted=True)
    inv = jnp.zeros((n,), jnp.int32).at[perm].set(seg)
    return uids, inv


def _flat_stream(inp: IdsLike) -> jax.Array:
    """The flat id stream of one input (Ragged capacities included —
    padding positions become redundant unique entries, harmless)."""
    if isinstance(inp, Ragged):
        return inp.values.reshape(-1)
    if isinstance(inp, SparseIds):
        return inp.values.reshape(-1)
    return jnp.asarray(inp).reshape(-1)


def _remap(inp: IdsLike, inv_slice: jax.Array) -> IdsLike:
    """Rebuild an input with its ids replaced by indices into the unique
    rows (same static encoding, so the remapped lookup reuses
    :func:`...ops.embedding_lookup` unchanged). ``weights`` carry through:
    positions are unchanged by the remap, so per-id weights stay aligned
    and the remapped lookup stays bitwise-identical to the direct weighted
    lookup (a dropped field here silently computed an UNWEIGHTED
    forward/gradient for weighted inputs — ADVICE r5, medium)."""
    if isinstance(inp, Ragged):
        return Ragged(values=inv_slice, row_splits=inp.row_splits,
                      weights=inp.weights)
    if isinstance(inp, SparseIds):
        return SparseIds(indices=inp.indices, values=inv_slice,
                         dense_shape=inp.dense_shape, weights=inp.weights)
    return inv_slice.reshape(jnp.asarray(inp).shape)


def sparse_value_and_grad(loss_fn: Callable,
                          combiners: Sequence[Optional[str]],
                          input_table_map: Optional[Sequence[int]] = None,
                          has_aux: bool = False,
                          dedup: bool = True):
    """Build ``f(dense_params, tables, inputs, *args) -> (loss,
    (dense_grads, sparse_grads))`` with table gradients in O(touched rows).

    Args:
      loss_fn: ``loss_fn(dense_params, emb_outs, *args) -> scalar`` (or
        ``(scalar, aux)`` with ``has_aux``) — the same contract as the
        hybrid trainer's, with ``emb_outs[i]`` the combined lookup of
        ``inputs[i]``.
      combiners: per-TABLE combiner (``None``/'sum'/'mean').
      input_table_map: ``inputs[i]`` looks up ``tables[input_table_map[i]]``
        (default: identity — one input per table). Inputs sharing a table
        dedup jointly, so shared tables still see one unique-row gather.
      has_aux: forwarded to ``jax.value_and_grad``.
      dedup: ``True`` (default) runs the :func:`unique_ids_static`
        sort-unique pass per table, yielding ``unique=True``
        :class:`SparseRows` every ``sparse_rows_*`` transform accepts.
        ``False`` SKIPS that pass entirely — the ROADMAP 3(a) SGD dedup
        cut: the per-position rows are gathered directly (bitwise the same
        forward: a gather of a gather of the same clamped ids) and the
        returned rows carry the raw clamped id stream with
        ``unique=False``, which only gradient-LINEAR consumers
        (:func:`sparse_rows_sgd`, :func:`apply_sparse_updates`) accept —
        duplicates scatter-add exactly; the stateful transforms raise.
        One sort + cumsum + two scatters + an inverse-permutation gather
        per table per step are eliminated. ``DETPU_SGD_DEDUP=1`` (checked
        at build time) forces ``dedup=True`` back on for A/B.

    Returns a function over ``tables``: a list (or dict values in order) of
    dense ``[vocab, width]`` arrays. Its ``sparse_grads`` output is a list
    of :class:`SparseRows` aligned with ``tables`` — feed them to a
    ``sparse_rows_*`` transform + :func:`apply_sparse_updates`.
    """
    combiners = list(combiners)
    if not dedup and sgd_dedup_forced():
        dedup = True  # the A/B escape hatch wins over the caller's skip

    def f(dense_params, tables: Sequence[jax.Array], inputs: Sequence[IdsLike],
          *args):
        tables = list(tables)
        inputs = list(inputs)
        tmap = (list(input_table_map) if input_table_map is not None
                else list(range(len(inputs))))
        if len(tmap) != len(inputs):
            raise ValueError("input_table_map must align with inputs")
        if len(combiners) != len(tables):
            raise ValueError("combiners must align with tables (one per "
                             "table)")
        # --- 1. per table: joint unique over all its inputs' id streams
        streams: List[List[jax.Array]] = [[] for _ in tables]
        for i, inp in enumerate(inputs):
            streams[tmap[i]].append(_flat_stream(inp))
        uids, invs, urows = [], [], []
        for t, parts in enumerate(streams):
            if not parts:
                raise ValueError(f"Table {t} has no inputs")
            cat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            vocab = tables[t].shape[0]
            if dedup:
                u, inv = unique_ids_static(cat, vocab)
            else:
                # dedup skipped: the "unique" rows are simply the
                # per-position rows under the same [0, vocab] clamp
                # unique_ids_static applies (negative -> row 0 symmetric
                # with the read; > vocab -> the dropped sentinel), and the
                # remap indices are the identity — the forward gather chain
                # and the update contract are bitwise unchanged
                u = jnp.clip(cat.astype(jnp.int32), 0, jnp.int32(vocab))
                inv = jnp.arange(cat.shape[0], dtype=jnp.int32)
            uids.append(u)
            invs.append(inv)
            # one gather per DISTINCT row (pad ids clip into the last row,
            # the op layer's documented read; their grads drop at apply)
            urows.append(jnp.take(tables[t], u, axis=0, mode="clip"))

        # --- 2. differentiate w.r.t. the gathered unique rows
        def inner(dp, rows_list):
            outs = []
            offs = [0] * len(tables)
            for i, inp in enumerate(inputs):
                t = tmap[i]
                nvals = _flat_stream(inp).shape[0]
                sl = lax.slice(invs[t], (offs[t],), (offs[t] + nvals,))
                offs[t] += nvals
                outs.append(embedding_lookup(rows_list[t], _remap(inp, sl),
                                             combiner=combiners[t]))
            return loss_fn(dp, outs, *args)

        (loss, *aux), (dgrads, rgrads) = _vg(inner, has_aux)(
            dense_params, urows)
        sgrads = [SparseRows(ids=u, rows=g, vocab=tables[t].shape[0],
                             unique=dedup)
                  for t, (u, g) in enumerate(zip(uids, rgrads))]
        if has_aux:
            return (loss, aux[0]), (dgrads, sgrads)
        return loss, (dgrads, sgrads)

    return f


def _vg(fn, has_aux):
    vg = jax.value_and_grad(fn, argnums=(0, 1), has_aux=has_aux)
    if has_aux:
        def run(dp, rows):
            (loss, aux), grads = vg(dp, rows)
            return (loss, aux), grads
        return run

    def run(dp, rows):
        loss, grads = vg(dp, rows)
        return (loss,), grads
    return run


# --------------------------------------------------------------- optax side


def _tree_rows(fn, updates, *rest):
    """Map ``fn`` over every :class:`SparseRows` leaf of ``updates`` (and
    aligned leaves of ``rest`` trees)."""
    return jax.tree.map(fn, updates, *rest,
                        is_leaf=lambda x: isinstance(x, SparseRows))


class _Out:
    """Opaque multi-value result of a per-leaf update fn. Deliberately NOT
    a registered pytree: jax.tree treats it as a leaf, so unpacking the
    per-leaf results cannot be confused with structural tuples/lists in
    the caller's parameter tree (a tuple-valued params pytree once made an
    ``is_leaf=tuple`` unpack return optimizer state as the update)."""

    __slots__ = ("vals",)

    def __init__(self, *vals):
        self.vals = vals


def _unpack(tree, i):
    return jax.tree.map(lambda o: o.vals[i], tree)


def _resolve_lr(lr, count):
    return lr(count) if callable(lr) else lr


def _require_unique(g: "SparseRows", who: str) -> None:
    """Stateful (read-modify-write) transforms need sorted-unique rows: a
    duplicated id would read stale state for its second occurrence. Raise
    at trace time rather than silently corrupt."""
    if not g.unique:
        raise ValueError(
            f"{who} requires unique SparseRows (duplicate ids would "
            "read-modify-write stale per-row state) — build the gradients "
            "with sparse_value_and_grad(dedup=True); dedup=False is only "
            "valid for gradient-linear consumers (sparse_rows_sgd, "
            "apply_sparse_updates)")


def sparse_rows_sgd(learning_rate) -> optax.GradientTransformation:
    """SGD over :class:`SparseRows` gradients: update rows are
    ``-lr * grad_rows``; dense (non-SparseRows) leaves get plain SGD.
    Linear in the gradient, so ``unique=False`` (dedup-skipped) rows are
    accepted — duplicates accumulate exactly in the apply scatter."""

    def init(params):
        del params
        return {"count": jnp.zeros((), jnp.int32)}

    def update(updates, state, params=None):
        del params
        lr = _resolve_lr(learning_rate, state["count"])

        def one(g):
            if isinstance(g, SparseRows):
                return SparseRows(ids=g.ids, rows=-lr * g.rows,
                                  vocab=g.vocab, unique=g.unique)
            return -lr * g
        return _tree_rows(one, updates), {"count": state["count"] + 1}

    return optax.GradientTransformation(init, update)


def sparse_rows_adagrad(learning_rate,
                        initial_accumulator_value: float = 0.1,
                        eps: float = 1e-7) -> optax.GradientTransformation:
    """Adagrad over :class:`SparseRows` gradients; ``optax.adagrad``
    numerics on the touched rows, untouched rows' accumulators unchanged
    (the Keras sparse-apply behavior the reference relies on)."""

    def init(params):
        return {"count": jnp.zeros((), jnp.int32),
                "acc": jax.tree.map(
                    lambda p: jnp.full(p.shape, initial_accumulator_value,
                                       jnp.result_type(p, jnp.float32)),
                    params)}

    def update(updates, state, params=None):
        del params
        lr = _resolve_lr(learning_rate, state["count"])
        accs = state["acc"]

        def one(g, acc):
            if not isinstance(g, SparseRows):
                new = acc + g * g
                return _Out(-lr * g * lax.rsqrt(new + eps), new)
            _require_unique(g, "sparse_rows_adagrad")
            rows = g.rows.astype(acc.dtype)
            # scatter-add FIRST, gather the updated rows after: the
            # accumulator's only write is a single-use scatter-add, which
            # XLA's TPU backend updates in place under donation — the
            # gather+scatter-set form has two uses of the old buffer and
            # forces a full slab copy every step (measured 4 GB/step at
            # vocab 16M; docs/perf_tpu.md r5)
            new_acc = acc.at[g.ids].add(
                rows * rows, mode="drop",
                indices_are_sorted=_sorted_decl(g.ids.shape[0], acc))
            new_rows = jnp.take(new_acc, g.ids, axis=0, mode="clip")
            upd = (-lr * rows * lax.rsqrt(new_rows + eps)).astype(
                g.rows.dtype)
            return _Out(SparseRows(ids=g.ids, rows=upd, vocab=g.vocab),
                        new_acc)

        pairs = _tree_rows(one, updates, accs)
        return _unpack(pairs, 0), {"count": state["count"] + 1,
                                   "acc": _unpack(pairs, 1)}

    return optax.GradientTransformation(init, update)


def sparse_rows_momentum(learning_rate, momentum: float = 0.9,
                         nesterov: bool = False
                         ) -> optax.GradientTransformation:
    """Heavy-ball SGD with lazy row momentum (``optax.trace`` numerics on
    touched rows; untouched rows' traces neither decay nor update)."""

    def init(params):
        return {"count": jnp.zeros((), jnp.int32),
                "trace": jax.tree.map(jnp.zeros_like, params)}

    def update(updates, state, params=None):
        del params
        lr = _resolve_lr(learning_rate, state["count"])

        def one(g, tr):
            if not isinstance(g, SparseRows):
                t_new = g + momentum * tr
                step = g + momentum * t_new if nesterov else t_new
                return _Out(-lr * step, t_new)
            _require_unique(g, "sparse_rows_momentum")
            rows = g.rows.astype(tr.dtype)
            srt = _sorted_decl(g.ids.shape[0], tr)
            # the affine state transition t <- m*t + g runs as two single-
            # use scatters (multiply, add) so the trace slab updates in
            # place under donation; a gather+scatter-set would copy the
            # whole slab every step (see sparse_rows_adagrad)
            new_tr = tr.at[g.ids].multiply(
                momentum, mode="drop", indices_are_sorted=srt
            ).at[g.ids].add(rows, mode="drop", indices_are_sorted=srt)
            t_new = jnp.take(new_tr, g.ids, axis=0, mode="clip")
            step = rows + momentum * t_new if nesterov else t_new
            return _Out(SparseRows(ids=g.ids,
                                   rows=(-lr * step).astype(g.rows.dtype),
                                   vocab=g.vocab), new_tr)

        pairs = _tree_rows(one, updates, state["trace"])
        return _unpack(pairs, 0), {"count": state["count"] + 1,
                                   "trace": _unpack(pairs, 1)}

    return optax.GradientTransformation(init, update)


def sparse_rows_adam(learning_rate, b1: float = 0.9, b2: float = 0.999,
                     eps: float = 1e-8, eps_root: float = 0.0
                     ) -> optax.GradientTransformation:
    """Adam with lazy row moments (LazyAdam: bias correction by the global
    step count; untouched rows' moments frozen — see
    :mod:`.optimizers`)."""

    def init(params):
        return {"count": jnp.zeros((), jnp.int32),
                "mu": jax.tree.map(jnp.zeros_like, params),
                "nu": jax.tree.map(jnp.zeros_like, params)}

    def update(updates, state, params=None):
        del params
        count = state["count"] + 1
        lr = _resolve_lr(learning_rate, state["count"])
        t = count.astype(jnp.float32)

        def one(g, mu, nu):
            if not isinstance(g, SparseRows):
                mu_n = b1 * mu + (1 - b1) * g
                nu_n = b2 * nu + (1 - b2) * g * g
                mu_hat = mu_n / (1 - b1 ** t)
                nu_hat = nu_n / (1 - b2 ** t)
                return _Out(
                    -lr * mu_hat / (jnp.sqrt(nu_hat + eps_root) + eps),
                    mu_n, nu_n)
            _require_unique(g, "sparse_rows_adam")
            rows = g.rows.astype(mu.dtype)
            srt = _sorted_decl(g.ids.shape[0], mu)
            # affine moment transitions as in-place-able multiply+add
            # scatter pairs (see sparse_rows_momentum)
            new_mu = mu.at[g.ids].multiply(
                b1, mode="drop", indices_are_sorted=srt
            ).at[g.ids].add((1 - b1) * rows, mode="drop",
                            indices_are_sorted=srt)
            new_nu = nu.at[g.ids].multiply(
                b2, mode="drop", indices_are_sorted=srt
            ).at[g.ids].add((1 - b2) * rows * rows, mode="drop",
                            indices_are_sorted=srt)
            mu_n = jnp.take(new_mu, g.ids, axis=0, mode="clip")
            nu_n = jnp.take(new_nu, g.ids, axis=0, mode="clip")
            mu_hat = mu_n / (1 - b1 ** t)
            nu_hat = nu_n / (1 - b2 ** t)
            upd = -lr * mu_hat / (jnp.sqrt(nu_hat + eps_root) + eps)
            return _Out(SparseRows(ids=g.ids, rows=upd.astype(g.rows.dtype),
                                   vocab=g.vocab), new_mu, new_nu)

        triples = _tree_rows(one, updates, state["mu"], state["nu"])
        return _unpack(triples, 0), {"count": count,
                                     "mu": _unpack(triples, 1),
                                     "nu": _unpack(triples, 2)}

    return optax.GradientTransformation(init, update)


def apply_sparse_updates(params, updates):
    """``optax.apply_updates`` for trees whose leaves may be
    :class:`SparseRows`: sparse leaves scatter-add their rows (ids past the
    vocab drop); dense leaves add elementwise."""

    def one(p, u):
        if isinstance(u, SparseRows):
            with obs.scope("sparse_rows_apply"):
                # unique=False rows (dedup skipped) are unsorted: declaring
                # sortedness would be a lie XLA is allowed to punish
                srt = u.unique and _sorted_decl(u.ids.shape[0], p)
                return p.at[u.ids].add(
                    u.rows.astype(p.dtype), mode="drop",
                    indices_are_sorted=srt)
        return p + u
    return jax.tree.map(one, params, updates,
                        is_leaf=lambda x: isinstance(x, SparseRows))


def sparse_grad_metrics(sparse_grads: Sequence[SparseRows]):
    """On-device observability of one sparse backward: per-table
    touched-row counts and gradient norms, jit-safe and near-free
    (the :mod:`~..utils.obs` layer's view into the sparse-optax pipeline).

    Returns ``{"touched_rows": [T] int32, "sparse_grad_norm": [T] f32}``
    aligned with ``sparse_grads`` — ``touched_rows`` counts the LIVE
    entries (ids below the vocab; pad/out-of-range sentinel entries at
    ``>= vocab`` excluded). :class:`SparseRows` built by
    :func:`sparse_value_and_grad` / :func:`unique_ids_static` carry
    sorted-unique ids, so there the live count IS the distinct-row count;
    hand-built rows with repeated ids count each repeat.
    ``sparse_grad_norm`` is the L2 norm of the live update rows. Log them
    next to the step metrics to see skew (a table whose touched count
    approaches its unique capacity every step is a dedup-win candidate; a
    norm spike localizes divergence to a table).
    """
    with obs.scope("sparse_grad_metrics"):
        touched, norms = [], []
        for g in sparse_grads:
            live = g.ids < g.vocab
            touched.append(jnp.sum(live.astype(jnp.int32)))
            rows = g.rows.astype(jnp.float32)
            norms.append(jnp.sqrt(jnp.sum(
                jnp.square(rows) * live[:, None].astype(rows.dtype))))
        return {"touched_rows": jnp.stack(touched),
                "sparse_grad_norm": jnp.stack(norms)}
