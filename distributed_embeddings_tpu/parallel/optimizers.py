"""Sparse embedding optimizers over width-grouped 2-D table slabs.

The reference applies ``tf.IndexedSlices`` gradients through Keras optimizers'
sparse paths (``optimizer.apply_gradients`` after
``dist_model_parallel.py:526-567``), touching only the looked-up rows. optax
has no IndexedSlices, so dense-gradient training would read+write every table
row each step — the difference between HBM-bound O(touched rows) and
O(all rows). These optimizers reproduce the sparse behavior on the
*physical* slab rows used by
:class:`~distributed_embeddings_tpu.parallel.DistributedEmbedding` — for
narrow widths those are lane-packed ``[phys_rows, 128]`` tiles and the
caller hands in physical row ids plus lane-expanded update rows
(``ops/packed_slab.py``; lane-disjoint expansion keeps per-logical-row
semantics, including Adagrad's dedup, exact).

Performance notes (TPU): updates are native 2-D row scatters
(``slab.at[row_ids].add(values)``) — the one scatter form XLA's TPU backend
lowers efficiently. Flat 1-D windowed/element scatters lower to a serialized
path measured ~30x slower end-to-end; hence the width-grouped 2-D layout.
Invalid/padded ids equal the slab row capacity, land out of bounds, and are
dropped (``mode='drop'``) — the static-shape analogue of the reference's
dynamic ``num_unique``.

:class:`SparseAdagrad`, :class:`SparseMomentum` and :class:`SparseAdam` dedup
duplicate ids first (sort + segment-sum — the CUB sort/unique of the
reference backward, ``.cu:499-515``) because their updates read-modify-write
per-row state; :class:`SparseSGD` scatter-adds duplicates directly. Every
optimizer *declares* which regime it needs via the class attribute
``needs_dedup`` — the statically-enforced dedup pass budget
(:mod:`..analysis.hlo_census`, ``tools/hlo_audit.py --strict``) requires a
compiled step's ``detpu/dedup`` phase to hold ZERO row-op passes when the
optimizer says ``needs_dedup=False``. ``DETPU_SGD_DEDUP=1`` (read at step
build time) forces the dedup pass back into the SGD path for A/B
comparison: the trajectories are mathematically identical (SGD is linear in
the gradient), so the knob exists purely to measure what the skipped pass
would cost and to regression-test the equivalence. Numerics
match ``optax.sgd`` / ``optax.adagrad`` (initial accumulator 0.1, eps 1e-7) /
``optax.sgd(momentum=...)`` / ``optax.adam`` so the dense data-parallel side
can use optax and both families see the same optimizer semantics.

**Lazy moment semantics** (momentum/Adam): only the rows touched by a step
update their momentum/moment state; untouched rows' state neither decays nor
produces an update. This is what the reference gets from Keras optimizers'
sparse ``IndexedSlices`` path (``dist_model_parallel.py:526-567`` +
``optimizer.apply_gradients``) and what every production embedding trainer
uses — decaying millions of untouched rows per step would turn an O(touched)
update into an O(all rows) one. Consequence: trajectories equal dense optax
exactly when every row is touched every step, and diverge (lazily) when not.
Adam's bias correction uses the *global* step count, not a per-row count —
the LazyAdam convention.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.packed_slab import expand_lane_mask, pack_factor
from ..ops.sparse_grad import _dedup_sparse_grad, dedup_sparse_grad
from ..utils import envvars, obs

SGD_DEDUP_ENV = "DETPU_SGD_DEDUP"


def sgd_dedup_forced() -> bool:
    """Whether ``DETPU_SGD_DEDUP=1`` asks the linear (SGD) paths to run the
    dedup pass they would otherwise skip. Read at step-BUILD time (like
    ``with_metrics``): flipping the env after a step is compiled changes
    nothing until the step is rebuilt."""
    return envvars.enabled(SGD_DEDUP_ENV)


#: The forms of the one scatter-add a width slab gets: the same rows added
#: to the same slab, duplicates accumulating, out-of-range ids dropped.
#: XLA's TPU backend has two scatters, and the forms differ in which one
#: they reach (readings: ``PERF.md`` section 6, PR 31, on the v5e inside
#: the benchmark's cells and in isolation at their shapes):
#:
#: * the **sweep**, which a scatter declared sorted gets: one pass over the
#:   whole operand at about 530 GB/s of read plus write, the rows merged in
#:   as it goes with duplicates summed before they are rounded;
#: * **row at a time**, which an undeclared scatter gets while the stream
#:   is short for the slab: nothing paid for the slab, 73-79 ns a row, and
#:   every duplicate rounded into the slab's dtype on its own (a hot row of
#:   a bf16 slab then drifts: the one-hot cell read ``correct`` false).
#:
#: ``sort_fused`` sorts the ids and hands the sweep the permute of the rows
#: as its fused producer; ``unsorted`` leaves the choice to XLA, which
#: sorts and sweeps by itself once the stream is long for the slab, 2 %
#: cheaper than ``sort_fused``; ``dedup_rows`` sums duplicates in float32
#: first and then goes row at a time over the distinct rows only.
SCATTER_FORMS = ("sort_fused", "unsorted", "dedup_rows")

# What each scatter costs, as (ns a stream row, ns a slab byte); PERF.md
# section 6, PR 31, has every reading. The sweep alone: 15.2 ns a row and
# 4.04 ms a GiB fit the scatter fusion at all four (rows, slab) pairs of the
# benchmark (58.4, 135.8, 49.4 and 7.9 ms read; 58.4, 136.1, 49.2, 8.1 fitted).
# Row at a time alone: 24.4 ms for 328 k rows, 123.9 for 1.70 M, 288 for 4 M.
# The sweep's row term is linear in the stream's rows whatever they hold, so a
# small table's ids cost what a huge one's do; :func:`sums_densely` takes such
# a slot off the stream for a block of ``onehot(ids)^T @ cotangents`` at
# ``_ONEHOT_ELEM_NS`` an element of the one-hot (PERF.md section 6, PR 33: the
# one-hot cell's sweep 58.4 -> 43.1 ms for 1.70 M -> 0.68 M rows, the sums 3.1;
# PR 37, ragged slots, a row a position: the multi-hot cell's 146.1 -> 77.3 ms
# for 6.8 M -> 2.64 M rows, 72.28 + 5.03 read where the line gives 72.7 + 5.0,
# the sums 12.3).
_SWEEP_NS = (15.2, 4.04e6 / 2 ** 30)
_RMW_ROW_NS = 75.0
_SCATTER_NS = {
    # the sweep and the key sort beside it (2.04 ms for 1.70 M, 13.1 for 6.8 M)
    "sort_fused": (_SWEEP_NS[0] + 1.9, _SWEEP_NS[1]),
    # XLA's own sort and sweep: 146.1 ms for 6.8 M rows where ours read 148.9
    "unsorted": (_SWEEP_NS[0] + 1.5, _SWEEP_NS[1]),
    # every row distinct, and the sort, the permute and the float32 sums
    # before it: 6.8 ms for 328 k rows, 38 for 1.70 M
    "dedup_rows": (_RMW_ROW_NS + 22.0, 0.0),
}
# XLA's own lowering of an undeclared scatter was the sweep at 1 268 slab
# bytes a stream row and below, and row at a time at 2 160 and above:
# ``unsorted`` is admitted only where the sweep has been read.
_XLA_SWEEPS_BELOW_BYTES_A_ROW = 1300
# distinct rows a step of the row-at-a-time loop; 8 k to 64 k read the same
_RMW_CHUNK = 8192
# One element of a small table's one-hot in the backward's dense sum
# (``parallel/apply.py``: the compare, the convert, its multiply-add on the
# MXU and its part of the touched-rows reduce): ``small_sum`` read 3.086 ms a
# step for 21 120 block rows x 65 536 samples in the one-hot cell (PERF.md
# section 6, PR 33). A block of 6 800 rows then costs what its 65 536 ids
# cost the sweep. Ragged slots have the class too, a column of the one-hot a
# position of the capacity: ``ragged_sum`` read 12.29 ms for the same 21 120
# block rows x 262 144 positions in the multi-hot cell, 2.22 ps an element
# (PR 37), so one constant serves both; a block of 7 450 rows costs what
# 262 144 positions cost the sweep.
_ONEHOT_ELEM_NS = 2.23e-3
# a block holds whole tiles of the matmul's output rows
_BLOCK_TILE = 128
# A slot of padding a sample, in the forward and the exchanges: the gather
# reads 12.4 ns a row (``lookup_ms`` 8.93 for 11 slots x 65 536 rows, four
# chips) and the three exchanges 6.5 ns a slot of 128 columns
# (``exchange_ms`` 3.07 for 928 columns; ledger, PR 32).
_SLOT_NS = 18.9
# a count matrix of hotness past this is not exact in bfloat16
_COUNT_EXACT = 256


def block_rows(table_rows: int) -> int:
    """Rows of the dense block a table of ``table_rows`` rows is summed into."""
    return -(-table_rows // _BLOCK_TILE) * _BLOCK_TILE


def small_sum_ns(blocks, ids: int) -> float:
    """What it costs to sum slots of ``ids`` ids a step each into dense
    blocks of ``blocks`` rows: the one-hots' elements, and the blocks' rows
    in the scatter's stream."""
    return sum(blocks) * (_ONEHOT_ELEM_NS * ids
                          + _SCATTER_NS["sort_fused"][0])


def padded_slots_ns(slots: int, samples: int) -> float:
    """What ``slots`` more slots in a plan's layout cost a step of
    ``samples`` samples before the backward: a gathered row and a slot's
    columns in each exchange, dead or not."""
    return _SLOT_NS * slots * samples


def sums_densely(table_rows: int, ids: int, hot: int = 1) -> bool:
    """Whether a slot that sends ``ids`` ids a step into a table of
    ``table_rows`` rows should leave the scatter's stream: the backward then
    sums its cotangents as ``onehot(ids)^T @ cotangents`` into one block of
    the table's rows, and the stream gets the block's rows in place of a row
    an id. It should where that costs less than the ids cost the cheapest
    sweep. A dense slot sends ``hot`` ids a sample, which meet in one column
    of a count matrix; a ragged slot sends the positions of its capacity,
    every source's and dead ones too, each a column of its own (``hot`` 1)."""
    return (hot <= _COUNT_EXACT
            and small_sum_ns([block_rows(table_rows)], ids)
            < scatter_ns("sort_fused", ids, 0))


def scatter_ns(form: str, rows: int, slab_bytes: int) -> float:
    """What ``form`` costs for ``rows`` update rows into ``slab_bytes``."""
    per_row, per_byte = _SCATTER_NS[form]
    return per_row * rows + per_byte * slab_bytes


def declare_sorted(rows: int, slab_bytes: int) -> bool:
    """Whether a scatter of ``rows`` ids that ARE sorted and distinct should
    say so: declared it is the sweep, undeclared row at a time."""
    return (_SWEEP_NS[0] * rows + _SWEEP_NS[1] * slab_bytes
            <= _RMW_ROW_NS * rows)


def scatter_form(rows: int, slab_bytes: int) -> str:
    """The cheapest of :data:`SCATTER_FORMS` for ``rows`` update rows into a
    slab of ``slab_bytes``: row at a time where even a stream of all
    distinct rows costs less that way than one pass over the slab, else the
    sweep, by XLA's own hand where that is known to be the sweep."""
    forms = [f for f in SCATTER_FORMS if f != "unsorted"
             or slab_bytes <= _XLA_SWEEPS_BELOW_BYTES_A_ROW * rows]
    return min(forms, key=lambda f: scatter_ns(f, rows, slab_bytes))


def _scatter_dedup_rows(slab, ids, vals):
    """Sum the rows of equal ids in float32, then add each distinct row to
    the slab once, row at a time, in steps of ``_RMW_CHUNK`` rows, as many
    steps as hold distinct rows (the tail of the buffers is padding)."""
    rows = slab.shape[0]
    # the unscoped body of dedup_sparse_grad: this is the scatter's own
    # work, not the ``dedup`` phase the pass budgets count
    uids, sums = _dedup_sparse_grad(ids, vals, rows, None, None,
                                    sum_dtype=jnp.float32)
    # distinct rows sort ahead of the dropped ones (``rows`` and past it)
    distinct = jnp.sum(uids < rows)
    chunk = min(_RMW_CHUNK, ids.shape[0])
    pad = -ids.shape[0] % chunk
    uids = jnp.pad(uids, (0, pad), constant_values=rows)
    sums = jnp.pad(sums.astype(slab.dtype), ((0, pad), (0, 0)))

    def step(i, slab):
        at = i * chunk
        return slab.at[lax.dynamic_slice_in_dim(uids, at, chunk)].add(
            lax.dynamic_slice_in_dim(sums, at, chunk), mode="drop")

    return lax.fori_loop(0, (distinct + chunk - 1) // chunk, step, slab)


def _scatter_add_as(form: str, slab: jax.Array, ids: jax.Array,
                    vals: jax.Array) -> jax.Array:
    """``slab.at[ids].add(vals, mode="drop")`` in the given form."""
    if form == "unsorted":
        return slab.at[ids].add(vals, mode="drop")
    if form == "dedup_rows":
        return _scatter_dedup_rows(slab, ids, vals)
    sorted_ids, perm = lax.sort_key_val(
        ids, jnp.arange(ids.shape[0], dtype=jnp.int32))
    upd = jnp.take(vals, perm, axis=0)  # fuses into the scatter
    return slab.at[sorted_ids].add(upd, mode="drop",
                                   indices_are_sorted=True)


def _sorted_scatter_add(slab: jax.Array, ids: jax.Array,
                        vals: jax.Array) -> jax.Array:
    """``slab.at[ids].add(vals, mode="drop")`` in the form
    :func:`scatter_form` picks from the stream's rows and the slab's
    bytes, both static at trace time. The form's name is the scope the
    scatter runs under (``sparse_apply_w{k}/scatter_<form>``), so a
    profile says which one engaged."""
    if not ids.shape[0]:
        return slab
    form = scatter_form(ids.shape[0], slab.size * slab.dtype.itemsize)
    with obs.scope(f"scatter_{form}"):
        return _scatter_add_as(form, slab, ids, vals)


class SparseSGD:
    """Plain SGD on slab rows; duplicate ids accumulate via scatter-add.

    ``needs_dedup=False``: the update is linear in the gradient, so
    duplicate ids are scatter-add-safe (``ops/sparse_grad.py``) and the
    sort + segment-sum dedup pass is skipped entirely — the first
    statically-verified pass cut of ROADMAP 3(a); ``tools/hlo_audit.py
    --strict`` pins the compiled dedup phase to zero row ops on this path.
    The one scatter-add takes the form :func:`scatter_form` picks for the
    stream and the slab; its ``dedup_rows`` form sums duplicates itself,
    inside the scatter's scope and for the scatter's sake (a short stream
    into a huge slab then skips the pass over the slab), not as a phase.
    ``DETPU_SGD_DEDUP=1`` forces the pass back in for A/B (mathematically
    identical; floating-point-identical too whenever the per-row sums are
    exact, which the equivalence test engineers)."""

    needs_dedup = False
    #: streaming moment hygiene: SGD carries no slab state to reset
    fresh_row_fill = 0.0

    def init(self, params):
        return jax.tree.map(lambda _: (), params)

    def apply_rows(self, slab: jax.Array, state, ids: jax.Array,
                   vals: jax.Array, lr):
        """``slab[ids] -= lr * vals``; ids >= slab rows are dropped."""
        if sgd_dedup_forced():
            # A/B escape hatch: pre-sum duplicate rows exactly like the
            # stateful optimizers do, then scatter the unique rows
            uids, uvals = dedup_sparse_grad(ids, vals,
                                            pad_id=slab.shape[0],
                                            max_unique=slab.shape[0] + 1)
            return slab.at[uids].add(
                (-lr * uvals).astype(slab.dtype), mode="drop",
                indices_are_sorted=True), state
        slab = _sorted_scatter_add(slab, ids,
                                   -lr * vals.astype(slab.dtype))
        return slab, state


class SparseAdagrad:
    """Adagrad with slab-shaped accumulators; optax.adagrad numerics
    (accumulator init 0.1, ``param -= lr * g * rsqrt(acc_new + eps)``).

    Two execution regimes, chosen per call by a measured cost model:

    ``needs_dedup=True``: the accumulator update is nonlinear in the
    gradient, so duplicate rows must be summed before the rsqrt (the
    sparse regime's sort + segment-sum pass, budgeted by the HLO census).

    * **sparse** (stream << slab rows): sort-dedup the id stream, then
      per-unique-row accumulator read-modify-write — 4-5 random row ops on
      the stream at the TPU's ~10-15 ns/row descriptor floor;
    * **dense-apply** (stream > slab rows / ``dense_apply_ratio``): ONE
      scatter-add sums the stream into a zero gradient slab, then the
      Adagrad transition runs elementwise over the whole slab at streaming
      HBM rates (~0.6 ns/row) — numerically identical, because an untouched
      row sees ``g = 0``: ``acc + 0*0 == acc`` and ``param - lr*0*rsqrt ==
      param``. This is what collapsed the tiny-zoo w=16 group's 2.9M-id
      stream cost (VERDICT r3 Weak #3): 4 full-stream row ops became one
      scatter + slab-wide elementwise passes.
    """

    needs_dedup = True

    def __init__(self, initial_accumulator_value: float = 0.1,
                 eps: float = 1e-7, dense_apply_ratio: float = 6.0):
        self.initial_accumulator_value = initial_accumulator_value
        # streaming moment hygiene (parallel/streaming.py commit): a
        # freshly admitted slot's accumulator resets to the same value a
        # fresh table init would give it
        self.fresh_row_fill = initial_accumulator_value
        self.eps = eps
        # dense-apply wins when stream * ratio > slab rows: the sparse path
        # pays ~4.5 random row ops/stream row at 10-15 ns, the dense path
        # ~5 slab-wide streams at ~0.6 ns/row plus the one scatter both pay.
        # None disables the dense path (e.g. when HBM can't hold one extra
        # slab-sized transient).
        self.dense_apply_ratio = dense_apply_ratio

    def init(self, params):
        return jax.tree.map(
            lambda p: jnp.full_like(p, self.initial_accumulator_value), params)

    def apply_rows(self, slab: jax.Array, accum: jax.Array, ids: jax.Array,
                   vals: jax.Array, lr):
        # moments accumulate in the ACCUMULATOR dtype: with bf16 tables +
        # fp32 accumulators, g*g must square in fp32 or the carefully
        # preserved fp32 state would hold bf16-precision statistics
        vals = vals.astype(accum.dtype)
        if (self.dense_apply_ratio is not None
                and vals.shape[0] * self.dense_apply_ratio > slab.shape[0]):
            # dense-apply regime: one scatter-sum, then elementwise Adagrad
            # over the slab (exact — untouched rows see g=0, a no-op)
            g = _sorted_scatter_add(jnp.zeros(slab.shape, accum.dtype),
                                    ids, vals)
            new_acc = accum + g * g
            # update computes in the accumulator dtype but must not promote
            # the slab (bf16 tables + fp32 accumulators would silently turn
            # fp32 here where the sparse regime's scatter keeps bf16)
            slab = slab - (lr * g * lax.rsqrt(new_acc + self.eps)
                           ).astype(slab.dtype)
            return slab, new_acc
        # nonlinear in g: must sum duplicate rows before the rsqrt.
        # vocab bound: distinct physical rows <= slab rows + sentinel, so
        # the unique buffers (and the accumulator ops on them) shrink to
        # min(stream, rows+1) — a large win for small-vocab width groups
        uids, uvals = dedup_sparse_grad(ids, vals, pad_id=slab.shape[0],
                                        max_unique=slab.shape[0] + 1)
        acc_rows = jnp.take(accum, uids, axis=0, mode="clip")
        new_acc = acc_rows + uvals * uvals
        # uids are sorted but NOT formally unique: the dedup tail repeats the
        # pad sentinel (slab row capacity). unique_indices=True would violate
        # XLA's contract (implementation-defined); sorted + mode='drop' keeps
        # the fast path and drops every sentinel copy out of bounds.
        accum = accum.at[uids].set(new_acc, mode="drop",
                                   indices_are_sorted=True)
        # optax scale_by_rss semantics: g * rsqrt(acc_new + eps); computed
        # in the accumulator dtype, cast to the slab's (mixed bf16/fp32)
        update = (lr * uvals * lax.rsqrt(new_acc + self.eps)
                  ).astype(slab.dtype)
        slab = slab.at[uids].add(-update, mode="drop",
                                 indices_are_sorted=True)
        return slab, accum


def _dedup_with_mask(ids, vals, mask, lane_width, pad_id):
    """Dedup vals (and, when given, a compact ``[n, p]`` lane touch-mask,
    ``ops/packed_slab.py:lane_one_hot``) by id in ONE sort + segment-sum:
    the mask rides as ``p`` extra columns (p/128 of the value payload) and
    is expanded to lane placement only after dedup. Returns
    ``(uids, uvals, touched)`` with ``touched=None`` when no mask.

    Why a mask: stateful-moment updates are nonzero wherever *state* is
    nonzero, so after duplicate physical rows are summed, lanes belonging to
    packed neighbour logical rows (``ops/packed_slab.py``) must be masked
    out of the state transition — a zero gradient cannot encode "untouched"
    (a touched row may legitimately have zero gradient)."""
    if mask is None:
        if lane_width is not None and pack_factor(lane_width) > 1:
            # without the mask, summed duplicate physical rows would count
            # packed *neighbour* logical rows as touched and corrupt their
            # momentum/moment state (ADVICE r3) — refuse rather than corrupt
            raise ValueError(
                f"lane_width={lane_width} is a packed width "
                f"(p={pack_factor(lane_width)}) but no lane touch-mask was "
                "given; build one with ops.packed_slab.lane_one_hot(ids, "
                "lane_width) or omit lane_width only for widths >= 128")
        uids, uvals = dedup_sparse_grad(ids, vals, pad_id=pad_id,
                                        max_unique=pad_id + 1)
        return uids, uvals, None
    if lane_width is None:
        raise ValueError(
            "mask requires lane_width (the logical row width the [n, p] "
            "lane mask expands to; 128//p is wrong for odd widths)")
    both = jnp.concatenate([vals, mask.astype(vals.dtype)], axis=1)
    uids, uboth = dedup_sparse_grad(ids, both, pad_id=pad_id,
                                    max_unique=pad_id + 1)
    w = vals.shape[1]
    touched = expand_lane_mask(uboth[:, w:], lane_width, phys_w=w)
    return uids, uboth[:, :w], touched


class SparseMomentum:
    """Heavy-ball SGD with lazy row-wise momentum; ``optax.sgd(momentum=m)``
    (``optax.trace``) numerics: ``trace = g + decay * trace``,
    ``param -= lr * trace`` (``nesterov`` applies the optax formula
    ``g + decay * trace_new``). See the module docstring for the lazy
    semantics of untouched rows."""

    needs_dedup = True
    needs_touch_mask = True
    #: streaming moment hygiene: momentum traces init (and reset) to zero
    fresh_row_fill = 0.0

    def __init__(self, momentum: float = 0.9, nesterov: bool = False):
        self.momentum = momentum
        self.nesterov = nesterov

    def init(self, params):
        return jax.tree.map(jnp.zeros_like, params)

    def apply_rows(self, slab: jax.Array, trace: jax.Array, ids: jax.Array,
                   vals: jax.Array, lr, mask=None, lane_width=None):
        vals = vals.astype(trace.dtype)  # momentum state sets the precision
        # read-modify-write of per-row trace: duplicates must sum first
        uids, uvals, touched = _dedup_with_mask(
            ids, vals, mask, lane_width, pad_id=slab.shape[0])
        t_rows = jnp.take(trace, uids, axis=0, mode="clip")
        t_new = uvals + self.momentum * t_rows
        if touched is not None:  # packed neighbours keep their state
            t_new = jnp.where(touched, t_new, t_rows)
        trace = trace.at[uids].set(t_new, mode="drop",
                                   indices_are_sorted=True)
        step = (uvals + self.momentum * t_new) if self.nesterov else t_new
        if touched is not None:
            step = jnp.where(touched, step, 0.0)
        slab = slab.at[uids].add((-lr * step).astype(slab.dtype),
                                 mode="drop", indices_are_sorted=True)
        return slab, trace


class SparseAdam:
    """Adam with lazy row-wise moments; ``optax.adam`` numerics
    (``scale_by_adam``: ``mu = b1*mu + (1-b1)*g``, ``nu = b2*nu +
    (1-b2)*g^2``, hat-corrected by the optimizer-global step count — the
    LazyAdam convention, see module docstring).

    State per width slab: ``(mu, nu, count)`` where ``count`` rides as a
    ``[..., 1, 1]`` array so it shards/squeezes uniformly with the slabs."""

    needs_dedup = True
    needs_touch_mask = True
    #: streaming moment hygiene: mu/nu init (and reset) to zero; the
    #: non-slab step count is never touched (shape-matched in commit)
    fresh_row_fill = 0.0

    def __init__(self, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8, eps_root: float = 0.0):
        self.b1, self.b2 = b1, b2
        self.eps, self.eps_root = eps, eps_root

    def init(self, params):
        def one(p):
            cnt_shape = (p.shape[0], 1, 1) if p.ndim == 3 else (1, 1)
            return (jnp.zeros_like(p), jnp.zeros_like(p),
                    jnp.zeros(cnt_shape, jnp.float32))
        return jax.tree.map(one, params)

    def apply_rows(self, slab: jax.Array, state, ids: jax.Array,
                   vals: jax.Array, lr, mask=None, lane_width=None):
        mu, nu, count = state
        vals = vals.astype(mu.dtype)  # moments set the precision
        uids, uvals, touched = _dedup_with_mask(
            ids, vals, mask, lane_width, pad_id=slab.shape[0])
        count = count + 1.0
        t = count.reshape(())  # scalar step for bias correction
        mu_rows = jnp.take(mu, uids, axis=0, mode="clip")
        nu_rows = jnp.take(nu, uids, axis=0, mode="clip")
        mu_new = self.b1 * mu_rows + (1.0 - self.b1) * uvals
        nu_new = self.b2 * nu_rows + (1.0 - self.b2) * uvals * uvals
        if touched is not None:  # packed neighbours keep their state
            mu_new = jnp.where(touched, mu_new, mu_rows)
            nu_new = jnp.where(touched, nu_new, nu_rows)
        mu = mu.at[uids].set(mu_new, mode="drop", indices_are_sorted=True)
        nu = nu.at[uids].set(nu_new, mode="drop", indices_are_sorted=True)
        mu_hat = mu_new / (1.0 - self.b1 ** t)
        nu_hat = nu_new / (1.0 - self.b2 ** t)
        update = lr * mu_hat / (jnp.sqrt(nu_hat + self.eps_root) + self.eps)
        if touched is not None:
            update = jnp.where(touched, update, 0.0)
        slab = slab.at[uids].add(-update.astype(slab.dtype), mode="drop",
                                 indices_are_sorted=True)
        return slab, (mu, nu, count)
