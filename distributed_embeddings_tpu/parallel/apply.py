"""Apply layer of the hybrid step: the manual sparse backward and the
per-width optimizer scatters.

One of the three executor modules the ``dist_embedding.py`` monolith
split into (:mod:`.exchange` / :mod:`.lookup` / apply). This module owns
everything after the dense backward: inverting the output collapse back
to worker order, packing the cotangent blocks for the reverse exchange
(:func:`~.exchange.pack_grad_blocks` + :func:`~.exchange.exchange_grads`),
rebuilding the per-group id streams from the forward residual, and the
ONE optimizer scatter per width slab (:func:`apply_width_streams`, the
:data:`~.schedule.PHASE_APPLY` phase family — ``sparse_apply_w{k}``).

Every function takes the owning
:class:`~.dist_embedding.DistributedEmbedding` as its first argument;
the split is pure code motion — the traced program is bit-for-bit what
the monolith's methods produced.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import obs
from ..ops import packed_slab as ps
from . import exchange as exchange_mod
from . import lookup as lookup_mod
from .lookup import _wkey, block_runs


def apply_width_streams(de, params, opt_state,
                        per_width: Dict[str, List], optimizer, lr,
                        scale, enable=None):
    """Concatenate each width's (logical ids, update rows) stream,
    lane-expand to physical full-tile rows, and run ONE optimizer scatter
    per width slab. Stateful-moment optimizers additionally receive the
    lane touch-mask (``ops/packed_slab.py:expand_touch_mask``) so packed
    neighbour rows keep their state.

    ``enable`` (scalar bool, traced): when False every update row is
    routed to the dropped sentinel — the scatters drop out of bounds,
    so the slabs AND every slab-shaped optimizer state component stay
    bitwise-unchanged. This is the non-finite guard's skip path: an
    O(ids) mask instead of a slab-wide select (which would read+write
    gigabytes of tables per step just to discard the result)."""
    new_params = dict(params)
    new_state = dict(opt_state) if isinstance(opt_state, dict) else opt_state
    wants_mask = getattr(optimizer, "needs_touch_mask", False)
    for k in sorted(per_width):
        with obs.scope(f"sparse_apply_{k}"):
            tris = per_width[k]
            w = tris[0][2]
            ids = jnp.concatenate([t[0].reshape(-1) for t in tris])
            if enable is not None:
                # disabled step: all rows -> logical sentinel (the same
                # dropped-row id the backward uses for OOB ids)
                ids = jnp.where(enable, ids,
                                jnp.asarray(de.rows_cap[w], ids.dtype))
            vals = jnp.concatenate(
                [t[1].reshape(-1, w) for t in tris]) * scale
            # lane-expand to physical rows: the scatter (and any dedup
            # in the optimizer) runs on full-tile rows; lane-disjoint
            # placement keeps per-logical-row semantics exact
            # (ops/packed_slab.py)
            phys_ids, pvals = ps.expand_update_rows(vals, ids, w)
            kw = {}
            if wants_mask:
                # compact [n, p] lane mask rides the optimizer's dedup
                # and expands to lanes after
                # (ops/packed_slab.py:lane_one_hot)
                m = ps.lane_one_hot(ids, w, dtype=pvals.dtype)
                if m is not None:
                    kw["mask"] = m
                    kw["lane_width"] = w
            slab = new_params[k]
            st = (new_state[k] if isinstance(new_state, dict)
                  else new_state)
            slab, st = optimizer.apply_rows(slab, st, phys_ids, pvals,
                                            lr, **kw)
            new_params[k] = slab
            if isinstance(new_state, dict):
                new_state[k] = st
    return new_params, new_state


def sparse_apply_gradients(de, params, opt_state, residuals, out_grads,
                           optimizer, lr, scale=None, enable=None):
    """Manual sparse backward + in-place optimizer update (the body of
    :meth:`~.dist_embedding.DistributedEmbedding.sparse_apply_gradients`;
    see that method's docstring for the full argument contract).

    Routes the output cotangents through the reverse all-to-all
    (:mod:`.exchange`), rebuilds the per-group id streams from the
    forward residual (:mod:`.lookup`'s ragged machinery), and applies
    per-row scatter updates via :func:`apply_width_streams` — never
    materializing dense table gradients. This is the IndexedSlices
    pipeline of the reference (``dist_model_parallel.py:526-567`` + the
    grad kernel) in SPMD form."""
    params = de.local_view(params)
    if isinstance(opt_state, dict):
        opt_state = de.local_view(opt_state)
    if scale is None:
        scale = 1.0 / de.world_size
    fallback = next(iter(params.values())).dtype
    per_width = cotangent_width_streams(de, residuals, out_grads,
                                        fallback_dtype=fallback)
    return apply_width_streams(de, params, opt_state, per_width,
                               optimizer, lr, scale, enable=enable)


def small_table_sums(g, ids4, grads, live, roff, sent, slot_major=False):
    """The stream of a small-table group (``GroupSpec.block``): per slot ONE
    dense block, rows ``roff .. roff + V - 1`` holding ``onehot(ids)^T @
    cotangents``, in place of a row an id. Slots of equal ``V`` share one
    batched matmul; the one-hot (a count matrix where ``hot > 1``) is exact
    in the cotangents' dtype and is the matmul's fused producer, never a
    buffer, and the sums accumulate in float32 and round once: what a
    dedup of the slot's rows would have made of them.

    ``ids4 [world, n, s, hot]`` are table-local ids and ``grads`` the
    cotangent rows they address (``mean`` already divided): for a dense
    group the slots' rows of the exchange row, ``[world, s, n, w]`` over the
    ``s = b`` samples; ``slot_major``, for a ragged group, the rows its
    ``take`` expanded, one array a run of :func:`block_runs`, ``[world,
    slots, s, w]`` over the ``s`` positions of the capacity, each its own
    column of the one-hot (``hot`` 1; a position that holds no id of the
    batch carries an id that matches no row). ``live [n]`` are this rank's
    table rows a slot (0: dead slot). A block row past ``live`` or that no
    id of the step touched goes to ``sent``: out-of-range ids and dead slots
    train nothing, and a lazy optimizer leaves an untouched row and its
    state alone. Returns ``(ids [sum V], vals [sum V, w])``."""
    world, n, s, hot = ids4.shape
    dtype = (grads[0] if slot_major else grads).dtype
    w = g.width
    precision = (lax.Precision.HIGHEST if dtype == jnp.float32
                 else None)  # 0/1 times float32 stays float32
    ids_l, vals_l = [], []
    for i, (v, k0, k1) in enumerate(block_runs(g.block)):
        # [slots, 1, world * s, hot] against the block's row numbers
        loc = ids4[:, k0:k1].transpose(1, 0, 2, 3).reshape(
            k1 - k0, 1, world * s, hot)
        row = lax.broadcasted_iota(loc.dtype, (1, v, 1, 1), 1)
        eq = loc == row
        onehot = jnp.sum(eq, axis=3, dtype=dtype)  # [slots, v, world * s]
        if slot_major:  # contract the sources and the positions in place
            lhs, rhs = onehot.reshape(k1 - k0, v, world, s), grads[i]
            dims = (((2, 3), (0, 2)), ((0,), (1,)))
        else:
            lhs = onehot
            rhs = grads[:, :, k0:k1].reshape(world * s, k1 - k0, w)
            dims = (((2,), (0,)), ((0,), (1,)))
        sums = lax.dot_general(lhs, rhs, dims, precision=precision,
                               preferred_element_type=jnp.float32
                               )  # [slots, v, w]
        at = row.reshape(1, v)
        keep = jnp.any(eq, axis=(2, 3)) & (at < live[k0:k1, None])
        ids_l.append(jnp.where(keep, at + roff[k0:k1, None], sent
                               ).reshape(-1))
        vals_l.append(sums.astype(dtype).reshape(-1, w))
    return jnp.concatenate(ids_l), jnp.concatenate(vals_l)


def cotangent_width_streams(de, residuals, out_grads, fallback_dtype=None,
                            tag: str = ""):
    """The sparse backward MINUS the optimizer scatter: route the output
    cotangents through the reverse all-to-all and rebuild the per-width
    ``(ids, update rows)`` streams from the forward residual. Split out
    of :func:`sparse_apply_gradients` so the pipelined step can build
    one stream set per microbatch (each behind its own
    ``grad_all_to_all_mb{k}`` exchange, overlapping other microbatches'
    dense compute) and MERGE them into the one
    :func:`apply_width_streams` scatter per width slab — grad
    accumulation across microbatches without a second pass over the
    slabs. ``tag`` suffixes the exchange scope (empty = the serialized
    step, byte-identical to the pre-split program)."""
    _, ids_recv, encs, b = residuals
    # single-worker no-combiner outputs keep their [b, h, w] rank
    # (reference call semantics); the exchange layout is flat columns
    out_grads = [g.reshape(g.shape[0], -1) for g in out_grads]
    world = de.world_size
    plan = de._get_plan(list(encs), b)

    # Invert the column-slice collapse then the input-order reorder,
    # rebuilding worker order. In fully-expanded coordinates, output entry
    # e has width worker_widths[rev[e]]; input i owns the next
    # slices-per-table[table(i)] expanded entries.
    worker_widths = [plan.out_width(inst) for inst in plan.instances]
    rev = de.strategy.rev_global_input_ids
    expanded: List[Optional[jax.Array]] = []
    e = 0
    for i, g in enumerate(out_grads):
        tid = de.strategy.input_table_map[i]
        k = de._slices_per_table[tid]
        if k == 1:
            expanded.append(g)
        elif tid in de.strategy.row_sliced_tables:
            # output was the SUM of row slices, so every slice's
            # cotangent is the full g (its own out-of-range rows drop)
            expanded.extend([g] * k)
        else:
            pos = 0
            for s in range(k):
                w = worker_widths[rev[e + s]]
                expanded.append(lax.slice(g, (0, pos), (b, pos + w)))
                pos += w
        e += k
    worker_grads: List[Optional[jax.Array]] = [None] * len(rev)
    for idx, g in enumerate(expanded):
        worker_grads[rev[idx]] = g

    # Pack [world, b, s_max] in the plan's column layout and reverse the
    # output all-to-all (autodiff of the forward exchange would insert the
    # same collective; reference rides Horovod's registered alltoall grad).
    out_dtype = (out_grads[0].dtype if out_grads else fallback_dtype)
    grads_by_worker = dict(zip(plan.instances, worker_grads))
    packed = exchange_mod.pack_grad_blocks(de, plan, grads_by_worker, b,
                                           out_dtype)
    mp_grad = exchange_mod.exchange_grads(de, packed, tag=tag)

    # Rank-uniform sparse update: per group, rebuild the id stream from
    # the forward's residual block and expand slot cotangents to per-id
    # update rows; per width, one optimizer scatter.
    my = de._my_rank()
    per_width: Dict[str, List] = {}
    for gi, g in enumerate(plan.groups):
        rows = de._plan_row(plan.rows[gi], my)
        roff = de._plan_row(plan.roff[gi], my)
        any_mean = bool(plan.mean[gi].any())
        all_mean = bool(plan.mean[gi].all())
        all_valid = bool((plan.valid[gi] > 0).all())
        valid = (None if all_valid
                 else de._plan_row(plan.valid[gi], my))
        rbase = (de._plan_row(plan.rbase[gi], my)
                 if plan.rsliced[gi].any() else None)
        sent = de.rows_cap[g.width]  # dropped-row sentinel (logical)
        region = lax.slice(ids_recv, (0, g.goff),
                           (world, g.goff + g.n * g.blen))
        gsl = lax.slice(mp_grad, (0, 0, g.col),
                        (world, b, g.col + g.n * g.width))
        gsl = gsl.reshape(world, b, g.n, g.width)
        if g.kind == "d":
            gb = gsl
            if g.hot > 1 and any_mean:
                if all_mean:
                    gb = gsl / g.hot
                else:
                    mean = de._plan_row(plan.mean[gi], my)
                    gb = jnp.where(mean[None, None, :, None] > 0,
                                   gsl / g.hot, gsl)
            ids4 = region.reshape(world, g.n, b, g.hot)
            if rbase is not None:  # row-sliced slots: range-local ids
                ids4 = ids4 - rbase[None, :, None, None]
        # a small-table group's sums run under the scope the width's scatter
        # will run in, so that a profile counts them to the apply, and under
        # a name of their own there
        if g.block:
            live = rows if valid is None else jnp.where(valid > 0, rows, 0)
        if g.block and g.kind == "d":
            with obs.scope(f"sparse_apply_{_wkey(g.width)}"), \
                    obs.scope("small_sum"):
                ids, vals = small_table_sums(g, ids4, gb, live, roff, sent)
        elif g.kind == "d":
            # b-major stream: the value rows are then exactly the
            # [world, b, n, w] grad layout — a FREE reshape of the
            # exchange row instead of a materialized transpose (the
            # [b, n*w] -> [n, b, w] copy + cast measured ~26 ms at the
            # DLRM headline shapes); only the small int id tensor
            # transposes. The optimizer sorts the stream anyway, so
            # stream order is free to choose (docs/perf_tpu.md r4).
            ids4 = ids4.transpose(0, 2, 1, 3)
            # out-of-range ids were clipped in the forward (safety net)
            # but are dropped here: a bad id trains nothing (see the
            # dist_embedding module docstring contract)
            ok = (ids4 >= 0) & (ids4 < rows[None, None, :, None])
            if valid is not None:
                ok = ok & (valid[None, None, :, None] > 0)
            ids = jnp.where(ok, ids4 + roff[None, None, :, None], sent)
            vals = jnp.broadcast_to(
                gb[:, :, :, None, :],
                (world, b, g.n, g.hot, g.width))
        else:
            values, _, seg, _, counts = lookup_mod.ragged_decode(
                de, g, b, region, rows, roff, valid,
                need_counts=any_mean, rbase=rbase)
            if rbase is not None:  # row-sliced slots: range-local ids
                values = values - rbase[None, :, None]
            if g.kind == "rw":
                # d(w_i * x_i)/dx_i: the weight multiplies the per-id
                # cotangent (the reference backward reuses the forward
                # kernel with the same weights input, .cu:539-627)
                wts = lookup_mod.region_weights(de, g, b, region)
            # What a position takes, it takes by its segment. A dead
            # position (seg == b) names the entry after its segments',
            # clipped into the buffer: its id goes to the sentinel below (in
            # a block it matches no row), so what it takes is never read.
            # Hence no entry is padded on for it, and the takes are spared
            # their fill: over the rows that is a select a matmul reading
            # them cannot absorb as the stream's ``* scale`` does.
            s_ix = jnp.arange(world, dtype=seg.dtype)[:, None, None]
            if any_mean:
                f_ix = jnp.arange(g.n, dtype=seg.dtype)[None, :, None]
                cval = jnp.take(
                    counts.reshape(-1),
                    ((s_ix * g.n + f_ix) * b + seg).reshape(-1), mode="clip"
                ).reshape(world, g.n, g.hot)
                mean = (None if all_mean
                        else de._plan_row(plan.mean[gi], my))

            def rows_of(k0, k1):
                """The update row of every position of slots ``k0 .. k1``,
                ``[world, slots, capacity, w]``: its segment's cotangent row
                straight out of the exchange row's ``[world, b, n, w]``
                layout (no transposed copy of it), times its weight, over
                its ``mean`` divisor."""
                f_ix = jnp.arange(k1 - k0, dtype=seg.dtype)[None, :, None]
                out = jnp.take(
                    gsl[:, :, k0:k1].reshape(-1, g.width),
                    ((s_ix * b + seg[:, k0:k1]) * (k1 - k0) + f_ix
                     ).reshape(-1), axis=0, mode="clip"
                ).reshape(world, k1 - k0, g.hot, g.width)
                if g.kind == "rw":
                    out = out * wts[:, k0:k1, :, None].astype(out.dtype)
                if any_mean:
                    div = out / cval[:, k0:k1, :, None].astype(out.dtype)
                    out = (div if all_mean else jnp.where(
                        mean[None, k0:k1, None, None] > 0, div, out))
                return out

            ok = (seg < b) & (values >= 0) & (values < rows[None, :, None])
            if valid is not None:
                ok = ok & (valid[None, :, None] > 0)
            if g.block:
                # The rows of one run of equal block rows at a time: what a
                # gather costs goes by whether its source stays near the
                # core, and a run's slice of the exchange row is likelier to
                # than the group's. A position that is not ok matches no
                # block row.
                runs = [rows_of(k0, k1) for _, k0, k1 in block_runs(g.block)]
                with obs.scope(f"sparse_apply_{_wkey(g.width)}"), \
                        obs.scope("ragged_sum"):
                    ids, vals = small_table_sums(
                        g, jnp.where(ok, values, -1)[..., None], runs, live,
                        roff, sent, slot_major=True)
            else:
                vals = rows_of(0, g.n)
                ids = jnp.where(ok, values + roff[None, :, None], sent)
        per_width.setdefault(_wkey(g.width), []).append(
            (ids, vals, g.width))

    return per_width
