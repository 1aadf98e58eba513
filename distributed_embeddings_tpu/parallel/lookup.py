"""Lookup layer of the hybrid step: plan-driven gathers and combiners.

One of the three executor modules the ``dist_embedding.py`` monolith
split into (:mod:`.exchange` / lookup / :mod:`.apply`). This module owns
everything between the two forward exchanges: decoding the received
group regions, the per-(width, kind) slab gathers, combiner reductions,
and the shared ragged CSR machinery the backward reuses.

Each (width, kind) group runs under its own ``obs.scope`` in the
:data:`~.schedule.PHASE_LOOKUP` phase family (``lookup_w{w}_{kind}``),
so profiles, the HLO census, and the schedule auditor attribute
gather/combine cost to the width it serves.

Every function takes the owning
:class:`~.dist_embedding.DistributedEmbedding` as its first argument
(except the pure shape helpers); the split is pure code motion — the
traced program is bit-for-bit what the monolith's methods produced.
"""

from __future__ import annotations

import functools
import itertools
from typing import List

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import obs
from ..ops.embedding_lookup import ragged_row_ids
from ..ops import packed_slab as ps

#: positions of one tile of a small-table ragged group's within-tile prefix
#: (:func:`block_bag_sums`): one pass of the MXU's 128 x 128 array a tile
_PREFIX_TILE = 128
#: positions a piece of :func:`block_bag_sums` holds at most: a piece keeps
#: its rows' float32 prefix live (128 MiB at width 128), so that pieces
#: follow each other and the step's peak does not grow with the slots
_PIECE_POSITIONS = 1 << 18


def _wkey(width: int) -> str:
    return f"w{width}"


def csr_splits(lengths) -> jax.Array:
    """CSR offsets ``[..., b+1]`` from per-row lengths ``[..., b]``."""
    zero = jnp.zeros(lengths.shape[:-1] + (1,), lengths.dtype)
    return jnp.concatenate([zero, jnp.cumsum(lengths, axis=-1)], axis=-1)


def csr_seg(lengths, cap: int):
    """CSR offsets and per-position segment ids from per-row lengths,
    for any leading batch dims: ``lengths [..., b]`` ->
    ``(splits [..., b+1], seg [..., cap])`` with positions past each
    CSR's total mapped to ``b``. The one derivation every ragged path
    shares (the reference's ``RowToSplit``/``OffsetToWeightsAndRowId``
    pair, ``embedding_lookup_kernels.cu:331-361``)."""
    lead = lengths.shape[:-1]
    b = lengths.shape[-1]
    splits = csr_splits(lengths.reshape(-1, b))
    seg = jax.vmap(functools.partial(ragged_row_ids, capacity=cap))(
        splits)
    return splits.reshape(*lead, b + 1), seg.reshape(*lead, cap)


def ragged_decode(de, g, b: int, region, rows, roff, valid,
                  need_counts: bool = True, rbase=None):
    """Decode one ragged group region ``[world, n*(cap+b)]`` into
    ``(values, lengths, seg, grow, counts)``, all ``[world, n, ...]``.
    Dead slots get zero lengths, so every position routes to the dropped
    segment ``b``. ``valid=None`` means every slot is statically live
    (skips the mask multiply); ``need_counts=False`` skips the
    mean-divisor counts (sum-only groups never read them); ``rbase``
    (row-sliced slots) is subtracted from the raw values before the
    clip — ``values`` stays raw so callers mask consistently."""
    world = de.world_size
    with obs.scope("ragged_decode"):
        r3 = region.reshape(world, g.n, g.blen)
        values = r3[:, :, :g.hot]
        lengths = r3[:, :, g.hot:g.hot + b]  # "rw" blocks carry weight
        # bits past the lengths (decoded by region_weights)
        if valid is not None:
            lengths = lengths * valid[None, :, None].astype(r3.dtype)
        _, seg = csr_seg(lengths, g.hot)
        loc = (values - rbase[None, :, None] if rbase is not None
               else values)
        grow = (jnp.clip(loc, 0, (rows - 1)[None, :, None])
                + roff[None, :, None])
        counts = jnp.maximum(lengths, 1) if need_counts else None
        return values, lengths, seg, grow, counts


def ragged_reads(de, plan, gi: int, my, loc, rows) -> jax.Array:
    """Where a ragged position of group ``gi`` reads its row, ``loc``
    table-local ids ``[world, n, cap]``: in its table's range, and on an
    unsliced slot anywhere (its id clipped) unless ``masked_reads``."""
    inr = ((loc >= 0) & (loc < rows[None, :, None]))
    if not de.masked_reads:  # only sliced slots mask
        rsl = de._plan_row(plan.rsliced[gi], my)
        inr = inr | (rsl[None, :, None] == 0)
    return inr


def region_weights(de, g, b: int, region) -> jax.Array:
    """Decode a weighted-ragged ("rw") region's per-id weights
    ``[world, n, cap]`` from the bitcast payload past the lengths."""
    world = de.world_size
    r3 = region.reshape(world, g.n, g.blen)
    bits = r3[:, :, g.hot + b:].astype(jnp.int32)
    return lax.bitcast_convert_type(bits, jnp.float32)


def ragged_scatter_idx(g, b: int, world: int, seg) -> jax.Array:
    """Flattened per-value output index into a ``[world*n*(b+1), w]``
    segment buffer; row ``b`` of each slot is the dropped sentinel."""
    s_ix = jnp.arange(world, dtype=seg.dtype)[:, None, None]
    f_ix = jnp.arange(g.n, dtype=seg.dtype)[None, :, None]
    return (s_ix * g.n + f_ix) * (b + 1) + seg


def block_runs(block):
    """``(block rows, first slot, end slot)`` of each run of equal block
    rows of a small-table group: the slots that share one batched matmul."""
    k0 = 0
    for v, run in itertools.groupby(block):
        k1 = k0 + len(list(run))
        yield v, k0, k1
        k0 = k1


def block_bag_sums(g, slab, rows, roff, splits, ids, wts=None, div=None,
                   out_dtype=None) -> jax.Array:
    """The bags of a small-table ragged group (``GroupSpec.block``) summed
    with no scatter: ``[world, n, b, width]`` in ``out_dtype`` (default the
    slab's).

    A position's row is ``onehot(id) @ T`` on the MXU, ``T`` the slot's
    block rows fetched once from the slab; the one-hot is the matmul's fused
    producer and the product is exact (one 1 a row). The rows' inclusive
    prefix ``A`` within each tile of :data:`_PREFIX_TILE` positions is a
    lower-triangular ones matrix times the tile, in float32. A bag's
    positions are contiguous (CSR), so a bag ``[s, e)`` within one tile is
    ``A[e-1] - A[s-1]`` (no second term where ``s`` starts the tile); one
    that crosses a boundary is the rest of its first tile (the tile's total
    less ``A[s-1]``) plus ``A[e-1]``, and the tiles between, where there
    are any, from a float32 prefix of the totals. One gather of ``b + 1``
    rows a slot at the splits serves both ends (``s_i - 1 = e_{i-1} - 1``).
    An empty bag, and every bag of a dead slot (lengths zeroed), reads
    nothing, nor does any position past the last split, whatever id it
    holds.

    ``splits [world, n, b + 1]`` are each source's CSR offsets clipped into
    the capacity; ``ids [world, n, capacity]`` table-local ids, clipped into
    the slot's table, and -1 where a position must read zero (row-sliced or
    ``masked_reads`` slots); ``rows``, ``roff [n]`` the slots' table rows
    and slab offsets on this rank; ``wts`` the ``"rw"`` weights, which
    multiply the rows in float32; ``div [world, n, b]`` the ``mean``
    divisor, 1 where a slot sums. The sums round once. A run of equal block
    rows (:func:`block_runs`) goes in pieces of at most
    :data:`_PIECE_POSITIONS` positions."""
    world, _, cap = ids.shape
    b = splits.shape[-1] - 1
    t, w = _PREFIX_TILE, g.width
    cap_p = -(-cap // t) * t
    nt = cap_p // t
    step = max(1, _PIECE_POSITIONS // (world * cap_p))
    pad = ((0, 0), (0, 0), (0, cap_p - cap))
    ids = jnp.pad(ids, pad, constant_values=-1)
    if wts is not None:
        wts = jnp.pad(wts, pad)

    def lead(x, k0, k1):
        # slots k0 .. k1 lead: [slots * world, ...], one matmul batch a row
        return x[:, k0:k1].transpose(1, 0, *range(2, x.ndim)).reshape(
            (k1 - k0) * world, *x.shape[2:])

    tri = (lax.broadcasted_iota(jnp.int32, (t, t), 0)
           >= lax.broadcasted_iota(jnp.int32, (t, t), 1))
    outs = []
    for v, k0, k1 in block_runs(g.block):
        for j0 in range(k0, k1, step):
            j1 = min(j0 + step, k1)
            s = j1 - j0
            at = lax.broadcasted_iota(roff.dtype, (1, v), 1)
            tab = ps.packed_gather(slab, roff[j0:j1, None] + at, w)
            # rows past the slot's table are another table's: no id reads
            # them, and a zero keeps a non-finite one out of the product
            tab = jnp.where((at < rows[j0:j1, None])[..., None], tab, 0)
            dtype = tab.dtype
            prec = lax.Precision.HIGHEST if dtype == jnp.float32 else None
            pid = lead(ids, j0, j1).reshape(s, world * cap_p, 1)
            onehot = (pid == lax.broadcasted_iota(pid.dtype, (1, 1, v), 2)
                      ).astype(dtype)
            got = lax.dot_general(onehot, tab, (((2,), (1,)), ((0,), (0,))),
                                  precision=prec)  # [s, world * cap_p, w]
            if wts is not None:
                got = (got.astype(jnp.float32)
                       * lead(wts, j0, j1).reshape(s, world * cap_p, 1))
            prec = (lax.Precision.HIGHEST if got.dtype == jnp.float32
                    else None)
            a = jnp.einsum("ij,njw->niw", tri.astype(got.dtype),
                           got.reshape(s * world * nt, t, w), precision=prec,
                           preferred_element_type=jnp.float32
                           ).reshape(s * world, nt, t, w)
            tot = a[:, :, t - 1]                       # [s * world, nt, w]
            cum = jnp.cumsum(tot, axis=1)
            sp = lead(splits, j0, j1)                  # [s * world, b + 1]
            base = jnp.arange(s * world, dtype=sp.dtype)[:, None]
            hi = jnp.take(a.reshape(-1, w),
                          (base * cap_p + jnp.maximum(sp - 1, 0)).reshape(-1),
                          axis=0, mode="clip").reshape(s * world, b + 1, w)
            st, en = sp[:, :-1], sp[:, 1:]
            ts, te = st // t, jnp.maximum(en - 1, 0) // t
            per_tile = jnp.concatenate([tot, cum], axis=2).reshape(-1, 2 * w)
            at_ts = jnp.take(per_tile, (base * nt + ts).reshape(-1), axis=0,
                             mode="clip").reshape(s * world, b, 2 * w)
            cum_te = jnp.take(cum.reshape(-1, w),
                              (base * nt + jnp.maximum(te - 1, 0)).reshape(-1),
                              axis=0, mode="clip").reshape(s * world, b, w)
            lo = jnp.where((st % t != 0)[..., None], hi[:, :-1], 0)
            mid = jnp.where((te > ts + 1)[..., None],
                            cum_te - at_ts[..., w:], 0)
            bag = hi[:, 1:] + jnp.where((te > ts)[..., None],
                                        at_ts[..., :w] - lo + mid, -lo)
            bag = jnp.where((en > st)[..., None], bag, 0)
            if div is not None:
                bag = bag / lead(div, j0, j1)[..., None].astype(bag.dtype)
            outs.append(bag.reshape(s, world, b, w).transpose(1, 0, 2, 3))
    return jnp.concatenate(outs, axis=1).astype(out_dtype or slab.dtype)


def plan_lookup(de, plan, params, ids_recv, tag: str = "") -> jax.Array:
    """All local lookups in exchange-row layout ``[world, b, s_max]``
    (``compute_dtype`` — the pre-comm mixed-precision cast, reference
    ``dist_model_parallel.py:300``). Dead slots produce garbage columns
    that no consumer ever slices. ``tag`` suffixes the group scopes
    (the pipelined step's ``_mb{k}`` instances; empty = serialized)."""
    world = de.world_size
    b = plan.b
    # plan_lookup_groups already casts to compute_dtype; only the
    # no-groups zeros fallback needs the explicit dtype
    zdt = (de.compute_dtype
           or next(iter(params.values())).dtype)
    sections = [
        red.transpose(0, 2, 1, 3).reshape(world, b, -1)
        for red in plan_lookup_groups(de, plan, params, ids_recv,
                                      tag=tag)]
    return (jnp.concatenate(sections, axis=2) if sections
            else de._vary(jnp.zeros((world, b, plan.s_max), zdt)))


def plan_lookup_groups(de, plan, params, ids_recv,
                       tag: str = "") -> List[jax.Array]:
    """Per-group combined lookups in slot-major ``[world, n, b, width]``
    layout: one region reshape, one slab gather, one combine per group.
    The single-worker forward consumes these directly (its per-instance
    outputs are plain slot slices), skipping the ``[world, b, s_max]``
    exchange-row transpose that only the all-to-all needs — the dense
    model re-stacks outputs feature-major anyway, so the transpose
    round trip was a pure extra pass at headline shapes."""
    my = de._my_rank()
    sections = []
    for gi, g in enumerate(plan.groups):
        # one named scope per (width, kind) group: a profile of the
        # step attributes gather/combine time to the width it serves
        with obs.scope(f"lookup_w{g.width}_{g.kind}{tag}"):
            red = lookup_group(de, plan, gi, g, params[_wkey(g.width)],
                               ids_recv, my, plan.b)
        dt = de.compute_dtype
        sections.append(red.astype(dt) if dt is not None else red)
    return sections


def lookup_group(de, plan, gi: int, g, slab, ids_recv, my,
                 b: int) -> jax.Array:
    """One exchange group's combined lookup in slot-major
    ``[world, n, b, width]`` layout (the body of
    :func:`plan_lookup_groups`, split out so each group runs under its
    own named scope)."""
    world = de.world_size
    rows = de._plan_row(plan.rows[gi], my)
    roff = de._plan_row(plan.roff[gi], my)
    # mean/valid are *static* plan tensors: when no slot on any rank
    # is a mean combiner (resp. dead), the divide (resp. mask) is
    # skipped at trace time — sum-only groups never touch counts
    any_mean = bool(plan.mean[gi].any())
    all_mean = bool(plan.mean[gi].all())
    all_valid = bool((plan.valid[gi] > 0).all())
    # row-sliced slots subtract their range base and must read zero
    # outside the range (their outputs SUM across slices); the same
    # mask doubles as the opt-in masked_reads debug contract. The
    # mask is gated PER SLOT (plan.rsliced): an unsliced table that
    # shares the exchange group keeps the documented
    # clip-to-last-row read unless masked_reads=True.
    any_rslice = bool(plan.rsliced[gi].any())
    use_mask = any_rslice or de.masked_reads
    rbase = (de._plan_row(plan.rbase[gi], my) if any_rslice
             else None)
    region = lax.slice(ids_recv, (0, g.goff),
                       (world, g.goff + g.n * g.blen))
    if g.kind == "d":
        ids = region.reshape(world, g.n, b, g.hot)
        if rbase is not None:
            ids = ids - rbase[None, :, None, None]
        grow = (jnp.clip(ids, 0, (rows - 1)[None, :, None, None])
                + roff[None, :, None, None])
        gath = ps.packed_gather(slab, grow, g.width)
        if use_mask:
            inr = ((ids >= 0) & (ids < rows[None, :, None, None]))
            if not de.masked_reads:  # only sliced slots mask
                rsl = de._plan_row(plan.rsliced[gi], my)
                inr = inr | (rsl[None, :, None, None] == 0)
            gath = gath * inr[..., None].astype(gath.dtype)
        red = jnp.sum(gath, axis=3)  # [world, n, b, w]
        if g.hot > 1 and any_mean:
            if all_mean:
                red = red / g.hot
            else:
                mean = de._plan_row(plan.mean[gi], my)
                red = jnp.where(mean[None, :, None, None] > 0,
                                red / g.hot, red)
    elif g.block:
        # a small-table ragged group: its bags are summed from within-tile
        # prefixes of rows made on the MXU, read at the splits (no scatter)
        with obs.scope("segment_prefix"):
            r3 = region.reshape(world, g.n, g.blen)
            loc = r3[:, :, :g.hot]
            lengths = r3[:, :, g.hot:g.hot + b]
            if not all_valid:
                valid = de._plan_row(plan.valid[gi], my)
                lengths = lengths * valid[None, :, None].astype(r3.dtype)
            splits = jnp.clip(csr_splits(lengths), 0, g.hot)
            if rbase is not None:
                loc = loc - rbase[None, :, None]
            ids = jnp.clip(loc, 0, (rows - 1)[None, :, None])
            if use_mask:
                ids = jnp.where(ragged_reads(de, plan, gi, my, loc, rows),
                                ids, -1)
            div = None
            if any_mean:
                div = jnp.maximum(lengths, 1)
                if not all_mean:
                    mean = de._plan_row(plan.mean[gi], my)
                    div = jnp.where(mean[None, :, None] > 0, div, 1)
            red = block_bag_sums(
                g, slab, rows, roff, splits, ids,
                region_weights(de, g, b, region) if g.kind == "rw" else None,
                div, de.compute_dtype)
    else:
        values, _, seg, grow, counts = ragged_decode(
            de, g, b, region, rows, roff,
            None if all_valid else de._plan_row(plan.valid[gi], my),
            need_counts=any_mean, rbase=rbase)
        gath = ps.packed_gather(slab, grow, g.width)  # [w, n, cap, ww]
        if g.kind == "rw":
            # per-id weights multiply the gathered rows (reference
            # kernel's optional weights, .cu:52-55); mean still
            # divides by the id count (.cu:220-222)
            wts = region_weights(de, g, b, region)
            gath = gath * wts[..., None].astype(gath.dtype)
        if use_mask:
            loc = (values - rbase[None, :, None]
                   if rbase is not None else values)
            inr = ragged_reads(de, plan, gi, my, loc, rows)
            gath = gath * inr[..., None].astype(gath.dtype)
        sidx = ragged_scatter_idx(g, b, world, seg)
        buf = jnp.zeros((world * g.n * (b + 1), g.width), gath.dtype)
        # sidx ascends globally: (source, slot) blocks are laid out
        # ascending and seg ascends within each CSR block
        buf = buf.at[sidx.reshape(-1)].add(
            gath.reshape(-1, g.width), indices_are_sorted=True)
        red = buf.reshape(world, g.n, b + 1, g.width)[:, :, :b, :]
        if any_mean:
            div = red / counts[..., None].astype(red.dtype)
            if all_mean:
                red = div
            else:
                mean = de._plan_row(plan.mean[gi], my)
                red = jnp.where(mean[None, :, None, None] > 0,
                                div, red)
    return red
