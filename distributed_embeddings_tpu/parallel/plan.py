"""Static exchange plans for the rank-uniform executor.

The reference executes per-rank heterogeneity as per-rank *programs*: each
Horovod process builds only its local layers and runs its own Python loop
over them (``dist_model_parallel.py:261-311``). The first TPU port of that
idea expressed the same thing as ``lax.switch`` over rank-specialized
branches — but SPMD compiles every branch on every device, so HLO grew as
O(world x tables) and colossal-scale models (2002 tables,
``config_v3.py:107-121``) became a compile-time cliff.

This module makes per-rank heterogeneity *data* instead of *program*. The
id-exchange block and the output-exchange row are laid out as a sequence of
**group regions at static offsets that are identical on every rank**:

* a *dense group* ``(width w, hotness h)`` holds ``n`` slots, each slot one
  combiner lookup: ``b*h`` ids in the block, ``w`` output columns;
* a *ragged group* ``(width w, capacity c)`` holds ``n`` slots, each slot one
  static-capacity CSR feature: ``c`` values + ``b`` lengths in the block,
  ``w`` output columns;
* ``n`` is the max slot count over ranks — ranks with fewer tables of that
  shape pad with dead slots (zero ids in, never-read columns out);
* a group, dense or ragged, is one of two **size classes**: the slots of
  tables small enough that the backward sums their cotangents as
  ``onehot(ids)^T @ cotangents`` (``optimizers.sums_densely``: the cost rule,
  over the ids a dense slot sends a step or the positions of a ragged slot's
  capacity) meet in a group of their own, each rank's slots largest first,
  and its ``GroupSpec.block`` holds every slot's block rows. The forward,
  the exchanges and the serve program treat it as any group of its kind.

What *differs* per rank — which table a slot reads (row count, slab row
offset), its combiner, whether the slot is live — is carried in small
``[world, n]`` plan tensors indexed by ``lax.axis_index`` at run time. One
compiled program serves every mesh position: per group, ONE reshape of the
block region, ONE slab gather, ONE reduction — O(#groups) heavy HLO ops
total, independent of world size and table count.

A multi-hot feature *without* a combiner ([batch, h] ids -> [batch, h*w]
activations) is expressed as ``h`` consecutive hotness-1 slots; its ids
travel column-major ([h, b]) so each slot's ids stay contiguous.

Plans depend on the per-input encodings and the local batch size, both known
only at trace time, so :class:`~.dist_embedding.DistributedEmbedding` builds
them lazily and caches by ``(encodings, batch)``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import optimizers


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    """One rank-uniform region of the exchange layout."""

    kind: str    # "d" dense | "r" ragged | "rw" ragged with per-id weights
    width: int   # per-slot output width (the column-slice width for slices)
    hot: int     # dense: ids per batch row per slot; ragged: value capacity
    n: int       # slots (max over ranks; shorter ranks are padded)
    blen: int    # ints one slot occupies per source block
    goff: int    # region start within the [l_max] id block
    col: int     # region start within the [s_max] output row
    #: small-table class only: per slot, the rows of the dense block its
    #: cotangents are summed into (the slot's largest table over the ranks,
    #: in whole tiles); empty where the slots ride the scatter's stream
    block: Tuple[int, ...] = ()

    def id_rows(self, world: int, b: int) -> int:
        """Update rows the group's slots send a step at one row an id
        (ragged: a row a position of the capacity, dead or not)."""
        per_source = (b * self.n * self.hot if self.kind == "d"
                      else self.n * self.hot)
        return world * per_source

    def stream_rows(self, world: int, b: int) -> int:
        """Update rows the group puts into its width's stream a step: a
        block's rows, else :meth:`id_rows`."""
        return sum(self.block) if self.block else self.id_rows(world, b)


@dataclasses.dataclass(frozen=True)
class InstanceSpec:
    """One routed input on one rank (worker-order entry).

    ``num_slots > 1`` for no-combiner multi-hot features (one slot per hot
    position, ids sent column-major) and for N-D dense combiner inputs
    (``[b, d1, ..., h]``: one hotness-``h`` slot per lead position — the
    reference flattens such inputs through its exchange the same way,
    ``dist_model_parallel.py:273-288``)."""

    input_id: int
    rank: int
    group: int
    slot0: int
    num_slots: int

    @property
    def transposed(self) -> bool:
        return self.num_slots > 1


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """Complete static layout + per-rank plan tensors for one input signature.

    Plan arrays are all ``[world, n_g]`` numpy, one per group:

    * ``rows``  — table row count a slot reads (1 for dead slots);
    * ``roff``  — slot's table row offset inside its width slab;
    * ``valid`` — 1.0 for live slots, 0.0 for padding (backward routes dead
      slots' ids to the dropped sentinel);
    * ``mean``  — 1.0 where the slot's combiner is ``'mean'`` (forward
      divides the reduced sum, backward divides the cotangent);
    * ``rbase`` — slot's first global row for row-sliced tables (subtracted
      from incoming ids; out-of-slice ids read zero forward and drop
      backward). 0 everywhere else;
    * ``rsliced`` — 1.0 exactly for row-sliced slots (``rbase`` can't mark
      them: a table's FIRST row slice has base 0). Gates the forward
      zero-read mask per slot so unsliced tables sharing the group keep the
      documented clip-to-last-row read.
    """

    b: int
    groups: Tuple[GroupSpec, ...]
    instances: Tuple[InstanceSpec, ...]
    l_max: int
    s_max: int
    rows: Tuple[np.ndarray, ...]
    roff: Tuple[np.ndarray, ...]
    valid: Tuple[np.ndarray, ...]
    mean: Tuple[np.ndarray, ...]
    rbase: Tuple[np.ndarray, ...]
    rsliced: Tuple[np.ndarray, ...]

    def out_width(self, inst: InstanceSpec) -> int:
        return self.groups[inst.group].width * inst.num_slots

    @property
    def world(self) -> int:
        return self.rows[0].shape[0] if self.rows else 1

    def stream_rows(self) -> Dict[int, int]:
        """Per width, the update rows a step scatters into the slab on one
        rank (``parallel/apply.py`` builds these streams)."""
        out: Dict[int, int] = {}
        for g in self.groups:
            out[g.width] = (out.get(g.width, 0)
                            + g.stream_rows(self.world, self.b))
        return out

    @property
    def dense_slots(self) -> int:
        """Slots whose cotangents are summed into a dense block."""
        return sum(len(g.block) for g in self.groups)

    @property
    def dense_rows(self) -> int:
        """Stream rows a step those slots would have sent (one an id)."""
        return sum(g.id_rows(self.world, self.b)
                   for g in self.groups if g.block)


def _n_slots(insts) -> int:
    return sum(len(entries) for _, _, entries in insts)


def _blocks(insts_by_rank) -> Tuple[int, ...]:
    """Per slot, the block rows of the largest table a rank has there."""
    rows = [[e[0] for _, _, entries in insts for e in entries]
            for insts in insts_by_rank]
    return tuple(optimizers.block_rows(max(r[k] for r in rows if k < len(r)))
                 for k in range(max(map(len, rows))))


def build_plan(strategy, row_offsets_list: Sequence[Sequence[int]],
               encs: Sequence[tuple], b: int) -> ExchangePlan:
    """Build the exchange plan for one input signature.

    Args:
      strategy: a planned :class:`~.strategy.DistEmbeddingStrategy`.
      row_offsets_list: per-rank per-local-table logical slab row offsets.
      encs: per global input: dense ``("d", hotness[, num_slots])`` (the
        third element — N-D lead positions — defaults to 1) or ragged
        ``("r", capacity)`` / ``("rw", capacity)`` (per-id weights ride
        the block as bitcast floats past the lengths).
      b: per-shard batch size.
    """
    world = strategy.world_size
    # pass 1: per-rank instance lists per group key, in worker order; an
    # instance is (position in worker order, input, the entries of its slots)
    key_insts: Dict[tuple, List[list]] = {}
    pos = 0
    for r in range(world):
        for j, i in enumerate(strategy.input_ids_list[r]):
            m = strategy.local_map_list[r][j]
            cfg = strategy.local_configs_list[r][m]
            w = int(cfg["output_dim"])
            # row offsets stay < 2^31 in practice: physical slab rows are
            # HBM-bounded and roff <= phys_rows * pack_factor
            rows = int(cfg["input_dim"])
            roff = int(row_offsets_list[r][m])
            comb = cfg.get("combiner")
            rbase = int(cfg.get("_row_base", 0))
            rsl = 1.0 if "_row_base" in cfg else 0.0
            enc = encs[i]
            kind, param = enc[0], int(enc[1])
            nslots = int(enc[2]) if len(enc) > 2 else 1
            if kind == "d":
                if comb:
                    # N-D inputs: one hotness-`param` slot per lead position
                    hot = param
                    entries = [(rows, roff, 1.0,
                                1.0 if comb == "mean" else 0.0, rbase, rsl)
                               ] * nslots
                else:
                    hot = 1
                    entries = [(rows, roff, 1.0, 0.0, rbase, rsl)
                               ] * (param * nslots)
                # the size class: 1 where the backward sums the slot's
                # cotangents into a dense block (sorts behind class 0)
                key = ("d", w, hot, int(optimizers.sums_densely(
                    rows, world * b * hot, hot)))
            else:
                if comb is None:
                    # without this, a combiner-less table would silently get
                    # the mean-flag 0.0, i.e. 'sum' semantics (ADVICE r3)
                    raise ValueError(
                        f"Input {i} is Ragged but table "
                        f"{strategy.input_table_map[i]} has no combiner; "
                        "ragged features require combiner='sum' or 'mean'")
                # "r" | "rw" (per-id weights ride the block as bitcast
                # floats, so weighted features group separately — their
                # slots are one capacity longer); the size class over the
                # positions a slot sends a step, dead ones too: each is one
                # column of a 0/1 one-hot whatever the hotness
                key = (kind, w, param, int(optimizers.sums_densely(
                    rows, world * param)))
                entries = [(rows, roff, 1.0,
                            1.0 if comb == "mean" else 0.0, rbase, rsl)]
            key_insts.setdefault(key, [[] for _ in range(world)]
                                 )[r].append((pos, i, entries))
            pos += 1

    # The small class of a (kind, width, hotness or capacity): each rank's
    # slots largest table first, so that a slot's block (the largest table
    # any rank has there) is tight. Two classes pad each to its fullest rank,
    # so the class stands only where the step's work is then less than with
    # one group. A padded dense slot costs the forward a row a sample, a
    # padded ragged slot a row a position.
    for k in [k for k in key_insts if k[3]]:
        small = [sorted(insts, key=lambda inst: -inst[2][0][0])
                 for insts in key_insts[k]]
        large = key_insts.get(k[:3] + (0,), [[] for _ in range(world)])
        n_large = max(_n_slots(insts) for insts in large)
        n_one = max(_n_slots(x) + _n_slots(y) for x, y in zip(large, small))
        n_small = max(_n_slots(insts) for insts in small)
        ids = world * (b * k[2] if k[0] == "d" else k[2])
        samples = world * b if k[0] == "d" else ids
        a_row = optimizers.scatter_ns("sort_fused", ids, 0)
        if (optimizers.small_sum_ns(_blocks(small), ids) + a_row * n_large
                + optimizers.padded_slots_ns(n_large + n_small - n_one,
                                             samples)
                < a_row * n_one):
            key_insts[k] = small
        else:  # one group, in worker order
            del key_insts[k]
            key_insts[k[:3] + (0,)] = [sorted(x + y)
                                       for x, y in zip(large, small)]

    # pass 2: deterministic group order, cumulative offsets, plan tensors
    keys = sorted(key_insts)
    groups = []
    inst_raw = []  # (position, input_id, rank, group, slot0, num_slots)
    rows_l, roff_l, valid_l, mean_l, rbase_l, rsl_l = [], [], [], [], [], []
    goff = col = 0
    for gi, k in enumerate(keys):
        kind, w, hp = k[:3]
        slots: List[list] = []
        for r, insts in enumerate(key_insts[k]):
            slots.append([])
            for at, i, entries in insts:
                inst_raw.append((at, i, r, gi, len(slots[r]), len(entries)))
                slots[r].extend(entries)
        n = max(len(s) for s in slots)
        blen = {"d": b * hp, "r": hp + b, "rw": 2 * hp + b}[kind]
        rows_a = np.ones((world, n), np.int32)
        roff_a = np.zeros((world, n), np.int32)
        val_a = np.zeros((world, n), np.float32)
        mn_a = np.zeros((world, n), np.float32)
        rb_a = np.zeros((world, n), np.int32)
        rs_a = np.zeros((world, n), np.float32)
        for r in range(world):
            for kk, (tr, to, tv, tm, trb, trs) in enumerate(slots[r]):
                rows_a[r, kk], roff_a[r, kk] = tr, to
                val_a[r, kk], mn_a[r, kk] = tv, tm
                rb_a[r, kk], rs_a[r, kk] = trb, trs
        block = _blocks(key_insts[k]) if k[3] else ()
        groups.append(GroupSpec(kind, w, hp, n, blen, goff, col, block))
        goff += n * blen
        col += n * w
        rows_l.append(rows_a)
        roff_l.append(roff_a)
        valid_l.append(val_a)
        mean_l.append(mn_a)
        rbase_l.append(rb_a)
        rsl_l.append(rs_a)

    # worker order (rank, then the rank's inputs): what the unpack of the
    # output exchange and the backward's zip with worker grads walk
    instances = tuple(InstanceSpec(*raw[1:]) for raw in sorted(inst_raw))
    return ExchangePlan(
        b=b, groups=tuple(groups), instances=instances,
        l_max=max(goff, 1), s_max=max(col, 1),
        rows=tuple(rows_l), roff=tuple(roff_l),
        valid=tuple(valid_l), mean=tuple(mean_l), rbase=tuple(rbase_l),
        rsliced=tuple(rsl_l))
