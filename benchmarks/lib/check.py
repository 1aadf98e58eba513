"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside its limit.

Training numbers (``How correct is decided``, training): the loss of each of
the first three steps, the first gradient as the optimizer got it (worked out
from the parameters after one step: ``(p0 - p1) / lr``) and the parameters'
change after the three, the last two by the worst leaf as a gap of norms.
A leaf is one MLP kernel or bias, or one embedding table. One more number
reads the first gradient over the rows that only one half of the first batch
touches.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Sequence

import numpy as np


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def worst_leaf_gap(got: Sequence[float], want: Sequence[float],
                   floor_of: Sequence[float], skip=None) -> float:
    """The largest ``|got - want|`` over leaves, each measured against the
    reference's norm of that leaf or of the median leaf of ``floor_of``,
    whichever is larger. ``skip[i]`` leaves leaf ``i`` out."""
    med = float(np.median(floor_of))
    worst = 0.0
    for i, (g, w) in enumerate(zip(got, want)):
        if skip is not None and skip[i]:
            continue
        worst = max(worst, abs(g - w) / max(w, med, 1e-30))
    return worst


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` hold ``losses`` [3], and per group (``dense``,
    ``tables``) the leaf norms ``grad1_<group>`` and ``delta3_<group>``."""
    out = {f"loss{k + 1}": rel_gap(prog["losses"][k], ref["losses"][k])
           for k in range(3)}
    # the median leaf is its group's (MLP leaves, tables): the two groups have
    # learning rates of their own, so their changes are not of one scale
    for group in ("dense", "tables"):
        g = f"grad1_{group}"
        d = f"delta3_{group}"
        # a leaf whose gradient is nought to rounding in the reference moves
        # by round-off alone: left out of the change by the reference's
        # gradient
        tiny = 1e-3 * float(np.median(ref[g]))
        out[g] = worst_leaf_gap(prog[g], ref[g], ref[g])
        out[d] = worst_leaf_gap(prog[d], ref[d], ref[d],
                                skip=[x < tiny for x in ref[g]])
    # rows that one half of the first batch touches alone (two leaves: the
    # first half's, the second half's, each over all tables): where half of a
    # batch is left out they stay put or move double, which a whole table's
    # norm hides behind its hot rows
    if "grad1_half_rows" in ref:
        out["grad1_half_rows"] = worst_leaf_gap(
            prog["grad1_half_rows"], ref["grad1_half_rows"],
            ref["grad1_half_rows"])
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """``(correct, compared)``: every number beside its limit. A number with
    no limit in the cell's file is printed and not compared; a limit whose
    number is missing fails."""
    compared = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(good)
        compared[name] = {"value": value, "limit": limit}
    for name, value in numbers.items():
        compared.setdefault(name, {"value": value, "limit": None})
    return ok, compared


def print_compared(compared: dict, correct: bool) -> None:
    """The numbers compared as the last lines of standard error."""
    for name, c in compared.items():
        lim = "not compared" if c["limit"] is None else f"limit {c['limit']:.6g}"
        val = "missing" if c["value"] is None else f"{c['value']:.6g}"
        print(f"check {name} {val} {lim}", file=sys.stderr)
    print(f"check correct {correct}", file=sys.stderr, flush=True)
