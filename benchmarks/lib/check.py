"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside its limit. Which numbers a
cell compares is its family's to say (``train_numbers``, ``serve_numbers``);
the two measures every family takes them by, the verdict and the printing are
here.
"""

from __future__ import annotations

import sys
from typing import Dict, Sequence

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
