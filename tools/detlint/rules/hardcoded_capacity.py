"""Capacity numbers must come from the plan-audit registry, not call sites.

Device counts, HBM sizes, bandwidth figures, and byte-scale limit
literals inlined in package code drift silently when hardware
assumptions change — a 16 GiB HBM figure, an ICI bandwidth — and a
stale copy turns the capacity contracts into fiction. PR 8 made
``analysis/plan_audit.py`` the single registry (``ChipSpec`` /
``CHIP_SPECS``, ``LANES``): everything else in
``distributed_embeddings_tpu/`` must import from it.

Two triggers:

* any numeric literal >= 2**30 (byte-scale magnitudes; 1 GiB and up) —
  model data that legitimately carries such numbers (e.g. the reference
  zoo's 2e9-row synthetic vocab) annotates the line with
  ``# capacity-ok: <reason>``;
* any assignment whose target name sounds like a hardware capability
  (``*_HBM_*``, ``*GBPS*``, ``*FLOPS*``, ``*CLIFF*``,
  ``*DEVICE_COUNT*``, ...) with a numeric literal on the right-hand
  side, regardless of magnitude.

The registry module itself is excluded (it IS the single home), and the
marker escapes genuinely non-capacity data.
"""

from __future__ import annotations

import ast
import re

from .. import Finding

NAME = "hardcoded-capacity"
SCOPE = ("distributed_embeddings_tpu/**",)
EXCLUDE = ("distributed_embeddings_tpu/analysis/plan_audit.py",)
MARKER = "capacity-ok:"

#: 1 GiB — numeric literals at byte-scale magnitude and above
BYTE_SCALE = 2**30

_CAP_NAME_RE = re.compile(
    r"(HBM|ICI|GBPS|GB_PER_S|TFLOP|FLOPS|CLIFF|DEVICE_COUNT|NUM_DEVICES|"
    r"HBM_HEADROOM)", re.IGNORECASE)


def _num_literals(node: ast.AST):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value,
                                                        (int, float)) \
                and not isinstance(sub.value, bool):
            yield sub


def _targets(node) -> list:
    if isinstance(node, ast.Assign):
        return node.targets
    if isinstance(node, (ast.AnnAssign, ast.AugAssign)):
        return [node.target]
    return []


def _name_of(t) -> str:
    if isinstance(t, ast.Name):
        return t.id
    if isinstance(t, ast.Attribute):
        return t.attr
    return ""


def check(tree: ast.Module, path: str, src: str, ctx) -> list:
    lines = src.splitlines()

    def marked(lineno: int) -> bool:
        return MARKER in lines[lineno - 1]

    findings = []
    flagged_lines = set()
    # trigger 2: capacity-named assignments with numeric literals
    for node in ast.walk(tree):
        values = getattr(node, "value", None)
        if values is None or not _targets(node):
            continue
        names = [_name_of(t) for t in _targets(node)]
        if not any(n and _CAP_NAME_RE.search(n) for n in names):
            continue
        lits = list(_num_literals(values))
        if not lits or marked(node.lineno):
            continue
        flagged_lines.add(node.lineno)
        findings.append(Finding(
            NAME, path, node.lineno,
            f"capacity-named constant {'/'.join(n for n in names if n)!r} "
            "assigned from a literal — hardware capability numbers live in "
            "the capacity registry (analysis/plan_audit.py: CHIP_SPECS / "
            "LANES); import from there (or annotate "
            f"'# {MARKER} <reason>' if this is genuinely not a hardware "
            "number)"))
    # trigger 1: byte-scale magnitudes anywhere. Hex/binary spellings are
    # exempt: hash multipliers and bit masks live in hex, capacity
    # numbers in decimal — the spelling encodes the intent.
    for lit in _num_literals(tree):
        if abs(lit.value) < BYTE_SCALE:
            continue
        if marked(lit.lineno) or lit.lineno in flagged_lines:
            continue
        seg = lines[lit.lineno - 1][lit.col_offset:lit.col_offset + 2]
        if seg.lower() in ("0x", "0b", "0o"):
            continue
        flagged_lines.add(lit.lineno)
        findings.append(Finding(
            NAME, path, lit.lineno,
            f"byte-scale literal {lit.value!r} (>= 2**30) — HBM sizes and "
            "byte limits come from the capacity registry "
            "(analysis/plan_audit.py); import from there, or annotate "
            f"'# {MARKER} <reason>' for non-capacity data (e.g. model "
            "vocab sizes)"))
    return findings
