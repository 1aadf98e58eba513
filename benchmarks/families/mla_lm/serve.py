"""A serving cell of family ``mla_lm``: the turns as the runtime takes them,
the reference's logits at the positions each picked turn asked for, and the
comparison.

The reference follows a session's context as the program wrote it: each
served turn's prompt and every generated token but the last, in the order
the turns were submitted (a refused turn left nothing). So it needs the
program's generated tokens (teacher forcing), which the harness hands to
``serve_numbers`` and not to ``reference_answers``: the latter returns the
deferred reference, :class:`Answers`, and the comparison computes it.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from benchmarks.families import system

from . import reference, traffic


def requests_of(schedule: traffic.SessionSchedule) -> List:
    """Every turn of the schedule as the runtime takes it."""
    out = []
    for i in range(len(schedule)):
        prompt, s, g, at = schedule.request(i)
        out.append(system.Request(cats=[prompt], session=s, max_new_tokens=g,
                                  logits_at=at))
    return out


class Answers:
    """The reference's logits for the picked turns, computed once the
    served tokens are known (:meth:`logits`)."""

    def __init__(self, config: dict, schedule: traffic.SessionSchedule,
                 picked: List[int], seed: int, precision: str = "float32",
                 fault: Optional[str] = None):
        self.config, self.schedule, self.picked = config, schedule, picked
        self.seed, self.precision, self.fault = seed, precision, fault

    def contexts(self, results: dict):
        """``({session: (document, suffix tokens, rows)}, {turn: (session,
        first row)})``: each session's served turns in order, up to its last
        picked one; a turn's logits at generated position ``j`` are at row
        ``first + j`` of its session's suffix. A session stops at a turn
        that was not answered or came back misshapen."""
        sch = self.schedule
        want = set(self.picked)
        sessions, where = {}, {}
        for s in sorted({int(sch.session[i]) for i in self.picked}):
            turns = [i for i in range(len(sch)) if sch.session[i] == s]
            last = max(i for i in turns if i in want)
            parts, n = [], 0
            for i in turns:
                if i > last:
                    break
                r = results.get(i)
                if r is None:
                    break
                if system.is_refused(r):
                    continue
                prompt, _, g, _ = sch.request(i)
                tok = np.asarray(r.tokens).reshape(-1)
                if len(tok) != g:
                    break
                if i in want:
                    where[i] = (s, n + len(prompt) - 1)
                parts += [prompt, tok[:-1]]
                n += len(prompt) + g - 1
            if where and any(v[0] == s for v in where.values()):
                sessions[s] = [sch.document_of(s), np.concatenate(parts)
                               .astype(np.int32), []]
        for i, (s, first) in sorted(where.items()):
            sessions[s][2] += [first + j for j in sch.logits_at]
        return ({s: (d, tok, np.asarray(rows, np.int32))
                 for s, (d, tok, rows) in sessions.items()}, where)

    def logits(self, results: dict) -> Dict[int, np.ndarray]:
        """``{turn: [len(logits_at), vocab]}`` for every picked turn whose
        session's context is known."""
        sessions, where = self.contexts(results)
        if not sessions:
            return {}
        got = reference.session_logits(
            self.config, self.seed, self.schedule.documents, sessions,
            self.precision, self.fault)
        k = len(self.schedule.logits_at)
        seen: Dict[int, int] = {}
        out = {}
        for i, (s, _) in sorted(where.items()):
            a = seen.get(s, 0)
            out[i] = got[s][a:a + k]
            seen[s] = a + k
        return out


def reference_answers(config: dict, schedule: traffic.SessionSchedule,
                      picked: List[int], seed: int,
                      precision: str = "float32", fault=None) -> Answers:
    return Answers(config, schedule, picked, seed, precision, fault)


def logit_gap(got: Dict[int, np.ndarray], want: Dict[int, np.ndarray]
              ) -> float:
    """The worst ``|got - want|`` over the rows compared, each over the
    reference row's RMS."""
    gap = 0.0
    for i, w in want.items():
        if i not in got:
            continue
        rms = np.sqrt(np.mean(np.square(w, dtype=np.float64), axis=-1))
        d = np.abs(np.asarray(got[i], np.float64) - w).max(axis=-1)
        gap = max(gap, float(np.max(d / np.maximum(rms, 1e-30))))
    return gap


def row_error_median(got: Dict[int, np.ndarray],
                     want: Dict[int, np.ndarray]) -> float:
    """The median over the rows compared of ``|got - want| / |want|`` (L2
    norms of a row): what most rows read, where :func:`logit_gap` reads the
    worst one, which a routing choice flipped by rounding decides."""
    errs = [np.linalg.norm(np.asarray(got[i], np.float64) - w, axis=-1)
            / np.maximum(np.linalg.norm(w.astype(np.float64), axis=-1),
                         1e-30)
            for i, w in want.items() if i in got]
    return float(np.median(np.concatenate(errs))) if errs else 0.0


def compare(schedule: traffic.SessionSchedule, results: dict,
            picked: List[int], answers: Answers) -> dict:
    """The widest gap between the logits a picked turn came back with and
    the reference's (:func:`logit_gap`), and how many picked turns came back
    with another shape, a non-finite logit, or a context the reference
    could not follow; beside them, not compared, the median row's error
    (:func:`row_error_median`) and the reference's logit scale."""
    k, want = len(schedule.logits_at), answers.logits(results)
    got, misshapen = {}, 0
    for i in picked:
        r = results[i]
        p = np.asarray(r.predictions, np.float32)
        if i not in want or p.shape != want[i].shape or p.shape[0] != k \
                or not np.isfinite(p).all():
            misshapen += 1
        else:
            got[i] = p
    rms = [float(np.sqrt(np.mean(np.square(w)))) for w in want.values()]
    return {"logit_gap": logit_gap(got, want), "misshapen": float(misshapen),
            "logit_err_median": row_error_median(got, want),
            "logit_rms": float(np.mean(rms)) if rms else 0.0}
