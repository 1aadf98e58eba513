"""Read, on the chip and in one process, the upper readings the cell's limits
are set from, through the harness's comparison: on a few seeds, a short
window of the program at the cell's load, then the reference in float32 and,
put in the program's place, the reference in float8 (the control) and with
the planted fault, each compared with the float32 reference by the family's
``serve_numbers`` and judged by ``check.verdict`` against the cell's limits,
as a run judges the program. The lower readings are the runs' own (``check
logit_gap``).

    python benchmarks/families/mla_lm/readings.py --workload axk1_serve_doc32k \\
        --seeds 3 --seconds 4

With ``--routes``, the first seed also reads what a sound gap is made of:
the reference with bfloat16 operands (what the program rounds to) against
the float32 one, with the experts each token chooses in both. A row whose
token chose another held expert in some layer is a "flipped" row; the line
gives the worst gap over flipped rows and over the others.

``benchmarks/tools/limits.py`` cannot read this family's control: its
reference needs the served tokens, which that tool does not hand it (a
``benchmark`` PR's one-argument change; PERF.md, section 7). The turns read
are those of the first document's sessions (half the reference's time); a
reading over fewer turns is the smaller, so a limit under it is under the
full one too. Prints one JSON object a line. It is not part of a benchmark
run.
"""

import argparse
import json
import os
import sys
import time
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)


def _held_flips(cfg, routes_a, routes_b):
    """``{(suffix, layer): bool [T]}``: where a token's chosen experts among
    those held differ between two runs of the reference (``T`` is the
    suffix's padded length: the padding's tokens are counted too)."""
    lo, hi = (int(e) for e in cfg["experts_held"])
    out = {}
    for (tag, a), (tag_b, b) in zip(routes_a, routes_b):
        assert tag == tag_b, (tag, tag_b)
        if tag[0] == "doc":
            continue
        held_a = np.where((a >= lo) & (a < hi), a, -1)
        held_b = np.where((b >= lo) & (b < hi), b, -1)
        out[tag] = (np.sort(held_a, axis=-1) != np.sort(held_b, axis=-1)
                    ).any(axis=-1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_300_000_000)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--routes", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.families import system
    from benchmarks.lib import manifest
    cell = manifest.Cell(args.workload)
    system.ensure_compile_cache()
    from benchmarks.lib import check, runner, serve

    fam, cfg, tr = cell.family, cell.config, cell.traffic
    ref = fam.reference
    per = int(tr["sessions_per_document"])
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        t0 = time.perf_counter()
        built = fam.build(cfg, tr, seed)
        rt = fam.serving_runtime(built, tr["serve"])
        schedule = fam.serve_schedule(cfg, tr, seed, args.seconds)
        rt.warmup(schedule.request(0))
        results, _, _ = serve.open_loop(rt, schedule,
                                        fam.requests_of(schedule))
        rt.state = None
        built.state = None
        del rt, built
        sizes = np.diff(schedule.offsets)
        picked = [i for i in serve.sample_to_compare(
            seed, results, sizes, runner.COMPARE_REQUESTS)
            if schedule.session[i] < per]
        routes = args.routes and k == 0
        ref.ROUTES = [] if routes else None
        answers = fam.reference_answers(cfg, schedule, picked, seed)
        want = answers.logits(results)
        routes32, ref.ROUTES = ref.ROUTES, None
        picked = [i for i in picked if i in want]
        # the float32 reference, computed once, for every comparison below
        fixed = types.SimpleNamespace(logits=lambda _results: want)

        def judged(numbers):
            # what the runner adds to the family's numbers
            numbers["unanswered"] = float(len(schedule) - len(results))
            ok, _ = check.verdict(numbers, cell.own["limits"])
            return dict(numbers, correct=ok)

        out = {"seed": seed, "turns": len(picked),
               "program": judged(fam.serve_numbers(schedule, results, picked,
                                                   fixed))}
        others = [("bfloat16", {"precision": "bfloat16"})] if routes else []
        others += [("control_" + fam.CONTROL_PRECISION,
                    {"precision": fam.CONTROL_PRECISION})] + [
            ("fault_" + f, {"fault": f}) for f in fam.REFERENCE_FAULTS]
        for who, kw in others:
            ref.ROUTES = [] if who == "bfloat16" else None
            low = fam.reference_answers(cfg, schedule, picked, seed, **kw) \
                .logits(results)
            stand_in = {i: types.SimpleNamespace(predictions=low[i])
                        for i in picked}
            out[who] = judged(fam.serve_numbers(schedule, stand_in, picked,
                                                fixed))
            if who == "bfloat16":
                out["routes"] = _route_rows(cfg, schedule, answers, results,
                                            want, low, routes32, ref.ROUTES)
            ref.ROUTES = None
            print(json.dumps({"seed": seed, who: out[who]}), file=sys.stderr,
                  flush=True)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


def _route_rows(cfg, schedule, answers, results, want, low, routes32,
                routes16):
    """Each compared row's gap (bfloat16 reference against float32, over the
    row's RMS) beside whether its token chose another held expert in some
    layer."""
    flips = _held_flips(cfg, routes32, routes16)
    sessions, where = answers.contexts(results)
    order = sorted(sessions)            # a suffix's index is its rank here
    flipped, steady = [], []
    for i, (s, first) in where.items():
        if i not in want:
            continue
        w, g = want[i].astype(np.float64), low[i].astype(np.float64)
        gaps = np.abs(g - w).max(-1) / np.sqrt(np.mean(w * w, -1))
        j = order.index(s)
        for a, gap in zip(schedule.logits_at, gaps):
            row = first + a
            hit = any(f[row] for (jj, _), f in flips.items() if jj == j)
            (flipped if hit else steady).append(float(gap))
    tokens = sum(int(f.size) for f in flips.values())
    moved = sum(int(f.sum()) for f in flips.values())
    return {"token_layers": tokens, "token_layers_flipped": moved,
            "rows_flipped": len(flipped), "rows_steady": len(steady),
            "worst_flipped": max(flipped, default=0.0),
            "median_flipped": float(np.median(flipped)) if flipped else 0.0,
            "worst_steady": max(steady, default=0.0),
            "median_steady": float(np.median(steady)) if steady else 0.0}


if __name__ == "__main__":
    sys.exit(main())
