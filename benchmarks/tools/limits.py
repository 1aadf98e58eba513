"""Read, on the chip and in one process, the numbers a cell's limits are set
from: the program against the reference on many seeds (the lower readings), and
on a few of them the control (the reference in the precision below, in the
program's place) and the planted faults (the upper readings). Whatever knows
the model is asked of the cell's family.

    python benchmarks/tools/limits.py --workload <cell> --seeds 12 --controls 3

It is not part of a benchmark run. Prints one JSON object a line.
"""

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_000)
    ap.add_argument("--seed-list", default="",
                    help="these seeds, comma-separated, ahead of the others")
    ap.add_argument("--seconds", type=float, default=4.0,
                    help="serving: the short window at the cell's own load")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.lib import manifest
    cell = manifest.Cell(args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
    from benchmarks.families import system
    system.ensure_compile_cache()
    import numpy as np
    from benchmarks.lib import runner, serve, train

    fam, cfg, tr = cell.family, cell.config, cell.traffic
    seeds = [int(x) for x in args.seed_list.split(",") if x] \
        + [args.first_seed + 7919 * k for k in range(args.seeds)]

    def say(**kw):
        print(json.dumps(kw), flush=True)

    def program_numbers(seed, batches):
        """The compiled step's first three steps, as a run's set-up reads
        them."""
        built = fam.build(cfg, tr, seed)
        staged = [fam.stage(built, b) for b in batches]
        step = fam.train_step(built, tr)
        return fam.first_steps(built, tr, step, staged, batches, seed)[0]

    for k, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if tr["kind"] == "train":
            batches = fam.train_batches(
                cfg, dict(tr, distinct_batches=train.CHECK_STEPS), seed)
            prog = program_numbers(seed, batches)
            ref = fam.reference_numbers(cfg, tr, batches, seed)
            say(seed=seed, who="program", numbers=fam.train_numbers(prog, ref),
                seconds=time.perf_counter() - t0)
            if k >= args.controls:
                continue
            if cell.chips > 1:
                with fam.exchange_left_out():
                    bad = program_numbers(seed, batches)
                say(seed=seed, who="fault_no_exchange",
                    numbers=fam.train_numbers(bad, ref))
            for who, kw in [("control_" + fam.CONTROL_PRECISION,
                             {"precision": fam.CONTROL_PRECISION})] + [
                    ("fault_" + f, {"fault": f}) for f in fam.REFERENCE_FAULTS]:
                low = fam.reference_numbers(cfg, tr, batches, seed, **kw)
                say(seed=seed, who=who, numbers=fam.train_numbers(low, ref))
        else:
            built = fam.build(cfg, tr, seed)
            rt = fam.serving_runtime(built, tr["serve"])
            schedule = fam.serve_schedule(cfg, tr, seed, args.seconds)
            rt.warmup(schedule.request(0))
            results, _, _ = serve.open_loop(rt, schedule,
                                            fam.requests_of(schedule))
            rt.state = None
            del rt, built
            sizes = np.diff(schedule.offsets)
            picked = serve.sample_to_compare(seed, results, sizes,
                                             runner.COMPARE_REQUESTS)
            want = fam.reference_answers(cfg, schedule, picked, seed)
            nums = fam.serve_numbers(schedule, results, picked, want)
            nums["failed"] = float(sum(serve.failed(r)
                                       for r in results.values()))
            say(seed=seed, who="program", numbers=nums,
                seconds=time.perf_counter() - t0)
            if k < args.controls:
                # the reference in the lower precision, put in the program's
                # place: its answers as the picked requests' results
                low = fam.reference_answers(cfg, schedule, picked, seed,
                                            precision=fam.CONTROL_PRECISION)
                cuts = np.cumsum([0] + [int(sizes[i]) for i in picked])
                stand_in = {i: types.SimpleNamespace(
                    predictions=low[cuts[j]:cuts[j + 1]])
                    for j, i in enumerate(picked)}
                say(seed=seed, who="control_" + fam.CONTROL_PRECISION,
                    numbers=fam.serve_numbers(schedule, stand_in, picked,
                                              want))
    return 0


if __name__ == "__main__":
    sys.exit(main())
