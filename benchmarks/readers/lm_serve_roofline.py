"""A kernel's share of its roofline in a serving window: the least time the
chip could take for the work its scopes did in a step, over the device time
they took a step. The family counts a step's work from what the window
counted, ``WORK[<the metric file's "work">](config, ctx) -> (FLOP, bytes)``;
a step is one of the runtime's ``stats()["steps"]``."""
from benchmarks.lib import peaks, tracered


def read(ctx, spec):
    trace = ctx.get("trace")
    steps = (ctx.get("stats") or {}).get("steps")
    if trace is None or not trace.devices or not steps \
            or not ctx.get("peaks"):
        return None
    seconds = tracered.scope_seconds(trace, spec["scopes"],
                                     spec.get("exclude", ())) / steps
    if seconds <= 0.0:
        return None
    count = ctx["family"].WORK.get(spec["work"])
    if count is None:
        raise SystemExit(f"no work function {spec['work']!r}")
    flops, nbytes = count(ctx["config"], ctx)
    if not flops and not nbytes:
        return None
    return peaks.roofline_share(flops, nbytes, seconds, ctx["peaks"])
