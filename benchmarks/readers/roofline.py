"""A kernel's share of its roofline: the least time the chip could take for
the work its scopes did in a step, over the device time they took. The work
is counted by the cell's family: ``WORK[<the metric file's "work">]``."""
from benchmarks.lib import peaks, tracered


def read(ctx, spec):
    trace, steps, w = ctx.get("trace"), ctx.get(spec["per"]), ctx.get("work")
    if trace is None or not trace.devices or not steps or not w \
            or not ctx.get("peaks"):
        return None
    seconds = tracered.scope_seconds(trace, spec["scopes"],
                                     spec.get("exclude", ())) / steps
    if seconds <= 0.0:
        return None
    count = ctx["family"].WORK.get(spec["work"])
    if count is None:
        raise SystemExit(f"no work function {spec['work']!r}")
    flops, nbytes = count(ctx["config"], w, ctx)
    chips = ctx["chips"]
    # the step's work is spread over the chips; the time is a device's own
    return peaks.roofline_share(flops / chips, nbytes / chips, seconds,
                                ctx["peaks"])
