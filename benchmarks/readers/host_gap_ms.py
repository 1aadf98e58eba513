"""Mean time a step (ms) in which the device ran nothing: what the host's
dispatching costs the device."""
from benchmarks.lib import tracered


def read(ctx, spec):
    trace, units = ctx.get("trace"), ctx.get(spec["per"])
    if trace is None or not trace.devices or not units:
        return None
    return 1e3 * (ctx["window_s"] - tracered.busy_seconds(trace)) / units
