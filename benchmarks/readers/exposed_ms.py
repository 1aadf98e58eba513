"""The part of some scopes' device time (ms a step) during which no other
operation ran on the same device."""
from benchmarks.lib import tracered


def read(ctx, spec):
    trace, units = ctx.get("trace"), ctx.get(spec["per"])
    if trace is None or not trace.devices or not units:
        return None
    if tracered.scope_seconds(trace, spec["scopes"]) == 0.0:
        return None
    return 1e3 * tracered.exposed_seconds(trace, spec["scopes"]) / units
