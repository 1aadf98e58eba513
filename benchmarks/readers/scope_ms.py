"""Device time (ms) of the operations under some scopes, or under none, for
each step or flush of the traced window."""
from benchmarks.lib import tracered


def read(ctx, spec):
    trace, units = ctx.get("trace"), ctx.get(spec["per"])
    if trace is None or not trace.devices or not units:
        return None
    s = tracered.scope_seconds(trace, spec.get("scopes", ()),
                               spec.get("exclude", ()),
                               unscoped=spec.get("unscoped", False))
    # an absent scope is nothing to read; no time under none is a reading
    if s == 0.0 and not spec.get("unscoped"):
        return None
    return 1e3 * s / units
