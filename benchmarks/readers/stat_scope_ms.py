"""Device time (ms) of the operations under some scopes for each of a count
that the serving runtime's ``stats()`` keeps (the metric file's
``per_stat``): the steps that held a prompt chunk, say, where ``scope_ms``
divides by every step."""
from benchmarks.lib import tracered


def read(ctx, spec):
    trace = ctx.get("trace")
    units = (ctx.get("stats") or {}).get(spec["per_stat"])
    if trace is None or not trace.devices or not units:
        return None
    s = tracered.scope_seconds(trace, spec.get("scopes", ()),
                               spec.get("exclude", ()))
    # an absent scope is nothing to read
    if s == 0.0:
        return None
    return 1e3 * s / units
