"""Device time (ms) of the compiled programs' own line in the trace, for each
step or flush."""


def read(ctx, spec):
    trace, units = ctx.get("trace"), ctx.get(spec["per"])
    if trace is None or not trace.devices or not units or not trace.modules:
        return None
    return 1e3 * sum(m.dur for m in trace.modules) / trace.devices / units
