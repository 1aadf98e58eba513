"""A count or ratio the run already holds: ``ctx[group][key]``."""


def read(ctx, spec):
    v = ctx.get(spec["group"], {}).get(spec["key"])
    return None if v is None else float(v)
