"""A count the driver read from the program over the window."""


def read(ctx, spec):
    value = ctx.counters.get(spec["counter"])
    return None if value is None else float(value)
