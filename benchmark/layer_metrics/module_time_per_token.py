"""Device time of a program's executions per valid token, in us."""


def read(ctx, spec):
    durs = ctx.module_durations(spec["module"])
    tokens = ctx.traced_tokens(spec, len(durs))
    if not durs or not tokens:
        return None
    return 1e6 * sum(durs) / tokens
