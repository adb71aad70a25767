"""Share of the traced window in which no operation ran on the device."""


def read(ctx, spec):
    r = ctx.reduced
    if not r["devices"] or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
