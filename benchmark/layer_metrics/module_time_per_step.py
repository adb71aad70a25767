"""Median device time of one execution of a scanned program, per step
it scans, in ms."""
import statistics


def read(ctx, spec):
    durs = ctx.module_durations(spec["module"])
    if not durs:
        return None
    steps = int(ctx.config["train"]["steps_per_dispatch"])
    return 1e3 * statistics.median(durs) / steps
