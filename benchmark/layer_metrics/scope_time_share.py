"""Device time of the ops under some ``jax.named_scope``s as a share in
% of the device time of a program's executions. ``scopes`` are regular
expressions over an op's scope path (``harness/xplane_scopes.py``); a
capture whose ops carry no such scope (a program without them) gives
nothing to read."""
from benchmark.harness import xplane_scopes


def read(ctx, spec):
    path = ctx.result.get("xplane_path")
    total = sum(ctx.module_durations(spec["module"]))
    if not path or total <= 0:
        return None
    under = xplane_scopes.seconds_under(path, spec["scopes"])
    return 100.0 * under / total if under > 0 else None
