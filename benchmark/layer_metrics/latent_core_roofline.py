"""Roofline share of the latent attention's core in a model whose latent
sublayers the configuration counts: the least time the chip could take
for the scores and values of the positions the traced groups' chunk
programs ATTENDED (``cache_steps_run`` of the ``engine.group`` spans:
rows run x cache positions reached, padding rows included, a chunk's
``bucket`` queries a row), in the expanded form the program runs, over
the device time of the ops under ``scopes``, in %. The spec's ``flops``
names the module of ``harness/`` whose ``core_flops`` and ``core_bytes``
count them (and with them how many sublayers are latent). Prints which
bounds it."""
import importlib

from benchmark.harness import flops, xplane_scopes


def read(ctx, spec):
    counts = importlib.import_module(f"benchmark.harness.{spec['flops']}")
    path = ctx.result.get("xplane_path")
    groups = [g for g in ctx.traced_spans.by_name().get("engine.group", [])
              if "cache_steps_run" in g.attrs]
    if not path or not groups:
        return None
    took = xplane_scopes.seconds_under(path, spec["scopes"])
    if took <= 0:
        return None
    model = ctx.config
    need = moved = 0.0
    for g in groups:
        a = g.attrs
        queries, steps = int(a["bucket"]), float(a["cache_steps_run"])
        rows = float(a["lane_steps_run"]) / queries
        need += counts.core_flops(model, queries, steps)
        moved += counts.core_bytes(model, queries, rows, steps)
    least, bound = flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"[bench] {spec['name']}: {len(groups)} groups, {need:.4g} "
          f"operations, {moved:.4g} bytes, least {least:.6f} s "
          f"({bound}-bound) over {took:.6f} s", flush=True)
    return 100.0 * least / took
