"""Roofline share of the cached attention cores of one kind of layer
(``kind``: sliding-window over a ring, or global over a growing cache):
the least time the chip could take for the scores and values of the
positions the traced groups' chunk programs MET (the ``steps`` attribute
of the ``engine.group`` spans: rows run x positions the cache held,
padding rows included, a chunk's ``bucket`` queries a row) or to move
those keys and values, the queries and the outputs, whichever is larger,
over the device time of the ops under ``scopes``, in %. A program whose
spans lack the attribute gives nothing to read. Prints which bounds
it."""
from benchmark.harness import flops, flops_afmoe, xplane_scopes


def read(ctx, spec):
    path = ctx.result.get("xplane_path")
    groups = [g for g in ctx.traced_spans.by_name().get("engine.group", [])
              if spec["steps"] in g.attrs]
    if not path or not groups:
        return None
    took = xplane_scopes.seconds_under(path, spec["scopes"])
    if took <= 0:
        return None
    model, kind = ctx.config, spec["kind"]
    need = moved = 0.0
    for g in groups:
        a = g.attrs
        queries, steps = int(a["bucket"]), float(a[spec["steps"]])
        rows = float(a["lane_steps_run"]) / queries
        need += flops_afmoe.core_flops(model, kind, queries, steps)
        moved += flops_afmoe.core_bytes(model, kind, queries, rows, steps)
    least, bound = flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"[bench] {spec['name']}: {len(groups)} groups, {need:.4g} "
          f"operations, {moved:.4g} bytes, least {least:.6f} s "
          f"({bound}-bound) over {took:.6f} s", flush=True)
    return 100.0 * least / took
