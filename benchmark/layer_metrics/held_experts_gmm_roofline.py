"""Roofline share of the routed experts' grouped matmuls where a chip
holds EVERY expert of a layer: the least time the chip could take for
the rows it ROUTED (``routed_rows`` of the traced ``engine.finalize``
spans) or to read the held experts once a program (``moe_programs``),
whichever is larger, over the device time of the ops under ``scopes``,
in %. The spec's ``flops`` names the module of ``harness/`` that counts
them. Prints which of the two bounds it."""
import importlib

from benchmark.harness import flops, xplane_scopes


def read(ctx, spec):
    counts = importlib.import_module(f"benchmark.harness.{spec['flops']}")
    path = ctx.result.get("xplane_path")
    flushes = [s for s in ctx.traced_spans.by_name().get(
        "engine.finalize", []) if "routed_rows" in s.attrs]
    if not path or not flushes:
        return None
    took = xplane_scopes.seconds_under(path, spec["scopes"])
    if took <= 0:
        return None
    rows = sum(float(s.attrs["routed_rows"]) for s in flushes)
    programs = sum(float(s.attrs["moe_programs"]) for s in flushes)
    need = counts.routed_flops(ctx.config, rows)
    moved = programs * counts.held_expert_bytes(ctx.config)
    least, bound = flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"[bench] {spec['name']}: {rows:.0f} rows in {programs:.0f} "
          f"programs, {need:.4g} operations, {moved:.4g} bytes, least "
          f"{least:.6f} s ({bound}-bound) over {took:.6f} s", flush=True)
    return 100.0 * least / took
