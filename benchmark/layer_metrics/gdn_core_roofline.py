"""Roofline share of a delta-rule recurrence whose operations the spec's
``flops`` module counts: the least time the chip could take for the
LANE-STEPS the recurrence ran (padding included: the core cannot skip
lanes the batcher gave it) over the device time of the ops under its
scope, in %. Program by program from the ``engine.program`` spans of the
traced calls (``lane_steps`` = the rows a chunk program RAN x its bucket;
``rows`` of matrix state read and written once a program), all times the
linear layers the module's ``layer_kinds`` counts. A program without such
spans, or without the scope, gives nothing to read. Prints which of the
two bounds it."""
import importlib

from benchmark.harness import flops, xplane_scopes


def read(ctx, spec):
    counts = importlib.import_module(f"benchmark.harness.{spec['flops']}")
    path = ctx.result.get("xplane_path")
    programs = ctx.traced_spans.by_name().get("engine.program")
    if not path or not programs:
        return None
    took = xplane_scopes.seconds_under(path, spec["scopes"])
    if took <= 0:
        return None
    model = ctx.config
    layers, _ = counts.layer_kinds(model)
    steps = sum(float(p.attrs["lane_steps"]) for p in programs)
    rows = sum(float(p.attrs["rows"]) for p in programs)
    need = layers * steps * counts.gdn_flops_per_token(model)
    moved = layers * (steps * counts.gdn_bytes_per_token(model)
                      + rows * counts.gdn_state_bytes_per_row(model))
    least, bound = flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"[bench] {spec['name']}: {len(programs)} programs, {steps:.0f} "
          f"lane-steps, {rows:.0f} rows of state, {need:.4g} operations, "
          f"{moved:.4g} bytes, least {least:.6f} s ({bound}-bound) over "
          f"{took:.6f} s", flush=True)
    return 100.0 * least / took
