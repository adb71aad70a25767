"""Roofline shares of the EVA encoder, each the least time the chip
could take over a device time, in %, for the work of the traced calls:
the lane-steps the traced ``engine.program`` spans ran (``lane_steps``,
padding included) and the query-key pairs the cores ADMITTED, a head a
layer, as the encoder counted them on the device (``eva_singleton_pairs``
+ ``eva_summary_pairs`` of the traced ``engine.finalize`` spans).
``part`` says which: ``fwd`` the whole forward (projections, SwiGLU,
summaries and cores; bytes one read of the held bf16 weights per
execution) over the forward programs' device time; ``core`` the joint
cores over the device time under ``scopes``; ``summaries`` the chunk
summaries over theirs. Counting admitted pairs, not visited blocks,
keeps a reading under 100 whatever a core visits. A program without the
spans, the counts or the scope gives nothing to read. Prints which bound
holds."""
import importlib

from benchmark.harness import flops, xplane_scopes


def read(ctx, spec):
    counts = importlib.import_module(f"benchmark.harness.{spec['flops']}")
    by_name = ctx.traced_spans.by_name()
    programs = by_name.get("engine.program")
    flushes = [s for s in by_name.get("engine.finalize", [])
               if "eva_singleton_pairs" in s.attrs]
    if not programs or not flushes:
        return None
    model = ctx.config
    layers = model["num_hidden_layers"]
    steps = sum(float(p.attrs["lane_steps"]) for p in programs)
    pairs = sum(float(s.attrs["eva_singleton_pairs"])
                + float(s.attrs["eva_summary_pairs"]) for s in flushes)
    if spec["part"] == "fwd":
        durs = ctx.module_durations(spec["module"])
        took = sum(durs)
        need = counts.forward_flops(model, steps, pairs)
        moved = len(durs) * counts.weight_bytes(model)
    else:
        path = ctx.result.get("xplane_path")
        took = xplane_scopes.seconds_under(path, spec["scopes"]) \
            if path else 0.0
        if spec["part"] == "core":
            queries = max(int(p.attrs["bucket"]) for p in programs)
            need = layers * counts.core_flops(model, pairs)
            moved = layers * counts.core_bytes(model, pairs, queries, steps)
        else:
            need = layers * steps * counts.summaries_flops_per_position(model)
            moved = layers * steps \
                * counts.summaries_bytes_per_position(model)
    if took <= 0:
        return None
    least, bound = flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"[bench] {spec['name']}: {len(programs)} programs, {steps:.0f} "
          f"lane-steps, {pairs:.0f} admitted pairs a head a layer, "
          f"{need:.4g} operations, {moved:.4g} bytes, least {least:.6f} s "
          f"({bound}-bound) over {took:.6f} s", flush=True)
    return 100.0 * least / took
