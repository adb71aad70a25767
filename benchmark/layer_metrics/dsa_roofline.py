"""Roofline shares of the sparse-attention encoder, each the least time
the chip could take over a device time, in %, for the work of the traced
calls: the lane positions the traced ``engine.program`` spans ran
(``lane_steps``, padding included), the positions their rows had reached
(``cache_steps_run`` of the traced ``engine.group`` spans), and what the
encoder counted on the device, summed over layers (the traced
``engine.finalize`` spans: ``dsa_pairs_scored``, ``dsa_pairs_selected``,
``routed_rows``). ``part`` says which: ``fwd`` the whole forward over the
forward programs' device time (bytes: one read of the held bf16 weights
per execution); ``indexer`` the 32-head scoring of the pairs SCORED over
the device time under ``scopes``; ``select`` the selection's bytes over
bandwidth; ``core`` the pairs the core ADMITTED, not the ones it
visited, and ``W_kvb`` over the positions met, or the latent rows,
queries and outputs over bandwidth. Counting admitted pairs keeps the
core's reading under 100 whatever it visits. A program without the
spans, the counts or the scope gives nothing to read. Prints which
bound holds."""
import importlib

from benchmark.harness import flops, xplane_scopes


def read(ctx, spec):
    counts = importlib.import_module(f"benchmark.harness.{spec['flops']}")
    by_name = ctx.traced_spans.by_name()
    programs = by_name.get("engine.program")
    groups = by_name.get("engine.group")
    flushes = [s for s in by_name.get("engine.finalize", [])
               if "dsa_pairs_scored" in s.attrs]
    if not programs or not groups or not flushes:
        return None
    model = ctx.config
    layers = model["num_hidden_layers"]
    steps = sum(float(p.attrs["lane_steps"]) for p in programs)
    met = sum(float(g.attrs["cache_steps_run"]) for g in groups)
    scored, selected, routed = (
        sum(float(s.attrs.get(name, 0)) for s in flushes)
        for name in ("dsa_pairs_scored", "dsa_pairs_selected", "routed_rows"))
    if spec["part"] == "fwd":
        durs = ctx.module_durations(spec["module"])
        took = sum(durs)
        need = counts.forward_flops(model, steps, routed, scored, selected,
                                    met)
        moved = len(durs) * counts.weight_bytes(model)
    else:
        path = ctx.result.get("xplane_path")
        took = xplane_scopes.seconds_under(path, spec["scopes"]) \
            if path else 0.0
        if spec["part"] == "indexer":
            need = scored * counts.index_pair_flops(model)
            moved = counts.index_bytes(model, scored, layers * steps,
                                       layers * met)
        elif spec["part"] == "select":
            need, moved = 0.0, counts.select_bytes(scored)
        else:
            need = counts.core_flops(model, selected, layers * met)
            moved = counts.core_bytes(model, layers * steps, layers * met,
                                      selected)
    if took <= 0:
        return None
    least, bound = flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"[bench] {spec['name']}: {len(programs)} programs, {steps:.0f} "
          f"lane positions, {met:.0f} positions met, {scored:.0f} scored "
          f"and {selected:.0f} admitted pairs over {layers} layers, "
          f"{need:.4g} operations, {moved:.4g} bytes, least {least:.6f} s "
          f"({bound}-bound) over {took:.6f} s", flush=True)
    return 100.0 * least / took
