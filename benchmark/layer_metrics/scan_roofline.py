"""Roofline share of the state-space scan: the least time the chip
could take for the LANE-STEPS the scan ran (padding included: a kernel
cannot skip lanes the batcher gave it) over the device time of the ops
under its scope, in %. Operations and bytes from
``harness/flops_hybrid.py``, group by group from the ``engine.group``
spans of the traced calls: a token's operations at the group's bucket,
its bytes, and every row's state read and written once a program, all
times the Mamba layers. Prints which of the two bounds it."""
from benchmark.harness import flops, flops_hybrid, xplane_scopes


def read(ctx, spec):
    path = ctx.result.get("xplane_path")
    groups = ctx.traced_spans.by_name().get("engine.group")
    if not path or not groups:
        return None
    took = xplane_scopes.seconds_under(path, spec["scopes"])
    if took <= 0:
        return None
    model = ctx.config
    layers, _ = flops_hybrid.layer_counts(model)
    need = moved = 0.0
    for g in groups:
        a = g.attrs
        steps = float(a["lane_steps"])
        need += steps * flops_hybrid.scan_flops_per_token(model, a["bucket"])
        moved += steps * flops_hybrid.scan_bytes_per_token(model) \
            + float(a["batch"]) * float(a["chunks"]) \
            * flops_hybrid.scan_state_bytes_per_row(model)
    least, bound = flops.roofline_seconds(layers * need, layers * moved,
                                          ctx.peaks)
    print(f"[bench] {spec['name']}: {len(groups)} groups, "
          f"{layers * need:.4g} operations, {layers * moved:.4g} bytes, "
          f"least {least:.6f} s ({bound}-bound) over {took:.6f} s",
          flush=True)
    return 100.0 * least / took
