"""Roofline share of a program: the least time the chip could take for
the valid tokens it processed over the device time it took, in %. The
operations come from ``harness/flops.py`` by the function the spec
names; the bytes are one read of the bf16 matmul weights per execution.
Prints which of the two bounds it."""
from benchmark.harness import flops


def read(ctx, spec):
    durs = ctx.module_durations(spec["module"])
    tokens = ctx.traced_tokens(spec, len(durs))
    if not durs or not tokens:
        return None
    model = ctx.config["model"]
    need = tokens * getattr(flops, spec["flops"])(model)
    moved = len(durs) * flops.encoder_weight_bytes(model)
    if "weight_reads_per_step" in spec:  # a scanned train program
        moved *= int(spec["weight_reads_per_step"]) \
            * int(ctx.config["train"]["steps_per_dispatch"])
    least, bound = flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"[bench] {spec['name']}: {tokens} tokens, {need:.4g} operations, "
          f"{moved:.4g} bytes, least {least:.6f} s ({bound}-bound) over "
          f"{sum(durs):.6f} s in {len(durs)} executions", flush=True)
    return 100.0 * least / sum(durs)
