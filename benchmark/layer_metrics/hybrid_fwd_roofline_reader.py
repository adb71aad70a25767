"""Roofline share of the hybrid encoder's forward programs: the least
time the chip could take for the VALID tokens of the traced calls over
the programs' device time, in %. Operations: matmuls and the scan a
token at its group's bucket (``engine.group`` spans), attention over
whole documents (``engine.tokenize`` spans' lengths); bytes: one read
of the bf16 matmul weights per execution. Prints which bounds it."""
from benchmark.harness import flops, flops_hybrid


def read(ctx, spec):
    durs = ctx.module_durations(spec["module"])
    by_name = ctx.traced_spans.by_name()
    groups = by_name.get("engine.group")
    docs = by_name.get("engine.tokenize")
    if not durs or not groups or not docs:
        return None
    model = ctx.config
    need = flops_hybrid.encoder_flops(
        model,
        [(int(g.attrs["valid_tokens"]), int(g.attrs["bucket"]))
         for g in groups],
        [int(d.attrs["n_tokens"]) for d in docs])
    moved = len(durs) * flops_hybrid.weight_bytes(model)
    least, bound = flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"[bench] {spec['name']}: {len(docs)} documents in {len(groups)} "
          f"groups, {need:.4g} operations, {moved:.4g} bytes, least "
          f"{least:.6f} s ({bound}-bound) over {sum(durs):.6f} s in "
          f"{len(durs)} executions", flush=True)
    return 100.0 * least / sum(durs)
