"""Roofline share of the early-routed ReGLU expert model's forward
programs: the least time the chip could take for the VALID tokens of the
traced calls over the programs' device time, in %. Operations: the
matmuls every token meets (``engine.group`` spans' valid tokens), the
grouped matmuls of the rows routed (``engine.finalize`` spans'
``routed_rows``), attention over whole documents with a sliding layer's
window applied (``engine.tokenize`` spans' lengths); bytes: one read of
the held bf16 matrices per execution. The spec's ``flops`` names the
module of ``harness/`` that counts them. Prints which bounds it."""
import importlib

from benchmark.harness import flops


def read(ctx, spec):
    counts = importlib.import_module(f"benchmark.harness.{spec['flops']}")
    durs = ctx.module_durations(spec["module"])
    by_name = ctx.traced_spans.by_name()
    groups = by_name.get("engine.group")
    docs = by_name.get("engine.tokenize")
    flushes = [s for s in by_name.get("engine.finalize", [])
               if "routed_rows" in s.attrs]
    if not durs or not groups or not docs or not flushes:
        return None
    model = ctx.config
    routed = sum(float(s.attrs["routed_rows"]) for s in flushes)
    need = counts.encoder_flops(
        model, sum(int(g.attrs["valid_tokens"]) for g in groups), routed,
        [int(d.attrs["n_tokens"]) for d in docs])
    moved = len(durs) * counts.weight_bytes(model)
    least, bound = flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"[bench] {spec['name']}: {len(docs)} documents in {len(groups)} "
          f"groups, {routed:.0f} routed rows, {need:.4g} operations, "
          f"{moved:.4g} bytes, least {least:.6f} s ({bound}-bound) over "
          f"{sum(durs):.6f} s in {len(durs)} executions", flush=True)
    return 100.0 * least / sum(durs)
