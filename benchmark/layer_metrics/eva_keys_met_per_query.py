"""Keys of either kind a query met, as the encoder counted them on the
device: ``eva_singleton_pairs`` + ``eva_summary_pairs`` of the window's
``engine.finalize`` spans (the pairs the mask admitted, a head a layer,
valid lanes only) over the valid positions of its ``engine.group`` spans
(``valid_tokens``). A program whose spans lack the counts gives nothing
to read."""


def read(ctx, spec):
    by_name = ctx.spans.by_name()
    met = [float(s.attrs["eva_singleton_pairs"])
           + float(s.attrs["eva_summary_pairs"])
           for s in by_name.get("engine.finalize", [])
           if "eva_singleton_pairs" in s.attrs]
    positions = sum(float(g.attrs["valid_tokens"])
                    for g in by_name.get("engine.group", [])
                    if "valid_tokens" in g.attrs)
    if not met or positions <= 0:
        return None
    return sum(met) / positions
