"""Cached positions a query attended, as the encoder counted them on the
device: ``dsa_pairs_selected`` of the window's ``engine.finalize`` spans
(the pairs the selection's mask admitted, summed over layers, valid
lanes only) over the layers and over the valid positions of its
``engine.group`` spans (``valid_tokens``). A program whose spans lack
the count gives nothing to read."""


def read(ctx, spec):
    by_name = ctx.spans.by_name()
    met = [float(s.attrs["dsa_pairs_selected"])
           for s in by_name.get("engine.finalize", [])
           if "dsa_pairs_selected" in s.attrs]
    positions = sum(float(g.attrs["valid_tokens"])
                    for g in by_name.get("engine.group", [])
                    if "valid_tokens" in g.attrs)
    if not met or positions <= 0:
        return None
    return sum(met) / ctx.config["num_hidden_layers"] / positions
