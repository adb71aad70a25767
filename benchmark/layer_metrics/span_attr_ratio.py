"""Ratio in % of two counts that one kind of program span carries as
attributes, each summed over the whole window's spans of that kind
(``num`` over ``den``; with ``complement`` 100 less that)."""


def read(ctx, spec):
    spans = ctx.spans.by_name().get(spec["span"])
    if not spans:
        return None
    den = sum(float(s.attrs.get(spec["den"], 0)) for s in spans)
    if den <= 0:
        return None
    share = 100.0 * sum(float(s.attrs.get(spec["num"], 0))
                        for s in spans) / den
    return 100.0 - share if spec.get("complement") else share
