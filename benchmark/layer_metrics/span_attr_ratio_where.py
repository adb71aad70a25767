"""Ratio of two counts that one kind of program span carries, each
summed over the window's spans of that kind whose attribute ``where``
exceeds ``more_than``, times ``scale``."""


def read(ctx, spec):
    spans = [s for s in ctx.spans.by_name().get(spec["span"], [])
             if float(s.attrs.get(spec["where"], 0)) > spec["more_than"]]
    den = sum(float(s.attrs.get(spec["den"], 0)) for s in spans)
    if den <= 0 or not all(spec["num"] in s.attrs for s in spans):
        return None
    return float(spec.get("scale", 1.0)) * sum(
        float(s.attrs[spec["num"]]) for s in spans) / den
