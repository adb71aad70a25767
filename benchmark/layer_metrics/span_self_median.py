"""Median self time of one kind of program span over the whole window:
a span's duration less the ``child`` spans that lie inside it (the
guide's rule: self time = a span less its children). A program that
records no such children gives nothing to read."""
import statistics


def read(ctx, spec):
    by_name = ctx.spans.by_name()
    spans, children = by_name.get(spec["span"]), by_name.get(spec["child"])
    if not spans or not children:
        return None
    selfs = []
    for s in spans:
        inside = sum(min(c.end_unix, s.end_unix) - max(c.start_unix,
                                                       s.start_unix)
                     for c in children
                     if c.start_unix < s.end_unix and c.end_unix > s.start_unix)
        selfs.append(s.end_unix - s.start_unix - inside)
    return statistics.median(selfs) * float(spec.get("scale", 1.0))
