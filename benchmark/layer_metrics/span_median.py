"""Median duration of one kind of program span over the whole window."""
import statistics


def read(ctx, spec):
    spans = ctx.spans.by_name().get(spec["span"])
    if not spans:
        return None
    return statistics.median(s.end_unix - s.start_unix for s in spans) \
        * float(spec.get("scale", 1.0))
