"""Sum of one kind of program span over the traced window, in %."""


def read(ctx, spec):
    if ctx.trace_window_unix is None:
        return None
    lo, hi = ctx.trace_window_unix
    spans = ctx.traced_spans.by_name().get(spec["span"])
    if not spans:
        return None
    total = sum(min(s.end_unix, hi) - max(s.start_unix, lo) for s in spans)
    return 100.0 * total / (hi - lo)
