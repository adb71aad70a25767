"""Host time between consecutive spans of one kind (end of one to the
start of the next) as a share in % of the time from the first's start
to the last's end. Read over the spans that lie wholly inside the
device trace's window: outside it the profiler's own start and stop sit
between two dispatches."""


def read(ctx, spec):
    if ctx.trace_window_unix is None:
        return None
    lo, hi = ctx.trace_window_unix
    spans = sorted((s for s in ctx.traced_spans.by_name().get(
        spec["span"], []) if s.start_unix >= lo and s.end_unix <= hi),
        key=lambda s: s.start_unix)
    if len(spans) < 2:
        return None
    gap = sum(max(0.0, b.start_unix - a.end_unix)
              for a, b in zip(spans, spans[1:]))
    return 100.0 * gap / (spans[-1].end_unix - spans[0].start_unix)
