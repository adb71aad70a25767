"""Mean of the attribute ``num`` over the window's spans of one kind,
each span weighted by its attribute ``weight`` (a span's own mean over
``weight`` cells, so the window's is the weighted one); with ``den``,
that mean over the like-weighted mean of ``den``. Spans that lack one of
the attributes (another encoder's, the parent commit's) are passed
over."""


def read(ctx, spec):
    keys = [spec[k] for k in ("num", "weight", "den") if k in spec]
    spans = [s for s in ctx.spans.by_name().get(spec["span"], [])
             if all(k in s.attrs for k in keys)]

    def weighted(key):
        return sum(float(s.attrs[key]) * float(s.attrs[spec["weight"]])
                   for s in spans)

    over = weighted(spec["den"]) if "den" in spec else sum(
        float(s.attrs[spec["weight"]]) for s in spans)
    return weighted(spec["num"]) / over if over > 0 else None
