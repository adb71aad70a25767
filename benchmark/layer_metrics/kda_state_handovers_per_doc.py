"""Hand-overs of carried state a document: a group of ``chunks`` chunk
programs hands its state over ``chunks - 1`` times, so sum of (``chunks``
- 1) x ``batch`` over sum of ``batch`` over the window's ``engine.group``
spans, from attributes they already carry."""


def read(ctx, spec):
    groups = [g for g in ctx.spans.by_name().get("engine.group", [])
              if "chunks" in g.attrs and "batch" in g.attrs]
    rows = sum(float(g.attrs["batch"]) for g in groups)
    if rows <= 0:
        return None
    return sum((float(g.attrs["chunks"]) - 1) * float(g.attrs["batch"])
               for g in groups) / rows
