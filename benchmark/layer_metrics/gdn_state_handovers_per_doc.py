"""Hand-overs of the matrix state a document, as the encoder counted them
on the device: ``gdn_state_handovers`` of the window's ``engine.finalize``
spans (in every chunk program after a group's first, the rows STILL
GOING: a finished row and a padding row are handed nothing that is read)
over the documents of its ``engine.group`` spans (``rows``). A program
whose spans lack the attribute gives nothing to read."""


def read(ctx, spec):
    by_name = ctx.spans.by_name()
    handed = [float(s.attrs["gdn_state_handovers"])
              for s in by_name.get("engine.finalize", [])
              if "gdn_state_handovers" in s.attrs]
    docs = sum(float(g.attrs["rows"]) for g in by_name.get("engine.group", [])
               if "rows" in g.attrs)
    if not handed or docs <= 0:
        return None
    return sum(handed) / docs
