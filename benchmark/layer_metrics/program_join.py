"""The device time of each forward program laid against what the program
held (PR 34). The engine names a compiled forward by its shape, so the
capture's modules read ``jit_fwd_b<rows>_l<bucket>``, and records one
``engine.program`` span a chunk program with the same ``rows`` and
``bucket`` and its ``valid_tokens`` / ``lane_steps``. The capture opens
and closes between calls and one chip runs programs in the order they
were enqueued, so the k-th execution of a shape is the k-th span of that
shape. Where that cannot be shown (no module carries a shape: an older
program, or no device plane; or the counts of any shape differ) there is
nothing to read: never a guess.

``stat`` picks the statistic over the joined programs:

``padded_time_pct``   100 x sum of dur x (1 - valid_tokens / lane_steps)
                      over sum of dur: the share of the forwards' device
                      seconds spent on padding
``narrow_time_pct``   100 x the device seconds of the programs that ran
                      at fewer rows than their group's batch, over all
``narrow_lane_cost``  device seconds a lane-step of the programs at the
                      smallest ``rows`` the window ran, over the same of
                      the programs at ``rows`` = ``batch``
"""
import re

_SHAPE = re.compile(r"fwd_b(\d+)_l(\d+)$")


def join(ctx, span="engine.program"):
    """``[(seconds, attrs)]`` of every forward program in the traced
    window, or ``None``."""
    modules = {}
    for name, durs in ctx.reduced["modules"].items():
        m = _SHAPE.search(name)
        if m:
            modules.setdefault((int(m[1]), int(m[2])), []).extend(durs)
    spans = {}
    for s in sorted(ctx.traced_spans.by_name().get(span, []),
                    key=lambda s: s.start_unix):
        spans.setdefault((int(s.attrs["rows"]), int(s.attrs["bucket"])),
                         []).append(s.attrs)
    if not modules or {k: len(v) for k, v in modules.items()} \
            != {k: len(v) for k, v in spans.items()}:
        return None
    return [pair for shape, durs in modules.items()
            for pair in zip(durs, spans[shape])]


def _per_lane(programs):
    lanes = sum(a["lane_steps"] for _, a in programs)
    return sum(d for d, _ in programs) / lanes if lanes else None


def read(ctx, spec):
    programs = join(ctx, spec.get("span", "engine.program"))
    total = sum(d for d, _ in programs) if programs else 0.0
    if total <= 0:
        return None
    stat = spec["stat"]
    if stat == "padded_time_pct":
        return 100.0 * sum(
            d * (1.0 - a["valid_tokens"] / a["lane_steps"])
            for d, a in programs) / total
    narrow = [(d, a) for d, a in programs if a["rows"] < a["batch"]]
    if stat == "narrow_time_pct":
        return 100.0 * sum(d for d, _ in narrow) / total
    if stat == "narrow_lane_cost":
        if not narrow:
            return None
        least = min(a["rows"] for _, a in narrow)
        wide = _per_lane([p for p in programs if p[1]["rows"] == p[1]["batch"]])
        small = _per_lane([p for p in narrow if p[1]["rows"] == least])
        return small / wide if wide and small else None
    raise ValueError(f"program_join: no statistic {stat!r}")
