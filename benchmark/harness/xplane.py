"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to what the
per-layer metrics read: per device, the executions of each XLA module
(a jitted program) and of each XLA op, on the device's own clock; from
them the busy seconds (union of the op intervals), the idle gaps, and
the operations that took the most time.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. The
reduction itself (``reduce_events``) works on plain tuples, so it is
tested on known inputs as well as on a small recorded trace.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from benchmark.harness import stats

DEVICE_PLANE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


class Event(NamedTuple):
    device: str    # plane name, e.g. "/device:TPU:0"
    line: str      # "XLA Modules" | "XLA Ops" | a host thread's name
    name: str
    start_s: float
    dur_s: float


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read_events(path: str, host_lines: bool = False) -> List[Event]:
    """Device module and op events (and, with ``host_lines``, the host
    planes' events) of one trace file, times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not is_dev and not host_lines:
            continue
        for line in plane.lines:
            if is_dev and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return out


def base_name(name: str) -> str:
    """``jit_fwd(123456789)`` -> ``jit_fwd``; ``fusion.12`` stays."""
    return name.split("(", 1)[0]


def op_label(name: str, limit: int = 96) -> str:
    """A device op's event name is its whole HLO line; keep the op's own
    name and the shape it produces."""
    head, _, rest = name.partition(" = ")
    label = head.lstrip("%")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    if shape and not shape.startswith("("):
        label += " " + shape
    return label[:limit]


def reduce_events(events: Iterable[Event], top: int = 10) -> dict:
    """Per-trace reduction, averaged over the devices that ran anything:

    ``busy_s``     union of the op intervals (modules where a device has
                   no op line), mean over devices
    ``window_s``   first start to last end over all device events
    ``modules``    {base name: [durations]} over all devices
    ``device_ops`` [[name, seconds], ...] top ops by total time
    ``gaps``       [(start, end), ...] idle gaps of the busiest device
    """
    events = list(events)
    dev = [e for e in events if e.device.startswith(DEVICE_PLANE_PREFIX)]
    if not dev:
        return {"busy_s": 0.0, "window_s": 0.0, "modules": {},
                "device_ops": [], "gaps": [], "devices": 0}
    lo = min(e.start_s for e in dev)
    hi = max(e.start_s + e.dur_s for e in dev)
    by_dev: Dict[str, Dict[str, List[Event]]] = defaultdict(
        lambda: defaultdict(list))
    for e in dev:
        by_dev[e.device][e.line].append(e)
    busy, gaps_of = {}, {}
    op_total: Dict[str, float] = defaultdict(float)
    modules: Dict[str, List[float]] = defaultdict(list)
    for name, lines in by_dev.items():
        ops = lines.get(OP_LINE) or lines.get(MODULE_LINE, [])
        iv = [(e.start_s, e.start_s + e.dur_s) for e in ops]
        busy[name] = stats.union_seconds(iv)
        gaps_of[name] = stats.gaps(iv, lo, hi)
        for e in lines.get(OP_LINE, []):
            op_total[op_label(e.name)] += e.dur_s
        for e in lines.get(MODULE_LINE, []):
            modules[base_name(e.name)].append(e.dur_s)
    busiest = max(busy, key=busy.get)
    ranked = sorted(op_total.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy.values()) / len(busy),
            "window_s": hi - lo,
            "t0_s": lo,
            "modules": dict(modules),
            "device_ops": [[k, v] for k, v in ranked],
            "gaps": gaps_of[busiest],
            "devices": len(busy)}


def name_gaps(gaps: List[Tuple[float, float]],
              host_spans: List[Tuple[str, float, float]],
              top: int = 10) -> List[list]:
    """Total idle seconds by what the host was doing: each gap is split
    among the host spans ``(name, start, end)`` that overlap it (the
    innermost, i.e. shortest, span wins where several do), the rest is
    ``"(no span)"``. Both on one clock."""
    total: Dict[str, float] = defaultdict(float)
    spans = sorted(host_spans, key=lambda s: s[2] - s[1])
    for g0, g1 in gaps:
        covered: List[Tuple[float, float]] = []
        for name, s0, s1 in spans:
            a, b = max(g0, s0), min(g1, s1)
            if b <= a:
                continue
            fresh = b - a - stats.union_seconds(
                [(max(a, c0), min(b, c1)) for c0, c1 in covered
                 if min(b, c1) > max(a, c0)])
            if fresh > 0:
                total[name] += fresh
                covered.append((a, b))
        rest = (g1 - g0) - stats.union_seconds(covered)
        if rest > 0:
            total["(no span)"] += rest
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v] for k, v in ranked]
