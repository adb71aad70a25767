"""Spans of the program's own tracer (``utils/tracing.py``), gathered as
finished traces arrive and put on the wall clock, so that they can be
laid against the device trace (whose host annotations anchor the two).
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, NamedTuple


class HostSpan(NamedTuple):
    name: str
    start_unix: float
    end_unix: float
    attrs: dict


class SpanLog:
    """``tracer.on_trace(log.ingest)`` — keeps every span of every
    finished trace."""

    def __init__(self):
        self.spans: List[HostSpan] = []
        self.dropped = 0

    def ingest(self, trace: dict) -> None:
        base = trace["start_unix"]
        self.dropped += int(trace.get("dropped_spans", 0))
        for s in trace.get("spans", []):
            t0 = base + s["start_s"]
            self.spans.append(HostSpan(
                s["name"], t0, t0 + s["duration_s"], s.get("attrs", {})))

    def by_name(self) -> Dict[str, List[HostSpan]]:
        out: Dict[str, List[HostSpan]] = defaultdict(list)
        for s in self.spans:
            out[s.name].append(s)
        return dict(out)

    def within(self, lo: float, hi: float) -> "SpanLog":
        sub = SpanLog()
        sub.spans = [s for s in self.spans
                     if s.end_unix > lo and s.start_unix < hi]
        return sub
