"""What a per-layer metric's reader is handed: the traced run's spans,
counters, the reduced device trace and the cell's data. A reader that
finds nothing to read returns ``None`` and its metric is left out.
"""

from __future__ import annotations

from typing import List, Optional

from benchmark.harness import peaks, xplane
from benchmark.harness.spans import SpanLog


class ReaderContext:
    def __init__(self):
        self.cell: dict = {}
        self.config: dict = {}
        self.mix: dict = {}
        self.counters: dict = {}
        self.result: dict = {}
        self.spans: SpanLog = SpanLog()         # whole window
        self.traced_spans: SpanLog = SpanLog()  # inside the device trace
        self.reduced: dict = {"busy_s": 0.0, "window_s": 0.0, "modules": {},
                              "device_ops": [], "gaps": [], "devices": 0}
        self.device_kind: str = ""
        self.window_s: float = 0.0
        self.trace_window_unix = None
        self.clock_offset: Optional[float] = None  # trace clock - unix

    @classmethod
    def build(cls, cell: dict, result: dict, ctx, dev: dict):
        self = cls()
        self.cell, self.config, self.mix = cell, cell["config"], cell["mix"]
        self.result = result
        self.counters = result.get("counters", {})
        self.window_s = result["window_s"]
        self.spans = result.get("spans") or SpanLog()
        self.device_kind = dev["kind"]
        prof = ctx.profiler
        path = xplane.find_xplane(prof.dir) if prof.dir else None
        if path is not None:
            events = xplane.read_events(path, host_lines=True)
            self.reduced = xplane.reduce_events(events)
            if prof.window_unix is not None:
                # the traced window is the capture's own length, idle
                # lead-in and tail included
                self.reduced["window_s"] = \
                    prof.window_unix[1] - prof.window_unix[0]
            self.clock_offset = _clock_offset(events, prof.anchors)
            if prof.window_unix is not None:
                self.trace_window_unix = prof.window_unix
                self.traced_spans = self.spans.within(*prof.window_unix)
        return self

    @property
    def peaks(self) -> dict:
        """The chip's published peaks; an unknown kind is an error."""
        return peaks.peaks_for(self.device_kind)

    def module_durations(self, needle: str) -> List[float]:
        """Device durations of every execution of the XLA modules whose
        name contains ``needle``."""
        out: List[float] = []
        for name, durs in self.reduced["modules"].items():
            if needle in name:
                out += durs
        return out

    def traced_tokens(self, spec: dict, executions: int) -> int:
        """Valid tokens of the work inside the device trace: training
        dispatches by the configuration's steps x rows x bptt (spec
        ``"tokens": "train_dispatch"``), documents by the ``n_tokens``
        the program's tokenize spans carry."""
        if spec.get("tokens") == "train_dispatch":
            t = self.config["train"]
            return executions * t["steps_per_dispatch"] * t["batch_size"] \
                * t["bptt"]
        spans = self.traced_spans.by_name().get("engine.tokenize", [])
        return sum(int(s.attrs.get("n_tokens", 0)) for s in spans)

    def breakdown(self) -> dict:
        host = []
        if self.clock_offset is not None:
            host = [(s.name, s.start_unix + self.clock_offset,
                     s.end_unix + self.clock_offset)
                    for s in self.traced_spans.spans]
        return {"device_ops": self.reduced["device_ops"],
                "idle_gaps": xplane.name_gaps(self.reduced["gaps"], host)}


def _clock_offset(events, anchors) -> Optional[float]:
    """Trace clock minus wall clock, from the host annotations the
    driver wrote: the i-th annotation of a name in the trace against the
    i-th wall-clock start remembered for it (median over all)."""
    import statistics

    by_name = {}
    for e in events:
        if not e.device.startswith(xplane.DEVICE_PLANE_PREFIX):
            by_name.setdefault(e.name, []).append(e.start_s)
    diffs = []
    seen = {}
    for name, unix in anchors:
        i = seen.get(name, 0)
        seen[name] = i + 1
        starts = sorted(by_name.get(name, []))
        if i < len(starts):
            diffs.append(starts[i] - unix)
    return statistics.median(diffs) if diffs else None
