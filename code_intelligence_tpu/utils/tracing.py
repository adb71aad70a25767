"""Zero-dependency request tracing: spans, context propagation, capture.

The reference's only "tracing" is W&B step metrics (SURVEY.md §5); PR 1
added ``/metrics`` gauges, but a slow ``/text`` request was still a black
box — queue wait, device time and GitHub write-back were indistinguishable.
This module is the missing layer, built the way serving systems attribute
latency per pipeline stage (LightSeq's stage timers, PAPERS.md):

* ``Tracer.span(name, **attrs)`` — context managers forming a tree; the
  innermost open span is tracked per thread, so nested spans attach
  automatically within a thread.
* **Thread handoff** — a span's ``.context`` (:class:`SpanContext`) is an
  immutable token that crosses queues/threads; ``tracer.span(name,
  parent=ctx)`` or :func:`record_span` attach work done on another thread
  (the micro-batcher loop, the slot scheduler) to the originating request's
  trace. Pinned by tests/test_tracing.py.
* **W3C ``traceparent``** — :meth:`Tracer.extract` reads the standard
  ``00-<trace_id>-<span_id>-<flags>`` header from inbound HTTP requests or
  queue-event attributes; :func:`inject` stamps it on outbound requests
  (github/transport.py), so worker → embedding-server → GitHub hops share
  one trace id.
* **Two export surfaces** — a bounded ring of finished traces served as
  JSON on ``/debug/traces`` (plus a separate pinned ring for traces over
  ``slow_threshold_s``: slow-request capture survives ring churn), and
  Chrome trace-event JSON (:func:`to_chrome`) loadable in Perfetto; every
  finished span's duration also rolls up into the bound
  ``utils.metrics.Registry`` as the ``trace_span_seconds`` histogram
  labeled by span name.

Always-on-safe by construction (the same observer-not-dependency rule as
training/trackers.py): sampling is decided once per trace at the root,
memory is bounded (trace rings, per-trace span cap, live-trace cap), and
no tracer failure may ever surface into the request path — every internal
mutation is guarded and downgraded to a debug log line.
"""

from __future__ import annotations

import json
import logging
import random
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, List, Optional

log = logging.getLogger(__name__)

TRACEPARENT = "traceparent"

#: span-count cap per trace: a runaway loop inside one request must not
#: grow its trace without bound; overflow is counted, not silently eaten
MAX_SPANS_PER_TRACE = 512
#: live (unfinished) traces cap — leaked roots (a span never exited on a
#: crashed thread) are evicted oldest-first instead of accumulating
MAX_LIVE_TRACES = 256
#: recently-finished traces kept amendable: a span that STARTED before
#: the root ended but finishes just after (a hedged duplicate still in
#: flight when the winner's response went out, fleet/router.py) lands in
#: the already-rendered tree instead of being dropped
MAX_CLOSING_TRACES = 32

# one module-level per-thread stack of open spans, shared by ALL tracer
# instances: injection points (github/transport.py) and deep modules
# (engine/slots/batcher) see the ambient request context without knowing
# which component's tracer opened it
_ambient = threading.local()


def _stack() -> List["Span"]:
    s = getattr(_ambient, "spans", None)
    if s is None:
        s = _ambient.spans = []
    return s


class SpanContext:
    """Immutable handoff token: enough to parent a span from any thread
    (and to emit a ``traceparent``), plus the owning tracer so deep
    modules can record against it without holding a tracer themselves."""

    __slots__ = ("trace_id", "span_id", "sampled", "tracer")

    def __init__(self, trace_id: str, span_id: str, sampled: bool,
                 tracer: Optional["Tracer"]):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled
        self.tracer = tracer

    def traceparent(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-{'01' if self.sampled else '00'}"


class Span:
    """One timed operation. Use as a context manager (``with tracer.span
    (...)``) or explicitly via ``Tracer.start_span`` + ``.end()``."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "t0", "t1",
                 "attrs", "sampled", "thread", "_tracer", "_on_stack")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str], sampled: bool, tracer: "Tracer",
                 attrs: Dict[str, Any]):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.sampled = sampled
        self.attrs = attrs
        self.thread = threading.current_thread().name
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None
        self._tracer = tracer
        self._on_stack = False

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id, self.sampled,
                           self._tracer)

    def set(self, **attrs) -> "Span":
        """Attach attributes after creation (guarded; never raises)."""
        try:
            self.attrs.update(attrs)
        except Exception:
            pass
        return self

    def end(self) -> None:
        if self.t1 is None:
            self.t1 = time.perf_counter()
            self._tracer._finish_span(self)

    # -- context-manager protocol -------------------------------------

    def __enter__(self) -> "Span":
        try:
            _stack().append(self)
            self._on_stack = True
        except Exception:
            pass
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            if exc_type is not None:
                self.attrs.setdefault("error", exc_type.__name__)
            if self._on_stack:
                s = _stack()
                if s and s[-1] is self:
                    s.pop()
                elif self in s:  # unbalanced exit on this thread — heal
                    s.remove(self)
            self.end()
        except Exception:
            log.debug("span exit failed (ignored)", exc_info=True)
        return False  # never swallow the traced code's exception


class _NullSpan:
    """Free no-op with the Span surface — returned when tracing is off."""

    __slots__ = ()
    name = trace_id = span_id = parent_id = thread = ""
    sampled = False
    t0 = t1 = 0.0
    attrs: Dict[str, Any] = {}

    @property
    def context(self) -> None:
        return None

    def set(self, **attrs):
        return self

    def end(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class _LiveTrace:
    __slots__ = ("trace_id", "root_id", "start_unix", "t0", "spans", "dropped")

    def __init__(self, trace_id: str, root_id: str):
        self.trace_id = trace_id
        self.root_id = root_id
        self.start_unix = time.time()
        self.t0 = time.perf_counter()
        self.spans: List[Span] = []
        self.dropped = 0


class Tracer:
    """Per-process span collector with bounded memory.

    One per component is fine (the embedding server and the worker each
    bind one to their own metrics registry); all instances share the
    per-thread ambient span stack, so cross-component nesting in one
    process still forms sensible trees.
    """

    def __init__(self, registry=None, sample_rate: float = 1.0,
                 max_traces: int = 64, slow_threshold_s: float = 1.0,
                 max_slow: int = 32, max_live: int = MAX_LIVE_TRACES):
        self.sample_rate = float(sample_rate)
        self.slow_threshold_s = float(slow_threshold_s)
        # live-trace cap: callers that legitimately hold many roots open at
        # once (the bench opens one per in-flight document) raise it to
        # their fan-out; serving keeps the default
        self.max_live = int(max_live)
        self._lock = threading.Lock()
        self._live: Dict[str, _LiveTrace] = {}
        self._ring: deque = deque(maxlen=max_traces)
        self._slow: deque = deque(maxlen=max_slow)
        # trace_id -> (rendered dict, live t0): recently-finished traces
        # still amendable by straggler spans (bounded, FIFO-evicted)
        self._closing: Dict[str, tuple] = {}
        self.registry = None
        self.traces_started = 0
        self.traces_dropped = 0
        # finished-trace observers (the SLO layer ingests per-stage
        # timestamps here); guarded like everything else — a failing
        # observer is logged and skipped, never surfaced into the
        # request path
        self._on_trace: List[Any] = []
        if registry is not None:
            self.bind_registry(registry)

    def on_trace(self, fn) -> None:
        """Register ``fn(trace_dict)`` to run when a trace finishes
        (root span ended; the dict is the same JSON-ready shape
        ``/debug/traces`` serves). Callbacks run outside the tracer
        lock and are guarded."""
        self._on_trace.append(fn)

    # -- metrics roll-up ----------------------------------------------

    def bind_registry(self, registry) -> None:
        """Attach a ``utils.metrics.Registry``: every finished span's
        duration lands in ``trace_span_seconds{span=<name>}``."""
        if registry is None or self.registry is registry:
            return
        try:
            registry.histogram(
                "trace_span_seconds",
                "span durations by span name (tracing roll-up)")
            self.registry = registry
        except Exception:
            log.debug("bind_registry failed (ignored)", exc_info=True)

    # -- span creation ------------------------------------------------

    def start_span(self, name: str, parent: Optional[SpanContext] = None,
                   **attrs):
        """Create a span WITHOUT entering the ambient stack — for explicit
        ``.end()`` call sites that hold many spans open at once (the bench
        harness opens one root per in-flight document)."""
        try:
            return self._start(name, parent, attrs)
        except Exception:
            log.debug("start_span failed (ignored)", exc_info=True)
            return _NULL_SPAN

    def span(self, name: str, parent: Optional[SpanContext] = None, **attrs):
        """Context-manager span. Parent resolution: explicit ``parent``
        (cross-thread handoff) > innermost open span on this thread > new
        root (a fresh trace, sampled at ``sample_rate``)."""
        return self.start_span(name, parent, **attrs)

    def _start(self, name: str, parent: Optional[SpanContext],
               attrs: Dict[str, Any]) -> Span:
        if parent is None:
            stack = _stack()
            if stack:
                parent = stack[-1].context
        span_id = f"{random.getrandbits(64):016x}"
        if parent is not None:
            span = Span(name, parent.trace_id, span_id, parent.span_id,
                        parent.sampled, self, attrs)
            if parent.tracer is not None and parent.tracer is not self:
                # record into the trace's owning tracer so one trace never
                # splits across rings
                span._tracer = parent.tracer
            return span
        # new root: the per-trace sampling decision happens exactly here
        trace_id = uuid.uuid4().hex
        sampled = self.sample_rate >= 1.0 or random.random() < self.sample_rate
        span = Span(name, trace_id, span_id, None, sampled, self, attrs)
        if sampled:
            with self._lock:
                self.traces_started += 1
                while len(self._live) >= self.max_live:
                    self._live.pop(next(iter(self._live)))
                    self.traces_dropped += 1
                self._live[trace_id] = _LiveTrace(trace_id, span_id)
        return span

    def record_span(self, name: str, t0: float, t1: float,
                    parent: Optional[SpanContext], **attrs) -> None:
        """Attach an already-timed interval (``time.perf_counter`` values)
        to a trace — the handoff primitive for schedulers that time work
        host-side and only later know which request it belonged to."""
        if parent is None or not parent.sampled:
            return
        tracer = parent.tracer or self
        try:
            span = Span(name, parent.trace_id,
                        f"{random.getrandbits(64):016x}", parent.span_id,
                        True, tracer, attrs)
            span.t0, span.t1 = float(t0), float(t1)
            tracer._finish_span(span)
        except Exception:
            log.debug("record_span failed (ignored)", exc_info=True)

    # -- assembly -----------------------------------------------------

    def _finish_span(self, span: Span) -> None:
        try:
            if not span.sampled:
                return
            reg = self.registry
            if reg is not None:
                try:
                    reg.observe("trace_span_seconds",
                                max(span.t1 - span.t0, 0.0),
                                labels={"span": span.name})
                except Exception:
                    pass
            finished = None
            with self._lock:
                live = self._live.get(span.trace_id)
                if live is None:
                    # root already finished: a straggler span (a hedged
                    # duplicate losing the race) amends the rendered
                    # tree while it stays in the closing window; a truly
                    # ancient handoff is dropped
                    self._amend_closing_locked(span)
                    return
                if (len(live.spans) >= MAX_SPANS_PER_TRACE
                        and span.span_id != live.root_id):
                    live.dropped += 1  # the root always lands, so a capped
                    return             # trace still renders its duration
                live.spans.append(span)
                if span.span_id == live.root_id:
                    del self._live[span.trace_id]
                    finished = self._render_trace(live)
                    self._ring.append(finished)
                    if finished["duration_s"] >= self.slow_threshold_s:
                        self._slow.append(finished)
                    self._closing[live.trace_id] = (finished, live.t0)
                    while len(self._closing) > MAX_CLOSING_TRACES:
                        self._closing.pop(next(iter(self._closing)))
            if finished is not None:
                # observers run OUTSIDE the tracer lock: an SLO ingest
                # takes its own locks, and holding both here would
                # couple lock orders across every instrumented caller
                for fn in self._on_trace:
                    try:
                        fn(finished)
                    except Exception:
                        log.debug("trace observer failed (ignored)",
                                  exc_info=True)
        except Exception:
            log.debug("finish_span failed (ignored)", exc_info=True)

    def _amend_closing_locked(self, span: Span) -> None:
        """Amend an already-rendered trace with a straggler span (caller
        holds the lock). COPY-ON-WRITE, never in-place: readers hold
        references to the published dict outside the lock (``traces()``
        copies the deque, serialization happens lock-free), so the
        amended trace is a NEW dict swapped into the rings — a
        concurrent reader sees either the old or the new version, both
        internally consistent."""
        entry = self._closing.get(span.trace_id)
        if entry is None:
            return
        rendered, t0 = entry
        if len(rendered["spans"]) >= MAX_SPANS_PER_TRACE:
            amended = {**rendered,
                       "dropped_spans": rendered["dropped_spans"] + 1}
        else:
            amended = {**rendered, "spans": sorted(
                rendered["spans"] + [{
                    "name": span.name,
                    "span_id": span.span_id,
                    "parent_id": span.parent_id,
                    "start_s": round(span.t0 - t0, 6),
                    "duration_s": round((span.t1 or span.t0) - span.t0, 6),
                    "thread": span.thread,
                    "attrs": dict(span.attrs),
                }], key=lambda s: s["start_s"])}
        self._closing[span.trace_id] = (amended, t0)
        for ring in (self._ring, self._slow):
            for i, t in enumerate(ring):
                if t is rendered:
                    ring[i] = amended
                    break

    @staticmethod
    def _render_trace(live: _LiveTrace) -> Dict[str, Any]:
        root = next((s for s in live.spans if s.span_id == live.root_id), None)
        spans = sorted(live.spans, key=lambda s: s.t0)
        return {
            "trace_id": live.trace_id,
            "root": root.name if root is not None else "?",
            "start_unix": live.start_unix,
            "duration_s": round(root.t1 - root.t0, 6) if root is not None else 0.0,
            "dropped_spans": live.dropped,
            "spans": [
                {
                    "name": s.name,
                    "span_id": s.span_id,
                    "parent_id": s.parent_id,
                    "start_s": round(s.t0 - live.t0, 6),
                    "duration_s": round((s.t1 or s.t0) - s.t0, 6),
                    "thread": s.thread,
                    "attrs": dict(s.attrs),
                }
                for s in spans
            ],
        }

    # -- read side ----------------------------------------------------

    def traces(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        """Most-recent-first finished traces (JSON-ready dicts)."""
        with self._lock:
            out = list(self._ring)
        out.reverse()
        return out[:n] if n else out

    def slow_traces(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        """Most-recent-first traces that exceeded ``slow_threshold_s``."""
        with self._lock:
            out = list(self._slow)
        out.reverse()
        return out[:n] if n else out

    # -- W3C propagation ----------------------------------------------

    def extract(self, headers) -> Optional[SpanContext]:
        """Parse a ``traceparent`` from any ``.get``-able mapping (HTTP
        headers, queue-event attributes). Returns a context usable as a
        root parent, or None on absence/malformation (never raises)."""
        try:
            raw = headers.get(TRACEPARENT) if headers is not None else None
            if not raw:
                return None
            parts = str(raw).strip().split("-")
            if len(parts) != 4:
                return None
            version, trace_id, span_id, flags = parts
            if (len(version) != 2 or len(trace_id) != 32
                    or len(span_id) != 16 or len(flags) != 2
                    or version == "ff"):
                return None
            # hex-validate every field (a non-hex version like "zz" must
            # be rejected, not treated as a valid future version)
            int(version, 16), int(trace_id, 16), int(span_id, 16)
            int(flags, 16)
            if trace_id == "0" * 32 or span_id == "0" * 16:
                return None
            sampled = bool(int(flags, 16) & 1)
            ctx = SpanContext(trace_id, span_id, sampled, self)
            if sampled:
                # continuing someone else's sampled trace: open a live
                # accumulator so local spans under it are captured
                with self._lock:
                    if trace_id not in self._live:
                        while len(self._live) >= self.max_live:
                            self._live.pop(next(iter(self._live)))
                            self.traces_dropped += 1
                        # root_id stays unknown until the first local span
                        self._live[trace_id] = _LiveTrace(trace_id, "")
            return ctx
        except Exception:
            return None

    def continue_trace(self, name: str, headers, **attrs):
        """Extract + open the local root span in one call: the inbound
        edge of a service (HTTP handler, queue callback)."""
        parent = self.extract(headers)
        span = self.start_span(name, parent=parent, **attrs)
        if parent is not None and parent.sampled and span is not _NULL_SPAN:
            with self._lock:
                live = self._live.get(span.trace_id)
                if live is not None and not live.root_id:
                    live.root_id = span.span_id
        return span


# ---------------------------------------------------------------------
# Module-level helpers (ambient-context API for deep modules)
# ---------------------------------------------------------------------

_default: Optional[Tracer] = None
_default_lock = threading.Lock()


def get_tracer() -> Tracer:
    """Process-global default tracer (training and other non-HTTP call
    sites that don't own a component tracer)."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Tracer()
    return _default


def current_context() -> Optional[SpanContext]:
    """Innermost open span on THIS thread, whichever tracer owns it."""
    try:
        s = _stack()
        return s[-1].context if s else None
    except Exception:
        return None


def set_attrs(**attrs) -> None:
    """Attributes onto the innermost open span on THIS thread; nothing
    without one. For what a deep module learns only while the span runs
    (the compile a dispatch paid, utils/flight_recorder.py)."""
    try:
        s = _stack()
        if s and attrs:
            s[-1].set(**attrs)
    except Exception:
        pass


def span(name: str, parent: Optional[SpanContext] = None, **attrs):
    """Ambient span: attaches to the explicit parent's tracer, else the
    thread's current trace. No-op (free) when neither exists — deep
    modules call this unconditionally without owning a tracer."""
    try:
        if parent is not None and parent.tracer is not None:
            return parent.tracer.span(name, parent=parent, **attrs)
        s = _stack()
        if s:
            return s[-1]._tracer.span(name, **attrs)
    except Exception:
        log.debug("ambient span failed (ignored)", exc_info=True)
    return _NULL_SPAN


def record_span(name: str, t0: float, t1: float,
                parent: Optional[SpanContext], **attrs) -> None:
    """Ambient record: no-op when ``parent`` is None/unsampled."""
    if parent is not None and parent.tracer is not None:
        parent.tracer.record_span(name, t0, t1, parent, **attrs)


def inject(headers: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Stamp the current thread's context as ``traceparent`` into a header
    dict (created if None). Outbound edge: github/transport.py calls this
    on every request; it never raises and never overwrites an explicit
    header."""
    headers = dict(headers) if headers else {}
    try:
        ctx = current_context()
        if ctx is not None and TRACEPARENT not in headers:
            headers[TRACEPARENT] = ctx.traceparent()
    except Exception:
        pass
    return headers


# ---------------------------------------------------------------------
# Chrome trace-event export (Perfetto-loadable)
# ---------------------------------------------------------------------

def to_chrome(traces: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Finished-trace dicts -> one Chrome trace-event JSON object
    (``{"traceEvents": [...]}``; load at https://ui.perfetto.dev). Each
    trace renders as its own process row; threads keep their names so a
    batcher handoff is visible as a lane change."""
    events: List[Dict[str, Any]] = []
    for pid, trace in enumerate(traces, start=1):
        base_us = trace.get("start_unix", 0.0) * 1e6
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": f"trace {trace['trace_id'][:8]} "
                             f"({trace.get('root', '?')})"},
        })
        tids: Dict[str, int] = {}
        for s in trace.get("spans", []):
            tid = tids.setdefault(s.get("thread", "main"), len(tids) + 1)
            events.append({
                "name": s["name"],
                "ph": "X",
                "pid": pid,
                "tid": tid,
                "ts": base_us + s["start_s"] * 1e6,
                "dur": max(s["duration_s"] * 1e6, 0.001),
                "args": {**s.get("attrs", {}), "span_id": s["span_id"],
                         "parent_id": s.get("parent_id")},
            })
        for name, tid in tids.items():
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": name}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------
# /debug/traces (shared by the embedding server and MetricsServer)
# ---------------------------------------------------------------------

def debug_traces_response(tracer: Optional[Tracer], query: str = ""):
    """Build the ``/debug/traces`` body: ``(status, bytes, content_type)``.

    Query knobs: ``n=<int>`` (recent-trace count, default 20),
    ``slow=1`` (serve only the pinned slow ring),
    ``format=chrome`` (one Perfetto-loadable trace-event JSON instead of
    the raw trace list).
    """
    if tracer is None:
        return 404, json.dumps({"error": "tracing not enabled"}).encode(), \
            "application/json"
    try:
        from urllib.parse import parse_qs

        q = parse_qs(query or "")
        n = int(q.get("n", ["20"])[0])
        slow_only = q.get("slow", ["0"])[0] in ("1", "true")
        traces = tracer.slow_traces(n) if slow_only else tracer.traces(n)
        if q.get("format", [""])[0] == "chrome":
            body = json.dumps(to_chrome(traces)).encode()
        else:
            body = json.dumps({
                "traces": traces,
                "slow": tracer.slow_traces(n),
                "slow_threshold_s": tracer.slow_threshold_s,
                "sample_rate": tracer.sample_rate,
                "traces_started": tracer.traces_started,
            }).encode()
        return 200, body, "application/json"
    except Exception as e:  # the debug surface must not 500 the listener
        return 500, json.dumps({"error": str(e)[:200]}).encode(), \
            "application/json"
