"""Profiling / tracing utilities.

SURVEY.md §5: the reference has no systems profiler — its "tracing" is
W&B step metrics. The TPU build keeps the metrics-hook interface
(``JSONLLogger``) and adds the real profiler: ``jax.profiler`` trace
capture around training/serving regions, viewable in TensorBoard or
Perfetto.
"""

from __future__ import annotations

import contextlib
import logging
import math
import os
import tempfile
import threading
import time
from pathlib import Path
from typing import Callable, Dict, Iterator, Optional

log = logging.getLogger(__name__)

# process-global active-trace guard: the XLA profiler is a singleton —
# a second start_trace while one is running fails deep inside the
# profiler with an opaque error, so guard it here with a clear one
_trace_lock = threading.Lock()
_active_dir: Optional[str] = None


def _get_profiler():
    """``jax.profiler``, or None when jax (or its profiler) can't be
    imported — the degrade signal for jax-free processes exposing the
    ``/debug/profile`` route."""
    try:
        import jax

        return jax.profiler
    except Exception:
        return None


def profiler_available() -> bool:
    return _get_profiler() is not None


@contextlib.contextmanager
def trace(log_dir, enabled: bool = True) -> Iterator[None]:
    """Capture a jax profiler trace for the enclosed region.

    Hardened for HTTP exposure (``/debug/profile``): ``stop_trace`` is
    guaranteed to run when the enclosed region raises, a concurrent /
    nested start fails fast with a clear error naming the active
    capture dir, and a missing ``jax.profiler`` degrades to a logged
    no-op instead of taking the listener down.

    Usage::

        with profiling.trace("/tmp/trace"):
            for batch in loader:
                state, m = trainer.train_step(state, *batch)
    """
    global _active_dir
    if not enabled:
        yield
        return
    profiler = _get_profiler()
    if profiler is None:
        log.warning("jax.profiler unavailable; trace(%s) is a no-op",
                    log_dir)
        yield
        return
    log_dir = str(log_dir)
    with _trace_lock:
        if _active_dir is not None:
            raise RuntimeError(
                f"a profiler trace is already active (capturing to "
                f"{_active_dir}); the XLA profiler is a process "
                f"singleton — stop that capture first")
        _active_dir = log_dir
    try:
        Path(log_dir).mkdir(parents=True, exist_ok=True)
        profiler.start_trace(log_dir)
    except BaseException:
        # start never happened: release the guard so the NEXT capture
        # isn't spuriously refused
        with _trace_lock:
            _active_dir = None
        raise
    try:
        yield
    finally:
        # stop unconditionally — a capture leaked across an exception
        # would poison every later profile request in the process
        try:
            profiler.stop_trace()
        finally:
            with _trace_lock:
                _active_dir = None
        log.info("profiler trace written to %s", log_dir)


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named sub-region inside a trace (TraceAnnotation): the engine's
    ``engine.host_prep`` (one per preparation slab of a groups-path
    call: the text rules and tokenising between two group enqueues) /
    ``engine.group`` / ``engine.finalize`` and the trainer's
    ``train.dispatch`` phases, on the device trace's own clock. Written whether or not a capture runs (a flag test when none
    does); no-op when the profiler, or its annotation, is unavailable
    (same degrade rule as :func:`trace`) — callers sit on the request
    path."""
    annotation = getattr(_get_profiler(), "TraceAnnotation", None)
    if annotation is None:
        yield
        return
    with annotation(name):
        yield


class ProfileBusy(RuntimeError):
    """A capture is already in flight (the profiler is a process
    singleton; concurrent ``/debug/profile`` pulls are single-flight)."""


class ProfileCapture:
    """On-demand, bounded, single-flight device-profile capture — the
    ``/debug/profile?seconds=N`` backend (serving/server.py).

    The profiler traces the WHOLE process for the window: a capture
    taken while handler threads serve live traffic records exactly the
    device programs and host gaps a "why is p99 up" investigation
    needs, without restarting the server under a profiling harness.

    * **single-flight** — one capture at a time; a concurrent request
      gets :class:`ProfileBusy` (HTTP 409), never a second
      ``start_trace`` into the singleton profiler.
    * **bounded** — ``seconds`` is clamped to ``(0, max_seconds]``; an
      HTTP caller cannot park the profiler (and its capture buffers)
      on the process indefinitely.
    * **degrades** — without ``jax.profiler`` the capture succeeds as
      a no-op and says so (``profiler_available: false``).
    """

    def __init__(self, base_dir: Optional[str] = None,
                 max_seconds: float = 30.0,
                 max_captures: int = 8,
                 sleep: Callable[[float], None] = time.sleep):
        self.base_dir = str(base_dir) if base_dir else os.path.join(
            tempfile.gettempdir(), "ci_tpu_profiles")
        self.max_seconds = float(max_seconds)
        # retention bound: capture dirs are written per pull and would
        # otherwise accumulate until the disk fills — keep the newest N
        self.max_captures = max(int(max_captures), 1)
        self._sleep = sleep  # injectable: tests capture without waiting
        self._mu = threading.Lock()
        self._busy = False
        self.captures = 0
        self.last: Optional[Dict] = None

    def capture(self, seconds: float) -> Dict:
        """Run one capture window; returns the JSON-ready report
        (trace dir, wall time, file count). Raises :class:`ProfileBusy`
        when a capture is already running."""
        seconds = float(seconds)
        if not math.isfinite(seconds):
            # nan survives min/max clamping (both comparisons are False)
            # and would start a real process-wide capture only to die in
            # sleep() — reject before any profiler side effect
            raise ValueError(f"seconds must be finite, got {seconds!r}")
        seconds = min(max(seconds, 0.05), self.max_seconds)
        with self._mu:
            if self._busy:
                raise ProfileBusy(
                    "a profile capture is already in flight (the XLA "
                    "profiler is a process singleton)")
            self._busy = True
        try:
            out_dir = os.path.join(
                self.base_dir,
                time.strftime("profile-%Y%m%d-%H%M%S")
                + f"-{self.captures}")
            available = profiler_available()
            t0 = time.perf_counter()
            with trace(out_dir):
                # the capture window: the profiler records every thread's
                # device/host activity while this handler sleeps
                self._sleep(seconds)
            elapsed = time.perf_counter() - t0
            n_files = (sum(1 for p in Path(out_dir).rglob("*")
                           if p.is_file())
                       if os.path.isdir(out_dir) else 0)
            info = {
                "trace_dir": out_dir,
                "requested_seconds": seconds,
                "elapsed_s": round(elapsed, 3),
                "files": n_files,
                "profiler_available": available,
                "at": time.time(),
                "view": "load the capture dir in TensorBoard or "
                        "ui.perfetto.dev (xplane.pb / trace.json.gz)",
            }
            self.captures += 1
            self.last = info
            self._prune()
            return info
        finally:
            with self._mu:
                self._busy = False

    def _prune(self) -> None:
        """Keep only the newest ``max_captures`` capture dirs — a
        failure to prune must never fail the capture that triggered
        it."""
        try:
            dirs = sorted((p for p in Path(self.base_dir).iterdir()
                           if p.is_dir() and p.name.startswith("profile-")),
                          key=lambda p: p.stat().st_mtime)
            for stale in dirs[:-self.max_captures]:
                import shutil

                shutil.rmtree(stale, ignore_errors=True)
        except OSError:
            pass


def debug_profile_response(capture: Optional[ProfileCapture],
                           query: str = ""):
    """Build the ``/debug/profile`` body: ``(status, bytes, ctype)``.
    Query knobs: ``seconds=<float>`` (default 2, clamped to the
    capture's bound). 400 on unparseable/non-finite ``seconds`` before
    any profiler side effect; 409 while another capture runs; the debug
    surface never raises into the listener."""
    import json

    if capture is None:
        return 404, json.dumps(
            {"error": "profiling not enabled"}).encode(), "application/json"
    try:
        from urllib.parse import parse_qs

        q = parse_qs(query or "")
        raw = q.get("seconds", ["2"])[0]
        try:
            seconds = float(raw)
            if not math.isfinite(seconds):
                raise ValueError
        except ValueError:
            return 400, json.dumps(
                {"error": f"seconds must be a finite number, "
                          f"got {raw!r}"}).encode(), "application/json"
        info = capture.capture(seconds)
        return 200, json.dumps(info).encode(), "application/json"
    except ProfileBusy as e:
        return 409, json.dumps({"error": str(e)}).encode(), \
            "application/json"
    except Exception as e:
        return 500, json.dumps({"error": str(e)[:200]}).encode(), \
            "application/json"


class StepTimer:
    """Lightweight step-time statistics (p50/p90/p99/max) for bench
    harnesses and the training flight recorder (loop.py times every
    dispatch through one of these — serve-path and train-path share this
    summary vocabulary).

    Times host-visible step latency; call ``sync()`` on a step output
    before ``stop`` for truthful device timings (dispatch is
    asynchronous: without it the sample is the enqueue).

    ``exclude_first_n`` drops the first N samples from ``summary()``
    percentiles (the samples stay in ``self.samples``): the first step of
    each compiled shape pays XLA compile, and a 30s compile in a 5ms-step
    distribution otherwise lands squarely on max/p99.
    """

    def __init__(self, exclude_first_n: int = 0):
        self.samples = []
        self.exclude_first_n = int(exclude_first_n)
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self) -> float:
        assert self._t0 is not None, "start() not called"
        dt = time.perf_counter() - self._t0
        self.samples.append(dt)
        self._t0 = None
        return dt

    @contextlib.contextmanager
    def step(self) -> Iterator[None]:
        self.start()
        try:
            yield
        finally:
            self.stop()

    def summary(self, exclude_first_n: Optional[int] = None) -> Dict[str, float]:
        skip = (self.exclude_first_n if exclude_first_n is None
                else int(exclude_first_n))
        s = sorted(self.samples[skip:] if skip > 0 else self.samples)
        if not s:
            return {}
        n = len(s)
        return {
            "n": n,
            "mean_s": sum(s) / n,
            "p50_s": s[n // 2],
            "p90_s": s[min(n - 1, int(n * 0.9))],
            "p99_s": s[min(n - 1, int(n * 0.99))],
            "max_s": s[-1],
        }
