"""What a driver is handed for one run: the cell's data, the seed, the
window's length, and the few services every driver needs (a log line, a
count of compilations, the profiler around part of a traced window, the
device's peak memory).
"""

from __future__ import annotations

import gc
import tempfile
import time
from contextlib import contextmanager
from pathlib import Path
from typing import List, Optional, Tuple

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_events = 0
_listening = False


def _listen_for_compiles() -> None:
    global _listening
    if _listening:
        return
    from jax import monitoring

    def on_event(event: str, duration: float, **kw) -> None:
        global _compile_events
        if event == _COMPILE_EVENT:
            _compile_events += 1

    monitoring.register_event_duration_secs_listener(on_event)
    _listening = True


class CompileCounter:
    """Backend compilations since it was made (``jax.monitoring``; a
    program found in the persistent cache still counts: it was not in
    this process before the window)."""

    def __init__(self):
        _listen_for_compiles()
        self.start = _compile_events

    def new(self) -> int:
        return _compile_events - self.start


class Profiler:
    """``jax.profiler`` around a few seconds of the steady window of a
    traced run, through the program's own capture
    (``utils/profiling.py::trace``). Host annotations written while it
    runs are remembered with their wall-clock start, which anchors the
    program's spans to the device trace's clock."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled = enabled
        self.seconds = seconds
        self.dir: Optional[str] = None
        self._tmp = None
        self._cm = None
        self.started_at: Optional[float] = None   # perf_counter
        self.window_unix: Optional[Tuple[float, float]] = None
        self.anchors: List[Tuple[str, float]] = []  # (name, unix start)

    @property
    def running(self) -> bool:
        return self._cm is not None

    def start(self) -> None:
        from code_intelligence_tpu.utils import profiling

        self._tmp = tempfile.TemporaryDirectory(prefix="bench_trace_")
        self.dir = self._tmp.name
        self._cm = profiling.trace(self.dir)
        self._cm.__enter__()
        self.started_at = time.perf_counter()
        self._t0_unix = time.time()

    def step(self, first_done: bool) -> None:
        """Call between operations: starts the capture once the first
        operation of the window is done, stops it once it has run for
        its seconds."""
        if not self.enabled:
            return
        if self._cm is None and self.window_unix is None and first_done:
            self.start()
        elif self._cm is not None and \
                time.perf_counter() - self.started_at >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if self._cm is None:
            return
        end = time.time()  # before the capture is written out
        self._cm.__exit__(None, None, None)
        self._cm = None
        self.window_unix = (self._t0_unix, end)

    @contextmanager
    def annotate(self, name: str):
        if self._cm is None:
            yield
            return
        import jax

        self.anchors.append((name, time.time()))
        with jax.profiler.TraceAnnotation(name):
            yield

    def cleanup(self) -> None:
        if self._tmp is not None:
            self._tmp.cleanup()
            self._tmp = None


class RunContext:
    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 overrides: Optional[dict] = None, t_process: float = None,
                 runtime_start_s: float = 0.0):
        self.name = cell["name"]
        self.cell = cell["cell"]
        self.config = cell["config"]
        self.mix = cell["mix"]
        self.bench_dir: Path = cell["bench_dir"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.overrides = dict(overrides or {})
        self.t_process = t_process if t_process is not None else time.time()
        self.runtime_start_s = float(runtime_start_s)
        self.setup_s: Optional[float] = None
        self.profiler = Profiler(
            self.trace, float(cell["mix"].get("trace_seconds", 3.0)))

    def log(self, msg: str) -> None:
        print(f"[bench +{time.time() - self.t_process:7.2f}s] {msg}",
              flush=True)

    def window_opens(self) -> None:
        """The first measured operation comes next: set-up ends here.
        ``setup_s`` is everything since the process started but the
        runtime's own start (``run.require_device``)."""
        self.setup_s = time.time() - self.t_process - self.runtime_start_s
        self.log(f"set-up done in {self.setup_s:.3f} s, besides "
                 f"{self.runtime_start_s:.3f} s of the runtime's own start; "
                 "window opens")

    def compile_counter(self) -> CompileCounter:
        return CompileCounter()

    @staticmethod
    def memory_peak_bytes() -> int:
        import jax

        peak = 0
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            # live buffers plus what the runtime reserves for compiled
            # programs' temporaries: the TPU allocator keeps the two
            # apart (a program holding a 2 GB tensor showed 0.87 GB
            # "in use"; its temp size turned up as bytes_reserved, and
            # stays reserved once the program has run). The two peaks
            # need not fall together. Against in_use + reserved sampled
            # every 2 ms through a run (PR 23): qrnn_bulk_mixed equal
            # (4,306,116,096 B), lstm_bulk_mixed 0.5 % over, and
            # lstm_train_lm 13 % over (in_use peaks in set-up, at
            # 3.23 GB, before the step's 3.27 GB is reserved).
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                       + int(stats.get("peak_bytes_reserved", 0)))
        return peak

    @staticmethod
    def release(*objects) -> None:
        """Drop the program's device state before the reference runs."""
        for obj in objects:
            for attr in list(vars(obj)):
                try:
                    setattr(obj, attr, None)
                except (AttributeError, TypeError):
                    pass  # a read-only attribute holds no device buffer
        gc.collect()
