"""Training flight recorder: bounded step telemetry + divergence sentinels
+ XLA compile/memory accounting.

PR 2 gave the *serving* path per-request traces; the training path — the
half of the north-star that actually reproduces the ULMFiT pipeline —
was still a black box: `LMTrainer.fit` emitted coarse epoch logs, and a
NaN loss was discovered by reading a dead run's perplexity. Production
LM training stacks treat per-step telemetry and divergence detection as
first-class (the monitoring/callback designs around fastai-era training
loops and large-batch LM practice, PAPERS.md); this module is that layer,
built on the same observer-not-dependency rules as utils/tracing.py:

* :class:`FlightRecorder` — every train/eval step appends ONE fixed-size
  structured record (step, loss, grad-norm, param-norm, LR, tokens/sec,
  step wall time, compile flag) into a preallocated numpy ring. Memory
  is bounded by construction; appending is a few array writes.
* **Divergence sentinels** — pluggable checks run on each record:
  non-finite loss, grad-norm spike vs. a running EMA, loss plateau.
  A tripped sentinel produces a :class:`Trip` and fires registered
  callbacks; halt-severity trips let the training loop halt-and-
  checkpoint instead of silently burning the run
  (training/telemetry.py wires this into `LMTrainer.fit`).
* **Crash/halt dump** — :meth:`FlightRecorder.dump` writes the ring as
  JSONL (one meta line, then one record per line) next to the
  checkpoint, so the last N steps before a divergence are always
  recoverable post-mortem.
* **XLA accounting** — :func:`instrument` wraps a ``jax.jit`` function
  so each newly-compiled input signature is lowered + compiled
  explicitly (jax AOT), recording compile wall time,
  ``cost_analysis()`` flops, and ``memory_analysis()`` HBM footprint
  per compiled shape. Results land as ``compile_seconds`` /
  ``compiled_flops`` / ``compiled_hbm_bytes`` gauges (labels: fn,
  shape) in a bound ``utils.metrics.Registry`` and on the
  ``/debug/flight`` endpoint (MetricsServer and the embedding server).
  The wrapper NEVER becomes a dependency: any failure in the
  accounting path permanently falls back to the plain jitted callable.

jax is imported lazily — the module must stay importable in jax-free
processes (the fleet gates' fake replicas import the serving module,
which imports this for ``/debug/flight``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import math
import os
import threading
import time
import weakref
from collections import deque
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from code_intelligence_tpu.utils import tracing

log = logging.getLogger(__name__)

#: the fixed flight-record schema (field, numpy dtype) — RUNBOOK §18
RECORD_FIELDS: Tuple[Tuple[str, str], ...] = (
    ("step", "i8"),            # global optimizer step (host-side counter)
    ("kind", "U5"),            # "train" | "eval"
    ("wall_time", "f8"),       # unix timestamp at record time
    ("loss", "f8"),
    ("grad_norm", "f8"),
    ("param_norm", "f8"),
    ("lr", "f8"),
    ("tokens_per_sec", "f8"),
    ("step_time_s", "f8"),
    ("compile", "?"),          # this step paid an XLA compile
)
RECORD_DTYPE = np.dtype(list(RECORD_FIELDS))
_NUMERIC_FIELDS = tuple(
    name for name, dt in RECORD_FIELDS if dt in ("f8", "i8"))


# ---------------------------------------------------------------------
# Sentinels
# ---------------------------------------------------------------------


@dataclasses.dataclass
class Trip:
    """One sentinel firing: enough to log, halt, and post-mortem."""

    sentinel: str
    reason: str
    step: int
    severity: str  # "halt" | "warn"
    wall_time: float


class Sentinel:
    """One divergence check, run on every appended record. Sentinels are
    stateful (EMAs, plateau counters) and must never raise — the
    recorder guards them, but keep ``check`` total anyway."""

    name = "sentinel"
    severity = "halt"

    def check(self, rec: Dict[str, Any]) -> Optional[str]:
        """Return a human reason string to trip, else None."""
        raise NotImplementedError


class NonFiniteLossSentinel(Sentinel):
    """NaN/inf loss — the classic silent run-killer. Applies to train
    AND eval records (a NaN validation loss is the same dead run)."""

    name = "nonfinite_loss"
    severity = "halt"

    def check(self, rec):
        loss = rec.get("loss")
        if loss is not None and not math.isfinite(loss):
            return f"loss={loss} at step {rec['step']}"
        return None


class GradSpikeSentinel(Sentinel):
    """Grad-norm spike vs. a running EMA (and non-finite grad norm).

    The EMA warms up for ``warmup`` train records before spike
    comparisons start — early steps legitimately have wild gradients.
    """

    name = "grad_spike"
    severity = "halt"

    def __init__(self, factor: float = 10.0, warmup: int = 20,
                 decay: float = 0.98):
        self.factor = float(factor)
        self.warmup = int(warmup)
        self.decay = float(decay)
        self._ema: Optional[float] = None
        self._seen = 0

    def check(self, rec):
        if rec.get("kind") != "train":
            return None
        g = rec.get("grad_norm")
        if g is None or math.isnan(g):
            # grad_norm may legitimately be absent (eval, coarse loops);
            # NaN-as-missing must not trip — nonfinite loss catches real
            # NaN blow-ups because the loss goes NaN the same step
            return None
        if math.isinf(g):
            return f"grad_norm={g} at step {rec['step']}"
        self._seen += 1
        ema = self._ema
        self._ema = g if ema is None else self.decay * ema + (1 - self.decay) * g
        if ema is not None and self._seen > self.warmup and g > self.factor * max(ema, 1e-12):
            return (f"grad_norm {g:.4g} > {self.factor:g}x EMA {ema:.4g} "
                    f"at step {rec['step']}")
        return None


class LossPlateauSentinel(Sentinel):
    """Loss hasn't improved by ``min_delta`` for ``window`` train
    records. Severity "warn" by default: a plateau wants eyes (or an LR
    cut), not a halted run."""

    name = "loss_plateau"
    severity = "warn"

    def __init__(self, window: int = 200, min_delta: float = 1e-3):
        self.window = int(window)
        self.min_delta = float(min_delta)
        self._best = math.inf
        self._since_best = 0

    def check(self, rec):
        if rec.get("kind") != "train":
            return None
        loss = rec.get("loss")
        if loss is None or not math.isfinite(loss):
            return None
        if loss < self._best - self.min_delta:
            self._best = loss
            self._since_best = 0
            return None
        self._since_best += 1
        if self._since_best >= self.window:
            self._since_best = 0  # re-arm: one trip per plateau window
            return (f"loss has not improved past {self._best:.4g} for "
                    f"{self.window} steps (step {rec['step']})")
        return None


def default_sentinels() -> List[Sentinel]:
    return [NonFiniteLossSentinel(), GradSpikeSentinel(),
            LossPlateauSentinel()]


class SentinelBank:
    """Reusable sentinel dispatch: run every sentinel over one record
    dict, collect :class:`Trip` objects, count them (deque + monotonic
    total + optional registry counter), and fire guarded callbacks.

    Extracted from :class:`FlightRecorder` so the SAME trip vocabulary
    covers both halves of the system: the recorder checks training-step
    records, and ``serving/rollout.py`` checks per-request serve-health
    records (NaN embeddings, latency bands, error rates) with its own
    sentinel set — a canary rollback and a training halt are the same
    mechanism pointed at different streams. ``check`` never raises; a
    failing sentinel or callback is logged and skipped."""

    def __init__(self, sentinels: Sequence[Sentinel], max_trips: int = 64,
                 registry=None,
                 trip_metric: str = "flight_sentinel_trips_total"):
        self.sentinels: List[Sentinel] = list(sentinels)
        self.trips: deque = deque(maxlen=max_trips)
        self.trips_total = 0  # monotonic (the deque evicts old trips)
        self.registry = registry
        self.trip_metric = trip_metric
        self._callbacks: List[Callable[[Trip, Dict[str, Any]], None]] = []
        # sentinels are stateful (deques, EMAs) and NOT thread-safe; the
        # serve path calls check() from concurrent handler threads, and
        # an unserialized "deque mutated during iteration" would be
        # swallowed by the per-sentinel guard — silently skipping the
        # very check that should have tripped
        self._check_lock = threading.Lock()

    def on_trip(self, fn: Callable[[Trip, Dict[str, Any]], None]) -> None:
        """Register a trip callback ``fn(trip, record_dict)``. Callbacks
        are guarded: an exception is logged and swallowed."""
        self._callbacks.append(fn)

    def trips_snapshot(self) -> List[Trip]:
        """A consistent copy of the trip ring, under the check lock —
        debug surfaces iterate trips while concurrent ``check`` calls
        append, and an unguarded deque iteration raises mid-serialize."""
        with self._check_lock:
            return list(self.trips)

    def reset_sentinels(self) -> None:
        """Reset every sentinel's windowed state (where one defines
        ``reset()``), under the same lock ``check`` holds — an
        unserialized clear() mid-iteration would raise inside a
        concurrent check and be silently swallowed by its guard."""
        with self._check_lock:
            for s in self.sentinels:
                reset = getattr(s, "reset", None)
                if reset is not None:
                    reset()

    def check(self, rec: Dict[str, Any]) -> List[Trip]:
        """Run every sentinel on ``rec``; return (and record) fired trips."""
        trips: List[Trip] = []
        with self._check_lock:
            for s in self.sentinels:
                try:
                    reason = s.check(rec)
                except Exception:
                    log.debug("sentinel %s failed (ignored)", s.name,
                              exc_info=True)
                    continue
                if reason:
                    trip = Trip(s.name, reason, int(rec.get("step", -1)),
                                s.severity,
                                float(rec.get("wall_time") or time.time()))
                    trips.append(trip)
                    self.trips.append(trip)
                    self.trips_total += 1
                    if self.registry is not None:
                        try:
                            self.registry.inc(self.trip_metric,
                                              labels={"sentinel": s.name})
                        except Exception:
                            log.debug("trip metric failed (ignored)",
                                      exc_info=True)
                    log.warning("sentinel %s tripped: %s", s.name, reason)
        # callbacks run OUTSIDE the check lock: a rollback callback takes
        # the rollout manager's lock, and holding both here would couple
        # the lock orders of every caller
        for trip in trips:
            for fn in self._callbacks:
                try:
                    fn(trip, rec)
                except Exception:
                    log.debug("trip callback failed (ignored)",
                              exc_info=True)
        return trips


# ---------------------------------------------------------------------
# Flight recorder (the bounded ring)
# ---------------------------------------------------------------------


class FlightRecorder:
    """Bounded per-step telemetry ring + sentinel dispatch.

    ``record()`` is the hot-path entry: a few structured-array writes,
    then each sentinel's ``check``. It never raises (guarded like the
    tracer) and returns the list of :class:`Trip` objects fired for
    this record so the caller can decide to halt.
    """

    def __init__(self, capacity: int = 4096,
                 sentinels: Optional[Sequence[Sentinel]] = None,
                 registry=None, max_trips: int = 64):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self._buf = np.zeros(self.capacity, RECORD_DTYPE)
        self._total = 0  # records ever appended
        self._lock = threading.Lock()
        self._bank = SentinelBank(
            sentinels if sentinels is not None else default_sentinels(),
            max_trips=max_trips)
        self.registry = None
        if registry is not None:
            self.bind_registry(registry)

    # sentinel state lives in the bank; these keep the recorder's
    # long-standing public surface (tests, telemetry) unchanged
    @property
    def sentinels(self) -> List[Sentinel]:
        return self._bank.sentinels

    @property
    def trips(self) -> deque:
        return self._bank.trips

    @property
    def trips_total(self) -> int:
        return self._bank.trips_total

    # -- wiring --------------------------------------------------------

    def bind_registry(self, registry) -> None:
        """Attach a ``utils.metrics.Registry`` (idempotent)."""
        if registry is None or self.registry is registry:
            return
        try:
            registry.counter("flight_records_total",
                             "flight-recorder records appended")
            registry.gauge("flight_last_step",
                           "last step the flight recorder saw")
            registry.counter("flight_sentinel_trips_total",
                             "divergence-sentinel trips, by sentinel")
            self.registry = registry
            self._bank.registry = registry
        except Exception:
            log.debug("bind_registry failed (ignored)", exc_info=True)

    def on_trip(self, fn: Callable[[Trip, Dict[str, Any]], None]) -> None:
        """Register a sentinel-trip callback ``fn(trip, record_dict)``.
        Callbacks are guarded: an exception is logged and swallowed."""
        self._bank.on_trip(fn)

    # -- hot path ------------------------------------------------------

    def record(self, step: int, kind: str = "train",
               loss: float = math.nan, grad_norm: float = math.nan,
               param_norm: float = math.nan, lr: float = math.nan,
               tokens_per_sec: float = math.nan,
               step_time_s: float = math.nan,
               compile: bool = False) -> List[Trip]:
        """Append one record; run sentinels; return fired trips."""
        try:
            rec = {
                "step": int(step), "kind": str(kind)[:5],
                "wall_time": time.time(),
                "loss": float(loss), "grad_norm": float(grad_norm),
                "param_norm": float(param_norm), "lr": float(lr),
                "tokens_per_sec": float(tokens_per_sec),
                "step_time_s": float(step_time_s),
                "compile": bool(compile),
            }
        except (TypeError, ValueError):
            log.debug("flight record coercion failed (ignored)", exc_info=True)
            return []
        try:
            with self._lock:
                row = self._buf[self._total % self.capacity]
                for name, _ in RECORD_FIELDS:
                    row[name] = rec[name]
                self._total += 1
            reg = self.registry
            if reg is not None:
                reg.inc("flight_records_total")
                reg.set("flight_last_step", rec["step"])
            return self._bank.check(rec)
        except Exception:
            log.debug("flight record failed (ignored)", exc_info=True)
            return []

    # -- read side -----------------------------------------------------

    @property
    def records_total(self) -> int:
        with self._lock:
            return self._total

    def snapshot(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        """Oldest-to-newest ring contents as JSON-ready dicts (at most
        the last ``n`` when given)."""
        with self._lock:
            count = min(self._total, self.capacity)
            start = self._total - count
            rows = [self._buf[(start + i) % self.capacity].copy()
                    for i in range(count)]
        out = []
        for row in rows:
            d: Dict[str, Any] = {}
            for name, dt in RECORD_FIELDS:
                v = row[name]
                if dt == "?":
                    d[name] = bool(v)
                elif dt == "i8":
                    d[name] = int(v)
                elif dt.startswith("U"):
                    d[name] = str(v)
                else:
                    f = float(v)
                    d[name] = f if math.isfinite(f) else (
                        None if math.isnan(f) else str(f))
                # NaN/inf -> None/"inf": json.dumps emits bare NaN
                # otherwise, which most parsers reject
            out.append(d)
        return out[-n:] if n else out

    def dump(self, path) -> Path:
        """Write the ring as JSONL: one meta line, then one record per
        line, oldest first — the crash/halt post-mortem artifact."""
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        with path.open("w") as f:
            f.write(json.dumps({
                "kind": "meta",
                "schema": [name for name, _ in RECORD_FIELDS],
                "capacity": self.capacity,
                "records_total": self.records_total,
                "dumped_at": time.time(),
                "trips": [dataclasses.asdict(t) for t in self.trips],
            }) + "\n")
            for rec in self.snapshot():
                f.write(json.dumps(rec) + "\n")
        return path

    def summary(self) -> Dict[str, Any]:
        last = self.snapshot(1)
        return {
            "records_total": self.records_total,
            "capacity": self.capacity,
            "sentinels": [s.name for s in self.sentinels],
            "trips": [dataclasses.asdict(t) for t in self.trips],
            "last_record": last[0] if last else None,
        }


# ---------------------------------------------------------------------
# XLA compile/memory accounting
# ---------------------------------------------------------------------


def _leaf_sig(leaf) -> Tuple:
    """Cheap per-call key component: shape, dtype, and the sharding
    OBJECT itself (hashable). Raw shardings over-discriminate —
    PartitionSpec('data', None) on a 1-wide axis and PartitionSpec()
    are the same layout — but that is resolved once at insert time via
    :func:`_canon_leaf_sig`; the steady-state call path must not pay
    device-assignment expansion per leaf per call."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return (type(leaf).__name__, repr(leaf)[:32])
    return (shape, dtype, getattr(leaf, "sharding", None))


def _canon_leaf_sig(leaf) -> Tuple:
    """Layout-equivalence key: (ordered device ids, per-device shard
    shape, memory kind). Spec SYNTAX must not discriminate — keying on
    sharding identity alone would re-lower an already-compiled program
    every time GSPMD canonicalizes an output spec differently than the
    input was placed. Computed only on cheap-key cache misses."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return (type(leaf).__name__, repr(leaf)[:32])
    sharding = getattr(leaf, "sharding", None)
    if sharding is None:
        key = None
    else:
        try:
            key = (tuple(d.id for d in sharding._device_assignment),
                   tuple(sharding.shard_shape(tuple(shape))),
                   getattr(sharding, "memory_kind", None))
        except Exception:
            key = repr(sharding)
    return (tuple(shape), str(dtype), key)


def _args_sig(args, leaf_fn=_leaf_sig) -> Tuple:
    import jax

    leaves, treedef = jax.tree.flatten(args)
    return (treedef, tuple(leaf_fn(leaf) for leaf in leaves))


def _shape_label(args, sig=None) -> str:
    """Gauge label for one compiled signature: the largest array shapes
    (human-readable) plus a short digest of the FULL signature — the
    largest leaves are usually params, identical across different batch
    shapes, and a label collision would silently overwrite one shape's
    gauges with another's."""
    import hashlib

    import jax

    shapes = sorted(
        {tuple(getattr(l, "shape", ())) for l in jax.tree.leaves(args)
         if getattr(l, "ndim", 0) > 0},
        key=lambda s: (-int(np.prod(s)), s))
    label = ",".join("x".join(map(str, s)) for s in shapes[:2]) or "scalar"
    if sig is not None:
        label += "@" + hashlib.md5(repr(sig).encode()).hexdigest()[:6]
    return label


def _flops_of(compiled) -> float:
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return float(cost.get("flops", 0.0)) if isinstance(cost, dict) else 0.0
    except Exception:
        return 0.0


def _hbm_of(compiled) -> int:
    try:
        mem = compiled.memory_analysis()
        return int(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0))
    except Exception:
        return 0


#: the three stages of a compile, as ``jax.monitoring`` times them
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_STAGES = tuple(_STAGE_EVENTS.values())
#: what the persistent cache says of a program before the end of its
#: backend stage. A miss is announced when its entry is WRITTEN: with no
#: cache directory, or for a program under the cache's thresholds of
#: compile time and size, neither fires and the compile reads ``off``
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

# the accountants the process's ONE set of jax.monitoring listeners
# feeds: registered once, forwarding through weak references, so that
# a listener never keeps an accountant alive (a test's private one dies
# with the test) and a compile pays three calls however many listen
_listening: Tuple["weakref.ref[XLAAccountant]", ...] = ()
_listeners_registered = False
_listen_lock = threading.Lock()


def _forward(method: str) -> Callable[..., None]:
    def on_event(event, *args, **kw) -> None:
        for ref in _listening:
            try:
                acct = ref()
                if acct is not None:
                    getattr(acct, method)(event, *args, **kw)
            except Exception:  # an observer: never reaches the compile
                log.debug("compile listener failed (ignored)",
                          exc_info=True)
    return on_event


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals: a function
    traced inside another's tracing counts once."""
    total, reach = 0.0, -math.inf
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


class _Ring:
    """The last ``capacity`` records, each numbered as it came (``seq``,
    from 1), and how many there have ever been: a reader that took
    ``seen`` as its mark finds what came after it, whatever has fallen
    off the ring or been taken out of it since."""

    def __init__(self, capacity: int):
        self.items: deque = deque(maxlen=int(capacity))
        self.seen = 0

    def append(self, item: Dict[str, Any]) -> None:
        self.seen += 1
        item["seq"] = self.seen
        self.items.append(item)

    def since(self, mark: int) -> list:
        out = []
        for item in reversed(self.items):
            if item["seq"] <= mark:
                break
            out.append(item)
        return out[::-1]


class XLAAccountant:
    """Per-process compile ledger. One global instance (``get_accountant``)
    is shared by the engine, trainer, fine-tuner, slot scheduler and the
    runtime audits, so the ``/debug/flight`` endpoint shows every
    compiled program in the process, whichever component owns the HTTP
    listener.

    Two bounded rings, both fed by ``jax.monitoring`` once
    :meth:`listen` has been called (RUNBOOK §18):

    * **stage records**, one a stage of a program (a function traced
      INSIDE another's tracing or lowering is part of that record):
      ``seq``, ``stage`` (``trace`` | ``lower`` | ``compile``), ``fn``
      (the event's ``fun_name`` with ``jit(...)`` stripped, so a
      program's three stages share a key),
      ``start_unix`` / ``end_unix`` (the event's own, on
      ``time.time()``), ``thread``, and on ``compile`` records ``cache``
      (``hit`` | ``miss`` | ``off``: what the persistent cache said on
      this thread since the previous backend stage) and ``retrieval_s``.
    * **compiles**, one a backend stage (:meth:`report`): ``seq``, ``fn``,
      ``shape``, ``at``, ``compile_seconds``, ``stage_s`` (the seconds
      of the program's own three stages), ``cache``, ``retrieval_s``,
      and ``flops`` / ``hbm_bytes`` where an :func:`instrument`-ed
      function's ahead-of-time compile claimed the entry
      (:meth:`note_compile`: then ``fn`` is the instrumented name and
      ``program`` the one jax knows it by).
    """

    def __init__(self, registry=None, capacity: int = 4096):
        self._lock = threading.Lock()
        self.registry = None
        self._compiles = _Ring(capacity)
        self._stages = _Ring(capacity)
        self._by_fn: Dict[str, int] = {}  # compiles ever, by fn
        # per thread: the cache's verdict awaiting its backend stage,
        # and the entries an instrumented compile holds back from export
        self._tls = threading.local()
        self.enabled = os.environ.get("CI_TPU_NO_XLA_ACCOUNTING", "") != "1"
        if registry is not None:
            self.bind_registry(registry)

    # -- the listener ----------------------------------------------------

    def listen(self) -> bool:
        """Feed this accountant from ``jax.monitoring`` (idempotent; the
        process's listeners are registered on the first call). False,
        and nothing registered, where accounting is disabled or jax is
        not installed: the module stays importable without it."""
        global _listening, _listeners_registered
        if not self.enabled:
            return False
        if any(ref() is self for ref in _listening):
            return True
        try:
            from jax import monitoring

            with _listen_lock:
                if not _listeners_registered:
                    monitoring.register_event_time_span_listener(
                        _forward("_on_stage"))
                    monitoring.register_event_listener(
                        _forward("_on_cache_event"))
                    monitoring.register_event_duration_secs_listener(
                        _forward("_on_duration"))
                    _listeners_registered = True
                _listening = tuple(
                    ref for ref in _listening if ref() is not None
                ) + (weakref.ref(self),)
            return True
        except Exception:
            log.debug("compile listener not registered (ignored)",
                      exc_info=True)
            return False

    def _on_cache_event(self, event: str, **kw) -> None:
        verdict = _CACHE_EVENTS.get(event)
        if verdict is not None:
            self._tls.cache = verdict

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == _RETRIEVAL_EVENT:
            self._tls.retrieval_s = float(duration)

    def _on_stage(self, event: str, start: float, end: float,
                  fun_name: str = "", **kw) -> None:
        stage = _STAGE_EVENTS.get(event)
        if stage is None:
            return
        fn = str(fun_name)
        if fn.startswith("jit(") and fn.endswith(")"):
            fn = fn[4:-1]
        thread = threading.get_ident()
        rec = {"stage": stage, "fn": fn, "start_unix": float(start),
               "end_unix": float(end), "thread": thread}
        if stage == "compile":
            tls = self._tls
            rec["cache"] = getattr(tls, "cache", None) or "off"
            rec["retrieval_s"] = getattr(tls, "retrieval_s", None) or 0.0
            tls.cache = tls.retrieval_s = None
        c = None
        with self._lock:
            items = self._stages.items
            # a record stands for the traces made inside its interval:
            # every jnp function a model calls is a jitted function
            # traced inside its tracing, and lowering a jax.random call
            # traces threefry's adds and xors by the thousand. A union
            # counts them once anyway, and the ring has no room for them
            while items and items[-1]["stage"] == "trace" \
                    and items[-1]["thread"] == thread \
                    and items[-1]["start_unix"] >= rec["start_unix"]:
                items.pop()
            if stage == "compile":
                # the program's own trace and lowering: this thread's
                # newest of each since it last reached its backend stage
                own = {"compile": rec["end_unix"] - rec["start_unix"]}
                for r in reversed(items):
                    if r["thread"] != thread or r["fn"] != fn:
                        continue
                    if r["stage"] == "compile" or len(own) == 3:
                        break
                    own.setdefault(r["stage"],
                                   r["end_unix"] - r["start_unix"])
                stage_s = {s: round(own.get(s, 0.0), 6) for s in _STAGES}
                c = {"fn": fn, "shape": "", "at": rec["end_unix"],
                     "compile_seconds": round(sum(stage_s.values()), 6),
                     "flops": 0.0, "hbm_bytes": 0, "stage_s": stage_s,
                     "cache": rec["cache"],
                     "retrieval_s": rec["retrieval_s"]}
                self._compiles.append(c)
                self._by_fn[fn] = self._by_fn.get(fn, 0) + 1
            self._stages.append(rec)
        if c is None:
            return
        held = getattr(self._tls, "held", None)
        if held is not None:
            held.append(c)
        else:
            self._export(c)

    # -- the stage records' read side -----------------------------------

    def stages_mark(self) -> int:
        """How many stage records there have ever been: hand it to
        :meth:`stage_records` or :meth:`compile_attrs` later."""
        with self._lock:
            return self._stages.seen

    def stage_records(self, since: int = 0) -> List[Dict[str, Any]]:
        """The retained stage records appended after mark ``since``,
        oldest first."""
        with self._lock:
            return self._stages.since(since)

    def compile_attrs(self, since: int) -> Dict[str, float]:
        """``{"compile_s": seconds}`` where THIS thread traced, lowered
        or compiled anything after mark ``since`` (the union of the
        records' intervals), else ``{}``: what a span around a jitted
        call says of the compile it paid."""
        thread = threading.get_ident()
        spans = [(r["start_unix"], r["end_unix"])
                 for r in self.stage_records(since) if r["thread"] == thread]
        return {"compile_s": round(union_seconds(spans), 6)} if spans else {}

    # -- the registry ---------------------------------------------------

    def bind_registry(self, registry) -> None:
        """Attach a ``utils.metrics.Registry`` (idempotent) and start
        listening; re-plays already-recorded compiles into it so late
        binding (a metrics server started after warmup) still sees the
        full ledger."""
        if registry is None or self.registry is registry:
            return
        self.listen()
        try:
            registry.gauge("compile_seconds",
                           "XLA compile wall time per compiled shape")
            registry.gauge("compiled_flops",
                           "cost_analysis flops per compiled shape")
            registry.gauge("compiled_hbm_bytes",
                           "memory_analysis HBM footprint (args+outputs+"
                           "temps-aliased) per compiled shape")
            registry.counter("compiles_total", "XLA compiles by function")
            registry.counter("compile_cache_misses_total",
                             "compiles the persistent cache did not "
                             "hold, by function")
            self.registry = registry
            for c in self.report():
                self._export(c)
        except Exception:
            log.debug("accountant bind_registry failed (ignored)",
                      exc_info=True)

    def _export(self, c: Dict[str, Any]) -> None:
        reg = self.registry
        if reg is None:
            return
        try:
            labels = {"fn": c["fn"], "shape": c["shape"]}
            reg.set("compile_seconds", c["compile_seconds"], labels=labels)
            reg.set("compiled_flops", c["flops"], labels=labels)
            reg.set("compiled_hbm_bytes", c["hbm_bytes"], labels=labels)
            reg.inc("compiles_total", labels={"fn": c["fn"]})
            if c.get("cache") == "miss":
                reg.inc("compile_cache_misses_total",
                        labels={"fn": c["fn"]})
        except Exception:
            log.debug("accountant export failed (ignored)", exc_info=True)

    # -- the compiles ---------------------------------------------------

    @contextlib.contextmanager
    def _claiming(self):
        """Around an instrumented function's ahead-of-time compile: the
        entries the listener makes on this thread are held back from the
        registry and yielded, so that :meth:`note_compile` can claim the
        program's own (the last: ``compile()`` is the scope's last step)
        before it is exported under the instrumented name."""
        held = self._tls.held = []
        try:
            yield held
        finally:
            self._tls.held = None
            for c in held:
                self._export(c)

    def note_compile(self, fn_name: str, shape: str, seconds: float,
                     flops: float, hbm_bytes: int,
                     entry: Optional[Dict[str, Any]] = None) -> None:
        """One instrumented compile. ``entry``, where the listener made
        one for the same compile, gains the instrumented name, the shape
        label, flops and HBM (it is exported by whoever held it back);
        without one (no listener) the compile is appended and exported
        here, as it always was."""
        mine = {"fn": fn_name, "shape": shape,
                "compile_seconds": round(float(seconds), 6),
                "flops": float(flops), "hbm_bytes": int(hbm_bytes)}
        with self._lock:
            if entry is not None:
                self._by_fn[entry["fn"]] -= 1
                entry.update(mine, program=entry["fn"])
            else:
                self._compiles.append(dict(mine, at=time.time()))
            self._by_fn[fn_name] = self._by_fn.get(fn_name, 0) + 1
        if entry is None:
            self._export(mine)
        log.info("XLA compile %s[%s]: %.3fs, %.3g flops, %d HBM bytes",
                 fn_name, shape, seconds, flops, hbm_bytes)

    def compiles_mark(self) -> int:
        """How many compiles there have ever been: hand it to
        :meth:`report` later for what came after."""
        with self._lock:
            return self._compiles.seen

    def report(self, since: int = 0) -> List[Dict[str, Any]]:
        """The retained compiles (after mark ``since``), oldest first."""
        with self._lock:
            return [dict(c) for c in self._compiles.since(since)]

    def count(self, fn: str) -> int:
        """Compiles ever recorded under ``fn``."""
        with self._lock:
            return self._by_fn.get(fn, 0)

    def wrap(self, jitted, name: str) -> "InstrumentedJit":
        return InstrumentedJit(jitted, name, self)


class InstrumentedJit:
    """AOT-compiling wrapper around a ``jax.jit`` callable.

    Each new input signature (pytree structure + leaf shape/dtype/
    sharding) is lowered and compiled explicitly, so compile wall time
    is measured exactly (not smeared into the first call) and the
    compiled executable's cost/memory analyses are captured. Steady
    state calls the cached executable directly — donation and sharding
    semantics are jax's own AOT path.

    Any failure anywhere in the accounting path (signature hashing,
    lowering, analyses) permanently downgrades this wrapper to a plain
    passthrough of the underlying jitted callable: accounting is an
    observer, never a dependency.
    """

    def __init__(self, jitted, name: str, accountant: XLAAccountant):
        self._jitted = jitted
        self._name = name
        self._acct = accountant
        # two-level cache: the cheap per-call key (shapes/dtypes/raw
        # sharding objects) aliases into the canonical layout key, so
        # spec-syntax variants of one layout share one executable and
        # the hot path never pays device-assignment expansion
        self._cache: Dict[Any, Any] = {}
        self._canon: Dict[Any, Any] = {}
        self._lock = threading.Lock()
        self._fallback = not accountant.enabled

    def __call__(self, *args):
        if self._fallback:
            return self._jitted(*args)
        try:
            sig = _args_sig(args)
            compiled = self._cache.get(sig)  # graft: noqa[unguarded-shared-field] — double-checked fast path: GIL-atomic dict read, misses re-check under the lock; locking here would serialize every dispatch
        except Exception:  # unhashable leaf etc. — run unaccounted
            log.debug("accounting sig failed; falling back for %s",
                      self._name, exc_info=True)
            self._fallback = True  # graft: noqa[rmw-outside-lock] — monotonic one-way latch: every racing writer writes True, no update can be lost
            return self._jitted(*args)
        if compiled is None:
            with self._lock:
                compiled = self._cache.get(sig)
                if compiled is None:
                    try:
                        canon = _args_sig(args, _canon_leaf_sig)
                        compiled = self._canon.get(canon)
                        if compiled is None:
                            compiled = self._compile(args, canon)
                            self._canon[canon] = compiled
                        self._cache[sig] = compiled
                    except Exception:
                        log.warning(
                            "XLA accounting failed for %s; running "
                            "unaccounted from here on", self._name,
                            exc_info=True)
                        self._fallback = True
                        return self._jitted(*args)
        return compiled(*args)

    def _compile(self, args, canon):
        """Lower and compile ahead of time: ONE ledger entry, the
        listener's where there is one, under this wrapper's name; and
        the span that paid says so (``compile_s`` on ``train.dispatch``,
        ``slots.device_steps``, ...: the compile path only, nothing on a
        warmed call)."""
        acct = self._acct
        mark = acct.stages_mark()
        t0 = time.perf_counter()
        with acct._claiming() as held:
            compiled = self._jitted.lower(*args).compile()
            acct.note_compile(
                self._name, _shape_label(args, canon),
                time.perf_counter() - t0, _flops_of(compiled),
                _hbm_of(compiled), entry=held[-1] if held else None)
        tracing.set_attrs(**acct.compile_attrs(mark))
        return compiled

    def _cache_size(self) -> int:
        """Compiled-PROGRAM count (canonical layouts), mirroring jit's
        private ``_cache_size`` so callers
        (SlotScheduler.compiled_step_shapes) work unchanged on either
        object."""
        # deliberately lock-free: __call__ holds _lock across an entire
        # lower().compile() (seconds), and this is a gauge read —
        # stale-by-one beats stalling /debug readers behind a compile
        if self._fallback:  # graft: noqa[unguarded-shared-field] — monotonic latch, GIL-atomic bool read
            cs = getattr(self._jitted, "_cache_size", None)
            return int(cs()) if cs is not None else -1
        return len(self._canon)  # graft: noqa[unguarded-shared-field] — GIL-atomic len() of a dict only grown under the lock; gauge tolerates staleness


_acct: Optional[XLAAccountant] = None
_acct_lock = threading.Lock()


def get_accountant() -> XLAAccountant:
    """Process-global compile accountant (lazy, like tracing.get_tracer)."""
    global _acct
    if _acct is None:
        with _acct_lock:
            if _acct is None:
                _acct = XLAAccountant()
    return _acct


def instrument(jitted, name: str) -> InstrumentedJit:
    """Wrap a jitted callable with the global accountant."""
    return get_accountant().wrap(jitted, name)


# ---------------------------------------------------------------------
# /debug/flight (shared by MetricsServer and the embedding server)
# ---------------------------------------------------------------------


def debug_flight_response(recorder: Optional[FlightRecorder],
                          accountant: Optional[XLAAccountant] = None,
                          query: str = ""):
    """Build the ``/debug/flight`` body: ``(status, bytes, content_type)``.

    Query knobs: ``n=<int>`` (recent-record count, default 100).
    The response carries the recent flight records + sentinel trips
    (when a recorder is attached) and the process's XLA compile ledger.
    """
    try:
        from urllib.parse import parse_qs

        q = parse_qs(query or "")
        n = int(q.get("n", ["100"])[0])
        acct = accountant if accountant is not None else get_accountant()
        body: Dict[str, Any] = {"compiles": acct.report()}
        if recorder is not None:
            body.update(recorder.summary())
            body["records"] = recorder.snapshot(n)
        else:
            body["records"] = []
        return 200, json.dumps(body).encode(), "application/json"
    except Exception as e:  # the debug surface must not 500 the listener
        return 500, json.dumps({"error": str(e)[:200]}).encode(), \
            "application/json"
