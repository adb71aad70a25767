"""Serve-path SLO observatory: streaming quantiles, per-stage latency
attribution, and multi-window burn-rate sentinels.

The serve path has traces (utils/tracing.py), flight recording
(utils/flight_recorder.py), canary sentinels (serving/rollout.py) and a
cache (serving/embed_cache.py) — but nothing continuously answers "is
serving meeting its latency objective *right now*, and where does the
time go?". TPU serving work lives and dies by tail-latency
characterization (the Gemma-on-TPU serving comparison in PAPERS.md is
organized entirely around p50/p99 SLO tables; LightSeq's wins are only
demonstrable because its harness measures per-stage time). This module
is that layer:

* :class:`ServeSLO` ingests finished request traces (via
  ``Tracer.on_trace``) or explicit :meth:`observe` calls and maintains
  **streaming quantile digests** (utils/digest.py — DDSketch-style,
  fixed memory, mergeable, serializable) for end-to-end latency and for
  every pipeline stage the spans name: batcher queue wait, cache
  lookup, slot queue wait, device steps, pool emit, tokenize. Stage
  attribution is *accounted against the root span*: whatever the stage
  spans don't cover lands in the explicit ``unattributed`` stage, so
  per-stage time provably sums to the request time instead of silently
  under-reporting.
* **Multi-window burn-rate evaluation** — the SRE alerting shape: a
  request is *bad* when it errors or exceeds the latency objective; the
  burn rate is (bad fraction / error budget) over a fast (default 5m)
  and a slow (default 1h) window, maintained as a ring of per-minute
  count buckets + digests (mergeable sketches make the window math a
  sum). A sustained burn in BOTH windows trips a
  :class:`BurnRateSentinel` on the flight-recorder
  :class:`~code_intelligence_tpu.utils.flight_recorder.SentinelBank`
  Trip vocabulary — the same mechanism that halts a diverging training
  run and rolls back a poisoned canary, pointed at the SLO stream — so
  rollout/canary machinery consumes burn alerts with zero new plumbing.
* **Export surfaces** — ``slo_*`` / ``stage_*`` metrics on ``/metrics``
  (summary quantiles with a relative-error guarantee, burn-rate
  gauges, outcome counters) and a ``/debug/slo`` JSON endpoint whose
  body embeds the *serialized digests* — a perfwatch snapshot carries
  the sketches themselves, so live-vs-baseline comparison runs on
  identical estimators instead of mismatched bucket math.

Device-free and jax-free by construction: the observatory (and the
perfwatch gate built on it, utils/perfwatch.py) must run anywhere the
HTTP layer runs.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Tuple

from code_intelligence_tpu.utils.digest import QuantileDigest
from code_intelligence_tpu.utils.flight_recorder import (
    Sentinel, SentinelBank, Trip)

log = logging.getLogger(__name__)

#: span names that count as attributable pipeline stages (everything
#: else a request spends lands in ``unattributed``). ``engine.group`` and
#: ``engine.finalize`` are NOT stages: they lie inside
#: ``engine.group_embed`` and would count its time twice. On the groups
#: path a document's ``engine.group_embed`` opens when the preparation
#: slab it was prepared in ends, so its own ``engine.text_rules`` /
#: ``engine.tokenize`` always lie before it and one request's stages
#: still sum to its time; on a call of several groups those two spans of
#: LATER documents lie inside EARLIER documents' ``engine.group_embed``
#: interval (the chip runs the short groups while the long documents are
#: tokenised): across the documents of such a call the stages overlap by
#: design, and a trace that holds several of them (one batched request)
#: can cover more than its root — ``unattributed`` is floored at 0.
DEFAULT_STAGE_SPANS: Tuple[str, ...] = (
    "engine.text_rules",
    "engine.tokenize",
    "batcher.queue_wait",
    "cache.lookup",
    "slots.queue_wait",
    "slots.device_steps",
    "slots.pool_emit",
    "engine.group_embed",
)

#: the catch-all stage: root duration not covered by any stage span
UNATTRIBUTED = "unattributed"


# ---------------------------------------------------------------------
# Objective + burn-rate sentinel
# ---------------------------------------------------------------------


@dataclasses.dataclass
class SLOObjective:
    """The serving objective: "``latency_target`` of requests complete
    under ``p99_ms`` and the error rate stays under
    ``max_error_rate``". A request that errors OR exceeds the latency
    bound burns the error budget; the budget per window is
    ``max(1 - latency_target, max_error_rate)`` worth of requests."""

    p99_ms: float = 250.0
    latency_target: float = 0.99
    max_error_rate: float = 0.01

    def __post_init__(self):
        if self.p99_ms <= 0:
            raise ValueError(f"p99_ms must be > 0, got {self.p99_ms}")
        if not (0.0 < self.latency_target < 1.0):
            raise ValueError(
                f"latency_target must be in (0, 1), got {self.latency_target}")
        if not (0.0 < self.max_error_rate < 1.0):
            raise ValueError(
                f"max_error_rate must be in (0, 1), got {self.max_error_rate}")

    @property
    def threshold_s(self) -> float:
        return self.p99_ms / 1e3

    @property
    def latency_budget(self) -> float:
        return 1.0 - self.latency_target

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class BurnRateSentinel(Sentinel):
    """Trips when the error budget burns at ``threshold``x in BOTH the
    fast and the slow window (the classic multi-window page: the fast
    window proves it's happening now, the slow window proves it's not a
    blip). Latched: one trip per sustained burn — it re-arms only after
    the fast window drops back under the threshold, so a long incident
    is one alert, not one per request."""

    name = "slo_burn_rate"
    severity = "halt"

    def __init__(self, threshold: float = 14.4, min_requests: int = 20):
        if threshold <= 0:
            raise ValueError(f"threshold must be > 0, got {threshold}")
        self.threshold = float(threshold)
        self.min_requests = int(min_requests)
        self._latched = False

    def reset(self) -> None:
        self._latched = False

    def check(self, rec):
        if rec.get("kind") != "slo":
            return None
        fast, slow = rec.get("fast_burn", 0.0), rec.get("slow_burn", 0.0)
        if rec.get("fast_requests", 0) < self.min_requests:
            # below the signal floor there is no burn claim either way:
            # unlatch, so a NEW burn after an idle gap alerts again
            # (a latch held here would silently swallow that alert)
            self._latched = False
            return None
        burning = fast >= self.threshold and slow >= self.threshold
        if not burning:
            self._latched = False
            return None
        if self._latched:
            return None
        self._latched = True
        return (f"SLO burn rate {fast:.1f}x (5m-class window) and "
                f"{slow:.1f}x (1h-class window) >= {self.threshold:g}x "
                f"budget: {rec.get('fast_bad', 0)}/{rec.get('fast_requests', 0)} "
                f"bad requests in the fast window "
                f"(objective p99 < {rec.get('objective_p99_ms')}ms, "
                f"error rate < {rec.get('objective_error_rate')})")


def default_slo_sentinels(burn_threshold: float = 14.4,
                          min_requests: int = 20) -> List[Sentinel]:
    return [BurnRateSentinel(burn_threshold, min_requests)]


# ---------------------------------------------------------------------
# Windowed counting ring
# ---------------------------------------------------------------------


class _Bucket:
    __slots__ = ("t0", "digest", "total", "bad", "errors", "slow")

    def __init__(self, t0: float, rel_err: float):
        self.t0 = t0
        self.digest = QuantileDigest(rel_err=rel_err)
        self.total = 0
        self.bad = 0     # errored OR over the latency objective
        self.errors = 0
        self.slow = 0    # over the latency objective only


# ---------------------------------------------------------------------
# The observatory
# ---------------------------------------------------------------------


class ServeSLO:
    """Per-request SLO accounting for one serving process.

    Feed it finished traces (``tracer.on_trace(slo.ingest_trace)``) or
    explicit :meth:`observe` calls; read it on ``/metrics``
    (``slo_*`` / ``stage_*``), ``/debug/slo``, and via
    :meth:`debug_state` (the perfwatch snapshot body). ``now`` is
    injectable so tests drive the windows without wall-clock sleeps.
    """

    def __init__(self, objective: Optional[SLOObjective] = None,
                 registry=None,
                 fast_window_s: float = 300.0,
                 slow_window_s: float = 3600.0,
                 bucket_s: float = 60.0,
                 rel_err: float = 0.01,
                 burn_threshold: float = 14.4,
                 min_requests: int = 20,
                 sentinels: Optional[Sequence[Sentinel]] = None,
                 stage_spans: Sequence[str] = DEFAULT_STAGE_SPANS,
                 root_span: str = "http.request",
                 gauge_every: int = 32,
                 now: Callable[[], float] = time.monotonic):
        if fast_window_s <= 0 or slow_window_s < fast_window_s:
            raise ValueError(
                f"windows must satisfy 0 < fast <= slow, got "
                f"{fast_window_s}/{slow_window_s}")
        if bucket_s <= 0 or bucket_s > fast_window_s:
            raise ValueError(
                f"bucket_s must be in (0, fast_window_s], got {bucket_s}")
        self.objective = objective or SLOObjective()
        self.fast_window_s = float(fast_window_s)
        self.slow_window_s = float(slow_window_s)
        self.bucket_s = float(bucket_s)
        self.rel_err = float(rel_err)
        self.stage_spans = tuple(stage_spans)
        self.root_span = root_span
        self.gauge_every = max(int(gauge_every), 1)
        self._now = now
        self._lock = threading.Lock()
        n_buckets = int(math.ceil(slow_window_s / bucket_s)) + 1
        self._buckets: Deque[_Bucket] = deque(maxlen=n_buckets)
        # cumulative (process-lifetime) digests: the perfwatch baseline
        self.e2e = QuantileDigest(rel_err=rel_err)
        self.stages: Dict[str, QuantileDigest] = {}
        self.requests_total = 0
        self.errors_total = 0
        self.breaches_total = 0   # over the latency objective
        self._seq = 0
        self._last_gauge_at = -math.inf  # monotonic; throttles burn-path
        self.started_at = time.time()
        # burn alerts ride the flight-recorder Trip vocabulary: the
        # rollout/canary machinery consumes them like any other sentinel
        self.bank = SentinelBank(
            list(sentinels) if sentinels is not None
            else default_slo_sentinels(burn_threshold, min_requests),
            trip_metric="slo_sentinel_trips_total")
        self.registry = None
        if registry is not None:
            self.bind_registry(registry)

    # -- wiring --------------------------------------------------------

    def bind_registry(self, registry) -> None:
        """Attach a ``utils.metrics.Registry`` (idempotent): quantile
        summaries, burn gauges and outcome counters land on
        ``/metrics``."""
        if registry is None or self.registry is registry:
            return
        try:
            registry.digest("slo_request_seconds",
                            "end-to-end request latency (streaming "
                            "quantile digest; relative-error bound)",
                            rel_err=self.rel_err)
            registry.digest("stage_seconds",
                            "per-stage serve latency by pipeline stage "
                            "(streaming quantile digest)",
                            rel_err=self.rel_err)
            registry.counter("slo_requests_total",
                             "requests by SLO outcome (ok/breach/error)")
            registry.gauge("slo_burn_rate",
                           "error-budget burn rate by window (fast/slow)")
            registry.gauge("slo_window_error_ratio",
                           "bad-request fraction by window")
            registry.gauge("slo_window_p99_ms",
                           "windowed p99 latency (merged digest), by window")
            registry.gauge("slo_objective_p99_ms",
                           "the configured latency objective")
            registry.gauge("slo_objective_error_rate",
                           "the configured error-rate objective")
            registry.counter("slo_sentinel_trips_total",
                             "SLO burn-rate sentinel trips, by sentinel")
            registry.set("slo_objective_p99_ms", self.objective.p99_ms)
            registry.set("slo_objective_error_rate",
                         self.objective.max_error_rate)
            self.registry = registry
            self.bank.registry = registry
        except Exception:
            log.debug("slo bind_registry failed (ignored)", exc_info=True)

    def on_burn(self, fn: Callable[[Trip, Dict[str, Any]], None]) -> None:
        """Register a burn-alert callback ``fn(trip, slo_record)`` —
        the hook rollout/promotion machinery listens on."""
        self.bank.on_trip(fn)

    # -- ingest --------------------------------------------------------

    def ingest_trace(self, trace: Dict[str, Any]) -> None:
        """``Tracer.on_trace`` observer: one finished request trace →
        one SLO observation with per-stage attribution. Guarded — a
        malformed trace is dropped, never raised into the tracer."""
        try:
            if trace.get("root") != self.root_span:
                return
            spans = trace.get("spans", ())
            local_ids = {s.get("span_id") for s in spans}
            root = next(
                (s for s in spans
                 if s.get("parent_id") is None
                 or s.get("parent_id") not in local_ids), None)
            duration = float(trace.get("duration_s", 0.0))
            error = False
            if root is not None:
                code = root.get("attrs", {}).get("code")
                try:
                    # 5xx is an error; so is 429 — on this server every
                    # 429 is a server-side refusal (admission shed /
                    # deadline expired, §17), and scoring shed traffic
                    # as fast healthy requests would DILUTE the burn
                    # rate precisely during an overload incident.
                    # Client-fault 4xx (400 bad payload) stays non-error.
                    error = code is not None and (int(code) >= 500
                                                  or int(code) == 429)
                except (TypeError, ValueError):
                    pass
            stages: Dict[str, float] = {}
            for s in spans:
                name = s.get("name")
                if name in self.stage_spans:
                    stages[name] = stages.get(name, 0.0) \
                        + float(s.get("duration_s", 0.0))
            self.observe(duration, error=error, stages=stages)
        except Exception:
            log.debug("slo trace ingest failed (ignored)", exc_info=True)

    def observe(self, latency_s: float, error: bool = False,
                stages: Optional[Dict[str, float]] = None) -> List[Trip]:
        """Record one request outcome; returns any fired burn trips.
        ``stages`` maps stage name → seconds; the remainder up to
        ``latency_s`` is accounted as ``unattributed`` so the stage
        table always sums to the end-to-end time."""
        latency_s = float(latency_s)
        breach = latency_s > self.objective.threshold_s
        bad = bool(error) or breach
        stages = dict(stages or {})
        covered = sum(stages.values())
        stages[UNATTRIBUTED] = max(latency_s - covered, 0.0)
        now = self._now()
        with self._lock:
            self._seq += 1
            seq = self._seq
            self.requests_total += 1
            if error:
                self.errors_total += 1
            if breach:
                self.breaches_total += 1
            self.e2e.add(latency_s)
            b = self._bucket_locked(now)
            b.total += 1
            b.digest.add(latency_s)
            if bad:
                b.bad += 1
            if error:
                b.errors += 1
            if breach:
                b.slow += 1
            for name, dur in stages.items():
                d = self.stages.get(name)
                if d is None:
                    d = self.stages[name] = QuantileDigest(rel_err=self.rel_err)
                d.add(dur)
            fast = self._counts_locked(self.fast_window_s, now)
            slow = self._counts_locked(self.slow_window_s, now)
        reg = self.registry
        if reg is not None:
            try:
                outcome = "error" if error else ("breach" if breach else "ok")
                reg.inc("slo_requests_total", labels={"outcome": outcome})
                reg.observe_digest("slo_request_seconds", latency_s)
                for name, dur in stages.items():
                    reg.observe_digest("stage_seconds", dur,
                                       labels={"stage": name})
            except Exception:
                log.debug("slo metric update failed (ignored)", exc_info=True)
        record = self._burn_record(seq, fast, slow)
        if reg is not None and (
                seq % self.gauge_every == 0
                # while burning, refresh promptly — but at most once a
                # second: the gauge pass merges the whole minute ring,
                # and paying that per-request during a latency incident
                # would pile work onto the exact path that is slow
                or (record["fast_burn"] >= 1.0
                    and now - self._last_gauge_at >= 1.0)):
            self._last_gauge_at = now
            self._update_gauges(record, now)
        # sentinel check OUTSIDE the slo lock: trip callbacks take the
        # rollout manager's lock, and nesting it under ours would couple
        # lock orders across the serve path
        return self.bank.check(record)

    # -- windows -------------------------------------------------------

    def _bucket_locked(self, now: float) -> _Bucket:
        t0 = now - (now % self.bucket_s)
        if not self._buckets or self._buckets[-1].t0 != t0:
            self._buckets.append(_Bucket(t0, self.rel_err))
        return self._buckets[-1]

    def _counts_locked(self, window_s: float, now: float
                       ) -> Tuple[int, int, int]:
        """(total, bad, errors) over the trailing window — count-only,
        O(buckets), no digest merging on the hot path."""
        cutoff = now - window_s
        total = bad = errors = 0
        for b in reversed(self._buckets):
            if b.t0 + self.bucket_s <= cutoff:
                break
            total += b.total
            bad += b.bad
            errors += b.errors
        return total, bad, errors

    def _window_digest_locked(self, window_s: float, now: float
                              ) -> QuantileDigest:
        cutoff = now - window_s
        parts = [b.digest for b in self._buckets
                 if b.t0 + self.bucket_s > cutoff]
        return QuantileDigest.merged(parts, rel_err=self.rel_err)

    @staticmethod
    def _burn(bad: int, total: int, budget: float) -> float:
        if total == 0:
            return 0.0
        return (bad / total) / max(budget, 1e-9)

    def _burn_record(self, seq: int, fast: Tuple[int, int, int],
                     slow: Tuple[int, int, int]) -> Dict[str, Any]:
        o = self.objective
        budget = max(o.latency_budget, o.max_error_rate)
        rec = {
            "kind": "slo", "step": seq, "wall_time": time.time(),
            "fast_requests": fast[0], "fast_bad": fast[1],
            "fast_errors": fast[2],
            "slow_requests": slow[0], "slow_bad": slow[1],
            "slow_errors": slow[2],
            "fast_burn": self._burn(fast[1], fast[0], budget),
            "slow_burn": self._burn(slow[1], slow[0], budget),
            "objective_p99_ms": o.p99_ms,
            "objective_error_rate": o.max_error_rate,
        }
        return rec

    def _update_gauges(self, record: Dict[str, Any], now: float) -> None:
        reg = self.registry
        if reg is None:
            return
        try:
            for window, window_s in (("fast", self.fast_window_s),
                                     ("slow", self.slow_window_s)):
                total = record[f"{window}_requests"]
                bad = record[f"{window}_bad"]
                reg.set("slo_burn_rate", record[f"{window}_burn"],
                        labels={"window": window})
                reg.set("slo_window_error_ratio",
                        bad / total if total else 0.0,
                        labels={"window": window})
                with self._lock:
                    d = self._window_digest_locked(window_s, now)
                if d.count:
                    reg.set("slo_window_p99_ms", d.quantile(0.99) * 1e3,
                            labels={"window": window})
        except Exception:
            log.debug("slo gauge update failed (ignored)", exc_info=True)

    # -- evaluation / read side ---------------------------------------

    def refresh_gauges(self) -> None:
        """Recompute the windowed gauges from CURRENT window state —
        the /metrics scrape path calls this so burn gauges decay to
        zero after traffic stops, instead of freezing at incident-era
        values (observe() only runs while requests flow). Guarded and
        cheap: two count scans + two window merges per scrape."""
        if self.registry is None:
            return
        try:
            now = self._now()
            with self._lock:
                fast = self._counts_locked(self.fast_window_s, now)
                slow = self._counts_locked(self.slow_window_s, now)
                seq = self._seq
            self._last_gauge_at = now
            self._update_gauges(self._burn_record(seq, fast, slow), now)
        except Exception:
            log.debug("slo gauge refresh failed (ignored)", exc_info=True)

    def burn_state(self) -> Dict[str, Any]:
        """Current burn record without recording a request (the
        poll-style read for controllers and tests)."""
        now = self._now()
        with self._lock:
            fast = self._counts_locked(self.fast_window_s, now)
            slow = self._counts_locked(self.slow_window_s, now)
            seq = self._seq
        return self._burn_record(seq, fast, slow)

    def stage_summary(self, qs: Sequence[float] = (0.5, 0.9, 0.99)
                      ) -> Dict[str, Dict[str, Any]]:
        """Per-stage quantile table (ms) from the cumulative digests."""
        with self._lock:
            items = sorted(self.stages.items())
            return {name: d.summary_ms(qs) for name, d in items}

    def debug_state(self, include_digests: bool = True) -> Dict[str, Any]:
        """The ``/debug/slo`` body. ``include_digests`` embeds the
        serialized sketches — what a perfwatch snapshot diffs on."""
        now = self._now()
        with self._lock:
            fast_d = self._window_digest_locked(self.fast_window_s, now)
            slow_d = self._window_digest_locked(self.slow_window_s, now)
            fast = self._counts_locked(self.fast_window_s, now)
            slow = self._counts_locked(self.slow_window_s, now)
            seq = self._seq
            e2e = self.e2e
            stages = sorted(self.stages.items())
            state: Dict[str, Any] = {
                "objective": self.objective.to_dict(),
                # what the e2e digest measures: perfwatch stamps this on
                # snapshots so diff refuses to gate e.g. a worker-process
                # SLO (root_span="worker.handle_event") against an HTTP
                # server baseline
                "root_span": self.root_span,
                "latency_kind": ("http_e2e"
                                 if self.root_span == "http.request"
                                 else self.root_span),
                "windows": {
                    "fast_s": self.fast_window_s,
                    "slow_s": self.slow_window_s,
                    "bucket_s": self.bucket_s,
                },
                "requests_total": self.requests_total,
                "errors_total": self.errors_total,
                "breaches_total": self.breaches_total,
                "started_at": self.started_at,
                "e2e": e2e.summary_ms(),
                "stages": {name: d.summary_ms() for name, d in stages},
            }
            if include_digests:
                state["digests"] = {
                    "e2e": e2e.to_dict(),
                    "stages": {name: d.to_dict() for name, d in stages},
                }
        burn = self._burn_record(seq, fast, slow)
        burn["fast_p99_ms"] = (round(fast_d.quantile(0.99) * 1e3, 3)
                               if fast_d.count else None)
        burn["slow_p99_ms"] = (round(slow_d.quantile(0.99) * 1e3, 3)
                               if slow_d.count else None)
        state["burn"] = burn
        state["trips"] = [dataclasses.asdict(t)
                          for t in self.bank.trips_snapshot()]
        state["trips_total"] = self.bank.trips_total
        return state


# ---------------------------------------------------------------------
# /debug/slo (shared by the embedding server and MetricsServer)
# ---------------------------------------------------------------------


def debug_slo_response(slo: Optional[ServeSLO], query: str = ""):
    """Build the ``/debug/slo`` body: ``(status, bytes, content_type)``.
    Query knobs: ``digests=0`` drops the serialized sketches (smaller
    body for dashboards that only want the quantile table)."""
    if slo is None:
        return 404, json.dumps({"error": "slo tracking not enabled"}
                               ).encode(), "application/json"
    try:
        from urllib.parse import parse_qs

        q = parse_qs(query or "")
        include = q.get("digests", ["1"])[0] not in ("0", "false")
        body = json.dumps(slo.debug_state(include_digests=include)).encode()
        return 200, body, "application/json"
    except Exception as e:  # the debug surface must not 500 the listener
        return 500, json.dumps({"error": str(e)[:200]}).encode(), \
            "application/json"
