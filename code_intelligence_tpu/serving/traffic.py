"""Seeded open-loop traffic generation for the serve fleet.

The load patterns that break a serving system are not "N threads in a
closed loop": a closed-loop client slows down exactly when the server
does, so overload is unobservable by construction. Following the
MLPerf server-scenario model, arrivals here are scheduled by a seeded
clock — a request arrives at its scheduled instant whether or not the
fleet has finished the previous one — so queue growth, shedding and
SLO burn under a spike are real, measurable outcomes.

Four scenarios cover the hostile shapes production traffic actually
takes (the reference system's worker fleet absorbs bursty GitHub
event streams; ours must absorb the same shapes):

``diurnal``      a compressed day: sinusoidal rate between ~0.3x and
                 ~1.7x the base rate — the pattern scale-in headroom
                 detection has to ride without flapping.
``flash_crowd``  flat base rate with a 10x spike for a window in the
                 middle — the scale-out trigger case.
``retry_storm``  flat base rate, but shed clients re-arrive after the
                 server's Retry-After hint; because every shed client
                 honours the same hint, the re-arrivals synchronize
                 into a thundering herd.
``slow_drip``    low rate, very long documents — the workload that
                 stresses per-request service time instead of arrival
                 rate (stragglers, not queues).

Everything is deterministic given a seed and device-free: schedules
are plain Python over ``random.Random``, and the clock is injectable
so the autoscale gate replays a scenario in virtual time; no benchmark
cell replays one in real time against a real engine yet (ROADMAP R2).
"""

from __future__ import annotations

import dataclasses
import heapq
import json
import math
import random
import threading
import time
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "Arrival",
    "OpenLoopRunner",
    "SCENARIOS",
    "TrafficSchedule",
]

# ---------------------------------------------------------------------------
# scenario rate curves
# ---------------------------------------------------------------------------

_WORDS = ("segfault in tokenizer ragged batch pallas kernel tpu host "
          "latency regression checkpoint shard loader mesh axis install "
          "failure docs build flaky test timeout memory oom probe").split()


def _rate_diurnal(t: float, base: float, duration: float) -> float:
    # one full "day" compressed into the schedule: trough ~0.3x, peak ~1.7x
    phase = 2.0 * math.pi * (t / max(duration, 1e-9))
    return max(base * (1.0 + 0.7 * math.sin(phase)), 0.3 * base)


def _rate_flash_crowd(t: float, base: float, duration: float,
                      spike_at: float, spike_len: float,
                      spike_factor: float) -> float:
    if spike_at <= t < spike_at + spike_len:
        return base * spike_factor
    return base


def _rate_flat(t: float, base: float, duration: float) -> float:
    return base


@dataclasses.dataclass(frozen=True)
class _Scenario:
    """Static description of one traffic shape. ``doc_profile`` picks
    the document generator (``short`` issue stubs vs ``long`` wall-of-
    text reports); ``retry_on_shed`` switches the runner into
    thundering-herd mode where shed clients re-arrive."""

    name: str
    blurb: str
    doc_profile: str = "short"
    retry_on_shed: bool = False
    rate_scale: float = 1.0   # slow_drip runs well under the base rate


SCENARIOS: Dict[str, _Scenario] = {
    "diurnal": _Scenario(
        "diurnal", "sinusoidal day curve, 0.3x-1.7x base rate"),
    "flash_crowd": _Scenario(
        "flash_crowd", "flat base with a 10x spike window"),
    "retry_storm": _Scenario(
        "retry_storm", "shed clients re-arrive on the Retry-After hint",
        retry_on_shed=True),
    "slow_drip": _Scenario(
        "slow_drip", "low rate, very long documents", doc_profile="long",
        rate_scale=0.2),
}


@dataclasses.dataclass
class Arrival:
    """One scheduled request: offset seconds from schedule start plus
    the document payload. ``kind`` distinguishes scheduled arrivals
    from retry-storm re-arrivals in summaries."""

    t: float
    doc: Dict[str, str]
    kind: str = "fresh"
    attempt: int = 0

    def __lt__(self, other: "Arrival") -> bool:   # heapq ordering
        return self.t < other.t


class TrafficSchedule:
    """A deterministic arrival plan for one scenario.

    Arrivals are drawn from a nonhomogeneous Poisson process by
    thinning: candidate gaps at the scenario's peak rate, each kept
    with probability ``rate(t) / peak``. Same seed, same scenario,
    same parameters -> byte-identical arrival list, which is what lets
    the acceptance gate pin scale-out timing and lets two bench runs
    on different machines replay the same offered load.
    """

    def __init__(self, scenario: str, base_rate_per_s: float = 20.0,
                 duration_s: float = 300.0, seed: int = 0,
                 spike_factor: float = 10.0,
                 spike_at_s: Optional[float] = None,
                 spike_len_s: Optional[float] = None,
                 long_doc_words: int = 600):
        if scenario not in SCENARIOS:
            raise ValueError(
                f"unknown traffic scenario {scenario!r}; "
                f"have {sorted(SCENARIOS)}")
        if base_rate_per_s <= 0 or duration_s <= 0:
            raise ValueError("base_rate_per_s and duration_s must be > 0")
        self.scenario = SCENARIOS[scenario]
        self.base_rate_per_s = float(base_rate_per_s)
        self.duration_s = float(duration_s)
        self.seed = int(seed)
        self.spike_factor = float(spike_factor)
        self.spike_at_s = (float(spike_at_s) if spike_at_s is not None
                           else 0.4 * self.duration_s)
        self.spike_len_s = (float(spike_len_s) if spike_len_s is not None
                            else 0.15 * self.duration_s)
        self.long_doc_words = int(long_doc_words)

    # -- rate curve ----------------------------------------------------

    def rate_at(self, t: float) -> float:
        """Instantaneous offered rate (requests/s) at offset ``t``."""
        base = self.base_rate_per_s * self.scenario.rate_scale
        if self.scenario.name == "diurnal":
            return _rate_diurnal(t, base, self.duration_s)
        if self.scenario.name == "flash_crowd":
            return _rate_flash_crowd(t, base, self.duration_s,
                                     self.spike_at_s, self.spike_len_s,
                                     self.spike_factor)
        return _rate_flat(t, base, self.duration_s)

    @property
    def peak_rate_per_s(self) -> float:
        base = self.base_rate_per_s * self.scenario.rate_scale
        if self.scenario.name == "diurnal":
            return 1.7 * base
        if self.scenario.name == "flash_crowd":
            return base * self.spike_factor
        return base

    # -- documents -----------------------------------------------------

    def _doc(self, rng: random.Random, i: int) -> Dict[str, str]:
        title = (f"[{self.scenario.name}] " +
                 " ".join(rng.choice(_WORDS) for _ in range(4)) + f" #{i}")
        n_words = (self.long_doc_words
                   if self.scenario.doc_profile == "long"
                   else rng.randint(12, 40))
        body = " ".join(rng.choice(_WORDS) for _ in range(n_words))
        return {"title": title, "body": body}

    # -- arrivals ------------------------------------------------------

    def arrivals(self) -> List[Arrival]:
        """Materialize the full schedule (thinning against the peak
        rate). Deterministic for a given seed."""
        rng = random.Random(self.seed)
        peak = self.peak_rate_per_s
        out: List[Arrival] = []
        t = 0.0
        i = 0
        while True:
            t += rng.expovariate(peak)
            if t >= self.duration_s:
                break
            if rng.random() <= self.rate_at(t) / peak:
                out.append(Arrival(t=t, doc=self._doc(rng, i)))
                i += 1
        return out

    def describe(self) -> Dict[str, Any]:
        """Provenance block for bench result lines: everything needed
        to regenerate this exact schedule."""
        return {
            "scenario": self.scenario.name,
            "base_rate_per_s": self.base_rate_per_s,
            "duration_s": self.duration_s,
            "seed": self.seed,
            "spike_factor": self.spike_factor,
            "spike_at_s": round(self.spike_at_s, 3),
            "spike_len_s": round(self.spike_len_s, 3),
            "retry_on_shed": self.scenario.retry_on_shed,
            "doc_profile": self.scenario.doc_profile,
        }


# ---------------------------------------------------------------------------
# open-loop replay
# ---------------------------------------------------------------------------

def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


class OpenLoopRunner:
    """Replay a :class:`TrafficSchedule` against a ``send`` callable in
    real time, open-loop: each arrival dispatches at its scheduled
    instant on its own thread, regardless of whether earlier requests
    have completed. ``send(doc) -> result`` must return a dict with at
    least ``ok`` (bool) and ``status`` (int); a shed response (HTTP
    429/503) may carry ``retry_after_s``.

    In ``retry_storm`` mode a shed arrival is re-enqueued at
    ``now + retry_after_s`` (bounded by ``retry_cap`` attempts) — the
    herd effect comes free, because every shed client honours the same
    hint and re-arrives in the same instant.

    ``clock``/``sleep`` are injectable so tests can compress time.
    """

    SHED_STATUSES = frozenset({429, 503})

    def __init__(self, schedule: TrafficSchedule,
                 send: Callable[[Dict[str, str]], Dict[str, Any]],
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 registry=None, max_inflight: int = 128,
                 retry_cap: int = 2,
                 default_retry_after_s: float = 0.5):
        self.schedule = schedule
        self.send = send
        self.clock = clock
        self.sleep = sleep
        self.retry_cap = int(retry_cap)
        self.default_retry_after_s = float(default_retry_after_s)
        self._sem = threading.Semaphore(int(max_inflight))
        self._lock = threading.Lock()
        self._latencies: List[float] = []
        self._counts = {"offered": 0, "completed": 0, "shed": 0,
                        "retried": 0, "failed": 0, "overflow": 0}
        self._retry_heap: List[Arrival] = []
        self.registry = registry
        if registry is not None:
            registry.counter("traffic_offered_total",
                             "open-loop arrivals dispatched")
            registry.counter("traffic_completed_total",
                             "open-loop requests completed ok")
            registry.counter("traffic_shed_total",
                             "open-loop requests shed (429/503)")
            registry.counter("traffic_retries_total",
                             "retry-storm re-arrivals enqueued")
            registry.counter("traffic_failed_total",
                             "open-loop requests failed (non-shed)")

    def _inc(self, key: str, metric: str) -> None:
        with self._lock:
            self._counts[key] += 1
        if self.registry is not None:
            self.registry.inc(metric,
                              labels={"scenario":
                                      self.schedule.scenario.name})

    def _dispatch(self, arrival: Arrival, started: float) -> None:
        try:
            t0 = self.clock()
            res = self.send(arrival.doc) or {}
            latency = self.clock() - t0
            status = int(res.get("status", 0))
            if res.get("ok"):
                with self._lock:
                    self._latencies.append(latency)
                self._inc("completed", "traffic_completed_total")
            elif status in self.SHED_STATUSES:
                self._inc("shed", "traffic_shed_total")
                if (self.schedule.scenario.retry_on_shed
                        and arrival.attempt < self.retry_cap):
                    retry_after = float(res.get("retry_after_s")
                                        or self.default_retry_after_s)
                    again = Arrival(
                        t=(self.clock() - started) + retry_after,
                        doc=arrival.doc, kind="retry",
                        attempt=arrival.attempt + 1)
                    with self._lock:
                        heapq.heappush(self._retry_heap, again)
                    self._inc("retried", "traffic_retries_total")
            else:
                self._inc("failed", "traffic_failed_total")
        finally:
            self._sem.release()

    def run(self) -> Dict[str, Any]:
        arrivals = self.schedule.arrivals()
        started = self.clock()
        threads: List[threading.Thread] = []
        idx = 0
        while True:
            with self._lock:
                next_retry = (self._retry_heap[0]
                              if self._retry_heap else None)
            nxt: Optional[Arrival] = None
            if idx < len(arrivals) and (
                    next_retry is None
                    or arrivals[idx].t <= next_retry.t):
                nxt = arrivals[idx]
                idx += 1
            elif next_retry is not None:
                with self._lock:
                    nxt = heapq.heappop(self._retry_heap)
            if nxt is None:
                # scheduled arrivals exhausted; a straggler thread may
                # still push a retry — wait for inflight to settle
                if any(th.is_alive() for th in threads):
                    self.sleep(0.01)
                    continue
                break
            delay = (started + nxt.t) - self.clock()
            if delay > 0:
                self.sleep(delay)
            self._inc("offered", "traffic_offered_total")
            if not self._sem.acquire(blocking=False):
                with self._lock:
                    self._counts["overflow"] += 1
                continue
            th = threading.Thread(target=self._dispatch,
                                  args=(nxt, started), daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=30.0)
        return self._summary(self.clock() - started)

    def _summary(self, wall_s: float) -> Dict[str, Any]:
        with self._lock:
            counts = dict(self._counts)
            lat = sorted(self._latencies)
        out: Dict[str, Any] = dict(counts)
        out["wall_s"] = round(wall_s, 3)
        out["achieved_rate_per_s"] = round(
            counts["completed"] / wall_s, 3) if wall_s > 0 else 0.0
        out["latency_ms"] = {
            "p50": round(_percentile(lat, 0.50) * 1e3, 3),
            "p90": round(_percentile(lat, 0.90) * 1e3, 3),
            "p99": round(_percentile(lat, 0.99) * 1e3, 3),
        }
        out["schedule"] = self.schedule.describe()
        return out


if __name__ == "__main__":   # quick eyeball: arrival counts per scenario
    for name in sorted(SCENARIOS):
        sched = TrafficSchedule(name, base_rate_per_s=20.0,
                                duration_s=60.0, seed=0)
        arr = sched.arrivals()
        print(json.dumps({"scenario": name, "arrivals": len(arr),
                          "peak_rate_per_s": sched.peak_rate_per_s}))
