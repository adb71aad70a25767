"""Open-loop traffic generator tests (serving/traffic.py).

Everything here is device-free and runs in compressed virtual time:
the runner's clock and sleep are injected, so an 8-second scenario
replays in milliseconds. The virtual-time consumer is the autoscale
gate (serving/fleet/autoscale_check.py); nothing replays a scenario in
real time against a real engine yet (ROADMAP R2).
"""

import threading

import pytest

from code_intelligence_tpu.serving.traffic import (
    SCENARIOS, Arrival, OpenLoopRunner, TrafficSchedule)
from code_intelligence_tpu.utils.metrics import Registry


class _VirtualTime:
    """Deterministic clock + sleep pair for compressed replay."""

    def __init__(self):
        self.t = 0.0
        self._lock = threading.Lock()

    def clock(self):
        with self._lock:
            return self.t

    def sleep(self, dt):
        with self._lock:
            self.t += max(dt, 0.0)


class TestTrafficSchedule:
    def test_same_seed_same_arrivals(self):
        a = TrafficSchedule("diurnal", duration_s=30.0, seed=7).arrivals()
        b = TrafficSchedule("diurnal", duration_s=30.0, seed=7).arrivals()
        assert [(x.t, x.doc) for x in a] == [(x.t, x.doc) for x in b]
        assert len(a) > 10

    def test_different_seed_different_arrivals(self):
        a = TrafficSchedule("diurnal", duration_s=30.0, seed=0).arrivals()
        b = TrafficSchedule("diurnal", duration_s=30.0, seed=1).arrivals()
        assert [x.t for x in a] != [x.t for x in b]

    def test_flash_crowd_spike_window_is_denser(self):
        sched = TrafficSchedule("flash_crowd", base_rate_per_s=20.0,
                                duration_s=100.0, seed=0,
                                spike_at_s=40.0, spike_len_s=15.0)
        arr = sched.arrivals()
        in_spike = sum(1 for a in arr if 40.0 <= a.t < 55.0)
        before = sum(1 for a in arr if 0.0 <= a.t < 15.0)
        # 10x the rate over an equal-length window: well over 5x the
        # arrivals even with Poisson noise
        assert in_spike > 5 * max(before, 1)
        assert sched.rate_at(45.0) == pytest.approx(200.0)
        assert sched.rate_at(10.0) == pytest.approx(20.0)

    def test_diurnal_rate_curve_bounds(self):
        sched = TrafficSchedule("diurnal", base_rate_per_s=20.0,
                                duration_s=100.0)
        rates = [sched.rate_at(t) for t in range(100)]
        assert max(rates) <= 1.7 * 20.0 + 1e-9
        assert min(rates) >= 0.3 * 20.0 - 1e-9
        assert sched.peak_rate_per_s == pytest.approx(34.0)

    def test_slow_drip_long_docs_low_rate(self):
        sched = TrafficSchedule("slow_drip", base_rate_per_s=20.0,
                                duration_s=60.0, seed=0)
        arr = sched.arrivals()
        # rate_scale 0.2: ~4/s offered, not 20/s
        assert 60 < len(arr) < 400
        assert all(len(a.doc["body"].split()) == 600 for a in arr)

    def test_arrivals_sorted_and_in_range(self):
        for name in SCENARIOS:
            arr = TrafficSchedule(name, duration_s=20.0).arrivals()
            ts = [a.t for a in arr]
            assert ts == sorted(ts)
            assert all(0.0 <= t < 20.0 for t in ts)

    def test_describe_regenerates_exactly(self):
        sched = TrafficSchedule("flash_crowd", base_rate_per_s=11.0,
                                duration_s=33.0, seed=5, spike_factor=4.0)
        d = sched.describe()
        again = TrafficSchedule(d["scenario"],
                                base_rate_per_s=d["base_rate_per_s"],
                                duration_s=d["duration_s"], seed=d["seed"],
                                spike_factor=d["spike_factor"],
                                spike_at_s=d["spike_at_s"],
                                spike_len_s=d["spike_len_s"])
        assert ([(x.t, x.doc) for x in sched.arrivals()]
                == [(x.t, x.doc) for x in again.arrivals()])

    def test_unknown_scenario_refused(self):
        with pytest.raises(ValueError, match="unknown traffic scenario"):
            TrafficSchedule("nope")

    def test_the_scenarios_are_the_documented_four(self):
        assert sorted(SCENARIOS) == ["diurnal", "flash_crowd",
                                     "retry_storm", "slow_drip"]


class TestOpenLoopRunner:
    def _run(self, scenario, send, registry=None, **sched_kw):
        vt = _VirtualTime()
        sched_kw.setdefault("base_rate_per_s", 30.0)
        sched_kw.setdefault("duration_s", 5.0)
        sched = TrafficSchedule(scenario, **sched_kw)
        runner = OpenLoopRunner(sched, send, clock=vt.clock,
                                sleep=vt.sleep, registry=registry)
        return runner.run()

    def test_open_loop_counts_every_arrival(self):
        seen = []

        def send(doc):
            seen.append(doc)
            return {"ok": True, "status": 200}

        out = self._run("diurnal", send, seed=3)
        assert out["offered"] == len(
            TrafficSchedule("diurnal", base_rate_per_s=30.0,
                            duration_s=5.0, seed=3).arrivals())
        assert out["completed"] == out["offered"] > 0
        assert out["shed"] == out["failed"] == out["retried"] == 0
        assert out["schedule"]["scenario"] == "diurnal"

    def test_shed_is_counted_not_failed(self):
        def send(doc):
            return {"ok": False, "status": 429, "retry_after_s": 0.1}

        out = self._run("diurnal", send)
        assert out["shed"] == out["offered"] > 0
        assert out["failed"] == 0
        # diurnal is not retry_on_shed: no re-arrivals
        assert out["retried"] == 0

    def test_retry_storm_shed_clients_rearrive(self):
        calls = {"n": 0}

        def send(doc):
            calls["n"] += 1
            # first contact sheds, the re-arrival succeeds
            if calls["n"] % 2 == 1:
                return {"ok": False, "status": 429, "retry_after_s": 0.2}
            return {"ok": True, "status": 200}

        out = self._run("retry_storm", send, seed=1)
        assert out["retried"] > 0
        assert out["completed"] > 0
        # every retry was a real extra dispatch beyond the schedule
        n_sched = len(TrafficSchedule("retry_storm", base_rate_per_s=30.0,
                                      duration_s=5.0, seed=1).arrivals())
        assert out["offered"] == n_sched + out["retried"]

    def test_retry_cap_bounds_the_herd(self):
        def send(doc):
            return {"ok": False, "status": 503, "retry_after_s": 0.1}

        vt = _VirtualTime()
        sched = TrafficSchedule("retry_storm", base_rate_per_s=10.0,
                                duration_s=3.0, seed=0)
        runner = OpenLoopRunner(sched, send, clock=vt.clock,
                                sleep=vt.sleep, retry_cap=2)
        out = runner.run()
        n_sched = len(sched.arrivals())
        # each scheduled arrival re-arrives at most retry_cap times
        assert out["retried"] <= 2 * n_sched
        assert out["offered"] == n_sched + out["retried"]

    def test_failures_counted_separately_from_shed(self):
        def send(doc):
            return {"ok": False, "status": 500}

        out = self._run("slow_drip", send)
        assert out["failed"] == out["offered"] > 0
        assert out["shed"] == 0

    def test_registry_counters_labeled_by_scenario(self):
        reg = Registry()

        def send(doc):
            return {"ok": True, "status": 200}

        self._run("flash_crowd", send, registry=reg, duration_s=2.0)
        text = reg.render()
        assert 'traffic_offered_total{scenario="flash_crowd"}' in text
        assert 'traffic_completed_total{scenario="flash_crowd"}' in text

    def test_arrival_ordering_for_heap(self):
        assert Arrival(1.0, {}) < Arrival(2.0, {})
