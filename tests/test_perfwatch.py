"""perfwatch (utils/perfwatch.py): the serve-path latency regression
gate, plus the ISSUE 8 acceptance pins.

The seeded-regression pin runs the whole loop device-free on a
simulated clock: a fake serve pipeline whose device step is wrapped by
``FaultInjector`` latency injection (the injector's injectable sleep
advances the same clock the SLO observatory reads, so no wall-clock
sleeps anywhere). perfwatch against the pre-injection snapshot must
exit nonzero NAMING ``slots.device_steps``, the burn-rate sentinel
must trip within the fast window — and with injection off, perfwatch
must exit 0.
"""

import json
import math
import threading
import time
import urllib.request
from pathlib import Path

import pytest

from code_intelligence_tpu.serving.slo import ServeSLO, SLOObjective
from code_intelligence_tpu.utils import perfwatch
from code_intelligence_tpu.utils.digest import QuantileDigest
from code_intelligence_tpu.utils.faults import FaultInjector
from code_intelligence_tpu.utils.metrics import Registry, start_metrics_server

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------


def _digest(values) -> dict:
    d = QuantileDigest()
    d.add_many(values)
    return d.to_dict()


def _snapshot(e2e, stages=None, provenance="fresh") -> dict:
    return {
        "kind": "perfwatch_snapshot",
        "provenance": provenance,
        "measured_git": "testgit",
        "measured_at": "2026-08-03T00:00:00Z",
        "slo": {"requests_total": len(e2e),
                "digests": {"e2e": _digest(e2e),
                            "stages": {k: _digest(v)
                                       for k, v in (stages or {}).items()}}},
    }


BASE = [0.010] * 50      # steady 10ms
SLOWER = [0.030] * 50    # 3x: far outside the default 25% band


# ---------------------------------------------------------------------
# compare()
# ---------------------------------------------------------------------


class TestCompare:
    def test_identical_passes(self):
        snap = _snapshot(BASE, {"slots.device_steps": BASE})
        report = perfwatch.compare(snap, snap)
        assert report["ok"] and not report["regressions"]
        assert set(report["compared"]) == {"e2e", "slots.device_steps"}

    def test_regression_names_the_stage(self):
        base = _snapshot(BASE, {"slots.device_steps": BASE,
                                "cache.lookup": BASE})
        cur = _snapshot(SLOWER, {"slots.device_steps": SLOWER,
                                 "cache.lookup": BASE})
        report = perfwatch.compare(cur, base)
        assert not report["ok"]
        assert report["regressed_stages"] == ["e2e", "slots.device_steps"]
        assert "cache.lookup" not in report["regressed_stages"]

    def test_improvement_is_not_a_regression(self):
        report = perfwatch.compare(_snapshot(BASE), _snapshot(SLOWER))
        assert report["ok"] and report["improvements"]

    def test_abs_floor_absorbs_microsecond_noise(self):
        # 2x in RELATIVE terms but only 0.2ms in absolute: under the
        # 5ms floor this is scheduler noise, not a regression
        report = perfwatch.compare(_snapshot([0.0004] * 50),
                                   _snapshot([0.0002] * 50))
        assert report["ok"]

    def test_low_count_skipped_loudly(self):
        report = perfwatch.compare(_snapshot([0.010] * 3),
                                   _snapshot([0.010] * 3))
        assert not report["ok"]  # nothing compared → not a pass
        assert report["skipped"]
        assert "insufficient samples" in report["skipped"][0]["reason"]

    def test_one_sided_stages_reported_uncompared(self):
        base = _snapshot(BASE, {"slots.device_steps": BASE})
        cur = _snapshot(BASE, {"cache.lookup": BASE})
        report = perfwatch.compare(cur, base)
        assert set(report["uncompared"]) == {"slots.device_steps",
                                             "cache.lookup"}

    def test_bench_line_baseline_compares_e2e(self):
        # a bench JSON line carries latency_digest at top level
        bench_line = {"metric": "embedding_serving_latency",
                      "provenance": "fresh",
                      "latency_digest": _digest(BASE)}
        report = perfwatch.compare(_snapshot(SLOWER), bench_line)
        assert not report["ok"]
        assert report["regressed_stages"] == ["e2e"]

    def test_latency_kind_mismatch_refused(self):
        # an engine-direct smoke digest must never gate an HTTP e2e
        # digest: different measurements, false verdict either way
        smoke_line = {"provenance": "fresh",
                      "latency_kind": "engine_single_doc",
                      "latency_digest": _digest(BASE)}
        live = dict(_snapshot(SLOWER), latency_kind="http_e2e")
        report = perfwatch.compare(live, smoke_line)
        assert not report["ok"] and not report["regressions"]
        assert any("latency_kind mismatch" in s["reason"]
                   for s in report["skipped"])
        # matching kinds still compare
        http_line = dict(smoke_line, latency_kind="http_e2e")
        assert perfwatch.compare(live, http_line)["regressed_stages"] == \
            ["e2e"]
        # an undeclared side keeps backward compatibility
        legacy = {"provenance": "fresh", "latency_digest": _digest(BASE)}
        assert perfwatch.compare(live, legacy)["compared"] == ["e2e"]


class TestProvenance:
    def test_fresh_gates(self):
        assert perfwatch.check_provenance({"provenance": "fresh"},
                                          False) is None

    @pytest.mark.parametrize("prov", ["last_good_fallback",
                                      "no_measurement_available"])
    def test_stale_refused_without_allow_stale(self, prov):
        reason = perfwatch.check_provenance({"provenance": prov}, False)
        assert reason and prov in reason
        assert perfwatch.check_provenance({"provenance": prov}, True) is None

    def test_missing_stamp_refused(self):
        assert "no provenance" in perfwatch.check_provenance({}, False)

    def test_real_stale_bench_artifact_refused(self):
        # BENCH_r05.json is the actual last_good_fallback artifact the
        # motivation cites — the gate must refuse it end-to-end
        rc = perfwatch.main(["diff", "--baseline",
                             str(REPO / "BENCH_r05.json"),
                             "--current", "/dev/null"])
        assert rc == 2


class TestParsing:
    def test_bench_wrapper_unwrapped(self, tmp_path):
        f = tmp_path / "b.json"
        f.write_text(json.dumps(
            {"parsed": {"metric": "m", "provenance": "fresh",
                        "latency_digest": _digest(BASE)}}))
        obj = perfwatch._parse_any(f)
        assert obj["metric"] == "m"

    def test_jsonl_takes_last_parseable_line(self, tmp_path):
        f = tmp_path / "series.jsonl"
        f.write_text("not json\n"
                     + json.dumps({"provenance": "fresh", "v": 1}) + "\n"
                     + json.dumps({"provenance": "fresh", "v": 2}) + "\n")
        assert perfwatch._parse_any(f)["v"] == 2


# ---------------------------------------------------------------------
# self-check + CLI
# ---------------------------------------------------------------------


class TestSelfCheckAndCLI:
    def test_committed_fixture_self_check(self):
        # the CI gate's own gate: identical passes, a planted 2x
        # slots.device_steps inflation fails naming that stage
        report = perfwatch.self_check()
        assert report["ok"], report
        assert report["planted_detected"]
        assert "slots.device_steps" in report["planted_regressed_stages"]

    def test_selfcheck_cli_exit_zero(self, capsys):
        assert perfwatch.main(["selfcheck"]) == 0
        assert json.loads(capsys.readouterr().out)["ok"]

    def test_diff_cli_exit_codes(self, tmp_path, capsys):
        base = tmp_path / "base.json"
        cur_ok = tmp_path / "ok.json"
        cur_bad = tmp_path / "bad.json"
        base.write_text(json.dumps(_snapshot(
            BASE, {"slots.device_steps": BASE})))
        cur_ok.write_text(json.dumps(_snapshot(
            BASE, {"slots.device_steps": BASE})))
        cur_bad.write_text(json.dumps(_snapshot(
            SLOWER, {"slots.device_steps": SLOWER})))
        assert perfwatch.main(["diff", "--baseline", str(base),
                               "--current", str(cur_ok)]) == 0
        capsys.readouterr()
        assert perfwatch.main(["diff", "--baseline", str(base),
                               "--current", str(cur_bad)]) == 1
        out, err = capsys.readouterr()
        assert "slots.device_steps" in json.loads(
            out)["regressed_stages"]
        assert "REGRESSION" in err  # the one-line human verdict
        assert perfwatch.main(["diff", "--baseline", "/nonexistent.json",
                               "--current", str(cur_ok)]) == 2

    def test_nothing_comparable_exits_two_not_one(self, tmp_path, capsys):
        # a warm-up server (every series under --min_count) is UNUSABLE
        # INPUT, not a latency regression: exit 2, like a refused stamp
        thin = tmp_path / "thin.json"
        thin.write_text(json.dumps(_snapshot([0.010] * 3)))
        assert perfwatch.main(["diff", "--baseline", str(thin),
                               "--current", str(thin)]) == 2
        assert "not gating" in capsys.readouterr().err

    def test_snapshot_and_live_diff_against_metrics_server(self, tmp_path,
                                                           capsys):
        # a live pull end-to-end over HTTP: MetricsServer exposes the
        # same /debug/slo + /metrics surfaces the embedding server does
        slo = ServeSLO(objective=SLOObjective(p99_ms=250.0))
        for _ in range(20):
            slo.observe(0.010, stages={"slots.device_steps": 0.008})
        reg = Registry()
        slo.bind_registry(reg)
        srv = start_metrics_server(reg, port=0, host="127.0.0.1", slo=slo)
        url = f"http://127.0.0.1:{srv.port}"
        try:
            out = tmp_path / "snap.json"
            assert perfwatch.main(["snapshot", "--url", url,
                                   "--out", str(out)]) == 0
            snap = json.loads(out.read_text())
            assert snap["provenance"] == "fresh"
            assert snap["slo"]["requests_total"] == 20
            capsys.readouterr()
            # live vs its own snapshot: in-band by construction
            assert perfwatch.main(["diff", "--url", url,
                                   "--baseline", str(out)]) == 0
        finally:
            srv.shutdown()

    def test_snapshot_unreachable_server_exits_two(self, capsys):
        # a down server is unusable input, not a latency regression:
        # exit 2 (like diff maps the same failure), one JSON object on
        # stdout, no traceback
        rc = perfwatch.main(["snapshot", "--url", "http://127.0.0.1:1",
                             "--timeout", "0.2"])
        assert rc == 2
        out = json.loads(capsys.readouterr().out)
        assert out["ok"] is False and "error" in out

    def test_snapshot_latency_kind_follows_slo_root_span(self):
        # a non-HTTP process (a worker) exposing its SLO through
        # MetricsServer must NOT be stamped http_e2e — compare()'s
        # kind-mismatch refusal depends on the label telling the truth
        slo = ServeSLO(objective=SLOObjective(),
                       root_span="worker.handle_event")
        for _ in range(20):
            slo.observe(0.010)
        reg = Registry()
        slo.bind_registry(reg)
        srv = start_metrics_server(reg, port=0, host="127.0.0.1", slo=slo)
        try:
            snap = perfwatch.take_snapshot(
                f"http://127.0.0.1:{srv.port}")
            assert snap["latency_kind"] == "worker.handle_event"
            http_base = _snapshot(BASE)
            http_base["latency_kind"] = "http_e2e"
            report = perfwatch.compare(snap, http_base)
            assert "e2e" not in report["compared"]
            assert any("latency_kind mismatch" in s["reason"]
                       for s in report["skipped"])
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------
# the acceptance pins
# ---------------------------------------------------------------------


class SimClock:
    def __init__(self, t=10_000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class SimServePath:
    """A miniature serve pipeline on a simulated clock: queue wait →
    device step → pool emit, each stage's duration read off the same
    clock the SLO observatory uses. The device step is a callable so
    ``FaultInjector.wrap`` can inject latency into exactly that stage —
    the injector's injectable ``sleep`` advances this clock."""

    def __init__(self, clock, slo, device_step):
        self.clock = clock
        self.slo = slo
        self.device_step = device_step

    def serve(self, n):
        trips = []
        for _ in range(n):
            t0 = self.clock.t
            stages = {}
            s = self.clock.t
            self.clock.advance(0.0005)                 # queue wait
            stages["slots.queue_wait"] = self.clock.t - s
            s = self.clock.t
            self.device_step()                         # device steps
            stages["slots.device_steps"] = self.clock.t - s
            s = self.clock.t
            self.clock.advance(0.0002)                 # pool emit
            stages["slots.pool_emit"] = self.clock.t - s
            trips += self.slo.observe(self.clock.t - t0, stages=stages)
            self.clock.advance(0.05)                   # request spacing
        return trips


def _sim_snapshot(slo) -> dict:
    return {"kind": "perfwatch_snapshot", "provenance": "fresh",
            "measured_git": "sim", "slo": slo.debug_state()}


class TestSeededRegressionPin:
    """ISSUE 8 acceptance: FaultInjector latency on the device step →
    perfwatch nonzero naming slots.device_steps + burn sentinel trips
    within the fast window; injection off → perfwatch exits 0."""

    OBJECTIVE = SLOObjective(p99_ms=20.0)  # steady path ~6ms, injected ~56ms

    def _run(self, inject: bool, n=60):
        clock = SimClock()
        slo = ServeSLO(objective=self.OBJECTIVE, now=clock,
                       min_requests=10, burn_threshold=2.0)
        base_step = lambda: clock.advance(0.005)
        if inject:
            inj = FaultInjector(seed=42, error_rate=0.0, latency_s=0.050,
                                latency_rate=1.0, sleep=clock.advance)
            step = inj.wrap(base_step)
        else:
            step = base_step
        trips = SimServePath(clock, slo, step).serve(n)
        return slo, trips, clock

    def test_injection_off_perfwatch_exits_zero(self, tmp_path, capsys):
        slo_a, trips, _ = self._run(inject=False)
        slo_b, _, _ = self._run(inject=False)
        base = tmp_path / "base.json"
        base.write_text(json.dumps(_sim_snapshot(slo_a)))
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps(_sim_snapshot(slo_b)))
        assert perfwatch.main(["diff", "--baseline", str(base),
                               "--current", str(cur)]) == 0
        assert trips == []  # healthy traffic never trips the sentinel

    def test_injected_latency_detected_and_named(self, tmp_path, capsys):
        slo_pre, _, _ = self._run(inject=False)
        base = tmp_path / "pre_injection.json"
        base.write_text(json.dumps(_sim_snapshot(slo_pre)))

        slo_inj, trips, clock = self._run(inject=True)
        cur = tmp_path / "injected.json"
        cur.write_text(json.dumps(_sim_snapshot(slo_inj)))

        rc = perfwatch.main(["diff", "--baseline", str(base),
                             "--current", str(cur)])
        out = capsys.readouterr().out
        assert rc == 1
        report = json.loads(out.splitlines()[-1])
        # the verdict NAMES the regressed stage — a page without a
        # diagnosis is the failure mode this gate exists to kill
        assert "slots.device_steps" in report["regressed_stages"]
        # ...and the untouched stages are NOT blamed
        assert "slots.queue_wait" not in report["regressed_stages"]
        assert "slots.pool_emit" not in report["regressed_stages"]

        # the burn-rate sentinel tripped DURING the injection run,
        # within the fast window (simulated time elapsed << 300s)
        assert trips and trips[0].sentinel == "slo_burn_rate"
        assert clock.t - 10_000.0 < slo_inj.fast_window_s
        assert slo_inj.bank.trips_total >= 1


class TestDigestOverheadPin:
    def test_observe_cost_under_one_percent_of_smoke_latency(self):
        # ISSUE 8 acceptance: digest overhead per request < 1% of the
        # smoke-workload serve latency. The smoke single-doc p50 is
        # ~10ms (a CPU reading from before the benchmark); 1% = 100µs.
        # One observe() = e2e digest add + 4 stage adds + window
        # bookkeeping + sentinel check — budget 100µs each.
        slo = ServeSLO(objective=SLOObjective(p99_ms=250.0))
        stages = {"slots.queue_wait": 0.0005,
                  "slots.device_steps": 0.008,
                  "slots.pool_emit": 0.0002,
                  "cache.lookup": 0.0001}
        for _ in range(100):  # warm
            slo.observe(0.010, stages=stages)
        n = 5_000
        t0 = time.perf_counter()
        for _ in range(n):
            slo.observe(0.010, stages=stages)
        per_request = (time.perf_counter() - t0) / n
        assert per_request < 100e-6, (
            f"observe() costs {per_request * 1e6:.1f}µs/request "
            f"(budget 100µs = 1% of the ~10ms smoke serve latency)")
