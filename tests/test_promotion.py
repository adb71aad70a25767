"""Promotion controller + rollout manager: shadow replay, deterministic
canary split, sentinel-gated automatic rollback, hot-swap, graceful
drain, and kill-at-any-phase restart recovery. Everything here except
the hot-swap pin is device-free (fake engines) — the chaos/recovery
machinery must be provable without a chip."""

import json
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from code_intelligence_tpu.registry.promotion import (
    PromotionController,
    PromotionError,
    PromotionState,
    SmokeEngine,
    run_promotion_smoke,
)
from code_intelligence_tpu.registry.registry import ModelRegistry
from code_intelligence_tpu.serving.rollout import (
    EmbeddingNormBandSentinel,
    NonFiniteEmbeddingSentinel,
    RolloutManager,
    ServeErrorRateSentinel,
    ServeLatencyBandSentinel,
    ShadowGates,
    TrafficRing,
    _split_bucket,
)
from code_intelligence_tpu.utils.faults import FaultInjector
from code_intelligence_tpu.utils.storage import LocalStorage


def _embed_fn(engine, title, body):
    return engine.embed_issue(title, body)


def _make_registry(tmp_path, versions=("v1", "v2"), auc=0.95):
    reg = ModelRegistry(LocalStorage(tmp_path / "store"))
    art = tmp_path / "art"
    art.mkdir(exist_ok=True)
    (art / "w.txt").write_text("w")
    for v in versions:
        reg.register("org/m", art, version=v, metrics={"weighted_auc": auc})
    return reg


def _make_ctrl(tmp_path, reg, rollout, **kw):
    kw.setdefault("deployed_config_path", tmp_path / "deployed.yaml")
    kw.setdefault("min_canary_requests", 3)
    # no wall-clock gate: shadow replay times ONE call of each engine, a
    # few microseconds of a SmokeEngine, and one stalled call on a loaded
    # host reads as a latency ratio above 5: the candidate is rejected
    # before its canary, and a later rollback is a no-op
    kw.setdefault("gates", ShadowGates(max_latency_ratio=None))
    return PromotionController(reg, rollout, tmp_path / "promo.json",
                               "org/m", **kw)


class TestTrafficRing:
    def test_bounded_and_ordered(self):
        ring = TrafficRing(capacity=4)
        for i in range(10):
            ring.record(f"t{i}", f"b{i}")
        snap = ring.snapshot()
        assert len(snap) == 4 and snap[-1]["title"] == "t9"
        assert ring.recorded_total == 10

    def test_snapshot_n(self):
        ring = TrafficRing(capacity=8)
        for i in range(5):
            ring.record(f"t{i}", "b")
        assert [d["title"] for d in ring.snapshot(2)] == ["t3", "t4"]


class TestCanarySplit:
    def test_deterministic_per_document(self):
        # same doc -> same bucket, always; buckets roughly uniform
        assert _split_bucket("a", "b") == _split_bucket("a", "b")
        buckets = [_split_bucket(f"t{i}", f"b{i}") for i in range(400)]
        frac = sum(b < 5000 for b in buckets) / len(buckets)
        assert 0.35 < frac < 0.65  # md5 uniformity, wide band

    def test_split_respects_pct(self):
        mgr = RolloutManager(SmokeEngine(), version="v1")
        mgr.start_canary("v2", SmokeEngine(), pct=30.0)
        roles = {}
        for i in range(300):
            _, _, role = mgr.route(f"t{i}", f"b{i}")
            roles[role] = roles.get(role, 0) + 1
        share = roles.get("canary", 0) / 300
        assert 0.15 < share < 0.45
        # determinism: the same traffic re-routes identically
        again = [mgr.route(f"t{i}", f"b{i}")[2] for i in range(300)]
        assert sum(r == "canary" for r in again) == roles.get("canary", 0)


class TestServeSentinels:
    def _rec(self, **kw):
        base = {"kind": "serve", "step": 1, "version": "v2",
                "role": "canary", "latency_s": 0.01, "error": False,
                "emb_finite": True, "emb_norm": 1.0,
                "wall_time": time.time()}
        base.update(kw)
        return base

    def test_nonfinite_trips_canary_only(self):
        s = NonFiniteEmbeddingSentinel()
        assert s.check(self._rec(emb_finite=False))
        assert s.check(self._rec(emb_finite=False, role="default")) is None
        assert s.check(self._rec()) is None

    def test_norm_band_needs_incumbent_ema(self):
        s = EmbeddingNormBandSentinel(factor=2.0, warmup=3)
        # no incumbent samples yet: the band can't fire
        assert s.check(self._rec(emb_norm=100.0)) is None
        for _ in range(5):
            assert s.check(self._rec(role="default", emb_norm=1.0)) is None
        assert s.check(self._rec(emb_norm=100.0))
        assert s.check(self._rec(emb_norm=1.1)) is None

    def test_error_rate_needs_min_count(self):
        s = ServeErrorRateSentinel(max_rate=0.5, window=10, min_count=3)
        assert s.check(self._rec(error=True)) is None  # 1/1 but count < 3
        assert s.check(self._rec(error=True)) is None
        assert s.check(self._rec(error=True))  # 3/3

    def test_latency_band_warms_up(self):
        s = ServeLatencyBandSentinel(factor=3.0, window=8, min_samples=4)
        for _ in range(10):
            s.check(self._rec(role="default", latency_s=0.01))
        for _ in range(3):
            assert s.check(self._rec(latency_s=1.0)) is None  # warming
        assert s.check(self._rec(latency_s=1.0))


class TestRolloutManager:
    def test_serve_falls_back_on_canary_error(self):
        mgr = RolloutManager(SmokeEngine(), version="v1")
        bad = SmokeEngine()
        inj = FaultInjector(flap=[(1, "down"), (100000, "up")])
        bad.embed_issues = inj.wrap(bad.embed_issues)
        mgr.start_canary("v2", bad, pct=100.0)
        emb, served = mgr.serve("t", "b", _embed_fn)
        assert served == "v1" and np.isfinite(emb).all()
        assert mgr.serve_counts[("v2", "error")] == 1

    def test_incumbent_error_still_raises(self):
        eng = SmokeEngine()
        inj = FaultInjector(flap=[(1, "down"), (100000, "up")])
        eng.embed_issues = inj.wrap(eng.embed_issues)
        mgr = RolloutManager(eng, version="v1")
        with pytest.raises(Exception):
            mgr.serve("t", "b", _embed_fn)

    def test_abort_canary_idempotent_and_atomic(self):
        mgr = RolloutManager(SmokeEngine(), version="v1")
        mgr.start_canary("v2", SmokeEngine(), pct=50.0)
        assert mgr.abort_canary("test") == "v2"
        assert mgr.canary_pct == 0.0 and mgr.canary_version is None
        assert "v2" not in mgr.engines
        assert mgr.abort_canary("again") is None  # no raise

    def test_promote_swaps_default(self):
        mgr = RolloutManager(SmokeEngine(), version="v1")
        mgr.start_canary("v2", SmokeEngine(), pct=10.0)
        assert mgr.promote() == "v2"
        assert mgr.default_version == "v2" and mgr.canary_version is None
        assert "v1" not in mgr.engines
        _, served = mgr.serve("t", "b", _embed_fn)
        assert served == "v2"

    def test_promote_notifies_swap_listeners(self):
        """Code-review regression: owners of direct default-engine
        references (server, batcher) must be rebound on promote, or the
        popped incumbent stays strongly referenced forever."""
        mgr = RolloutManager(SmokeEngine(), version="v1")
        new = SmokeEngine()
        swaps = []
        mgr.on_swap(lambda v, e: swaps.append((v, e)))
        mgr.on_swap(lambda v, e: 1 / 0)  # guarded: must not abort the swap
        mgr.start_canary("v2", new, pct=10.0)
        assert mgr.promote() == "v2"
        assert swaps == [("v2", new)]
        assert mgr.default_version == "v2"  # failing listener ignored

    def test_start_canary_resets_sentinels_under_check_lock(self):
        """Code-review regression: resetting a sentinel's window while a
        handler thread iterates it in check() raises inside the bank's
        guard and silently skips the check — the reset must hold the
        same lock check() does."""

        class LockProbe(ServeErrorRateSentinel):
            held = None

            def reset(self):
                LockProbe.held = mgr.monitor._check_lock.locked()
                super().reset()

        mgr = RolloutManager(SmokeEngine(), version="v1",
                             sentinels=[LockProbe()])
        mgr.start_canary("v2", SmokeEngine(), pct=10.0)
        assert LockProbe.held is True

    def test_new_canary_does_not_inherit_previous_state(self):
        """Code-review regression: candidate B must not be judged on
        candidate A's error window, and a re-canaried version must not
        look promote-ready on its OLD clean-request count."""
        mgr = RolloutManager(
            SmokeEngine(), version="v1",
            sentinels=[ServeErrorRateSentinel(max_rate=0.5, window=10,
                                              min_count=3)])
        bad_a = SmokeEngine()
        inj = FaultInjector(flap=[(2, "down"), (100000, "up")])
        bad_a.embed_issues = inj.wrap(bad_a.embed_issues)
        mgr.start_canary("vA", bad_a, 100.0)
        for i in range(2):  # 2 errors: below min_count, no trip yet
            mgr.serve(f"a{i}", "b", _embed_fn)
        assert mgr.monitor.trips_total == 0
        mgr.abort_canary("operator")

        bad_b = SmokeEngine()
        inj_b = FaultInjector(flap=[(1, "down"), (100000, "up")])
        bad_b.embed_issues = inj_b.wrap(bad_b.embed_issues)
        mgr.start_canary("vB", bad_b, 100.0)
        # B's FIRST error would be the 3rd in a polluted window — with
        # the reset it is 1/1 and must not trip
        mgr.serve("b0", "b", _embed_fn)
        assert mgr.monitor.trips_total == 0
        for i in range(3):
            mgr.serve(f"b{i + 1}", "b", _embed_fn)
        assert mgr.serve_counts[("vB", "ok")] == 3
        mgr.abort_canary("operator")
        # re-canary the SAME version: clean count starts from zero
        mgr.start_canary("vB", SmokeEngine(), 100.0)
        assert mgr.serve_counts.get(("vB", "ok"), 0) == 0

    def test_shadow_replay_parity_and_gates(self):
        mgr = RolloutManager(SmokeEngine(), version="v1")
        for i in range(12):
            mgr.serve(f"t{i}", f"b{i}", _embed_fn)
        good = mgr.shadow_replay(SmokeEngine())
        assert good.passed and good.drift_max_abs == 0.0 \
            and good.cosine_min == pytest.approx(1.0)

        class Skewed(SmokeEngine):
            def embed_issues(self, issues, **kw):
                return -super().embed_issues(issues, **kw)  # anti-parallel

        bad = mgr.shadow_replay(Skewed())
        assert not bad.passed and any("cosine" in r for r in bad.reasons)

    def test_shadow_replay_rejects_nonfinite(self):
        mgr = RolloutManager(SmokeEngine(), version="v1")
        mgr.serve("t", "b", _embed_fn)

        class NaNEngine(SmokeEngine):
            def embed_issues(self, issues, **kw):
                return np.full_like(super().embed_issues(issues, **kw),
                                    np.nan)

        rep = mgr.shadow_replay(NaNEngine())
        assert not rep.passed and rep.nonfinite_rows == 1

    def test_shadow_replay_requires_recorded_traffic(self):
        mgr = RolloutManager(SmokeEngine(), version="v1")
        rep = mgr.shadow_replay(SmokeEngine(),
                                gates=ShadowGates(min_requests=5))
        assert not rep.passed and "recorded requests" in rep.reasons[0]

    def test_deadline_exceeded_is_not_canary_error(self):
        """Code-review regression: a client whose budget expired says
        nothing about engine health — no error record, no incumbent
        fallback burn, the exception propagates."""
        from code_intelligence_tpu.utils.resilience import DeadlineExceeded

        incumbent = SmokeEngine()
        mgr = RolloutManager(incumbent, version="v1")
        mgr.start_canary("v2", SmokeEngine(), pct=100.0)

        def expired(engine, title, body):
            raise DeadlineExceeded("budget spent in queue")

        with pytest.raises(DeadlineExceeded):
            mgr.serve("t", "b", expired)
        assert mgr.serve_counts.get(("v2", "error"), 0) == 0
        assert incumbent.calls == 0  # no futile fallback embed

    def test_debug_state_is_strict_json_after_empty_ring_shadow(self):
        """Code-review regression: a rejected empty-ring ShadowReport
        carries NaN fields — /debug/promotion must still be strict JSON."""
        mgr = RolloutManager(SmokeEngine(), version="v1")
        rep = mgr.shadow_replay(SmokeEngine())  # empty ring -> NaN drift
        assert not rep.passed
        body = json.dumps({"rollout": mgr.debug_state()})
        assert "NaN" not in body and "Infinity" not in body
        json.loads(body)  # parseable by a strict consumer

    def test_debug_state_reconstructs_history(self):
        mgr = RolloutManager(SmokeEngine(), version="v1")
        mgr.serve("t", "b", _embed_fn)
        mgr.start_canary("v2", SmokeEngine(), pct=10.0)
        mgr.abort_canary("test trip")
        st = mgr.debug_state()
        events = [e["event"] for e in st["history"]]
        assert events == ["init", "canary_started", "canary_aborted"]
        assert st["canary_pct"] == 0.0
        assert st["serve_counts"]["v1/ok"] == 1


class TestPromotionController:
    def test_reject_on_metric_band(self, tmp_path):
        reg = _make_registry(tmp_path, versions=("v1",), auc=0.95)
        art = tmp_path / "art"
        reg.register("org/m", art, version="v2",
                     metrics={"weighted_auc": 0.5})  # regressed candidate
        mgr = RolloutManager(SmokeEngine(), version="v1")
        for i in range(4):
            mgr.serve(f"t{i}", "b", _embed_fn)
        ctrl = _make_ctrl(tmp_path, reg, mgr,
                          metric_bands={"weighted_auc": 0.05})
        rep = ctrl.begin("v2", SmokeEngine())
        assert ctrl.state.phase == "rejected"
        assert rep.passed  # embedding gates fine; the METRIC band failed
        assert reg.get_version("org/m", "v2").status == "rejected"
        assert mgr.canary_version is None  # never saw live traffic

    def test_begin_refuses_second_concurrent_promotion(self, tmp_path):
        reg = _make_registry(tmp_path, versions=("v1", "v2", "v3"))
        mgr = RolloutManager(SmokeEngine(), version="v1")
        mgr.serve("t", "b", _embed_fn)
        ctrl = _make_ctrl(tmp_path, reg, mgr)
        ctrl.begin("v2", SmokeEngine())
        assert ctrl.state.phase == "canary"
        with pytest.raises(PromotionError, match="still"):
            ctrl.begin("v3", SmokeEngine())

    def test_promote_requires_clean_canary_requests(self, tmp_path):
        reg = _make_registry(tmp_path)
        mgr = RolloutManager(SmokeEngine(), version="v1")
        mgr.serve("t", "b", _embed_fn)
        ctrl = _make_ctrl(tmp_path, reg, mgr, min_canary_requests=5,
                          canary_pct=100.0)
        ctrl.begin("v2", SmokeEngine())
        with pytest.raises(PromotionError, match="clean"):
            ctrl.promote()
        for i in range(5):
            mgr.serve(f"x{i}", "b", _embed_fn)
        ctrl.promote()
        assert ctrl.state.phase == "promoted"
        assert mgr.default_version == "v2"
        assert reg.get_version("org/m", "v2").status == "promoted"
        from code_intelligence_tpu.registry.modelsync import (
            read_deployed_version)

        assert read_deployed_version(tmp_path / "deployed.yaml") == "v2"

    def test_rollback_stamps_registry_and_opens_cooldown(self, tmp_path):
        reg = _make_registry(tmp_path)
        mgr = RolloutManager(SmokeEngine(), version="v1")
        mgr.serve("t", "b", _embed_fn)
        ctrl = _make_ctrl(tmp_path, reg, mgr, cooldown_s=3600.0)
        ctrl.begin("v2", SmokeEngine())
        ctrl.rollback("manual: test")
        assert ctrl.state.phase == "rolled_back"
        mv = reg.get_version("org/m", "v2")
        assert mv.status == "rolled_back"
        assert mv.meta["status_reason"] == "manual: test"
        assert float(mv.meta["cooldown_until"]) > time.time()
        ok, why = ctrl.eligible("v2")
        assert not ok and "cool-down" in why
        ctrl.rollback("second trip")  # idempotent
        assert ctrl.state.trip_reason == "manual: test"

    def test_registry_cooldown_survives_new_controller(self, tmp_path):
        """A fresh controller (empty in-memory cooldown) must still
        refuse a candidate whose REGISTRY meta carries the window."""
        reg = _make_registry(tmp_path)
        mgr = RolloutManager(SmokeEngine(), version="v1")
        mgr.serve("t", "b", _embed_fn)
        ctrl = _make_ctrl(tmp_path, reg, mgr)
        ctrl.begin("v2", SmokeEngine())
        assert ctrl.state.phase == "canary", ctrl.state.history[-1]
        ctrl.rollback("trip")
        meta = reg.get_version("org/m", "v2").meta
        assert meta["status"] == "rolled_back" and meta["cooldown_until"]
        mgr2 = RolloutManager(SmokeEngine(), version="v1")
        ctrl2 = PromotionController(reg, mgr2, tmp_path / "promo2.json",
                                    "org/m")
        ok, why = ctrl2.eligible("v2")
        assert not ok and "cool-down" in why


class TestChaosPin:
    """The acceptance pin: seeded NaN candidate -> automatic rollback,
    bounded detection, zero client failures, audited registry + history."""

    @pytest.mark.chaos
    def test_bad_candidate_rolls_back_with_zero_client_failures(self):
        out = run_promotion_smoke(n_requests=40, nan_at=5)
        assert out["ok"], out
        assert out["rolled_back"] is True
        assert out["client_failures"] == 0
        # detection is bounded: the NaN lands at canary request index 5
        # and the sentinel trips on that very request
        assert out["rollback_within_requests"] <= 6
        assert out["registry_status"] == "rolled_back"
        assert "nonfinite_embedding" in out["trip_reason"]
        assert out["cooldown_blocks_repromote"] is True
        # reconstructable: the rollout history carries the whole arc
        assert out["history_events"][-3:] == [
            "shadow_replayed", "canary_started", "canary_aborted"]

    @pytest.mark.chaos
    def test_registry_write_failure_mid_rollback_still_reverts_split(
            self, tmp_path, monkeypatch):
        reg = _make_registry(tmp_path)
        mgr = RolloutManager(SmokeEngine(), version="v1")
        mgr.serve("t", "b", _embed_fn)
        ctrl = _make_ctrl(tmp_path, reg, mgr)
        ctrl.begin("v2", SmokeEngine())
        monkeypatch.setattr(
            reg, "set_version_status",
            lambda *a, **k: (_ for _ in ()).throw(OSError("store down")))
        ctrl.rollback("trip during registry outage")
        # the split is reverted and the STATE FILE says rolled_back even
        # though the registry stamp failed — recovery re-stamps later
        assert mgr.canary_version is None
        assert PromotionState.load(ctrl.state_path).phase == "rolled_back"


class TestRestartRecovery:
    """Kill-at-any-phase chaos: a promotion interrupted at every
    state-machine transition resumes or safely aborts from persisted
    state on controller restart, with the incumbent still serving."""

    def _setup(self, tmp_path):
        reg = _make_registry(tmp_path)
        mgr = RolloutManager(SmokeEngine(), version="v1")
        for i in range(4):
            mgr.serve(f"t{i}", "b", _embed_fn)
        # 100% split so the promoting_* scenarios can accumulate clean
        # canary requests deterministically
        ctrl = _make_ctrl(tmp_path, reg, mgr, canary_pct=100.0)
        return reg, mgr, ctrl

    def _restart(self, tmp_path, reg):
        """A fresh process: new rollout (incumbent only — the old split
        died with the process), new controller reading persisted state."""
        mgr2 = RolloutManager(SmokeEngine(), version="v1")
        ctrl2 = _make_ctrl(tmp_path, reg, mgr2)
        phase_before = ctrl2.state.phase if ctrl2.state else None
        ctrl2.recover()
        return mgr2, ctrl2, phase_before

    def _kill_at(self, tmp_path, phase, reg, mgr, ctrl):
        """Drive the promotion to `phase` and 'kill' the process there
        (abandon the objects with the state file as the only survivor)."""
        if phase == "shadow":
            # die inside shadow replay: the transition to shadow is
            # persisted, the replay result never lands
            def die(*a, **k):
                raise KeyboardInterrupt("killed mid-shadow")

            orig = mgr.shadow_replay
            mgr.shadow_replay = die
            with pytest.raises(KeyboardInterrupt):
                ctrl.begin("v2", SmokeEngine())
            mgr.shadow_replay = orig
        elif phase == "canary":
            ctrl.begin("v2", SmokeEngine())
        elif phase == "promoting_before_deploy":
            ctrl.begin("v2", SmokeEngine())
            for i in range(5):
                mgr.serve(f"x{i}", "b", _embed_fn)
            orig_record = ctrl._record_deployed
            ctrl._record_deployed = lambda v: (_ for _ in ()).throw(
                KeyboardInterrupt("killed before deploy record"))
            with pytest.raises(KeyboardInterrupt):
                ctrl.promote()
            ctrl._record_deployed = orig_record
        elif phase == "promoting_after_deploy":
            ctrl.begin("v2", SmokeEngine())
            for i in range(5):
                mgr.serve(f"x{i}", "b", _embed_fn)
            orig_stamp = reg.set_version_status
            reg.set_version_status = lambda *a, **k: (_ for _ in ()).throw(
                KeyboardInterrupt("killed after deploy record"))
            with pytest.raises(KeyboardInterrupt):
                ctrl.promote()
            reg.set_version_status = orig_stamp
        elif phase == "rolled_back":
            ctrl.begin("v2", SmokeEngine())
            ctrl.rollback("sentinel trip before the kill")
        else:  # pragma: no cover - scenario typo guard
            raise AssertionError(phase)

    PHASES = ("shadow", "canary", "promoting_before_deploy",
              "promoting_after_deploy", "rolled_back")

    @pytest.mark.chaos
    @pytest.mark.parametrize("phase", PHASES)
    def test_recovers_from_kill_at(self, tmp_path, phase):
        reg, mgr, ctrl = self._setup(tmp_path)
        self._kill_at(tmp_path, phase, reg, mgr, ctrl)
        mgr2, ctrl2, persisted = self._restart(tmp_path, reg)

        # universal invariants: a consistent terminal phase, no stray
        # canary split, and the serving path still works
        assert ctrl2.state.phase in ("promoted", "aborted", "rolled_back")
        assert mgr2.canary_version is None
        emb, served = mgr2.serve("after restart", "body", _embed_fn)
        assert np.isfinite(emb).all()

        v2 = reg.get_version("org/m", "v2")
        if phase == "promoting_after_deploy":
            # deployed record already named the candidate: recovery
            # completes the promotion rather than reverting it
            assert persisted == "promoting"
            assert ctrl2.state.phase == "promoted"
            assert v2.status == "promoted"
        elif phase == "rolled_back":
            assert ctrl2.state.phase == "rolled_back"
            ok, why = ctrl2.eligible("v2")
            assert not ok  # the cool-down survived the restart
        else:
            assert ctrl2.state.phase == "aborted"
            assert v2.status == "aborted"
            from code_intelligence_tpu.registry.modelsync import (
                read_deployed_version)

            assert read_deployed_version(tmp_path / "deployed.yaml") != "v2"

    @pytest.mark.chaos
    def test_random_phase_kill_loop(self, tmp_path):
        """Seeded random phase selection over fresh workdirs — the
        any-transition form of the scenario matrix above."""
        import random

        rng = random.Random(1234)
        for i in range(4):
            phase = rng.choice(self.PHASES)
            sub = tmp_path / f"run{i}"
            sub.mkdir()
            reg, mgr, ctrl = self._setup(sub)
            self._kill_at(sub, phase, reg, mgr, ctrl)
            mgr2, ctrl2, _ = self._restart(sub, reg)
            assert ctrl2.state.phase in ("promoted", "aborted",
                                         "rolled_back"), phase
            emb, _ = mgr2.serve("still serving", "body", _embed_fn)
            assert np.isfinite(emb).all(), phase


class TestServerIntegration:
    """Drain + routing + debug surface on the real HTTP server, with a
    device-free engine (the rollout/drain machinery is jax-free)."""

    def _server(self, delay_s=0.0, **kw):
        from code_intelligence_tpu.serving.server import make_server

        eng = SmokeEngine(delay_s=delay_s)
        mgr = RolloutManager(eng, version="v1")
        srv = make_server(eng, host="127.0.0.1", port=0, scheduler="groups",
                          rollout=mgr, **kw)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        return srv, mgr, srv.server_address[1]

    def _post(self, port, title="t", body="b", timeout=10):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/text",
            data=json.dumps({"title": title, "body": body}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.read(), dict(resp.headers)

    def test_model_version_stamped_on_response(self):
        srv, mgr, port = self._server()
        try:
            raw, headers = self._post(port)
            assert headers.get("X-Model-Version") == "v1"
            assert len(np.frombuffer(raw, "<f4")) == 8
        finally:
            srv.shutdown()
            srv.server_close()

    def test_promote_rebinds_server_and_batcher_engine(self):
        """Code-review regression: after a hot-swap the server's direct
        engine reference (non-routed embed path, drain accounting) and
        the batcher's fallback engine must point at the new default."""
        import types

        srv, mgr, port = self._server()
        try:
            old = srv.engine
            srv.batcher = types.SimpleNamespace(engine=old)
            new = SmokeEngine()
            mgr.start_canary("v2", new, pct=10.0)
            mgr.promote()
            assert srv.engine is new
            assert srv.batcher.engine is new
            srv.batcher = None  # fake has no embed path
            self._post(port)  # still serves after the rebind
        finally:
            srv.batcher = None
            srv.shutdown()
            srv.server_close()

    def test_debug_promotion_endpoint(self):
        srv, mgr, port = self._server()
        try:
            self._post(port)
            mgr.start_canary("v2", SmokeEngine(), pct=25.0)
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/promotion",
                timeout=10).read()
            state = json.loads(body)["rollout"]
            assert state["canary_version"] == "v2"
            assert state["canary_pct"] == 25.0
            assert state["ring"]["recorded_total"] >= 1
            assert [e["event"] for e in state["history"]][:1] == ["init"]
        finally:
            srv.shutdown()
            srv.server_close()

    def test_drain_finishes_inflight_then_sheds_503(self):
        srv, mgr, port = self._server(delay_s=0.4)
        try:
            results = {}

            def slow_client():
                try:
                    raw, _ = self._post(port, "slow", "request")
                    results["slow"] = len(raw)
                except Exception as e:  # pragma: no cover - the failure arm
                    results["slow"] = e

            t = threading.Thread(target=slow_client)
            t.start()
            # wait until the request is genuinely ADMITTED (a fixed sleep
            # races thread startup on a loaded host), then drain around it
            deadline = time.time() + 5.0
            while srv._pending == 0 and time.time() < deadline:
                time.sleep(0.01)
            assert srv._pending > 0, "slow request never got admitted"
            assert srv.drain(timeout_s=10.0) is True
            t.join(timeout=5)
            # the in-flight request completed — zero dropped
            assert results["slow"] == 8 * 4
            # new work is refused with 503 (balancer: go elsewhere)
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._post(port)
            assert ei.value.code == 503
            # and readiness flipped
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/readyz", timeout=10)
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["status"] == "draining"
        finally:
            srv.shutdown()
            srv.server_close()

    def test_canary_routing_over_http_and_metrics(self):
        srv, mgr, port = self._server()
        try:
            mgr.start_canary("v2", SmokeEngine(), pct=100.0)
            _, headers = self._post(port, "x", "y")
            assert headers.get("X-Model-Version") == "v2"
            metrics = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ).read().decode()
            assert 'canary_requests_total{outcome="ok",role="canary"' \
                   ',version="v2"}' in metrics
            assert "canary_pct 100.0" in metrics
        finally:
            srv.shutdown()
            srv.server_close()


class TestRunbookCIPromoGate:
    def test_check_promo_composes(self):
        from code_intelligence_tpu.utils import runbook_ci

        report = runbook_ci.check_promo()
        assert report["ok"] is True
        assert report["rolled_back"] is True and report["promoted"] is True

    def test_cli_flag_exits_zero(self, capsys):
        from code_intelligence_tpu.utils import runbook_ci

        rc = runbook_ci.main(["--runbook", "docs/RUNBOOK.md",
                              "--check_promo"])
        out = capsys.readouterr().out.strip().splitlines()[-1]
        verdict = json.loads(out)
        assert rc == 0 and verdict["promo_ok"] is True


class TestHotSwapPin:
    """Acceptance pin with REAL engines (~7s, tiny smoke encoder):
    promoting under sustained load drops zero in-flight requests and
    causes no slot-step recompile beyond the candidate's own warmup
    (PR 5 recompile_guard)."""

    def test_hot_swap_under_load_zero_drops_zero_recompiles(self):
        from smoke_engine import make_smoke_engine

        from code_intelligence_tpu.analysis import runtime as audit
        from code_intelligence_tpu.serving.server import make_server

        incumbent = make_smoke_engine(batch_size=4)
        candidate = make_smoke_engine(batch_size=4)
        incumbent.version, candidate.version = "v1", "v2"
        # value-shaped sentinel only: the wall-clock latency band could
        # spuriously roll the canary back on a CI host stall, and this
        # pin is about drops/recompiles, not latency policy
        mgr = RolloutManager(incumbent, version="v1",
                             sentinels=[NonFiniteEmbeddingSentinel()])
        srv = make_server(incumbent, host="127.0.0.1", port=0,
                          scheduler="slots", rollout=mgr)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        port = srv.server_address[1]

        def post(i):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/text",
                data=json.dumps({"title": f"t{i}",
                                 "body": "word " * (3 + i % 17)}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                vec = np.frombuffer(resp.read(), "<f4")
                return vec, resp.headers.get("X-Model-Version")

        try:
            # warm BOTH engines' slot steps: the candidate pays its
            # compile here (its "own warmup"), never on live traffic
            post(0)
            candidate.warmup(scheduler="slots")

            errors, versions = [], []
            lock = threading.Lock()
            stop = threading.Event()

            def client(cid):
                k = 0
                while not stop.is_set() or k < 4:
                    try:
                        vec, v = post(cid * 100 + k)
                        with lock:
                            versions.append(v)
                        assert np.isfinite(vec).all()
                    except Exception as e:
                        with lock:
                            errors.append(repr(e)[:200])
                    k += 1
                    if k >= 5000:  # runaway bound only: a fast host
                        break      # must still be posting at the swap

            with audit.recompile_guard(fn="slots.step", budget=0):
                threads = [threading.Thread(target=client, args=(c,))
                           for c in range(3)]
                for t in threads:
                    t.start()
                time.sleep(0.3)  # sustained load before the swap
                mgr.start_canary("v2", candidate, pct=50.0)
                time.sleep(0.3)
                mgr.promote("v2")
                time.sleep(0.3)
                stop.set()
                for t in threads:
                    t.join(timeout=30)

            assert errors == []  # zero dropped/failed in-flight requests
            assert "v1" in versions and "v2" in versions
            # after the swap every response comes from the candidate
            _, v_final = post(9999)
            assert v_final == "v2"
        finally:
            srv.shutdown()
            srv.server_close()
