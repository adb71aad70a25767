"""Device-memory observatory (utils/memtrack.py, RUNBOOK §31).

The pins: the attribution table sums EXACTLY (owner rows +
``unattributed`` == total live bytes — the SLO stage table's honesty
contract, applied to bytes); ``memory_guard`` passes a warmed steady
state and fires on a planted leak, on both schedulers and with
per-device attribution under a mesh (conftest forces 8 CPU devices);
the ``device_memory_growth`` sentinel latches once per growth episode
and re-arms on release; a canary's double-residency is visible in
``hbm_version_bytes`` and the retired version's bytes are OBSERVED at
zero after promote/abort (the PR 6 hot-swap pin never checked memory);
the ragged page-occupancy gauges reconcile against the ledger's
paged-pool row; the embed cache's budgeted byte counter matches actual
entry nbytes; and ``perfwatch diff --memory`` gates under the §22
honesty rules (cross-kind refusal included).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code_intelligence_tpu.analysis import runtime as audit
from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.inference.slots import (
    RaggedSlotScheduler, SlotScheduler)
from code_intelligence_tpu.models import (
    AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states)
from code_intelligence_tpu.text import SPECIALS, Vocab
from code_intelligence_tpu.utils.memtrack import (
    DEFAULT_DEVICE_BUDGET_BYTES, UNATTRIBUTED, DeviceMemoryGrowthSentinel,
    DeviceMemoryLedger, debug_memory_response, live_buffer_totals)
from code_intelligence_tpu.utils.metrics import Registry


def make_engine(batch_size=4, buckets=(8, 16)):
    cfg = AWDLSTMConfig(vocab_size=200, emb_sz=8, n_hid=12, n_layers=2)
    enc = AWDLSTMEncoder(cfg)
    params = enc.init(
        {"params": jax.random.PRNGKey(0)},
        np.zeros((1, 4), np.int32), init_lstm_states(cfg, 1)
    )["params"]
    vocab = Vocab(SPECIALS + [f"w{i}" for i in range(150)])
    return InferenceEngine(params, cfg, vocab, buckets=buckets,
                           batch_size=batch_size)


@pytest.fixture(scope="module")
def engine():
    return make_engine()


def mixed_seqs(n=9, seed=0):
    rng = np.random.RandomState(seed)
    seqs = [rng.randint(20, 150, rng.randint(1, 40)).astype(np.int32)
            for _ in range(n)]
    seqs.append(np.arange(30, 60, dtype=np.int32))
    return seqs


def gval(reg, name, **labels):
    return reg._values.get((name, tuple(sorted(labels.items()))))


class TestLedgerHonesty:
    def test_attribution_sums_exactly(self, engine):
        ledger = DeviceMemoryLedger()
        ledger.register("engine.params",
                        lambda: getattr(engine, "_enc_params", None))
        snap = ledger.snapshot()
        assert snap["sums_exactly"] is True
        attributed = sum(r["bytes"] for r in snap["owners"].values())
        assert attributed + snap["unattributed"]["bytes"] \
            == snap["total_bytes"]
        assert snap["owners"]["engine.params"]["bytes"] > 0
        # the same enumeration grouped by device sums too
        dev_total = sum(d["total_bytes"] for d in snap["devices"].values())
        assert dev_total == snap["total_bytes"]
        for drow in snap["devices"].values():
            assert sum(drow["owners"].values()) == drow["total_bytes"]
        # ledger total and the guard's shared measurement agree
        assert live_buffer_totals()[0] == ledger.snapshot()["total_bytes"]

    def test_register_unregister_and_duplicates(self, engine):
        ledger = DeviceMemoryLedger()
        ledger.register("engine.params", lambda: engine._enc_params)
        with pytest.raises(ValueError):
            ledger.register("engine.params", lambda: None)
        ledger.register("engine.params", lambda: engine._enc_params,
                        replace=True)
        assert ledger.unregister("engine.params") is True
        assert ledger.unregister("engine.params") is False
        snap = ledger.snapshot()
        assert "engine.params" not in snap["owners"]
        assert snap["sums_exactly"] is True  # all unattributed, still sums

    def test_failed_provider_attributes_nothing_but_sums(self):
        ledger = DeviceMemoryLedger()
        ledger.register("broken", lambda: 1 / 0)
        snap = ledger.snapshot()
        assert snap["sums_exactly"] is True
        assert snap["owners"]["broken"]["bytes"] == 0
        assert "broken" in snap["provider_errors"]
        assert "ZeroDivisionError" in snap["provider_errors"]["broken"]

    def test_shared_buffer_first_registration_wins(self):
        shared = jnp.ones((32, 32), jnp.float32)
        ledger = DeviceMemoryLedger()
        ledger.register("first", lambda: shared)
        ledger.register("second", lambda: shared)
        snap = ledger.snapshot()
        assert snap["owners"]["first"]["bytes"] == shared.nbytes
        assert snap["owners"]["second"]["bytes"] == 0  # counted ONCE
        assert snap["sums_exactly"] is True

    def test_watermarks_survive_release(self):
        held = [jnp.ones((64, 64), jnp.float32)]
        ledger = DeviceMemoryLedger()
        ledger.register("held", lambda: held)
        peak = ledger.snapshot()["owners"]["held"]["bytes"]
        assert peak == 64 * 64 * 4
        held.clear()
        snap = ledger.snapshot()
        assert snap["owners"]["held"]["bytes"] == 0
        assert ledger.watermarks()["held"] == peak
        assert ledger.watermarks()["_total"] >= peak

    def test_gauges_export_on_snapshot(self, engine):
        reg = Registry()
        ledger = DeviceMemoryLedger(registry=reg)
        ledger.register("engine.params", lambda: engine._enc_params)
        snap = ledger.snapshot()
        assert gval(reg, "hbm_total_bytes") == snap["total_bytes"]
        assert gval(reg, "hbm_unattributed_bytes") \
            == snap["unattributed"]["bytes"]
        assert gval(reg, "hbm_owner_bytes", owner="engine.params") \
            == snap["owners"]["engine.params"]["bytes"]
        assert gval(reg, "hbm_watermark_bytes") == snap["watermark_bytes"]


class TestMemoryGuard:
    def test_clean_steady_state_both_schedulers(self, engine):
        seqs = mixed_seqs()
        for scheduler in ("slots", "ragged"):
            # warm the step shapes AND jax's per-shape constant caches
            engine.embed_ids_batch(seqs, scheduler=scheduler)
            engine.embed_ids_batch(seqs, scheduler=scheduler)
            with audit.memory_guard(budget_bytes=0):
                engine.embed_ids_batch(seqs, scheduler=scheduler)

    def test_planted_leak_fires_and_names_owner(self, engine):
        seqs = mixed_seqs()
        engine.embed_ids_batch(seqs, scheduler="slots")
        engine.embed_ids_batch(seqs, scheduler="slots")
        ledger = DeviceMemoryLedger()
        ledger.register("engine.params", lambda: engine._enc_params)
        leak = []
        with pytest.raises(audit.MemoryGrowthExceeded) as ei:
            with audit.memory_guard(budget_bytes=0, ledger=ledger):
                engine.embed_ids_batch(seqs, scheduler="slots")
                leak.append(jax.device_put(
                    np.ones((128, 128), np.float32)))
        msg = str(ei.value)
        assert "retained buffer" in msg
        assert UNATTRIBUTED in msg  # nobody claimed the leak
        del leak

    def test_budget_allows_declared_growth(self):
        held = []
        with audit.memory_guard(budget_bytes=1 << 20, budget_buffers=4):
            held.append(jax.device_put(np.ones((16, 16), np.float32)))
        del held

    def test_mesh_per_device_attribution(self):
        # conftest forces 8 virtual CPU devices for the whole session
        from code_intelligence_tpu.parallel.serve_shard import (
            build_serve_mesh)

        assert len(jax.devices()) >= 2
        mesh = build_serve_mesh("data=2,model=1", devices=jax.devices()[:2])
        eng = make_engine()
        sched = SlotScheduler(eng, mesh=mesh)
        seqs = mixed_seqs(n=5, seed=2)
        sched.embed_ids(seqs)
        sched.embed_ids(seqs)  # warm before the guarded pass
        ledger = DeviceMemoryLedger()
        sched.register_memory_owners(ledger, prefix="slots")
        with audit.memory_guard(budget_bytes=0, ledger=ledger):
            sched.embed_ids(seqs)
        snap = ledger.snapshot()
        assert snap["sums_exactly"] is True
        # the sharded params are a second resident copy the single-chip
        # path doesn't have — and both mesh devices carry attribution
        assert snap["owners"]["slots.params_sharded"]["bytes"] > 0
        assert snap["owners"]["slots.state_arenas"]["bytes"] > 0
        attributed_devices = [
            dev for dev, drow in snap["devices"].items()
            if any(o != UNATTRIBUTED and b > 0
                   for o, b in drow["owners"].items())]
        assert len(attributed_devices) >= 2
        # host-tier staging rides the snapshot but not device totals
        assert snap["host"]["slots.staging"] >= 0


class TestSentinel:
    def _rec(self, growth_bytes, buffers=0, owners=None):
        return {"kind": "memory", "step": 0, "wall_time": 0.0,
                "total_bytes": 1000 + growth_bytes, "total_buffers": 10,
                "baseline_bytes": 1000, "baseline_buffers": 10,
                "growth_bytes": growth_bytes, "growth_buffers": buffers,
                "unattributed_growth_bytes": growth_bytes,
                "grown_owners": owners or {}}

    def test_latch_once_then_rearm_on_release(self):
        s = DeviceMemoryGrowthSentinel()
        reason = s.check(self._rec(5 << 20, owners={"slots.pool": 5 << 20}))
        assert reason is not None and s.latched
        assert "slots.pool" in reason
        # latched: the SAME sustained episode is one alert, not one per scrape
        assert s.check(self._rec(6 << 20)) is None
        assert s.latched
        # release re-arms
        assert s.check(self._rec(0)) is None
        assert not s.latched
        reason2 = s.check(self._rec(1, buffers=1))
        assert reason2 is not None and s.latched
        assert UNATTRIBUTED in reason2  # no named owners -> the leak row

    def test_ignores_other_kinds_and_respects_tolerance(self):
        s = DeviceMemoryGrowthSentinel(tolerance_bytes=1 << 20)
        assert s.check({"kind": "serve", "growth_bytes": 1 << 30}) is None
        assert s.check(self._rec(1 << 10)) is None  # under tolerance
        assert not s.latched
        assert s.check(self._rec(2 << 20)) is not None
        s.reset()
        assert not s.latched

    def test_ledger_sentinel_record_roundtrip(self):
        jnp.ones((64, 64), jnp.float32)  # warm jax's per-shape constant
        held = []
        ledger = DeviceMemoryLedger()
        ledger.register("held", lambda: held)
        ledger.set_baseline()
        s = DeviceMemoryGrowthSentinel()
        assert s.check(ledger.sentinel_record(step=1)) is None
        held.append(jnp.ones((64, 64), jnp.float32))
        reason = s.check(ledger.sentinel_record(step=2))
        assert reason is not None and "held" in reason
        held.clear()
        import gc

        gc.collect()  # collectable cycles are garbage, not leaks —
        # the same re-measure discipline memory_guard applies
        assert s.check(ledger.sentinel_record(step=3)) is None
        assert not s.latched  # growth released -> re-armed


class TestCanaryResidency:
    """The hbm_version_bytes satellite: double-residency during a live
    canary, and the retired version's bytes OBSERVED at zero after the
    swap — the memory check the PR 6 hot-swap pin never made."""

    def _mgr(self):
        from code_intelligence_tpu.registry.promotion import SmokeEngine
        from code_intelligence_tpu.serving.rollout import RolloutManager

        reg = Registry()
        eng1 = SmokeEngine()
        eng1._enc_params = {"w": jnp.ones((64, 32), jnp.float32)}
        mgr = RolloutManager(eng1, version="v1", registry=reg)
        ledger = DeviceMemoryLedger()
        mgr.bind_ledger(ledger)
        return mgr, ledger, reg

    def test_double_residency_then_promote_drops_to_zero(self):
        from code_intelligence_tpu.registry.promotion import SmokeEngine

        mgr, ledger, reg = self._mgr()
        vbytes = 64 * 32 * 4
        snap = ledger.snapshot()
        assert snap["owners"]["engine.params.v1"]["bytes"] == vbytes
        eng2 = SmokeEngine()
        eng2._enc_params = {"w": jnp.ones((64, 32), jnp.float32)}
        mgr.start_canary("v2", eng2, 25.0)
        # both versions resident: incumbent + candidate rows AND gauges
        snap = ledger.snapshot()
        assert snap["owners"]["engine.params.v1"]["bytes"] == vbytes
        assert snap["owners"]["engine.params.v2"]["bytes"] == vbytes
        assert gval(reg, "hbm_version_bytes", version="v1") == vbytes
        assert gval(reg, "hbm_version_bytes", version="v2") == vbytes
        mgr.promote()
        # the retired incumbent's row is gone and its gauge reads 0 —
        # re-snapshotted BEFORE unregistering, so the 0 is observed
        assert "engine.params.v1" not in ledger.owners()
        assert gval(reg, "hbm_version_bytes", version="v1") == 0.0
        assert gval(reg, "hbm_version_bytes", version="v2") == vbytes
        snap = ledger.snapshot()
        assert "engine.params.v1" not in snap["owners"]
        assert snap["sums_exactly"] is True

    def test_abort_releases_candidate(self):
        from code_intelligence_tpu.registry.promotion import SmokeEngine

        mgr, ledger, reg = self._mgr()
        eng2 = SmokeEngine()
        eng2._enc_params = {"w": jnp.ones((64, 32), jnp.float32)}
        mgr.start_canary("v2", eng2, 10.0)
        assert ledger.snapshot()["owners"]["engine.params.v2"]["bytes"] > 0
        assert mgr.abort_canary("tests") == "v2"
        assert "engine.params.v2" not in ledger.owners()
        assert gval(reg, "hbm_version_bytes", version="v2") == 0.0
        assert gval(reg, "hbm_version_bytes", version="v1") > 0

    def test_observe_memory_feeds_monitor_and_history(self):
        mgr, ledger, _ = self._mgr()
        ledger.set_baseline()
        assert mgr.observe_memory(step=1) == []
        held = jnp.ones((256, 256), jnp.float32)  # noqa: F841 planted
        trips = mgr.observe_memory(step=2)
        assert [t.sentinel for t in trips] == ["device_memory_growth"]
        events = [h["event"] for h in mgr.history]
        assert "memory_sentinel_tripped" in events


class TestPageGauges:
    """The slots_pages_* satellite, reconciled against the ledger's
    paged-pool row."""

    def test_occupancy_gauges_and_ledger_reconcile(self, engine):
        reg = Registry()
        rs = RaggedSlotScheduler(engine)
        rs.bind_registry(reg)
        ledger = DeviceMemoryLedger()
        rs.register_memory_owners(ledger, prefix="slots")
        B, n_pages = engine.batch_size, rs.n_pages
        # idle: every slot parks one page, the spare half is free
        assert rs.pages_free() == n_pages - B
        assert rs.pages_live() == 0
        assert gval(reg, "slots_pages_free") == n_pages - B
        assert gval(reg, "slots_pages_live") == 0
        rs.embed_ids(mixed_seqs(n=7, seed=4))
        # drained: occupancy is back to idle and the gauges re-exported
        assert rs.pages_live() == 0
        assert gval(reg, "slots_pages_free") == rs.pages_free()
        assert gval(reg, "slots_pages_live") == 0
        assert rs.pages_free() + rs.pages_live() <= n_pages
        # ledger reconciliation: the paged-pool row is the pool arena,
        # and the noted geometry prices a page over pool + state arenas
        snap = ledger.snapshot()
        assert snap["owners"]["slots.paged_pool"]["bytes"] \
            == rs._pool.nbytes
        cap = ledger.capacity_report(snap=snap)
        geo = cap["geometry"]
        assert geo["pages_total"] == n_pages
        assert geo["page_len"] == rs.page_len
        arena_bytes = rs._pool.nbytes + sum(
            int(l.nbytes) for l in rs._h_leaves)
        assert geo["page_bytes"] == arena_bytes // n_pages


class TestEmbedCacheHonesty:
    """The embed-cache byte-honesty satellite: the budgeted counter must
    equal a re-sum of actual entry nbytes, and the cache rides the
    ledger as a host-tier row."""

    def test_budgeted_counter_matches_actual_nbytes(self):
        from code_intelligence_tpu.serving.embed_cache import EmbedCache

        row = np.ones((100,), np.float32)
        cache = EmbedCache(max_bytes=3 * row.nbytes)
        for i in range(3):
            assert cache.put(("v1", "m", f"k{i}"), row) is True
        actual = sum(r.nbytes for r in cache._lru.values())
        assert cache.resident_bytes() == actual == cache._bytes
        # eviction keeps the books honest
        cache.put(("v1", "m", "k3"), row)
        assert cache.evictions == 1
        assert cache.resident_bytes() \
            == sum(r.nbytes for r in cache._lru.values()) == cache._bytes
        assert cache.stats()["resident_bytes"] == cache.resident_bytes()

    def test_cache_is_a_ledger_host_row(self):
        from code_intelligence_tpu.serving.embed_cache import EmbedCache

        reg = Registry()
        cache = EmbedCache(max_bytes=1 << 20, registry=reg)
        cache.put(("v1", "m", "k"), np.ones((64,), np.float32))
        ledger = DeviceMemoryLedger()
        cache.register_memory_owner(ledger)
        snap = ledger.snapshot()
        assert snap["host"]["cache_resident_bytes"] == 256
        # host rows never count against device totals (host RAM != HBM)
        assert snap["sums_exactly"] is True
        # the planner sees it, and stats() refreshes the gauge
        assert ledger.capacity_report(snap=snap)["host"][
            "cache_resident_bytes"] == 256
        cache.stats()
        assert gval(reg, "cache_resident_bytes") == 256


class TestCapacityReport:
    def test_default_vs_caller_budget_and_fit_math(self):
        params = {"w": jnp.ones((128, 16), jnp.float32)}  # 8192B
        ledger = DeviceMemoryLedger()
        ledger.register("engine.params", lambda: params)
        ledger.note_geometry(head_bytes=1024)
        snap = ledger.snapshot()
        cap = ledger.capacity_report(snap=snap)
        assert cap["budget_source"] == "default"
        assert cap["budget_bytes"] == DEFAULT_DEVICE_BUDGET_BYTES
        assert cap["version_bytes"] == 8192  # largest engine.params* row
        used = cap["used_bytes_fullest_device"]
        cap2 = ledger.capacity_report(budget_bytes=used + 3 * 8192 + 1,
                                      snap=snap)
        assert cap2["budget_source"] == "caller"
        assert cap2["versions_fit"] == 3
        assert cap2["heads_fit"] == cap2["headroom_bytes"] // 1024

    def test_budget_comes_from_the_device_where_it_reports_one(
            self, monkeypatch):
        from code_intelligence_tpu.utils import memtrack

        # the CPU backend reports nothing: the 16 GiB planning constant
        assert memtrack.device_budget_bytes() is None
        # an accelerator reports bytes_limit (16,909,336,064 on the v5e)
        monkeypatch.setattr(memtrack, "device_budget_bytes",
                            lambda: 16909336064)
        cap = DeviceMemoryLedger().capacity_report()
        assert cap["budget_source"] == "device"
        assert cap["budget_bytes"] == 16909336064
        # a caller's figure still wins
        cap = DeviceMemoryLedger().capacity_report(budget_bytes=1 << 20)
        assert cap["budget_source"] == "caller"

    def test_debug_memory_response_body(self):
        ledger = DeviceMemoryLedger()
        code, body, ctype = debug_memory_response(ledger, "")
        assert code == 200 and ctype == "application/json"
        out = json.loads(body)
        assert set(out) == {"snapshot", "sentinel", "capacity",
                            "watermarks"}
        assert out["snapshot"]["sums_exactly"] is True
        assert out["capacity"]["budget_source"] == "default"
        code2, body2, _ = debug_memory_response(ledger,
                                                "budget_bytes=12345")
        assert code2 == 200
        assert json.loads(body2)["capacity"]["budget_bytes"] == 12345
        assert json.loads(body2)["capacity"]["budget_source"] == "caller"
        code3, body3, _ = debug_memory_response(None, "")
        assert code3 == 404 and "error" in json.loads(body3)


class TestFleetMemoryRollup:
    """/fleet/memory: per-member /debug/memory pulls with the /fleet/slo
    stale-member degrade rule, plus the fleet capacity aggregate."""

    def test_rollup_aggregates_and_degrades(self):
        import http.server
        import threading
        import types

        from code_intelligence_tpu.serving.fleet.router import (
            fleet_memory_response)

        ledger = DeviceMemoryLedger()
        params = {"w": jnp.ones((32, 16), jnp.float32)}
        ledger.register("engine.params", lambda: params)

        class _H(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                code, body, ctype = debug_memory_response(
                    ledger, self.path.partition("?")[2])
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        httpd = http.server.HTTPServer(("127.0.0.1", 0), _H)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            port = httpd.server_address[1]
            alive = types.SimpleNamespace(
                member_id="m1", base_url=f"http://127.0.0.1:{port}")
            dead = types.SimpleNamespace(
                member_id="m2", base_url="http://127.0.0.1:1")
            srv = types.SimpleNamespace(
                proxy_timeout_s=5.0,
                table=types.SimpleNamespace(
                    ready_members=lambda: [alive, dead]))
            code, body, _ = fleet_memory_response(srv, "budget_bytes=100000")
            assert code == 200
            out = json.loads(body)
            # the dead member degrades to an error entry, never a 5xx
            assert out["members"]["m1"]["ok"] is True
            assert out["members"]["m2"]["ok"] is False
            assert out["fleet"]["members_ok"] == 1
            assert out["fleet"]["members_failed"] == 1
            snap = out["members"]["m1"]["memory"]["snapshot"]
            assert snap["sums_exactly"] is True
            assert out["fleet"]["total_bytes"] == snap["total_bytes"]
            cap = out["members"]["m1"]["memory"]["capacity"]
            assert cap["budget_bytes"] == 100000  # query passthrough
            assert out["fleet"]["min_member_headroom_bytes"] \
                == cap["headroom_bytes"]
        finally:
            httpd.shutdown()
            httpd.server_close()


class TestPerfwatchMemory:
    """perfwatch --memory under the §22 honesty rules: regression names
    the owner, a new owner gates against 0, cross-kind input is refused
    (exit 2), and a clean diff exits 0."""

    def _snap(self, owners, unattributed=0, host=None):
        from code_intelligence_tpu.utils.perfwatch import MEMORY_KIND

        total = sum(owners.values()) + unattributed
        return {"kind": "perfwatch_memory_snapshot", "url": None,
                "latency_kind": MEMORY_KIND, "provenance": "fresh",
                "measured_at": "2026-01-01T00:00:00Z",
                "measured_git": "deadbeef",
                "total_bytes": total, "total_buffers": len(owners),
                "unattributed_bytes": unattributed,
                "owners": dict(owners), "host": dict(host or {}),
                "watermark_bytes": total, "capacity": {}}

    def test_compare_names_grown_owner(self):
        from code_intelligence_tpu.utils import perfwatch

        base = self._snap({"engine.params": 10 << 20, "slots.pool": 1 << 20})
        cur = self._snap({"engine.params": 40 << 20, "slots.pool": 1 << 20})
        report = perfwatch.compare_memory(cur, base)
        assert report["ok"] is False
        assert report["regressed_owners"] == ["engine.params", "total"]
        worst = report["regressions"][0]
        assert worst["series"] == "engine.params"
        assert worst["delta_bytes"] == 30 << 20

    def test_new_owner_gates_against_zero(self):
        from code_intelligence_tpu.utils import perfwatch

        # a canary candidate never released after promote is exactly a
        # series appearing out of nowhere
        base = self._snap({"engine.params.v1": 10 << 20})
        cur = self._snap({"engine.params.v1": 10 << 20,
                          "engine.params.v2": 10 << 20})
        report = perfwatch.compare_memory(cur, base)
        assert "engine.params.v2" in report["regressed_owners"]
        v2 = [r for r in report["regressions"]
              if r["series"] == "engine.params.v2"][0]
        assert v2["baseline_bytes"] == 0

    def test_band_and_floor_absorb_jitter(self):
        from code_intelligence_tpu.utils import perfwatch

        base = self._snap({"engine.params": 10 << 20})
        cur = self._snap({"engine.params": (10 << 20) + 1024})
        assert perfwatch.compare_memory(cur, base)["ok"] is True
        # shrinking is an improvement, never a regression
        report = perfwatch.compare_memory(
            self._snap({"engine.params": 2 << 20}), base)
        assert report["ok"] is True
        assert [i["series"] for i in report["improvements"]] \
            == ["engine.params", "total"]

    def test_cross_kind_refusal(self):
        from code_intelligence_tpu.utils import perfwatch

        latency = {"latency_kind": "wall_ms", "provenance": "fresh",
                   "digest": {}}
        report = perfwatch.compare_memory(self._snap({"a": 1}), latency)
        assert report["ok"] is False
        assert report["compared"] == []
        assert report["skipped"][0]["series"] == "*"
        assert "refusing" in report["skipped"][0]["reason"]

    def test_main_exit_codes(self, tmp_path, capsys):
        from code_intelligence_tpu.utils import perfwatch

        base = self._snap({"engine.params": 10 << 20})
        leak = self._snap({"engine.params": 10 << 20},
                          unattributed=8 << 20)
        bp = tmp_path / "base.json"
        bp.write_text(json.dumps(base))
        cp = tmp_path / "cur.json"
        cp.write_text(json.dumps(base))
        lp = tmp_path / "leak.json"
        lp.write_text(json.dumps(leak))
        assert perfwatch.main(["diff", "--memory", "--current", str(cp),
                               "--baseline", str(bp)]) == 0
        capsys.readouterr()
        rc = perfwatch.main(["diff", "--memory", "--current", str(lp),
                             "--baseline", str(bp)])
        out = capsys.readouterr()
        assert rc == 1
        assert "unattributed" in out.err  # the verdict names the owner
        assert "DEVICE-MEMORY REGRESSION" in out.err
        # cross-kind: a latency baseline can never gate a byte ledger
        xp = tmp_path / "lat.json"
        xp.write_text(json.dumps({"latency_kind": "wall_ms",
                                  "provenance": "fresh", "digest": {}}))
        capsys.readouterr()
        assert perfwatch.main(["diff", "--memory", "--current", str(cp),
                               "--baseline", str(xp)]) == 2

    def test_snapshot_from_ledger_roundtrips(self):
        from code_intelligence_tpu.utils import perfwatch

        params = {"w": jnp.ones((32, 32), jnp.float32)}
        ledger = DeviceMemoryLedger()
        ledger.register("engine.params", lambda: params)
        snap = perfwatch.memory_snapshot_from_ledger(ledger)
        assert snap["latency_kind"] == perfwatch.MEMORY_KIND
        assert snap["provenance"] == "fresh"
        assert snap["owners"]["engine.params"] == 32 * 32 * 4
        report = perfwatch.compare_memory(snap, snap)
        assert report["ok"] is True and report["regressions"] == []
