"""Mesh-sharded serve step (parallel/serve_shard.py + ``mesh=`` on the
slot schedulers, RUNBOOK §26).

The key invariants: sharded scheduler output == the single-device path on
identical inputs (the real multi-device proof runs in the forced-8-device
subprocess gate, pinned in test_delivery; the in-process pins here run
the SAME pjit/NamedSharding code path on a 1-device ("data","model")
mesh); the sharded step keeps donation + one compiled shape + a clean
transfer/recompile audit under its own step name; ``mesh=None`` leaves
today's single-chip path bitwise unchanged; and the shared partition
rules / bounded program cache cannot drift between train and serve.
"""

import types

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.inference.slots import (
    RaggedSlotScheduler, SlotScheduler)
from code_intelligence_tpu.models import (
    AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states)
from code_intelligence_tpu.parallel import mesh as mesh_mod
from code_intelligence_tpu.parallel import serve_shard
from code_intelligence_tpu.parallel.serve_shard import (
    DegenerateMeshError, ProgramCache, ServeMeshError, build_serve_mesh,
    match_partition_rules, parse_mesh_spec)
from code_intelligence_tpu.text import SPECIALS, Vocab


def make_engine(batch_size=4, buckets=(8, 16), **kw):
    cfg = AWDLSTMConfig(vocab_size=200, emb_sz=8, n_hid=12, n_layers=2)
    enc = AWDLSTMEncoder(cfg)
    params = enc.init(
        {"params": jax.random.PRNGKey(0)},
        np.zeros((1, 4), np.int32), init_lstm_states(cfg, 1))["params"]
    vocab = Vocab(SPECIALS + [f"w{i}" for i in range(150)])
    return InferenceEngine(params, cfg, vocab, buckets=buckets,
                           batch_size=batch_size, **kw)


def mixed_seqs(n=11, seed=0):
    rng = np.random.RandomState(seed)
    seqs = [rng.randint(20, 150, rng.randint(1, 50)).astype(np.int32)
            for _ in range(n)]
    seqs.append(np.zeros((0,), np.int32))           # empty doc
    seqs.append(np.arange(30, 75, dtype=np.int32))  # > 2 chunks at C=16
    return seqs


@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.fixture(scope="module")
def mesh1():
    # a REAL ("data","model") mesh over one device: the pjit path with
    # in_/out_shardings, param placement, and the sharded staging
    # device_put all run — only the collective traffic is degenerate
    # (the multi-device twin is the --check_meshserve subprocess gate)
    return build_serve_mesh("data=1,model=1", devices=jax.devices()[:1])


class TestMeshSpec:
    def test_parse_sized_and_unsized(self):
        assert parse_mesh_spec("data=4,model=2") == {"data": 4, "model": 2}
        assert parse_mesh_spec("data,model") == {"data": None,
                                                 "model": None}
        assert parse_mesh_spec("data") == {"data": None}

    def test_parse_rejects_bad_specs(self):
        for bad in ("seq,model", "data=0", "data=x", "", "data,data"):
            with pytest.raises(ServeMeshError):
                parse_mesh_spec(bad)

    def test_build_resolves_unsized_model_heuristic(self):
        # 1 visible device: unsized model takes 1, data absorbs
        m = build_serve_mesh("data,model", devices=jax.devices()[:1])
        assert dict(m.shape) == {"data": 1, "model": 1}

    def test_build_rejects_oversized_mesh(self):
        with pytest.raises(ValueError):
            build_serve_mesh("data=2,model=2", devices=jax.devices()[:1])

    def test_validate_rejects_uneven_batch_split(self):
        stub = types.SimpleNamespace(shape={"data": 3, "model": 1})
        with pytest.raises(ServeMeshError, match="evenly"):
            serve_shard.validate_serve_mesh(stub, batch_size=4)
        serve_shard.validate_serve_mesh(stub, batch_size=6)  # 6 % 3 == 0

    def test_validate_rejects_foreign_axes(self):
        stub = types.SimpleNamespace(shape={"seq": 2})
        with pytest.raises(ServeMeshError, match="axes"):
            serve_shard.validate_serve_mesh(stub, batch_size=4)

    def test_validate_requires_data_axis(self):
        # a model-only mesh would crash with a raw jax error deep in
        # scheduler construction (row shardings build P("data", ...)) —
        # it must be a NAMED refusal instead
        stub = types.SimpleNamespace(shape={"model": 2})
        with pytest.raises(ServeMeshError, match="data"):
            serve_shard.validate_serve_mesh(stub, batch_size=4)

    def test_ensure_multi_device_named_refusal(self):
        with pytest.raises(DegenerateMeshError):
            serve_shard.ensure_multi_device(1, smoke=False)
        serve_shard.ensure_multi_device(1, smoke=True)   # smoke forces
        serve_shard.ensure_multi_device(8, smoke=False)  # real mesh ok

    def test_mesh_with_groups_scheduler_refused_at_cli(self):
        # only the slot/ragged schedulers run the sharded step: the groups
        # path would silently serve unsharded, so the server's CLI refuses
        from code_intelligence_tpu.serving.server import main as server_main

        with pytest.raises(SystemExit) as exc:
            server_main(["--model_dir", "/x", "--mesh", "data,model",
                         "--scheduler", "groups"])
        assert exc.value.code == 2


class TestPartitionRules:
    def test_match_partition_rules_by_path(self):
        params = {"params": {"embedding": np.zeros((6, 4)),
                             "lstm_0_w_ih": np.zeros((8, 4)),
                             "misc_scale": np.zeros((4,))}}
        specs = match_partition_rules(serve_shard.PARTITION_RULES, params)
        assert specs["params"]["embedding"] == P("model", None)
        assert specs["params"]["lstm_0_w_ih"] == P("model", None)
        assert specs["params"]["misc_scale"] == P()

    def test_train_and_serve_share_one_rule_table(self):
        # the extraction contract: mesh.py's historical name IS the
        # shared serve_shard table — they cannot drift
        assert mesh_mod._PARAM_RULES is serve_shard.PARTITION_RULES

    def test_param_shardings_replicates_without_model_axis(self, mesh1):
        tree = {"embedding": np.zeros((6, 4))}
        sh = mesh_mod.param_shardings(tree, mesh1)  # model axis size 1
        assert sh["embedding"].spec == P()


class TestProgramCache:
    def test_lru_bound_and_build_once(self):
        calls = []
        cache = ProgramCache(maxsize=2)
        for key in ("a", "b", "a", "c"):  # c evicts b (a was refreshed)
            cache.get(key, lambda k=key: calls.append(k) or k.upper())
        assert calls == ["a", "b", "c"]
        assert len(cache) == 2
        assert "a" in cache and "c" in cache and "b" not in cache
        # an evicted key rebuilds — never an error, never a stale hit
        assert cache.get("b", lambda: "B2") == "B2"

    def test_seq_parallel_cache_is_bounded(self):
        from code_intelligence_tpu.parallel import seq_parallel

        assert isinstance(seq_parallel._PROGRAMS, ProgramCache)
        bound = seq_parallel._PROGRAMS.maxsize
        mesh = build_serve_mesh("data=1,model=1",
                                devices=jax.devices()[:1])
        # churn far past the bound (programs are built lazily — the
        # jitted shard_map is never traced here, so this is cheap);
        # the old dict pinned every one of these forever
        for i in range(bound + 8):
            seq_parallel._forget_mult_program(mesh, "seq",
                                              batch_axis=f"b{i}")
        assert len(seq_parallel._PROGRAMS) <= bound


class TestMeshedScheduler:
    def test_dense_sharded_parity_and_audit(self, engine, mesh1):
        from code_intelligence_tpu.analysis import runtime as audit

        seqs = mixed_seqs()
        reference = engine.embed_ids_batch(seqs, scheduler="groups")
        sched = SlotScheduler(engine, mesh=mesh1)
        assert sched._step_name == "slots.step_mesh"
        out = sched.embed_ids(seqs)
        np.testing.assert_allclose(out, reference, atol=1e-5, rtol=1e-5)
        # steady state: one compiled shape, zero implicit transfers —
        # the sharded staging device_put is the ONE explicit h2d, and
        # CompileWatch pins zero ledger recompiles of the mesh step
        watch = audit.CompileWatch(fn="slots.step_mesh")
        with audit.recompile_guard(fn="slots.step_mesh", budget=0), \
                watch.steady_state():
            audited = sched.embed_ids(seqs)
        np.testing.assert_array_equal(audited, out)
        assert watch.new_compiles == {}
        assert sched.compiled_step_shapes() in (1, -1)

    def test_ragged_sharded_parity_page_boundary_and_midstream(
            self, engine, mesh1):
        # page straddles + 3x-oversubscribed alternating long/short docs
        # (every slot cycles long -> short -> long, changing its staged
        # valid length mid-stream) — the nasty shapes from the ragged
        # suite, under the mesh
        rsched = RaggedSlotScheduler(engine, mesh=mesh1)
        assert rsched._step_name == "slots.step_ragged_mesh"
        pg = rsched.page_len
        seqs = [np.full((l,), 30 + i, np.int32) for i, l in
                enumerate((pg - 1, pg, pg + 1, 2 * pg, 2 * pg + 1, 1))]
        for i in range(3 * engine.batch_size):
            if i % 2 == 0:
                seqs.append(np.full((3 * pg + i % pg,), 40 + i % 50,
                                    np.int32))
            else:
                seqs.append(np.array([60 + i % 40], np.int32))
        dense = engine.embed_ids_batch(seqs, scheduler="slots")
        out = rsched.embed_ids(seqs)
        np.testing.assert_allclose(out, dense, atol=1e-5, rtol=1e-5)

    def test_ragged_sharded_audit_and_page_reuse(self, engine, mesh1):
        from code_intelligence_tpu.analysis import runtime as audit

        rsched = RaggedSlotScheduler(engine, mesh=mesh1)
        ids = np.array([60, 61, 62], np.int32)
        e1 = rsched.embed_ids([ids])[0]
        # churn every page through retire/recycle under the audit: the
        # page table must keep riding the packed staging block (no
        # per-step transfers) with zero new compiled shapes
        rsched.embed_ids(mixed_seqs(n=9, seed=7))  # warm all shapes
        watch = audit.CompileWatch(fn="slots.step_ragged_mesh")
        with audit.recompile_guard(fn="slots.step_ragged_mesh",
                                   budget=0), \
                watch.steady_state():
            rsched.embed_ids(mixed_seqs(n=9, seed=7))
        e2 = rsched.embed_ids([ids])[0]
        np.testing.assert_array_equal(e1, e2)  # no state leak on reuse

    def test_donation_and_shardings_reach_jit(self, engine, mesh1,
                                              monkeypatch):
        # the contract the runtime can't cheaply observe on CPU (donation
        # is a no-op there): the sharded step must be built with BOTH
        # donate_argnums on the state/pool AND explicit in_/out_shardings
        captured = {}
        real_jit = jax.jit

        def spy(fun, **kw):
            captured.update(kw)
            return real_jit(fun, **kw)

        monkeypatch.setattr(jax, "jit", spy)
        RaggedSlotScheduler(engine, mesh=mesh1)
        assert captured["donate_argnums"] == (2, 3)
        assert "in_shardings" in captured and "out_shardings" in captured
        # state tuple + pool row-sharded over 'data'
        state_sh = captured["in_shardings"][2]
        assert all(s.spec[0] == "data" for s in state_sh)
        assert captured["in_shardings"][3].spec[0] == "data"

    def test_mesh_metrics_on_registry(self, mesh1):
        from code_intelligence_tpu.utils.metrics import Registry

        eng = make_engine()
        reg = Registry()
        sched = RaggedSlotScheduler(eng, mesh=mesh1, registry=reg)
        sched.embed_ids(mixed_seqs(n=5, seed=3))
        sched.step_cost_analysis()  # lands the per-device flops gauge
        text = reg.render()
        assert 'slots_mesh_devices 1' in text
        assert 'slots_mesh_axis_size{axis="data"} 1' in text
        assert 'slots_mesh_axis_size{axis="model"} 1' in text
        assert "slots_step_flops_per_device" in text
        assert 'slots_wasted_lane_fraction_shard{shard="0"}' in text
        # per-shard counters reconcile with the global ones (1 shard)
        assert sched.n_data_shards == 1
        assert sched.shard_wasted_lane_fraction(0) == pytest.approx(
            sched.wasted_lane_fraction())
        # a registry bound AFTER the first (memoized) cost pull still
        # receives the per-device flops gauge on the next pull
        reg2 = Registry()
        sched.bind_registry(reg2)
        sched.step_cost_analysis()
        assert "slots_step_flops_per_device" in reg2.render()

    def test_mesh_off_bitwise_unchanged_and_default(self, mesh1):
        eng = make_engine()
        seqs = mixed_seqs(n=7, seed=5)
        before = eng.embed_ids_batch(seqs, scheduler="ragged")
        # running a sharded scheduler on the SAME engine must not
        # perturb the engine's own single-chip path in any bit
        RaggedSlotScheduler(eng, mesh=mesh1).embed_ids(seqs)
        after = eng.embed_ids_batch(seqs, scheduler="ragged")
        np.testing.assert_array_equal(before, after)
        # the default scheduler is meshless with the historical step
        # name — today's path, not a 1-device mesh in disguise
        sched = eng.slot_scheduler(ragged=True)
        assert sched.mesh is None
        assert sched._step_name == "slots.step_ragged"
        assert sched._params is None

    def test_engine_level_mesh_plumbs_to_schedulers(self, mesh1):
        eng = make_engine(mesh=mesh1)
        assert eng.mesh is mesh1
        sched = eng.slot_scheduler(ragged=True)
        assert sched.mesh is mesh1
        out = sched.embed_ids([np.array([40, 41], np.int32)])
        assert out.shape == (1, eng.embed_dim)

    def test_mesh_with_the_pallas_cell_is_refused_on_tpu(self, mesh1,
                                                         monkeypatch):
        # on the TPU a requested kernel is never swapped for the scan
        # behind a log line: the sharded step has no shard_map around
        # the Pallas call, so the combination raises at start-up
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="does not compose with "
                                             "--mesh"):
            make_engine(mesh=mesh1, lstm_pallas=True)
        with pytest.raises(ValueError, match="--mesh"):
            make_engine(mesh=mesh1, lstm_pallas=True, precision="int8")
        make_engine(mesh=mesh1)  # the scan under a mesh: fine

    def test_uneven_batch_raises_at_construction(self, mesh1):
        stub = types.SimpleNamespace(shape={"data": 3, "model": 1})
        with pytest.raises(ServeMeshError, match="evenly"):
            SlotScheduler(make_engine(), mesh=stub)

    def test_step_failure_heals_sharded_scheduler(self, engine, mesh1):
        sched = RaggedSlotScheduler(engine, mesh=mesh1)
        good = sched.embed_ids(mixed_seqs(n=5, seed=2))
        real_step = sched._step

        def boom(*a, **kw):
            raise RuntimeError("device exploded")

        sched._step = boom
        with pytest.raises(RuntimeError, match="device exploded"):
            sched.embed_ids(mixed_seqs(n=5, seed=2))
        sched._step = real_step
        # reset() rebuilt the SHARDED device state (placement included)
        again = sched.embed_ids(mixed_seqs(n=5, seed=2))
        np.testing.assert_array_equal(good, again)


class TestSupervisorMeshKnob:
    def test_mesh_plumbed_to_real_replicas_only(self, tmp_path):
        from code_intelligence_tpu.serving.fleet.supervisor import (
            FleetSupervisor)

        sup = FleetSupervisor(n=2, engine="real", model_dir=str(tmp_path),
                              mesh="data=2,model=2")
        for r in sup.replicas:
            i = r.cmd.index("--mesh")
            assert r.cmd[i + 1] == "data=2,model=2"
        with pytest.raises(ValueError, match="mesh requires"):
            FleetSupervisor(n=1, engine="fake", mesh="data,model")


class TestMeshserveGateWiring:
    """runbook_ci --check_meshserve composition (the real forced-device
    subprocess gate is slow-pinned in test_delivery — one subprocess
    run total)."""

    def _run(self, monkeypatch, capsys, report):
        import json as _json
        from pathlib import Path

        from code_intelligence_tpu.parallel import meshserve_check
        from code_intelligence_tpu.utils import runbook_ci

        monkeypatch.setattr(meshserve_check, "run_meshserve_check",
                            lambda: report)
        repo = Path(__file__).resolve().parent.parent
        rc = runbook_ci.main(
            ["--runbook", str(repo / "docs" / "RUNBOOK.md"),
             "--check_meshserve"])
        out = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        return rc, out

    def test_ok_report_composes(self, monkeypatch, capsys):
        rc, out = self._run(monkeypatch, capsys,
                            {"ok": True, "parity_ok": True,
                             "flops_balance": 1.02})
        assert rc == 0
        assert out["meshserve_ok"] is True and out["ok"] is True
        assert out["meshserve"]["flops_balance"] == 1.02

    def test_failing_report_fails_the_gate(self, monkeypatch, capsys):
        rc, out = self._run(monkeypatch, capsys,
                            {"ok": False, "error": "parity broke"})
        assert rc == 1
        assert out["meshserve_ok"] is False and out["ok"] is False
