"""Continuous slot-based batching (inference/slots.py).

The key invariants: slot output == group-synchronous reference output on
identical inputs (mixed lengths, docs longer than chunk_len, empty docs,
n=0); slot reuse never leaks LSTM state across documents; the steady-state
loop compiles exactly ONE step shape; the MicroBatcher slots path fans out
correctly and fails fast when closed mid-flight.
"""

import threading

import jax
import numpy as np
import pytest

from code_intelligence_tpu.inference import InferenceEngine, SlotScheduler
from code_intelligence_tpu.models import AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states
from code_intelligence_tpu.text import SPECIALS, Vocab


def make_engine(batch_size=4, buckets=(8, 16), n_layers=2, **kw):
    cfg = AWDLSTMConfig(vocab_size=200, emb_sz=8, n_hid=12, n_layers=n_layers)
    enc = AWDLSTMEncoder(cfg)
    params = enc.init(
        {"params": jax.random.PRNGKey(0)},
        np.zeros((1, 4), np.int32), init_lstm_states(cfg, 1)
    )["params"]
    vocab = Vocab(SPECIALS + [f"w{i}" for i in range(150)])
    return InferenceEngine(params, cfg, vocab, buckets=buckets,
                           batch_size=batch_size, **kw)


@pytest.fixture(scope="module")
def engine():
    return make_engine()


def mixed_seqs(n=13, seed=0):
    """Mixed lengths spanning sub-chunk, multi-chunk and empty docs."""
    rng = np.random.RandomState(seed)
    seqs = [rng.randint(20, 150, rng.randint(1, 50)).astype(np.int32)
            for _ in range(n)]
    seqs.append(np.zeros((0,), np.int32))          # empty doc
    seqs.append(np.arange(30, 75, dtype=np.int32))  # > 2 chunks at C=16
    return seqs


class TestParity:
    def test_mixed_lengths_match_groups(self, engine):
        seqs = mixed_seqs()
        groups = engine.embed_ids_batch(seqs, scheduler="groups")
        slots = engine.embed_ids_batch(seqs, scheduler="slots")
        np.testing.assert_allclose(slots, groups, atol=1e-5, rtol=1e-5)

    def test_embed_issues_parity(self, engine):
        issues = [
            {"title": "crash in w3", "body": "w4 w5 " * 20},
            {"title": "", "body": ""},                       # empty body
            {"title": "w9", "body": "w10 " * 60},            # > chunk_len
            {"title": "short", "body": "w11"},
        ]
        groups = engine.embed_issues(issues, scheduler="groups")
        slots = engine.embed_issues(issues, scheduler="slots")
        np.testing.assert_allclose(slots, groups, atol=1e-5, rtol=1e-5)

    def test_n_zero(self, engine):
        out = engine.embed_ids_batch([], scheduler="slots")
        assert out.shape == (0, engine.embed_dim)

    def test_more_docs_than_slots(self, engine):
        # queue depth > batch_size forces refill churn mid-drain
        seqs = mixed_seqs(n=25, seed=3)
        groups = engine.embed_ids_batch(seqs, scheduler="groups")
        slots = engine.embed_ids_batch(seqs, scheduler="slots")
        np.testing.assert_allclose(slots, groups, atol=1e-5, rtol=1e-5)

    def test_steady_state_passes_transfer_and_recompile_audit(self, engine):
        """graftcheck runtime auditors over the warmed-up slot loop: no
        implicit host<->device transfer (the intended sync points are
        explicit device_get), ZERO new compiled step shapes, and no
        unsanctioned host materialization (CompileWatch)."""
        from code_intelligence_tpu.analysis import runtime as audit
        from code_intelligence_tpu.utils.metrics import Registry

        seqs = mixed_seqs(n=9, seed=11)
        expected = engine.embed_ids_batch(seqs, scheduler="slots")  # warmup
        reg = Registry()
        watch = audit.CompileWatch(fn="slots.step", registry=reg)
        with audit.recompile_guard(fn="slots.step", budget=0), \
                watch.steady_state():
            audited = engine.embed_ids_batch(seqs, scheduler="slots")
        np.testing.assert_array_equal(audited, expected)
        # the watch exports its sentinel gauges on the bound registry
        rendered = reg.render()
        assert "jit_recompiles_total" in rendered
        assert 'h2d_d2h_bytes{dir="d2h"}' in rendered

    def test_state_never_leaks_on_slot_reuse(self, engine):
        # same doc embedded cold vs after a long unrelated workload: the
        # refill reset must give it a fresh slot state both times
        ids = np.array([60, 61, 62], np.int32)
        e1 = engine.embed_ids_batch([ids], scheduler="slots")[0]
        engine.embed_ids_batch(mixed_seqs(n=9, seed=7), scheduler="slots")
        e2 = engine.embed_ids_batch([ids], scheduler="slots")[0]
        np.testing.assert_array_equal(e1, e2)


class TestOneCompiledShape:
    def test_single_step_shape_after_warmup(self):
        eng = make_engine()
        # warmup: one doc compiles the persistent step
        eng.embed_ids_batch([np.array([40, 41], np.int32)], scheduler="slots")
        sched = eng.slot_scheduler()
        # -1 = jit cache not introspectable on this jax (documented
        # sentinel) — unknown, not a recompile
        assert sched.compiled_step_shapes() in (1, -1)
        fwd_keys = set(eng._fwd_cache)
        # a full mixed workload (short, multi-chunk, empty, overflow) must
        # not add ANY compiled shape: not to the slot step, not to the
        # group path's (batch, bucket) cache
        eng.embed_ids_batch(mixed_seqs(n=21, seed=5), scheduler="slots")
        assert sched.compiled_step_shapes() in (1, -1)
        assert set(eng._fwd_cache) == fwd_keys

    def test_scheduler_reuse_across_calls(self):
        eng = make_engine()
        s1 = eng.slot_scheduler()
        eng.embed_ids_batch([np.array([40, 41], np.int32)], scheduler="slots")
        assert eng.slot_scheduler() is s1

    def test_engine_scheduler_default_validated(self):
        with pytest.raises(ValueError):
            make_engine(scheduler="nope")

    def test_per_call_scheduler_validated(self, engine):
        # a typo must raise, not silently run the groups path
        with pytest.raises(ValueError, match="scheduler"):
            engine.embed_ids_batch([np.array([40], np.int32)],
                                   scheduler="slot")

    def test_batcher_and_server_scheduler_validated(self):
        from code_intelligence_tpu.serving import make_server
        from code_intelligence_tpu.serving.batcher import MicroBatcher

        eng = make_engine()
        with pytest.raises(ValueError, match="scheduler"):
            MicroBatcher(eng, scheduler="Slots")
        with pytest.raises(ValueError, match="scheduler"):
            make_server(eng, host="127.0.0.1", port=0, scheduler="group")

    def test_conflicting_chunk_len_raises(self):
        eng = make_engine()
        eng.slot_scheduler(chunk_len=8)
        with pytest.raises(ValueError, match="chunk_len"):
            eng.slot_scheduler(chunk_len=16)
        # same (snapped) value is fine
        assert eng.slot_scheduler(chunk_len=8).chunk_len == 8


class TestMicroBatcherSlots:
    def test_batcher_feeds_slots_and_matches_direct(self):
        from code_intelligence_tpu.serving.batcher import MicroBatcher

        eng = make_engine(batch_size=4)
        b = MicroBatcher(eng, max_batch=8, window_ms=20.0)
        assert b.scheduler == "slots"
        try:
            results = {}

            def req(i):
                results[i] = b.embed_issue(f"w{i} crash", f"w{i + 1} " * (3 * i + 1))

            threads = [threading.Thread(target=req, args=(i,)) for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            for i in range(6):
                direct = eng.embed_issue(f"w{i} crash", f"w{i + 1} " * (3 * i + 1))
                np.testing.assert_allclose(results[i], direct, atol=1e-5,
                                           rtol=1e-5, err_msg=str(i))
        finally:
            b.close()

    def test_refill_under_closing_batcher(self):
        """Closing mid-flight must fail queued waiters fast, never hang."""
        from code_intelligence_tpu.serving.batcher import MicroBatcher

        eng = make_engine(batch_size=2)
        b = MicroBatcher(eng, max_batch=2, window_ms=1.0)
        outcomes = []
        lock = threading.Lock()

        def req(i):
            try:
                out = b.embed_issue(f"w{i}", "w1 " * 40)
                with lock:
                    outcomes.append(("ok", out.shape))
            except RuntimeError as e:
                with lock:
                    outcomes.append(("err", str(e)))

        threads = [threading.Thread(target=req, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        b.close()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), "waiter hung on close"
        assert len(outcomes) == 8
        for kind, detail in outcomes:
            if kind == "ok":
                assert detail == (eng.embed_dim,)
        # post-close submits fail fast
        with pytest.raises(RuntimeError):
            b.embed_issue("late", "request")

    def test_server_no_batcher_uses_slots(self):
        from code_intelligence_tpu.serving import make_server

        eng = make_engine()
        srv = make_server(eng, host="127.0.0.1", port=0)
        try:
            assert srv.scheduler == "slots"
            emb = srv.embed("w3 crash", "w4 w5")
            direct = eng.embed_issue("w3 crash", "w4 w5")
            np.testing.assert_allclose(emb, direct, atol=1e-5, rtol=1e-5)
            # the slot metrics are bound to the server registry
            assert "slot_occupancy" in srv.metrics.render()
        finally:
            srv.server_close()


class TestFailureRecovery:
    def test_step_failure_heals_scheduler(self):
        # the step donates its state/pool buffers: a runtime failure must
        # not poison the engine-cached scheduler forever (on TPU the
        # donated inputs are really consumed) — the failing call errors,
        # the next call runs on rebuilt state
        eng = make_engine()
        good = eng.embed_ids_batch(mixed_seqs(n=5, seed=2), scheduler="slots")
        sched = eng.slot_scheduler()
        real_step = sched._step

        def boom(*a, **kw):
            raise RuntimeError("device exploded")

        sched._step = boom
        with pytest.raises(RuntimeError, match="device exploded"):
            eng.embed_ids_batch(mixed_seqs(n=5, seed=2), scheduler="slots")
        sched._step = real_step
        # slot table and queue were cleared, device state rebuilt
        assert all(d is None for d in sched._slot_doc)
        assert not sched._queue
        again = eng.embed_ids_batch(mixed_seqs(n=5, seed=2), scheduler="slots")
        np.testing.assert_array_equal(good, again)


class TestTicketAPI:
    def test_unfinished_ticket_raises(self, engine):
        sched = SlotScheduler(make_engine())
        t = sched.submit(np.array([40, 41], np.int32))
        with pytest.raises(RuntimeError):
            sched.materialize([t])
        sched.drain()
        out = sched.materialize([t])
        assert out.shape == (1, sched.engine.embed_dim)


class TestRaggedParity:
    """Ragged paged scheduler vs the dense slot reference: exact allclose
    pins across the nasty shapes — mostly-idle batches, length-1 docs,
    lengths straddling a page boundary, mid-stream refill changing a
    row's valid length."""

    def test_mixed_lengths_match_dense(self, engine):
        seqs = mixed_seqs()
        dense = engine.embed_ids_batch(seqs, scheduler="slots")
        ragged = engine.embed_ids_batch(seqs, scheduler="ragged")
        np.testing.assert_allclose(ragged, dense, atol=1e-5, rtol=1e-5)

    def test_single_length_one_doc_idle_lanes(self, engine):
        # a single 1-token doc in a 4-slot batch: 3 idle lanes stage
        # valid 0 and must contribute nothing
        ids = [np.array([50], np.int32)]
        dense = engine.embed_ids_batch(ids, scheduler="slots")
        ragged = engine.embed_ids_batch(ids, scheduler="ragged")
        np.testing.assert_allclose(ragged, dense, atol=1e-5, rtol=1e-5)

    def test_empty_doc_and_n_zero(self, engine):
        dense = engine.embed_ids_batch([np.zeros((0,), np.int32)],
                                       scheduler="slots")
        ragged = engine.embed_ids_batch([np.zeros((0,), np.int32)],
                                        scheduler="ragged")
        np.testing.assert_allclose(ragged, dense, atol=1e-5, rtol=1e-5)
        out = engine.embed_ids_batch([], scheduler="ragged")
        assert out.shape == (0, engine.embed_dim)

    def test_lengths_straddling_page_boundary(self, engine):
        P = engine.slot_scheduler(ragged=True).page_len
        seqs = [np.full((l,), 30 + i, np.int32)
                for i, l in enumerate((P - 1, P, P + 1, 2 * P, 2 * P + 1, 1))]
        dense = engine.embed_ids_batch(seqs, scheduler="slots")
        ragged = engine.embed_ids_batch(seqs, scheduler="ragged")
        np.testing.assert_allclose(ragged, dense, atol=1e-5, rtol=1e-5)

    def test_mid_stream_refill_changes_row_valid_length(self, engine):
        # 3x more docs than slots, alternating multi-page and length-1:
        # every slot cycles long → short → long, so its staged valid
        # length changes across refills while OTHER rows are mid-doc
        P = engine.slot_scheduler(ragged=True).page_len
        seqs = []
        for i in range(3 * engine.batch_size):
            if i % 2 == 0:
                seqs.append(np.full((3 * P + i % P,), 40 + i % 50,
                                    np.int32))
            else:
                seqs.append(np.array([60 + i % 40], np.int32))
        dense = engine.embed_ids_batch(seqs, scheduler="slots")
        ragged = engine.embed_ids_batch(seqs, scheduler="ragged")
        np.testing.assert_allclose(ragged, dense, atol=1e-5, rtol=1e-5)

    def test_state_never_leaks_on_page_reuse(self, engine):
        # same doc embedded cold vs after a workload that churns every
        # page through retire/recycle: fresh page state both times
        ids = np.array([60, 61, 62], np.int32)
        e1 = engine.embed_ids_batch([ids], scheduler="ragged")[0]
        engine.embed_ids_batch(mixed_seqs(n=9, seed=7), scheduler="ragged")
        e2 = engine.embed_ids_batch([ids], scheduler="ragged")[0]
        np.testing.assert_array_equal(e1, e2)

    def test_steady_state_passes_transfer_and_recompile_audit(self, engine):
        """The page table and valid lengths must ride the packed staging
        block (no per-step h2d transfers) and the ragged step must stay
        ONE compiled shape in steady state, with every host
        materialization an explicit device_get (CompileWatch)."""
        from code_intelligence_tpu.analysis import runtime as audit
        from code_intelligence_tpu.utils.metrics import Registry

        seqs = mixed_seqs(n=9, seed=11)
        expected = engine.embed_ids_batch(seqs, scheduler="ragged")
        reg = Registry()
        watch = audit.CompileWatch(fn="slots.step_ragged", registry=reg)
        with audit.recompile_guard(fn="slots.step_ragged", budget=0), \
                watch.steady_state():
            audited = engine.embed_ids_batch(seqs, scheduler="ragged")
        np.testing.assert_array_equal(audited, expected)
        assert "jit_recompiles_total" in reg.render()


class TestRaggedScheduler:
    def test_one_compiled_shape_separate_instances(self):
        eng = make_engine()
        eng.embed_ids_batch([np.array([40, 41], np.int32)],
                            scheduler="ragged")
        rs = eng.slot_scheduler(ragged=True)
        assert rs.compiled_step_shapes() in (1, -1)
        eng.embed_ids_batch(mixed_seqs(n=21, seed=5), scheduler="ragged")
        assert rs.compiled_step_shapes() in (1, -1)
        # the ragged and dense schedulers are distinct cached instances
        # with their own single step shape each
        assert eng.slot_scheduler() is not rs
        assert eng.slot_scheduler(ragged=True) is rs

    def test_page_len_geometry(self):
        eng = make_engine()
        rs = eng.slot_scheduler(ragged=True)
        # default page is a quarter of the dense chunk, floored at 8
        assert rs.page_len == max(8, eng.slot_scheduler().chunk_len // 4)
        assert rs.n_pages == 2 * eng.batch_size

    def test_conflicting_page_len_raises(self):
        eng = make_engine()
        eng.slot_scheduler(ragged=True, page_len=8)
        with pytest.raises(ValueError, match="page_len"):
            eng.slot_scheduler(ragged=True, page_len=16)
        assert eng.slot_scheduler(ragged=True, page_len=8).page_len == 8
        # chunk_len is the dense knob: the ragged branch must reject it,
        # not silently hand back a different step geometry
        with pytest.raises(ValueError, match="page_len"):
            eng.slot_scheduler(ragged=True, chunk_len=32)

    def test_wasted_lane_gauge_and_ragged_win(self):
        from code_intelligence_tpu.utils.metrics import Registry

        eng = make_engine()
        reg = Registry()
        eng.slot_scheduler(registry=reg)
        eng.slot_scheduler(ragged=True, registry=reg)
        seqs = mixed_seqs(n=13, seed=3)
        eng.embed_ids_batch(seqs, scheduler="slots")
        eng.embed_ids_batch(seqs, scheduler="ragged")
        assert "slots_wasted_lane_fraction" in reg.render()
        ds, rs = eng.slot_scheduler(), eng.slot_scheduler(ragged=True)
        # the ragged geometry must waste fewer lanes on the same docs
        assert 0.0 <= rs.wasted_lane_fraction() < ds.wasted_lane_fraction()
        # counters are pure host arithmetic and reconcile exactly
        assert ds.tokens_stepped == ds.steps_run * ds.batch_size * ds.chunk_len
        assert rs.tokens_stepped == rs.steps_run * rs.batch_size * rs.page_len
        assert ds.tokens_valid == rs.tokens_valid  # same documents

    def test_step_cost_analysis_flops(self):
        eng = make_engine()
        seqs = mixed_seqs(n=13, seed=3)
        eng.embed_ids_batch(seqs, scheduler="slots")
        eng.embed_ids_batch(seqs, scheduler="ragged")
        ds, rs = eng.slot_scheduler(), eng.slot_scheduler(ragged=True)
        cd, cr = ds.step_cost_analysis(), rs.step_cost_analysis()
        # the page-sized ragged program is strictly cheaper per step
        assert 0 < cr["flops"] < cd["flops"]
        # memoized — the lowering must not be paid per call
        assert rs.step_cost_analysis() is cr

    def test_failure_recovery_heals_ragged_scheduler(self):
        eng = make_engine()
        good = eng.embed_ids_batch(mixed_seqs(n=5, seed=2),
                                   scheduler="ragged")
        sched = eng.slot_scheduler(ragged=True)
        real_step = sched._step

        def boom(*a, **kw):
            raise RuntimeError("device exploded")

        sched._step = boom
        with pytest.raises(RuntimeError, match="device exploded"):
            eng.embed_ids_batch(mixed_seqs(n=5, seed=2), scheduler="ragged")
        sched._step = real_step
        # slot table, queue, page table and free list were rebuilt
        assert all(d is None for d in sched._slot_doc)
        assert not sched._queue and not sched._retired
        assert len(sched._free_pages) == sched.n_pages - sched.batch_size
        again = eng.embed_ids_batch(mixed_seqs(n=5, seed=2),
                                    scheduler="ragged")
        np.testing.assert_array_equal(good, again)


class _AlignedNumpy:
    """numpy, except ``full`` returns 64-byte-aligned arrays: the CPU
    backend aliases such a buffer zero-copy on ``jnp.asarray`` /
    ``device_put``, so the staging race below does not hinge on where
    malloc happened to put the block."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def full(shape, fill_value, dtype=None):
        dt = np.dtype(dtype)
        n = int(np.prod(shape)) * dt.itemsize
        raw = np.zeros(n + 64, np.uint8)
        off = (-raw.ctypes.data) % 64
        out = raw[off:off + n].view(dt).reshape(shape)
        out[...] = fill_value
        return out


class TestStagingUnderAsyncDispatch:
    """The device must never read a host block the host can still write.

    Dispatch is asynchronous (always on TPU; the jax default on CPU), so
    the scheduler's host loop runs many steps ahead of the device. Here
    the first step is made to wait on a deliberately slow computation:
    every later step is staged while none has run. A staging buffer that
    is reused (the parent's double buffer) has been rewritten by the
    time the steps that were handed it execute."""

    @pytest.mark.parametrize("scheduler", ["slots", "ragged"])
    def test_steps_queued_behind_a_slow_step_read_their_own_block(
            self, scheduler, monkeypatch):
        import jax.numpy as jnp

        from code_intelligence_tpu.inference import slots as slots_mod

        assert jax.config.read("jax_cpu_enable_async_dispatch")
        monkeypatch.setattr(slots_mod, "np", _AlignedNumpy())
        eng = make_engine()
        seqs = mixed_seqs(n=25, seed=7)  # > slots: refills mid-drain
        want = eng.embed_ids_batch(seqs, scheduler="groups")
        sched = eng.slot_scheduler(ragged=scheduler == "ragged")
        sched.embed_ids(seqs[:3])  # compile the step off the slow path

        @jax.jit
        def slow(x):
            # ~1 s of dependent matmuls, far longer than the host needs
            # to stage the whole drain (a host callback would not do:
            # its dispatch is synchronous on CPU)
            m = jnp.eye(384) * 0.5 + 0.001
            a = jax.lax.fori_loop(0, 3000, lambda i, a: jnp.tanh(a @ m),
                                  jnp.ones((384, 384)))
            return x + 0.0 * a[0, 0]

        slow(sched._pool).block_until_ready()  # compile  # graft: measure
        with sched._lock:
            # step 1 consumes the pool, so it (and every step after it)
            # waits for the slow computation
            sched._pool = slow(sched._pool)
            tickets = [sched.submit(ids) for ids in seqs]
            sched.drain()
            # the host finished staging EVERY step before the device
            # finished the slow one — the regime the chip always runs in
            assert not sched._pool.is_ready()
            assert sched.steps_run > 4
            got = sched.materialize(tickets)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
