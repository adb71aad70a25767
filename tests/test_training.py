"""Training-loop tests on the virtual 8-device CPU mesh.

Covers: loss actually decreases end-to-end, one-cycle schedule shape,
DP/TP mesh execution (SURVEY.md §4: multi-chip paths testable without a
TPU), callback semantics, checkpoint/restore, encoder export.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code_intelligence_tpu.data import LMStreamLoader
from code_intelligence_tpu.models import AWDLSTMConfig
from code_intelligence_tpu.parallel import make_mesh
from code_intelligence_tpu.training import (
    EarlyStopping,
    History,
    LMTrainer,
    ReduceLROnPlateau,
    TrainConfig,
    one_cycle_lr,
    one_cycle_momentum,
)
from code_intelligence_tpu.training import checkpoint as ckpt


def tiny_model(vocab=32, **kw):
    kw.setdefault("emb_sz", 8)
    kw.setdefault("n_hid", 16)
    kw.setdefault("n_layers", 2)
    return AWDLSTMConfig(vocab_size=vocab, **kw)


def repeating_corpus(vocab=32, n=4096, period=8, seed=0):
    # A highly learnable stream: cyclic token pattern + noise.
    rng = np.random.RandomState(seed)
    base = np.arange(n, dtype=np.int32) % period + 2
    noise = rng.randint(0, vocab, n).astype(np.int32)
    mask = rng.rand(n) < 0.05
    return np.where(mask, noise, base).astype(np.int32)


class TestSchedules:
    def test_one_cycle_lr_shape(self):
        s = one_cycle_lr(100, lr_max=1.0, pct_start=0.3)
        vals = [float(s(i)) for i in range(100)]
        peak = int(np.argmax(vals))
        assert 25 <= peak <= 35  # peaks around pct_start
        assert vals[0] < vals[peak] and vals[-1] < vals[0]

    def test_one_cycle_lr_finite_at_tiny_horizons(self):
        # optax's one-cycle is NaN at every step when int(pct_start * n)
        # rounds to zero (zero-length warmup interval); the wrapper must
        # clamp the horizon for the GIVEN pct_start, not just the default
        for pct in (0.3, 0.2, 0.05):
            for n in (1, 2, 3, 4, 8):
                s = one_cycle_lr(n, lr_max=1e-3, pct_start=pct)
                vals = [float(s(i)) for i in range(n + 1)]
                assert all(np.isfinite(v) for v in vals), (pct, n, vals)
                assert all(v > 0 for v in vals), (pct, n, vals)

    def test_one_cycle_lr_warns_when_horizon_stretched(self, caplog):
        # the NaN clamp silently retimed tiny runs (training ends
        # mid-cycle at elevated LR); that must be visible in the logs
        import logging

        with caplog.at_level(logging.WARNING,
                             logger="code_intelligence_tpu.training.schedules"):
            one_cycle_lr(2, lr_max=1e-3, pct_start=0.3)
        assert any("NaN-safe horizon" in r.message for r in caplog.records)
        caplog.clear()
        with caplog.at_level(logging.WARNING,
                             logger="code_intelligence_tpu.training.schedules"):
            one_cycle_lr(100, lr_max=1e-3, pct_start=0.3)
        assert not caplog.records  # normal horizons stay quiet

    def test_one_cycle_momentum_mirrors(self):
        m = one_cycle_momentum(100, 0.85, 0.95, pct_start=0.3)
        vals = [float(m(i)) for i in range(100)]
        trough = int(np.argmin(vals))
        assert 25 <= trough <= 35
        assert abs(vals[0] - 0.95) < 1e-6 and abs(vals[-1] - 0.95) < 1e-3
        assert abs(min(vals) - 0.85) < 1e-6


class TestTrainStep:
    def test_loss_decreases(self):
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        tcfg = TrainConfig(batch_size=8, bptt=6, lr=5e-3, cycle_len=1, grad_clip=1.0)
        trainer = LMTrainer(tiny_model(), tcfg, mesh=mesh, steps_per_epoch=80)
        dl = LMStreamLoader(repeating_corpus(), 8, 6, shuffle_offsets=False)
        state = trainer.init_state(jax.random.PRNGKey(0))
        first, last = [], []
        with mesh:
            for i, (x, y) in enumerate(dl.epoch(0)):
                if i >= 80:
                    break
                state, m = trainer.train_step(state, x, y)
                (first if i < 10 else last).append(float(m["ce"]))
        assert np.mean(last[-10:]) < np.mean(first) * 0.8, (np.mean(first), np.mean(last[-10:]))

    def test_metrics_finite(self):
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        trainer = LMTrainer(tiny_model(), TrainConfig(batch_size=8, bptt=6), mesh=mesh)
        dl = LMStreamLoader(repeating_corpus(), 8, 6)
        state = trainer.init_state(jax.random.PRNGKey(0))
        with mesh:
            x, y = next(dl.epoch(0))
            state, m = trainer.train_step(state, x, y)
        for k, v in m.items():
            assert np.isfinite(float(v)), k


class TestTrainSteps:
    """k-windows-per-dispatch scan must equal k sequential train_step calls."""

    def _setup(self):
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        tcfg = TrainConfig(batch_size=8, bptt=6, lr=5e-3, cycle_len=1)
        trainer = LMTrainer(tiny_model(), tcfg, mesh=mesh, steps_per_epoch=40)
        dl = LMStreamLoader(repeating_corpus(), 8, 6, shuffle_offsets=False)
        windows = []
        for i, (x, y) in enumerate(dl.epoch(0)):
            if i >= 6:
                break
            windows.append((x, y))
        return mesh, trainer, windows

    def test_scan_matches_sequential(self):
        mesh, trainer, windows = self._setup()
        k = len(windows)
        # sequential reference
        state_a = trainer.init_state(jax.random.PRNGKey(0))
        seq_metrics = []
        with mesh:
            for x, y in windows:
                state_a, m = trainer.train_step(state_a, x, y)
                seq_metrics.append(m)
            # scanned: same init, one dispatch
            state_b = trainer.init_state(jax.random.PRNGKey(0))
            xs = np.stack([x for x, _ in windows])
            ys = np.stack([y for _, y in windows])
            state_b, ms = trainer.train_steps(state_b, xs, ys)
        assert int(state_b.step) == int(state_a.step) == k
        # stacked metrics: leaf shape (k,), each equal to the sequential run
        for i in range(k):
            np.testing.assert_allclose(
                float(ms["ce"][i]), float(seq_metrics[i]["ce"]),
                rtol=1e-5, atol=1e-6)
        # end-state parity: params and BPTT hidden carry match exactly-ish
        pa = jax.tree_util.tree_leaves(state_a.params)
        pb = jax.tree_util.tree_leaves(state_b.params)
        for a, b in zip(pa, pb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(state_a.lstm_states),
                        jax.tree_util.tree_leaves(state_b.lstm_states)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_dispatch_steady_state_passes_transfer_and_recompile_audit(self):
        """graftcheck runtime auditors over the warmed-up train dispatch:
        with device-placed windows, the steady-state `train.steps` scan
        must make NO implicit host<->device transfer (the device_get of
        the stacked metrics is explicit) and compile ZERO new shapes."""
        from code_intelligence_tpu.analysis import runtime as audit

        mesh, trainer, windows = self._setup()
        xs = jax.device_put(np.stack([x for x, _ in windows]))
        ys = jax.device_put(np.stack([y for _, y in windows]))
        state = trainer.init_state(jax.random.PRNGKey(0))
        with mesh:
            state, _ = trainer.train_steps(state, xs, ys)  # warmup compile
            with audit.recompile_guard(fn="train.steps", budget=0), \
                    audit.no_implicit_transfers():
                state, ms = trainer.train_steps(state, xs, ys)
                ms = jax.device_get(ms)
        assert all(np.isfinite(ms["ce"]))

    def test_scan_composes_with_tensor_parallel(self):
        # dryrun_multichip jits the SINGLE step over dp x tp; the scanned
        # product default must compose with the same mesh
        mesh = make_mesh({"data": 4, "model": 2})
        cfg = tiny_model()
        tcfg = TrainConfig(batch_size=8, bptt=6)
        trainer = LMTrainer(cfg, tcfg, mesh=mesh, steps_per_epoch=10)
        dl = LMStreamLoader(repeating_corpus(), 8, 6, shuffle_offsets=False)
        it = dl.epoch(0)
        xs, ys = zip(*(next(it) for _ in range(2)))
        state = trainer.init_state(jax.random.PRNGKey(0))
        with mesh:
            state, ms = trainer.train_steps(state, np.stack(xs), np.stack(ys))
        assert ms["ce"].shape == (2,)
        assert all(np.isfinite(np.asarray(ms["ce"])))

    def test_scan_shards_over_data_mesh(self):
        mesh = make_mesh({"data": 8})
        tcfg = TrainConfig(batch_size=16, bptt=6)
        trainer = LMTrainer(tiny_model(), tcfg, mesh=mesh, steps_per_epoch=10)
        dl = LMStreamLoader(repeating_corpus(), 16, 6, shuffle_offsets=False)
        it = dl.epoch(0)
        xs, ys = zip(*(next(it) for _ in range(3)))
        state = trainer.init_state(jax.random.PRNGKey(0))
        with mesh:
            state, ms = trainer.train_steps(state, np.stack(xs), np.stack(ys))
        assert ms["ce"].shape == (3,)
        assert all(np.isfinite(np.asarray(ms["ce"])))


class TestEvalSteps:
    def test_chunked_eval_matches_single(self):
        def run(k):
            mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
            tcfg = TrainConfig(batch_size=8, bptt=6, steps_per_dispatch=k)
            trainer = LMTrainer(tiny_model(), tcfg, mesh=mesh, steps_per_epoch=8)
            dl = LMStreamLoader(repeating_corpus(), 8, 6, shuffle_offsets=False)
            state = trainer.init_state(jax.random.PRNGKey(0))
            with mesh:
                return trainer.evaluate(state, dl)

        a, b = run(1), run(3)
        assert a["val_loss"] == pytest.approx(b["val_loss"], rel=1e-6)
        assert a["val_accuracy"] == pytest.approx(b["val_accuracy"], rel=1e-6)


class TestStepsPerDispatch:
    def test_fit_chunked_matches_single_dispatch(self):
        # the SAME training run (deterministic loader, fixed seed) through
        # fit() with steps_per_dispatch=3 vs 1 — including a non-dividing
        # tail — must produce the same loss history and step count
        def run(k):
            mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
            tcfg = TrainConfig(batch_size=8, bptt=6, lr=5e-3, cycle_len=1,
                               steps_per_dispatch=k)
            trainer = LMTrainer(tiny_model(), tcfg, mesh=mesh, steps_per_epoch=8)
            dl = LMStreamLoader(repeating_corpus(), 8, 6, shuffle_offsets=False)
            steps = []

            class Rec:
                def on_train_begin(self, tr): ...
                def on_step_end(self, step, metrics):
                    steps.append((step, float(metrics["ce"])))
                def on_epoch_end(self, *a): ...
                def on_train_end(self, h): ...

            state, hist = trainer.fit(dl, epochs=1, callbacks=[Rec()],
                                      rng=jax.random.PRNGKey(0))
            return steps, hist

        s1, h1 = run(1)
        s3, h3 = run(3)
        assert [s for s, _ in s1] == [s for s, _ in s3]
        np.testing.assert_allclose([c for _, c in s1], [c for _, c in s3],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(h1[0]["loss"], h3[0]["loss"],
                                   rtol=1e-5, atol=1e-6)


class TestMeshExecution:
    def test_data_parallel_8(self):
        mesh = make_mesh({"data": 8})
        trainer = LMTrainer(tiny_model(), TrainConfig(batch_size=16, bptt=6), mesh=mesh)
        dl = LMStreamLoader(repeating_corpus(), 16, 6)
        state = trainer.init_state(jax.random.PRNGKey(0))
        with mesh:
            x, y = next(dl.epoch(0))
            state, m = trainer.train_step(state, x, y)
        assert np.isfinite(float(m["loss"]))

    def test_tensor_parallel_4x2(self):
        mesh = make_mesh({"data": 4, "model": 2})
        trainer = LMTrainer(tiny_model(), TrainConfig(batch_size=8, bptt=6), mesh=mesh)
        dl = LMStreamLoader(repeating_corpus(), 8, 6)
        state = trainer.init_state(jax.random.PRNGKey(0))
        with mesh:
            x, y = next(dl.epoch(0))
            state, m = trainer.train_step(state, x, y)
        assert np.isfinite(float(m["loss"]))

    def test_a_mesh_trains_on_the_scan_where_one_chip_would_be_resident(
            self, monkeypatch):
        # what was a refusal by name (a Mosaic call inside the
        # GSPMD-partitioned step: JAX refuses it on the chip at the first
        # dispatch, 4x v5e, PR 21): the rule sees the mesh and the step
        # runs the scan, single window and scanned dispatch, no raise
        _rule_sees_backend(monkeypatch, "tpu")
        mesh = make_mesh({"data": 8})
        trainer = LMTrainer(
            tiny_model(lstm_use_pallas=True),  # what a caller set: not read
            TrainConfig(batch_size=16, bptt=6), mesh=mesh)
        assert trainer.resident_lstm_layers == 0
        assert not trainer.model.config.lstm_use_pallas
        dl = LMStreamLoader(repeating_corpus(), 16, 6)
        state = trainer.init_state(jax.random.PRNGKey(0))
        it = dl.epoch(0)
        with mesh:
            x, y = next(it)
            state, m = trainer.train_step(state, x, y)
            assert np.isfinite(float(m["loss"]))
            xs, ys = zip(*(next(it) for _ in range(3)))
            state, ms = trainer.train_steps(state, np.stack(xs), np.stack(ys))
        assert np.isfinite(np.asarray(jax.device_get(ms["loss"]))).all()

    def test_qrnn_pallas_under_a_mesh_is_refused_by_name_on_tpu(
            self, monkeypatch):
        # the QRNN kernel is still a flag, so its refusal stays; the LSTM
        # cell is the step's own choice and has nothing left to refuse
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="qrnn_pallas.*shard_map"):
            LMTrainer(tiny_model(qrnn=True, qrnn_use_pallas=True),
                      TrainConfig(batch_size=16, bptt=6),
                      mesh=make_mesh({"data": 4, "model": 2}))
        for mesh in (make_mesh({"data": 8}),
                     make_mesh({"data": 1}, devices=jax.devices()[:1])):
            LMTrainer(tiny_model(lstm_use_pallas=True),
                      TrainConfig(batch_size=16, bptt=6), mesh=mesh)

    def test_mp_dispatches_reuse_one_program(self):
        # GSPMD hands the carried states back split over 'model' unless
        # they are pinned; the changed input signature then recompiled
        # the second dispatch (train.steps compiled twice under
        # --model_parallel 2 before PR 21)
        from code_intelligence_tpu.analysis import runtime as audit

        mesh = make_mesh({"data": 2, "model": 2}, devices=jax.devices()[:4])
        trainer = LMTrainer(tiny_model(),
                            TrainConfig(batch_size=8, bptt=6), mesh=mesh)
        dl = LMStreamLoader(repeating_corpus(), 8, 6)
        state = trainer.init_state(jax.random.PRNGKey(0))
        it = dl.epoch(0)

        def take(k):
            xs, ys = zip(*(next(it) for _ in range(k)))
            return np.stack(xs), np.stack(ys)

        with mesh, audit.recompile_guard(fn="train.steps", budget=1):
            for _ in range(3):
                state, _ = trainer.train_steps(state, *take(2))

    def test_dp_matches_single_device(self):
        # Same seed, same data: an 8-way DP step must equal the 1-device step.
        tok = repeating_corpus()
        results = {}
        for name, mesh in [
            ("single", make_mesh({"data": 1}, devices=jax.devices()[:1])),
            ("dp8", make_mesh({"data": 8})),
        ]:
            trainer = LMTrainer(tiny_model(), TrainConfig(batch_size=8, bptt=6), mesh=mesh)
            dl = LMStreamLoader(tok, 8, 6, shuffle_offsets=False)
            state = trainer.init_state(jax.random.PRNGKey(0))
            with mesh:
                for i, (x, y) in enumerate(dl.epoch(0)):
                    if i >= 3:
                        break
                    state, m = trainer.train_step(state, x, y)
            results[name] = float(m["ce"])
        assert results["single"] == pytest.approx(results["dp8"], rel=1e-4)


class TestFitAndCallbacks:
    def _fit(self, callbacks, epochs=4):
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        tcfg = TrainConfig(batch_size=8, bptt=6, lr=3e-3, cycle_len=epochs)
        trainer = LMTrainer(tiny_model(), tcfg, mesh=mesh, steps_per_epoch=20)
        tok = repeating_corpus(n=1200)
        dl = LMStreamLoader(tok, 8, 6, shuffle_offsets=False)
        vl = LMStreamLoader(repeating_corpus(n=600, seed=1), 8, 6, shuffle_offsets=False)
        return trainer.fit(dl, vl, epochs=epochs, callbacks=callbacks)

    def test_fit_returns_history_with_val(self):
        hist_cb = History()
        state, history = self._fit([hist_cb], epochs=2)
        assert len(history) == 2
        assert "val_loss" in history[0] and "val_perplexity" in history[0]
        assert hist_cb.epochs == history

    def test_early_stopping_stops(self):
        class Worsen(Callback := __import__("code_intelligence_tpu.training.callbacks", fromlist=["Callback"]).Callback):
            def on_epoch_end(self, epoch, metrics, state, trainer):
                metrics["val_loss"] = 1.0 + epoch  # strictly worsening
                return None

        es = EarlyStopping(monitor="val_loss", patience=0)
        state, history = self._fit([Worsen(), es], epochs=4)
        assert len(history) == 2  # epoch0 sets best, epoch1 triggers stop

    def test_reduce_lr_on_plateau_scales(self):
        class Flat(__import__("code_intelligence_tpu.training.callbacks", fromlist=["Callback"]).Callback):
            def on_epoch_end(self, epoch, metrics, state, trainer):
                metrics["val_loss"] = 5.0
                return None

        rl = ReduceLROnPlateau(patience=0, factor=0.5)
        state, history = self._fit([Flat(), rl], epochs=3)
        # epoch0 best; epochs1,2 plateau -> scaled twice
        assert float(state.lr_scale) == pytest.approx(0.25)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        trainer = LMTrainer(tiny_model(), TrainConfig(batch_size=4, bptt=5), mesh=mesh)
        state = trainer.init_state(jax.random.PRNGKey(0))
        dl = LMStreamLoader(repeating_corpus(n=600), 4, 5)
        with mesh:
            x, y = next(dl.epoch(0))
            state, _ = trainer.train_step(state, x, y)
        ckpt.save_checkpoint(tmp_path / "c", state, step=1)
        assert ckpt.latest_step(tmp_path / "c") == 1
        fresh = trainer.init_state(jax.random.PRNGKey(42))
        restored = ckpt.restore_checkpoint(tmp_path / "c", fresh)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
            state.params,
            restored.params,
        )
        assert int(restored.step) == 1

    def test_encoder_export_import(self, tmp_path):
        from code_intelligence_tpu.training.checkpoint import export_encoder, load_encoder

        mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
        cfg = tiny_model()
        trainer = LMTrainer(cfg, TrainConfig(batch_size=4, bptt=5), mesh=mesh)
        state = trainer.init_state(jax.random.PRNGKey(0))
        out = export_encoder(tmp_path / "enc", state.params, cfg)
        params, cfg2, vocab_path = load_encoder(out)
        assert cfg2.emb_sz == cfg.emb_sz and cfg2.vocab_size == cfg.vocab_size
        np.testing.assert_allclose(
            np.asarray(params["embedding"]),
            np.asarray(state.params["encoder"]["embedding"]),
        )


# -- the train step's cell: resident Pallas cell or XLA scan ----------------

def _rule_sees_backend(monkeypatch, backend):
    """The rule's first input, steered from the test (the trainer asks
    ``jax.default_backend()``, which is "cpu" here); its other inputs
    come from the config and the mesh as they do on the chip."""
    from code_intelligence_tpu.training import loop

    real = loop.train_cell_is_resident
    monkeypatch.setattr(
        loop, "train_cell_is_resident",
        lambda _backend, *rest: real(backend, *rest))


def _one_chip():
    return make_mesh({"data": 1}, devices=jax.devices()[:1])


# W_hh bytes at (itemsize, H) against the 52 MiB residency budget:
# bf16 800 -> 5 MB, 2500 -> 50 MB, 2610 -> 54.5 MB (the edge, inside);
# f32 800 -> 10 MB, 2500 -> 100 MB, 2610 -> 109 MB
_FITS = {(2, 800): True, (2, 2500): True, (2, 2610): True,
         (4, 800): True, (4, 2500): False, (4, 2610): False}


@pytest.mark.parametrize("mesh_size", [1, 4])
@pytest.mark.parametrize("hidden", [800, 2500, 2610])
@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("backend", ["tpu", "cpu", "gpu"])
def test_train_cell_rule_table(backend, itemsize, hidden, mesh_size):
    from code_intelligence_tpu.training.loop import train_cell_is_resident

    want = backend == "tpu" and mesh_size == 1 and _FITS[itemsize, hidden]
    assert train_cell_is_resident(
        backend, itemsize, hidden, mesh_size) is want


class TestTrainCell:
    def test_off_the_tpu_the_step_is_the_scan_and_says_so(self, monkeypatch):
        from code_intelligence_tpu.utils import tracing

        tracer = tracing.Tracer()
        monkeypatch.setattr(tracing, "_default", tracer)
        trainer = LMTrainer(
            tiny_model(), TrainConfig(batch_size=8, bptt=6,
                                      steps_per_dispatch=3),
            mesh=_one_chip(), steps_per_epoch=40)
        assert trainer.resident_lstm_layers == 0
        assert not trainer.model.config.lstm_use_pallas
        got = []
        tracer.on_trace(got.append)
        dl = LMStreamLoader(repeating_corpus(), 8, 6)
        trainer.fit(_FirstWindows(dl, 3), None, epochs=1)
        by_root = {t["root"]: t for t in got}
        for root in ("train.dispatch", "train.fit"):
            attrs = by_root[root]["spans"][0]["attrs"]
            assert attrs["resident_lstm_layers"] == 0, (root, attrs)

    def test_each_layer_decides_for_itself(self, monkeypatch):
        # float32 at H=2500 is 100 MB and stays on the scan; the last
        # layer (emb_sz wide) of the same model fits and is resident
        _rule_sees_backend(monkeypatch, "tpu")
        for dtype, want in ((jnp.float32, 1), (jnp.bfloat16, 4)):
            trainer = LMTrainer(
                AWDLSTMConfig(vocab_size=50, dtype=dtype),  # the defaults
                TrainConfig(batch_size=8, bptt=6), mesh=_one_chip())
            assert trainer.resident_lstm_layers == want
            assert trainer.model.config.lstm_use_pallas
        assert LMTrainer(
            tiny_model(qrnn=True), TrainConfig(batch_size=8, bptt=6),
            mesh=_one_chip()).resident_lstm_layers == 0

    def test_resident_steps_equal_the_scans(self, monkeypatch):
        # the rule's answer forced to "resident" (the kernels in interpret
        # mode, tiny widths): the first three steps' loss and gradient
        # norm are the scan's, in TestTrainSteps' band, and so are the
        # dropout draws (the recurrent mask is drawn before the branch)
        tcfg = TrainConfig(batch_size=8, bptt=6, lr=5e-3,
                           steps_per_dispatch=3)
        scan = LMTrainer(tiny_model(), tcfg, mesh=_one_chip(),
                         steps_per_epoch=40)
        _rule_sees_backend(monkeypatch, "tpu")
        resident = LMTrainer(tiny_model(), tcfg, mesh=_one_chip(),
                             steps_per_epoch=40)
        assert (scan.resident_lstm_layers,
                resident.resident_lstm_layers) == (0, 2)
        dl = LMStreamLoader(repeating_corpus(), 8, 6, shuffle_offsets=False)
        it = dl.epoch(0)
        xs, ys = map(np.stack, zip(*(next(it) for _ in range(3))))
        out = {}
        for name, trainer in (("scan", scan), ("resident", resident)):
            state = trainer.init_state(jax.random.PRNGKey(0))
            drawn = trainer.model.apply(
                {"params": state.params}, xs[0], state.lstm_states,
                deterministic=False,
                rngs={"dropout": jax.random.PRNGKey(7)})[2]
            with trainer.mesh:
                _, ms = trainer.train_steps(state, xs, ys)
            out[name] = (jax.device_get(ms), np.asarray(drawn))
        for key in ("loss", "grad_norm"):
            np.testing.assert_allclose(
                out["resident"][0][key], out["scan"][0][key],
                rtol=1e-5, atol=1e-6)
        # same masks: a different draw would move whole units to zero
        np.testing.assert_allclose(out["resident"][1], out["scan"][1],
                                   rtol=1e-5, atol=1e-6)
        assert ((out["resident"][1] == 0) == (out["scan"][1] == 0)).all()


class _FirstWindows:
    """The first ``n`` windows of a loader, as a loader."""

    def __init__(self, loader, n):
        self.loader, self.n = loader, n
        self.local_bs = loader.local_bs
        self.tokens_per_epoch = n * loader.local_bs * loader.bptt

    def epoch(self, epoch):
        for i, xy in enumerate(self.loader.epoch(epoch)):
            if i >= self.n:
                return
            yield xy

