"""Embedding distillation: the student must (a) converge toward the
teacher's pooled embeddings, (b) export as a drop-in encoder for the
inference engine with the same pooled dim (wire contract), and (c) carry
the Pallas-resident flag in its exported config."""

import json

import jax
import numpy as np
import pytest

from code_intelligence_tpu.models import AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states
from code_intelligence_tpu.text import SPECIALS, Vocab
from code_intelligence_tpu.training.distill import DistillConfig, EmbeddingDistiller


@pytest.fixture(scope="module")
def teacher():
    cfg = AWDLSTMConfig(vocab_size=60, emb_sz=8, n_hid=16, n_layers=2)
    enc = AWDLSTMEncoder(cfg)
    params = enc.init(
        {"params": jax.random.PRNGKey(1)},
        np.zeros((1, 4), np.int32),
        init_lstm_states(cfg, 1),
    )["params"]
    return params, cfg


def _docs(n, rng):
    return [rng.randint(2, 60, size=rng.randint(6, 20)).astype(np.int32)
            for _ in range(n)]


class TestDistill:
    def test_student_converges_toward_teacher(self, teacher):
        params, cfg = teacher
        dcfg = DistillConfig(n_hid=8, n_layers=2, max_len=24, batch_size=8,
                             steps=120, lr=5e-3, lstm_use_pallas=False)
        d = EmbeddingDistiller(params, cfg, dcfg)
        d.init()
        rng = np.random.RandomState(0)
        train, held = _docs(64, rng), _docs(16, rng)
        before = d.evaluate(held)
        history = d.fit(train, log_every=40)
        after = d.evaluate(held)
        assert after["mean_cosine"] > before["mean_cosine"] + 0.15, (
            before, after)
        assert history[-1]["loss"] < history[0]["loss"]

    def test_export_is_drop_in_for_inference_engine(self, teacher, tmp_path):
        from code_intelligence_tpu.inference import InferenceEngine

        params, cfg = teacher
        dcfg = DistillConfig(n_hid=8, n_layers=2, max_len=24, batch_size=8,
                             steps=10, lstm_use_pallas=True)
        d = EmbeddingDistiller(params, cfg, dcfg)
        # the flag is what the export carries; the distillation step's own
        # cell is the train-side rule's, which off the TPU is the scan
        assert d.student_cfg.lstm_use_pallas
        assert not d.student_enc.config.lstm_use_pallas
        d.init()
        d.fit(_docs(16, np.random.RandomState(1)), log_every=10)
        vocab = Vocab(SPECIALS + [f"w{i}" for i in range(60 - len(SPECIALS))])
        out = d.export(tmp_path / "student", vocab)
        # exported config keeps the wire contract and the Pallas flag
        meta = json.loads((out / "model_config.json").read_text())
        assert meta["emb_sz"] == cfg.emb_sz and meta["n_hid"] == 8
        assert meta["lstm_use_pallas"] is True
        engine = InferenceEngine.from_export(out, batch_size=2, buckets=(16,))
        emb = engine.embed_issue("w1 w2", "w3 w4")
        assert emb.shape == (3 * cfg.emb_sz,)
        assert np.isfinite(emb).all()

    @pytest.mark.parametrize("backend,flag,resident", [
        ("tpu", True, True), ("tpu", False, True),
        ("cpu", True, False), ("cpu", False, False)])
    def test_the_distillation_step_takes_the_train_side_rule(
            self, teacher, monkeypatch, backend, flag, resident):
        # float32 on one device: resident on the TPU wherever W_hh fits,
        # whatever the export is told to carry
        from code_intelligence_tpu.training import loop

        real = loop.train_cell_is_resident
        monkeypatch.setattr(loop, "train_cell_is_resident",
                            lambda _b, *rest: real(backend, *rest))
        params, cfg = teacher
        d = EmbeddingDistiller(params, cfg, DistillConfig(
            n_hid=8, n_layers=2, lstm_use_pallas=flag))
        assert d.student_enc.config.lstm_use_pallas is resident
        assert d.student_cfg.lstm_use_pallas is flag

    def test_student_cannot_exceed_teacher_width(self, teacher):
        params, cfg = teacher
        with pytest.raises(ValueError):
            EmbeddingDistiller(params, cfg, DistillConfig(n_hid=32))

    def test_pallas_flag_requires_residency_at_export_dtype(self):
        # n_hid=2048 is resident in bf16 (33.5MB W_hh) but NOT in f32
        # (67MB > the ~52MB VMEM-scope budget) — asking for the Pallas
        # student with an f32 export must fail loudly, not silently fall
        # back to the HBM-streaming scan at serve time. (Round 3 raised
        # the residency budget to v5e reality, so the boundary moved:
        # every H<=1800-class f32 and H<=2500-class bf16 is resident.)
        big = AWDLSTMConfig(vocab_size=60, emb_sz=8, n_hid=2500, n_layers=2)
        with pytest.raises(ValueError, match="resident"):
            EmbeddingDistiller(None, big, DistillConfig(
                n_hid=2048, export_dtype="float32"))
        # bf16 default is fine
        EmbeddingDistiller(None, big, DistillConfig(n_hid=2048))


class TestDispatchBatching:
    def test_k_invariant_batch_order(self, teacher):
        # steps_per_dispatch must not change the training run: same rng
        # draw order -> same batches -> (numerically close) same history
        params, cfg = teacher
        rng = np.random.RandomState(3)
        docs = _docs(40, rng)

        def run(k):
            dcfg = DistillConfig(n_hid=8, n_layers=2, max_len=24,
                                 batch_size=8, steps=12, lr=5e-3,
                                 steps_per_dispatch=k,
                                 lstm_use_pallas=False)
            d = EmbeddingDistiller(params, cfg, dcfg)
            d.init()
            return d.fit(docs, log_every=1)

        h1, h5 = run(1), run(5)
        assert [m["step"] for m in h1] == [m["step"] for m in h5]
        for a, b in zip(h1, h5):
            assert abs(a["loss"] - b["loss"]) < 1e-4, (a, b)

    def test_ragged_tail_dispatch(self, teacher):
        # steps not divisible by k: the short final chunk still runs and
        # the last logical step is logged
        params, cfg = teacher
        dcfg = DistillConfig(n_hid=8, n_layers=2, max_len=24, batch_size=8,
                             steps=7, lr=5e-3, steps_per_dispatch=5,
                             lstm_use_pallas=False)
        d = EmbeddingDistiller(params, cfg, dcfg)
        d.init()
        h = d.fit(_docs(20, np.random.RandomState(4)), log_every=3)
        assert h[-1]["step"] == 6
