"""End-to-end quality-parity harness.

The reference publishes its quality numbers as notebook outputs — weighted
AUC 0.9169 for the fine-tuned sig-label classifier
(`Issue_Embeddings/notebooks/08_Train_Repo_Specific_IssueLabeler.ipynb`
cell 20), per-label AUC 0.70-0.99 (`06_FineTune.ipynb` cell 64), MLP test
AUC 0.760 (`Label_Microservice/notebooks/repo_mlp.ipynb` cells 32-33).
This harness reproduces the same *pipeline* as one scripted, resumable
run over the generative corpus (`data/synthetic.py`) and emits a single
JSON report with those numbers side by side:

    python -m code_intelligence_tpu.quality.harness \
        --workdir /tmp/quality --preset full --out QUALITY.json

Stages (each writes ``stage_<name>.json`` into the workdir and is skipped
on re-run, so an interrupted run resumes where it stopped):

* ``gen``    — generate issues; build the LM corpus (train/valid) through
               the real text pipeline; write labeled classifier splits.
* ``lm``     — pretrain the AWD-LSTM LM (`training/cli.py`), record val
               loss/perplexity; export the encoder.
* ``ft``     — LM -> classifier fine-tune with gradual unfreezing
               (`training/fine_tune.py`); per-label AUC, weighted AUC,
               macro-F1 on a held-out test split.
* ``mlp``    — embed the labeled issues with the inference engine
               (2400-d pooled, truncated to 1600-d — the reference's
               contract, `repo_specific_model.py:182`), train the Flax
               MLP head (`labels/mlp.py`), test AUC + thresholds.
* ``distill`` — distill the flagship encoder into the Pallas-resident
               serving student (`training/distill.py`); holdout cosine,
               engine-direct serving A/B (docs/sec teacher vs student),
               and the downstream-AUC-preserved check (MLP head on
               student embeddings vs the ``mlp`` stage's teacher AUC).
* ``universal`` — train the GRU-tower universal kind model on the labeled
               split, report held-out accuracy/per-class AUC, and
               re-derive the .52/.60 thresholds from PR curves on a
               validation slice carved from train.
* ``report`` — assemble the side-by-side JSON.

The ``smoke`` preset runs the identical code path at toy scale on CPU
(used by tests); ``full`` is the flagship-scale on-chip run.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

log = logging.getLogger("quality")

# Reference quality numbers (BASELINE.md / SURVEY.md §6, notebook outputs).
REFERENCE = {
    "fine_tuned_weighted_auc": 0.9169,   # 08_Train_Repo_Specific... cell 20
    "fine_tuned_per_label_auc_band": [0.70, 0.99],  # 06_FineTune.ipynb cell 64
    "mlp_test_weighted_auc": 0.760,      # repo_mlp.ipynb cells 32-33
    "mlp_train_weighted_auc": 0.793,
}


@dataclasses.dataclass
class QualityConfig:
    workdir: Path
    # corpus scale
    n_lm_issues: int = 120_000
    n_train_issues: int = 14_000
    n_test_issues: int = 3_000
    max_vocab: int = 60_000
    tokenize_workers: int = 8
    # LM hyperparameters (reference flagship: train.py:42-46, sweep best)
    emb_sz: int = 800
    n_hid: int = 2500
    n_layers: int = 4
    bs: int = 96
    bptt: int = 67
    lr: float = 1.3e-3
    cycle_len: int = 3
    bf16: bool = True
    # fine-tune / head
    ft_epochs: Sequence[int] = (1, 1, 2)
    ft_batch_size: int = 32
    ft_max_len: int = 400
    ft_lr: float = 1e-2
    mlp_truncate: int = 1600          # embeddings.py:116 contract
    # universal kind-model sizing (GRU towers)
    uni_emb_dim: int = 64
    uni_hidden: int = 128
    uni_title_len: int = 32
    uni_body_len: int = 256
    # optional caps for the mlp stage (CPU-fallback scale when the chip is
    # down); when set, the stage subsets the splits and stamps _scale_note
    mlp_max_train: Optional[int] = None
    mlp_max_test: Optional[int] = None
    # distilled serving student (round-3 VERDICT next #4: full-scale A/B)
    distill_n_hid: int = 1024      # every layer Pallas-resident in bf16
    distill_steps: int = 1500
    distill_batch_size: int = 16
    distill_max_len: int = 400
    seed: int = 0

    @classmethod
    def smoke(cls, workdir) -> "QualityConfig":
        return cls(
            workdir=Path(workdir),
            n_lm_issues=300,
            n_train_issues=120,
            n_test_issues=60,
            max_vocab=6000,
            tokenize_workers=0,
            emb_sz=24,
            n_hid=32,
            n_layers=2,
            bs=8,
            bptt=24,
            cycle_len=1,
            bf16=False,
            ft_epochs=(1, 1),
            ft_batch_size=8,
            ft_max_len=96,
            mlp_truncate=48,
            uni_emb_dim=12,
            uni_hidden=16,
            uni_title_len=12,
            uni_body_len=48,
            distill_n_hid=16,
            distill_steps=30,
            distill_batch_size=8,
            distill_max_len=64,
        )

    @classmethod
    def full(cls, workdir) -> "QualityConfig":
        return cls(workdir=Path(workdir))


# ---------------------------------------------------------------------------
# Stage plumbing
# ---------------------------------------------------------------------------


def _platform() -> str:
    """Provenance stamp: which backend produced a stage's numbers. Some
    stages may legitimately be CPU runs — the report must say which.

    Only called from stages that already ran jax compute, so the backend is
    initialized and this cannot trigger (possibly-hanging) device discovery;
    host-only stages (gen, oracle) are stamped as constants instead."""
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "unknown"


def _stage_path(cfg: QualityConfig, name: str) -> Path:
    return cfg.workdir / f"stage_{name}.json"


def _stage_done(cfg: QualityConfig, name: str) -> Optional[dict]:
    p = _stage_path(cfg, name)
    if p.exists():
        return json.loads(p.read_text())
    return None


def _atomic_write_json(path: Path, obj: dict) -> None:
    """tmp+rename: a SIGKILL mid-write (stage timeout, OOM-killer) must
    never truncate a stage marker or the accumulated report."""
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(json.dumps(obj, indent=1))
    os.replace(tmp, path)


def _stage_write(cfg: QualityConfig, name: str, payload: dict) -> dict:
    _atomic_write_json(_stage_path(cfg, name), payload)
    return payload


# ---------------------------------------------------------------------------
# gen
# ---------------------------------------------------------------------------


def stage_gen(cfg: QualityConfig) -> dict:
    from code_intelligence_tpu.data.corpus import build_corpus
    from code_intelligence_tpu.data.synthetic import (
        ALL_LABELS,
        SyntheticIssueGenerator,
        issue_texts,
    )
    from code_intelligence_tpu.text import rules

    t0 = time.time()
    gen = SyntheticIssueGenerator()
    cfg.workdir.mkdir(parents=True, exist_ok=True)

    # LM split: indices [0, n_lm); labeled splits follow so they never leak
    # into LM pretraining text.
    log.info("generating %d LM issues", cfg.n_lm_issues)
    texts = issue_texts(gen, 0, cfg.n_lm_issues)
    train, valid = build_corpus(
        texts,
        cfg.workdir / "corpus",
        max_vocab=cfg.max_vocab,
        min_freq=2,
        n_workers=cfg.tokenize_workers,
        seed=cfg.seed,
    )

    def dump_labeled(name: str, start: int, count: int) -> Path:
        path = cfg.workdir / f"issues_{name}.jsonl"
        with path.open("w", encoding="utf-8") as f:
            for iss in gen.issues(start, count):
                f.write(json.dumps({
                    "text": rules.build_issue_text(iss.title, iss.body),
                    "labels": iss.labels,
                    "true_area": iss.true_area,
                    "true_kind": iss.true_kind,
                }) + "\n")
        return path

    log.info("generating labeled splits")
    dump_labeled("train", cfg.n_lm_issues, cfg.n_train_issues)
    dump_labeled("test", cfg.n_lm_issues + cfg.n_train_issues, cfg.n_test_issues)

    return _stage_write(cfg, "gen", {
        "train_tokens": train.total_tokens,
        "valid_tokens": valid.total_tokens,
        "vocab_size": len(train.vocab),
        "n_labels": len(ALL_LABELS),
        "labels": list(ALL_LABELS),
        "unigram_entropy_bits": gen.unigram_entropy_bits(),
        "topic_conditional_entropy_bits": gen.topic_conditional_entropy_bits(),
        "_elapsed_s": round(time.time() - t0, 1),
        # no _platform stamp: gen is pure-host numpy and must stay jax-free
    })


# ---------------------------------------------------------------------------
# lm
# ---------------------------------------------------------------------------


def stage_lm(cfg: QualityConfig) -> dict:
    from code_intelligence_tpu.training import cli as train_cli

    t0 = time.time()
    argv = [
        "--corpus_dir", str(cfg.workdir / "corpus"),
        "--model_dir", str(cfg.workdir / "lm"),
        "--bs", str(cfg.bs), "--bptt", str(cfg.bptt),
        "--emb_sz", str(cfg.emb_sz), "--n_hid", str(cfg.n_hid),
        "--n_layers", str(cfg.n_layers),
        "--lr", str(cfg.lr), "--cycle_len", str(cfg.cycle_len),
        "--seed", str(cfg.seed),
        "--resume",
    ]
    if cfg.bf16:
        argv.append("--bf16")
    summary = train_cli.main(argv)
    out = {
        "val_loss": summary.get("val_loss"),
        "val_perplexity": summary.get("val_perplexity"),
        "val_accuracy": summary.get("val_accuracy"),
        "epochs": cfg.cycle_len,
        "_elapsed_s": round(time.time() - t0, 1),
        "_platform": _platform(),
    }
    return _stage_write(cfg, "lm", out)


# ---------------------------------------------------------------------------
# labeled-data helpers
# ---------------------------------------------------------------------------


def _load_labeled(cfg: QualityConfig, name: str, vocab, labels: List[str]):
    from code_intelligence_tpu.text.tokenizer import Tokenizer

    tok = Tokenizer(backend="auto")
    X: List[np.ndarray] = []
    Y = []
    with (cfg.workdir / f"issues_{name}.jsonl").open() as f:
        for line in f:
            rec = json.loads(line)
            # text is already pre-ruled (build_issue_text); tokenize only
            ids = vocab.numericalize(tok.tokenize_pre_processed(rec["text"]))
            X.append(np.asarray(ids, np.int32))
            row = np.zeros((len(labels),), np.float32)
            for l in rec["labels"]:
                if l in labels:
                    row[labels.index(l)] = 1.0
            Y.append(row)
    return X, np.stack(Y)


def _macro_f1(y: np.ndarray, probs: np.ndarray, thresholds: np.ndarray) -> float:
    f1s = []
    for j in range(y.shape[1]):
        pred = probs[:, j] >= thresholds[j]
        tp = float((pred & (y[:, j] > 0)).sum())
        fp = float((pred & (y[:, j] == 0)).sum())
        fn = float(((~pred) & (y[:, j] > 0)).sum())
        if tp == 0:
            f1s.append(0.0)
            continue
        prec, rec = tp / (tp + fp), tp / (tp + fn)
        f1s.append(2 * prec * rec / (prec + rec))
    return float(np.mean(f1s))


def _best_f1_thresholds(y: np.ndarray, probs: np.ndarray) -> np.ndarray:
    """Per-label threshold maximizing F1 on the given (validation) split."""
    out = np.full((y.shape[1],), 0.5)
    grid = np.linspace(0.05, 0.95, 19)
    for j in range(y.shape[1]):
        if y[:, j].min() == y[:, j].max():
            continue
        best, best_t = -1.0, 0.5
        for t in grid:
            f1 = _macro_f1(y[:, j : j + 1], probs[:, j : j + 1], np.array([t]))
            if f1 > best:
                best, best_t = f1, t
        out[j] = best_t
    return out


# ---------------------------------------------------------------------------
# ft
# ---------------------------------------------------------------------------


def stage_ft(cfg: QualityConfig) -> dict:
    import jax.numpy as jnp

    from code_intelligence_tpu.data.corpus import TokenCorpus
    from code_intelligence_tpu.models import AWDLSTMConfig
    from code_intelligence_tpu.models.classifier import ClassifierConfig
    from code_intelligence_tpu.training.checkpoint import load_encoder
    from code_intelligence_tpu.training.fine_tune import FineTuneConfig, FineTuner

    t0 = time.time()
    gen_info = _stage_done(cfg, "gen")
    labels = gen_info["labels"]
    corpus = TokenCorpus(cfg.workdir / "corpus" / "train")
    vocab = corpus.vocab
    X, y = _load_labeled(cfg, "train", vocab, labels)
    X_test, y_test = _load_labeled(cfg, "test", vocab, labels)

    enc_params, _, _ = load_encoder(cfg.workdir / "lm" / "encoder_export")

    mcfg = AWDLSTMConfig(
        vocab_size=len(vocab),
        emb_sz=cfg.emb_sz,
        n_hid=cfg.n_hid,
        n_layers=cfg.n_layers,
        pad_id=vocab.pad_id,
        dtype=jnp.bfloat16 if cfg.bf16 else jnp.float32,
    )
    ccfg = ClassifierConfig(encoder=mcfg, n_labels=len(labels), multi_label=True)
    ft = FineTuner(
        ccfg,
        FineTuneConfig(
            lr=cfg.ft_lr,
            epochs_per_stage=tuple(cfg.ft_epochs),
            batch_size=cfg.ft_batch_size,
            max_len=cfg.ft_max_len,
            seed=cfg.seed,
        ),
        pretrained_encoder=enc_params,
    )
    history = ft.fit_gradual(X, y, X_val=X_test, y_val=y_test)

    probs = ft.predict_proba(X_test)
    # persist per-doc test probabilities: the oracle stage pairs them with
    # its own scores for a paired-bootstrap margin CI (the statistically
    # valid "at the frontier" test — shared slice variance cancels)
    np.savez(cfg.workdir / "ft_test_probs.npz",
             probs=np.asarray(probs), labels=np.asarray(labels))
    final = history[-1] if history else {}
    per_label = {
        labels[int(k)]: v for k, v in (final.get("per_label_auc") or {}).items()
    }
    # thresholds tuned on a train subsample (threshold curves stabilize
    # well below full-corpus size; 500+ sequential device calls are the
    # actual cost), F1 reported on test
    n_fit = min(len(X), 3000)
    probs_tr = ft.predict_proba(X[:n_fit])
    th = _best_f1_thresholds(y[:n_fit], probs_tr)
    out = {
        "weighted_auc": final.get("weighted_auc"),
        "per_label_auc": per_label,
        "macro_f1_at_0.5": _macro_f1(y_test, probs, np.full(len(labels), 0.5)),
        "macro_f1_at_best": _macro_f1(y_test, probs, th),
        "thresholds": {labels[j]: float(th[j]) for j in range(len(labels))},
        "stages": [{k: v for k, v in h.items() if k != "per_label_auc"} for h in history],
        "n_train": len(X),
        "n_test": len(X_test),
        "_elapsed_s": round(time.time() - t0, 1),
        "_platform": _platform(),
    }
    return _stage_write(cfg, "ft", out)


# ---------------------------------------------------------------------------
# mlp
# ---------------------------------------------------------------------------


def stage_mlp(cfg: QualityConfig) -> dict:
    from code_intelligence_tpu.data.corpus import TokenCorpus
    from code_intelligence_tpu.inference import InferenceEngine
    from code_intelligence_tpu.labels.mlp import MLPHead

    t0 = time.time()
    gen_info = _stage_done(cfg, "gen")
    labels = gen_info["labels"]
    corpus = TokenCorpus(cfg.workdir / "corpus" / "train")
    vocab = corpus.vocab

    engine = InferenceEngine.from_export(cfg.workdir / "lm" / "encoder_export")
    X, y = _load_labeled(cfg, "train", vocab, labels)
    X_test, y_test = _load_labeled(cfg, "test", vocab, labels)
    scale_note = None
    if cfg.mlp_max_train or cfg.mlp_max_test:
        full = (len(X), len(X_test))
        X, y = X[: cfg.mlp_max_train], y[: cfg.mlp_max_train]
        X_test, y_test = X_test[: cfg.mlp_max_test], y_test[: cfg.mlp_max_test]
        scale_note = (
            f"reduced scale: {len(X)} train / {len(X_test)} test of the "
            f"{full[0]}/{full[1]} split (mlp_max_train/mlp_max_test caps — "
            "typically a CPU fallback while the chip is down)")

    def embed(seqs: List[np.ndarray]) -> np.ndarray:
        emb = engine.embed_ids_batch(seqs)
        return emb[:, : cfg.mlp_truncate]  # reference 1600-d truncation

    E, E_test = embed(X), embed(X_test)
    head = MLPHead(seed=cfg.seed)
    head.fit(E, y)
    head.find_probability_thresholds(E, y)
    train_aucs, train_weighted = head.calculate_auc(E, y)
    test_aucs, test_weighted = head.calculate_auc(E_test, y_test)
    out = {
        "embedding_dim": int(E.shape[1]),
        "train_weighted_auc": train_weighted,
        "test_weighted_auc": test_weighted,
        "test_per_label_auc": {labels[int(k)]: v for k, v in test_aucs.items()},
        "n_train": len(X),
        "n_test": len(X_test),
        "_elapsed_s": round(time.time() - t0, 1),
        "_platform": _platform(),
    }
    if scale_note:
        out["_scale_note"] = scale_note
    return _stage_write(cfg, "mlp", out)


# ---------------------------------------------------------------------------
# distill (Pallas-resident serving student: fidelity + serving A/B +
# downstream-AUC-preserved check — round-3 VERDICT next #4)
# ---------------------------------------------------------------------------


def stage_distill(cfg: QualityConfig) -> dict:
    import dataclasses as _dc
    import time as _time

    from code_intelligence_tpu.data.corpus import TokenCorpus
    from code_intelligence_tpu.inference import InferenceEngine
    from code_intelligence_tpu.labels.mlp import MLPHead
    from code_intelligence_tpu.training.checkpoint import load_encoder
    from code_intelligence_tpu.training.distill import (
        DistillConfig,
        EmbeddingDistiller,
    )

    t0 = time.time()
    gen_info = _stage_done(cfg, "gen")
    labels = gen_info["labels"]
    corpus = TokenCorpus(cfg.workdir / "corpus" / "train")
    vocab = corpus.vocab
    X, y = _load_labeled(cfg, "train", vocab, labels)
    X_test, y_test = _load_labeled(cfg, "test", vocab, labels)

    teacher_dir = cfg.workdir / "lm" / "encoder_export"
    teacher_params, teacher_cfg, _ = load_encoder(teacher_dir)
    teacher_cfg = _dc.replace(teacher_cfg, vocab_size=len(vocab))
    dcfg = DistillConfig(
        n_hid=cfg.distill_n_hid,
        n_layers=cfg.n_layers,
        steps=cfg.distill_steps,
        batch_size=cfg.distill_batch_size,
        max_len=cfg.distill_max_len,
        seed=cfg.seed,
        # what the student's EXPORT carries for the serve side (smoke
        # students are tiny: the residency promise only makes sense at
        # serving scale). The distillation step's cell is the train-side
        # rule's, not this (training/loop.py::train_cell_config).
        lstm_use_pallas=cfg.distill_n_hid >= 128,
    )
    distiller = EmbeddingDistiller(teacher_params, teacher_cfg, dcfg)
    history = distiller.fit(X)
    fidelity = distiller.evaluate(X_test)
    student_dir = cfg.workdir / "student_export"
    distiller.export(student_dir, vocab)

    # --- serving A/B: engine-direct docs/sec, teacher vs student -------
    def rate(engine, seqs, reps: int = 3) -> float:
        engine.embed_ids_batch(seqs)  # compile
        best = float("inf")
        for _ in range(reps):
            s = _time.perf_counter()
            engine.embed_ids_batch(seqs)  # host materialization = sync
            best = min(best, _time.perf_counter() - s)
        return len(seqs) / best

    ab_seqs = X_test[: min(len(X_test), 64)]
    teacher_eng = InferenceEngine.from_export(teacher_dir, batch_size=32)
    student_eng = InferenceEngine.from_export(student_dir, batch_size=32)
    rt, rs = rate(teacher_eng, ab_seqs), rate(student_eng, ab_seqs)

    # --- downstream-AUC preserved: MLP head on STUDENT embeddings ------
    def embed(engine, seqs):
        return engine.embed_ids_batch(seqs)[:, : cfg.mlp_truncate]

    E, E_test = embed(student_eng, X), embed(student_eng, X_test)
    head = MLPHead(seed=cfg.seed)
    head.fit(E, y)
    _, train_auc = head.calculate_auc(E, y)
    _, test_auc = head.calculate_auc(E_test, y_test)
    teacher_mlp = _stage_done(cfg, "mlp") or {}
    teacher_test_auc = teacher_mlp.get("test_weighted_auc")

    out = {
        "student": {
            "n_hid": cfg.distill_n_hid,
            "n_layers": cfg.n_layers,
            "steps": cfg.distill_steps,
            "lstm_use_pallas": dcfg.lstm_use_pallas,
            "export_dtype": dcfg.export_dtype,
        },
        "holdout_cosine": fidelity["mean_cosine"],
        "holdout_mse": fidelity["mean_mse"],
        "train_history_tail": history[-1] if history else None,
        "serving_ab": {
            "teacher_docs_per_sec": round(rt, 2),
            "student_docs_per_sec": round(rs, 2),
            "speedup": round(rs / rt, 3) if rt else None,
        },
        "downstream_mlp": {
            "student_train_weighted_auc": train_auc,
            "student_test_weighted_auc": test_auc,
            "teacher_test_weighted_auc": teacher_test_auc,
            "auc_delta_vs_teacher": (
                round(test_auc - teacher_test_auc, 4)
                if teacher_test_auc is not None else None
            ),
        },
        "_elapsed_s": round(time.time() - t0, 1),
        "_platform": _platform(),
    }
    return _stage_write(cfg, "distill", out)


# ---------------------------------------------------------------------------
# universal (kind classifier: sequence towers + derived thresholds)
# ---------------------------------------------------------------------------


# the reference's production operating point (universal_kind_label_model.py:50-51)
REFERENCE_THRESHOLDS = {"bug": 0.52, "feature": 0.52, "question": 0.60}


def _carve_val(titles, bodies, kinds):
    """Split off the validation slice used for threshold derivation — the
    reported test metrics must never see threshold fitting. One rule for
    the easy corpus and the noisy sub-stage, or their comparison breaks."""
    n_val = max(10, len(kinds) // 10)
    train = (titles[:-n_val], bodies[:-n_val], kinds[:-n_val])
    val = (titles[-n_val:], bodies[-n_val:], kinds[-n_val:])
    return train, val


def _fit_universal(cfg: QualityConfig, titles, bodies, kinds):
    """Train the GRU-tower kind model with the harness's sizing — shared by
    the easy-corpus stage and the noisy sub-stage so a hyperparameter tune
    cannot silently apply to only one of them."""
    from code_intelligence_tpu.labels.universal import train_universal_model

    return train_universal_model(
        titles, bodies, kinds,
        epochs=4 if cfg.n_train_issues > 1000 else 8,
        seed=cfg.seed,
        max_vocab=min(20000, cfg.max_vocab),
        module_kwargs={
            "emb_dim": cfg.uni_emb_dim,
            "hidden": cfg.uni_hidden,
            "title_len": cfg.uni_title_len,
            "body_len": cfg.uni_body_len,
        },
    )


def stage_universal(cfg: QualityConfig) -> dict:
    from code_intelligence_tpu.labels.universal import (
        derive_thresholds,
        evaluate_at_thresholds,
        evaluate_universal,
    )

    t0 = time.time()

    def load_kind_split(name: str):
        titles, bodies, kinds = [], [], []
        with (cfg.workdir / f"issues_{name}.jsonl").open() as f:
            for line in f:
                rec = json.loads(line)
                # field contract text carries both parts; split them back
                text = rec["text"]
                title, _, body = text.partition(" xxxfldbody ")
                titles.append(title.replace("xxxfldtitle ", "", 1))
                bodies.append(body)
                kinds.append({"kind/bug": 0, "kind/feature": 1, "kind/question": 2}[
                    rec["true_kind"]])
        return titles, bodies, kinds

    from code_intelligence_tpu.labels.universal import predict_probabilities_batch

    tr_t, tr_b, tr_k = load_kind_split("train")
    te_t, te_b, te_k = load_kind_split("test")
    (tr_t, tr_b, tr_k), (va_t, va_b, va_k) = _carve_val(tr_t, tr_b, tr_k)
    model = _fit_universal(cfg, tr_t, tr_b, tr_k)
    test_probs = predict_probabilities_batch(model, te_t, te_b)
    report = evaluate_universal(model, te_t, te_b, te_k, probs=test_probs)
    thresholds = derive_thresholds(model, va_t, va_b, va_k)
    model.thresholds = thresholds
    model.save(cfg.workdir / "universal_model")

    # Noisy-kind sub-stage (round-3 VERDICT weak #5): on the main corpus
    # the model is accurate enough that derived thresholds degenerate to
    # ~1e-5 — the 0.52/0.60-style operating point is never exercised. Rerun
    # train -> derive -> operate on the noisy_kind preset (weak kind
    # signal, 20% label flips, 25% signal-free docs), training on the
    # EMITTED noisy labels like the reference trained on human labels, so
    # the PR-curve logic faces real precision/recall trade-offs.
    noisy = _universal_noisy_substage(cfg)

    out = {
        "tower": model.module.tower,
        "test_accuracy": report["accuracy"],
        "per_class_auc": report["per_class_auc"],
        "derived_thresholds": thresholds,
        "at_derived_thresholds": evaluate_at_thresholds(
            test_probs, te_k, thresholds),
        "reference_thresholds": dict(REFERENCE_THRESHOLDS),
        "noisy_kind": noisy,
        "n_train": len(tr_k),
        "n_test": len(te_k),
        "_elapsed_s": round(time.time() - t0, 1),
        "_platform": _platform(),
    }
    return _stage_write(cfg, "universal", out)


def _universal_noisy_substage(cfg: QualityConfig) -> dict:
    from code_intelligence_tpu.data.synthetic import (
        KIND_LABELS,
        SyntheticConfig,
        SyntheticIssueGenerator,
    )
    from code_intelligence_tpu.labels.universal import (
        derive_thresholds,
        evaluate_at_thresholds,
        evaluate_universal,
        predict_probabilities_batch,
    )

    gen = SyntheticIssueGenerator(SyntheticConfig.noisy_kind(seed=cfg.seed))
    kind_idx = {k: i for i, k in enumerate(KIND_LABELS)}

    def split(start: int, count: int):
        titles, bodies, emitted, true = [], [], [], []
        for iss in gen.issues(start, count):
            titles.append(iss.title)
            bodies.append(iss.body)
            # labels[0] is always the emitted (possibly flipped) kind
            emitted.append(kind_idx[iss.labels[0]])
            true.append(kind_idx[iss.true_kind])
        return titles, bodies, emitted, true

    tr_t, tr_b, tr_k, _ = split(0, cfg.n_train_issues)
    te_t, te_b, te_emit, te_true = split(cfg.n_train_issues, cfg.n_test_issues)
    (tr_t, tr_b, tr_k), (va_t, va_b, va_k) = _carve_val(tr_t, tr_b, tr_k)
    model = _fit_universal(cfg, tr_t, tr_b, tr_k)
    probs = predict_probabilities_batch(model, te_t, te_b)
    thresholds = derive_thresholds(model, va_t, va_b, va_k)
    return {
        # vs the labels a labeler emitted (what the reference could see)
        "test_vs_emitted": evaluate_universal(
            model, te_t, te_b, te_emit, probs=probs),
        # vs the generator's latent truth (the Bayes-ceiling view)
        "test_vs_true": evaluate_universal(
            model, te_t, te_b, te_true, probs=probs),
        "derived_thresholds": thresholds,
        "at_derived_thresholds": evaluate_at_thresholds(
            probs, te_emit, thresholds),
        "at_reference_thresholds": evaluate_at_thresholds(
            probs, te_emit, REFERENCE_THRESHOLDS),
    }


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------


def stage_oracle(cfg: QualityConfig) -> dict:
    """Bayes-optimal ceiling on the SAME held-out test slice the classifier
    stages use — round-2 VERDICT weak #7: every measured AUC needs a
    ceiling so 'beats 0.9169' can be read as a margin, not an artifact of
    the generator's design. CPU-only: completes even with the chip down."""
    from code_intelligence_tpu.data.synthetic import SyntheticIssueGenerator
    from code_intelligence_tpu.quality.oracle import bayes_ceiling

    t0 = time.time()
    comparison = None
    probs_path = cfg.workdir / "ft_test_probs.npz"
    if probs_path.exists():
        saved = np.load(probs_path, allow_pickle=True)
        if len(saved["probs"]) == cfg.n_test_issues:
            comparison = saved["probs"]
    out = bayes_ceiling(
        SyntheticIssueGenerator(),
        n_docs=cfg.n_test_issues,
        start=cfg.n_lm_issues + cfg.n_train_issues,
        comparison_scores=comparison,
    )
    out["_elapsed_s"] = round(time.time() - t0, 1)
    return _stage_write(cfg, "oracle", out)


def stage_report(cfg: QualityConfig, out_path: Optional[Path] = None) -> dict:
    gen_info = _stage_done(cfg, "gen") or {}
    lm = _stage_done(cfg, "lm") or {}
    ft = _stage_done(cfg, "ft") or {}
    mlp = _stage_done(cfg, "mlp") or {}
    distill = _stage_done(cfg, "distill") or {}
    uni = _stage_done(cfg, "universal") or {}
    oracle = _stage_done(cfg, "oracle") or {}
    per_label = ft.get("per_label_auc") or {}
    aucs = [v for v in per_label.values() if v is not None]
    report = {
        "corpus": {
            "train_tokens": gen_info.get("train_tokens"),
            "valid_tokens": gen_info.get("valid_tokens"),
            "vocab_size": gen_info.get("vocab_size"),
            "n_labels": gen_info.get("n_labels"),
            "generator_unigram_entropy_bits": gen_info.get("unigram_entropy_bits"),
            "generator_topic_entropy_bits": gen_info.get("topic_conditional_entropy_bits"),
        },
        "lm": {
            "val_perplexity": lm.get("val_perplexity"),
            "val_loss": lm.get("val_loss"),
            "val_accuracy": lm.get("val_accuracy"),
            # iid-word floor from the generator, for context (bits -> ppl)
            "generator_word_ppl_floor": (
                2 ** gen_info["topic_conditional_entropy_bits"]
                if gen_info.get("topic_conditional_entropy_bits") else None
            ),
        },
        "fine_tuned_classifier": {
            "weighted_auc": ft.get("weighted_auc"),
            "per_label_auc": per_label,
            "per_label_auc_range": [min(aucs), max(aucs)] if aucs else None,
            "macro_f1_at_0.5": ft.get("macro_f1_at_0.5"),
            "macro_f1_at_best": ft.get("macro_f1_at_best"),
            "reference_weighted_auc": REFERENCE["fine_tuned_weighted_auc"],
            "reference_per_label_auc_band": REFERENCE["fine_tuned_per_label_auc_band"],
        },
        "mlp_head": {
            "train_weighted_auc": mlp.get("train_weighted_auc"),
            "test_weighted_auc": mlp.get("test_weighted_auc"),
            "n_train": mlp.get("n_train"),
            "n_test": mlp.get("n_test"),
            "scale_note": mlp.get("_scale_note"),
            "reference_train_weighted_auc": REFERENCE["mlp_train_weighted_auc"],
            "reference_test_weighted_auc": REFERENCE["mlp_test_weighted_auc"],
        },
        "distilled_student": {
            # TPU-first serving alternative to the reference's 965MB full
            # model at serve time (`flask_app/app.py:24-33`): same wire
            # contract, every layer Pallas/VMEM-resident
            "student": distill.get("student"),
            "holdout_cosine": distill.get("holdout_cosine"),
            "serving_ab": distill.get("serving_ab"),
            "downstream_mlp": distill.get("downstream_mlp"),
        },
        "universal_kind_model": {
            "tower": uni.get("tower"),
            "test_accuracy": uni.get("test_accuracy"),
            "per_class_auc": uni.get("per_class_auc"),
            "derived_thresholds": uni.get("derived_thresholds"),
            "at_derived_thresholds": uni.get("at_derived_thresholds"),
            "reference_thresholds": uni.get("reference_thresholds"),
            # noisy_kind preset: the regime where threshold derivation has
            # real trade-offs to make (round-3 VERDICT weak #5)
            "noisy_kind": uni.get("noisy_kind"),
        },
        "bayes_ceiling": {
            "weighted_auc": oracle.get("weighted_auc"),
            "weighted_auc_ci95": oracle.get("weighted_auc_ci95"),
            "per_label_auc": oracle.get("per_label_auc"),
            "note": oracle.get("note"),
            # margin of the measured fine-tuned classifier below the
            # oracle on the same test slice (negative = below ceiling)
            "fine_tuned_margin": (
                round(ft["weighted_auc"] - oracle["weighted_auc"], 4)
                if ft.get("weighted_auc") is not None
                and oracle.get("weighted_auc") is not None else None
            ),
            # paired-bootstrap margin (present when per-doc ft test probs
            # were persisted): the valid "at the frontier" test
            "paired_margin": oracle.get("paired_margin"),
        },
        "note": (
            "Reference numbers were measured on real GitHub-issue data; this "
            "run uses the in-sandbox generative corpus (data/synthetic.py — "
            "no network egress), whose label noise is designed to put the "
            "Bayes-optimal AUC in the reference's published band."
        ),
    }
    report["stage_platforms"] = {
        # gen and oracle are host-only by construction (numpy; no device)
        "gen": "host" if gen_info else None,
        "oracle": "host" if oracle else None,
        **{name: marker.get("_platform")
           for name, marker in (("lm", lm), ("ft", ft), ("mlp", mlp),
                                ("distill", distill), ("universal", uni))},
    }
    missing = [name for name in STAGES
               if name != "report" and _stage_done(cfg, name) is None]
    report["status"] = "COMPLETE" if not missing else "PARTIAL"
    if missing:
        report["missing_stages"] = missing
    if out_path is not None:
        _atomic_write_json(Path(out_path), report)
    _stage_write(cfg, "report", report)
    return report


# oracle sits late in the order on purpose: it depends only on the
# generator config, so a pre-oracle workdir (e.g. the interrupted round-2
# run) resumes without the cascade invalidating finished lm/ft stages
STAGES = ("gen", "lm", "ft", "mlp", "distill", "universal", "oracle", "report")


def run_quality(cfg: QualityConfig, out_path: Optional[Path] = None,
                force: Sequence[str] = ()) -> dict:
    cfg.workdir.mkdir(parents=True, exist_ok=True)
    # estimator-version guard: an oracle marker from before the
    # sequence-likelihood/CI upgrade must not survive a resume
    stale = _stage_done(cfg, "oracle")
    if stale is not None and "weighted_auc_ci95" not in stale:
        log.info("oracle marker predates the sequence estimator; re-running")
        _stage_path(cfg, "oracle").unlink()
    cascade = False  # re-running a stage invalidates everything after it
    for name in STAGES:
        if name == "report":
            continue  # always re-assembled below (never stale vs forced stages)
        if cascade or name in force or _stage_done(cfg, name) is None:
            cascade = True
            log.info("=== stage %s ===", name)
            _stage_path(cfg, name).unlink(missing_ok=True)
            {"gen": stage_gen, "oracle": stage_oracle, "lm": stage_lm,
             "ft": stage_ft, "mlp": stage_mlp, "distill": stage_distill,
             "universal": stage_universal}[name](cfg)
        else:
            log.info("=== stage %s: already done, skipping ===", name)
    log.info("=== stage report ===")
    return stage_report(cfg, out_path)


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workdir", required=True)
    p.add_argument("--preset", choices=("smoke", "full"), default="full")
    p.add_argument("--out", default=None, help="also write the report here")
    p.add_argument("--force", nargs="*", default=(), choices=STAGES,
                   help="re-run these stages even if marked done")
    p.add_argument("--cpu", action="store_true", help="force CPU platform")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    from code_intelligence_tpu.utils import devices

    devices.enable_compile_cache()
    cfg = QualityConfig.smoke(args.workdir) if args.preset == "smoke" else QualityConfig.full(args.workdir)
    report = run_quality(cfg, Path(args.out) if args.out else None, force=args.force)
    print(json.dumps({
        "lm_val_perplexity": report["lm"]["val_perplexity"],
        "ft_weighted_auc": report["fine_tuned_classifier"]["weighted_auc"],
        "mlp_test_auc": report["mlp_head"]["test_weighted_auc"],
    }))
    return report


if __name__ == "__main__":
    main()
