"""Scripted quality evaluation.

SURVEY.md §7 "parity/eval harness": the reference's quality numbers live
in notebook outputs (AUC tables, W&B val_loss); this CLI produces them as
one JSON report so runs are comparable to BASELINE.md:

    python -m code_intelligence_tpu.training.eval_cli lm \
        --corpus_dir ./corpus --model_dir ./runs/lm
    # -> {"val_loss": ..., "val_perplexity": ..., "val_accuracy": ...}

    python -m code_intelligence_tpu.training.eval_cli mlp \
        --model_dir ./repo-models/kubeflow/examples \
        --features f.npy --labels y.npy
    # -> {"weighted_auc": ..., "per_label_auc": {...}, "macro_f1": ...}
"""

from __future__ import annotations

import argparse
import json
import logging
from pathlib import Path

import numpy as np

log = logging.getLogger(__name__)


def cmd_lm(args) -> dict:
    import jax

    from code_intelligence_tpu.data import LMStreamLoader, TokenCorpus
    from code_intelligence_tpu.models import AWDLSTMConfig
    from code_intelligence_tpu.parallel import make_mesh
    from code_intelligence_tpu.training import LMTrainer, TrainConfig
    from code_intelligence_tpu.training import checkpoint as ckpt

    model_dir = Path(args.model_dir)
    train_args = json.loads((model_dir / "train_args.json").read_text())
    corpus = TokenCorpus(Path(args.corpus_dir) / "valid")
    vocab = corpus.vocab  # both splits carry the vocab

    import jax.numpy as jnp

    mcfg = AWDLSTMConfig(
        vocab_size=len(vocab),
        emb_sz=train_args["emb_sz"],
        n_hid=train_args["n_hid"],
        n_layers=train_args["n_layers"],
        pad_id=vocab.pad_id,
        qrnn=train_args.get("qrnn", False),
        dtype=jnp.bfloat16 if train_args.get("bf16") else jnp.float32,
    )
    train_bs = train_args["bs"]
    bs, bptt = args.bs or train_bs, train_args["bptt"]
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    # Restore at the TRAINING shapes (grad_clip changes the opt-state tree,
    # batch size shapes the carried lstm_states); evaluate() builds its own
    # eval-sized carry from the loader, so no state rebuild is needed here.
    tcfg = TrainConfig(
        batch_size=train_bs, bptt=bptt, grad_clip=train_args.get("grad_clip")
    )
    trainer = LMTrainer(mcfg, tcfg, mesh=mesh)
    state = trainer.init_state(jax.random.PRNGKey(0), local_batch_size=train_bs)
    state = ckpt.restore_checkpoint(model_dir / "ckpt", state)
    tokens = corpus.stream() if args.max_tokens is None else corpus.tokens(args.max_tokens)
    loader = LMStreamLoader(tokens, bs, bptt, shuffle_offsets=False)
    with mesh:
        report = trainer.evaluate(state, loader)
    report["step"] = int(state.step)
    print(json.dumps(report))
    return report


def cmd_mlp(args) -> dict:
    from sklearn.metrics import f1_score

    from code_intelligence_tpu.labels.mlp import MLPHead

    head = MLPHead.load(args.model_dir)
    X = np.load(args.features)
    y = np.load(args.labels)
    aucs, weighted = head.calculate_auc(X, y)
    probs = head.predict_proba(X)
    thresholds = head.probability_thresholds or {}
    preds = np.zeros_like(probs)
    for i in range(probs.shape[1]):
        t = thresholds.get(i)
        if t is not None:
            preds[:, i] = probs[:, i] >= t
    report = {
        "weighted_auc": weighted,
        "per_label_auc": {str(k): v for k, v in aucs.items()},
        "macro_f1": float(f1_score(y, preds, average="macro", zero_division=0)),
        "n_examples": int(len(X)),
    }
    print(json.dumps(report))
    return report


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)
    lm = sub.add_parser("lm", help="LM val perplexity/accuracy")
    lm.add_argument("--corpus_dir", required=True)
    lm.add_argument("--model_dir", required=True)
    lm.add_argument("--bs", type=int, default=None)
    lm.add_argument("--max_tokens", type=int, default=None)
    lm.set_defaults(fn=cmd_lm)
    mlp = sub.add_parser("mlp", help="label-head AUC/F1")
    mlp.add_argument("--model_dir", required=True)
    mlp.add_argument("--features", required=True)
    mlp.add_argument("--labels", required=True)
    mlp.set_defaults(fn=cmd_mlp)
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.WARNING)
    from code_intelligence_tpu.utils import devices

    devices.enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    main()
