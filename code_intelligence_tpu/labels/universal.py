"""Universal issue-kind model (bug / feature / question).

Replaces the reference's TF 1.15 / Keras two-input HDF5 model
(`py/label_microservice/universal_kind_label_model.py:14-110`; SURVEY.md
§2.4: "Flax reimplementation of the 2-tower (title/body) text
classifier"). Behavior preserved:

* two towers — title sequence and body sequence — merged into a 3-class
  softmax over ``['bug', 'feature', 'question']``;
* per-class prediction thresholds 0.52, question 0.60
  (`universal_kind_label_model.py:50-51`);
* full probabilities logged via ``extra={"predictions": ...}`` before
  threshold filtering.

What is deliberately *not* preserved: the per-predict graph reload
(`:86-92`) and TF thread-affinity hacks — jax inference is pure and
thread-safe, so one jitted apply serves all worker threads (SURVEY.md §5
"race detection": this whole bug class is designed out).
"""

from __future__ import annotations

import json
import logging
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from code_intelligence_tpu.labels.models import IssueLabelModel
from code_intelligence_tpu.text import Tokenizer, Vocab, pre_process

log = logging.getLogger(__name__)

DEFAULT_CLASS_NAMES = ["bug", "feature", "question"]
DEFAULT_THRESHOLDS = {"bug": 0.52, "feature": 0.52, "question": 0.60}


class TwoTowerClassifier(nn.Module):
    """Title tower + body tower -> softmax(kind).

    ``tower="gru"`` (default) is a sequence-aware encoder in the same
    architecture family as the reference's Keras HDF5 artifact
    (Embedding -> GRU -> concat -> Dense -> softmax), so converted Keras
    weights drop in (`labels/convert_keras.py`) and word order matters
    ("doesn't work" vs "works"). ``tower="mean"`` is the round-1 masked
    mean-pool bag-of-words, kept so old saved artifacts still load.
    """

    vocab_size: int
    n_classes: int = 3
    emb_dim: int = 64
    hidden: int = 128
    title_len: int = 32
    body_len: int = 256
    tower: str = "gru"
    merge_dim: int = 0  # 0 = same as hidden (converted models may differ)

    def _tower(self, tokens: jnp.ndarray, pad_id: int, name: str) -> jnp.ndarray:
        emb = nn.Embed(self.vocab_size, self.emb_dim, name=f"{name}_embed")(tokens)
        mask = tokens != pad_id
        if self.tower == "gru":
            # final GRU state at each sequence's true length; all-pad rows
            # clamp to length>=1 so the carry stays well-defined
            lengths = jnp.maximum(mask.sum(axis=1), 1).astype(jnp.int32)
            rnn = nn.RNN(
                nn.GRUCell(features=self.hidden, name=f"{name}_gru_cell"),
                return_carry=True,
                name=f"{name}_gru",
            )
            carry, _ = rnn(emb, seq_lengths=lengths)
            return carry
        m = mask.astype(emb.dtype)[:, :, None]
        summed = jnp.sum(emb * m, axis=1)
        count = jnp.maximum(m.sum(axis=1), 1.0)
        pooled = summed / count  # masked mean pool
        return nn.relu(nn.Dense(self.hidden, name=f"{name}_dense")(pooled))

    @nn.compact
    def __call__(self, title_tokens: jnp.ndarray, body_tokens: jnp.ndarray, pad_id: int = 1):
        t = self._tower(title_tokens, pad_id, "title")
        b = self._tower(body_tokens, pad_id, "body")
        x = jnp.concatenate([t, b], axis=-1)
        x = nn.relu(nn.Dense(self.merge_dim or self.hidden, name="merge")(x))
        return nn.Dense(self.n_classes, name="out")(x)  # logits


class UniversalKindLabelModel(IssueLabelModel):
    def __init__(
        self,
        params,
        vocab: Vocab,
        class_names: Sequence[str] = tuple(DEFAULT_CLASS_NAMES),
        thresholds: Optional[Dict[str, float]] = None,
        module: Optional[TwoTowerClassifier] = None,
    ):
        self.vocab = vocab
        self.class_names = list(class_names)
        self.thresholds = dict(thresholds or DEFAULT_THRESHOLDS)
        self.module = module or TwoTowerClassifier(
            vocab_size=len(vocab), n_classes=len(self.class_names)
        )
        self.params = params
        self.tokenizer = Tokenizer(add_bos=False, backend="auto")
        self._predict = jax.jit(
            lambda p, t, b: jax.nn.softmax(self.module.apply(p, t, b, self.vocab.pad_id))
        )

    # -- encoding -----------------------------------------------------------

    def _encode(self, text: str, max_len: int) -> np.ndarray:
        ids = self.vocab.numericalize(self.tokenizer.tokenize(text or ""))[:max_len]
        out = np.full((max_len,), self.vocab.pad_id, np.int32)
        out[: len(ids)] = ids
        return out

    def predict_probabilities(self, title: str, body: str) -> Dict[str, float]:
        t = self._encode(title, self.module.title_len)[None]
        b = self._encode(body, self.module.body_len)[None]
        probs = np.asarray(self._predict(self.params, jnp.asarray(t), jnp.asarray(b)))[0]
        return dict(zip(self.class_names, probs.astype(float)))

    def predict_issue_labels(self, org, repo, title, text, context=None):
        body = "\n".join(text) if isinstance(text, (list, tuple)) else (text or "")
        raw = self.predict_probabilities(title or "", body)
        extra = {"predictions": raw}
        extra.update(context or {})
        results = {
            label: p
            for label, p in raw.items()
            if p >= self.thresholds.get(label, 0.52)
        }
        extra["labels"] = list(results.keys())
        log.info("Universal model predictions.", extra=extra)
        return results

    # -- persistence --------------------------------------------------------

    def save(self, path) -> None:
        from code_intelligence_tpu.utils.params_io import save_params_npz

        path = Path(path)
        path.mkdir(parents=True, exist_ok=True)
        save_params_npz(path / "universal_params.npz", self.params)
        meta = {
            "class_names": self.class_names,
            "thresholds": self.thresholds,
            "emb_dim": self.module.emb_dim,
            "hidden": self.module.hidden,
            "title_len": self.module.title_len,
            "body_len": self.module.body_len,
            "tower": self.module.tower,
            "merge_dim": self.module.merge_dim,
        }
        (path / "universal_meta.json").write_text(json.dumps(meta, indent=1))
        self.vocab.save(path / "vocab.json")

    @classmethod
    def load(cls, path) -> "UniversalKindLabelModel":
        path = Path(path)
        meta = json.loads((path / "universal_meta.json").read_text())
        vocab = Vocab.load(path / "vocab.json")
        module = TwoTowerClassifier(
            vocab_size=len(vocab),
            n_classes=len(meta["class_names"]),
            emb_dim=meta["emb_dim"],
            hidden=meta["hidden"],
            title_len=meta["title_len"],
            body_len=meta["body_len"],
            # round-1 artifacts predate the GRU towers and carry no key
            tower=meta.get("tower", "mean"),
            merge_dim=meta.get("merge_dim", 0),
        )
        from code_intelligence_tpu.utils.params_io import load_params_npz

        params = load_params_npz(path / "universal_params.npz")
        return cls(
            params,
            vocab,
            class_names=meta["class_names"],
            thresholds=meta["thresholds"],
            module=module,
        )


# ---------------------------------------------------------------------------
# Evaluation + threshold derivation
# ---------------------------------------------------------------------------


def predict_probabilities_batch(
    model: "UniversalKindLabelModel", titles: Sequence[str], bodies: Sequence[str]
) -> np.ndarray:
    """(n, n_classes) softmax probabilities, batched through one jit."""
    T = np.stack([model._encode(t, model.module.title_len) for t in titles])
    B = np.stack([model._encode(b, model.module.body_len) for b in bodies])
    return np.asarray(model._predict(model.params, jnp.asarray(T), jnp.asarray(B)))


def evaluate_universal(
    model: "UniversalKindLabelModel",
    titles: Sequence[str],
    bodies: Sequence[str],
    kinds: Sequence[int],
    probs: Optional[np.ndarray] = None,
) -> Dict:
    """Held-out accuracy + per-class one-vs-rest AUC (the numbers the
    reference never published for its universal model). Pass ``probs`` to
    reuse probabilities already computed for the same split."""
    from sklearn.metrics import roc_auc_score

    if probs is None:
        probs = predict_probabilities_batch(model, titles, bodies)
    y = np.asarray(kinds)
    acc = float((probs.argmax(-1) == y).mean())
    per_class_auc = {}
    for i, name in enumerate(model.class_names):
        col = (y == i).astype(np.float32)
        if col.min() == col.max():
            continue
        per_class_auc[name] = float(roc_auc_score(col, probs[:, i]))
    return {"accuracy": acc, "per_class_auc": per_class_auc, "n": int(len(y))}


def evaluate_at_thresholds(
    probs: np.ndarray,
    kinds: Sequence[int],
    thresholds: Dict[str, float],
    class_names: Sequence[str] = ("bug", "feature", "question"),
) -> Dict:
    """Metrics of the model *as operated*: apply label i iff
    ``p_i >= thresholds[i]`` — the worker's actual decision rule
    (`universal_kind_label_model.py:79-86` applies 0.52/0.60 this way) —
    rather than argmax. Reports per-class precision/recall/F1 at the
    cutoffs, micro-F1, coverage (fraction of issues that get >=1 kind
    label), and exact accuracy over covered issues (highest passing
    class == true kind)."""
    y = np.asarray(kinds)
    # out["thresholds"] records the EFFECTIVE per-class cutoffs — including
    # the 0.5 default applied to any class missing from the input dict — so
    # the report states the operating point actually evaluated.
    out: Dict = {"per_class": {}, "thresholds": {}}
    tp_all = fp_all = fn_all = 0.0
    passing = np.zeros_like(probs, dtype=bool)
    for i, name in enumerate(class_names):
        th = float(thresholds.get(name, 0.5))
        out["thresholds"][name] = th
        pred = probs[:, i] >= th
        passing[:, i] = pred
        truth = y == i
        tp = float((pred & truth).sum())
        fp = float((pred & ~truth).sum())
        fn = float((~pred & truth).sum())
        tp_all, fp_all, fn_all = tp_all + tp, fp_all + fp, fn_all + fn
        prec = tp / (tp + fp) if tp + fp else 0.0
        rec = tp / (tp + fn) if tp + fn else 0.0
        f1 = 2 * prec * rec / (prec + rec) if prec + rec else 0.0
        out["per_class"][name] = {
            "precision": round(prec, 4), "recall": round(rec, 4),
            "f1": round(f1, 4), "n_pos": int(truth.sum()),
        }
    micro_p = tp_all / (tp_all + fp_all) if tp_all + fp_all else 0.0
    micro_r = tp_all / (tp_all + fn_all) if tp_all + fn_all else 0.0
    out["micro_f1"] = round(
        2 * micro_p * micro_r / (micro_p + micro_r)
        if micro_p + micro_r else 0.0, 4)
    covered = passing.any(axis=1)
    out["coverage"] = round(float(covered.mean()), 4)
    if covered.any():
        masked = np.where(passing, probs, -np.inf)
        out["accuracy_covered"] = round(
            float((masked.argmax(-1)[covered] == y[covered]).mean()), 4)
    else:
        out["accuracy_covered"] = None
    return out


def derive_thresholds(
    model: "UniversalKindLabelModel",
    titles: Sequence[str],
    bodies: Sequence[str],
    kinds: Sequence[int],
    precision_target: float = 0.65,
    recall_floor: float = 0.5,
    probs: Optional[np.ndarray] = None,
) -> Dict[str, float]:
    """Re-derive per-class thresholds from PR curves on a VALIDATION split
    (never the reported test split — thresholds fit on the eval data would
    overstate precision) instead of inheriting the reference's hardcoded
    .52/.60 (`universal_kind_label_model.py:50-51`): the smallest
    threshold whose precision meets ``precision_target`` while recall
    stays above ``recall_floor``; if no point satisfies both, fall back to
    the threshold maximizing F1 (never predicting would be worse than the
    reference's fixed cutoffs)."""
    from sklearn.metrics import precision_recall_curve

    if probs is None:
        probs = predict_probabilities_batch(model, titles, bodies)
    y = np.asarray(kinds)
    out: Dict[str, float] = {}
    for i, name in enumerate(model.class_names):
        col = (y == i).astype(np.int32)
        if col.min() == col.max():
            out[name] = model.thresholds.get(name, 0.52)
            continue
        prec, rec, th = precision_recall_curve(col, probs[:, i])
        # precision_recall_curve: th[j] pairs with prec[j+1], rec[j+1]
        candidates = [
            float(th[j])
            for j in range(len(th))
            if prec[j + 1] >= precision_target and rec[j + 1] >= recall_floor
        ]
        if candidates:
            out[name] = min(candidates)
        else:
            f1 = 2 * prec[1:] * rec[1:] / np.maximum(prec[1:] + rec[1:], 1e-9)
            out[name] = float(th[int(np.argmax(f1))])
    return out


# ---------------------------------------------------------------------------
# Training (the reference ships only a pre-trained HDF5; we own the trainer)
# ---------------------------------------------------------------------------


def train_universal_model(
    titles: Sequence[str],
    bodies: Sequence[str],
    kinds: Sequence[int],
    vocab: Optional[Vocab] = None,
    class_names: Sequence[str] = tuple(DEFAULT_CLASS_NAMES),
    epochs: int = 10,
    batch_size: int = 64,
    lr: float = 1e-3,
    seed: int = 0,
    max_vocab: int = 20000,
    module_kwargs: Optional[Dict] = None,
    steps_per_dispatch: int = 8,
) -> UniversalKindLabelModel:
    """Train the two-tower classifier from labeled (title, body, kind)
    rows. ``module_kwargs`` overrides :class:`TwoTowerClassifier` sizing
    (emb_dim/hidden/title_len/body_len/tower)."""
    import optax

    from code_intelligence_tpu.text import tokenize_texts
    from code_intelligence_tpu.text.vocab import Vocab as V

    tok_docs = tokenize_texts([pre_process(t) + " " + pre_process(b) for t, b in zip(titles, bodies)])
    if vocab is None:
        vocab = V.build(tok_docs, max_vocab=max_vocab, min_freq=1)

    module = TwoTowerClassifier(
        vocab_size=len(vocab), n_classes=len(class_names), **(module_kwargs or {})
    )
    model = UniversalKindLabelModel(
        params=None, vocab=vocab, class_names=class_names, module=module
    )
    module = model.module
    T = np.stack([model._encode(t, module.title_len) for t in titles])
    B = np.stack([model._encode(b, module.body_len) for b in bodies])
    Y = np.asarray(kinds, np.int32)

    params = module.init(
        jax.random.PRNGKey(seed), jnp.asarray(T[:1]), jnp.asarray(B[:1]), vocab.pad_id
    )
    tx = optax.adam(lr)
    opt_state = tx.init(params)
    pad_id = vocab.pad_id

    def step(params, opt_state, tb, bb, yb):
        def loss_fn(p):
            logits = module.apply(p, tb, bb, pad_id)
            return optax.softmax_cross_entropy_with_integer_labels(logits, yb).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    # k batches scanned per device dispatch (training/dispatch.py): this
    # small model's steps are fast, so the per-dispatch host cost
    # dominates a naive per-batch loop. Chunking is
    # per-epoch; the tail chunk's size is the same every epoch, so at
    # most two program shapes compile.
    from code_intelligence_tpu.training.dispatch import scan_dispatch

    steps = scan_dispatch(step)

    rng = np.random.RandomState(seed)
    n = len(Y)
    bs = min(batch_size, n)
    k = max(1, steps_per_dispatch)
    for _ in range(epochs):
        order = rng.permutation(n)
        batches = []
        for i in range(0, n, bs):
            idx = order[i : i + bs]
            if len(idx) < bs:
                idx = np.concatenate([idx, order[: bs - len(idx)]])
            batches.append(idx)
        for c in range(0, len(batches), k):
            chunk = np.stack(batches[c : c + k])
            params, opt_state, _ = steps(
                params, opt_state, jnp.asarray(T[chunk]),
                jnp.asarray(B[chunk]), jnp.asarray(Y[chunk])
            )
    model.params = params
    model._predict = jax.jit(
        lambda p, t, b: jax.nn.softmax(module.apply(p, t, b, pad_id))
    )
    return model


def main(argv=None):
    """Train + export the universal kind model from labeled issues.

    Input: JSONL of ``{title, body, kind}`` where kind is one of
    bug/feature/question (or an integer class index). The reference only
    ships a pre-trained HDF5; this owns the retrain path:

        python -m code_intelligence_tpu.labels.universal \
            --issues kinds.jsonl --out_dir ./models/universal --epochs 10
    """
    import argparse

    p = argparse.ArgumentParser(description=main.__doc__)
    p.add_argument("--issues", required=True, help="JSONL with title/body/kind")
    p.add_argument("--out_dir", required=True)
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--valid_frac", type=float, default=0.1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--derive_thresholds", action="store_true", default=True,
        help="re-derive per-class thresholds from validation PR curves",
    )
    p.add_argument("--no_derive_thresholds", dest="derive_thresholds",
                   action="store_false")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")

    titles, bodies, kinds = [], [], []
    kind_index = {name: i for i, name in enumerate(DEFAULT_CLASS_NAMES)}
    n_classes = len(DEFAULT_CLASS_NAMES)
    with open(args.issues) as fh:
        for lineno, line in enumerate(fh, 1):
            if not line.strip():
                continue
            rec = json.loads(line)
            kind = rec["kind"]
            if isinstance(kind, str):
                if kind not in kind_index:
                    raise SystemExit(
                        f"{args.issues}:{lineno}: unknown kind {kind!r}; "
                        f"allowed: {DEFAULT_CLASS_NAMES} or 0..{n_classes - 1}"
                    )
                kind = kind_index[kind]
            kind = int(kind)
            if not 0 <= kind < n_classes:
                raise SystemExit(
                    f"{args.issues}:{lineno}: kind index {kind} out of range "
                    f"0..{n_classes - 1}"
                )
            titles.append(rec.get("title", ""))
            bodies.append(rec.get("body", ""))
            kinds.append(kind)

    # seeded shuffle before the split: grouped-by-kind dumps would otherwise
    # yield a single-class validation set.
    rng = np.random.RandomState(args.seed)
    order = rng.permutation(len(titles)).tolist()
    titles = [titles[i] for i in order]
    bodies = [bodies[i] for i in order]
    kinds = [kinds[i] for i in order]
    n_valid = int(len(titles) * args.valid_frac) if args.valid_frac > 0 else 0
    model = train_universal_model(
        titles[n_valid:], bodies[n_valid:], kinds[n_valid:],
        epochs=args.epochs, batch_size=args.batch_size, lr=args.lr, seed=args.seed,
    )
    eval_report = None
    if n_valid:
        vt, vb, vk = titles[:n_valid], bodies[:n_valid], kinds[:n_valid]
        probs = predict_probabilities_batch(model, vt, vb)
        eval_report = evaluate_universal(model, vt, vb, vk, probs=probs)
        if args.derive_thresholds:
            model.thresholds = derive_thresholds(model, vt, vb, vk, probs=probs)
    model.save(args.out_dir)
    report = {
        "n_train": len(titles) - n_valid,
        "n_valid": n_valid,
        "valid_accuracy": eval_report["accuracy"] if eval_report else None,
        "per_class_auc": eval_report["per_class_auc"] if eval_report else None,
        "thresholds": model.thresholds,
        "tower": model.module.tower,
        "out_dir": str(Path(args.out_dir)),
    }
    print(json.dumps(report))
    return report


if __name__ == "__main__":
    main()
