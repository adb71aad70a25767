"""LM -> classifier transfer learning with gradual unfreezing.

Rebuild of the reference's fine-tune recipe (`06_FineTune.ipynb` cells
33-62; SURVEY.md §7 stage 5):

* start from the pretrained LM encoder (``load_encoder`` artifact);
* **gradual unfreezing** — train the head only (``freeze``), then head +
  last recurrent layer (``freeze_to(-2)``), then everything, exactly
  fastai's staging;
* **discriminative learning rates** — deeper encoder layers get
  geometrically smaller LRs (fastai's ``slice(lr/factor, lr)``);
* per-label ROC AUC evaluation after each stage (the notebook's AUC
  tables are the reference quality metric, BASELINE.md).

Freezing is implemented functionally: one ``optax.multi_transform`` per
stage routes frozen params to ``set_to_zero`` — no mutable module state,
and each stage is its own compiled step (a handful of compiles total).
"""

from __future__ import annotations

import dataclasses
import logging
import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from code_intelligence_tpu.models.classifier import (
    AWDLSTMClassifier,
    ClassifierConfig,
)

log = logging.getLogger(__name__)


def _param_group(path: str, n_layers: int) -> int:
    """Map a param path to an unfreeze group:
    0 = head (+batchnorm), 1 = last recurrent layer, ..., n = embedding.
    Matches fastai's layer groups for AWD-LSTM classifiers."""
    m = re.search(r"(?:lstm|qrnn)_(\d+)_", path)
    if m:
        layer = int(m.group(1))
        return n_layers - layer  # last layer -> group 1
    if "embedding" in path:
        return n_layers + 1
    return 0  # head


def _group_tree(params, n_layers: int):
    return jax.tree_util.tree_map_with_path(
        lambda path, _: _param_group(
            "/".join(str(getattr(k, "key", k)) for k in path), n_layers
        ),
        params,
    )


@dataclasses.dataclass
class FineTuneConfig:
    lr: float = 1e-2
    lr_div: float = 2.6  # fastai discriminative-LR factor per group
    epochs_per_stage: Sequence[int] = (1, 1, 2)
    batch_size: int = 32
    max_len: int = 256
    wd: float = 0.01
    # batches scanned per device dispatch (training/dispatch.py): the old
    # loop additionally blocked on float(loss) EVERY step — a full host
    # round-trip per batch
    steps_per_dispatch: int = 8
    seed: int = 0


class FineTuner:
    def __init__(
        self,
        config: ClassifierConfig,
        ft_config: Optional[FineTuneConfig] = None,
        pretrained_encoder: Optional[dict] = None,
    ):
        self.config = config
        self.ft = ft_config if ft_config is not None else FineTuneConfig()
        self.model = AWDLSTMClassifier(config)
        self.pretrained_encoder = pretrained_encoder
        self.variables = None  # {'params': ..., 'batch_stats': ...}

    # ------------------------------------------------------------------

    def init(self, rng: Optional[jax.Array] = None) -> None:
        rng = rng if rng is not None else jax.random.PRNGKey(self.ft.seed)
        tokens = jnp.zeros((2, 8), jnp.int32)
        lengths = jnp.full((2,), 8, jnp.int32)
        self.variables = self.model.init({"params": rng}, tokens, lengths)
        if self.pretrained_encoder is not None:
            params = dict(self.variables["params"])
            # Pretrained LM encoder drops in param-for-param
            # (load_encoder artifact, SURVEY.md §7 "checkpoint compatibility").
            # jnp.array COPIES (jnp.asarray would alias when dtypes
            # already match): the training dispatch donates its inputs,
            # and a donated alias of self.pretrained_encoder would leave
            # the caller's loaded encoder deleted on device after the
            # first step (re-init / second FineTuner would then crash)
            params["encoder"] = jax.tree.map(
                lambda new, old: jnp.array(old, dtype=new.dtype),
                params["encoder"],
                self.pretrained_encoder,
            )
            self.variables = {**self.variables, "params": params}

    # ------------------------------------------------------------------

    def _make_optimizer(self, max_group: int, steps: int):
        """Stage optimizer: groups > max_group are frozen; unfrozen group g
        trains at lr / lr_div**g (discriminative LRs).

        Discriminative attenuation exists to protect PRETRAINED deep
        layers from catastrophic forgetting (the ULMFiT rationale the
        reference inherits from fastai). When this FineTuner was built
        WITHOUT a pretrained encoder there is nothing to protect, and the
        attenuation starves exactly the layers that must learn from
        scratch — on the separable-task regression test the embedding
        (where the class signal lives) trained at lr/2.6**3 and the task
        never converged at full unfreeze. So: attenuate only when a
        pretrained encoder was loaded.
        """
        n_layers = self.config.encoder.n_layers

        def label_fn(params):
            return jax.tree.map(
                lambda g: f"g{g}" if g <= max_group else "frozen",
                _group_tree(params, n_layers),
            )

        from code_intelligence_tpu.training.schedules import one_cycle_lr

        div = self.ft.lr_div if self.pretrained_encoder is not None else 1.0
        transforms = {"frozen": optax.set_to_zero()}
        for g in range(max_group + 1):
            # one_cycle_lr carries the NaN-safe horizon clamp (optax's
            # one-cycle divides by a zero-length warmup interval at tiny
            # step counts — see training/schedules.py)
            sched = one_cycle_lr(steps, lr_max=self.ft.lr / (div**g))
            transforms[f"g{g}"] = optax.adamw(sched, weight_decay=self.ft.wd)
        return optax.multi_transform(transforms, label_fn)

    def _make_step(self, optimizer):
        model = self.model
        multi = self.config.multi_label

        def step(variables, opt_state, rng, tokens, lengths, y):
            def loss_fn(params):
                logits, updates = model.apply(
                    {**variables, "params": params},
                    tokens,
                    lengths,
                    deterministic=False,
                    rngs={"dropout": rng},
                    mutable=["batch_stats"],
                )
                logits = logits.astype(jnp.float32)
                if multi:
                    loss = optax.sigmoid_binary_cross_entropy(logits, y).mean()
                else:
                    loss = optax.softmax_cross_entropy_with_integer_labels(
                        logits, y
                    ).mean()
                return loss, updates

            (loss, updates), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                variables["params"]
            )
            upd, opt_state = optimizer.update(grads, opt_state, variables["params"])
            params = optax.apply_updates(variables["params"], upd)
            new_vars = {**variables, "params": params, **updates}
            return new_vars, opt_state, loss

        # k batches per device program; carry = (variables, opt_state).
        # The accountant wrapper (utils/flight_recorder.py) records
        # compile time / flops / HBM per compiled shape — gradual
        # unfreezing compiles one program per stage, and the ledger on
        # /debug/flight is how that cost stays visible.
        from code_intelligence_tpu.training.dispatch import scan_dispatch
        from code_intelligence_tpu.utils import flight_recorder

        return flight_recorder.instrument(scan_dispatch(step),
                                          "fine_tune.step")

    # ------------------------------------------------------------------

    def _batches(self, X: List[np.ndarray], y: np.ndarray, rng: np.random.RandomState):
        bs = self.ft.batch_size
        order = rng.permutation(len(X))
        for i in range(0, len(order), bs):
            idx = order[i : i + bs]
            if len(idx) < bs:
                idx = np.concatenate([idx, order[: bs - len(idx)]])
            yield self._pad(X, idx, y)

    def _pad(self, X, idx, y=None):
        L = self.ft.max_len
        tokens = np.ones((len(idx), L), np.int32) * self.config.encoder.pad_id
        lengths = np.zeros((len(idx),), np.int32)
        for r, j in enumerate(idx):
            seq = np.asarray(X[j])[:L]
            tokens[r, : len(seq)] = seq
            lengths[r] = len(seq)
        if y is None:
            return tokens, lengths
        return tokens, lengths, y[idx]

    def _dispatch_chunk(self, step_fn, chunk, opt_state):
        """Run one scanned device program over a chunk of (rng, tokens,
        lengths, y) batches; updates ``self.variables`` and returns
        ``(per-step loss array on device, new opt_state)``."""
        subs = jnp.stack([c[0] for c in chunk])
        toks = jnp.asarray(np.stack([c[1] for c in chunk]))
        lens = jnp.asarray(np.stack([c[2] for c in chunk]))
        ys = jnp.asarray(np.stack([c[3] for c in chunk]))
        # scan_dispatch donates (variables, opt_state): commit the result
        # to self.variables only AFTER the dispatch returned, so a raise
        # during trace/compile leaves the instance on live buffers and a
        # failed fit_gradual stays retryable
        new_vars, opt_state, losses = step_fn(
            self.variables, opt_state, subs, toks, lens, ys)
        self.variables = new_vars
        return losses, opt_state

    def fit_gradual(  # graft: hot
        self,
        X: List[np.ndarray],
        y: np.ndarray,
        X_val: Optional[List[np.ndarray]] = None,
        y_val: Optional[np.ndarray] = None,
    ) -> List[Dict]:
        """The fastai recipe: freeze -> freeze_to(-2) -> unfreeze
        (`06_FineTune.ipynb`). Returns per-stage metrics."""
        if self.variables is None:
            self.init()
        rng = np.random.RandomState(self.ft.seed)
        key = jax.random.PRNGKey(self.ft.seed)
        history: List[Dict] = []
        n_groups = self.config.encoder.n_layers + 1
        stages = list(enumerate(self.ft.epochs_per_stage))
        for stage, epochs in stages:
            # stage 0: head only; stage 1: +last layer; final stage: all.
            max_group = 0 if stage == 0 else (1 if stage == 1 else n_groups)
            # ceil: _batches wrap-pads the short tail batch, so the loop
            # takes ceil(n/bs) optimizer steps per epoch — a floor here
            # would run the one-cycle schedule past its horizon
            steps = max(1, -(-len(X) // self.ft.batch_size) * epochs)
            optimizer = self._make_optimizer(max_group, steps)
            opt_state = optimizer.init(self.variables["params"])
            step_fn = self._make_step(optimizer)
            # k batches scanned per device program; losses stay on device
            # until the stage ends (the old loop blocked on float(loss)
            # every step — one host round-trip per batch)
            k = max(1, self.ft.steps_per_dispatch)
            loss_chunks = []
            for _ in range(epochs):
                chunk = []
                for batch in self._batches(X, y, rng):
                    key, sub = jax.random.split(key)
                    chunk.append((sub, *batch))
                    if len(chunk) == k:
                        losses_k, opt_state = self._dispatch_chunk(
                            step_fn, chunk, opt_state)
                        loss_chunks.append(losses_k)
                        chunk = []
                # per-epoch tail keeps a constant second shape (batches
                # per epoch is constant, so the tail size is too)
                if chunk:
                    losses_k, opt_state = self._dispatch_chunk(
                        step_fn, chunk, opt_state)
                    loss_chunks.append(losses_k)
            losses = (np.concatenate([np.asarray(jax.device_get(c))
                                      for c in loss_chunks])
                      if loss_chunks else np.array([]))
            rec = {
                "stage": stage,
                "max_group": max_group,
                "loss": float(np.mean(losses[-20:])) if len(losses) else float("nan"),
            }
            if X_val is not None and y_val is not None:
                rec.update(self.evaluate(X_val, y_val))
            history.append(rec)
            log.info("fine-tune stage %d done: %s", stage, rec)
        return history

    # ------------------------------------------------------------------

    def predict_proba(self, X: List[np.ndarray], batch_size: Optional[int] = None) -> np.ndarray:
        if self.variables is None:
            raise ValueError("not initialized")
        out = []
        # inference carries no backward activations: default to 4x the
        # training batch, for fewer dispatches
        bs = batch_size or 4 * self.ft.batch_size
        for i in range(0, len(X), bs):
            idx = np.arange(i, min(i + bs, len(X)))
            pad_idx = idx
            if len(pad_idx) < bs:
                pad_idx = np.concatenate([idx, np.zeros(bs - len(idx), np.int64)])
            tokens, lengths = self._pad(X, pad_idx)
            logits = self.model.apply(
                self.variables, jnp.asarray(tokens), jnp.asarray(lengths)
            )
            logits = np.asarray(logits, np.float32)[: len(idx)]
            if self.config.multi_label:
                out.append(1.0 / (1.0 + np.exp(-logits)))
            else:
                e = np.exp(logits - logits.max(-1, keepdims=True))
                out.append(e / e.sum(-1, keepdims=True))
        return np.concatenate(out, axis=0)

    def evaluate(self, X: List[np.ndarray], y: np.ndarray) -> Dict:
        """Per-label AUC + weighted average (the notebook's quality table)."""
        from sklearn.metrics import roc_auc_score

        probs = self.predict_proba(X)
        y = np.asarray(y)
        if not self.config.multi_label:
            acc = float((probs.argmax(-1) == y).mean())
            return {"val_accuracy": acc}
        aucs, weights = {}, []
        for label in range(y.shape[1]):
            col = y[:, label]
            if col.min() == col.max():
                continue
            aucs[label] = float(roc_auc_score(col, probs[:, label]))
            weights.append(col.sum())
        weighted = float(np.average(list(aucs.values()), weights=weights)) if aucs else float("nan")
        return {"per_label_auc": aucs, "weighted_auc": weighted}
