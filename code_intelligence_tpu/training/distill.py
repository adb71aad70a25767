"""Embedding distillation: a Pallas-resident student for the serving path.

The flagship encoder (emb_sz=800, n_hid=2500 — `Issue_Embeddings/
train.py:42-46`) is HBM-roofline-bound on TPU: its recurrent weights are
3-10x VMEM, so every inference step re-streams them (docs/RUNBOOK.md §11).
This module distills it into a student with the SAME emb_sz — the pooled
embedding is ``concat[mean,max,last]`` of emb_sz-dim outputs, so the 2400-d
wire contract (`app.py:69`) and every downstream head (MLP 1600-d
truncation, `embeddings.py:116`) keep working unchanged — but ``n_hid <=
1024``, which makes EVERY recurrent layer fit the weights-resident Pallas
cell (`ops/pallas_lstm.py`): one VMEM load per window instead of one HBM
stream per step. The student is a drop-in for `InferenceEngine.from_export`.

No reference counterpart (the reference serves the full model, V100-sized);
this is TPU-first serving optimization the framework adds. Training
objective: cosine + MSE between teacher and student pooled embeddings over
issue documents — the quantity the serving path actually returns.

CLI:

    python -m code_intelligence_tpu.training.distill \
        --teacher runs/lm/encoder_export --issues issues.jsonl \
        --out runs/student_export --n_hid 1024 --n_layers 4 --steps 2000
"""

from __future__ import annotations

import dataclasses
import json
import logging
from pathlib import Path
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from code_intelligence_tpu.models import AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states
from code_intelligence_tpu.models.classifier import masked_concat_pool
from code_intelligence_tpu.training.loop import train_cell_config

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class DistillConfig:
    """Student sizing + optimization knobs."""

    n_hid: int = 1024          # <= MAX_RESIDENT_H: every layer Pallas-resident
    n_layers: int = 4
    max_len: int = 400         # window per doc (fine-tune's ft_max_len scale)
    batch_size: int = 16
    lr: float = 2e-3
    steps: int = 2000
    alpha_mse: float = 0.5     # loss = (1 - cosine) + alpha * MSE
    # optimization steps scanned per device dispatch (the LM trainer's
    # steps_per_dispatch pattern): per-dispatch host latency would
    # otherwise dominate the 1500-step full-scale run
    steps_per_dispatch: int = 10
    seed: int = 0
    # what the EXPORTED student config carries, for the serve side to read
    # (inference/engine.py). The distillation step's own cell is chosen by
    # the train-side rule (training/loop.py::train_cell_config).
    lstm_use_pallas: bool = True
    # dtype written into the exported config — the one the SERVING path
    # runs. bf16 halves serve-time HBM traffic and W_hh residency cost
    # (under the round-3 v5e budget, bf16 is resident to H~2600 vs ~1800
    # for f32 — ops/pallas_lstm.fits_resident); training itself stays f32.
    export_dtype: str = "bfloat16"


class EmbeddingDistiller:
    """Trains a student encoder to reproduce the teacher's pooled
    embeddings; both run deterministic (this is regression, not LM
    training — the AWD regularizers would only add target noise)."""

    def __init__(
        self,
        teacher_params,
        teacher_cfg: AWDLSTMConfig,
        dcfg: DistillConfig = DistillConfig(),
    ):
        if dcfg.n_hid > teacher_cfg.n_hid:
            raise ValueError("student n_hid must not exceed the teacher's")
        if dcfg.lstm_use_pallas:
            from code_intelligence_tpu.ops.pallas_lstm import fits_resident

            itemsize = np.dtype(dcfg.export_dtype).itemsize
            if not fits_resident(dcfg.n_hid, itemsize):
                raise ValueError(
                    f"n_hid={dcfg.n_hid} at {dcfg.export_dtype} is not "
                    "Pallas-resident (W_hh exceeds the VMEM budget) — the "
                    "whole point of the student; lower n_hid or use bf16")
        self.teacher_params = teacher_params
        self.teacher_cfg = dataclasses.replace(teacher_cfg, dtype=jnp.float32)
        self.dcfg = dcfg
        # same emb_sz => same 3*emb_sz pooled dim => same wire contract
        self.student_cfg = dataclasses.replace(
            teacher_cfg,
            n_hid=dcfg.n_hid,
            n_layers=dcfg.n_layers,
            lstm_use_pallas=dcfg.lstm_use_pallas,
            dtype=jnp.float32,
        )
        self.teacher_enc = AWDLSTMEncoder(self.teacher_cfg)
        # one device, float32: the same rule as the LM trainer's step
        self.student_enc = AWDLSTMEncoder(
            train_cell_config(self.student_cfg, mesh_size=1)[0])
        self.optimizer = optax.adamw(dcfg.lr, weight_decay=0.01)
        self.params = None
        self.opt_state = None
        self._step = None
        self._eval = None

    # ------------------------------------------------------------------

    def _pooled(self, enc: AWDLSTMEncoder, params, tokens, lengths):
        states = init_lstm_states(enc.config, tokens.shape[0])
        _, dropped, _ = enc.apply(
            {"params": params}, tokens, states, deterministic=True)
        return masked_concat_pool(dropped.astype(jnp.float32), lengths)

    def init(self, rng: Optional[jax.Array] = None) -> None:
        rng = rng if rng is not None else jax.random.PRNGKey(self.dcfg.seed)
        tokens = jnp.zeros((1, 8), jnp.int32)
        states = init_lstm_states(self.student_cfg, 1)
        self.params = self.student_enc.init(
            {"params": rng}, tokens, states)["params"]
        self.opt_state = self.optimizer.init(self.params)

    def _make_step(self):
        optimizer = self.optimizer

        def step(params, opt_state, tokens, lengths):
            target = jax.lax.stop_gradient(
                self._pooled(self.teacher_enc, self.teacher_params,
                             tokens, lengths))

            def loss_fn(p):
                pred = self._pooled(self.student_enc, p, tokens, lengths)
                cos = optax.cosine_similarity(pred, target, epsilon=1e-8)
                mse = jnp.mean(jnp.square(pred - target))
                return jnp.mean(1.0 - cos) + self.dcfg.alpha_mse * mse, (
                    jnp.mean(cos), mse)

            (loss, (cos, mse)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, {"loss": loss, "cosine": cos, "mse": mse}

        # k steps scanned per device program — tokens/lengths arrive
        # stacked (k, B, L); metrics come back as (k,) arrays
        from code_intelligence_tpu.training.dispatch import scan_dispatch

        return scan_dispatch(step)

    # ------------------------------------------------------------------

    def _pad(self, seqs: Sequence[np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
        L = self.dcfg.max_len
        out = np.full((len(seqs), L), self.student_cfg.pad_id, np.int32)
        lengths = np.zeros(len(seqs), np.int32)
        for i, s in enumerate(seqs):
            s = np.asarray(s, np.int32)[:L]
            out[i, : len(s)] = s
            lengths[i] = max(len(s), 1)
        return out, lengths

    def fit(  # graft: hot
        self,
        id_seqs: Sequence[np.ndarray],
        log_every: int = 50,
    ) -> List[dict]:
        """Run ``dcfg.steps`` optimization steps over shuffled doc batches.

        Batch selection order is identical regardless of
        ``steps_per_dispatch`` (the rng draws per logical step), so the
        dispatch batching changes wall-clock, not the training run."""
        if self.params is None:
            self.init()
        if self._step is None:
            self._step = self._make_step()
        rng = np.random.RandomState(self.dcfg.seed)
        history: List[dict] = []
        B = self.dcfg.batch_size
        k = max(1, self.dcfg.steps_per_dispatch)
        step_i = 0
        while step_i < self.dcfg.steps:
            # Full chunks run the (k, B, L) program; a ragged tail runs
            # the (1, B, L) program step-by-step — at most TWO traced
            # shapes ever, never a one-off recompile of the k-scan for a
            # leftover size (the loop.py evaluate() tail pattern).
            kk = k if self.dcfg.steps - step_i >= k else 1
            toks, lens = [], []
            for _ in range(kk):
                idx = rng.randint(0, len(id_seqs), size=B)
                t, ln = self._pad([id_seqs[j] for j in idx])
                toks.append(t)
                lens.append(ln)
            self.params, self.opt_state, metrics = self._step(
                self.params, self.opt_state, np.stack(toks), np.stack(lens))
            logged = [j for j in range(kk)
                      if (step_i + j) % log_every == 0
                      or (step_i + j) == self.dcfg.steps - 1]
            if logged:
                # transfer metrics only when some step in the chunk is
                # actually logged — an unconditional device->host pull per
                # dispatch would re-add the round-trip this scan removes
                ms = {key: np.asarray(jax.device_get(v))
                      for key, v in metrics.items()}
                for j in logged:
                    s = step_i + j
                    m = {key: float(v[j]) for key, v in ms.items()}
                    m["step"] = s
                    history.append(m)
                    log.info(
                        "distill step %d: loss=%.4f cosine=%.4f mse=%.5f",
                        s, m["loss"], m["cosine"], m["mse"])
            step_i += kk
        return history

    def evaluate(self, id_seqs: Sequence[np.ndarray]) -> dict:
        """Mean cosine/MSE between teacher and student pooled embeddings.

        One jitted program, fixed (B, max_len) shapes — the ragged last
        batch is padded to B rows and the extras masked out, so no batch
        retraces the two encoders."""
        if self.params is None:
            self.init()
        if self._eval is None:

            def eval_fn(params, tokens, lengths):
                t = self._pooled(self.teacher_enc, self.teacher_params,
                                 tokens, lengths)
                s = self._pooled(self.student_enc, params, tokens, lengths)
                return (optax.cosine_similarity(s, t, epsilon=1e-8),
                        jnp.mean(jnp.square(s - t), axis=-1))

            self._eval = jax.jit(eval_fn)
        cos_all, mse_all = [], []
        B = self.dcfg.batch_size
        for i in range(0, len(id_seqs), B):
            chunk = list(id_seqs[i : i + B])
            n = len(chunk)
            chunk += [chunk[-1]] * (B - n)  # pad batch; drop extras below
            tokens, lengths = self._pad(chunk)
            cos, mse = self._eval(self.params, tokens, lengths)
            cos_all.append(np.asarray(cos)[:n])
            mse_all.append(np.asarray(mse)[:n])
        return {
            "mean_cosine": float(np.concatenate(cos_all).mean()),
            "mean_mse": float(np.concatenate(mse_all).mean()),
            "n_docs": len(id_seqs),
        }

    def export(self, out_dir, vocab=None) -> Path:
        """Write the student as an ``encoder_export`` directory —
        `InferenceEngine.from_export` loads it unchanged. The exported
        config carries ``export_dtype`` (bf16 by default: the dtype at
        which the Pallas residency promise actually holds at serve time)."""
        from code_intelligence_tpu.training.checkpoint import export_encoder

        serve_cfg = dataclasses.replace(
            self.student_cfg, dtype=np.dtype(self.dcfg.export_dtype))
        return export_encoder(out_dir, self.params, serve_cfg, vocab)


def main(argv=None) -> dict:
    import argparse

    from code_intelligence_tpu.data.corpus import TokenCorpus
    from code_intelligence_tpu.training.checkpoint import load_encoder

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--teacher", required=True, help="teacher encoder_export dir")
    p.add_argument("--issues", required=True,
                   help="JSONL with a 'text' field (quality-harness labeled "
                        "split format) used as the distillation corpus")
    p.add_argument("--corpus_dir", default=None,
                   help="TokenCorpus dir for the vocab (defaults to the "
                        "teacher export's vocab)")
    p.add_argument("--out", required=True, help="student encoder_export dir")
    p.add_argument("--n_hid", type=int, default=1024)
    p.add_argument("--n_layers", type=int, default=4)
    p.add_argument("--steps", type=int, default=2000)
    p.add_argument("--batch_size", type=int, default=16)
    p.add_argument("--max_len", type=int, default=400)
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--steps_per_dispatch", type=int, default=10,
                   help="optimization steps scanned per device dispatch "
                        "(tune to the attachment's dispatch latency; 1 "
                        "disables the scan)")
    p.add_argument("--holdout", type=int, default=200,
                   help="docs reserved for the fidelity eval")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")

    teacher_params, teacher_cfg, vocab_path = load_encoder(args.teacher)
    if args.corpus_dir:
        vocab = TokenCorpus(Path(args.corpus_dir)).vocab
    else:
        from code_intelligence_tpu.text import Vocab

        if vocab_path is None:
            raise SystemExit("teacher export has no vocab; pass --corpus_dir")
        vocab = Vocab.load(vocab_path)

    # SAME tokenization as the serving path (engine.numericalize): the
    # student must be trained on the token distribution it will serve —
    # raw .split() would skew toward unk and untrain case/punct handling
    from code_intelligence_tpu.text.tokenizer import Tokenizer

    tok = Tokenizer(backend="auto")
    seqs: List[np.ndarray] = []
    with open(args.issues, encoding="utf-8") as f:
        for line in f:
            text = json.loads(line)["text"]  # pre-ruled (build_issue_text)
            seqs.append(np.asarray(
                vocab.numericalize(tok.tokenize_pre_processed(text)), np.int32))
    if len(seqs) <= args.holdout:
        raise SystemExit(f"need more than {args.holdout} docs, got {len(seqs)}")
    train, held = seqs[args.holdout:], seqs[: args.holdout]

    dcfg = DistillConfig(
        n_hid=args.n_hid, n_layers=args.n_layers, steps=args.steps,
        batch_size=args.batch_size, max_len=args.max_len, lr=args.lr,
        steps_per_dispatch=args.steps_per_dispatch,
    )
    distiller = EmbeddingDistiller(teacher_params, teacher_cfg, dcfg)
    distiller.init()
    before = distiller.evaluate(held)
    distiller.fit(train)
    after = distiller.evaluate(held)
    out_dir = distiller.export(args.out, vocab)
    report = {
        "student": {"n_hid": args.n_hid, "n_layers": args.n_layers,
                    "lstm_use_pallas": dcfg.lstm_use_pallas},
        "holdout_cosine_before": before["mean_cosine"],
        "holdout_cosine_after": after["mean_cosine"],
        "holdout_mse_after": after["mean_mse"],
        "export": str(out_dir),
    }
    log.info("distilled: %s", report)
    print(json.dumps(report))
    return report


if __name__ == "__main__":
    main()
