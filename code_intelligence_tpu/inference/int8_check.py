"""Device-free int8-vs-f32 serve acceptance fixture (``runbook_ci
--check_int8``). RUNBOOK §28.

The int8 serve path's whole claim — ~4x smaller resident encoder
weights at unchanged answers — is provable WITHOUT a TPU, on the same
committed mixed-length fixture the ragged gate uses
(`fixtures/ragged_lengths.json`). On a tiny randomly-initialized
engine pair built from the SAME f32 init (quantize-at-load on one
side, ops/quantize.py), the gate asserts:

* **parity band**: int8 ragged embeddings allclose to f32 within the
  quantization band (`atol`/`rtol` loose vs the ragged gate's 1e-5 —
  int8 is lossy by construction, but boundedly so),
* **footprint**: the int8 engine's resident encoder weight bytes are
  >= ``min_footprint_ratio`` (3x) smaller than f32 — biases and f32
  per-channel scales ride along, so the ratio lands ~3.5x rather than
  a clean 4x — with the PR 4 accountant's ``compiled_hbm_bytes`` for
  both step programs recorded as supporting evidence,
* **embedding quality**: a label head trained on f32 embeddings loses
  at most ``max_auc_drop`` weighted AUC when evaluated over int8
  embeddings of the same docs (deterministic seeded synthetic labels —
  marker tokens injected into positive docs, so the pooled embedding
  carries the signal by construction),
* **audited steady state**: the int8 ragged loop clean under
  ``no_implicit_transfers()`` + ``recompile_guard(budget=0)`` — int8
  changes leaf dtypes, never shapes, so the ONE compiled step shape
  per scheduler survives.

CI is the right place: a quantization regression (a scale-axis slip, a
kernel dequant drift, a load path that silently re-quantizes) would
otherwise surface only as a quality droop in production metrics.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Optional

import numpy as np

from code_intelligence_tpu.inference.ragged_check import FIXTURE, _tiny_engine


def _tiny_engine_pair(batch_size: int = 8):
    """f32 + int8 engines over the SAME randomly-initialized params —
    the int8 one quantizes at load exactly like a real serve boot."""
    from code_intelligence_tpu.inference import InferenceEngine

    f32 = _tiny_engine(batch_size=batch_size)
    int8 = InferenceEngine(
        f32._enc_params["params"], f32.config, f32.vocab,
        buckets=f32.buckets, batch_size=batch_size, precision="int8")
    return f32, int8


def _synthetic_labeled_ids(rng: np.random.RandomState, vocab_size: int,
                           n_docs: int = 96, n_labels: int = 3):
    """Deterministic labeled docs: label k's positives carry marker
    token ``vocab_size - 1 - k`` in ~half their positions, so any
    mean-pooled embedding separates the classes."""
    ids, ys = [], np.zeros((n_docs, n_labels), np.float32)
    for d in range(n_docs):
        length = int(rng.randint(8, 40))
        doc = rng.randint(5, vocab_size - n_labels - 1, length).astype(np.int32)
        for k in range(n_labels):
            if rng.rand() < 0.5:
                ys[d, k] = 1.0
                marks = rng.rand(length) < 0.9
                doc = np.where(marks, np.int32(vocab_size - 1 - k), doc)
        ids.append(doc)
    return ids, ys


def _auc_band(f32_engine, int8_engine, max_auc_drop: float) -> dict:
    """Label-head quality gate: fit on f32 embeddings, evaluate the SAME
    head over both precisions' embeddings of held-out docs.

    Embeddings are standardized with the f32 TRAIN split's stats (the
    tiny random encoder emits ~0.06-std features the head would
    otherwise underfit); int8 embeddings go through the SAME transform —
    a quantization shift big enough to matter shows up as an AUC drop,
    which is the point."""
    from code_intelligence_tpu.labels.mlp import MLPHead

    rng = np.random.RandomState(7)
    ids, ys = _synthetic_labeled_ids(rng, f32_engine.config.vocab_size)
    n_train = int(len(ids) * 0.7)
    emb_f = f32_engine.embed_ids_batch(ids, scheduler="ragged")
    emb_q = int8_engine.embed_ids_batch(ids, scheduler="ragged")
    mu = emb_f[:n_train].mean(axis=0)
    sd = emb_f[:n_train].std(axis=0) + 1e-6
    emb_f = (emb_f - mu) / sd
    emb_q = (emb_q - mu) / sd
    head = MLPHead(hidden=(32,), batch_size=32, max_epochs=200, patience=20,
                   lr=3e-3, seed=0)
    head.fit(emb_f[:n_train], ys[:n_train])
    _, auc_f = head.calculate_auc(emb_f[n_train:], ys[n_train:])
    _, auc_q = head.calculate_auc(emb_q[n_train:], ys[n_train:])
    drop = float(auc_f - auc_q)
    return {
        "auc_f32": round(float(auc_f), 4),
        "auc_int8": round(float(auc_q), 4),
        "auc_drop": round(drop, 4),
        "max_auc_drop": max_auc_drop,
        # the head must have learned SOMETHING for the band to mean
        # anything — markers make this ~1.0 by construction
        "auc_informative": bool(auc_f > 0.8),
        "auc_ok": bool(auc_f > 0.8 and drop <= max_auc_drop),
    }


def _step_hbm_evidence(report, start_f32: int, start_int8: int) -> dict:
    """Accountant ``compiled_hbm_bytes`` for each engine's ragged step
    (PR 4 InstrumentedJit): windowed by the ledger's marks (an entry's
    ``seq``) since both engines share the process-global accountant. Evidence, not the pin
    — the tiny gate engine's activation share dominates its step args,
    so the hard >=3x lives on the WEIGHT footprint; here we only require
    int8 not be LARGER when both numbers exist (the accountant can be
    disabled via CI_TPU_NO_XLA_ACCOUNTING)."""
    def window_hbm(start, stop):
        vals = [e.get("hbm_bytes", 0) for e in report
                if start < e["seq"] <= stop
                and e.get("fn") == "slots.step_ragged"]
        return max(vals) if vals else 0

    hbm_f = window_hbm(start_f32, start_int8)
    hbm_q = window_hbm(start_int8, math.inf)
    return {
        "step_hbm_bytes_f32": int(hbm_f),
        "step_hbm_bytes_int8": int(hbm_q),
        "step_hbm_ok": bool(hbm_f == 0 or hbm_q == 0 or hbm_q <= hbm_f),
    }


def run_int8_check(fixture: Optional[Path] = None,
                   atol: float = 0.05, rtol: float = 0.05,
                   min_footprint_ratio: float = 3.0,
                   max_auc_drop: float = 0.05) -> dict:
    """Run the committed fixture through the f32 and int8 serve paths
    and return the verdict (see module docstring for what ``ok``
    asserts)."""
    from code_intelligence_tpu.analysis import runtime as audit
    from code_intelligence_tpu.utils import flight_recorder

    fixture = Path(fixture) if fixture else FIXTURE
    spec = json.loads(fixture.read_text())
    lengths = [int(l) for l in spec["lengths"]]
    rng = np.random.RandomState(int(spec.get("seed", 0)))
    f32_engine, int8_engine = _tiny_engine_pair()
    hi = f32_engine.config.vocab_size - 1
    ids = [rng.randint(5, hi, l).astype(np.int32) for l in lengths]

    acct = flight_recorder.get_accountant()
    start_f32 = acct.compiles_mark()
    ref = f32_engine.embed_ids_batch(ids, scheduler="ragged")
    start_int8 = acct.compiles_mark()
    got = int8_engine.embed_ids_batch(ids, scheduler="ragged")
    parity = float(np.max(np.abs(ref - got))) if ids else 0.0
    parity_ok = bool(np.allclose(got, ref, atol=atol, rtol=rtol))

    # steady state: zero new compiles, zero implicit transfers — int8
    # leaves changed dtype, not shape, so the one step shape holds
    with audit.recompile_guard(fn="slots.step_ragged", budget=0), \
            audit.no_implicit_transfers():
        int8_engine.embed_ids_batch(ids, scheduler="ragged")

    ratio = (int8_engine.weight_bytes_f32
             / max(int8_engine.weight_bytes, 1))
    footprint_ok = bool(ratio >= min_footprint_ratio)
    auc = _auc_band(f32_engine, int8_engine, max_auc_drop)
    hbm = _step_hbm_evidence(acct.report(start_f32), start_f32, start_int8)
    return {
        "fixture": str(fixture),
        "n_docs": len(ids),
        "total_tokens": int(sum(lengths)),
        "precision": int8_engine.precision,
        "parity_max_abs_diff": round(parity, 6),
        "parity_atol": atol,
        "parity_rtol": rtol,
        "parity_ok": parity_ok,
        "weight_bytes_f32": int(int8_engine.weight_bytes_f32),
        "weight_bytes_int8": int(int8_engine.weight_bytes),
        "footprint_ratio": round(float(ratio), 4),
        "min_footprint_ratio": min_footprint_ratio,
        "footprint_ok": footprint_ok,
        **hbm,
        **auc,
        "int8_compiled_step_shapes":
            int8_engine.slot_scheduler(ragged=True).compiled_step_shapes(),
        "audited": True,
        "ok": bool(parity_ok and footprint_ok and auc["auc_ok"]
                   and hbm["step_hbm_ok"]),
    }
