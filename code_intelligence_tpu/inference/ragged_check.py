"""Device-free ragged-vs-dense acceptance fixture (``runbook_ci
--check_ragged``).

The ragged paged scheduler's whole claim — mixed-length serve batches
cost ~sum-of-tokens instead of rows×chunk_len — is provable WITHOUT a
TPU: the step programs' flops come from AOT ``cost_analysis`` and the
step counts from actually running both schedulers on the committed
mixed-length fixture (`fixtures/ragged_lengths.json`, frozen literal
lengths so the gate never depends on a sampler's cross-version
stability). The gate asserts, on a tiny randomly-initialized engine:

* exact allclose parity between the ragged and dense slot paths (a
  scheduler that changes answers is not a scheduler),
* flops-per-token(ragged) < flops-per-token(dense), with the committed
  fixture expected to land well under the ``max_ratio`` acceptance bound,
* the ragged steady-state loop clean under ``no_implicit_transfers()``
  + ``recompile_guard(budget=0)`` — one compiled step shape, the page
  table riding the packed staging block.

CI is the right place for this: the ragged path is an optimization that
only pays off on mixed lengths, so a regression (a geometry change, a
step program growing per-step overhead, a parity break) would otherwise
surface only in production metrics. RUNBOOK §23.

Flops-per-token is the ONE step program's AOT flops × steps ÷ valid
tokens, off the schedulers' lifetime counters (every pass stages the same
schedule). This gate is the only holder of these pins: it proves counts
and parity on the CPU, never a rate; what the ragged path is worth in
time is for a benchmark cell on the chip (ROADMAP Queue 2, part B).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Optional

import numpy as np

#: the committed mixed-length acceptance fixture
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "ragged_lengths.json"


def _tiny_engine(batch_size: int = 8):
    """Small randomly-initialized engine with a compute-dominated forward
    (chunk_len 64 / page_len 16 — the production geometry ratio, not the
    unit-test toy one)."""
    import jax

    from code_intelligence_tpu.inference import InferenceEngine
    from code_intelligence_tpu.models import (
        AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states)
    from code_intelligence_tpu.text import SPECIALS, Vocab

    cfg = AWDLSTMConfig(vocab_size=160, emb_sz=16, n_hid=48, n_layers=2)
    enc = AWDLSTMEncoder(cfg)
    params = enc.init(
        {"params": jax.random.PRNGKey(0)},
        np.zeros((1, 4), np.int32), init_lstm_states(cfg, 1))["params"]
    vocab = Vocab(SPECIALS + [f"w{i}" for i in range(160 - len(SPECIALS))])
    return InferenceEngine(params, cfg, vocab, buckets=(32, 64),
                           batch_size=batch_size)


def run_ragged_check(fixture: Optional[Path] = None,
                     max_ratio: float = 0.6) -> dict:
    """Run the fixture through both schedulers and return the verdict
    (see module docstring for what ``ok`` asserts)."""
    from code_intelligence_tpu.analysis import runtime as audit

    fixture = Path(fixture) if fixture else FIXTURE
    spec = json.loads(fixture.read_text())
    lengths = [int(l) for l in spec["lengths"]]
    rng = np.random.RandomState(int(spec.get("seed", 0)))
    engine = _tiny_engine()
    hi = engine.config.vocab_size - 1
    ids = [rng.randint(5, hi, l).astype(np.int32) for l in lengths]

    # warm both single step shapes + the parity pin
    dense = engine.embed_ids_batch(ids, scheduler="slots")
    ragged = engine.embed_ids_batch(ids, scheduler="ragged")
    parity = float(np.max(np.abs(dense - ragged))) if ids else 0.0
    parity_ok = bool(np.allclose(ragged, dense, atol=1e-5, rtol=1e-5))

    # steady state: zero new compiles, zero implicit transfers, zero
    # retained device buffers — the page table and valid lengths ride
    # the packed staging block, and a serve pass must not grow the
    # live-buffer footprint (memory_guard, RUNBOOK §31)
    with audit.recompile_guard(fn="slots.step_ragged", budget=0), \
            audit.no_implicit_transfers(), \
            audit.memory_guard(budget_bytes=0):
        engine.embed_ids_batch(ids, scheduler="ragged")

    ds = engine.slot_scheduler()
    rs = engine.slot_scheduler(ragged=True)
    fd = (ds.step_cost_analysis()["flops"] * ds.steps_run
          / max(ds.tokens_valid, 1))
    fr = (rs.step_cost_analysis()["flops"] * rs.steps_run
          / max(rs.tokens_valid, 1))
    ratio = fr / max(fd, 1e-9)
    return {
        "fixture": str(fixture),
        "n_docs": len(ids),
        "total_tokens": int(sum(lengths)),
        "chunk_len": ds.chunk_len,
        "page_len": rs.page_len,
        "parity_max_abs_diff": parity,
        "parity_ok": parity_ok,
        "dense_wasted_lane_fraction": round(ds.wasted_lane_fraction(), 4),
        "ragged_wasted_lane_fraction": round(rs.wasted_lane_fraction(), 4),
        "flops_per_token_dense": round(fd, 1),
        "flops_per_token_ragged": round(fr, 1),
        "flops_per_token_ratio": round(ratio, 4),
        "max_ratio": max_ratio,
        "ragged_compiled_step_shapes": rs.compiled_step_shapes(),
        "audited": True,
        "ok": bool(parity_ok and ratio <= max_ratio),
    }
