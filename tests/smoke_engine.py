"""A small seeded engine for tests that need real compute, no artifact.

Sized so that the forward's compute, not the dispatch overhead, dominates:
at toy widths a test of the serve path measures the host."""

import jax
import numpy as np

from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.models import (
    AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states)
from code_intelligence_tpu.text import SPECIALS, Vocab


def make_smoke_engine(batch_size: int = 8) -> InferenceEngine:
    cfg = AWDLSTMConfig(vocab_size=200, emb_sz=32, n_hid=96, n_layers=2)
    params = AWDLSTMEncoder(cfg).init(
        {"params": jax.random.PRNGKey(0)},
        np.zeros((1, 4), np.int32), init_lstm_states(cfg, 1))["params"]
    vocab = Vocab(SPECIALS + [f"w{i}" for i in range(200 - len(SPECIALS))])
    return InferenceEngine(params, cfg, vocab, batch_size=batch_size)
