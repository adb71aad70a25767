"""What the plain references share: seeded weights in the program's
parameter layout, the embedding lookup, the three-way pooling and the LM
loss. Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``; nothing here imports the
program, and nothing the program has made (weights, scales, tables) is
read: the benchmark makes the weights from ``--seed`` and hands the same
tree to the program and to the reference.
"""

from __future__ import annotations

import math
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (the driver's seeds
    pass 2**31, which ``PRNGKey`` alone refuses without x64)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def layer_size(model: dict, layer: int) -> int:
    return model["emb_sz"] if layer == model["n_layers"] - 1 \
        else model["n_hid"]


def draw(key, shape, scale: float, weights: dict = None):
    """Seeded weights with the variance of ``U(-scale, scale)``, the
    init range of the source configuration. ``{"dist": "uniform"}`` is
    that init itself; ``{"dist": "student_t", "df": n}`` keeps the
    variance and gives the heavy tails trained recurrent weights have
    (uniform weights fill an int8 channel's range evenly, the best case
    for quantisation, which no trained model offers)."""
    weights = weights or {"dist": "uniform"}
    if weights["dist"] == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -scale, scale)
    if weights["dist"] == "student_t":
        df = float(weights["df"])
        t = jax.random.t(key, df, shape, jnp.float32)
        return t * (scale / math.sqrt(3.0) / math.sqrt(df / (df - 2.0)))
    raise ValueError(f"unknown weight distribution {weights['dist']!r}")


def embed(params: dict, tokens) -> jnp.ndarray:
    return jnp.take(params["embedding"], tokens, axis=0)


def pool_rows(raw: np.ndarray, lengths: Sequence[int]) -> np.ndarray:
    """``concat[mean, max, last]`` over each row's valid prefix, in
    float64 numpy."""
    rows = []
    for r, n in enumerate(lengths):
        h = np.asarray(raw[r, :n], np.float64)
        rows.append(np.concatenate([h.mean(0), h.max(0), h[-1]]))
    return np.asarray(rows)


def pooled_rows(encode: Callable, params: dict, id_seqs: List[np.ndarray],
                pad_id: int, pad_to: int, block_rows: int = 16) -> np.ndarray:
    """Rows of the reference for ``id_seqs``: every document a row of a
    padded batch (rows of a recurrent encoder are independent and it is
    causal, so padding after a row's end cannot reach its valid prefix),
    ``block_rows`` rows at a time so the reference fits beside nothing.
    ``encode(params, tokens) -> (rows, T, E)`` is jitted by the caller."""
    out = []
    for start in range(0, len(id_seqs), block_rows):
        block = id_seqs[start:start + block_rows]
        tokens = np.full((block_rows, pad_to), pad_id, np.int32)
        for r, s in enumerate(block):
            tokens[r, :len(s)] = s
        with jax.default_matmul_precision("highest"):
            raw = jax.device_get(encode(params, jnp.asarray(tokens)))
        out.append(pool_rows(raw, [len(s) for s in block]))
    return np.concatenate(out, axis=0)


def lm_logits(params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
    """Tied decoder: ``hidden @ embedding^T + decoder_b``."""
    return jnp.einsum("bte,ve->btv", hidden, params["encoder"]["embedding"]) \
        + params["decoder_b"]


def cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def fake_quant_int8(w: jnp.ndarray) -> jnp.ndarray:
    """Symmetric per-output-channel int8 and back: the control's weights
    (the nearest precision below bfloat16 the contract names)."""
    if w.ndim < 2:
        return w
    scale = jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def inv_sqrt(h: int) -> float:
    return 1.0 / math.sqrt(h)
