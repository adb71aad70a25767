"""The comparison that decides ``correct`` for rows of embeddings.

A served row is ``concat[mean, max, last]`` of the encoder's last layer
over the document. Max-abs over the whole row does not tell bfloat16
from int8 weights (PR 21: 0.00244 against 0.00246), so each third is
compared by its relative root-mean-square error over the whole sample:
the mean-pool third averages the per-token rounding of bfloat16 away
and keeps what a change of the weights does at every token.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

THIRDS = ("mean", "max", "last")


def row_numbers(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """``rel_rms_<third>`` for each third, and the count of non-finite
    values in ``got``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or got.shape[1] % 3:
        raise ValueError(f"rows {got.shape} against reference {want.shape}")
    e = got.shape[1] // 3
    out = {"nonfinite": float(np.size(got) - np.isfinite(got).sum())}
    got = np.nan_to_num(got, nan=0.0, posinf=0.0, neginf=0.0)
    for k, third in enumerate(THIRDS):
        g, w = got[:, k * e:(k + 1) * e], want[:, k * e:(k + 1) * e]
        out[f"rel_rms_{third}"] = float(
            np.sqrt(np.mean((g - w) ** 2)) / np.sqrt(np.mean(w ** 2)))
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each number beside its limit; ``correct`` only if every number
    that has a limit is inside it, and every limit has its number."""
    lines = []
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        inside = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(inside)
        lines.append({"name": name, "value": value, "limit": limit,
                      "inside": bool(inside)})
    return {"correct": ok, "compared": lines}
