"""Flax-param pytree <-> .npz serialization.

Shared by the encoder export (`training/checkpoint.py`), the MLP head and
the universal model: params are stored as a flat npz keyed by
``'/'.join(path)`` so artifacts are plain numpy files loadable without
flax (or from the native runtime).
"""

from __future__ import annotations

from pathlib import Path
from typing import Any, Dict

import jax
import numpy as np


def params_to_arrays(params: Any) -> Dict[str, np.ndarray]:
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): np.asarray(v)
        for path, v in flat
    }


def save_params_npz(path, params: Any) -> None:
    np.savez(Path(path), **params_to_arrays(params))


def load_params_npz(path) -> dict:
    import jax.numpy as jnp

    npz = np.load(Path(path))
    params: dict = {}
    for key in npz.files:
        node = params
        parts = key.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        arr = npz[key]
        if arr.dtype == np.dtype("V2"):
            # numpy stores bfloat16 (an ml_dtypes extension type) as raw
            # 2-byte records; nothing else here is 2 bytes of void
            arr = arr.view(jnp.bfloat16)
        node[parts[-1]] = jnp.asarray(arr)
    return params
