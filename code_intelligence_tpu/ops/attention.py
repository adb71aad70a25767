"""Causal grouped-query attention over a key/value cache.

One chunk of ``T`` queries attends to everything cached before it and to
itself: the chunk's keys and values are written into the cache at
``pos`` and the queries read the whole cache under the mask
``key position <= pos + query index``. The cache is fixed-size
(``S`` positions), so one compiled program serves every chunk of a
document; positions past ``pos + T`` hold stale or zero rows and are
masked. No rotary embedding (the one model that uses this has
``position_embedding_type: nope``).

Scores and softmax are float32; the two products take ``mxu_dtype``
inputs. Queries are processed ``q_block`` at a time so that the float32
scores of a (16, 512) chunk against 2048 positions never exist at once.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


def gqa_cached(
    q: jnp.ndarray,        # (b, T, Hq, d)
    k: jnp.ndarray,        # (b, T, Hkv, d)
    v: jnp.ndarray,        # (b, T, Hkv, d)
    k_cache: jnp.ndarray,  # (b, S, Hkv, d)
    v_cache: jnp.ndarray,  # (b, S, Hkv, d)
    pos: jnp.ndarray,      # () int32: positions already cached
    scale: float,
    q_block: int = 128,
    mxu_dtype=jnp.bfloat16,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(out (b, T, Hq, d) float32, k_cache, v_cache)`` with the chunk
    appended at ``pos``. Query head ``i`` reads key/value head
    ``i // (Hq // Hkv)``."""
    b, T, Hq, d = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
    k_cache = lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), pos, axis=1)
    kc, vc = k_cache.astype(mxu_dtype), v_cache.astype(mxu_dtype)
    key_pos = jnp.arange(S)

    def block(q_blk, first):
        # q_blk (b, t, Hkv, rep, d); first: chunk index of its first query
        t = q_blk.shape[1]
        s = jnp.einsum("btgrd,bsgd->bgrts", q_blk.astype(mxu_dtype), kc,
                       preferred_element_type=jnp.float32) * scale
        seen = key_pos[None, :] <= (pos + first + jnp.arange(t))[:, None]
        # every query sees at least itself, so no row is all -inf
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgrts,bsgd->btgrd", p.astype(mxu_dtype), vc,
                          preferred_element_type=jnp.float32)

    qg = q.reshape(b, T, Hkv, rep, d)
    if T <= q_block or T % q_block:
        out = block(qg, 0)
    else:
        n = T // q_block
        blocks = qg.reshape(b, n, q_block, Hkv, rep, d).swapaxes(0, 1)
        out = lax.map(lambda a: block(a[0], a[1]),
                      (blocks, jnp.arange(n) * q_block))
        out = out.swapaxes(0, 1).reshape(b, T, Hkv, rep, d)
    return out.reshape(b, T, Hq, d), k_cache, v_cache
