"""Causal grouped-query attention over a key/value cache: global, or
under a sliding window over a ring.

One chunk of ``T`` queries attends to what is cached before it and to
itself: the chunk's keys and values are written into the cache and the
queries read it under a mask. Two encoders call it: one with no rotary
and no window (``position_embedding_type: nope``), one whose sliding
layers rotate queries and keys before they come here and pass
``window``; rotary is the caller's, positions here only mask.

**The cache is read as a ring.** It has ``S`` slots and position ``p``
lives in slot ``p % S``. A cache allocated for the whole document (``S``
>= its length) never wraps and is the plain growing cache of a global
layer. A layer with a ``window`` needs, for a chunk of ``T`` queries, the keys of
the last ``window + T - 1`` positions only, so its cache stops growing
at ``window`` + one chunk: later chunks overwrite the oldest slots. Such
a chunk is written as one slice at ``pos % S``, so with a ``window``
``S`` is a whole number of chunks and ``pos`` a multiple of ``T`` (every
chunk program of a group runs one length); without one the caller's
cache holds the whole document, chunks of any lengths are written where
they fall and nothing wraps. The position a slot holds after the write
is the latest one congruent to it, ``s + S * floor((pos + T - 1 - s) / S)``:
negative for a slot never written, which the mask drops, as it drops
``key > query`` (causal) and ``query - key >= window``.

**The work follows the positions reached**, not the allocation: keys go
``key_block`` slots at a time under a running softmax (maximum, sum and
weighted values carried between blocks, all float32), and a block of
queries stops at the last key block it can see, so the first chunk of a
16,384-slot cache pays for 512 keys and the float32 scores that exist
at once are one ``(rows, heads, q_block, key_block)`` tile whatever
``S``. A cache of one key block takes the plain softmax. Scores and
softmax are float32; the two products take ``mxu_dtype`` inputs.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# what a masked score is set to: finite, so that a key block a query sees
# nothing of leaves its running maximum finite (exp(-inf - -inf) is NaN);
# whatever such a block adds is multiplied by exp(_MASKED - score) = 0 at
# the query's first visible key, and every query sees at least itself
_MASKED = -1e30


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
    window: Optional[int] = None,
    key_block: int = 512,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(out (b, T, Hq, d) float32, k_cache, v_cache)`` with the chunk
    written at ``pos`` (at ``pos % S`` under a ``window``). Query head
    ``i`` reads key/value head ``i // (Hq // Hkv)``; with ``window`` a
    query at position ``t`` sees the keys ``t - window < j <= t``."""
    b, T, Hq, d = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
    if window is None:
        at = pos  # allocated for the whole document: it never wraps
    elif S % T:
        raise ValueError(
            f"a ring of {S} slots does not hold whole chunks of {T}: a "
            "chunk is written as one slice")
    else:
        at = pos % S
    k_cache = lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), at, axis=1)
    v_cache = lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), at, axis=1)
    last = pos + T - 1  # the newest position cached

    def seen(first, t, slot0, n):
        """(t, n) mask: queries ``first ..`` of the chunk against the
        slots ``slot0 .. slot0 + n``."""
        slots = slot0 + jnp.arange(n)
        held = slots + S * jnp.floor_divide(last - slots, S)
        query = (pos + first + jnp.arange(t))[:, None]
        ok = (held[None, :] >= 0) & (held[None, :] <= query)
        if window is not None:
            ok = ok & (query - held[None, :] < window)
        return ok

    def scores(q_blk, keys, mask):
        s = jnp.einsum("btgrd,bsgd->bgrts", q_blk, keys.astype(mxu_dtype),
                       preferred_element_type=jnp.float32) * scale
        return jnp.where(mask, s, _MASKED)

    def values(p, vals):
        return jnp.einsum("bgrts,bsgd->bgrtd", p.astype(mxu_dtype),
                          vals.astype(mxu_dtype),
                          preferred_element_type=jnp.float32)

    kb = key_block if S > key_block and S % key_block == 0 else S
    n_kb = S // kb

    def block(q_blk, first):
        # q_blk (b, t, Hkv, rep, d); first: chunk index of its first query
        t = q_blk.shape[1]
        q_blk = q_blk.astype(mxu_dtype)
        if n_kb == 1:
            p = jax.nn.softmax(
                scores(q_blk, k_cache, seen(first, t, 0, S)), axis=-1)
            return values(p, v_cache).transpose(0, 3, 1, 2, 4)

        def key_blk(j, carry):
            m, l, acc = carry
            keys = lax.dynamic_slice_in_dim(k_cache, j * kb, kb, axis=1)
            vals = lax.dynamic_slice_in_dim(v_cache, j * kb, kb, axis=1)
            s = scores(q_blk, keys, seen(first, t, j * kb, kb))
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            fade = jnp.exp(m - m_new)
            return (m_new, l * fade + p.sum(axis=-1),
                    acc * fade[..., None] + values(p, vals))

        # key blocks that hold a position these queries can see: all of
        # a ring that has wrapped
        reached = pos + first + t
        live = jnp.clip((reached + kb - 1) // kb, 1, n_kb)
        stat = jnp.full((b, Hkv, rep, t), _MASKED, jnp.float32)
        _, l, acc = lax.fori_loop(0, live, key_blk, (
            stat, jnp.zeros_like(stat),
            jnp.zeros((b, Hkv, rep, t, d), jnp.float32)))
        return (acc / l[..., None]).transpose(0, 3, 1, 2, 4)

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
