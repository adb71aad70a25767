"""EVA attention over a block cache and a summary cache: one softmax
over two kinds of key.

Positions are cut into BLOCKS of ``window`` and every block into CHUNKS
of ``chunk`` (``window`` a whole number of chunks). A query at position
``i`` in block ``B = i // window`` sees

* the keys of ITS OWN block at or before it, exactly (``B(j) = B`` and
  ``j <= i``: causal inside the block; the block starts at a multiple of
  ``window`` and is not a sliding window), and
* for every chunk of every EARLIER block ONE summary key and ONE
  summary value (``chunk_summaries``): none of its own block,

under one maximum, one sum and one normalisation:

    Z_i = sum_{j in block} exp(s q_i.k_j) + sum_{c earlier} exp(s q_i.ksum_c)
    o_i = [sum_j exp(s q_i.k_j) v_j + sum_c exp(s q_i.ksum_c) vsum_c] / Z_i

Rotary is the caller's: keys come here turned, positions here only mask.

**What is carried, head-major as ``ops/attention.py`` carries its
cache.** The block cache ``(b, H, W, d)`` twice: position ``p`` lives in
slot ``p % W`` and a new block overwrites it from slot 0, so the slots
after the query's own hold the block BEFORE and are masked (``slot <=
query's slot``); a ring keeps the last ``window`` positions, this cache
EMPTIES at every multiple of ``window``. The summary cache ``(b, H, P /
chunk, d)`` twice, chunk ``c`` in slot ``c``: it grows at a ``chunk``-th
of the document's rate, and a block's summaries become visible together
when the block has passed (``slot < B * window / chunk``).

**A chunk program never straddles a block.** The one premise is
``ops/attention.py``'s own: every chunk program of a group runs one
length ``T``, so ``pos`` is a multiple of ``T``. With a block cache of a
whole number of programs (``W % T == 0``, refused otherwise) a program's
queries then share one ``B``: the visible summaries are the prefix ``[0,
B * window / chunk)`` for all of them, and the
program's own summaries are written at ``pos / chunk`` where they stay
masked until the block has passed. A document one block holds (a cache
allocated under ``window`` slots) never sees a summary: plain causal
softmax attention.

**The work follows the positions reached**: keys go a block of slots at
a time under a running softmax (maximum, sum and weighted values carried
between blocks, float32), first the block cache up to the query's own
slots, then the summaries of the blocks passed; nothing allocated and
not yet visible is scored. Scores and softmax are float32; the two
products take ``mxu_dtype`` inputs (``mixedp_attn``).

**One core so far**, XLA's (``lax.fori_loop`` over key blocks, its
float32 score tiles through HBM between fusions): a Pallas core, picked
by a ``core_is_kernel`` of observables as ``ops/attention.py`` picks
its own, is a later change (the encoder's ``eva_kernel_layers`` is where
it will show).
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
from jax import lax

from code_intelligence_tpu.ops.attention import _MASKED


def chunk_summaries(
    k: jnp.ndarray,      # (b, T, H, d): keys, turned
    v: jnp.ndarray,      # (b, T, H, d)
    phi: jnp.ndarray,    # (H, d): a head's pooling direction
    mu: jnp.ndarray,     # (H, d): a head's offset of the pooled key
    valid: jnp.ndarray,  # (b, T) bool: lanes that hold a token
    scale: float,
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(ksum, vsum)`` ``(b, H, T / chunk, d)`` float32, head-major as
    they are cached: a chunk's positions weighed by ``a_m = softmax_m(s
    phi_h . k_m)`` over the chunk's VALID lanes, ``ksum = sum a_m k_m +
    mu_h``, ``vsum = sum a_m v_m``. A padding lane has weight 0; a chunk
    of padding lanes alone gives zeros (and ``mu``), and is never
    visible."""
    b, T, H, d = k.shape
    if T % chunk:
        raise ValueError(
            f"a chunk program of {T} positions does not hold whole chunks "
            f"of {chunk}: a chunk is summarised where it is written")
    n = T // chunk
    kf = k.astype(jnp.float32).reshape(b, n, chunk, H, d)
    vf = v.astype(jnp.float32).reshape(b, n, chunk, H, d)
    ok = valid.reshape(b, n, chunk, 1)
    s = jnp.einsum("bnmhd,hd->bnmh", kf, phi.astype(jnp.float32)) * scale
    s = jnp.where(ok, s, _MASKED)
    e = jnp.where(ok, jnp.exp(s - s.max(axis=2, keepdims=True)), 0.0)
    a = e / jnp.maximum(e.sum(axis=2, keepdims=True), 1e-30)
    ksum = jnp.einsum("bnmh,bnmhd->bhnd", a, kf) \
        + mu.astype(jnp.float32)[None, :, None, :]
    vsum = jnp.einsum("bnmh,bnmhd->bhnd", a, vf)
    return ksum, vsum


def write_summaries(k_sum, v_sum, ksum, vsum, pos, chunk: int):
    """The summary caches with a program's own ``ksum`` and ``vsum``
    written at ``pos / chunk`` (masked there until the block passes)."""
    at = pos // chunk
    return (lax.dynamic_update_slice_in_dim(
                k_sum, ksum.astype(k_sum.dtype), at, axis=2),
            lax.dynamic_update_slice_in_dim(
                v_sum, vsum.astype(v_sum.dtype), at, axis=2))


def eva_cached(
    q: jnp.ndarray,        # (b, T, H, d)
    k: jnp.ndarray,        # (b, T, H, d): turned, as they are cached
    v: jnp.ndarray,        # (b, T, H, d)
    k_block: jnp.ndarray,  # (b, H, W, d): the block cache
    v_block: jnp.ndarray,
    k_sum: jnp.ndarray,    # (b, H, S, d): the summary cache
    v_sum: jnp.ndarray,
    pos: jnp.ndarray,      # () int32: positions before this chunk
    scale: float,
    window: int,
    chunk: int,
    mxu_dtype=jnp.bfloat16,
    key_block: int = 512,
):
    """``(out (b, T, H, d) float32, k_block, v_block, met)`` with the
    chunk written into the block cache at ``pos % W``. The summary
    caches are read as they come (``write_summaries`` is the caller's:
    a program's own summaries are not visible to it). ``met`` is
    ``(singletons, summaries)``, each ``(T,)`` int32: the keys of either
    kind the mask ADMITTED for each of the chunk's queries, counted from
    the masks as they were applied."""
    b, T, H, d = q.shape
    W = k_block.shape[2]
    if window % chunk:
        raise ValueError(
            f"a block of {window} positions is not whole chunks of {chunk}")
    if W > window or W % T:
        raise ValueError(
            f"a block cache of {W} slots does not hold whole chunk programs "
            f"of {T} inside a block of {window}: a program that straddled "
            "a block would see two sets of summaries")
    at = pos % W
    k_block = lax.dynamic_update_slice_in_dim(
        k_block, k.swapaxes(1, 2).astype(k_block.dtype), at, axis=2)
    v_block = lax.dynamic_update_slice_in_dim(
        v_block, v.swapaxes(1, 2).astype(v_block.dtype), at, axis=2)
    out, met = _xla_core(q, k_block, v_block, k_sum, v_sum, pos, scale,
                         window, chunk, mxu_dtype, key_block)
    return out, k_block, v_block, met


def _reach(pos, T: int, W: int, window: int, chunk: int):
    """What the queries of the chunk at ``pos`` can see, as the core's
    two loops need it: ``(slots, stale, summaries)``. ``slots``: the
    block cache's slots up to the chunk's last query's own; ``stale``:
    whether a slot AFTER a query's own, which holds the block before, is
    admitted (never: the block empties); ``summaries``: those of the
    blocks passed, none of the chunk's own block."""
    return pos % W + T, False, (pos // window) * (window // chunk)


def _xla_core(q, k_block, v_block, k_sum, v_sum, pos, scale, window, chunk,
              mxu_dtype, key_block):
    """The chunk's queries against the block cache they are already
    written into and the summaries of the blocks passed: two
    ``lax.fori_loop`` s over key blocks that share one running softmax."""
    b, T, H, d = q.shape
    W, S = k_block.shape[2], k_sum.shape[2]
    slots, stale, seen = _reach(pos, T, W, window, chunk)
    qh = q.astype(mxu_dtype).swapaxes(1, 2)  # (b, H, T, d)

    def absorb(state, keys, vals, ok):
        """One block of keys of either kind into the running softmax,
        and the keys it admitted a query into the count."""
        (m, l, acc), met = state
        s = jnp.einsum("bhtd,bhsd->bhts", qh, keys.astype(mxu_dtype),
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, _MASKED)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        fade = jnp.exp(m - m_new)
        return ((m_new, l * fade + p.sum(axis=-1),
                 acc * fade[..., None] + jnp.einsum(
                     "bhts,bhsd->bhtd", p.astype(mxu_dtype),
                     vals.astype(mxu_dtype),
                     preferred_element_type=jnp.float32)),
                met + ok.sum(axis=-1, dtype=jnp.int32))

    kb = key_block if W > key_block and W % key_block == 0 else W
    query_slot = (pos % W + jnp.arange(T))[:, None]

    def own_block(j, state):
        at = j * kb + jnp.arange(kb)[None, :]
        return absorb(
            state, lax.dynamic_slice_in_dim(k_block, j * kb, kb, axis=2),
            lax.dynamic_slice_in_dim(v_block, j * kb, kb, axis=2),
            (at <= query_slot) | stale)

    # summaries go a block's worth at a time (what is visible is whole
    # blocks of them); a cache allocated under one block's worth is of a
    # document that never sees a summary
    sb = window // chunk
    sb = sb if S % sb == 0 else S

    def passed_block(j, state):
        at = j * sb + jnp.arange(sb)[None, :]
        return absorb(
            state, lax.dynamic_slice_in_dim(k_sum, j * sb, sb, axis=2),
            lax.dynamic_slice_in_dim(v_sum, j * sb, sb, axis=2),
            jnp.broadcast_to(at < seen, (T, sb)))

    stat = jnp.full((b, H, T), _MASKED, jnp.float32)
    none = jnp.zeros((T,), jnp.int32)
    softmax = (stat, jnp.zeros_like(stat),
               jnp.zeros((b, H, T, d), jnp.float32))
    softmax, singletons = lax.fori_loop(
        0, jnp.clip((slots + kb - 1) // kb, 1, W // kb), own_block,
        (softmax, none))
    (_, l, acc), summaries = lax.fori_loop(
        0, (seen + sb - 1) // sb, passed_block, (softmax, none))
    out = acc / l[..., None]
    return out.swapaxes(1, 2), (singletons, summaries)
