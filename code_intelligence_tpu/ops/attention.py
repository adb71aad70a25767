"""Causal grouped-query attention over a key/value cache: global, or
under a sliding window over a ring.

One chunk of ``T`` queries attends to what is cached before it and to
itself: the chunk's keys and values are written into the cache and the
queries read it under a mask. Two encoders call it: one with no rotary
and no window (``position_embedding_type: nope``), one whose sliding
layers rotate queries and keys before they come here and pass
``window``; rotary is the caller's, positions here only mask.

**The cache is carried head-major**, ``(b, Hkv, S, d)``: a key/value
head's slots are rows of ``d`` numbers, so a block of them is a tile
either core multiplies as it lies (no relayout of the cache around the
loop; the chunk's own ``T`` positions are transposed on the way in).

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
a block of slots at a time under a running softmax (maximum, sum and
weighted values carried between blocks, all float32), and a block of
queries stops at the last key block it can see, so the first chunk of a
16,384-slot cache pays for one block of keys whatever ``S``. Scores and
softmax are float32; the two products take ``mxu_dtype`` inputs.

**Two cores, one arithmetic, chosen here** (``core_is_kernel``, from
what the program can observe; no caller selects one):

* the Pallas kernel (``_kernel_core``) on the TPU for bfloat16 operands,
  a head size the MXU's lanes take and a cache of more than one key
  block: a grid over rows, key/value heads (the ``Hq // Hkv`` query
  heads of one ride together), query blocks and, innermost, key blocks.
  A tile's scores, running maximum, sum and weighted values stay in
  VMEM from the first key block to the last and only the normalised
  output returns to HBM; ``pos`` comes in by scalar prefetch, the mask
  is made in the kernel, a key block past the last one a query block
  can see is neither fetched nor computed, and a block every query
  sees whole skips the mask. Tiles are a function of the shapes
  (``_kernel_tiles``, from one sweep on the chip);
* the XLA core (``_xla_core``) everywhere else: the CPU, float32
  operands (the parity tests), a cache of one key block (the plain
  softmax of a single-chunk document), a shape no tile divides. The
  same blocks under ``lax.fori_loop`` / ``lax.map``, its float32 score
  tiles going through HBM between fusions.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what a masked score is set to: finite, so that a key block a query sees
# nothing of leaves its running maximum finite (exp(-inf - -inf) is NaN);
# whatever such a block adds is multiplied by exp(_MASKED - score) = 0 at
# the query's first visible key, and every query sees at least itself
_MASKED = -1e30

# what the kernel gives a slot never written for the position it holds:
# after every query, so the causal test drops it
_NEVER = 1 << 30

# the kernel's score tile, its exponentials and their bfloat16 copy are
# a few MB at the tiles below; Mosaic's default scoped limit is 16 MiB of
# the v5e's 128
_KERNEL_VMEM_LIMIT = 96 * 1024 * 1024


# the largest tile: rows of queries (rep * q_block) and slots of keys.
# One sweep on the chip (v5e, 16 rows x 512 queries, 48 / 8 heads of 128;
# ms a layer, the XLA core beside it; PERF.md §6, PR 33): a wrapped ring
# of 4608 slots 34.6 -> 8.5 at (512, 1536), 9.2 at (256, 1536), 11.9 at
# (512, 512), 14.3 at (512, 2304); a full 16,384-slot cache 116.9 ->
# 24.8 at (512, 1024), 25.7 at (256, 1024), 37.0 at (512, 512), 41.6 at
# (512, 2048): the whole chunk's queries, and the most keys short of
# 2048, where the score tile (3072 x 2048 float32 and its copies) no
# longer fits what Mosaic keeps in VMEM
_TILE_ROWS = 3072
_TILE_KEYS = 1536


def _kernel_tiles(T: int, S: int, rep: int) -> Optional[Tuple[int, int]]:
    """``(q_block, key_block)`` of the kernel for a chunk of ``T`` queries
    against ``S`` slots, ``rep`` query heads a key/value head; ``None``
    where no aligned tile divides the shape. A function of the shapes
    alone: the largest tile under the two limits above.

    A tile is ``rep * q_block`` rows of queries by ``key_block`` slots:
    ``q_block`` a power of two (the kernel finds a row's query with a
    bit mask) of at least 16 rows (bfloat16's sublanes), ``key_block`` a
    multiple of the 128 lanes a score tile's rows lie along."""
    q_block = next((n for n in (512, 256, 128, 64, 32, 16)
                    if T % n == 0 and rep * n <= _TILE_ROWS), None)
    most = min(_TILE_KEYS, S)
    key_block = next((n for n in range(most - most % 128, 0, -128)
                      if S % n == 0), None)
    if q_block is None or key_block is None:
        return None
    return q_block, key_block


def core_is_kernel(backend: str, mxu_dtype, T: int, S: int, rep: int,
                   head_dim: int) -> bool:
    """Pallas kernel or XLA core, for ONE call of ``gqa_cached``: the
    rule, from what the program can observe and nothing a user sets.

    The kernel runs where it exists and pays: on the TPU (off it the
    kernel is the interpreter, a test device); for bfloat16 operands
    (float32 is the parity tests'); for a head size that fills the
    MXU's lanes or half of them (128 and 64: both measured, PERF.md §6,
    PR 33); where a tile divides the shapes; and for a cache of
    more than one key block (one block is the plain softmax of a
    single-chunk document: nothing to keep between blocks)."""
    tiles = _kernel_tiles(T, S, rep)
    return (backend == "tpu" and jnp.dtype(mxu_dtype) == jnp.bfloat16
            and head_dim % 64 == 0 and tiles is not None
            and S > tiles[1])


def gqa_cached(
    q: jnp.ndarray,        # (b, T, Hq, d)
    k: jnp.ndarray,        # (b, T, Hkv, d)
    v: jnp.ndarray,        # (b, T, Hkv, d)
    k_cache: jnp.ndarray,  # (b, Hkv, S, d)
    v_cache: jnp.ndarray,  # (b, Hkv, S, d)
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
    query at position ``t`` sees the keys ``t - window < j <= t``.
    ``q_block`` and ``key_block`` are the XLA core's; the kernel's tiles
    follow the shapes."""
    b, T, Hq, d = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
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
        k_cache, k.swapaxes(1, 2).astype(k_cache.dtype), at, axis=2)
    v_cache = lax.dynamic_update_slice_in_dim(
        v_cache, v.swapaxes(1, 2).astype(v_cache.dtype), at, axis=2)
    if core_is_kernel(jax.default_backend(), mxu_dtype, T, S, rep, d):
        out = _kernel_core(
            q, k_cache, v_cache, pos, scale, window, mxu_dtype,
            _kernel_tiles(T, S, rep))
    else:
        out = _xla_core(q, k_cache, v_cache, pos, scale, window, mxu_dtype,
                        q_block, key_block)
    return out, k_cache, v_cache


def _xla_core(q, k_cache, v_cache, pos, scale, window, mxu_dtype, q_block,
              key_block):
    """``out (b, T, Hq, d)`` float32 of the chunk's queries against the
    cache the chunk is already written into: blocks of ``q_block``
    queries under ``lax.map``, each walking the key blocks it can see
    under ``lax.fori_loop``."""
    b, T, Hq, d = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
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
        s = jnp.einsum("bgrtd,bgsd->bgrts", q_blk, keys.astype(mxu_dtype),
                       preferred_element_type=jnp.float32) * scale
        return jnp.where(mask, s, _MASKED)

    def values(p, vals):
        return jnp.einsum("bgrts,bgsd->bgrtd", p.astype(mxu_dtype),
                          vals.astype(mxu_dtype),
                          preferred_element_type=jnp.float32)

    kb = key_block if S > key_block and S % key_block == 0 else S
    n_kb = S // kb

    def block(q_blk, first):
        # q_blk (b, Hkv, rep, t, d); first: chunk index of its first query
        t = q_blk.shape[3]
        q_blk = q_blk.astype(mxu_dtype)
        if n_kb == 1:
            p = jax.nn.softmax(
                scores(q_blk, k_cache, seen(first, t, 0, S)), axis=-1)
            return values(p, v_cache)

        def key_blk(j, carry):
            m, l, acc = carry
            keys = lax.dynamic_slice_in_dim(k_cache, j * kb, kb, axis=2)
            vals = lax.dynamic_slice_in_dim(v_cache, j * kb, kb, axis=2)
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
        return acc / l[..., None]

    # (b, Hkv, rep, T, d): a key/value head's query heads beside it
    qg = q.reshape(b, T, Hkv, rep, d).transpose(0, 2, 3, 1, 4)
    if T <= q_block or T % q_block:
        out = block(qg, 0)
    else:
        n = T // q_block
        blocks = jnp.moveaxis(qg.reshape(b, Hkv, rep, n, q_block, d), 3, 0)
        out = lax.map(lambda a: block(a[0], a[1]),
                      (blocks, jnp.arange(n) * q_block))
        out = jnp.moveaxis(out, 0, 3).reshape(b, Hkv, rep, T, d)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, T, Hq, d)


def _kernel_core(q, k_cache, v_cache, pos, scale, window, mxu_dtype, tiles):
    """``_xla_core``'s result from one ``pallas_call``: the grid is
    (rows, key/value heads, query blocks, key blocks), the key blocks
    innermost and in order; a step multiplies the ``rep * q_block`` rows
    of queries that share a key/value head into one ``key_block`` of its
    slots. Off the TPU the kernel is interpreted."""
    b, T, Hq, d = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
    qb, kb = tiles
    n_q, n_kb, rows = T // qb, S // kb, rep * qb
    if T % qb or S % kb or qb & (qb - 1):
        raise ValueError(f"tiles {tiles} do not divide T={T}, S={S}")

    def live_blocks(pos, i):
        """Key blocks that hold a position query block ``i`` can see."""
        return jnp.clip(lax.div(pos + (i + 1) * qb + kb - 1, kb), 1, n_kb)

    def kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        i, j = pl.program_id(2), pl.program_id(3)
        pos = pos_ref[0]
        low = pos + i * qb            # the block's first query
        last = pos + T - 1            # the newest position cached
        at = lax.rem(last, S)         # its slot: the ring's write pointer
        lap = last - at               # S * floor(last / S)
        slot0 = j * kb
        end = slot0 + kb - 1

        def held(slot):
            """The position a slot holds: the latest congruent to it."""
            return slot + jnp.where(slot <= at, lap, lap - S)

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def step(masked: bool):
            s = lax.dot_general(
                q_ref[...], k_ref[...].astype(mxu_dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                key = held(slot0 + lax.broadcasted_iota(
                    jnp.int32, (1, kb), 1))
                key = jnp.where(key >= 0, key, _NEVER)
                query = low + (lax.broadcasted_iota(
                    jnp.int32, (rows, 1), 0) & (qb - 1))
                ok = key <= query
                if window is not None:
                    ok = ok & (query - key < window)
                s = jnp.where(ok, s, _MASKED)
            m = m_ref[...]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            fade = jnp.exp(m - m_new)
            l_ref[...] = l_ref[...] * fade + p.sum(axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * fade + lax.dot_general(
                p.astype(mxu_dtype), v_ref[...].astype(mxu_dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        # a block whose slots hold consecutive positions, all written,
        # none after the block's first query nor a window before its
        # last: every query sees every key, and the mask is not made
        whole = ((slot0 <= at) == (end <= at)) & (held(slot0) >= 0) \
            & (held(end) <= low)
        if window is not None:
            whole = whole & (low + qb - 1 - held(slot0) < window)
        run = j < live_blocks(pos, i)
        pl.when(run & whole)(lambda: step(False))
        pl.when(run & jnp.logical_not(whole))(lambda: step(True))

        @pl.when(j == n_kb - 1)
        def _():
            o_ref[...] = acc_ref[...] / l_ref[...]

    def q_map(r, g, i, j, pos_ref):
        return (r, g, i, 0, 0)

    def kv_map(r, g, i, j, pos_ref):
        # past the last block these queries see: the same block again,
        # which is not fetched again
        return (r, g, jnp.minimum(j, live_blocks(pos_ref[0], i) - 1), 0)

    # (b, Hkv, n_q, rep * qb, d): the rows of one tile together
    tiled = q.astype(mxu_dtype).reshape(b, n_q, qb, Hkv, rep, d).transpose(
        0, 3, 1, 4, 2, 5).reshape(b, Hkv, n_q, rows, d)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, Hkv, n_q, rows, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, Hkv, n_q, n_kb),
            in_specs=[
                pl.BlockSpec((None, None, None, rows, d), q_map),
                pl.BlockSpec((None, None, kb, d), kv_map),
                pl.BlockSpec((None, None, kb, d), kv_map),
            ],
            out_specs=pl.BlockSpec((None, None, None, rows, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, d), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_LIMIT),
        interpret=jax.default_backend() != "tpu",
        name="gqa_cached_core",
    )(jnp.asarray(pos, jnp.int32).reshape(1), tiled, k_cache, v_cache)
    return out.reshape(b, Hkv, n_q, rep, qb, d).transpose(
        0, 2, 4, 1, 3, 5).reshape(b, T, Hq, d)
