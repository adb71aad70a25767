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

**Two cores, one arithmetic, chosen here** (``core_is_kernel``, from
what the program can observe; no caller selects one), as
``ops/attention.py`` chooses its own:

* the Pallas kernel (``_kernel_core``) on the TPU for bfloat16 operands,
  heads of 128 and a block cache of more than one chunk program: a grid
  over rows, groups of heads and, innermost, key blocks of BOTH kinds,
  the block cache's (a chunk program's worth of slots each) first and
  the summary cache's after, each cache under its own ``BlockSpec``. A
  head's scores (keys down, queries across: the softmax's reductions are
  elementwise over vector registers), running maximum, sum and weighted
  values stay in VMEM from the first key block to the last and only the
  normalised output returns to HBM. ``q``, the chunk's ``k`` and ``v``
  and the output are read and written in the projections' own layout
  ``(b, T, H * d)`` (a head is a 128-lane column group of a block), and
  the kernel writes the chunk into its block of the cache itself: XLA
  turns nothing head-major around the call. What is visible comes in by
  scalar prefetch from ``_reach`` and from nowhere else; the masks are
  made in the kernel, a block past what the chunk can see is neither
  fetched nor computed, and a block every query sees whole skips the
  mask;
* the XLA core (``_xla_core``) everywhere else: the CPU, float32
  operands (the parity tests), a document one program holds (one key
  block: plain softmax), a shape no tile divides. The chunk written by a
  ``dynamic_update_slice``, then the same blocks under two
  ``lax.fori_loop`` s, its float32 score tiles going through HBM between
  fusions.

Both count the keys their masks admitted (``met``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from code_intelligence_tpu.ops.attention import _KERNEL_VMEM_LIMIT, _MASKED

# the kernel's tiles: heads a grid step and summaries a key block (a key
# block of the block cache is a chunk program's worth of slots: the
# chunk's own block is the one the kernel writes). Two sweeps on the chip
# (v5e, 8 rows x 512 queries, 32 heads of 128, W 2048, S 2048; PERF.md
# §6, PR 52). The kernel alone, ms a layer weighted over the cell's
# positions (device time of the call in a capture), scores keys down:
# heads 4 / 8 / 16 at key blocks of 512 | 512: 1.708 / 1.631 / 1.593;
# summaries 512 / 1024 / 2048 a key block at 8 heads: 1.631 / 1.820 /
# 1.996 (a larger tile buys nothing once the column arithmetic is gone,
# and the last block's masked part grows); the block cache in key blocks
# of 256 / 1024 / 2048: 1.778 / 1.961 / 2.354. Scores queries down (the
# first build): 2.141 at 512 | 512, 1.785 at 1024 | 1024. In a two-layer
# stage at the cell's widths, ms a (8, 512) program 17,408 positions in,
# the XLA core 40.47: queries down 33.95 (512) / 32.98 (1024), keys down
# 32.40, keys down with the chunk written by the kernel 28.69 / 28.78 /
# 28.78 at summaries 512 / 1024 / 2048 a block, 28.70 at 16 heads, 28.94
# at 4: what XLA turned head-major round the call was worth more than
# any tile
_TILE_HEADS = 8
_TILE_SUMMARIES = 512


def _kernel_tiles(T: int, S: int, H: int) -> Optional[Tuple[int, int]]:
    """``(heads a step, summary_block)`` of the kernel for a chunk of
    ``T`` queries against ``S`` summaries, ``H`` heads; ``None`` where no
    aligned tile divides the shape. A function of the shapes alone: the
    most heads up to ``_TILE_HEADS`` that divide ``H``; the most
    summaries up to ``_TILE_SUMMARIES``, whole 128s of them (keys lie
    down a score tile, 16 bfloat16 rows a register) or the whole (small)
    cache."""
    most = min(_TILE_SUMMARIES, S)
    if most == S and S % 16 == 0:
        summaries = S
    else:
        summaries = next((n for n in range(most - most % 128, 0, -128)
                          if S % n == 0), None)
    if T % 128 or summaries is None:
        return None
    return (next(n for n in range(min(_TILE_HEADS, H), 0, -1) if H % n == 0),
            summaries)


def core_is_kernel(backend: str, mxu_dtype, T: int, W: int, S: int,
                   head_dim: int) -> bool:
    """Pallas kernel or XLA core, for ONE call of ``eva_cached``: the
    rule, from what the program can observe and nothing a user sets.

    The kernel runs where it exists and pays: on the TPU (off it the
    kernel is the interpreter, a test device); for bfloat16 operands
    (float32 is the parity tests'); for a head size that fills the
    lanes (a head is a 128-lane column group of a block of queries) and
    a chunk of whole 128s of queries (they lie across a score tile's
    lanes); where a tile divides the summaries; and for a block cache of
    more than one chunk program (one is a document one program holds:
    plain softmax, nothing to keep between key blocks and never a
    summary)."""
    return (backend == "tpu" and jnp.dtype(mxu_dtype) == jnp.bfloat16
            and head_dim % 128 == 0 and W > T
            and _kernel_tiles(T, S, 1) is not None)


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
    the masks as they were applied. ``key_block`` is the XLA core's; the
    kernel's tiles follow the shapes."""
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
    S = k_sum.shape[2]
    if core_is_kernel(jax.default_backend(), mxu_dtype, T, W, S, d):
        return _kernel_core(q, k, v, k_block, v_block, k_sum, v_sum, pos,
                            scale, window, chunk, mxu_dtype,
                            _kernel_tiles(T, S, H))
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


def _kernel_core(q, k, v, k_block, v_block, k_sum, v_sum, pos, scale,
                 window, chunk, mxu_dtype, tiles):
    """``eva_cached``'s result from one ``pallas_call``, the write of the
    chunk into the block cache included. What is visible comes from
    ``_reach`` and from nowhere else: its three values go in beside
    ``pos`` by scalar prefetch."""
    T, W = q.shape[1], k_block.shape[2]
    slots, stale, seen = _reach(pos, T, W, window, chunk)
    reach = jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                       for x in (pos, slots, stale, seen)])
    return _kernel_call(reach, q, k, v, k_block, v_block, k_sum, v_sum,
                        scale=scale, mxu_dtype=jnp.dtype(mxu_dtype),
                        tiles=tiles,
                        interpret=jax.default_backend() != "tpu")


# jitted and inlined: an encoder's layers call it with one signature, so a
# chunk program traces the kernel's body once and not once a layer (the
# trace of the cell's 8-layer program 0.9 -> 0.3 s on a CPU host, the XLA
# core's 0.4; set-up pays it 16 programs a run, warm or cold), and every
# call site keeps its own named scope
@functools.partial(jax.jit, inline=True, static_argnames=(
    "scale", "mxu_dtype", "tiles", "interpret"))
def _kernel_call(reach, q, k, v, k_block, v_block, k_sum, v_sum, *, scale,
                 mxu_dtype, tiles, interpret):
    """The grid is (rows, groups of heads, key blocks), the key blocks
    innermost and in the XLA core's order, the block cache's ``W / T``
    first (the chunk's own among them, read from what the step writes)
    and the summary cache's after. A step folds one block of keys of
    either kind into each of its heads' running maximum, sum and
    accumulator. ``reach`` is ``(pos, slots, stale, summaries)``.

    **A tile's scores lie keys down, queries across** (``(keys, T)``),
    as ``ops/mla.py``'s do and for its reason: the softmax's maximum and
    sum over the keys are then elementwise over vector registers, and a
    query's maximum, sum and fade are lanes of one row, not a column of
    one lane each (queries down, that column arithmetic was worth more
    than the tile's: PERF.md §6, PR 52). So the accumulator is ``(d, T)``
    and is turned once, when the last key block is done. Off the TPU the
    kernel is interpreted."""
    b, T, H, d = q.shape
    W, S = k_block.shape[2], k_sum.shape[2]
    G, sb = tiles
    if H % G or W % T or S % sb:
        raise ValueError(f"tiles {tiles} do not divide H={H}, W={W}, S={S}")
    n_own, n_sum = W // T, S // sb

    def own_live(reach_ref):
        """Blocks of the block cache that hold a slot the chunk sees."""
        return jnp.clip(lax.div(reach_ref[1] + T - 1, T), 1, n_own)

    def sum_live(reach_ref):
        """Blocks of the summary cache that hold a visible summary."""
        return jnp.clip(lax.div(reach_ref[3] + sb - 1, sb), 0, n_sum)

    def chunks_block(reach_ref):
        """The key block of the block cache the chunk itself is."""
        return lax.div(lax.rem(reach_ref[0], W), T)

    def kernel(reach_ref, q_ref, kn_ref, vn_ref, ko_ref, vo_ref, ks_ref,
               vs_ref, o_ref, met_ref, kc_ref, vc_ref, qh_ref, m_ref, l_ref,
               acc_ref):
        j = pl.program_id(2)
        first = lax.rem(reach_ref[0], W)   # the chunk's first query's slot
        stale = reach_ref[2] != 0
        seen = reach_ref[3]

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)
            met_ref[...] = jnp.zeros_like(met_ref)
            # a head's queries, and the chunk's keys and values as the
            # cache holds them (its block of the cache, written here),
            # as tiles of their own: the loop over heads indexes the
            # leading axis
            for h in range(G):
                qh_ref[h] = q_ref[:, h * d:(h + 1) * d]
                kc_ref[h] = kn_ref[:, h * d:(h + 1) * d]
                vc_ref[h] = vn_ref[:, h * d:(h + 1) * d]

        def absorb(keys_ref, vals_ref, ok):
            """One block of keys of either kind into each head's running
            softmax; ``ok`` the block's mask, ``None`` for a block every
            query sees whole."""
            def head(h, _):
                s = lax.dot_general(
                    keys_ref[h].astype(mxu_dtype), qh_ref[h],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if ok is not None:
                    s = jnp.where(ok, s, _MASKED)
                m = m_ref[h]
                m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))
                p = jnp.exp(s - m_new)
                fade = jnp.exp(m - m_new)
                l_ref[h] = l_ref[h] * fade + p.sum(axis=0, keepdims=True)
                acc_ref[h] = acc_ref[h] * fade + lax.dot_general(
                    vals_ref[h].astype(mxu_dtype), p.astype(mxu_dtype),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new

            lax.fori_loop(0, G, head, None)

        def admitted(ok):
            """The keys a mask admitted a query: its own sum."""
            return ok.astype(jnp.int32).sum(axis=0, keepdims=True)

        # the block cache, a chunk program's worth of slots a key block:
        # the blocks before the chunk's own every query sees whole (and
        # those after it where a stale slot is admitted); the chunk's own
        # is read from what this step writes, under the mask
        slot0 = j * T
        run = j < own_live(reach_ref)
        own = j == chunks_block(reach_ref)

        @pl.when(run & own)
        def _():
            at = slot0 + lax.broadcasted_iota(jnp.int32, (T, 1), 0)
            query = first + lax.broadcasted_iota(jnp.int32, (1, T), 1)
            ok = (at <= query) | stale
            absorb(kc_ref, vc_ref, ok)
            met_ref[0] = met_ref[0] + admitted(ok)

        whole = stale | (slot0 + T - 1 <= first)

        @pl.when(run & jnp.logical_not(own) & whole)
        def _():
            absorb(ko_ref, vo_ref, None)
            met_ref[0] = met_ref[0] + T

        # the summary cache's blocks: those of the blocks passed
        i = j - n_own
        sum0 = i * sb

        def passed_masked():
            at = sum0 + lax.broadcasted_iota(jnp.int32, (sb, 1), 0)
            ok = at < seen
            absorb(ks_ref, vs_ref, ok)
            met_ref[1] = met_ref[1] + admitted(ok)

        def passed_whole():
            absorb(ks_ref, vs_ref, None)
            met_ref[1] = met_ref[1] + sb

        run = (i >= 0) & (i < sum_live(reach_ref))
        whole = sum0 + sb <= seen
        pl.when(run & whole)(passed_whole)
        pl.when(run & jnp.logical_not(whole))(passed_masked)

        @pl.when(j == n_own + n_sum - 1)
        def _():
            for h in range(G):
                o_ref[:, h * d:(h + 1) * d] = (acc_ref[h] / l_ref[h]).T

    def q_map(r, g, j, reach_ref):
        return (r, 0, g)

    def own_map(r, g, j, reach_ref):
        # past the last block the chunk sees: the same block again, which
        # is not fetched again; never the chunk's own (stale in HBM)
        at = jnp.minimum(j, own_live(reach_ref) - 1)
        c = chunks_block(reach_ref)
        return (r, g, jnp.where(at == c, jnp.maximum(c - 1, 0), at), 0)

    def chunk_map(r, g, j, reach_ref):
        return (r, g, chunks_block(reach_ref), 0)

    def sum_map(r, g, j, reach_ref):
        return (r, g, jnp.clip(j - n_own, 0,
                               jnp.maximum(sum_live(reach_ref) - 1, 0)), 0)

    def flat(x, dtype):
        """The projections' own layout: a head a group of 128 columns."""
        return x.astype(dtype).reshape(b, T, H * d)

    out, met, k_block, v_block = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, T, H * d), jnp.float32),
                   jax.ShapeDtypeStruct((b, H // G, 2, 1, T), jnp.int32),
                   jax.ShapeDtypeStruct(k_block.shape, k_block.dtype),
                   jax.ShapeDtypeStruct(v_block.shape, v_block.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, H // G, n_own + n_sum),
            in_specs=[
                pl.BlockSpec((None, T, G * d), q_map),
                pl.BlockSpec((None, T, G * d), q_map),
                pl.BlockSpec((None, T, G * d), q_map),
                pl.BlockSpec((None, G, T, d), own_map),
                pl.BlockSpec((None, G, T, d), own_map),
                pl.BlockSpec((None, G, sb, d), sum_map),
                pl.BlockSpec((None, G, sb, d), sum_map),
            ],
            out_specs=(
                pl.BlockSpec((None, T, G * d), q_map),
                pl.BlockSpec((None, None, 2, 1, T),
                             lambda r, g, j, reach_ref: (r, g, 0, 0, 0)),
                pl.BlockSpec((None, G, T, d), chunk_map),
                pl.BlockSpec((None, G, T, d), chunk_map),
            ),
            scratch_shapes=[
                pltpu.VMEM((G, T, d), mxu_dtype),
                pltpu.VMEM((G, 1, T), jnp.float32),
                pltpu.VMEM((G, 1, T), jnp.float32),
                pltpu.VMEM((G, d, T), jnp.float32),
            ]),
        # the block caches are written where they lie
        input_output_aliases={4: 2, 5: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_LIMIT),
        interpret=interpret,
        name="eva_core",
    )(reach, flat(q, mxu_dtype), flat(k, k_block.dtype),
      flat(v, v_block.dtype), k_block, v_block, k_sum, v_sum)
    # every row and head admits the same keys: one of them is the count
    return (out.reshape(b, T, H, d), k_block, v_block,
            (met[0, 0, 0, 0], met[0, 0, 1, 0]))
