"""Multi-head latent attention (MLA, DeepSeek-V2/V3) over a latent cache.

What is cached a token a layer is not 128 heads of keys and values but
the ``kv_lora_rank`` numbers they are made from, ``c_kv`` after its
norm, and ONE rotated rotary key ``k_pe`` that all heads share: 512 + 64
numbers against 128 x (192 + 128). A head's key is ``[k_nope | k_pe]``
with ``[k_nope | v] = c_kv W_kvb``; its query is ``[q_nope | q_pe]``;
rotary goes on the ``pe`` parts only.

One chunk of ``T`` queries attends to everything cached before it and to
itself: the chunk's ``[c_kv | k_pe]`` rows are written into the cache at
``pos`` and the queries read the cache under ``key position <= pos +
query index``. The core runs the **expanded** form: ``k_nope`` and ``v``
of the cached positions are re-derived from ``c_kv`` by ``W_kvb`` (``2 *
rank * heads * (nope + v)`` operations a cached position a chunk, 33.5
MFLOP at the published sizes), then ``nope + rope + v`` multiply-adds a
query-key pair a head (320). The other algebraic form of the same scores
and values, **absorbed** (``W_kvb``'s key half folded into the query and
its value half into the output: ``2 * rank + rope`` = 1088 multiply-adds
a pair a head, nothing expanded), is cheaper only for a few queries
against a long cache, a decode step: for the shapes this engine runs (T
= S in 32..512, or 512 queries against 2048 positions: 155 against 309
GFLOP a row a layer) expanded costs less in every one, and nothing here
yields tokens one at a time, so the absorbed form is not in the program
(``tests/test_deepseek_v3.py`` derives it and holds the core equal to
it).

The cache is allocated at ``S`` positions, but a chunk meets only its
first ``pos + T``: the work follows the positions reached, a block of
``key_block`` at a time, so the first chunk of a 2048-position cache
pays for 512 keys, not 2048. Scores and softmax are float32; the three
products (expansion, scores, values) take ``mxu_dtype`` inputs and
accumulate in float32, and the expanded keys and values are rounded to
``mxu_dtype`` before they meet queries and probabilities.

**Two cores, one arithmetic, chosen here** (``core_is_kernel``, from
what the program can observe: backend, operand type, shapes; no caller
selects one):

* the Pallas kernel (``_kernel_core``) on the TPU for bfloat16 operands
  at head sizes that fill the lanes, for a chunk of 256 or 512 queries:
  the multi-chunk groups' programs and the single-chunk groups of
  buckets 256 and 512. A grid over rows, groups of heads, query blocks
  and, innermost, key blocks. A step takes one ``key_block`` of a row's
  latent cache into VMEM and, a head at a time, **expands it there**
  (``k_nope = c W_k``, ``v^T = W_v^T c^T``: the third product of a
  step), multiplies ``[k_nope | k_pe]`` into the head's queries and
  folds the tile into the head's running maximum, sum and weighted
  values. The score tile, its exponentials, the expanded keys and
  values and the three running statistics never leave VMEM; only the
  normalised output returns to HBM. The expansion is in the kernel
  because expanded once a layer in XLA the keys and values of a (16,
  512) program at 1024 positions are 1.07 GB written and read again
  (and 268 MB of the chip's memory), against a product the MXU has room
  for beside the softmax's vector work; with one query block a chunk
  (``q_block = T``) it is done once a row, head and key block, as the
  count of ``flops_moe.core_flops`` has it. ``pos`` comes in by scalar
  prefetch, the causal mask is made in the kernel from iotas and
  skipped for a block every query sees whole, and a key block past the
  last one a query block can see is neither fetched (its index is
  clamped in the ``index_map``) nor computed, so there is one body a
  shape and no ``lax.switch``. **A tile lies keys down, queries
  across**: the softmax's reductions over the keys are then elementwise
  over vector registers and a query's statistics lanes of one row
  (PERF.md §6, PR 35); the queries are read transposed, ``(rows, heads,
  [nope | rope | 0], T)``, which is the layout XLA gives the query
  projection's output anyway, and the output is written ``(rows, T,
  heads x v)``, as the output projection reads it: no relayout of
  either around the kernel. Tiles are a function of the shapes
  (``_kernel_tiles``, from one sweep on the chip);
* the XLA core (``_xla_core``) everywhere else: the CPU, float32
  operands (the parity tests), chunks of 128 queries and fewer (a step
  of so few queries leaves the MXU loading weights: the XLA core is
  ahead there by measurement), shapes no tile divides. Heads go
  ``head_block`` at a time and queries ``q_block`` at a time under
  ``lax.map``, over the shortest static prefix of whole key blocks that
  holds the positions reached (``lax.switch``), so the float32 scores
  of a (16, 512) chunk's 128 heads against 2048 positions (8.6 GB)
  never exist at once: sixteen heads of 128 queries are 268 MB, as are
  their expanded keys and values; each such tile goes through HBM
  between the fusions of the softmax. (Blocking over rows instead, two
  at a time, which needs no copy of the queries in another order, was
  17 % slower in the core on the chip: PERF.md §6, PR 30.)

**A third core, for a caller that SELECTS what a query attends**
(``mla_cached(..., admit=...)`` / ``mla_core``; ``ops/dsa.py`` is the
caller): ``admit`` ``(b, T, S)`` says, a query and a cached position,
whether the pair is attended, in place of "every position up to the
query's own". Two queries of one chunk then admit different keys of one
block, which neither core above can take: both skip a key block for the
whole chunk or for no query and make their mask from iotas. ``mla_core``
is the masked, expanded form in XLA: one ``lax.fori_loop`` over the key
blocks that hold the positions reached, all heads a step, a block's
latent rows expanded, scored, masked and folded into a running maximum,
sum and weighted values (the kernel's arithmetic). ``admit=None`` lowers
to the program ``mla_cached`` always was. **Heads of 192 + 64 | 256 did
not join the kernel's rule** (``nope % 128 == 0`` fails at 192, though
``[192 | 64]`` is exactly two lane tiles): the kernel has no masked form
yet, the one model with such heads always hands in ``admit``, and no
measurement on the chip stands behind a tile at that head size (PERF.md
§6, PR 53); a masked kernel would take ``W_k`` padded a head to 256
columns and add the shared ``k_pe`` into lanes 192..255, no concatenate.

Rotary follows the published implementation: YaRN's blended inverse
frequencies (``yarn_inv_freq``), pairs ``(x[2i], x[2i+1])`` rotated by
angle ``position * f_i`` and written de-interleaved (all first elements,
then all second): queries and keys are permuted alike, so scores do not
depend on it.
"""

from __future__ import annotations

import math
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# -- rotary ------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float,
                  scaling: Optional[Mapping] = None) -> np.ndarray:
    """``dim // 2`` inverse frequencies. Plain rotary is ``theta **
    (-2i / dim)``; YaRN (``scaling`` = the published ``rope_scaling``)
    keeps it for the dimensions that turn more than ``beta_fast`` times
    within the original context, divides it by ``factor`` for those that
    turn fewer than ``beta_slow`` times, and blends linearly between."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return f
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return f / scaling["factor"] * ramp + f * (1.0 - ramp)


def rope_factor(scaling: Optional[Mapping]) -> float:
    """What multiplies cos and sin: ``mscale / mscale_all_dim`` of the
    two temperatures (1 when they are equal, as DeepSeek-V3 has them)."""
    if not scaling:
        return 1.0
    return yarn_mscale(scaling["factor"], scaling.get("mscale", 1)) \
        / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0))


def softmax_scale(q_head_dim: int, scaling: Optional[Mapping]) -> float:
    """``q_head_dim ** -0.5``, times YaRN's temperature squared."""
    scale = q_head_dim ** -0.5
    if scaling and scaling.get("mscale_all_dim", 0):
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freq,
               factor: float = 1.0, interleaved: bool = True) -> jnp.ndarray:
    """``x`` ``(b, T, ..., d)`` rotated at ``positions`` ``(T,)``;
    float32 out. ``interleaved``: the pair turned by frequency ``i`` is
    ``(x[2i], x[2i+1])``, written de-interleaved (the module's
    docstring); otherwise it is ``(x[i], x[i + d/2])``, the
    ``rotate_half`` of the models that publish plain rotary."""
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (ang.shape[-1],)
    cos = (jnp.cos(ang) * factor).reshape(shape)
    sin = (jnp.sin(ang) * factor).reshape(shape)
    xf = x.astype(jnp.float32)
    if interleaved:
        a, b = xf[..., 0::2], xf[..., 1::2]
    else:
        a, b = jnp.split(xf, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# -- the core over the latent cache -----------------------------------------

# what a masked score is set to in the kernel: finite, so that a running
# maximum stays finite whatever a block holds (exp(-inf - -inf) is NaN);
# every query sees at least itself, and what a block it sees nothing of
# adds is multiplied by exp(_MASKED - score) = 0 at its first visible key
_MASKED = -1e30

# the kernel's score tile, its exponentials and their bfloat16 copy are a
# few MB a head at the tiles below; Mosaic's default scoped limit is 16
# MiB of the v5e's 128
_KERNEL_VMEM_LIMIT = 96 * 1024 * 1024

# the kernel's tiles. One sweep on the chip (v5e, standalone, 128 heads of
# 128 + 64 | 128, rank 512, bfloat16; ms a layer for the whole call, the
# XLA core 17.6 / 39.4 beside it; PERF.md §6, PR 35, has the table).
# ``q_block`` is the whole chunk: at 16 rows x 512 queries against 512 /
# 1024 positions reached, key blocks of 512, 4 heads a step, 13.05 /
# 17.78 whole and 15.28 / 22.09 halved (the expansion is repeated a query
# block). ``key_block`` is 512, the chunks' length (256: 13.73 / 18.23;
# 1024: 16.87 / 16.85, a first chunk then expands 512 positions nobody
# has written; 2048: 23.4), or a single-chunk cache whole (512 queries
# against 512 positions: 11.27 whole, 11.63 halved). 8 heads a step (2 /
# 4 / 8 at key blocks of 512: 13.66 / 13.05 / 12.75 and 18.87 / 17.78 /
# 17.26; 16: the kernel alone 4.74 -> 7.80). Chunks of 256 and 512
# queries only: in the cell the ``mla_core`` scope took 0.920 s of four
# calls with every bucket from 64 up on the kernel, 0.871 from 256 up,
# 0.927 with 512 alone (with 128 queries and fewer the kernel alone is
# level with the XLA core and what is around it is not)
_TILE_QUERIES = (256, 512)
_TILE_KEYS = 512
_TILE_HEADS = 8


def _kernel_tiles(T: int, S: int, H: int) -> Optional[Tuple[int, int, int]]:
    """``(q_block, key_block, heads)`` of the kernel for a chunk of ``T``
    queries against ``S`` cached positions, ``H`` heads; ``None`` where
    the sweep above has no tile for the shape. A function of the shapes
    alone: the chunk whole, the cache in blocks of ``_TILE_KEYS`` or
    whole where it is shorter, the most heads up to ``_TILE_HEADS``
    that divide ``H``."""
    key_block = min(S, _TILE_KEYS)
    if T not in _TILE_QUERIES or S % key_block or key_block % 16:
        return None
    heads = next(n for n in range(min(H, _TILE_HEADS), 0, -1) if H % n == 0)
    return T, key_block, heads


def core_is_kernel(backend: str, mxu_dtype, T: int, S: int, H: int,
                   nope: int, v_dim: int, rank: int) -> bool:
    """Pallas kernel or XLA core, for ONE call of ``mla_cached``: the
    rule, from what the program can observe and nothing a user sets.

    The kernel runs where it exists and pays: on the TPU (off it the
    kernel is the interpreter, a test device); for bfloat16 operands
    (float32 is the parity tests'); for head sizes and a rank that fill
    the lanes' 128 (the published 128 | 128 | 512; the rotary part is
    padded); and where ``_kernel_tiles`` has a tile, which is where it
    was measured ahead: chunks of 256 and 512 queries, single-chunk
    groups among them."""
    return (backend == "tpu" and jnp.dtype(mxu_dtype) == jnp.bfloat16
            and nope % 128 == 0 and v_dim % 128 == 0 and rank % 128 == 0
            and _kernel_tiles(T, S, H) is not None)


def mla_cached(
    q_nope: jnp.ndarray,   # (b, T, H, nope)
    q_pe: jnp.ndarray,     # (b, T, H, rope), rotated
    latent: jnp.ndarray,   # (b, T, rank + rope): [c_kv | k_pe], normed, rotated
    cache: jnp.ndarray,    # (b, S, rank + rope)
    pos: jnp.ndarray,      # () int32: positions already cached
    w_kvb: jnp.ndarray,    # (rank, H * (nope + v))
    scale: float,
    v_dim: int,
    head_block: int = 16,
    q_block: int = 128,
    key_block: int = 512,
    mxu_dtype=jnp.bfloat16,
    admit: Optional[jnp.ndarray] = None,  # (b, T, S) bool
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(out (b, T, H, v) float32, cache)`` with the chunk's latent rows
    appended at ``pos``. The block sizes are the XLA core's, parameters
    so that the CPU tests can run every blocked path at a tiny size (the
    engine takes the defaults); the kernel's tiles follow the shapes.
    ``admit``, where a caller has one (``ops/dsa.py``), is the set of
    cached positions each query attends, in place of "every position up
    to its own": the core is then ``mla_core``. ``None`` lowers to the
    program this function always was."""
    b, T, H, nope = q_nope.shape
    S, rank = cache.shape[1], cache.shape[2] - q_pe.shape[-1]
    cache = lax.dynamic_update_slice_in_dim(
        cache, latent.astype(cache.dtype), pos, axis=1)
    if admit is not None:
        return mla_core(q_nope, q_pe, cache, pos, w_kvb, scale, v_dim, admit,
                        key_block, mxu_dtype), cache
    if core_is_kernel(jax.default_backend(), mxu_dtype, T, S, H, nope, v_dim,
                      rank):
        out = _kernel_core(q_nope, q_pe, cache, pos, w_kvb, scale, v_dim,
                           mxu_dtype, _kernel_tiles(T, S, H))
    else:
        out = _xla_core(q_nope, q_pe, cache, pos, w_kvb, scale, v_dim,
                        mxu_dtype, head_block, q_block, key_block)
    return out, cache


def mla_core(q_nope, q_pe, cache, pos, w_kvb, scale, v_dim, admit,
             key_block: int = 512, mxu_dtype=jnp.bfloat16) -> jnp.ndarray:
    """``out (b, T, H, v)`` float32 of the chunk's queries against a
    ``cache`` the chunk is already written into, each query's softmax
    over the cached positions ``admit`` ``(b, T, S)`` holds true for it
    (at least one, none after its own): the MASKED, EXPANDED form, in
    XLA. Two queries of one chunk may admit different keys of one block,
    so neither core above can take it: both skip a key block "for the
    whole chunk or for no query" and make their mask from iotas.

    One loop over the key blocks that hold the ``pos + T`` positions
    reached, its trip count the program's own (``lax.fori_loop``: one
    body a layer to compile, where a ``lax.switch`` over static prefixes
    is a body a prefix, 64 of them at 32,768 positions), all heads a
    step: a block's latent rows are expanded, scored, masked and folded
    into every query's running maximum, sum and weighted values, the
    kernel's arithmetic (the probabilities meet the values unnormalised
    in ``mxu_dtype``; the sum divides once, at the end). A block a query
    admits nothing of adds what its first admitted key then multiplies
    by ``exp(_MASKED - score) = 0``."""
    b, T, H, nope = q_nope.shape
    rope = q_pe.shape[-1]
    S, rank = cache.shape[1], cache.shape[2] - rope
    kb = _blocks(S, key_block)
    w = w_kvb.reshape(rank, H, nope + v_dim)
    w_k, w_v = w[..., :nope].astype(mxu_dtype), w[..., nope:].astype(mxu_dtype)
    qn, qp = q_nope.astype(mxu_dtype), q_pe.astype(mxu_dtype)

    def step(j, carry):
        m, l, acc = carry
        rows = lax.dynamic_slice_in_dim(cache, j * kb, kb, axis=1)
        c = rows[..., :rank].astype(mxu_dtype)
        k_nope = jnp.einsum("bsc,chd->bshd", c, w_k,
                            preferred_element_type=mxu_dtype)
        v = jnp.einsum("bsc,chd->bshd", c, w_v,
                       preferred_element_type=mxu_dtype)
        s = (jnp.einsum("bthd,bshd->bhts", qn, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bthr,bsr->bhts", qp,
                          rows[..., rank:].astype(mxu_dtype),
                          preferred_element_type=jnp.float32)) * scale
        seen = lax.dynamic_slice_in_dim(admit, j * kb, kb, axis=2)
        s = jnp.where(seen[:, None], s, _MASKED)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        fade = jnp.exp(m - m_new)
        l = l * fade + p.sum(axis=-1)
        acc = acc * fade[..., None] + jnp.einsum(
            "bhts,bshd->bhtd", p.astype(mxu_dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = lax.fori_loop(0, reached_blocks(pos, T, S, kb), step, (
        jnp.full((b, H, T), _MASKED, jnp.float32),
        jnp.zeros((b, H, T), jnp.float32),
        jnp.zeros((b, H, T, v_dim), jnp.float32)))
    return (acc / l[..., None]).swapaxes(1, 2)


def reached_blocks(pos, T: int, S: int, kb: int):
    """Key blocks of ``kb`` that hold the ``pos + T`` positions a chunk
    reaches, of a cache of ``S``: the trip count of a loop over them."""
    return jnp.clip((pos + T + kb - 1) // kb, 1, S // kb)


def _blocks(n: int, block: int) -> int:
    """Size of the blocks ``n`` is cut into: ``block`` where it divides
    ``n`` into more than one, else ``n`` whole."""
    return block if n > block and n % block == 0 else n


def _xla_core(q_nope, q_pe, cache, pos, w_kvb, scale, v_dim, mxu_dtype,
              head_block, q_block, key_block):
    """``out (b, T, H, v)`` float32 of the chunk's queries against the
    cache the chunk is already written into: ``lax.map`` over groups of
    ``head_block`` heads and blocks of ``q_block`` queries, over the
    shortest static prefix of ``key_block`` positions that holds what
    the chunk reaches (``lax.switch``)."""
    b, T, H, nope = q_nope.shape
    rope = q_pe.shape[-1]
    S, rank = cache.shape[1], cache.shape[2] - rope

    hb = _blocks(H, head_block)
    qb = _blocks(T, q_block)
    kb = _blocks(S, key_block)
    G, nq = H // hb, T // qb
    w = w_kvb.reshape(rank, G, hb, nope + v_dim).swapaxes(0, 1)
    w_k, w_v = w[..., :nope].astype(mxu_dtype), w[..., nope:].astype(mxu_dtype)

    def grouped(x):  # (b, T, H, d) -> (G, nq, b, qb, hb, d)
        return x.astype(mxu_dtype).reshape(
            b, nq, qb, G, hb, x.shape[-1]).transpose(3, 1, 0, 2, 4, 5)

    qn, qp = grouped(q_nope), grouped(q_pe)
    firsts = jnp.arange(nq) * qb

    def attend(keys: int):
        """The core over the first ``keys`` cached positions (static)."""
        c = cache[:, :keys, :rank].astype(mxu_dtype)
        k_pe = cache[:, :keys, rank:].astype(mxu_dtype)
        key_pos = jnp.arange(keys)

        def probs(s_nope, q_pe_blk, first):
            s = (s_nope + jnp.einsum(
                "bthr,bsr->bhts", q_pe_blk, k_pe,
                preferred_element_type=jnp.float32)) * scale
            seen = key_pos[None, :] <= (
                pos + first + jnp.arange(s.shape[2]))[:, None]
            # every query sees at least itself, so no row is all -inf
            return jax.nn.softmax(
                jnp.where(seen, s, -jnp.inf), axis=-1).astype(mxu_dtype)

        def head_group(xs):
            qn_g, qp_g, wk_g, wv_g = xs
            k_nope = jnp.einsum("bsc,chd->bshd", c, wk_g,
                                preferred_element_type=mxu_dtype)
            v = jnp.einsum("bsc,chd->bshd", c, wv_g,
                           preferred_element_type=mxu_dtype)

            def q_blk(ys):
                qn_b, qp_b, first = ys
                p = probs(jnp.einsum(
                    "bthd,bshd->bhts", qn_b, k_nope,
                    preferred_element_type=jnp.float32), qp_b, first)
                return jnp.einsum("bhts,bshd->bthd", p, v,
                                  preferred_element_type=jnp.float32)

            return lax.map(q_blk, (qn_g, qp_g, firsts))  # (nq, b, qb, hb, v)

        out = lax.map(head_group, (qn, qp, w_k, w_v))   # (G, nq, b, qb, hb, v)
        return out.transpose(2, 1, 3, 0, 4, 5).reshape(b, T, H, v_dim)

    prefixes = list(range(kb, S + 1, kb))
    if len(prefixes) == 1:
        return attend(S)
    live = (pos + T + kb - 1) // kb  # key blocks that hold pos + T positions
    return lax.switch(jnp.clip(live, 1, len(prefixes)) - 1,
                      [lambda keys=keys: attend(keys) for keys in prefixes])


def _kernel_core(q_nope, q_pe, cache, pos, w_kvb, scale, v_dim, mxu_dtype,
                 tiles):
    """``_xla_core``'s result from one ``pallas_call``: the grid is
    (rows, groups of ``heads``, query blocks, key blocks), the key
    blocks innermost and in order. A step takes one ``key_block`` of a
    row's latent cache and, a head at a time, expands it by the head's
    slices of ``W_kvb`` (rounded to ``mxu_dtype``, as the XLA core's
    ``preferred_element_type`` rounds it), multiplies ``[k_nope | k_pe]``
    into the head's ``q_block`` queries in one product and folds scores
    and values into the head's running maximum, sum and accumulator.

    **A tile's scores lie keys down, queries across** (``(key_block,
    q_block)``): the softmax's maximum and sum over the keys are then
    elementwise over vector registers, and a query's maximum, sum and
    fade are lanes of one row, not a column of one lane each (with the
    queries down, the two reductions along the lanes and the column
    arithmetic cost ~10 ns a query a head a step whatever the keys: all
    of the time, PERF.md §6, PR 35). So values are expanded transposed,
    ``v^T = W_v^T c^T``, the accumulator is ``(v, q_block)`` and is
    turned once, when the last key block is done. The queries come
    ``(rows, heads, [nope | rope | 0], T)``, a group of heads a block of
    them; the output goes ``(rows, T, heads x v)``, a group of heads a
    block of columns. Off the TPU the kernel is interpreted."""
    b, T, H, nope = q_nope.shape
    rope = q_pe.shape[-1]
    S, rank = cache.shape[1], cache.shape[2] - rope
    qb, kb, hs = tiles
    n_q, n_kb = T // qb, S // kb
    if T % qb or S % kb or H % hs:
        raise ValueError(f"tiles {tiles} do not divide T={T}, S={S}, H={H}")
    # a head's key is [k_nope | k_pe | 0] against the query [q_nope | q_pe
    # | 0]: one product, the rotary part padded up to the lanes' 128
    pad = -(nope + rope) % 128
    qd = nope + rope + pad
    nt = (((1,), (1,)), ((), ()))  # a @ b.T

    def live_blocks(pos, i):
        """Key blocks that hold a position query block ``i`` can see."""
        return jnp.clip(lax.div(pos + (i + 1) * qb + kb - 1, kb), 1, n_kb)

    def kernel(pos_ref, q_ref, c_ref, pe_ref, wk_ref, wv_ref, o_ref, m_ref,
               l_ref, acc_ref):
        i, j = pl.program_id(2), pl.program_id(3)
        pos = pos_ref[0]
        low = pos + i * qb            # the block's first query
        key0 = j * kb

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def step(masked: bool):
            c, pe = c_ref[...].astype(mxu_dtype), pe_ref[...]
            if masked:
                seen = key0 + lax.broadcasted_iota(jnp.int32, (kb, 1), 0) \
                    <= low + lax.broadcasted_iota(jnp.int32, (1, qb), 1)
            for h in range(hs):
                k_nope = jnp.dot(c, wk_ref[:, h * nope:(h + 1) * nope],
                                 preferred_element_type=jnp.float32)
                v_t = lax.dot_general(
                    wv_ref[h * v_dim:(h + 1) * v_dim, :], c, nt,
                    preferred_element_type=jnp.float32).astype(mxu_dtype)
                keys = jnp.concatenate(
                    [k_nope.astype(mxu_dtype), pe], axis=1)
                s = jnp.dot(keys, q_ref[h],
                            preferred_element_type=jnp.float32) * scale
                if masked:
                    s = jnp.where(seen, s, _MASKED)
                m = m_ref[h]
                m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))
                p = jnp.exp(s - m_new)
                fade = jnp.exp(m - m_new)
                l_ref[h] = l_ref[h] * fade + p.sum(axis=0, keepdims=True)
                acc_ref[h] = acc_ref[h] * fade + jnp.dot(
                    v_t, p.astype(mxu_dtype),
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new

        # a block that ends at or before the block's first query: every
        # query sees every key, and the mask is not made
        whole = key0 + kb - 1 <= low
        run = j < live_blocks(pos, i)
        pl.when(run & whole)(lambda: step(False))
        pl.when(run & jnp.logical_not(whole))(lambda: step(True))

        @pl.when(j == n_kb - 1)
        def _():
            for h in range(hs):
                o_ref[:, h * v_dim:(h + 1) * v_dim] = \
                    (acc_ref[h] / l_ref[h]).T

    def key_map(r, g, i, j, pos_ref):
        # past the last block these queries see: the same block again,
        # which is not fetched again; column block 0 is ``c_kv``
        return (r, jnp.minimum(j, live_blocks(pos_ref[0], i) - 1), 0)

    # (b, H, qd, T): a head's [q_nope | q_pe | 0], queries across
    q = jnp.concatenate(
        [q_nope.astype(mxu_dtype), q_pe.astype(mxu_dtype),
         jnp.zeros((b, T, H, pad), mxu_dtype)], axis=-1).transpose(0, 2, 3, 1)
    # (b, S, rope + pad): the shared rotary key, padded as the queries are
    pe = jnp.pad(cache[..., rank:].astype(mxu_dtype),
                 ((0, 0), (0, 0), (0, pad)))
    # W_kvb's key half as it lies, a head a block of columns; its value
    # half transposed, a head a block of rows
    w = w_kvb.astype(mxu_dtype).reshape(rank, H, nope + v_dim)
    w_k = w[..., :nope].reshape(rank, H * nope)
    w_v = w[..., nope:].reshape(rank, H * v_dim).T
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, T, H * v_dim), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, H // hs, n_q, n_kb),
            in_specs=[
                pl.BlockSpec((None, hs, qd, qb),
                             lambda r, g, i, j, pos_ref: (r, g, 0, i)),
                pl.BlockSpec((None, kb, rank), key_map),
                pl.BlockSpec((None, kb, rope + pad), key_map),
                pl.BlockSpec((rank, hs * nope),
                             lambda r, g, i, j, pos_ref: (0, g)),
                pl.BlockSpec((hs * v_dim, rank),
                             lambda r, g, i, j, pos_ref: (g, 0)),
            ],
            out_specs=pl.BlockSpec((None, qb, hs * v_dim),
                                   lambda r, g, i, j, pos_ref: (r, i, g)),
            scratch_shapes=[
                pltpu.VMEM((hs, 1, qb), jnp.float32),
                pltpu.VMEM((hs, 1, qb), jnp.float32),
                pltpu.VMEM((hs, v_dim, qb), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_LIMIT),
        interpret=jax.default_backend() != "tpu",
        name="mla_cached_core",
    )(jnp.asarray(pos, jnp.int32).reshape(1), q, cache, pe, w_k, w_v)
    return out.reshape(b, T, H, v_dim)
