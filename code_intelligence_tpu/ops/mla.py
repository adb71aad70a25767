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
first ``pos + T``: the core runs over the shortest prefix of whole key
blocks that holds them (``lax.switch`` over the static prefixes), so the
first chunk of a 2048-position cache pays for 512 keys, not 2048.
Scores and softmax are float32; products take ``mxu_dtype`` inputs.
Heads go ``head_block`` at a time and queries ``q_block`` at a time, so
the float32 scores of a (16, 512) chunk's 128 heads against 2048
positions (8.6 GB) never exist at once: sixteen heads of 128 queries are
268 MB, as are their expanded keys and values. (Blocking over rows
instead, two at a time, which needs no copy of the queries in another
order, was 17 % slower in the core on the chip: PERF.md §6, PR 30.)

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

def _blocks(n: int, block: int) -> int:
    """Size of the blocks ``n`` is cut into: ``block`` where it divides
    ``n`` into more than one, else ``n`` whole."""
    return block if n > block and n % block == 0 else n


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
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(out (b, T, H, v) float32, cache)`` with the chunk's latent rows
    appended at ``pos``. The block sizes are parameters so that the CPU
    tests can run every blocked path at a tiny size; the engine takes
    the defaults."""
    b, T, H, nope = q_nope.shape
    rope = q_pe.shape[-1]
    S, rank = cache.shape[1], cache.shape[2] - rope
    cache = lax.dynamic_update_slice_in_dim(
        cache, latent.astype(cache.dtype), pos, axis=1)

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
        return attend(S), cache
    live = (pos + T + kb - 1) // kb  # key blocks that hold pos + T positions
    out = lax.switch(jnp.clip(live, 1, len(prefixes)) - 1,
                     [lambda keys=keys: attend(keys) for keys in prefixes])
    return out, cache
