"""Learned sparse attention over a latent cache (DeepSeek Sparse Attention,
as ``glm_moe_dsa`` publishes it): every query attends only the
``index_topk`` cached positions a small INDEXER picks for it.

Beside the latent cache a layer keeps a second one, one index key
``kI[s]`` of ``index_head_dim`` numbers a position for all index heads.
A query at position ``t`` has ``index_n_heads`` index queries ``qI[t,j]``
and as many weights ``w[t,j]`` (any sign), and scores every position it
may see,

    I[t,s] = sum_j w[t,j] * relu(qI[t,j] . kI[s])        s <= t

(the heads are summed AFTER the ReLU: neither an attention score nor one
matmul); ``S_t`` is the positions of the ``min(index_topk, t + 1)``
largest ``I[t, 0..t]``, ties to the lower position (``lax.top_k``'s
rule), and the latent core's softmax runs over ``S_t`` alone. Three
steps, a function each, and ``sparse_attention`` the three in a row:

``index_scores``
    ``I`` for a chunk's queries against the index-key cache, ``-inf``
    where ``s > t``. The 32-head product takes ``mxu_dtype`` inputs; it
    is accumulated, ReLU'd, weighed and summed over the heads in float32,
    a key block at a time (all heads of a (8, 512) chunk against 32,768
    positions are 17 GB of float32; against a block of 512 they are 268
    MB).
``select``
    the admitted set as a boolean mask, and never a sort: the exact
    ``index_topk``-th largest score a query is found as a THRESHOLD by a
    bisection over the 32 bits of the scores read as ordered unsigned
    integers (32 counting passes over ``I``, each a fused compare and
    sum; ``lax.top_k`` at ``k`` = 2,048 of 32,768 is a sort a query, and
    ``lax.approx_max_k`` another model), and the set is ``I > threshold``
    plus the first ``k - count(I > threshold)`` positions that EQUAL it.
    Equal float32 scores at the threshold beyond the one taken are rare
    (counted: ``ties``); only a program that meets one pays the running
    count over positions that settles them (``lax.cond``).
``mla.mla_core``
    the latent core under the admitted mask: the MASKED, EXPANDED form.

**Work follows the positions reached, not the cache's length**: every
step is a loop over the key blocks that hold the ``pos + T`` positions
the chunk reaches, its trip count the program's own, so the first chunk
program of a 32,768-position cache scores, counts and attends one block.
(A ``lax.switch`` over static prefixes, as ``ops/mla.py::_xla_core``
takes them, is a compiled body a prefix a step a layer: 112 s of
compile for ONE (8, 512) program at 16 prefixes of 2,048, PERF.md §6,
PR 53.) What lies past the blocks reached is ``-inf`` in ``I`` and
false in the mask, written once.

Nothing here attends a superset or a subset of ``S_t``, scores fewer
heads, or skips the scoring where every position is admitted anyway (a
query before position ``index_topk`` admits all it sees: the bisection
then ends at its smallest score and the mask is the causal one).

Counts (``sparse_attention``'s second result, int32, valid lanes only):
pairs scored (a valid query times the positions ``<=`` it), pairs the
core admits (counted from the mask), threshold ties beyond the one
taken.

Every op here is XLA's: there is no Pallas core for the scoring, the
selection or the masked core yet (PERF.md §6, PR 53, has the pricing),
and an encoder says so on its ``engine.finalize`` span
(``dsa_kernel_layers``: 0).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from code_intelligence_tpu.ops import mla


def head_scores(s: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``sum_j w[t,j] relu(s[j,t,s])``: a block's products of all index
    heads ``(b, Hi, T, keys)`` float32 and the queries' weights ``(b, Hi,
    T, 1)`` to one score a pair ``(b, T, keys)``."""
    return jnp.sum(jax.nn.relu(s) * w, axis=1)


def index_scores(
    q_idx: jnp.ndarray,    # (b, T, Hi, d), rotated
    w_idx: jnp.ndarray,    # (b, T, Hi) float32
    idx_cache: jnp.ndarray,  # (b, S, d), the chunk written in
    pos: jnp.ndarray,      # () int32: positions cached before the chunk
    key_block: int = 512,
    mxu_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """``I`` ``(b, T, S)`` float32, ``-inf`` at positions after the
    query's own."""
    b, T, Hi, d = q_idx.shape
    S = idx_cache.shape[1]
    kb = mla._blocks(S, key_block)
    q = q_idx.astype(mxu_dtype)
    w = w_idx.astype(jnp.float32).swapaxes(1, 2)[..., None]  # (b, Hi, T, 1)
    at = pos + jnp.arange(T)

    def block(j, scores):
        keys = lax.dynamic_slice_in_dim(idx_cache, j * kb, kb, axis=1)
        s = jnp.einsum("bthd,bsd->bhts", q, keys.astype(mxu_dtype),
                       preferred_element_type=jnp.float32)
        s = head_scores(s, w)
        seen = (j * kb + jnp.arange(kb))[None, :] <= at[:, None]
        return lax.dynamic_update_slice_in_dim(
            scores, jnp.where(seen, s, -jnp.inf), j * kb, axis=2)

    return lax.fori_loop(0, mla.reached_blocks(pos, T, S, kb), block,
                         jnp.full((b, T, S), -jnp.inf, jnp.float32))


def _ordered(x: jnp.ndarray) -> jnp.ndarray:
    """Float32 as uint32 whose unsigned order is the floats' total order
    (``-0.0`` below ``+0.0``, as ``lax.top_k`` and ``lax.sort`` have
    it): a negative number's bits are flipped whole, a positive one's
    sign bit set."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select(scores: jnp.ndarray, pos: jnp.ndarray, k: int,
           key_block: int = 512, lanes: Optional[jnp.ndarray] = None,
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(admit (b, T, S) bool, threshold (b, T) float32, ties (b, T)
    int32, admitted () int32)`` from ``index_scores``' ``(b, T, S)``: a
    query at position ``pos + t`` admits the positions of its ``min(k,
    pos + t + 1)`` largest scores, of equal ones the lower positions
    first (``lax.top_k``'s rule); ``threshold`` is the smallest admitted
    score, ``ties`` the positions that equal it and are NOT admitted,
    ``admitted`` the pairs of the mask on the ``lanes`` ``(b, T)`` that
    count (all by default)."""
    b, T, S = scores.shape
    kb = mla._blocks(S, key_block)
    live = mla.reached_blocks(pos, T, S, kb)
    wanted = jnp.minimum(k, pos + 1 + jnp.arange(T))[None, :]  # (1, T)
    lanes = jnp.ones((b, T), bool) if lanes is None else lanes

    def ordered(j):
        return _ordered(lax.dynamic_slice_in_dim(scores, j * kb, kb, axis=2))

    def count(test):
        """``sum over the positions reached of test(block)``, a query."""
        return lax.fori_loop(
            0, live, lambda j, n: n + jnp.sum(test(ordered(j)), axis=-1,
                                              dtype=jnp.int32),
            jnp.zeros((b, T), jnp.int32))

    def bit(i, found):
        # the largest value that ``wanted`` scores reach, a bit a pass
        # from the top one down
        cand = found | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = count(lambda u: u >= cand[..., None]) >= wanted
        return jnp.where(enough, cand, found)

    thr = lax.fori_loop(0, 32, bit, jnp.zeros((b, T), jnp.uint32))
    # of the positions that equal the threshold, the first ``need``
    need = wanted - count(lambda u: u > thr[..., None])
    ties = count(lambda u: u == thr[..., None]) - need

    def masks(exact: bool):
        def block(j, carry):
            admit, before, n = carry
            u = ordered(j)
            equal = u == thr[..., None]
            taken = equal
            if exact:
                taken = equal & (before[..., None] + jnp.cumsum(
                    equal, axis=-1, dtype=jnp.int32) <= need[..., None])
                before = before + jnp.sum(equal, axis=-1, dtype=jnp.int32)
            here = (u > thr[..., None]) | taken
            n = n + jnp.sum(here & lanes[..., None], dtype=jnp.int32)
            return lax.dynamic_update_slice_in_dim(
                admit, here, j * kb, axis=2), before, n

        admit, _, n = lax.fori_loop(0, live, block, (
            jnp.zeros((b, T, S), bool), jnp.zeros((b, T), jnp.int32),
            jnp.zeros((), jnp.int32)))
        return admit, n

    admit, admitted = lax.cond(jnp.any(ties > 0), lambda: masks(True),
                               lambda: masks(False))
    # the threshold as the float it is
    t_bits = jnp.where(thr >> 31 == 1, thr & jnp.uint32(0x7FFFFFFF), ~thr)
    return (admit, lax.bitcast_convert_type(t_bits, jnp.float32), ties,
            admitted)


def sparse_attention(
    q_nope: jnp.ndarray,   # (b, T, H, nope)
    q_pe: jnp.ndarray,     # (b, T, H, rope), rotated
    cache: jnp.ndarray,    # (b, S, rank + rope), the chunk written in
    q_idx: jnp.ndarray,    # (b, T, Hi, d), rotated
    w_idx: jnp.ndarray,    # (b, T, Hi) float32
    idx_cache: jnp.ndarray,  # (b, S, d), the chunk written in
    pos: jnp.ndarray,      # () int32: positions cached before the chunk
    w_kvb: jnp.ndarray,    # (rank, H * (nope + v))
    scale: float,
    v_dim: int,
    topk: int,
    valid: Optional[jnp.ndarray] = None,  # (b, T) bool: lanes that count
    key_block: int = 512,
    mxu_dtype=jnp.bfloat16,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(out (b, T, H, v) float32, counts (3,) int32)``: the chunk's
    queries against the positions the indexer admits, both caches
    holding the chunk already; ``counts`` = pairs scored, pairs
    admitted, ties (the module's docstring). Named scopes
    ``dsa_indexer`` (the scoring), ``dsa_select``, ``mla_core``."""
    b, T = q_nope.shape[:2]
    lanes = jnp.ones((b, T), bool) if valid is None else valid
    with jax.named_scope("dsa_indexer"):
        scores = index_scores(q_idx, w_idx, idx_cache, pos, key_block,
                              mxu_dtype)
    with jax.named_scope("dsa_select"):
        admit, _, ties, admitted = select(scores, pos, topk, key_block,
                                          lanes)
        counts = jnp.stack([
            jnp.sum(jnp.where(lanes, pos + 1 + jnp.arange(T)[None, :], 0),
                    dtype=jnp.int32),
            admitted,
            jnp.sum(jnp.where(lanes, ties, 0), dtype=jnp.int32)])
    with jax.named_scope("mla_core"):
        out = mla.mla_core(q_nope, q_pe, cache, pos, w_kvb, scale, v_dim,
                           admit, key_block, mxu_dtype)
    return out, counts
