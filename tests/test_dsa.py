"""``ops/dsa.py`` at small sizes on the CPU, compiled: index scores and the
selection against a query-by-query float32 loop (the SETS equal exactly,
ties to the lower position, padding lanes, fewer positions than ``k``, a
chunk program in the middle of a cache, the threshold form equal to
``lax.top_k``), ``mla_cached(admit=...)`` against ``mla_cached()`` and
the masked core against a gathered loop."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from code_intelligence_tpu.ops import dsa, mla

K, HI, D = 8, 4, 16          # positions a query, index heads, their width
B, T, S = 2, 16, 64          # rows, a chunk program's queries, the cache
F32 = jnp.float32


def _indexer(seed, pos, n=None):
    """Index queries and weights of one chunk at ``pos`` and a cache
    whose first ``pos + T`` positions are written (zeros after)."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, T, HI, D)).astype(np.float32)
    w = rng.standard_normal((B, T, HI)).astype(np.float32) / 8
    cache = np.zeros((B, S, D), np.float32)
    cache[:, :pos + T] = rng.standard_normal((B, pos + T, D))
    return q, w, cache


def _loop_scores(q, w, cache, pos):
    """``I[t, s]`` a query and a position at a time, float64."""
    out = np.full((B, T, S), -np.inf)
    for b in range(B):
        for t in range(T):
            for s in range(pos + t + 1):
                dots = q[b, t].astype(np.float64) @ cache[b, s]
                out[b, t, s] = np.sum(w[b, t] * np.maximum(dots, 0.0))
    return out


def _loop_sets(scores, pos, k=K):
    """``S_t`` by a stable sort a query: the ``min(k, pos + t + 1)``
    largest, of equal scores the lower positions."""
    sets = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            n = pos + t + 1
            order = np.argsort(-scores[b, t, :n], kind="stable")
            sets[b, t, order[:min(k, n)]] = True
    return sets


@functools.lru_cache(maxsize=None)
def _compiled(key_block, k=K):
    def run(q, w, cache, pos):
        scores = dsa.index_scores(q, w, cache, pos, key_block, F32)
        return (scores,) + dsa.select(scores, pos, k, key_block)
    return jax.jit(run)


@pytest.mark.parametrize("pos,key_block", [
    (0, 16),     # the first chunk program: every query has t + 1 <= 16
    (0, 64),     # the cache whole, one block
    (16, 16),    # a program in the middle of a cache, whole blocks
    (32, 8),     # blocks shorter than the chunk
    (48, 32),    # the cache's last program
])
def test_scores_and_sets_against_a_loop(pos, key_block):
    q, w, cache = _indexer(pos + key_block, pos)
    with jax.default_matmul_precision("highest"):
        scores, admit, thr, ties, admitted = _compiled(key_block)(
            q, w, cache, jnp.int32(pos))
    want = _loop_scores(q, w, cache, pos)
    reached = -(-(pos + T) // key_block) * key_block
    seen = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(scores), seen)
    np.testing.assert_allclose(np.asarray(scores)[seen], want[seen],
                               rtol=2e-5, atol=2e-6)
    # the sets are those of the program's OWN float32 scores, exactly
    sets = _loop_sets(np.asarray(scores), pos)
    np.testing.assert_array_equal(np.asarray(admit), sets)
    assert not np.asarray(admit)[:, :, reached:].any()
    counts = np.minimum(K, pos + 1 + np.arange(T))
    np.testing.assert_array_equal(np.asarray(admit).sum(-1),
                                  np.broadcast_to(counts, (B, T)))
    assert int(admitted) == B * counts.sum()
    # the threshold is the smallest admitted score; what else equals it
    # (four index heads that all read negative give an exact 0) is a tie
    np.testing.assert_array_equal(
        np.asarray(thr), np.where(sets, np.asarray(scores), np.inf).min(-1))
    np.testing.assert_array_equal(np.asarray(ties), (
        (np.asarray(scores) == np.asarray(thr)[..., None]) & ~sets).sum(-1))


def test_the_threshold_form_is_lax_top_k_on_tied_scores():
    """Scores drawn from five values, so that most thresholds are tied
    several times over: the admitted set is ``lax.top_k``'s (of equal
    scores the lower positions), and ``ties`` counts what it leaves."""
    pos = 32
    rng = np.random.default_rng(7)
    scores = rng.integers(-2, 3, (B, T, S)).astype(np.float32) / 4
    scores[..., 0] = -0.0      # below +0.0 in the total order, as top_k's
    scores[..., 1] = 0.0
    causal = np.arange(S)[None, :] <= (pos + np.arange(T))[:, None]
    scores = np.where(causal, scores, -np.inf).astype(np.float32)
    admit, thr, ties, admitted = jax.jit(
        lambda s, p: dsa.select(s, p, K, 16))(scores, jnp.int32(pos))
    _, chosen = lax.top_k(jnp.asarray(scores), K)
    want = np.zeros((B, T, S), bool)
    np.put_along_axis(want, np.asarray(chosen), True, axis=-1)
    np.testing.assert_array_equal(np.asarray(admit), want)
    # and the stable sort's, but for the signed zeros it reads as equal
    np.testing.assert_array_equal(np.asarray(admit)[..., 2:],
                                  _loop_sets(scores, pos)[..., 2:])
    same = scores.view(np.uint32) == np.asarray(thr).view(np.uint32)[..., None]
    left = same & ~want
    np.testing.assert_array_equal(np.asarray(ties), left.sum(-1))
    assert np.asarray(ties).sum() > B * T and int(admitted) == B * T * K


def test_padding_lanes_are_left_out_of_the_counts():
    pos = 16
    q, w, cache = _indexer(3, pos)
    lanes = np.arange(T)[None, :] < np.array([[T], [5]])
    _, _, _, admitted = jax.jit(
        lambda s, p, l: dsa.select(s, p, K, 16, l))(
            _loop_scores(q, w, cache, pos).astype(np.float32),
            jnp.int32(pos), lanes)
    assert int(admitted) == K * (T + 5)


def _latent(seed, pos):
    rng = np.random.default_rng(seed)
    H, nope, rope, v, rank = 4, 12, 4, 16, 16
    q_nope = rng.standard_normal((B, T, H, nope)).astype(np.float32)
    q_pe = rng.standard_normal((B, T, H, rope)).astype(np.float32)
    cache = np.zeros((B, S, rank + rope), np.float32)
    cache[:, :pos] = rng.standard_normal((B, pos, rank + rope))
    latent = rng.standard_normal((B, T, rank + rope)).astype(np.float32)
    w_kvb = (rng.standard_normal((rank, H * (nope + v))) / 4).astype(
        np.float32)
    return q_nope, q_pe, latent, cache, w_kvb, v


@pytest.mark.parametrize("pos,key_block", [(0, 16), (16, 8), (48, 64)])
def test_admitting_every_position_seen_is_mla_cached(pos, key_block):
    """``mla_cached(admit=causal)`` against ``mla_cached()``: the same
    cache to the bit; the same output to float32 rounding and not to the
    bit (the masked core keeps a running maximum and sum over key blocks
    and divides once, the plain XLA core takes one softmax a prefix)."""
    q_nope, q_pe, latent, cache, w_kvb, v = _latent(pos, pos)
    causal = jnp.broadcast_to(
        jnp.arange(S)[None, :] <= (pos + jnp.arange(T))[:, None], (B, T, S))
    run = jax.jit(lambda admit: mla.mla_cached(
        q_nope, q_pe, latent, cache, jnp.int32(pos), w_kvb, 0.25, v,
        key_block=key_block, mxu_dtype=F32, admit=admit))
    with jax.default_matmul_precision("highest"):
        want, cache_w = jax.jit(lambda: mla.mla_cached(
            q_nope, q_pe, latent, cache, jnp.int32(pos), w_kvb, 0.25, v,
            key_block=key_block, mxu_dtype=F32))()
        got, cache_g = run(causal)
    np.testing.assert_array_equal(np.asarray(cache_g), np.asarray(cache_w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_the_masked_core_against_a_gathered_loop():
    """Random sets of 1 .. 8 positions a query, none after its own, some
    with nothing in the first key block: the masked core equals a loop
    that GATHERS a query's admitted rows and attends them densely."""
    pos = 32
    q_nope, q_pe, latent, cache, w_kvb, v = _latent(11, pos)
    rng = np.random.default_rng(12)
    admit = np.zeros((B, T, S), bool)
    for b in range(B):
        for t in range(T):
            first = 16 if t % 3 == 0 else 0   # nothing of the first block
            at = rng.choice(np.arange(first, pos + t + 1),
                            size=rng.integers(1, K + 1), replace=False)
            admit[b, t, at] = True
    with jax.default_matmul_precision("highest"):
        got, full = jax.jit(lambda a: mla.mla_cached(
            q_nope, q_pe, latent, cache, jnp.int32(pos), w_kvb, 0.25, v,
            key_block=16, mxu_dtype=F32, admit=a))(admit)
    full = np.asarray(full, np.float64)
    H, nope = q_nope.shape[2], q_nope.shape[3]
    rank = full.shape[-1] - q_pe.shape[-1]
    w = w_kvb.astype(np.float64).reshape(rank, H, nope + v)
    want = np.zeros((B, T, H, v))
    for b in range(B):
        for t in range(T):
            rows = full[b, admit[b, t]]                       # gathered
            k_nope = np.einsum("sc,chd->shd", rows[:, :rank], w[..., :nope])
            vals = np.einsum("sc,chd->shd", rows[:, :rank], w[..., nope:])
            s = (np.einsum("hd,shd->hs", q_nope[b, t], k_nope)
                 + np.einsum("hr,sr->hs", q_pe[b, t], rows[:, rank:])) * 0.25
            p = np.exp(s - s.max(-1, keepdims=True))
            want[b, t] = np.einsum("hs,shd->hd", p / p.sum(-1, keepdims=True),
                                   vals)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


def test_sparse_attention_counts_and_a_short_cache_is_plain_attention():
    """Through ``sparse_attention``: a program whose every query sees
    fewer than ``k`` positions attends all of them (``mla_cached`` as it
    stands), and the counts are the pairs scored and admitted on the
    valid lanes."""
    pos = 0
    q_nope, q_pe, latent, cache, w_kvb, v = _latent(5, pos)
    q, w, idx_cache = _indexer(6, pos)
    valid = np.arange(T)[None, :] < np.array([[T], [9]])

    def run(topk):
        written = lax.dynamic_update_slice_in_dim(
            jnp.asarray(cache), jnp.asarray(latent), pos, axis=1)
        return dsa.sparse_attention(
            q_nope, q_pe, written, q, w, idx_cache, jnp.int32(pos), w_kvb,
            0.25, v, topk, valid, key_block=16, mxu_dtype=F32)

    with jax.default_matmul_precision("highest"):
        out, counts = jax.jit(lambda: run(T))()
        few, few_counts = jax.jit(lambda: run(4))()
        want, _ = jax.jit(lambda: mla.mla_cached(
            q_nope, q_pe, latent, cache, jnp.int32(pos), w_kvb, 0.25, v,
            key_block=16, mxu_dtype=F32))()
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    scored = sum(t + 1 for t in range(T)) + sum(t + 1 for t in range(9))
    assert list(np.asarray(counts))[:2] == [scored, scored]
    assert list(np.asarray(few_counts))[:2] == [
        scored, sum(min(4, t + 1) for t in list(range(T)) + list(range(9)))]
    assert np.abs(np.asarray(few) - np.asarray(want))[0, 8:].max() > 1e-3
