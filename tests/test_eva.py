"""``ops/eva.py`` against a token-by-token float32 loop written from the
equations: a block of ``window`` positions attended exactly beside one
summary a chunk of every EARLIER block, under one softmax. Small sizes
(window 32, chunk 4, 2 heads of 8) on seeded inputs, compiled; rows of
different lengths so that padding lanes, a partial last chunk and a
chunk of padding alone are inside; chunk programs of several lengths,
each of which ends a block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code_intelligence_tpu.ops import attention, eva

W, C, H, D = 32, 4, 2, 8
SCALE = D ** -0.5
LENGTHS = (100, 77, 128, 9)      # 4 rows: 3+ blocks, a partial last chunk
P = 128                          # positions the caches are allocated for


def seeded(n=P, rows=len(LENGTHS), seed=0):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.standard_normal((rows, n, H, D)).astype(np.float32)
               for _ in range(3))
    phi = 2.0 * rng.standard_normal((H, D)).astype(np.float32)
    mu = 0.5 * rng.standard_normal((H, D)).astype(np.float32)
    return q, k, v, phi, mu


def loop_summaries(k, v, phi, mu, length, chunk=C):
    """A row's chunk summaries, a chunk and a head at a time, over the
    valid positions alone: ``(ksum, vsum)`` ``(chunks, H, D)``."""
    n = -(-length // chunk)
    ksum, vsum = np.zeros((n, H, D)), np.zeros((n, H, D))
    for c in range(n):
        at = np.arange(c * chunk, min((c + 1) * chunk, length))
        for h in range(H):
            s = SCALE * k[at, h].astype(np.float64) @ phi[h]
            a = np.exp(s - s.max())
            a /= a.sum()
            ksum[c, h] = a @ k[at, h] + mu[h]
            vsum[c, h] = a @ v[at, h]
    return ksum, vsum


def loop_attention(q, k, v, phi, mu, length, window=W, chunk=C):
    """A row's outputs ``(length, H, D)`` and the keys of either kind
    each query met, a position and a head at a time."""
    ksum, vsum = loop_summaries(k, v, phi, mu, length, chunk)
    out = np.zeros((length, H, D))
    met = np.zeros((length, 2), np.int64)
    for i in range(length):
        own = np.arange(i // window * window, i + 1)            # E_i
        passed = np.arange((i // window) * (window // chunk))    # C_i
        met[i] = len(own), len(passed)
        for h in range(H):
            s = SCALE * np.concatenate([
                k[own, h].astype(np.float64) @ q[i, h],
                ksum[passed, h] @ q[i, h]])
            p = np.exp(s - s.max())
            p /= p.sum()
            out[i, h] = p[:len(own)] @ v[own, h] \
                + p[len(own):] @ vsum[passed, h]
    return out, met, ksum, vsum


def through_programs(q, k, v, phi, mu, lengths, T, positions=P, window=W):
    """The document through chunk programs of ``T``: outputs, the final
    caches, and the keys each lane's query met."""
    rows = len(lengths)
    block = min(window, positions)
    caches = [jnp.zeros((rows, H, slots, D), jnp.float32)
              for slots in (block, block, positions // C, positions // C)]

    @jax.jit
    def program(q, k, v, caches, pos, valid):
        k_block, v_block, k_sum, v_sum = caches
        ksum, vsum = eva.chunk_summaries(k, v, phi, mu, valid, SCALE, C)
        out, k_block, v_block, met = eva.eva_cached(
            q, k, v, k_block, v_block, k_sum, v_sum, pos, SCALE, window, C,
            mxu_dtype=jnp.float32, key_block=16)
        k_sum, v_sum = eva.write_summaries(k_sum, v_sum, ksum, vsum, pos, C)
        return out, [k_block, v_block, k_sum, v_sum], met

    outs, mets = [], []
    for pos in range(0, positions, T):
        valid = (pos + np.arange(T))[None, :] < np.asarray(lengths)[:, None]
        with jax.default_matmul_precision("highest"):
            out, caches, met = program(
                q[:, pos:pos + T], k[:, pos:pos + T], v[:, pos:pos + T],
                caches, jnp.int32(pos), jnp.asarray(valid))
        outs.append(np.asarray(out))
        mets.append(np.stack([np.asarray(m) for m in met], axis=-1))
    return (np.concatenate(outs, axis=1), [np.asarray(c) for c in caches],
            np.concatenate(mets, axis=0))


@pytest.fixture(scope="module")
def inputs():
    return seeded()


@pytest.fixture(scope="module")
def want(inputs):
    q, k, v, phi, mu = inputs
    return [loop_attention(q[r], k[r], v[r], phi, mu, n)
            for r, n in enumerate(LENGTHS)]


@pytest.mark.parametrize("T", [8, 16, 32])
def test_the_joint_core_agrees_with_the_loop(inputs, want, T):
    """Every valid position of every row, through chunk programs of
    ``T`` (each ends a block at every ``32 / T``-th program): float32,
    so the running softmax over key blocks and the loop's one softmax
    differ by rounding alone (1e-5 of outputs of order 1)."""
    q, k, v, phi, mu = inputs
    got, caches, met = through_programs(q, k, v, phi, mu, LENGTHS, T)
    for r, n in enumerate(LENGTHS):
        out, keys_met, ksum, vsum = want[r]
        np.testing.assert_allclose(got[r, :n], out, atol=1e-5)
        # what the mask admitted, counted from the masks as applied
        np.testing.assert_array_equal(met[:n], keys_met)
        # the summaries as cached, the partial last chunk's among them
        chunks = len(ksum)
        np.testing.assert_allclose(
            caches[2][r, :, :chunks].swapaxes(0, 1), ksum, atol=1e-5)
        np.testing.assert_allclose(
            caches[3][r, :, :chunks].swapaxes(0, 1), vsum, atol=1e-5)


def test_a_chunk_of_padding_alone_is_zeros_and_the_offset(inputs):
    q, k, v, phi, mu = inputs
    valid = np.zeros((len(LENGTHS), 16), bool)
    valid[0, :5] = True   # row 0: one whole chunk, one lane of the next
    ksum, vsum = jax.jit(lambda k, v, ok: eva.chunk_summaries(
        k, v, phi, mu, ok, SCALE, C))(k[:, :16], v[:, :16], valid)
    np.testing.assert_allclose(
        ksum[1], np.broadcast_to(mu[:, None, :], ksum[1].shape))
    assert not np.asarray(vsum[1:]).any()
    np.testing.assert_allclose(ksum[0, :, 2:], np.broadcast_to(
        mu[:, None, :], ksum[0, :, 2:].shape))
    # the one valid lane of row 0's second chunk is its whole summary
    np.testing.assert_allclose(vsum[0, :, 1], v[0, 4], atol=1e-6)
    np.testing.assert_allclose(ksum[0, :, 1], k[0, 4] + mu, atol=1e-6)


def causal_softmax(q, k, v):
    """Plain causal attention of one row, float64."""
    s = SCALE * np.einsum("thd,shd->hts", q.astype(np.float64), k)
    s = np.where(np.tril(np.ones(s.shape[1:], bool)), s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    return np.einsum("hts,shd->thd", p / p.sum(axis=-1, keepdims=True), v)


@pytest.mark.parametrize("T", [16, 32])
def test_a_document_inside_one_block_is_gqa_cached(inputs, T):
    """No summary is ever visible: the same numbers as the global cached
    core of ``ops/attention.py`` over a cache of the block's size."""
    q, k, v, phi, mu = (a[:1] if a.ndim == 4 else a for a in inputs)
    got, _, met = through_programs(q, k, v, phi, mu, (W,), T, positions=W)
    assert not met[:, 1].any()
    caches = [jnp.zeros((1, H, W, D), jnp.float32)] * 2
    outs = []
    for pos in range(0, W, T):
        with jax.default_matmul_precision("highest"):
            out, *caches = attention.gqa_cached(
                q[:, pos:pos + T], k[:, pos:pos + T], v[:, pos:pos + T],
                *caches, jnp.int32(pos), SCALE, mxu_dtype=jnp.float32,
                window=None)
        outs.append(np.asarray(out))
    np.testing.assert_allclose(got, np.concatenate(outs, axis=1), atol=1e-6)


def test_a_window_that_holds_the_document_is_full_causal_attention(inputs):
    q, k, v, phi, mu = inputs
    n = 96
    got, _, met = through_programs(
        q, k, v, phi, mu, (n,) * len(LENGTHS), 32, positions=128, window=128)
    assert not met[:, 1].any()
    for r in range(len(LENGTHS)):
        np.testing.assert_allclose(
            got[r, :n], causal_softmax(q[r, :n], k[r, :n], v[r, :n]),
            atol=1e-5)


@pytest.mark.parametrize("T,slots,window,chunk,match", [
    (6, 32, 32, 4, "whole chunks"),        # summaries: T % chunk
    (16, 24, 32, 4, "whole chunk programs"),   # a block cache of 1.5 programs
    (16, 64, 32, 4, "whole chunk programs"),   # a block cache past the block
    (16, 32, 30, 4, "not whole chunks"),   # a block of 7.5 chunks
])
def test_shapes_a_program_could_straddle_a_block_with_are_refused(
        T, slots, window, chunk, match):
    x = jnp.zeros((1, T, H, D))
    cache = jnp.zeros((1, H, slots, D))
    with pytest.raises(ValueError, match=match):
        if match == "whole chunks":
            eva.chunk_summaries(x, x, jnp.zeros((H, D)), jnp.zeros((H, D)),
                                jnp.ones((1, T), bool), SCALE, chunk)
        else:
            eva.eva_cached(x, x, x, cache, cache, cache, cache,
                           jnp.int32(0), SCALE, window, chunk)
