"""`ops/gdn.py`: the delta rule with ONE decay a head and grouped value
heads. The chunked scan (state in and out) against its token-by-token
twin over chunk sizes, padded tails and a padded row; a document across
2, 3 and 5 calls; a gate with no lower bound; two value heads on one key
head; and against the per-channel rule's twin with the decay broadcast
over the channels (`ops/kda.py::kda_recurrence`: the same rule where
every channel of a head decays alike)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code_intelligence_tpu.ops import gdn, kda


@functools.partial(jax.jit, static_argnames=("b", "T", "Hk", "Hv", "dk",
                                              "dv"))
def gdn_inputs(seed, b, T, Hk=2, Hv=4, dk=16, dv=8):
    """One compiled program a shape: drawn op by op, every ``normal`` of
    a new shape is a compilation of its own."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, T, Hk, dk))
    k = jax.random.normal(ks[1], (b, T, Hk, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, T, Hv, dv))
    # -exp(A_log) * softplus(.): no lower bound, most of it near 0
    g = -jax.nn.softplus(2 * jax.random.normal(ks[3], (b, T, Hv)) - 1)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, Hv)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, Hv, dk, dv))


recurrence = jax.jit(gdn.gdn_recurrence)


def scan(chunk=64, dtype=jnp.float32):
    return jax.jit(functools.partial(gdn.gdn_scan, chunk=chunk,
                                     mxu_dtype=dtype))


@pytest.mark.parametrize("T,chunk", [
    (128, 64),    # chunks divide T
    (100, 64),    # the last chunk is padded
    (37, 32),     # one chunk, padded
    (200, 64),
    (48, 16),     # a chunk of one diagonal block of the solve
    (40, 24),     # a chunk the solve's sub-block does not divide
])
def test_chunked_equals_token_by_token_with_state_in(T, chunk):
    inputs = gdn_inputs(T, 2, T)
    o, S = scan(chunk)(*inputs)
    o_want, S_want = recurrence(*inputs)
    # float32 sums in another order; outputs are O(0.3), states O(1)
    np.testing.assert_allclose(o, o_want, atol=5e-6)
    np.testing.assert_allclose(S, S_want, atol=5e-6)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-6),
                                        (jnp.bfloat16, 3e-3)])
def test_a_gate_of_minus_fifty_a_token_stays_finite(dtype, atol):
    """``g = -50`` at every token: ``G`` reaches -3200 inside a chunk
    and ``e^{3200}`` is no float32; every factor formed is ``e^{<= 0}``
    and underflows to 0, as the recurrence's own ``e^{-50}`` does."""
    q, k, v, g, beta, S = gdn_inputs(7, 1, 128)
    g = jnp.full_like(g, -50.0)
    o, S1 = scan(dtype=dtype)(q, k, v, g, beta, S)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S1).all())
    o_want, S_want = recurrence(q, k, v, g, beta, S)
    np.testing.assert_allclose(o, o_want, atol=atol)
    np.testing.assert_allclose(S1, S_want, atol=atol)


def test_no_exp_of_a_positive_sum_is_formed(monkeypatch):
    """Every argument ``gdn_scan`` hands ``exp`` is ``<= 0``, whatever
    the gate: nothing is rescaled because nothing can overflow."""
    seen = []
    real = jnp.exp

    def listening(x):
        seen.append(float(jnp.max(x)))
        return real(x)

    monkeypatch.setattr(gdn.jnp, "exp", listening)
    q, k, v, g, beta, S = gdn_inputs(8, 1, 128)
    gdn.gdn_scan(q, k, v, 40.0 * g, beta, S, mxu_dtype=jnp.float32)
    monkeypatch.undo()
    assert seen and max(seen) <= 0.0


def test_two_value_heads_read_one_key_head():
    """32 on 16 at the published sizes, 4 on 2 here: the scan with the
    key heads as they are equals the scan handed each key head twice
    (one a value head), and value head ``j`` reads key head ``j // 2``,
    not ``j % 2``."""
    q, k, v, g, beta, S = gdn_inputs(9, 2, 96)
    o, S1 = scan()(q, k, v, g, beta, S)
    o_rep, S_rep = scan()(jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2),
                          v, g, beta, S)
    np.testing.assert_allclose(o, o_rep, atol=2e-6)
    np.testing.assert_allclose(S1, S_rep, atol=2e-6)
    o_tiled, _ = scan()(jnp.tile(q, (1, 1, 2, 1)), jnp.tile(k, (1, 1, 2, 1)),
                        v, g, beta, S)
    assert float(jnp.abs(o - o_tiled).max()) > 1e-2
    with pytest.raises(ValueError, match="do not divide"):
        gdn.gdn_scan(q, k, v[:, :, :3], g[..., :3], beta[..., :3], S[:, :3])


def test_one_decay_a_head_is_the_per_channel_rule_with_equal_channels():
    """The scalar-decay rule against ``ops/kda.py``'s token-by-token twin
    handed the same decay on every channel of a head."""
    q, k, v, g, beta, S = gdn_inputs(10, 2, 96)
    o, S1 = scan()(q, k, v, g, beta, S)
    per_channel = jnp.broadcast_to(g[..., None], v.shape[:3] + (16,))
    o_want, S_want = jax.jit(kda.kda_recurrence)(
        jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v, per_channel,
        beta, S)
    np.testing.assert_allclose(o, o_want, atol=5e-6)
    np.testing.assert_allclose(S1, S_want, atol=5e-6)


@pytest.mark.parametrize("calls", [2, 3, 5])
def test_a_document_across_calls_equals_one_call(calls):
    """State in, state out: the matrix state handed over ``calls - 1``
    times, the calls' lengths no multiple of the chunk."""
    q, k, v, g, beta, S = gdn_inputs(11, 2, 200)
    o_want, S_want = scan()(q, k, v, g, beta, S)
    size = -(-200 // calls)
    outs = []
    for a in range(0, 200, size):
        o, S = scan()(*(x[:, a:a + size] for x in (q, k, v, g, beta)), S)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), o_want, atol=5e-6)
    np.testing.assert_allclose(S, S_want, atol=5e-6)


def test_padding_lanes_and_a_padding_row_leave_the_state_to_the_bit():
    """``g = 0`` and ``b = 0``: a row of padding alone hands its state
    back bit for bit; a row with 40 valid lanes of 64 ends where its 40
    tokens alone end, whatever the padding lanes hold."""
    q, k, v, g, beta, S = gdn_inputs(12, 2, 64)
    valid = jnp.arange(64)[None, :] < jnp.array([40, 0])[:, None]
    g0 = jnp.where(valid[..., None], g, 0.0)
    b0 = jnp.where(valid[..., None], beta, 0.0)
    _, S1 = scan()(q, k, v, g0, b0, S)
    np.testing.assert_array_equal(S1[1], S[1])
    _, alone = scan()(*(x[:1, :40] for x in (q, k, v, g, beta)), S[:1])
    np.testing.assert_allclose(S1[0], alone[0], atol=2e-6)
    assert float(jnp.abs(S1[0] - S[0]).max()) > 1e-2


def test_repeated_keys_do_not_cancel_in_the_inverse():
    """The same key at every token with ``b = 1`` and no decay: ``A`` is
    all ones below the diagonal (a Neumann product loses float32 there);
    its inverse by forward substitution is exact."""
    q, k, v, g, beta, S = gdn_inputs(13, 1, 128)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    args = (q, k, v, jnp.zeros_like(g), jnp.ones_like(beta), S)
    o, S1 = scan()(*args)
    o_want, S_want = recurrence(*args)
    np.testing.assert_allclose(o, o_want, atol=5e-6)
    np.testing.assert_allclose(S1, S_want, atol=5e-6)


def test_bfloat16_tiles_stay_near_the_float32_recurrence():
    """The in-chunk products' inputs rounded to bfloat16, float32
    accumulation; the decays, the solve and the state's products stay
    float32: outputs O(0.3) to 3e-3."""
    inputs = gdn_inputs(14, 2, 192)
    o, S = scan(dtype=jnp.bfloat16)(*inputs)
    o_want, S_want = recurrence(*inputs)
    np.testing.assert_allclose(o, o_want, atol=4e-3)
    np.testing.assert_allclose(S, S_want, atol=4e-3)
    assert o.dtype == S.dtype == jnp.float32


def test_the_solve_is_kdas_one_copy():
    assert gdn._solve_unit_lower is kda._solve_unit_lower
