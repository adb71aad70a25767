"""`ops/gdn.py`: the scalar-decay delta rule's two cores and the rule
between them. The kernel is interpreted here (the CPU); that Mosaic takes it
at the cell's shapes is `tests/test_pallas_tpu_compile_latent.py`'s to say.
`tests/test_gdn.py`'s cases are the XLA core's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code_intelligence_tpu.ops import gdn
from test_gdn import gdn_inputs, recurrence

BF16, F32 = jnp.bfloat16, jnp.float32


# the kernel's two neighbours as compiled programs (the kernel itself is
# interpreted, and what it costs here is its run)
xla_scan = jax.jit(gdn._xla_scan, static_argnums=(6, 7))


# -- one arithmetic, two cores -------------------------------------------------

@pytest.mark.parametrize("seed,b,T,Hk,Hv,chunk", [
    (1, 2, 128, 2, 4, 64),    # two rows, two value heads a key head, two chunks
    (2, 1, 96, 3, 3, 32),     # a value head a key head; other chunks
    (3, 2, 192, 1, 4, 64),    # four value heads on one key head, three chunks
    (2, 3, 64, 2, 4, 64),     # one chunk: the state in is the state met
    (1, 1, 48, 2, 4, 24),     # a chunk of one diagonal block of the solve
], ids=["b2_rep2", "rep1", "rep4", "one_chunk", "chunk_24"])
def test_the_kernel_equals_the_scan_and_the_recurrence(seed, b, T, Hk, Hv,
                                                       chunk):
    inputs = gdn_inputs(seed, b, T, Hk=Hk, Hv=Hv)
    assert float(jnp.abs(inputs[-1]).max()) > 1     # a state comes in
    o, S = gdn._kernel_scan(*inputs, chunk, F32)
    o_xla, S_xla = xla_scan(*inputs, chunk, F32)
    o_want, S_want = recurrence(*inputs)
    # float32 sums in another order; outputs are O(0.3), states O(1): the
    # XLA core's tightness against the recurrence, twice it between the two
    # cores (each is that far from it)
    np.testing.assert_allclose(o, o_want, atol=5e-6)
    np.testing.assert_allclose(S, S_want, atol=5e-6)
    np.testing.assert_allclose(o, o_xla, atol=1e-5)
    np.testing.assert_allclose(S, S_xla, atol=1e-5)


def test_bfloat16_tiles_stay_near_the_float32_recurrence_in_the_kernel():
    inputs = gdn_inputs(14, 2, 192)
    o, S = gdn._kernel_scan(*inputs, 64, BF16)
    o_want, S_want = recurrence(*inputs)
    np.testing.assert_allclose(o, o_want, atol=4e-3)
    np.testing.assert_allclose(S, S_want, atol=4e-3)
    assert o.dtype == S.dtype == F32
    # the XLA core rounds the same operands: the two are nearer each other
    o_xla, S_xla = xla_scan(*inputs, 64, BF16)
    np.testing.assert_allclose(o, o_xla, atol=2e-3)
    np.testing.assert_allclose(S, S_xla, atol=2e-3)


@pytest.mark.parametrize("dtype,atol", [(F32, 1e-6), (BF16, 3e-3)])
def test_a_gate_of_minus_fifty_a_token_stays_finite_in_the_kernel(dtype,
                                                                  atol):
    q, k, v, g, beta, S = gdn_inputs(7, 1, 128)
    g = jnp.full_like(g, -50.0)
    o, S1 = gdn._kernel_scan(q, k, v, g, beta, S, 64, dtype)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S1).all())
    o_want, S_want = recurrence(q, k, v, g, beta, S)
    np.testing.assert_allclose(o, o_want, atol=atol)
    np.testing.assert_allclose(S1, S_want, atol=atol)


def test_the_kernels_step_forms_no_exp_of_a_positive_sum(monkeypatch):
    """What the kernel runs a key head a chunk (``_chunk_step``), run here
    on arrays with a gate forty times the usual: every argument it hands
    ``exp`` is ``<= 0``."""
    from jax.experimental.pallas import tpu as pltpu

    seen = []
    real = jnp.exp

    def listening(x):
        seen.append(float(jnp.max(x)))
        return real(x)

    # the kernel's sublane rotation has no meaning outside a kernel
    monkeypatch.setattr(pltpu, "roll", lambda x, s, axis: jnp.roll(x, s, axis))
    monkeypatch.setattr(gdn.jnp, "exp", listening)
    q, k, v, g, beta, S = gdn_inputs(8, 1, 64)
    g = 40.0 * g
    os, Ss = gdn._chunk_step(
        q[0, :, 0], k[0, :, 0], [v[0, :, j] for j in (0, 1)],
        [g[0, :, j:j + 1] for j in (0, 1)],
        [beta[0, :, j:j + 1] for j in (0, 1)], [S[0, 0], S[0, 1]], 16, F32)
    monkeypatch.undo()
    assert seen and max(seen) <= 0.0
    o_want, S_want = gdn.gdn_recurrence(q[:, :, :1], k[:, :, :1], v[:, :, :2],
                                        g[:, :, :2], beta[:, :, :2], S[:, :2])
    for j in (0, 1):
        np.testing.assert_allclose(os[j], o_want[0, :, j], atol=5e-6)
        np.testing.assert_allclose(Ss[j], S_want[0, j], atol=5e-6)


def test_repeated_keys_do_not_cancel_in_the_kernels_solve():
    """The same key at every token with ``b = 1`` and no decay: ``A`` is
    all ones below the diagonal; forward substitution is exact."""
    q, k, v, g, beta, S = gdn_inputs(13, 1, 128)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    args = (q, k, v, jnp.zeros_like(g), jnp.ones_like(beta), S)
    o, S1 = gdn._kernel_scan(*args, 64, F32)
    o_want, S_want = recurrence(*args)
    np.testing.assert_allclose(o, o_want, atol=5e-6)
    np.testing.assert_allclose(S1, S_want, atol=5e-6)


# -- padding ------------------------------------------------------------------

def _padded_after(inputs, lengths):
    """``g = 0`` and ``b = 0`` past each row's length, as the encoder
    hands a padding lane over; ``q``, ``k``, ``v`` stay what they were."""
    q, k, v, g, beta, S = inputs
    valid = jnp.arange(q.shape[1])[None, :] < jnp.asarray(lengths)[:, None]
    return (q, k, v, jnp.where(valid[..., None], g, 0.0),
            jnp.where(valid[..., None], beta, 0.0), S)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_a_padded_tail_and_a_padded_row_leave_the_state_bit_equal(dtype):
    """Row 0 is valid for one chunk of two, row 1 for 40 tokens of its
    first chunk, row 2 not at all: the state after the padded lanes is,
    bit for bit, the state after the valid ones, whatever the padded
    lanes hold; a wholly padded row's state is the one that came in."""
    inputs = _padded_after(gdn_inputs(3, 3, 128), [64, 40, 0])
    q, k, v, g, beta, S = inputs
    _, S_all = gdn._kernel_scan(*inputs, 64, dtype)
    _, S_first = gdn._kernel_scan(q[:, :64], k[:, :64], v[:, :64], g[:, :64],
                                  beta[:, :64], S, 64, dtype)
    np.testing.assert_array_equal(S_all, S_first)
    np.testing.assert_array_equal(S_all[2], S[2])
    assert float(jnp.abs(S_all[1] - S[1]).max()) > 1e-2
    noise = gdn_inputs(4, 3, 128)
    valid = (jnp.arange(128)[None, :] < jnp.array([64, 40, 0])[:, None])[
        ..., None, None]
    _, S_noise = gdn._kernel_scan(
        *(jnp.where(valid, a, n) for a, n in zip((q, k, v), noise)),
        g, beta, S, 64, dtype)
    np.testing.assert_array_equal(S_noise, S_all)


# -- through gdn_scan, the state handed from program to program ---------------

@pytest.mark.parametrize("programs", [2, 3, 5])
def test_a_document_across_programs_equals_one_program(monkeypatch, programs):
    """The rule's answer steered from the test (it sees the CPU and
    float32 here); the kernel itself asks the real backend and is
    interpreted."""
    monkeypatch.setattr(gdn, "core_is_kernel", lambda *a: True)
    T = 64 * programs
    q, k, v, g, beta, S = gdn_inputs(programs, 2, T)
    calls = []
    real = gdn._kernel_scan
    monkeypatch.setattr(gdn, "_kernel_scan",
                        lambda *a: calls.append(a[0].shape[1]) or real(*a))
    o_one, S_one = gdn.gdn_scan(q, k, v, g, beta, S, 64, F32)
    outs, state = [], S
    for lo in range(0, T, 64):
        at = slice(lo, lo + 64)
        o, state = gdn.gdn_scan(q[:, at], k[:, at], v[:, at], g[:, at],
                                beta[:, at], state, 64, mxu_dtype=F32)
        outs.append(o)
    assert calls == [T] + [64] * programs      # every call took the kernel
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), o_one, atol=1e-6)
    np.testing.assert_allclose(state, S_one, atol=1e-6)
    o_want, S_want = recurrence(q, k, v, g, beta, S)
    np.testing.assert_allclose(o_one, o_want, atol=5e-6)
    np.testing.assert_allclose(S_one, S_want, atol=5e-6)


def test_off_the_tpu_gdn_scan_is_the_xla_scan(monkeypatch):
    """No patch: here the rule says XLA, and the kernel is not built."""
    monkeypatch.setattr(gdn, "_kernel_scan", None)
    inputs = gdn_inputs(5, 1, 100)
    o, S = gdn.gdn_scan(*inputs, mxu_dtype=F32)
    o_xla, S_xla = gdn._xla_scan(*inputs, 64, F32)       # eager, as o
    np.testing.assert_array_equal(o, o_xla)
    np.testing.assert_array_equal(S, S_xla)


# -- the rule ------------------------------------------------------------------

PUBLISHED = (16, 32, 128, 128)  # key heads, value heads, dk, dv


# the cell's 38 programs a call are all 512 tokens, 32 value heads on 16
# key heads of 128 | 128, chunks of 64, bfloat16: whatever their rows
@pytest.mark.parametrize("backend,dtype,T,sizes,chunk,kernel", [
    ("tpu", BF16, 512, PUBLISHED, 64, True),
    ("tpu", BF16, 64, PUBLISHED, 64, True),          # one chunk
    ("cpu", BF16, 512, PUBLISHED, 64, False),
    ("gpu", BF16, 512, PUBLISHED, 64, False),
    ("tpu", F32, 512, PUBLISHED, 64, False),         # the parity tests' type
    ("tpu", BF16, 500, PUBLISHED, 64, False),        # the scan pads, not it
    ("tpu", BF16, 32, PUBLISHED, 64, False),         # under a chunk
    ("tpu", BF16, 512, (16, 32, 64, 128), 64, False),    # keys under a lane
    ("tpu", BF16, 512, (16, 32, 128, 64), 64, False),    # values under a lane
    ("tpu", BF16, 512, (2, 4, 16, 8), 64, False),    # the tiny preset
    ("tpu", BF16, 512, PUBLISHED, 24, False),        # half a bfloat16 tile
    ("tpu", BF16, 512, PUBLISHED, 128, True),        # other whole tiles
    ("tpu", BF16, 512, (4, 12, 128, 256), 64, True),     # other heads
    ("tpu", BF16, 512, (64, 128, 128, 128), 64, False),  # blocks past the VMEM
], ids=["cell", "one_chunk", "cpu", "gpu", "float32", "ragged_T",
        "under_a_chunk", "small_dk", "small_dv", "tiny", "chunk_24",
        "chunk_128", "heads_12", "heads_128"])
def test_the_rule_reads_observables_alone(backend, dtype, T, sizes, chunk,
                                          kernel):
    assert gdn.core_is_kernel(backend, dtype, T, *sizes, chunk) is kernel


def test_a_ragged_length_is_refused_by_the_kernel_and_heads_by_the_scan():
    q, k, v, g, beta, S = gdn_inputs(1, 1, 64)
    with pytest.raises(ValueError, match="does not divide"):
        gdn._kernel_scan(q[:, :40], k[:, :40], v[:, :40], g[:, :40],
                         beta[:, :40], S, 64, F32)
    with pytest.raises(ValueError, match="do not divide"):
        gdn.gdn_scan(q, k, v[:, :, :3], g[..., :3], beta[..., :3], S[:, :3])
