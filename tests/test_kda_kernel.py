"""`ops/kda.py`: the delta-rule recurrence's two cores and the rule between
them. The kernel is interpreted here (the CPU); that Mosaic takes it at the
cell's shapes is `tests/test_pallas_tpu_compile_latent.py`'s to say.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code_intelligence_tpu.ops import kda
from test_bailing_hybrid import kda_inputs, recurrence

BF16, F32 = jnp.bfloat16, jnp.float32


# the kernel's two neighbours as compiled programs (the kernel itself is
# interpreted, and what it costs here is its run)
xla_scan = jax.jit(kda._xla_scan, static_argnums=(6, 7, 8))


def _the_rule_says_kernel(monkeypatch, heads):
    """The rule's answer steered from the test (it sees the CPU and
    float32 here), and a head block that divides the tiny shapes; the
    kernel itself asks the real backend and is interpreted."""
    monkeypatch.setattr(kda, "core_is_kernel", lambda *a: True)
    monkeypatch.setattr(kda, "_kernel_tiles", lambda *a: heads)


# -- one arithmetic, two cores -------------------------------------------------

@pytest.mark.parametrize("seed,b,T,H,hb,chunk,sub", [
    (1, 2, 128, 4, 2, 64, 16),   # two rows, two head blocks, two chunks
    (2, 1, 64, 3, 3, 32, 8),     # one head block; other sub-blocks
    (3, 2, 192, 2, 1, 64, 16),   # a head a step, three chunks
    (2, 3, 64, 6, 2, 64, 16),    # one chunk: the state in is the state met
    (1, 1, 256, 4, 4, 64, 32),   # two sub-blocks a chunk
], ids=["b2_hb2", "sub8", "hb1", "one_chunk", "sub32"])
def test_the_kernel_equals_the_scan_and_the_recurrence(seed, b, T, H, hb,
                                                       chunk, sub):
    inputs = kda_inputs(seed, b, T, H)
    assert float(jnp.abs(inputs[-1]).max()) > 1     # a state comes in
    o, S = kda._kernel_scan(*inputs, chunk, F32, sub, hb)
    o_xla, S_xla = xla_scan(*inputs, chunk, F32, sub)
    o_want, S_want = recurrence(*inputs)
    # float32 sums in another order; outputs are O(0.3), states O(1): the
    # existing test's tightness against the recurrence, twice it between
    # the two cores (each is that far from it)
    np.testing.assert_allclose(o, o_want, atol=5e-6)
    np.testing.assert_allclose(S, S_want, atol=5e-6)
    np.testing.assert_allclose(o, o_xla, atol=1e-5)
    np.testing.assert_allclose(S, S_xla, atol=1e-5)


@pytest.mark.parametrize("dtype,atol", [(F32, 1e-6), (BF16, 3e-3)])
def test_gates_at_the_lower_bound_stay_finite_in_the_kernel(dtype, atol):
    inputs = kda_inputs(7, 1, 128, at_bound=True)
    o, S = kda._kernel_scan(*inputs, 64, dtype, 16, 3)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    o_want, S_want = recurrence(*inputs)
    np.testing.assert_allclose(o, o_want, atol=atol)
    np.testing.assert_allclose(S, S_want, atol=atol)


def test_the_kernels_step_forms_no_exp_of_a_large_sum(monkeypatch):
    """What the kernel runs a head a chunk (``_chunk_step``), run here on
    arrays: every argument it hands ``exp`` is at most half a sub-block
    of steps at the bound."""
    from jax.experimental.pallas import tpu as pltpu

    seen = []
    real = jnp.exp

    def listening(x):
        seen.append(float(jnp.max(x)))
        return real(x)

    # the kernel's sublane rotation has no meaning outside a kernel
    monkeypatch.setattr(pltpu, "roll", lambda x, s, axis: jnp.roll(x, s, axis))
    monkeypatch.setattr(kda.jnp, "exp", listening)
    q, k, v, g, beta, S = kda_inputs(8, 1, 64, at_bound=True)
    o, S1 = kda._chunk_step(q[0, :, 0], k[0, :, 0], v[0, :, 0], g[0, :, 0],
                            beta[0, :, :1], S[0, 0], 16, F32)
    monkeypatch.undo()
    assert seen and max(seen) <= 16 // 2 * 5.0
    o_want, S_want = kda.kda_recurrence(q[:, :, :1], k[:, :, :1], v[:, :, :1],
                                        g[:, :, :1], beta[:, :, :1], S[:, :1])
    np.testing.assert_allclose(o, o_want[0, :, 0], atol=1e-6)
    np.testing.assert_allclose(S1, S_want[0, 0], atol=1e-6)


def test_repeated_keys_do_not_cancel_in_the_kernels_solve():
    """The same key at every token with ``b = 1`` and no decay: ``A`` is
    all ones below the diagonal; forward substitution is exact."""
    q, k, v, g, beta, S = kda_inputs(9, 1, 128)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    args = (q, k, v, jnp.zeros_like(g), jnp.ones_like(beta), S)
    o, S1 = kda._kernel_scan(*args, 64, F32, 16, 3)
    o_want, S_want = recurrence(*args)
    np.testing.assert_allclose(o, o_want, atol=5e-6)
    np.testing.assert_allclose(S1, S_want, atol=5e-6)


# -- padding ------------------------------------------------------------------

def _padded_after(inputs, lengths):
    """``g = 0`` and ``b = 0`` past each row's length, as the encoder
    hands a padding lane over; ``q``, ``k``, ``v`` stay what they were."""
    q, k, v, g, beta, S = inputs
    valid = jnp.arange(q.shape[1])[None, :] < jnp.asarray(lengths)[:, None]
    return (q, k, v, jnp.where(valid[..., None, None], g, 0.0),
            jnp.where(valid[..., None], beta, 0.0), S)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_a_padded_tail_and_a_padded_row_leave_the_state_bit_equal(dtype):
    """Row 0 is valid for one chunk of two, row 1 for 40 tokens of its
    first chunk, row 2 not at all: the state after the padded lanes is,
    bit for bit, the state after the valid ones, whatever the padded
    lanes hold; a wholly padded row's state is the one that came in."""
    inputs = _padded_after(kda_inputs(3, 3, 128, H=2), [64, 40, 0])
    q, k, v, g, beta, S = inputs
    _, S_all = kda._kernel_scan(*inputs, 64, dtype, 16, 2)
    _, S_first = kda._kernel_scan(q[:, :64], k[:, :64], v[:, :64], g[:, :64],
                                  beta[:, :64], S, 64, dtype, 16, 2)
    np.testing.assert_array_equal(S_all, S_first)
    np.testing.assert_array_equal(S_all[2], S[2])
    noise = kda_inputs(4, 3, 128, H=2)
    valid = (jnp.arange(128)[None, :] < jnp.array([64, 40, 0])[:, None])[
        ..., None, None]
    _, S_noise = kda._kernel_scan(
        *(jnp.where(valid, a, n) for a, n in zip((q, k, v), noise)),
        g, beta, S, 64, dtype, 16, 2)
    np.testing.assert_array_equal(S_noise, S_all)


# -- through kda_scan, the state handed from program to program ---------------

@pytest.mark.parametrize("programs", [2, 3, 5])
def test_a_document_across_programs_equals_one_program(monkeypatch, programs):
    _the_rule_says_kernel(monkeypatch, 2)
    T = 64 * programs
    q, k, v, g, beta, S = kda_inputs(programs, 2, T, H=4)
    calls = []
    real = kda._kernel_scan
    monkeypatch.setattr(kda, "_kernel_scan",
                        lambda *a: calls.append(a[-1]) or real(*a))
    o_one, S_one = kda.kda_scan(q, k, v, g, beta, S, 64, F32)
    outs, state = [], S
    for lo in range(0, T, 64):
        at = slice(lo, lo + 64)
        o, state = kda.kda_scan(q[:, at], k[:, at], v[:, at], g[:, at],
                                beta[:, at], state, 64, F32)
        outs.append(o)
    assert calls == [2] * (programs + 1)       # every call took the kernel
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), o_one, atol=1e-6)
    np.testing.assert_allclose(state, S_one, atol=1e-6)
    o_want, S_want = recurrence(q, k, v, g, beta, S)
    np.testing.assert_allclose(o_one, o_want, atol=5e-6)
    np.testing.assert_allclose(S_one, S_want, atol=5e-6)


def test_off_the_tpu_kda_scan_is_the_xla_scan(monkeypatch):
    """No patch: here the rule says XLA, and the kernel is not built."""
    monkeypatch.setattr(kda, "_kernel_scan", None)
    inputs = kda_inputs(5, 1, 100)
    o, S = kda.kda_scan(*inputs, mxu_dtype=F32)
    o_xla, S_xla = kda._xla_scan(*inputs, 64, F32, 16)   # eager, as o
    np.testing.assert_array_equal(o, o_xla)
    np.testing.assert_array_equal(S, S_xla)


# -- the rule ------------------------------------------------------------------

PUBLISHED = (32, 128, 128)  # heads, dk, dv


# the cell's 38 programs a call are all 512 tokens, 32 heads of 128 | 128,
# chunks of 64 in sub-blocks of 16, bfloat16: whatever their rows
@pytest.mark.parametrize("backend,dtype,T,sizes,chunk,sub,kernel", [
    ("tpu", BF16, 512, PUBLISHED, 64, 16, True),
    ("tpu", BF16, 64, PUBLISHED, 64, 16, True),      # one chunk
    ("cpu", BF16, 512, PUBLISHED, 64, 16, False),
    ("tpu", F32, 512, PUBLISHED, 64, 16, False),     # the parity tests' type
    ("tpu", BF16, 500, PUBLISHED, 64, 16, False),    # the scan pads, not it
    ("tpu", BF16, 512, (32, 64, 128), 64, 16, False),   # keys under a lane
    ("tpu", BF16, 512, (32, 128, 64), 64, 16, False),   # values under a lane
    ("tpu", BF16, 512, (3, 16, 8), 64, 16, False),   # the tiny preset
    ("tpu", BF16, 512, PUBLISHED, 64, 8, False),     # half a bfloat16 tile
    ("tpu", BF16, 512, (12, 128, 128), 64, 16, True),   # other heads
], ids=["cell", "one_chunk", "cpu", "float32", "ragged_T", "small_dk",
        "small_dv", "tiny", "sub_8", "heads_12"])
def test_the_rule_reads_observables_alone(backend, dtype, T, sizes, chunk,
                                          sub, kernel):
    assert kda.core_is_kernel(backend, dtype, T, *sizes, chunk, sub) is kernel


def test_the_kernels_tiles_are_a_function_of_the_shapes():
    """The most heads a turn of the head loop up to the sweep's that
    divide the heads, for sub-blocks of whole bfloat16 tiles, where a
    step's blocks fit the VMEM asked for: the cell's programs, at 16
    rows or 2, get the same tile (rows are a grid axis, not a shape of
    the blocks)."""
    heads = kda._kernel_tiles(32, 128, 128, 64, 16)
    assert heads == kda._TILE_HEADS and 32 % heads == 0
    assert kda._kernel_tiles(12, 128, 128, 64, 16) == max(
        n for n in range(1, kda._TILE_HEADS + 1) if 12 % n == 0)
    assert kda._kernel_tiles(1, 128, 128, 64, 16) == 1
    assert kda._kernel_tiles(32, 128, 128, 64, 8) is None   # half a tile
    assert kda._kernel_tiles(32, 128, 128, 64, 48) is None  # does not divide
    assert kda._kernel_tiles(32, 128, 128, 128, 32) == heads
    # the blocks of 32 heads are 18.9 MB; of 128 heads they do not fit
    assert kda._kernel_tiles(128, 128, 128, 64, 16) is None
    assert not kda.core_is_kernel("tpu", BF16, 512, 128, 128, 128, 64, 16)


def test_tiles_that_do_not_divide_are_refused():
    inputs = kda_inputs(1, 1, 64, H=3)
    with pytest.raises(ValueError, match="do not divide"):
        kda._kernel_scan(*inputs, 64, F32, 16, 2)
    with pytest.raises(ValueError, match="does not divide"):
        kda.kda_scan(*inputs, chunk=64, sub=24)
