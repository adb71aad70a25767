"""`ops/eva.py`: the joint core's Pallas kernel against its XLA twin and
against `tests/test_eva.py`'s token-by-token loop, and the rule between
the two cores. The kernel is interpreted here (the CPU); that Mosaic takes
it at the cell's shapes is `tests/test_pallas_tpu_compile_eva.py`'s to say.
`tests/test_eva.py`'s cases are the XLA core's.

Small sizes (a block of 32 slots in chunks of 4, 2 heads of 8, chunk
programs of 8, float32 so that the two cores differ by the order of their
sums alone), tiles that divide them: heads a step, summaries a key block
(a key block of the block cache is a chunk program's worth of slots).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.cell import load_driver
from code_intelligence_tpu.models import EvaByteConfig, EvaByteEncoder
from code_intelligence_tpu.ops import eva
from test_eva import C, D, H, LENGTHS, SCALE, W, loop_attention, seeded, \
    through_programs

BF16, F32 = jnp.bfloat16, jnp.float32
P = 128                  # positions the summaries are allocated for
T = 8
_REACH = eva._reach      # the sound program's, whatever a control patches


def _the_rule_says_kernel(monkeypatch, tiles):
    """The rule's answer steered from the test (it sees the CPU and
    float32 here), and tiles that divide the tiny shapes; the kernel
    itself asks the real backend and is interpreted."""
    monkeypatch.setattr(eva, "core_is_kernel", lambda *a: True)
    monkeypatch.setattr(eva, "_kernel_tiles", lambda *a: tiles)


def _caches(rows, S, seed):
    """A chunk's queries, keys and values and the four caches, every slot
    written: what a core must NOT read is as large as what it must."""
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(shape).astype(np.float32))
            for shape in ((rows, T, H, D),) * 3 + ((rows, H, W, D),) * 2
            + ((rows, H, S, D),) * 2]


def _both_cores(arrays, pos, tiles, dtype=F32):
    """``(out, k_block, v_block, met)`` of the kernel and of its twin:
    ``eva_cached`` as the CPU runs it (the chunk written by XLA, then
    ``_xla_core``)."""
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: eva.eva_cached(
            *a, SCALE, W, C, mxu_dtype=dtype, key_block=16))(
                *arrays, jnp.int32(pos))
        got = jax.jit(lambda *a: eva._kernel_core(
            *a, SCALE, W, C, dtype, tiles))(*arrays, jnp.int32(pos))
    return got, want


def _same_caches_and_counts(got, want):
    """The block caches to the bit (the chunk written, every other slot
    as it was) and the counts to the integer."""
    for a, b in zip(got[1:3] + tuple(got[3]), want[1:3] + tuple(want[3])):
        np.testing.assert_array_equal(a, b)


@pytest.fixture
def control(request):
    """``eva._reach`` as one of the benchmark's placement controls has
    it (the driver's own patch, for the length of the test)."""
    on = dict.fromkeys(("window", "summaries", "norm_weight", "rope"))
    on.update(request.param)
    with load_driver("bulk_eva")._program_as(
            on, EvaByteEncoder(EvaByteConfig())):
        yield on


# -- one arithmetic, two cores -------------------------------------------------

# the chunk at ``pos``: inside the first block (no summary yet), the last
# program of a block and the first of the next (a block's edge), several
# blocks in (24 summaries of 32 visible, the own block a quarter full)
@pytest.mark.parametrize("pos", [8, 24, 32, 104],
                         ids=["first_block", "blocks_end", "blocks_start",
                              "blocks_in"])
@pytest.mark.parametrize("rows", [1, 2])
# the summary cache of several key blocks, and smaller than one (the one
# block is the whole cache); one head a step and two
@pytest.mark.parametrize("tiles", [(2, 8), (1, 32), (2, 16)],
                         ids=["sums_4_blocks", "sums_1_block",
                              "sums_2_blocks"])
def test_the_kernel_equals_the_xla_core(pos, rows, tiles):
    got, want = _both_cores(_caches(rows, P // C, pos + rows), pos, tiles)
    # float32 sums in another order (the summaries go several passed
    # blocks a key block): the XLA core's tightness against the loop
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    own, passed = (np.asarray(m) for m in got[3])
    np.testing.assert_array_equal(own, pos % W + np.arange(T) + 1)
    np.testing.assert_array_equal(passed, np.full(T, pos // W * (W // C)))
    _same_caches_and_counts(got, want)


@pytest.mark.parametrize("chunk,tiles", [
    (8, (2, 8)), (16, (1, 16)), (16, (2, 32))])
def test_the_kernel_through_chunk_programs_agrees_with_the_loop(
        monkeypatch, chunk, tiles):
    """`tests/test_eva.py`'s documents (rows that end early, a partial
    last chunk) through ``eva_cached`` with its core on the kernel:
    every program of four blocks, each reading the block cache the
    programs before it wrote."""
    _the_rule_says_kernel(monkeypatch, tiles)
    q, k, v, phi, mu = seeded()
    got, _, met = through_programs(q, k, v, phi, mu, LENGTHS, chunk)
    for r, n in enumerate(LENGTHS):
        out, keys_met, _, _ = loop_attention(q[r], k[r], v[r], phi, mu, n)
        np.testing.assert_allclose(got[r, :n], out, atol=1e-5)
        np.testing.assert_array_equal(met[:n], keys_met)


def test_bfloat16_operands_stay_near_the_xla_core():
    """The two products on bfloat16 operands, as the cell runs them: both
    cores round the same operands, the sums differ in order."""
    arrays = _caches(2, P // C, 5)
    arrays[3:] = [a.astype(BF16) for a in arrays[3:]]
    got, want = _both_cores(arrays, 104, (2, 16), dtype=BF16)
    assert got[0].dtype == F32 and got[1].dtype == got[2].dtype == BF16
    np.testing.assert_allclose(got[0], want[0], atol=2e-2)
    _same_caches_and_counts(got, want)


# -- what is visible comes from `_reach` ----------------------------------------

@pytest.mark.parametrize("control,pos,own,passed", [
    # a stale slot is admitted once the block cache has wrapped: every
    # query meets all 32 slots
    ({"window": "sliding"}, 40, np.full(T, W), np.full(T, 8)),
    ({"window": "sliding"}, 8, 8 + np.arange(T) + 1, np.zeros(T)),
    # the summaries of the chunk's own block's chunks before it: 10 and
    # 26 of them, inside a key block of 8 or 16 (a masked block)
    ({"summaries": "early"}, 40, 8 + np.arange(T) + 1, np.full(T, 10)),
    ({"summaries": "early"}, 104, 8 + np.arange(T) + 1, np.full(T, 26)),
], indirect=["control"], ids=["stale", "not_yet_stale", "early", "early_in"])
@pytest.mark.parametrize("tiles", [(2, 8), (1, 16)])
def test_the_benchmarks_placement_controls_steer_the_kernel(
        control, pos, own, passed, tiles):
    """``window=sliding`` and ``summaries=early`` patch ``eva._reach``:
    the kernel's masks follow, as the XLA core's do, and the counts say
    what was admitted."""
    arrays = _caches(2, P // C, pos)
    got, want = _both_cores(arrays, pos, tiles)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    np.testing.assert_array_equal(got[3][0], own)
    np.testing.assert_array_equal(got[3][1], passed)
    _same_caches_and_counts(got, want)
    # and where they admit other keys they are another program's numbers
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(eva, "_reach", _REACH)
        sound, _ = _both_cores(arrays, pos, tiles)
    if (np.asarray(own) + np.asarray(passed)
            != pos % W + np.arange(T) + 1 + pos // W * (W // C)).any():
        assert float(jnp.abs(sound[0] - got[0]).max()) > 1e-3


def test_a_block_past_the_visible_ones_is_not_read():
    """The chunk at 40: slots 0..7 of the block cache, the chunk itself
    and summaries 0..7 are visible. Everything else is NaN, the stale
    slots the chunk is written over among it: the key blocks that hold
    it alone are neither fetched (the index maps hand a visible block
    again) nor computed, and what a visible block holds past the mask
    weighs 0."""
    pos, tiles = 40, (2, 8)
    arrays = _caches(2, P // C, 3)
    clean, _ = _both_cores(arrays, pos, tiles)
    for i in (3, 4):        # the block cache: keys (masked scores), values
        arrays[i] = arrays[i].at[:, :, 8:].set(jnp.nan)
    for i in (5, 6):        # the summary cache
        arrays[i] = arrays[i].at[:, :, 8:].set(jnp.nan)
    got, _ = _both_cores(arrays, pos, tiles)
    assert bool(jnp.isfinite(got[0]).all())
    np.testing.assert_array_equal(got[0], clean[0])
    # the chunk's slots are written, the rest of the cache is as it came
    for new, cache, came in ((arrays[1], got[1], arrays[3]),
                             (arrays[2], got[2], arrays[4])):
        np.testing.assert_array_equal(cache[:, :, 8:16], new.swapaxes(1, 2))
        np.testing.assert_array_equal(cache[:, :, :8], came[:, :, :8])
        assert bool(jnp.isnan(cache[:, :, 16:]).all())


def test_the_index_maps_hand_a_visible_block_again():
    """Read off the ``BlockSpec`` s: with 4 key blocks of the block cache
    (8 slots each) and 4 of summaries, the chunk at 40 (its own block is
    1, read from what the step writes and never fetched) is handed block
    0 throughout and writes block 1; the chunk at 120 is handed 0, 1, 2
    and then 2 again, and writes block 3; summaries block 0 until the
    second is visible; a first-block chunk is handed summaries block 0
    alone."""
    from jax.experimental import pallas as pl

    maps = []
    real = pl.BlockSpec

    def recording(shape, index_map):
        maps.append(index_map)
        return real(shape, index_map)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pl, "BlockSpec", recording)
        # three rows: a signature no other test has traced (the kernel's
        # call is jitted, and a trace that is cached makes no ``BlockSpec``)
        jax.eval_shape(lambda *a: eva._kernel_core(
            *a, SCALE, W, C, F32, (2, 8)), *_caches(3, P // C, 0),
            jnp.int32(0))
    # q, k, v | block cache x 2 | summaries x 2 | out, met, written x 2
    own_map, sum_map, written_map = maps[3], maps[5], maps[9]

    def walked(index_map, pos):
        reach = np.asarray([pos, *(int(x) for x in eva._reach(
            pos, T, W, W, C))], np.int32)
        return [int(index_map(0, 0, j, reach)[2]) for j in range(8)]

    assert walked(own_map, 40) == [0] * 8
    assert walked(written_map, 40) == [1] * 8
    assert walked(sum_map, 40) == [0] * 8
    assert walked(own_map, 120) == [0, 1, 2, 2, 2, 2, 2, 2]
    assert walked(written_map, 120) == [3] * 8
    assert walked(sum_map, 120) == [0, 0, 0, 0, 0, 1, 2, 2]
    assert walked(sum_map, 8) == [0] * 8


# -- which core: the rule ------------------------------------------------------

@pytest.mark.parametrize("backend,dtype,chunk,slots,sums,d,kernel", [
    ("tpu", BF16, 512, 2048, 2048, 128, True),    # the cell's long groups
    ("tpu", BF16, 512, 2048, 512, 128, True),     # 8,192 positions
    ("tpu", BF16, 512, 2048, 128, 128, True),     # 2,048: one block, four
    ("tpu", BF16, 512, 1024, 64, 128, True),      # programs; two programs
    ("tpu", BF16, 256, 2048, 2048, 128, True),    # chunk programs of 256
    ("cpu", BF16, 512, 2048, 2048, 128, False),   # the interpreter
    ("tpu", F32, 512, 2048, 2048, 128, False),    # the parity tests
    ("tpu", BF16, 512, 512, 32, 128, False),      # one program holds it
    ("tpu", BF16, 256, 256, 16, 128, False),
    ("tpu", BF16, 512, 2048, 2048, 96, False),    # a head short of the lanes
    ("tpu", BF16, 512, 2048, 2048, 64, False),
    ("tpu", BF16, 512, 2048, 2056, 128, False),   # no tile divides it
    ("tpu", BF16, 64, 2048, 2048, 128, False),    # queries short of the lanes
    ("tpu", BF16, 8, 32, 32, 128, False),
])
def test_the_rule(backend, dtype, chunk, slots, sums, d, kernel):
    assert eva.core_is_kernel(backend, dtype, chunk, slots, sums, d) is kernel


def test_the_tiles_divide_the_cells_shapes():
    for S in (2048, 1024, 512, 256, 128, 64):
        heads, summaries = eva._kernel_tiles(512, S, 32)
        assert 32 % heads == 0 and S % summaries == 0
        assert summaries % 128 == 0 or summaries == S
        assert summaries == min(S, eva._TILE_SUMMARIES)
    assert eva._kernel_tiles(512, 2048, 3)[0] == 3
    assert eva._kernel_tiles(512, 1536, 32)[1] in (384, 512, 768, 1536)
    assert eva._kernel_tiles(512, 2056, 32) is None


def test_no_option_selects_a_core():
    """``eva_cached`` takes no argument that names a core, and on the CPU
    its program holds no kernel."""
    import inspect

    assert not {"kernel", "core", "pallas", "use_kernel"} & set(
        inspect.signature(eva.eva_cached).parameters)
    arrays = [a.astype(BF16) for a in _caches(1, P // C, 0)]
    text = jax.jit(lambda *a: eva.eva_cached(
        *a, jnp.int32(8), SCALE, W, C)[0]).lower(*arrays).as_text()
    assert "eva_core" not in text and "pallas" not in text
