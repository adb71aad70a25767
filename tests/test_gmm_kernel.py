"""`ops/gmm.py`: the held experts' two grouped products as Pallas kernels,
the list of visits their grid follows, and the rule between them and
``lax.ragged_dot``. The kernels are interpreted here (the CPU); that Mosaic
takes them at the cells' widths is `tests/test_pallas_tpu_compile_latent.py`'s
to say.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from code_intelligence_tpu.ops import gmm, moe

BF16, F32 = jnp.bfloat16, jnp.float32
R, COUNT, E, F = 96, 6, 64, 32

# rows an expert, of R = 96 handed in
DRAWS = {
    "even": [16, 16, 16, 16, 16, 16],
    "skewed": [50, 3, 21, 9, 2, 11],
    "one_idle": [30, 20, 0, 25, 11, 10],
    "all_in_one": [0, 0, 96, 0, 0, 0],
    "a_group_of_one_row": [17, 1, 30, 1, 1, 46],
    "short": [7, 0, 22, 5, 0, 13],       # 47 of 96: garbage past the end
    "short_on_a_tile_edge": [16, 0, 0, 16, 0, 0],
    "empty": [0, 0, 0, 0, 0, 0],
}


def _operands(dtype, sizes):
    k = jax.random.split(jax.random.PRNGKey(46), 3)
    rows = jax.random.normal(k[0], (R, E), F32)
    # what lies past the last row routed here is never to be multiplied
    rows = jnp.where((jnp.arange(R) < sum(sizes))[:, None], rows, jnp.nan)
    w_in = jax.random.normal(k[1], (COUNT, E, 2 * F), F32) / 8
    w_out = jax.random.normal(k[2], (COUNT, F, E), F32) / 4
    return (rows.astype(dtype), w_in.astype(dtype), w_out.astype(dtype),
            jnp.asarray(sizes, jnp.int32))


@functools.partial(jax.jit, static_argnames="act")
def _parent(rows, w_in, w_out, sizes, act="silu"):
    """``routed_experts``' ``held`` as the parent commit wrote it."""
    dtype = w_in.dtype
    g, u = jnp.split(lax.ragged_dot(
        rows, w_in, sizes, preferred_element_type=dtype), 2, axis=-1)
    gated = moe._GATE_ACTS[act](g.astype(F32)) * u.astype(F32)
    return lax.ragged_dot(gated.astype(dtype), w_out, sizes,
                          preferred_element_type=F32)


def _plain(rows, w_in, w_out, sizes, act):
    """A loop over the experts, a ``jnp.dot`` each, at the module
    docstring's rounding points: ``(gated (n, F), out (n, E))`` for the
    ``n = sum(sizes)`` rows routed here."""
    dtype = w_in.dtype
    gated, out, at = [], [], 0
    for j, n in enumerate(int(n) for n in sizes):
        x = rows[at:at + n]
        g, u = jnp.split(jnp.dot(x, w_in[j], preferred_element_type=F32)
                         .astype(dtype).astype(F32), 2, axis=-1)
        gated.append((moe._GATE_ACTS[act](g) * u).astype(dtype))
        out.append(jnp.dot(gated[-1], w_out[j], preferred_element_type=F32))
        at += n
    return jnp.concatenate(gated), jnp.concatenate(out)


# -- one arithmetic, two cores -------------------------------------------------

@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("draw", list(DRAWS))
def test_the_kernels_equal_ragged_dot_and_a_plain_loop(draw, act):
    """Both products over every draw of group sizes, tiles of 16 rows
    (so groups straddle tile edges, share tiles, and end inside one) and
    two column blocks a product; the rows past ``sum(sizes)`` are NaN and
    reach no routed row's result."""
    operands = _operands(F32, DRAWS[draw])
    n = sum(DRAWS[draw])
    got = gmm._both_products(*operands, moe._GATE_ACTS[act],
                             (16, 16, 16, 32))
    assert got.shape == (R, E) and got.dtype == F32
    assert bool(jnp.isfinite(got[:n]).all())
    np.testing.assert_allclose(got[:n], _parent(*operands, act)[:n],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[:n], _plain(*operands, act)[1],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tiles", [(8, 8, 32, 64), (32, 32, 16, 16),
                                   (96, 96, 32, 64), (32, 8, 32, 64),
                                   (96, 16, 16, 32)],
                         ids=lambda t: "x".join(map(str, t)))
def test_a_tile_that_straddles_group_edges_is_visited_once_a_group(tiles):
    """Row tiles smaller than, larger than and as large as all the rows;
    with and without sub-blocks of a tile skipped where the group has no
    row: the same rows."""
    operands = _operands(F32, DRAWS["skewed"])
    got = gmm._both_products(*operands, jax.nn.silu, tiles)
    np.testing.assert_allclose(got, _plain(*operands, "silu")[1],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("draw,tm,want", [
    # (groups, tiles) of the visits, in order
    ("even", 16, [(g, g) for g in range(6)]),
    ("even", 32, [(0, 0), (1, 0), (2, 1), (3, 1), (4, 2), (5, 2)]),
    ("skewed", 32, [(0, 0), (0, 1), (1, 1), (2, 1), (2, 2), (3, 2), (4, 2),
                    (5, 2)]),
    ("one_idle", 48, [(0, 0), (1, 0), (1, 1), (3, 1), (4, 1), (5, 1)]),
    ("all_in_one", 32, [(2, 0), (2, 1), (2, 2)]),
    ("short", 16, [(0, 0), (2, 0), (2, 1), (3, 1), (3, 2), (5, 2)]),
    ("short_on_a_tile_edge", 16, [(0, 0), (3, 1)]),
    ("empty", 16, []),
])
def test_the_grid_follows_the_group_sizes(draw, tm, want):
    """A group's visits are the row tiles it has rows in: a group without
    rows has none (its weights are never asked for), a tile past
    ``sum(sizes)`` has none (its rows are never multiplied), a tile two
    groups share is visited by each, one after the other."""
    sizes = jnp.asarray(DRAWS[draw], jnp.int32)
    offsets, groups, tiles, n = jax.jit(
        lambda s: gmm._visits(s, R, tm))(sizes)
    assert groups.shape == tiles.shape == (R // tm + COUNT - 1,)
    np.testing.assert_array_equal(
        offsets, np.concatenate([[0], np.cumsum(DRAWS[draw])]))
    assert list(zip(np.asarray(groups)[:int(n)].tolist(),
                    np.asarray(tiles)[:int(n)].tolist())) == want
    # what lies past the last visit still names a block that exists
    assert 0 <= int(groups.min()) and int(groups.max()) < COUNT
    assert 0 <= int(tiles.min()) and int(tiles.max()) < R // tm


@pytest.mark.parametrize("act", ["silu", "relu"])
def test_the_rounding_points_are_the_parents(act):
    """bfloat16 weights: ``g`` and ``u`` rounded to bfloat16, the
    activation and the product in float32, their result rounded to
    bfloat16: bit for bit the plain loop's ``(n, F)`` array, and its
    float32 second product but for the order of a float32 sum; rounded
    at any other point it is another array."""
    rows, w_in, w_out, sizes = _operands(BF16, DRAWS["skewed"])
    visits = gmm._visits(sizes, R, 16)
    gated = gmm._grouped(rows, w_in, visits, 16, 16, 16, BF16,
                         moe._GATE_ACTS[act])
    assert gated.shape == (R, F) and gated.dtype == BF16
    want_gated, want = _plain(rows, w_in, w_out, sizes, act)
    np.testing.assert_array_equal(gated.astype(F32), want_gated.astype(F32))
    got = gmm._grouped(gated, w_out, visits, 16, 16, 32, F32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the same product with g and u left in float32
    g, u = jnp.split(jnp.einsum("re,ef->rf", rows[:50], w_in[0],
                                preferred_element_type=F32), 2, axis=-1)
    unrounded = (moe._GATE_ACTS[act](g) * u).astype(BF16)
    assert bool((unrounded != want_gated[:50]).any())
    np.testing.assert_allclose(
        gmm._both_products(rows, w_in, w_out, sizes, moe._GATE_ACTS[act],
                           (16, 16, 16, 32)),
        _parent(rows, w_in, w_out, sizes, act), rtol=2e-2, atol=2e-2)


# -- which core, and which tiles: the rule ---------------------------------------

# (R, count, E, F) a call of the held experts is handed in the five expert
# cells (``6 N`` rows in SmallThinker's one pass, ``N`` a round of a
# share; 16 rows of 512 tokens and what the long group narrows to), and
# the tiles it gets: (rows a tile, rows a product takes of it, columns of
# F a step of the first product, columns of E a step of the second)
CELLS = {
    "smallthinker": ((49152, 64, 2560, 768), (512, 128, 768, 2560)),
    "smallthinker_8_rows": ((24576, 64, 2560, 768), (512, 128, 768, 2560)),
    "smallthinker_2_rows": ((6144, 64, 2560, 768), (512, 128, 768, 2560)),
    "ling": ((8192, 128, 2560, 768), (512, 128, 768, 2560)),
    "ling_2_rows": ((1024, 128, 2560, 768), (512, 128, 768, 2560)),
    "deepseek": ((8192, 16, 7168, 2048), (512, 128, 512, 3584)),
    "deepseek_2_rows": ((1024, 16, 7168, 2048), (512, 128, 512, 3584)),
    "trinity": ((8192, 32, 3072, 3072), (512, 128, 1024, 1536)),
    "longcat": ((8192, 16, 6144, 2048), (512, 128, 512, 3072)),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_cells_widths_get_the_kernels_and_these_tiles(cell):
    """One rule for thin groups (82 rows an expert in DeepSeek's round,
    8 in Ling's narrow one) and fat (768 in SmallThinker's pass): tiles
    of 512 rows taken 128 at a time, a whole expert a step where its
    weights fit 16 MB (SmallThinker, Ling), the widest column block that
    does elsewhere; what a step holds twice fits the VMEM asked for."""
    (R, count, E, F), tiles = CELLS[cell]
    assert gmm.gmm_is_kernel("tpu", BF16, R, count, E, F)
    assert gmm._kernel_tiles(R, count, E, F) == tiles
    tm, sub, tn_in, tn_out = tiles
    assert R % tm == 0 and tm % sub == 0 and F % tn_in == 0 \
        and E % tn_out == 0
    first = 2 * E * tn_in * 2, tm * E * 2 + tm * tn_in * 2
    second = F * tn_out * 2, tm * F * 2 + tm * tn_out * 4
    for weights, rows_and_out in (first, second):
        assert weights <= gmm._WEIGHT_BLOCK_BYTES
        assert 2 * (weights + rows_and_out) < gmm._KERNEL_VMEM_LIMIT * 3 // 4
    # off the TPU and in float32 the same call stays lax.ragged_dot's
    assert not gmm.gmm_is_kernel("cpu", BF16, R, count, E, F)
    assert not gmm.gmm_is_kernel("tpu", F32, R, count, E, F)


@pytest.mark.parametrize("R,count,E,F,tiles", [
    (768, 64, 2560, 768, (256, 128, 768, 2560)),    # 2 rows of a 64 bucket
    (384, 64, 2560, 768, (128, 128, 768, 2560)),
    (192, 64, 2560, 768, None),                     # no tile of 128 rows
    (8192, 16, 7168, 2000, None),                   # F under a lane's 128
    (8192, 16, 7100, 2048, None),
    (8192, 4, 16384, 128, (512, 128, 128, 16384)),
    (8192, 4, 32768 + 128, 128, None),   # one lane of [g | u] past 16 MB
], ids=lambda v: str(v).replace(" ", ""))
def test_the_rule_keeps_ragged_dot_where_no_tile_exists(R, count, E, F,
                                                        tiles):
    assert gmm.gmm_is_kernel("tpu", BF16, R, count, E, F) == (
        tiles is not None)
    if E % 128 == 0 and F % 128 == 0:
        assert gmm._kernel_tiles(R, count, E, F) == tiles


def test_experts_on_kernel_is_the_rule_at_the_rows_a_call_is_handed(
        monkeypatch):
    """``6 N`` rows where every expert is held (one pass), ``N`` a round
    of a share: what ``routed_experts`` asks and what an encoder counts
    are one answer."""
    seen = []
    monkeypatch.setattr(gmm, "gmm_is_kernel",
                        lambda *a: seen.append(a) or True)
    w_in = jax.ShapeDtypeStruct((64, 2560, 1536), BF16)
    assert moe.experts_on_kernel(1024, 6, w_in, 64)
    assert moe.experts_on_kernel(1024, 6, w_in, 256)
    assert [a[1:] for a in seen] == [(BF16, 6144, 64, 2560, 768),
                                     (BF16, 1024, 64, 2560, 768)]
