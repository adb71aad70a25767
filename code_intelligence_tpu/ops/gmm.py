"""The held experts' two grouped matmuls as Pallas TPU kernels.

``rows (R, E)`` lie sorted by expert, ``sizes[g]`` rows for expert ``g``
(``ops/moe.py::routed_experts`` made them so); the first product is
``rows @ W_in[g]`` with ``W_in = [gate | up]`` and the gate's activation
in its epilogue, the second ``(act(g) * u) @ W_out[g]``:

    gated_experts(rows, W_in, W_out, sizes, act)  ->  (R, E) float32

**The grid follows the group sizes.** A step is a VISIT: one group's
rows inside one tile of ``tm`` rows. ``_visits`` lists them from the
running sum of ``sizes`` (scalar prefetch: the lists steer every block's
DMA): a tile that straddles two groups is visited once for each, the
other group's rows masked at the store (the tile stays in VMEM between
the two, so the earlier rows are still there); **a group without rows
has no visit and its weights no DMA; a tile past ``sum(sizes)`` has
none either**, so what such rows hold is never multiplied and what
comes back there is whatever the buffer held. Inside a visit the
product is taken ``sub`` rows at a time, and only the sub-blocks in
which the group has a row: a thin group costs one sub-block's products
wherever it lies in its tile, a fat one its own rows' and at most two
part-used sub-blocks'. The grid is (column blocks, visits), visits
inner: consecutive visits of one group ask for the same weight block,
which Pallas then leaves where it is, so **every held expert's weights
cross HBM once a product**, a column block at a time (a whole expert
where it fits), and the rows' tile once a column block.

**The rounding points are ``lax.ragged_dot``'s as ``routed_experts``
called it**: ``g`` and ``u`` accumulate in float32 and are rounded to
the weights' type, the activation and the product are float32, their
result is rounded to the weights' type (and is all that is written: no
``(R, 2 F)`` array and no pass over it), the second product is float32.

Which of the two runs, this or ``lax.ragged_dot``, is ``gmm_is_kernel``'s
to say, from what the call can observe; the tiles are ``_kernel_tiles``'.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the weight blocks of a step, one buffer of the pipeline's two: a whole
# expert of SmallThinker's and Ling's (7.9 | 3.9 MB), a quarter or a half
# of DeepSeek's, Trinity's and LongCat's (58.7 | 29.4, 37.7 | 18.9, 50.3 |
# 25.2 MB an expert)
_WEIGHT_BLOCK_BYTES = 16 * 1024 * 1024

# two buffers of the weight blocks, of the rows' tile and of the output's,
# and the float32 products before the store: 50 MB at the widest (LongCat's
# second product); Mosaic's default scoped limit is 16 MiB of the v5e's 128
_KERNEL_VMEM_LIMIT = 96 * 1024 * 1024

# rows a tile has at most, and rows of it a product takes at a time: a
# visit multiplies only the sub-blocks of its tile in which its group has
# a row. Two sweeps on the chip (v5e, standalone, both products, bfloat16,
# ms a call with twenty in flight, ``lax.ragged_dot`` and the activation
# pass beside it; PERF.md section 6, PR 46). Tiles of 128 / 256 / 512
# rows taken whole, then 256 and 512 by sub-blocks of 128, 512 by 256:
#   SmallThinker's 64 experts, 49,152 rows (768 a group)  XLA 7.53:
#     4.45 / 4.50 / 5.30, 4.26 / 4.25, 4.57
#   the same, 6,144 rows (96 a group)                     XLA 3.14:
#     1.57 / 1.58 / 2.44, 1.40 / 1.32, 1.56
#   DeepSeek's 16 experts, 1,312 rows of 8,192 (82)       XLA 5.37:
#     2.63 / 2.66 / 4.31, 2.35 / 2.25, 2.68
#   Trinity's 32, 4,096 of 8,192 (128)                    XLA 7.19:
#     3.75 / 3.66 / 5.64, 3.26 / 3.08, 3.71
# (LongCat's, Ling's two rounds and its 1,024-row ones order the same.)
# A group that straddles a tile's edge costs a whole tile's products
# again where the tile is taken whole, so large tiles lose; by sub-blocks
# the large tile costs what the small one does and halves the steps: one
# rule for thin groups and fat. Then, at 512 by 128: the sub-blocks as a
# loop or unrolled into branches 4.26 / 4.24 and 1.32 / 1.32 (the loop
# compiles in a quarter of the time: 0.8-1.9 s a pair against 4.2-8.3);
# sub-blocks of 64 rows 4.20 and 1.26, tiles of 1,024 rows 4.33 and 1.27:
# within 5 %, not taken. The weight blocks at half of
# ``_WEIGHT_BLOCK_BYTES`` 2.35 for 2.22 (DeepSeek) and 3.19 for 3.06
# (Trinity), at twice 2.21-2.22 (DeepSeek): level from 16 MB on
_TILE_ROWS, _SUB_ROWS = 512, 128


def _columns(width: int, depth: int, blocks: int):
    """The widest column block, a multiple of the lanes' 128 that divides
    ``width``, whose ``blocks`` bfloat16 blocks of ``depth`` rows fit
    ``_WEIGHT_BLOCK_BYTES``; ``None`` where none does."""
    fit = [tn for tn in range(128, width + 1, 128) if width % tn == 0
           and blocks * depth * tn * 2 <= _WEIGHT_BLOCK_BYTES]
    return max(fit) if fit else None


def _kernel_tiles(R: int, count: int, E: int,
                  F: int) -> Optional[Tuple[int, int, int, int]]:
    """``(tm, sub, tn_in, tn_out)``: rows a tile and rows a product takes
    of it at a time, columns of ``F`` a step of the first product (of the
    gate half and of the up half each) and of ``E`` a step of the second;
    ``None`` where no tile exists. A function of the static shapes alone:
    the most rows up to ``_TILE_ROWS``, halved down to ``_SUB_ROWS``, that
    divide ``R``, and the widest column blocks whose weights fit
    ``_WEIGHT_BLOCK_BYTES``."""
    tm = next((tm for tm in (_TILE_ROWS, _TILE_ROWS // 2, _SUB_ROWS)
               if R % tm == 0), None)
    tn_in, tn_out = _columns(F, E, 2), _columns(E, F, 1)
    if not count or tm is None or tn_in is None or tn_out is None:
        return None
    return tm, _SUB_ROWS, tn_in, tn_out


def gmm_is_kernel(backend: str, dtype, R: int, count: int, E: int,
                  F: int) -> bool:
    """Pallas kernels or ``lax.ragged_dot``, for ONE call of
    ``routed_experts``' held experts over ``R`` rows: the rule, from what
    the call can observe and nothing a user sets.

    The kernels run on the TPU (off it they are the interpreter, a test
    device); for bfloat16 weights (float32 is the parity tests'); for
    widths that fill the lanes' 128; and where ``_kernel_tiles`` has a
    tile."""
    return (backend == "tpu" and jnp.dtype(dtype) == jnp.bfloat16
            and E % 128 == 0 and F % 128 == 0
            and _kernel_tiles(R, count, E, F) is not None)


def _visits(sizes: jnp.ndarray, R: int, tm: int):
    """The grid's second axis: ``(offsets (count + 1,), groups (V,),
    tiles (V,), n ())``, ``V = R / tm + count - 1`` the most visits there
    can be and ``n`` how many there are. Visit ``v < n`` is group
    ``groups[v]``'s rows ``[offsets[g], offsets[g + 1])`` inside row
    tile ``tiles[v]``; groups in order and a group's tiles in order, so a
    tile's visits are consecutive."""
    count = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    tiles_of = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles_of)
    v = jnp.arange(R // tm + count - 1, dtype=jnp.int32)
    groups = jnp.minimum(
        jnp.sum(visit_ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        count - 1)
    tiles = jnp.take(first, groups) + v - jnp.take(visit_ends - tiles_of,
                                                   groups)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, groups, jnp.clip(tiles, 0, R // tm - 1), visit_ends[-1]


def _grouped(rows, w, visits, tm: int, sub: int, tn: int, out_dtype,
             act: Optional[Callable] = None):
    """One grouped product over the visits: ``rows (R, K)`` against
    ``w (count, K, n)``, ``tn`` columns a step, a tile of ``tm`` rows
    ``sub`` at a time. With ``act``, ``w`` is ``[gate | up]`` and the
    result ``act(g) * u`` ``(R, n / 2)`` at the module docstring's
    rounding points; without, the float32 product ``(R, n)``."""
    R, K = rows.shape
    width = w.shape[2] // 2 if act else w.shape[2]
    dtype = w.dtype
    f32 = jnp.float32
    offsets, groups, tiles, n = visits

    def kernel(offsets_ref, groups_ref, tiles_ref, x_ref, *refs):
        o_ref = refs[-1]
        v = pl.program_id(1)
        g = groups_ref[v]
        # the group's rows, counted from the tile's first
        lo = offsets_ref[g] - tiles_ref[v] * tm
        hi = offsets_ref[g + 1] - tiles_ref[v] * tm

        def part(s, carry):
            rows = pl.ds(pl.multiple_of(s * sub, sub), sub)
            at = s * sub + lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
            x = x_ref[rows, :]
            if act:
                gate, up = (jnp.dot(x, ref[...], preferred_element_type=f32)
                            .astype(dtype).astype(f32) for ref in refs[:2])
                y = (act(gate) * up).astype(dtype).astype(f32)
            else:
                y = jnp.dot(x, refs[0][...], preferred_element_type=f32)
            # the other groups' rows of the tile stay as they are
            o_ref[rows, :] = jnp.where(
                (at >= lo) & (at < hi), y,
                o_ref[rows, :].astype(f32)).astype(o_ref.dtype)
            return carry

        # the sub-blocks of the tile in which the group has a row
        lax.fori_loop(jnp.maximum(lo, 0) // sub,
                      (jnp.minimum(hi, tm) + sub - 1) // sub, part, None)

    def columns(first):
        return pl.BlockSpec(
            (None, K, tn), lambda j, v, offsets, groups, tiles:
            (groups[v], 0, first + j))

    weights = [columns(0), columns(width // tn)] if act else [columns(0)]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((R, width), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(width // tn, n),
            in_specs=[pl.BlockSpec(
                (tm, K), lambda j, v, offsets, groups, tiles:
                (tiles[v], 0))] + weights,
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, offsets, groups, tiles:
                (tiles[v], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_LIMIT),
        interpret=jax.default_backend() != "tpu",
        name="gated_gmm" if act else "gmm",
    )(offsets, groups, tiles, rows, *([w, w] if act else [w]))


def gated_experts(rows: jnp.ndarray, w_in: jnp.ndarray, w_out: jnp.ndarray,
                  sizes: jnp.ndarray, act: Callable) -> jnp.ndarray:
    """``(R, E)`` float32, not weighed: the held experts over the sorted
    ``rows (R, E)``, ``sizes`` ``(count,)`` rows an expert, ``w_in``
    ``(count, E, 2 F)`` ``[gate | up]``, ``w_out`` ``(count, F, E)``,
    ``act`` the gate's activation, at ``_kernel_tiles``' tiles. Rows past
    ``sum(sizes)`` come back as the buffer held them."""
    R, E = rows.shape
    count, F = w_out.shape[:2]
    return _both_products(rows, w_in, w_out, sizes, act,
                          _kernel_tiles(R, count, E, F))


# jitted, so that the layers of a program that call it at one shape are
# traced and lowered once: a program's set-up grows by one kernel's
# lowering, not by one a layer
@functools.partial(jax.jit, static_argnames=("act", "tiles"))
def _both_products(rows, w_in, w_out, sizes, act, tiles):
    tm, sub, tn_in, tn_out = tiles
    visits = _visits(sizes, rows.shape[0], tm)
    gated = _grouped(rows.astype(w_in.dtype), w_in, visits, tm, sub, tn_in,
                     w_in.dtype, act)
    return _grouped(gated, w_out, visits, tm, sub, tn_out, jnp.float32)
