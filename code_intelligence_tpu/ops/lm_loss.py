"""The tied decoder's product, its cross-entropy and the accuracy as ONE
op with a backward of its own.

    decoder_cross_entropy(h, w, b, y)  ->  (ce float32, hit bool)

``h (..., E)`` is the encoder's dropped output and ``w (V, E)``,
``b (V,)`` (or ``None``) the decoder's leaves, all at the compute dtype;
``y (...)`` the next tokens. ``ce`` is ``logsumexp(l) - l[y]`` a row and
``hit`` is ``argmax(l) == y``, with ``l`` the logits **at the rounding
points the trainer always had**: the product accumulated in float32 and
rounded to the compute dtype, the bias added in the compute dtype, the
result read as float32. The backward forms ``d = (softmax(l) -
onehot(y)) * g`` in float32, rounds it to the compute dtype as autodiff
did, and takes ``dh = d @ w``, ``dw = d.T @ h`` and ``db = sum(d)`` from
it.

**Two cores, one arithmetic, chosen here** (``loss_is_kernel``, from
what the call can observe; no flag, no configuration key):

* ``_reference_core``: the einsum, ``optax``'s cross-entropy and the
  ``argmax`` as `training/loop.py` had them until PR 50, under autodiff.
  On the CPU, in float32 and on a mesh of more than one device this is
  the whole program, as it was. It holds the logits as an array (836 MB
  of bfloat16 at the flagship's 104 x 67 rows by 60,000) and reads them
  as float32 in three passes, forward and back.
* ``_kernel_core``: two Pallas kernels under a ``jax.custom_vjp``, which
  never hold a float32 array of the logits' shape. **Forward**
  (``lm_loss_fwd``): a grid over (row tile, vocabulary tile), vocabulary
  inner; a step takes a ``(rows, E) x (E, tile)`` product on the MXU
  and, in its epilogue in VMEM, folds the tile into the row's running
  maximum, sum of exponentials, logit at ``y`` and first argmax; it
  writes three ``(N,)`` vectors and, where a gradient will be taken,
  the bfloat16 logits, once (measured on the chip against taking the
  product again in the backward, which lost by 3.7 ms of 14.7: PERF.md
  section 6, PR 50). **Backward** (``lm_loss_bwd``): a grid over
  (vocabulary tile, row tile), rows inner; a step makes ``d``'s tile in
  VMEM from the logits' tile, ``lse``, ``y`` and ``g`` and feeds it
  straight to both products and the column sum: ``d`` is never an array.
  ``dw``'s tile accumulates over the inner axis; ``dh``, all of it,
  stays in VMEM in float32 (22.5 MB at the flagship's shapes) from the
  first step to the last, beside ``h`` transposed (11.3 MB), which a
  step slices.

The shapes divide nothing evenly and the kernels take them as they are:
rows are padded to whole tiles with ``g = 0``; the vocabulary is padded
to whole tiles with zero rows of ``w`` and **a bias of -inf**, so a
padded column's logit is ``-inf`` wherever it is read (never the
maximum, ``exp`` of it 0 forward and back) at the cost of the bias's
add, which was there; without a bias the columns at or past ``V`` are
masked by an iota compare. ``E`` is the contraction and stays whole.

**The kernels take ``w`` and give ``dw`` transposed, ``(E, Vp)``.** XLA
keeps the float32 ``(60000, 800)`` embedding, its two moments and its
gradient with the vocabulary minor inside the train program (800 is 6.25
lanes); a ``(Vp, E)`` operand in row-major order made it carry them the
other way round and copy seven of them a step, 4.3 ms (PR 50's first
trace). Transposed, the pad rides on the cast as a bitcast and the
gradient goes into the optimizer's fusion as it comes.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the backward's residents (dh in float32, h transposed), two buffers of
# the logits', w's and dw's tiles, dw's float32 accumulator and a step's
# float32 temporaries of d's tile; Mosaic's default scoped limit is
# 16 MiB of the v5e's 128
_KERNEL_VMEM_LIMIT = 100 * 1024 * 1024

# rows a tile may have (multiples of the lanes' 128, the largest first)
# and columns of the vocabulary: the sweep on the chip is in PERF.md
# section 6, PR 50
_ROW_TILES = tuple(range(1408, 0, -128))
_VOCAB_TILE = 1024


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _kernel_tiles(N: int, E: int, V: int) -> Optional[Tuple[int, int]]:
    """``(tm, tn)``: rows and vocabulary columns a step takes; ``None``
    where the backward's residents do not fit. A function of the static
    shapes alone: of ``_ROW_TILES`` the one that pads ``N`` least (the
    largest of those), and ``_VOCAB_TILE`` columns, fewer for a
    vocabulary under one tile."""
    tm = min(_ROW_TILES, key=lambda t: (_round_up(N, t), -t))
    tn = min(_VOCAB_TILE, _round_up(V, 128))
    rows = _round_up(N, tm)
    resident = rows * E * (4 + 2)            # dh float32, h transposed
    step = 2 * 2 * (tm * tn + 2 * tn * E)    # logits, w, dw: two buffers
    step += tn * E * 4 + 4 * tm * tn * 4     # dw's accumulator, d's tile
    if resident + step > _KERNEL_VMEM_LIMIT * 3 // 4:
        return None
    return tm, tn


def loss_is_kernel(backend: str, dtype, N: int, E: int, V: int,
                   devices: int) -> bool:
    """Pallas kernels or the einsum under ``optax``, for ONE call of
    ``decoder_cross_entropy`` over ``N`` rows: the rule, from what the
    call can observe and nothing a user sets.

    The kernels run on the TPU (off it they are the interpreter, a test
    device); for a bfloat16 compute dtype (float32 is the parity tests'
    and the CPU's); in a program of one device (a GSPMD-partitioned step
    cannot hold a Mosaic call: `training/loop.py::train_cell_is_resident`);
    for an ``E`` of whole sublanes; and where ``_kernel_tiles`` has a
    tile."""
    return (backend == "tpu" and jnp.dtype(dtype) == jnp.bfloat16
            and devices == 1 and E % 8 == 0
            and _kernel_tiles(N, E, V) is not None)


def decoder_cross_entropy(h: jnp.ndarray, w: jnp.ndarray,
                          b: Optional[jnp.ndarray], y: jnp.ndarray,
                          devices: int = 1
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(ce (...) float32, hit (...) bool)`` of the rows ``h (..., E)``
    against ``w (V, E)`` and ``b (V,)`` or ``None`` (all at the compute
    dtype) and the targets ``y (...)``; ``devices`` is how many the
    calling program spans. Differentiable in ``h``, ``w`` and ``b``.
    Which core runs it is ``loss_is_kernel``'s to say."""
    E = h.shape[-1]
    N = h.size // E
    if not loss_is_kernel(jax.default_backend(), h.dtype, N, E, w.shape[0],
                          devices):
        return _reference_core(h, w, b, y)
    ce, hit = _kernel_core(h.reshape(N, E), w, b, y.reshape(N),
                           _kernel_tiles(N, E, w.shape[0]))
    return ce.reshape(y.shape), hit.reshape(y.shape)


def _reference_core(h, w, b, y):
    # named like the trainer's other parts (training/loop.py), as the two
    # were before they were one op
    with jax.named_scope("decoder"):
        logits = jnp.einsum("...e,ve->...v", h, w)
        if b is not None:
            logits = logits + b
    with jax.named_scope("loss"):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y)
        return ce, jnp.argmax(logits, -1) == y


# -- the kernel core ---------------------------------------------------------


def _kernel_core(h, w, b, y, tiles):
    """``(ce (N,) float32, hit (N,) bool)`` of ``h (N, E)``, ``w (V,
    E)``, ``b (V,)`` or ``None`` and ``y (N,)`` at ``tiles``: the
    operands padded to whole tiles, ``_tiled_core``, the rows cut back.
    The pads are plain `jnp.pad`, so autodiff cuts the gradients back
    and hands a padded row ``g = 0``; they ride on copies the step makes
    anyway (`models/awd_lstm.py` casts ``w`` and ``b`` to the compute
    dtype)."""
    tm, tn = tiles
    N, V = h.shape[0], w.shape[0]
    rows, cols = _round_up(N, tm) - N, _round_up(V, tn) - V
    with jax.named_scope("decoder"):
        wT = jnp.pad(w, ((0, cols), (0, 0))).T
        if b is not None:
            b = jnp.pad(b, (0, cols), constant_values=-jnp.inf)[None, :]
    with jax.named_scope("loss"):
        ce, hit = _tiled_core(
            jnp.pad(h, ((0, rows), (0, 0))), wT, b,
            jnp.pad(y.astype(jnp.int32), (0, rows))[:, None], V, tiles)
        return ce[:N], hit[:N]


def _column(j, tn: int):
    """The vocabulary ids of tile ``j``'s columns, ``(1, tn)`` int32."""
    return j * tn + lax.broadcasted_iota(jnp.int32, (1, tn), 1)


def _forward(h, wT, b, y, V: int, tiles, keep: bool):
    """``(ce (Np,), hit (Np,) bool, lse (Np, 1), logits (Np, Vp) or
    None)`` of the padded operands: the grid is (row tiles, vocabulary
    tiles), vocabulary inner, a row tile's four running numbers in VMEM
    across it; the logits are written where ``keep`` (a backward will
    read them)."""
    tm, tn = tiles
    (Np, E), Vp = h.shape, wT.shape[1]
    dtype, f32 = h.dtype, jnp.float32
    last = Vp // tn - 1

    def kernel(*refs):
        refs = iter(refs)
        h_ref, wT_ref = next(refs), next(refs)
        b_ref = next(refs) if b is not None else None
        y_ref, lse_ref, ly_ref, hit_ref = (next(refs) for _ in range(4))
        logits_ref = next(refs) if keep else None
        m_ref, s_ref, a_ref = refs
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            s_ref[...] = jnp.zeros_like(s_ref)
            a_ref[...] = jnp.zeros_like(a_ref)
            ly_ref[...] = jnp.zeros_like(ly_ref)

        col = _column(j, tn)
        # the logits' tile at the module docstring's rounding points
        l = jnp.dot(h_ref[...], wT_ref[...],
                    preferred_element_type=f32).astype(dtype).astype(f32)
        if b is None:
            l = jnp.where(col < V, l, -jnp.inf)
        else:
            l = (l + b_ref[...].astype(f32)).astype(dtype).astype(f32)
        if keep:
            logits_ref[...] = l.astype(dtype)
        t_max = jnp.max(l, axis=1, keepdims=True)
        # the tile's first column at its maximum, as argmax takes it; ids
        # as float32 (exact to 2**24), whose reductions every Mosaic has
        t_arg = jnp.min(jnp.where(l == t_max, col.astype(f32), f32(2 ** 24)),
                        axis=1, keepdims=True)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, t_max)
        s_ref[...] = s_ref[...] * jnp.exp(m_old - m_new) + jnp.sum(
            jnp.exp(l - m_new), axis=1, keepdims=True)
        # an equal maximum in a later tile does not take the place
        a_ref[...] = jnp.where(t_max > m_old, t_arg, a_ref[...])
        m_ref[...] = m_new
        ly_ref[...] += jnp.sum(jnp.where(col == y_ref[...], l, 0.0), axis=1,
                               keepdims=True)

        @pl.when(j == last)
        def _():
            lse_ref[...] = m_ref[...] + jnp.log(s_ref[...])
            hit_ref[...] = (a_ref[...] == y_ref[...].astype(f32)).astype(
                jnp.int32)

    def row(width):
        return pl.BlockSpec((tm, width), lambda i, j: (i, 0))

    bias = [pl.BlockSpec((1, tn), lambda i, j: (0, j))] if b is not None \
        else []
    out_shape = [jax.ShapeDtypeStruct((Np, 1), f32),
                 jax.ShapeDtypeStruct((Np, 1), f32),
                 jax.ShapeDtypeStruct((Np, 1), jnp.int32)]
    out_specs = [row(1), row(1), row(1)]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((Np, Vp), dtype))
        out_specs.append(pl.BlockSpec((tm, tn), lambda i, j: (i, j)))
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(Np // tm, Vp // tn),
        in_specs=[row(E), pl.BlockSpec((E, tn), lambda i, j: (0, j))]
        + bias + [row(1)],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((tm, 1), f32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_LIMIT),
        interpret=jax.default_backend() != "tpu",
        name="lm_loss_fwd",
    )(*([h, wT] + ([b] if b is not None else []) + [y]))
    lse, ly, hit = out[:3]
    return (lse - ly)[:, 0], hit[:, 0] != 0, lse, out[3] if keep else None


def _backward(logits, h, wT, y, lse, g, with_bias: bool, tiles):
    """``(dh (Np, E) float32, dwT (E, Vp), db (1, Vp) float32 or None)``
    of the padded operands and the forward's logits: the grid is
    (vocabulary tiles, row tiles), rows inner; ``dwT``'s tile accumulates
    over the inner axis, ``dh`` whole stays in its output block, and
    ``h`` comes transposed, a row tile a slab, so that ``dwT``'s product
    is a plain one."""
    tm, tn = tiles
    (Np, E), Vp = h.shape, wT.shape[1]
    dtype, f32 = h.dtype, jnp.float32
    n_i = Np // tm
    hT = h.reshape(n_i, tm, E).transpose(0, 2, 1)

    def kernel(logits_ref, wT_ref, hT_ref, y_ref, lse_ref, g_ref, dh_ref,
               dwT_ref, *rest):
        db_ref = rest[0] if with_bias else None
        acc_ref = rest[-1]
        j, i = pl.program_id(0), pl.program_id(1)
        p = jnp.exp(logits_ref[...].astype(f32) - lse_ref[...])
        d = (jnp.where(_column(j, tn) == y_ref[...], p - 1.0, p)
             * g_ref[...]).astype(dtype)
        to_h = lax.dot_general(d, wT_ref[...], (((1,), (1,)), ((), ())),
                               preferred_element_type=f32)
        to_w = jnp.dot(hT_ref[i], d, preferred_element_type=f32)

        @pl.when(j == 0)
        def _():
            dh_ref[i] = to_h

        @pl.when(j > 0)
        def _():
            dh_ref[i] += to_h

        @pl.when(i == 0)
        def _():
            acc_ref[...] = to_w

        @pl.when(i > 0)
        def _():
            acc_ref[...] += to_w

        if with_bias:
            to_b = jnp.sum(d.astype(f32), axis=0, keepdims=True)

            @pl.when(i == 0)
            def _():
                db_ref[...] = to_b

            @pl.when(i > 0)
            def _():
                db_ref[...] += to_b

        @pl.when(i == n_i - 1)
        def _():
            dwT_ref[...] = acc_ref[...].astype(dtype)

    def row():
        return pl.BlockSpec((tm, 1), lambda j, i: (i, 0))

    def column(rows):
        return pl.BlockSpec((rows, tn), lambda j, i: (0, j))

    def whole(shape):
        return pl.BlockSpec(shape, lambda j, i: (0, 0, 0))

    out_shape = [jax.ShapeDtypeStruct((n_i, tm, E), f32),
                 jax.ShapeDtypeStruct((E, Vp), dtype)]
    out_specs = [whole((n_i, tm, E)), column(E)]
    if with_bias:
        out_shape.append(jax.ShapeDtypeStruct((1, Vp), f32))
        out_specs.append(column(1))
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(Vp // tn, n_i),
        in_specs=[pl.BlockSpec((tm, tn), lambda j, i: (i, j)), column(E),
                  whole((n_i, E, tm)), row(), row(), row()],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((E, tn), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_LIMIT),
        interpret=jax.default_backend() != "tpu",
        name="lm_loss_bwd",
    )(logits, wT, hT, y, lse, g)
    return out[0].reshape(Np, E), out[1], out[2] if with_bias else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _tiled_core(h, wT, b, y, V: int, tiles):
    """``(ce (Np,) float32, hit (Np,) bool)`` of operands at whole tiles:
    ``h (Np, E)``, ``wT (E, Vp)`` with zero columns from ``V`` on, ``b
    (1, Vp)`` with ``-inf`` from ``V`` on or ``None``, ``y (Np, 1)``.
    Where no gradient is taken (a validation step) the logits are not
    kept."""
    return _forward(h, wT, b, y, V, tiles, keep=False)[:2]


def _tiled_fwd(h, wT, b, y, V, tiles):
    ce, hit, lse, logits = _forward(h, wT, b, y, V, tiles, keep=True)
    return (ce, hit), (logits, h, wT, b, y, lse)


def _tiled_bwd(V, tiles, saved, cts):
    logits, h, wT, b, y, lse = saved
    g = cts[0].astype(jnp.float32)[:, None]
    dh, dwT, db = _backward(logits, h, wT, y, lse, g, b is not None, tiles)
    return (dh.astype(h.dtype), dwT,
            None if b is None else db.astype(b.dtype), None)


_tiled_core.defvjp(_tiled_fwd, _tiled_bwd)
