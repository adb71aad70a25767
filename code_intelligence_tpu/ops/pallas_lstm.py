"""Pallas fused LSTM cell (weights-resident forward).

The XLA-scan LSTM (`ops/lstm.py`) re-fetches ``W_hh`` from HBM on every
timestep once it exceeds VMEM. This kernel is the TPU-first alternative
for hidden sizes whose recurrent weights FIT on-chip: ``W_hh`` is loaded
into VMEM once and stays resident while time is walked inside the kernel
— one ``pallas_call``, grid ``(batch tiles, time chunks)`` with time
minor, carry held in VMEM scratch that persists across the sequential
time steps of each batch tile.

Replaces (role-wise) the cuDNN fused LSTM cell the reference reaches
through torch (`Issue_Embeddings/train.py:88-92`; SURVEY.md §2.4 row 1 —
"Pallas ... fused LSTM cell as stage 2 optimization"; round-1 VERDICT
item #2). v5e's 128MB VMEM (~64MB Mosaic scope) holds the flagship's
50MB bf16 ``W_hh`` resident. Where it runs and what it reads (v5e, jax
0.9.0, PR 31, `PERF.md` §5-6): the TRAIN step of a one-chip bf16 run
(`training/loop.py::train_cell_is_resident` chooses it; the benchmark
cell `lstm_train_lm`) runs the forward with residuals and the adjoint
below in all four layers. Inside that step at B=104 T=67 H=2500 the
forward kernel takes 2.0-2.1 ms a layer a window (30-31 us a timestep,
its matmul alone is 28 us at the MXU's peak) and the adjoint 2.3-2.4 ms,
where the XLA scan's forward took 2.9 ms and its backward, with the
recurrent weight gradients accumulated in the loop, 9-15 ms a layer; the
step went from 91.87 to 74.9 ms. The inference
kernels (dense without residuals, ragged, int8-ragged) are in no cell:
alone, at 104 rows, the scan's forward (2.96 ms a layer) is level with
the dense kernel's (2.61-2.96 ms by tile), so the serve side has no case
for them yet (ROADMAP D4).

Layout notes:

* The kernel speaks TIME-MAJOR (``(T, B, ·)``) end to end: the dynamic
  per-step index must be on the leading block axis (Mosaic verification),
  the feeding projection einsum emits ``tbg`` as its natural output
  layout, and the backward adjoint scans time-major — so no HBM
  transpose exists on the fused path (an earlier batch-major variant
  paid ~9% of the train step in transposes).
* The bulk input projection ``x @ W_ih^T + b`` stays OUTSIDE the kernel —
  it is one big MXU matmul XLA already handles optimally; the kernel
  receives ``x_proj (T, B, 4H)`` and streams it tile-by-tile.
* Gate order i,f,g,o matches `ops/lstm.py` / torch, so parameters and
  checkpoints are shared with the scan path.
* The VMEM gate (`fits_resident`) is dtype-aware: residency is decided on
  ``4H·H·itemsize`` plus the streamed tile budget, not on H alone.
* Training: ``lstm_layer_fused`` wraps the kernel in a ``custom_vjp``
  whose forward also emits the post-activation gates and the pre-step
  cell states (inference calls skip both outputs); the backward is the
  weights-resident Pallas adjoint ``fused_lstm_backward`` — reversed
  time walk, carry in f32 scratch, ``c_t``/``tanh(c_t)`` recomputed
  from the streamed ``c_prev_seq`` — emitting the pre-activation grads
  for XLA's weight/input einsums.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LSTMState = Tuple[jnp.ndarray, jnp.ndarray]

# Mosaic's scoped-VMEM ceiling on v5e is ~64MB (half the 128MB physical
# VMEM); staying a couple MB under it in the estimate below keeps the
# tile search away from the compile-failure edge measured on chip
# (H=2500: bt56/tc2 at an estimated ~61MB compiled, bt56/tc4 at ~71MB
# did not).
_VMEM_BUDGET = 63 * 1024 * 1024
# Streamed-tile ceiling from Mosaic's ~16MB per-iteration stack budget
# (see _pick_tiles docstring for the on-chip boundary mapping).
_STREAM_TILE_BUDGET = int(4.5 * 1024 * 1024)
# The same ceiling for the two TRAIN kernels (forward with residuals, and
# the adjoint), mapped on today's toolchain by compiling every candidate
# for a described v5e (PR 31, B104 T67 H=2500): whole-batch tiles
# bt112/tc1 stream 5.04 MB (forward) and 5.6 MB (adjoint) a grid step and
# compile; the forward at 10 MB (bt112/tc2, bt56/tc4) runs out of VMEM.
# The inference kernels keep the budget above, so the serve programs'
# tiles are the ones they were.
_TRAIN_STREAM_TILE_BUDGET = 6 * 1024 * 1024
# W_hh residency gate: the flagship H=2500 (50MB bf16) fits with room
# for minimum streaming tiles; H≈2610 bf16 is the practical edge
# (4·2610²·2 = 51.9MB).
_W_HH_BUDGET = 52 * 1024 * 1024
# Per-kernel scoped-VMEM limit passed to Mosaic. Without it the kernel
# inherits XLA's 16MB default *when embedded in a larger module* (e.g.
# jit(train_step)), and the resident W_hh alone blows it: inside a
# train step the kernel died at compile with "scoped allocation 54.80M,
# limit 16.00M" while the SAME kernel compiled standalone (whole-module
# budget). _VMEM_BUDGET already keeps the real usage under the ~64MB
# Mosaic ceiling; this just tells XLA so.
_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=_VMEM_BUDGET + 8 * 1024 * 1024)
# The ADJOINT at a whole-batch tile sits at that limit's edge: inside
# `train_steps` bt112/tc1 compiled, but under a plain `jax.grad` of one
# layer it did not ("ran out of memory in memory space vmem": there XLA
# keeps the kernel's small operands in VMEM itself, and Mosaic's own f32
# temporaries of a (112, 10000) tile are 4.5 MB each). v5e has 128 MiB;
# at 80 MiB every batch tried compiled (32-200 rows at H=2500, AOT for
# v5e, PR 31). (95 MiB on both train kernels read 0.9 % fewer tokens/s
# in the cell than 71 / 80; the kernels' own times were the same, so
# that is the spread between two compiles, not a reason.) The forward
# kernels keep the limit above: the serve programs are what they were.
_BWD_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=_VMEM_BUDGET + 17 * 1024 * 1024)


def fits_resident(hidden_size: int, itemsize: int = 2) -> bool:
    """True when the fused kernel can hold W_hh resident: 4H·H·itemsize
    within budget. On v5e's 128MB VMEM (~64MB Mosaic scope) that covers
    the flagship H=2500 (50MB bf16), not just the sweep/serving sizes —
    round 3's on-chip A/B refuted the earlier 16MB-VMEM roofline claim
    (docs/RUNBOOK.md §11)."""
    return 4 * hidden_size * hidden_size * itemsize <= _W_HH_BUDGET


MAX_RESIDENT_H = 2500  # bf16 boundary (flagship), for docs/tests


def _sublane_snap(batch: int, itemsize: int) -> Tuple[int, int, list]:
    """(sublane multiple, padded batch dim, candidate batch tiles).

    The padded BATCH ARRAY dim snaps to the dtype's native sublane tile
    (bf16: (16,128); f32: (8,128)) — on chip, a 104-row bf16 array
    compiled into a monolithic 60MB "stack" allocation (fail) while the
    same kernel over a 112-row array streamed fine, and 56-row BLOCKS of
    that 112-row array also worked, so the constraint is on the array,
    not the block. Batch tiles are the multiple-of-8 divisors of the
    padded dim (exact grid, no second padding)."""
    sub = 16 if itemsize == 2 else 8
    bp = -(-batch // sub) * sub
    bts = [b for b in range(bp, 7, -8) if bp % b == 0]
    return sub, bp, bts


def feasible_tiles(batch: int, hidden: int, gate_dim: int, with_gates: bool,
                   itemsize: int) -> list:
    """All ``(batch_tile, time_chunk)`` candidates under both compile-time
    ceilings (scoped VMEM + per-iteration stream budget): what
    `_pick_tiles` chooses from, and the space a chip sweep would time."""
    _, _, bts = _sublane_snap(batch, itemsize)
    w_bytes = gate_dim * hidden * itemsize

    def feasible(bt: int, tc: int) -> bool:
        x_tile = tc * bt * gate_dim * itemsize
        c_tile = tc * bt * hidden * itemsize
        # training fwd streams x_proj in + gates and c_prev out
        streamed = x_tile + (x_tile + c_tile if with_gates else 0)
        if streamed > (_TRAIN_STREAM_TILE_BUDGET if with_gates
                       else _STREAM_TILE_BUDGET):
            return False
        tile = 2 * x_tile
        out = 2 * c_tile
        state = 4 * bt * hidden * itemsize
        est = (w_bytes + tile + (tile + 2 * c_tile if with_gates else 0)
               + out + state)
        return est <= _VMEM_BUDGET

    return [(bt, tc) for bt in bts for tc in (4, 2, 1) if feasible(bt, tc)]


def _pick_tiles(batch: int, hidden: int, gate_dim: int, with_gates: bool,
                itemsize: int) -> Tuple[int, int]:
    """Choose (batch_tile, time_chunk) for the fused kernel: a function
    of the shapes alone, written from ONE sweep of `feasible_tiles` on
    the chip (v5e, jax 0.9.0, PR 31; B104 x T67 bf16, ms a window, the
    wrapper's pad and slices included; the full table is in PERF.md §6).

    Training forward (``with_gates``), H=2500: bt112/tc1 **3.16**,
    bt56/tc1 3.45, bt56/tc2 3.50, bt16 5.15-5.23, bt8 9.1-9.3; H=800:
    bt112/tc1 **0.607**, bt112/tc2 0.623, bt56 0.626-0.643, bt16 0.87,
    bt8 1.38. The batch tile decides (each grid step pays ~9 us whatever
    its rows, then ~0.34 us a row: at 112 rows the kernel alone is 30-31
    us a timestep inside `train_steps`, against 28 us for its matmul at
    the MXU's peak), the time chunk moves nothing (under 2 %), so: the
    LARGEST batch tile, then the SMALLEST time chunk.

    Inference (no gates): the rule measured on the toolchain before this
    one stands, because the serve programs are not this sweep's to
    change; for whoever decides that (ROADMAP D4) the same sweep read
    H=2500 bt112/tc1 2.61, bt112/tc2 2.66, bt56/tc1 2.92 and this rule's
    bt56/tc4 2.96; H=800 bt112/tc2 0.451, bt56/tc1 0.452, this rule's
    bt112/tc4 0.476.

    Two compile-time ceilings bound the choice (`feasible_tiles`): the
    scoped-VMEM budget (resident W_hh + all blocks) and the streamed
    bytes of one grid step. Forward bt112/tc2 and bt56/tc4 at H=2500
    with gates (10 MB streamed) fail to compile: "ran out of memory in
    memory space vmem".
    """
    cands = feasible_tiles(batch, hidden, gate_dim, with_gates, itemsize)
    if not cands:
        _, _, bts = _sublane_snap(batch, itemsize)
        return bts[-1], 1
    if with_gates:
        return max(cands, key=lambda c: (c[0], -c[1]))
    # no gates: maximize (min(bt, 56), tc, bt), the fit to the earlier
    # toolchain's measurements ((56,4) at H=2500, (112,4) at serve sizes)
    return max(cands, key=lambda c: (min(c[0], 56), c[1], c[0]))


def _kernel_body(t_real, emit_gates, x_proj_ref, w_hh_t_ref, h0_ref, c0_ref,
                 out_ref, gates_ref, c_prev_ref, h_t_ref, c_t_ref,
                 h_scr, c_scr):
    """Grid = (batch tiles, time chunks), time minor. Carry scratch
    persists across the time dimension of one batch tile; ``t_real``
    (static) freezes the carry on zero-padded tail steps."""
    t_chunk = x_proj_ref.shape[0]
    t_base = pl.program_id(1) * t_chunk

    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    # TIME-MAJOR blocks (tc, bt, ·): Mosaic requires the per-step dynamic
    # index to be on the LEADING block axis (a dynamic middle-axis
    # vector.load fails verification on real TPU), and the trailing
    # (bt, ·) dims satisfy the (8, 128)-divisibility rule. The layout
    # change is free at the HBM boundary: the caller's projection einsum
    # emits "tbg" directly and the backward adjoint scans time-major too.
    def step(i, _):
        h = h_scr[:]
        c = c_scr[:]
        # Gate math stays in f32: Mosaic rejects the weak-typed f32
        # constants inside sigmoid/tanh when the vector dtype is bf16
        # (vector.broadcast f32 -> bf16 verification error on real TPU),
        # and f32 accumulation is numerically better regardless. Only the
        # stores cast back to the carry dtype.
        gates = x_proj_ref[i].astype(jnp.float32) + jnp.dot(
            h, w_hh_t_ref[:], preferred_element_type=jnp.float32
        )
        H = h.shape[-1]
        i_g = jax.nn.sigmoid(gates[:, :H])
        f_g = jax.nn.sigmoid(gates[:, H : 2 * H])
        g_g = jnp.tanh(gates[:, 2 * H : 3 * H])
        o_g = jax.nn.sigmoid(gates[:, 3 * H :])
        c_new = f_g * c.astype(jnp.float32) + i_g * g_g
        h_new = o_g * jnp.tanh(c_new)
        live = (t_base + i) < t_real  # padded tail: freeze the carry
        h_new = jnp.where(live, h_new.astype(h.dtype), h)
        c_new = jnp.where(live, c_new.astype(c.dtype), c)
        h_scr[:] = h_new
        c_scr[:] = c_new
        out_ref[i] = h_new
        if emit_gates:
            gates_ref[i] = jnp.concatenate(
                [i_g, f_g, g_g, o_g], axis=-1
            ).astype(gates_ref.dtype)
            # c BEFORE this step's update: the backward kernel streams it
            # to recompute c_t (and tanh c_t) on the fly instead of
            # streaming a second c array.
            c_prev_ref[i] = c
        return 0

    lax.fori_loop(0, t_chunk, step, 0)
    h_t_ref[:] = h_scr[:]
    c_t_ref[:] = c_scr[:]


def _kernel_with_gates(t_real, *refs):
    return _kernel_body(t_real, True, *refs)


def _kernel_no_gates(t_real, x_proj_ref, w_hh_t_ref, h0_ref, c0_ref,
                     out_ref, h_t_ref, c_t_ref, h_scr, c_scr):
    return _kernel_body(t_real, False, x_proj_ref, w_hh_t_ref, h0_ref, c0_ref,
                        out_ref, None, None, h_t_ref, c_t_ref, h_scr, c_scr)


def _pad_axis(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("with_gates", "interpret",
                                              "tiles", "t_real"))
def fused_lstm_forward(
    x_proj: jnp.ndarray,
    w_hh: jnp.ndarray,
    h0: jnp.ndarray,
    c0: jnp.ndarray,
    with_gates: bool = False,
    interpret: bool = False,
    tiles: "Tuple[int, int] | None" = None,
    t_real: "int | None" = None,
):
    """Run the fused cell over a window.

    TIME-MAJOR contract (round 3): the projection einsum that feeds this
    kernel emits ``(T, B, 4H)`` at no extra cost (it is just the matmul's
    output layout), the backward adjoint scans want time-leading anyway,
    and Mosaic needs the dynamic time index on the leading block axis —
    so the kernel speaks time-major end to end and no HBM transpose
    exists anywhere on the fused path.

    Args:
      x_proj: ``(T, B, 4H)`` precomputed ``x @ W_ih^T + bias``.
      w_hh: ``(4H, H)`` recurrent weights (DropConnect already applied).
      h0, c0: ``(B, H)`` carried state.
      with_gates: also return the training residuals — post-activation
        gates ``(T, B, 4H)`` and the pre-step cell state ``c_prev_seq``
        ``(T, B, H)`` — for the fused backward; inference skips both
        HBM writes.
      tiles: explicit ``(batch_tile, time_chunk)`` for a chip sweep
        over ``feasible_tiles``; product callers leave it None and get
        ``_pick_tiles``.
      t_real: the live timesteps of a window the CALLER already padded in
        time (the train path hands in arrays padded to the kernel's own
        grid, so the pads and slices below are all no-ops; the carry
        freezes past ``t_real``). Default: all ``T``.

    Returns:
      ``(outputs (T, B, H), (gates, c_prev_seq)-or-None, (h_T, c_T))``.
    """
    T, B, G = x_proj.shape
    H = G // 4
    dtype = x_proj.dtype
    bt, tc = tiles or _pick_tiles(B, H, G, with_gates, dtype.itemsize)
    t_live = T if t_real is None else t_real
    # Batch pads to the sublane-snapped dim (bf16: mult of 16) — see
    # _sublane_snap; bt divides it, so no second batch padding happens.
    sub, _, _ = _sublane_snap(B, dtype.itemsize)
    x_pad = _pad_axis(_pad_axis(_pad_axis(x_proj, 0, tc), 1, sub), 1, bt)
    Tp, Bp = x_pad.shape[0], x_pad.shape[1]
    h0p = _pad_axis(_pad_axis(h0.astype(dtype), 0, sub), 0, bt)
    c0p = _pad_axis(_pad_axis(c0.astype(dtype), 0, sub), 0, bt)
    grid = (Bp // bt, Tp // tc)
    w_hh_t = w_hh.T.astype(dtype)  # (H, 4H)
    in_specs = [
        pl.BlockSpec((tc, bt, G), lambda b, t: (t, b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((H, G), lambda b, t: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, H), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, H), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
    ]
    out_block_seq = pl.BlockSpec((tc, bt, H), lambda b, t: (t, b, 0),
                                 memory_space=pltpu.VMEM)
    out_block_state = pl.BlockSpec((bt, H), lambda b, t: (b, 0),
                                   memory_space=pltpu.VMEM)
    scratch = [pltpu.VMEM((bt, H), dtype), pltpu.VMEM((bt, H), dtype)]

    if with_gates:
        kernel = functools.partial(_kernel_with_gates, t_live)
        out_specs = [
            out_block_seq,
            pl.BlockSpec((tc, bt, G), lambda b, t: (t, b, 0), memory_space=pltpu.VMEM),
            out_block_seq,  # c_prev_seq
            out_block_state, out_block_state,
        ]
        out_shape = [
            jax.ShapeDtypeStruct((Tp, Bp, H), dtype),
            jax.ShapeDtypeStruct((Tp, Bp, G), dtype),
            jax.ShapeDtypeStruct((Tp, Bp, H), dtype),
            jax.ShapeDtypeStruct((Bp, H), dtype),
            jax.ShapeDtypeStruct((Bp, H), dtype),
        ]
    else:
        kernel = functools.partial(_kernel_no_gates, t_live)
        out_specs = [out_block_seq, out_block_state, out_block_state]
        out_shape = [
            jax.ShapeDtypeStruct((Tp, Bp, H), dtype),
            jax.ShapeDtypeStruct((Bp, H), dtype),
            jax.ShapeDtypeStruct((Bp, H), dtype),
        ]

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(x_pad, w_hh_t, h0p, c0p)
    if with_gates:
        outputs, gates, c_prev_seq, h_t, c_t = outs
        gates = gates[:T, :B]
        c_prev_seq = c_prev_seq[:T, :B]
        residuals = (gates, c_prev_seq)
    else:
        outputs, h_t, c_t = outs
        residuals = None
    return outputs[:T, :B], residuals, (h_t[:B], c_t[:B])


# ---------------------------------------------------------------------------
# Ragged (length-aware) inference forward: per-row valid lengths
# ---------------------------------------------------------------------------


def _ragged_kernel(x_proj_ref, w_hh_t_ref, h0_ref, c0_ref, valid_ref,
                   out_ref, h_t_ref, c_t_ref, h_scr, c_scr):
    """Length-aware variant of ``_kernel_no_gates``: ``valid_ref`` is a
    lane-broadcast ``(bt, 128)`` int32 block of per-row valid lengths.
    A time chunk whose rows are ALL exhausted (chunk start past the
    tile's max valid length) does no matmul work — it only zero-fills
    its output block so downstream masked pooling reads finite values.
    Within a live chunk, rows past their own valid length freeze their
    carry and emit zeros, so ``h_T``/``c_T`` are each row's state after
    exactly ``min(valid, T)`` real steps."""
    t_chunk = x_proj_ref.shape[0]
    t_base = pl.program_id(1) * t_chunk

    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    valid_col = valid_ref[:, :1]  # (bt, 1): per-row valid length
    block_max = jnp.max(valid_ref[:, 0])
    live_chunk = t_base < block_max

    @pl.when(live_chunk)
    def _run():
        def step(i, _):
            h = h_scr[:]
            c = c_scr[:]
            # f32 gate math, bf16-safe constants: same recipe as the
            # dense kernel (Mosaic rejects weak-typed f32 broadcasts
            # into bf16 vectors)
            gates = x_proj_ref[i].astype(jnp.float32) + jnp.dot(
                h, w_hh_t_ref[:], preferred_element_type=jnp.float32
            )
            H = h.shape[-1]
            i_g = jax.nn.sigmoid(gates[:, :H])
            f_g = jax.nn.sigmoid(gates[:, H : 2 * H])
            g_g = jnp.tanh(gates[:, 2 * H : 3 * H])
            o_g = jax.nn.sigmoid(gates[:, 3 * H :])
            c_new = f_g * c.astype(jnp.float32) + i_g * g_g
            h_new = o_g * jnp.tanh(c_new)
            live = (t_base + i) < valid_col  # (bt, 1): per-row freeze
            h_new = jnp.where(live, h_new.astype(h.dtype), h)
            c_new = jnp.where(live, c_new.astype(c.dtype), c)
            h_scr[:] = h_new
            c_scr[:] = c_new
            out_ref[i] = jnp.where(live, h_new, jnp.zeros_like(h_new))
            return 0

        lax.fori_loop(0, t_chunk, step, 0)

    @pl.when(jnp.logical_not(live_chunk))
    def _skip():
        # dead chunk: the output block must still be DEFINED (the pooled
        # consumer multiplies by a zero mask — an uninitialized NaN would
        # poison the sum) but costs one VPU store, zero MXU work
        out_ref[:] = jnp.zeros(out_ref.shape, out_ref.dtype)

    h_t_ref[:] = h_scr[:]
    c_t_ref[:] = c_scr[:]


@functools.partial(jax.jit, static_argnames=("interpret", "tiles"))
def fused_lstm_forward_ragged(
    x_proj: jnp.ndarray,
    w_hh: jnp.ndarray,
    h0: jnp.ndarray,
    c0: jnp.ndarray,
    valid_lens: jnp.ndarray,
    interpret: bool = False,
    tiles: "Tuple[int, int] | None" = None,
):
    """Length-aware fused forward over a window (inference only, no VJP).

    Same layout contract as :func:`fused_lstm_forward` (time-major
    ``x_proj (T, B, 4H)``), plus ``valid_lens (B,) int32``: row ``b``'s
    tokens past ``valid_lens[b]`` are dead lanes. Contract (the ragged
    slot step's — see ``inference/slots.py``):

    * ``outputs[t, b]`` equals the dense kernel's for ``t < valid``,
      and is exactly zero (finite, maskable) for ``t >= valid``;
    * ``h_T[b]``/``c_T[b]`` are the carry after ``min(valid, T)`` real
      steps — a row never pollutes its state on dead tail tokens;
    * a time chunk whose batch tile is entirely exhausted skips ALL
      matmul work (grid-level ``pl.when`` masking).

    The VMEM feasibility gate is the dense inference kernel's
    (``feasible_tiles`` with ``with_gates=False``) — the per-tile valid
    block adds ``bt*128`` int32, noise at these budgets.
    """
    T, B, G = x_proj.shape
    H = G // 4
    dtype = x_proj.dtype
    bt, tc = tiles or _pick_tiles(B, H, G, False, dtype.itemsize)
    sub, _, _ = _sublane_snap(B, dtype.itemsize)
    x_pad = _pad_axis(_pad_axis(_pad_axis(x_proj, 0, tc), 1, sub), 1, bt)
    Tp, Bp = x_pad.shape[0], x_pad.shape[1]
    h0p = _pad_axis(_pad_axis(h0.astype(dtype), 0, sub), 0, bt)
    c0p = _pad_axis(_pad_axis(c0.astype(dtype), 0, sub), 0, bt)
    # padding rows get valid 0 — they are dead lanes by construction, so
    # the block-max skip sees them as exhausted, never as work
    valid_p = _pad_axis(valid_lens.astype(jnp.int32).reshape(-1), 0, sub)
    valid_p = _pad_axis(valid_p, 0, bt)
    # lane-broadcast so each batch tile reads a plain (bt, 128) int32
    # block (the sublane/lane tiling a (bt,) vector cannot express)
    valid2d = jnp.broadcast_to(valid_p[:, None], (Bp, 128))
    grid = (Bp // bt, Tp // tc)
    w_hh_t = w_hh.T.astype(dtype)  # (H, 4H)
    in_specs = [
        pl.BlockSpec((tc, bt, G), lambda b, t: (t, b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((H, G), lambda b, t: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, H), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, H), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, 128), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
    ]
    out_specs = [
        pl.BlockSpec((tc, bt, H), lambda b, t: (t, b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, H), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, H), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((Tp, Bp, H), dtype),
        jax.ShapeDtypeStruct((Bp, H), dtype),
        jax.ShapeDtypeStruct((Bp, H), dtype),
    ]
    outputs, h_t, c_t = pl.pallas_call(
        _ragged_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bt, H), dtype), pltpu.VMEM((bt, H), dtype)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(x_pad, w_hh_t, h0p, c0p, valid2d)
    return outputs[:T, :B], (h_t[:B], c_t[:B])


def lstm_layer_fused_ragged(x, state, w_ih, w_hh, bias, valid_lens,
                            interpret: bool = False):
    """Length-aware drop-in for :func:`lstm_layer_fused` (inference only —
    the serve path's ragged slot step; no VJP is defined). ``x`` is
    batch-major ``(B, T, in)`` like the dense wrapper; ``valid_lens``
    ``(B,) int32`` marks each row's live prefix."""
    interpret = interpret or jax.default_backend() != "tpu"
    x_proj = jnp.einsum("bti,gi->tbg", x, w_ih) + bias
    h0, c0 = state
    out_tm, new_state = fused_lstm_forward_ragged(
        x_proj, w_hh, h0, c0, valid_lens, interpret=interpret
    )
    return out_tm.swapaxes(0, 1), new_state


# ---------------------------------------------------------------------------
# Int8-weight ragged inference forward (post-training quantized serve path)
# ---------------------------------------------------------------------------


def fits_resident_int8(hidden_size: int) -> bool:
    """Residency gate for the int8 serve kernel: the resident recurrent
    weight costs ``4H*H`` bytes (int8) PLUS one f32 dequantized gate
    slice ``H*H*4`` the kernel materializes per gate — recomputed
    against the same ``_W_HH_BUDGET``, NOT reused from the f32 gate
    (the whole point: H=2500 int8+slice is 50MB and fits where the
    100MB f32 weight never did)."""
    return 4 * hidden_size * hidden_size + hidden_size * hidden_size * 4 \
        <= _W_HH_BUDGET


def feasible_tiles_int8(batch: int, hidden: int, gate_dim: int,
                        act_itemsize: int) -> list:
    """``(batch_tile, time_chunk)`` candidates for the int8-resident
    ragged kernel. The activation stream budget keeps the f32/bf16
    itemsize (x_proj is dequantized OUTSIDE the kernel); the weight
    budget is int8 residency + the per-gate f32 dequant slice + the
    sublane-broadcast scale block."""
    _, _, bts = _sublane_snap(batch, act_itemsize)
    w_bytes = gate_dim * hidden + hidden * hidden * 4 + 8 * gate_dim * 4

    def feasible(bt: int, tc: int) -> bool:
        x_tile = tc * bt * gate_dim * act_itemsize
        if x_tile > _STREAM_TILE_BUDGET:
            return False
        out_tile = tc * bt * hidden * act_itemsize
        state = 4 * bt * hidden * act_itemsize
        est = w_bytes + 2 * x_tile + 2 * out_tile + state
        return est <= _VMEM_BUDGET

    return [(bt, tc) for bt in bts for tc in (4, 2, 1) if feasible(bt, tc)]


def _pick_tiles_int8(batch: int, hidden: int, gate_dim: int,
                     act_itemsize: int) -> Tuple[int, int]:
    cands = feasible_tiles_int8(batch, hidden, gate_dim, act_itemsize)
    if not cands:
        _, _, bts = _sublane_snap(batch, act_itemsize)
        return bts[-1], 1
    return max(cands, key=lambda c: (min(c[0], 56), c[1], c[0]))


def _ragged_kernel_int8(x_proj_ref, w_q_t_ref, scale_ref, h0_ref, c0_ref,
                        valid_ref, out_ref, h_t_ref, c_t_ref, h_scr, c_scr):
    """Int8-weight variant of ``_ragged_kernel``: the resident recurrent
    weight block is INT8 (``(H, 4H)``, a 4x VMEM shrink) plus a
    sublane-broadcast f32 per-output-channel scale block ``(8, 4H)``.
    Dequantization happens in-register, one gate slice at a time — the
    per-channel scale rides the matmul's OUTPUT axis, so it is applied
    to the ``(bt, H)`` accumulator after the dot, never to the weight
    (``(x @ W_q) * s == x @ (W_q * s)`` exactly): the transient f32
    weight copy is one ``(H, H)`` gate slice, not the whole ``(H, 4H)``
    block. Exhausted-tile skip, per-row carry freeze, and zero-fill
    semantics are inherited verbatim from the f32 ragged kernel."""
    t_chunk = x_proj_ref.shape[0]
    t_base = pl.program_id(1) * t_chunk

    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    valid_col = valid_ref[:, :1]  # (bt, 1): per-row valid length
    block_max = jnp.max(valid_ref[:, 0])
    live_chunk = t_base < block_max

    @pl.when(live_chunk)
    def _run():
        def step(i, _):
            h = h_scr[:]
            c = c_scr[:]
            H = h.shape[-1]
            xp = x_proj_ref[i].astype(jnp.float32)
            h32 = h.astype(jnp.float32)

            def gate(g):
                # one (H, H) int8 slice dequantized in-register; scale
                # applied to the (bt, H) accumulator (output channels)
                w_slice = w_q_t_ref[:, g * H:(g + 1) * H].astype(jnp.float32)
                acc = jnp.dot(h32, w_slice,
                              preferred_element_type=jnp.float32)
                return xp[:, g * H:(g + 1) * H] \
                    + acc * scale_ref[0:1, g * H:(g + 1) * H]

            i_g = jax.nn.sigmoid(gate(0))
            f_g = jax.nn.sigmoid(gate(1))
            g_g = jnp.tanh(gate(2))
            o_g = jax.nn.sigmoid(gate(3))
            c_new = f_g * c.astype(jnp.float32) + i_g * g_g
            h_new = o_g * jnp.tanh(c_new)
            live = (t_base + i) < valid_col  # (bt, 1): per-row freeze
            h_new = jnp.where(live, h_new.astype(h.dtype), h)
            c_new = jnp.where(live, c_new.astype(c.dtype), c)
            h_scr[:] = h_new
            c_scr[:] = c_new
            out_ref[i] = jnp.where(live, h_new, jnp.zeros_like(h_new))
            return 0

        lax.fori_loop(0, t_chunk, step, 0)

    @pl.when(jnp.logical_not(live_chunk))
    def _skip():
        out_ref[:] = jnp.zeros(out_ref.shape, out_ref.dtype)

    h_t_ref[:] = h_scr[:]
    c_t_ref[:] = c_scr[:]


@functools.partial(jax.jit, static_argnames=("interpret", "tiles"))
def fused_lstm_forward_ragged_int8(
    x_proj: jnp.ndarray,
    w_hh_q: jnp.ndarray,
    w_hh_scale: jnp.ndarray,
    h0: jnp.ndarray,
    c0: jnp.ndarray,
    valid_lens: jnp.ndarray,
    interpret: bool = False,
    tiles: "Tuple[int, int] | None" = None,
):
    """Int8-weight twin of :func:`fused_lstm_forward_ragged`.

    Same time-major layout and ragged contract; the recurrent weight
    arrives QUANTIZED — ``w_hh_q (4H, H) int8`` plus ``w_hh_scale
    (4H,) f32`` per-output-channel scales (``ops/quantize.py``) — and
    stays int8 in VMEM. Tile selection goes through the int8 budget
    (:func:`feasible_tiles_int8`), never the f32 one.
    """
    T, B, G = x_proj.shape
    H = G // 4
    dtype = x_proj.dtype
    if w_hh_q.dtype != jnp.int8:
        raise ValueError(f"w_hh_q must be int8, got {w_hh_q.dtype}")
    bt, tc = tiles or _pick_tiles_int8(B, H, G, dtype.itemsize)
    sub, _, _ = _sublane_snap(B, dtype.itemsize)
    x_pad = _pad_axis(_pad_axis(_pad_axis(x_proj, 0, tc), 1, sub), 1, bt)
    Tp, Bp = x_pad.shape[0], x_pad.shape[1]
    h0p = _pad_axis(_pad_axis(h0.astype(dtype), 0, sub), 0, bt)
    c0p = _pad_axis(_pad_axis(c0.astype(dtype), 0, sub), 0, bt)
    valid_p = _pad_axis(valid_lens.astype(jnp.int32).reshape(-1), 0, sub)
    valid_p = _pad_axis(valid_p, 0, bt)
    valid2d = jnp.broadcast_to(valid_p[:, None], (Bp, 128))
    grid = (Bp // bt, Tp // tc)
    w_q_t = w_hh_q.T  # (H, 4H) int8 — no astype: residency IS the win
    # sublane-broadcast (8, 4H) f32 block: a (4H,) vector has no legal
    # sublane/lane tiling; 8 rows cost 128KB at the flagship shape
    scale2d = jnp.broadcast_to(
        w_hh_scale.astype(jnp.float32)[None, :], (8, G))
    in_specs = [
        pl.BlockSpec((tc, bt, G), lambda b, t: (t, b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((H, G), lambda b, t: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((8, G), lambda b, t: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, H), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, H), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, 128), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
    ]
    out_specs = [
        pl.BlockSpec((tc, bt, H), lambda b, t: (t, b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, H), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bt, H), lambda b, t: (b, 0), memory_space=pltpu.VMEM),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((Tp, Bp, H), dtype),
        jax.ShapeDtypeStruct((Bp, H), dtype),
        jax.ShapeDtypeStruct((Bp, H), dtype),
    ]
    outputs, h_t, c_t = pl.pallas_call(
        _ragged_kernel_int8,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bt, H), dtype), pltpu.VMEM((bt, H), dtype)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(x_pad, w_q_t, scale2d, h0p, c0p, valid2d)
    return outputs[:T, :B], (h_t[:B], c_t[:B])


def lstm_layer_fused_ragged_int8(x, state, w_ih_q, w_ih_scale, w_hh_q,
                                 w_hh_scale, bias, valid_lens,
                                 interpret: bool = False):
    """Int8 drop-in for :func:`lstm_layer_fused_ragged` (serve path only).

    The input projection stays the one big XLA matmul outside the
    kernel: the int8 ``w_ih_q`` feeds the einsum directly and the
    per-output-channel scale lands on the ``(T, B, 4H)`` result before
    the bias — XLA fuses the convert+scale into the matmul, so no f32
    weight copy persists in HBM.
    """
    interpret = interpret or jax.default_backend() != "tpu"
    dtype = x.dtype
    x_proj = jnp.einsum("bti,gi->tbg", x, w_ih_q.astype(dtype)) \
        * w_ih_scale.astype(dtype) + bias
    h0, c0 = state
    out_tm, new_state = fused_lstm_forward_ragged_int8(
        x_proj, w_hh_q, w_hh_scale, h0, c0, valid_lens, interpret=interpret
    )
    return out_tm.swapaxes(0, 1), new_state


# ---------------------------------------------------------------------------
# Training wrapper: Pallas forward with residuals + Pallas adjoint; the
# weight / input gradients are XLA einsums over the adjoint's dz
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def lstm_layer_fused(x, state, w_ih, w_hh, bias, interpret=False):
    """Drop-in for `ops.lstm.lstm_layer` (same signature minus the mask —
    callers apply DropConnect to ``w_hh`` before the call). Called without
    a gradient (validation) it runs the forward without residuals."""
    # CPU (tests, multichip dryrun) has no Mosaic backend: interpret mode
    # keeps the exact same numerics there.
    interpret = interpret or jax.default_backend() != "tpu"
    # The projection emits time-major directly — just the matmul's output
    # layout, not an extra transpose pass.
    x_proj = jnp.einsum("bti,gi->tbg", x, w_ih) + bias
    h0, c0 = state
    out_tm, _, new_state = fused_lstm_forward(
        x_proj, w_hh, h0, c0, interpret=interpret)
    return out_tm.swapaxes(0, 1), new_state


def _train_grid(batch: int, steps: int, hidden: int,
                itemsize: int) -> Tuple[int, int]:
    """``(padded batch, padded steps)`` that BOTH train kernels' grids
    divide: what `_fwd` pads the layer's input to, once, so that neither
    kernel's wrapper pads or slices anything."""
    _, tc_f = _pick_tiles(batch, hidden, 4 * hidden, True, itemsize)
    _, tc_b = _pick_tiles_bwd(batch, hidden, 4 * hidden, itemsize)
    _, bp, _ = _sublane_snap(batch, itemsize)  # every batch tile divides it
    tc = max(tc_f, tc_b)  # time chunks are 1, 2 or 4
    return bp, -(-steps // tc) * tc


def _fwd(x, state, w_ih, w_hh, bias, interpret):
    """Forward for the adjoint. The layer's INPUT is padded to the
    kernels' grid (104 rows to 112: 35 MB) and the projection emits
    ``x_proj`` padded; the residuals stay padded until `_bwd`. Padding
    ``x_proj`` itself, and slicing the residuals only for `_bwd` to pad
    them again, were 4.0 ms of the 78.4 ms train step (v5e, PR 31)."""
    interpret = interpret or jax.default_backend() != "tpu"
    B, T, _ = x.shape
    H = w_hh.shape[1]
    bp, tp = _train_grid(B, T, H, x.dtype.itemsize)
    x_p = jnp.pad(x, ((0, bp - B), (0, tp - T), (0, 0)))
    x_proj = jnp.einsum("bti,gi->tbg", x_p, w_ih) + bias
    h0, c0 = state
    pad_b = ((0, bp - B), (0, 0))
    out_p, (gates_p, c_prev_p), (h_t, c_t) = fused_lstm_forward(
        x_proj, w_hh, jnp.pad(h0, pad_b), jnp.pad(c0, pad_b),
        with_gates=True, interpret=interpret, t_real=T)
    res = (x, h0, c0, w_ih, w_hh, bias, out_p, gates_p, c_prev_p)
    return (out_p[:T, :B].swapaxes(0, 1), (h_t[:B], c_t[:B])), res


def feasible_tiles_bwd(batch: int, hidden: int, gate_dim: int,
                       itemsize: int) -> list:
    """Backward-kernel tile candidates (what `_pick_tiles_bwd` chooses
    from). Streams per grid step: gates + dz (G each) and c_prev +
    d_out (H each) — heavier than the forward, so tiles come out smaller
    at the same budgets."""
    _, _, bts = _sublane_snap(batch, itemsize)
    w_bytes = gate_dim * hidden * itemsize

    def feasible(bt: int, tc: int) -> bool:
        g_tile = tc * bt * gate_dim * itemsize
        c_tile = tc * bt * hidden * itemsize
        streamed = g_tile + c_tile + c_tile  # gates, c_prev, d_out in
        if streamed + g_tile > _TRAIN_STREAM_TILE_BUDGET:  # + dz out
            return False
        est = (w_bytes + 2 * (2 * g_tile + 2 * c_tile)  # dbl-buffered
               + 4 * bt * hidden * itemsize             # state blocks
               + 2 * bt * hidden * 4)                   # f32 scratch
        return est <= _VMEM_BUDGET

    return [(bt, tc) for bt in bts for tc in (4, 2, 1) if feasible(bt, tc)]


def _pick_tiles_bwd(batch: int, hidden: int, gate_dim: int,
                    itemsize: int) -> Tuple[int, int]:
    """The adjoint's tile, from the same sweep as `_pick_tiles` (v5e,
    PR 31, B104 x T67 bf16, ms a window): H=2500 bt112/tc1 **3.60**,
    bt56/tc1 3.94, bt56/tc2 3.98, bt16 5.64-5.71, bt8 9.7; H=800
    bt112/tc1 = bt112/tc2 **0.690**, bt56 0.755-0.764, bt16 1.02, bt8
    1.6. Past the stream budget bt112/tc2 and bt112/tc4 compile and read
    3.65 / 3.66: nothing to gain there. Largest batch tile, then the
    smallest time chunk (inside `train_steps` the kernel alone is 34-36 us
    a timestep at H=2500)."""
    cands = feasible_tiles_bwd(batch, hidden, gate_dim, itemsize)
    if not cands:
        _, _, bts = _sublane_snap(batch, itemsize)
        return bts[-1], 1
    return max(cands, key=lambda c: (c[0], -c[1]))


def _bwd_kernel(t_real, gates_ref, c_prev_ref, d_out_ref, w_hh_ref,
                dht_ref, dct_ref, dz_ref, dh0_ref, dc0_ref, dh_scr, dc_scr):
    """Time-REVERSED walk: the index maps hand this kernel the chunks in
    reverse order (grid time step 0 sees the last chunk), the carry
    (dh, dc) lives in f32 VMEM scratch, and W_hh stays resident for the
    per-step ``dz @ W_hh`` — the same residency win as the forward."""
    t_chunk = gates_ref.shape[0]
    n_tc = pl.num_programs(1)
    t_base = (n_tc - 1 - pl.program_id(1)) * t_chunk

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dh_scr[:] = dht_ref[:].astype(jnp.float32)
        dc_scr[:] = dct_ref[:].astype(jnp.float32)

    def step(j, _):
        i = t_chunk - 1 - j  # walk the chunk backwards
        H = dh_scr.shape[-1]
        g = gates_ref[i].astype(jnp.float32)
        i_t = g[:, :H]
        f_t = g[:, H:2 * H]
        g_t = g[:, 2 * H:3 * H]
        o_t = g[:, 3 * H:]
        c_prev = c_prev_ref[i].astype(jnp.float32)
        # recompute c_t from the streamed pre-step cell state: cheaper
        # than streaming a second (T, B, H) array from HBM.
        c_t = f_t * c_prev + i_t * g_t
        tanh_c = jnp.tanh(c_t)
        dh = dh_scr[:] + d_out_ref[i].astype(jnp.float32)
        do = dh * tanh_c
        dc = dc_scr[:] + dh * o_t * (1.0 - tanh_c * tanh_c)
        dzi = (dc * g_t) * i_t * (1.0 - i_t)
        dzf = (dc * c_prev) * f_t * (1.0 - f_t)
        dzg = (dc * i_t) * (1.0 - g_t * g_t)
        dzo = do * o_t * (1.0 - o_t)
        dz = jnp.concatenate([dzi, dzf, dzg, dzo], axis=-1)
        # keep the resident W in its storage dtype on the MXU (an
        # astype here would materialize a ~100MB f32 copy of the 50MB
        # bf16 flagship W_hh inside the VMEM scope); f32 accumulation
        # comes from preferred_element_type, as in the forward.
        dh_prev = jnp.dot(dz.astype(w_hh_ref.dtype), w_hh_ref[:],
                          preferred_element_type=jnp.float32)
        dc_prev = dc * f_t
        live = (t_base + i) < t_real  # zero-padded tail: inert
        dz_ref[i] = jnp.where(live, dz, 0.0).astype(dz_ref.dtype)
        dh_scr[:] = jnp.where(live, dh_prev, dh_scr[:])
        dc_scr[:] = jnp.where(live, dc_prev, dc_scr[:])
        return 0

    lax.fori_loop(0, t_chunk, step, 0)
    dh0_ref[:] = dh_scr[:].astype(dh0_ref.dtype)
    dc0_ref[:] = dc_scr[:].astype(dc0_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "tiles", "t_real"))
def fused_lstm_backward(
    gates: jnp.ndarray,
    c_prev_seq: jnp.ndarray,
    d_out: jnp.ndarray,
    w_hh: jnp.ndarray,
    d_h_t: jnp.ndarray,
    d_c_t: jnp.ndarray,
    interpret: bool = False,
    tiles: "Tuple[int, int] | None" = None,
    t_real: "int | None" = None,
):
    """Weights-resident adjoint over a window (time-major).

    Args:
      gates: ``(T, B, 4H)`` post-activation gates from the forward.
      c_prev_seq: ``(T, B, H)`` pre-step cell states from the forward.
      d_out: ``(T, B, H)`` output cotangent.
      w_hh: ``(4H, H)`` recurrent weights (the same DropConnect-masked
        tensor the forward ran with).
      d_h_t, d_c_t: ``(B, H)`` final-state cotangents.
      t_real: as in :func:`fused_lstm_forward` (steps past it emit zero
        ``dz`` and leave the carry alone).

    Returns:
      ``(dz (T, B, 4H) pre-activation grads, dh0, dc0)``.
    """
    T, B, G = gates.shape
    H = G // 4
    dtype = gates.dtype
    bt, tc = tiles or _pick_tiles_bwd(B, H, G, dtype.itemsize)
    sub, _, _ = _sublane_snap(B, dtype.itemsize)

    def pad3(a):
        return _pad_axis(_pad_axis(_pad_axis(a, 0, tc), 1, sub), 1, bt)

    gates_p = pad3(gates)
    c_prev_p = pad3(c_prev_seq.astype(dtype))
    d_out_p = pad3(d_out.astype(dtype))
    dht_p = _pad_axis(_pad_axis(d_h_t.astype(dtype), 0, sub), 0, bt)
    dct_p = _pad_axis(_pad_axis(d_c_t.astype(dtype), 0, sub), 0, bt)
    Tp, Bp = gates_p.shape[0], gates_p.shape[1]
    grid = (Bp // bt, Tp // tc)
    n_tc = Tp // tc

    # Reversed index maps: grid time step t receives chunk n_tc-1-t.
    def rev_seq(block_h):
        return pl.BlockSpec((tc, bt, block_h),
                            lambda b, t: (n_tc - 1 - t, b, 0),
                            memory_space=pltpu.VMEM)

    state_block = pl.BlockSpec((bt, H), lambda b, t: (b, 0),
                               memory_space=pltpu.VMEM)
    in_specs = [
        rev_seq(G),  # gates
        rev_seq(H),  # c_prev
        rev_seq(H),  # d_out
        pl.BlockSpec((G, H), lambda b, t: (0, 0), memory_space=pltpu.VMEM),
        state_block, state_block,
    ]
    out_specs = [rev_seq(G), state_block, state_block]
    out_shape = [
        jax.ShapeDtypeStruct((Tp, Bp, G), dtype),
        jax.ShapeDtypeStruct((Bp, H), dtype),
        jax.ShapeDtypeStruct((Bp, H), dtype),
    ]
    scratch = [pltpu.VMEM((bt, H), jnp.float32),
               pltpu.VMEM((bt, H), jnp.float32)]

    dz, dh0, dc0 = pl.pallas_call(
        functools.partial(_bwd_kernel, T if t_real is None else t_real),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_BWD_COMPILER_PARAMS,
        interpret=interpret,
    )(gates_p, c_prev_p, d_out_p, w_hh.astype(dtype), dht_p, dct_p)
    return dz[:T, :B], dh0[:B], dc0[:B]


def _bwd(interpret, res, cts):
    """LSTM adjoint: the sequential dh/dc recurrence runs in the
    weights-resident Pallas kernel (interpret mode off-TPU), emitting the
    pre-activation grads ``dz``; the weight/bias/input gradients are the
    big batched einsums XLA already does at high MFU."""
    x, h0, c0, w_ih, w_hh, bias, out_p, gates_p, c_prev_p = res
    d_out, (d_h_t, d_c_t) = cts
    f32 = jnp.float32
    B, T, _ = x.shape
    tp, bp, _ = gates_p.shape

    interpret = interpret or jax.default_backend() != "tpu"
    # the cotangents (H wide, 35 MB) are padded; the residuals (4H wide)
    # arrive padded. Padded rows and steps carry zero cotangent, so their
    # dz is zero and they are sliced off before the einsums below.
    pad_b = ((0, bp - B), (0, 0))
    dz_p, dh0, dc0 = fused_lstm_backward(
        gates_p, c_prev_p,
        jnp.pad(d_out.swapaxes(0, 1), ((0, tp - T), (0, bp - B), (0, 0))),
        w_hh, jnp.pad(d_h_t, pad_b), jnp.pad(d_c_t, pad_b),
        interpret=interpret, t_real=T,
    )
    dz = dz_p[:T, :B].astype(f32)
    dh0, dc0 = dh0[:B], dc0[:B]
    out_tm = out_p[:T, :B]
    h_prev = jnp.concatenate(
        [h0.astype(f32)[None], out_tm.astype(f32)[:-1]], axis=0)

    # weight/bias/input grads: big batched matmuls (MXU work). The f32
    # upcasts cost nothing on the chip: XLA folds them into the matmuls'
    # operands (PR 31 ran these einsums on the bf16 operands instead and
    # every one of them read the same to 0.01 ms).
    d_w_hh = jnp.einsum("tbg,tbh->gh", dz, h_prev)
    d_bias = dz.sum(axis=(0, 1))
    d_w_ih = jnp.einsum("tbg,bti->gi", dz, x.astype(f32))
    d_x = jnp.einsum("tbg,gi->bti", dz, w_ih.astype(f32))

    return (
        d_x.astype(x.dtype),
        (dh0.astype(h0.dtype), dc0.astype(c0.dtype)),
        d_w_ih.astype(w_ih.dtype),
        d_w_hh.astype(w_hh.dtype),
        d_bias.astype(bias.dtype),
    )


lstm_layer_fused.defvjp(_fwd, _bwd)
