"""Pallas TPU kernels for the QRNN forget-mult (forward + fused backward).

The reference's one custom GPU kernel is fastai's QRNN ``forget_mult``
CUDA op (`Issue_Embeddings/train.py:53-54,73`; SURVEY.md §2.4 row 2).
The XLA-level rebuild in :mod:`ops.qrnn` uses ``lax.associative_scan`` —
log(T) passes that each read and write O(B·T·H) from HBM. These kernels
do the recurrence

    h_t = f_t * h_{t-1} + (1 - f_t) * z_t

in **one** HBM pass per direction: the grid tiles (batch × hidden); each
program pulls its ``(T, bt, 128)`` block of ``z``/``f`` into VMEM, runs
the sequential T-loop on the VPU with ``h`` carried in f32, and writes
``h`` back once. Time stays sequential (a true recurrence) but every
(batch, hidden) tile is independent.

Layout history (round-4 VERDICT item 3): the round-3 kernel was
batch-major ``(B, T, H)`` with a dynamic MIDDLE-axis slice
``f_ref[:, t, :]`` — proven on chip to crash the Mosaic compiler for
bf16 (a ``vector<8x1x128xbf16>`` load; bf16's (16, 128) packed tiling
cannot express the sub-sublane slice), which forced an f32 upcast that
doubled streamed bytes on a bandwidth-bound op. This rewrite speaks
TIME-MAJOR ``(T, B, H)`` like the fused LSTM kernel
(`ops/pallas_lstm.py`): the per-step dynamic index sits on the LEADING
block axis, every accessed tile is a plain ``(bt, 128)`` 2-D tile, and
the batch tile is snapped to the dtype's sublane multiple (bf16: 16) —
the exact layout recipe that made the LSTM kernel compile and win in
bf16 on v5e. Gate math runs in f32 inside the kernel (Mosaic rejects
weak-typed f32 constants broadcast into bf16 vectors; f32 accumulation
is numerically better regardless); only the stores cast back.

Training: :func:`forget_mult_fused` wraps forward+backward in a
``custom_vjp``. The adjoint of the affine recurrence is itself an
affine recurrence run in reverse —

    s_t = g_t + f_{t+1} * s_{t+1}        (g = output cotangent)
    dz_t = s_t * (1 - f_t)
    df_t = s_t * (h_{t-1} - z_t)
    dh0  = f_0 * s_0

— so the backward kernel walks the SAME VMEM-resident tiles in reverse
with ``s`` carried in f32, emitting dz/df/dh0 in one pass (the round-3
kernel had no VJP at all: gradients could not flow through the Pallas
path, so ``--qrnn_pallas`` training silently required the scan).

``interpret=True`` runs the same kernels on CPU for the parity tests
(tests/test_pallas.py: values AND gradients vs the associative scan).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128  # last-dim tile (all dtypes)
# Streamed-VMEM budget per grid program: one copy of every streamed
# ``(T, bt, 128)`` block; bounds the batch tile so long-T windows
# (sequence-parallel locals) still fit.
_STREAM_BUDGET = 12 * 1024 * 1024
# Scoped-VMEM limit. The Pallas pipeline double-buffers every streamed
# block, so the kernel needs twice the budget plus its f32 temporaries:
# on the v5e the backward at the flagship train shape (6 streams,
# T=67, bt=112, bf16: 11.5 MB counted) asked Mosaic for 22.09 MB and was
# refused under a budget+8 MB limit (PR 21 chip run). Embedded in
# jit(train_step) the kernel would otherwise inherit XLA's 16 MB default.
_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=2 * _STREAM_BUDGET + 8 * 1024 * 1024)


def _sublane(itemsize: int) -> int:
    return 16 if itemsize == 2 else 8


def fits_stream_budget(seq_len: int, itemsize: int) -> bool:
    """True when even the minimum batch tile (one sublane group — the
    padded batch is always a multiple of it) keeps the kernels' streamed
    ``(T, bt, 128)`` blocks inside the VMEM budget — checked for the
    BACKWARD pass (6 streams), the wider of the two, so a shape that
    forward-compiles can't fail later in grad."""
    sub = _sublane(itemsize)
    return 6 * seq_len * sub * _LANE * itemsize <= _STREAM_BUDGET


def _pick_block_b(batch_padded: int, seq_len: int, itemsize: int,
                  n_streams: int) -> int:
    """Largest sublane-multiple divisor of the padded batch whose
    ``n_streams`` ``(T, bt, 128)`` blocks fit the stream budget.

    Raises when nothing fits: silently returning the smallest tile let
    Mosaic fail compilation downstream on long-T bf16 inputs — callers
    gate on :func:`fits_stream_budget` and fall back
    to the associative scan instead of reaching this error.
    """
    sub = _sublane(itemsize)
    cands = [b for b in range(batch_padded, sub - 1, -sub)
             if batch_padded % b == 0]
    for bt in cands:
        if n_streams * seq_len * bt * _LANE * itemsize <= _STREAM_BUDGET:
            return bt
    raise ValueError(
        f"forget-mult Pallas kernel cannot tile T={seq_len} itemsize="
        f"{itemsize} within the {_STREAM_BUDGET // (1024*1024)}MB VMEM "
        f"stream budget even at the minimum batch tile ({sub}); use the "
        f"associative scan (ops.qrnn.forget_mult) for this shape")


def _fwd_kernel(z_ref, f_ref, h0_ref, out_ref, *, seq_len: int):
    h = h0_ref[:, :].astype(jnp.float32)

    def step(t, h):
        ft = f_ref[t].astype(jnp.float32)
        zt = z_ref[t].astype(jnp.float32)
        h = ft * h + (1.0 - ft) * zt
        out_ref[t] = h.astype(out_ref.dtype)
        return h

    lax.fori_loop(0, seq_len, step, h)


def _bwd_kernel(z_ref, f_ref, h_ref, h0_ref, g_ref,
                dz_ref, df_ref, dh0_ref, *, seq_len: int):
    """Reverse walk of the adjoint recurrence; carry ``c = f_{t+1}·s_{t+1}``
    in f32 (init 0 — the last output's cotangent arrives through g)."""
    c = jnp.zeros(dh0_ref.shape, jnp.float32)

    def step(j, c):
        t = seq_len - 1 - j
        s = c + g_ref[t].astype(jnp.float32)
        ft = f_ref[t].astype(jnp.float32)
        zt = z_ref[t].astype(jnp.float32)
        # h_{t-1}: the stored output for t>0, else the initial state. The
        # dynamic index stays on the LEADING axis (max keeps it in range;
        # the where discards the t=0 misread).
        h_prev = jnp.where(
            t > 0,
            h_ref[jnp.maximum(t - 1, 0)].astype(jnp.float32),
            h0_ref[:, :].astype(jnp.float32),
        )
        dz_ref[t] = (s * (1.0 - ft)).astype(dz_ref.dtype)
        df_ref[t] = (s * (h_prev - zt)).astype(df_ref.dtype)
        return ft * s

    c = lax.fori_loop(0, seq_len, step, c)
    dh0_ref[:, :] = c.astype(dh0_ref.dtype)


def _fwd_kernel_ragged(z_ref, f_ref, h0_ref, valid_ref, out_ref, *,
                       seq_len: int):
    """Length-aware forward walk: ``valid_ref`` is a lane-broadcast
    ``(bt, 128)`` int32 block of per-row valid lengths. The sequential
    loop runs only to the tile's max valid length (dynamic trip count —
    a tile of exhausted rows does no recurrence work); the dead tail is
    filled with plain stores of each row's FROZEN CARRY — so the output
    block is always defined and finite for the masked pooled consumer,
    and ``out[-1]`` is every row's state after exactly ``min(valid, T)``
    real steps (the ``h_T`` contract ``qrnn_layer`` reads off the last
    output). Rows past their own valid length freeze their carry within
    a live prefix too."""
    h = h0_ref[:, :].astype(jnp.float32)
    valid_col = valid_ref[:, :1]  # (bt, 1)
    block_max = jnp.minimum(jnp.max(valid_ref[:, 0]), seq_len)

    def step(t, h):
        ft = f_ref[t].astype(jnp.float32)
        zt = z_ref[t].astype(jnp.float32)
        h_new = ft * h + (1.0 - ft) * zt
        live = t < valid_col
        h = jnp.where(live, h_new, h)
        out_ref[t] = h.astype(out_ref.dtype)
        return h

    h = lax.fori_loop(0, block_max, step, h)
    h_frozen = h.astype(out_ref.dtype)

    def carry_tail(t, _):
        out_ref[t] = h_frozen
        return 0

    lax.fori_loop(block_max, seq_len, carry_tail, 0)


def _pad_tm(a: jnp.ndarray, bt: int, sub: int) -> jnp.ndarray:
    """Pad a time-major (T, B, H) array: B to the sublane-snapped tile
    multiple, H to the lane tile."""
    pb = (-a.shape[1]) % sub
    pb += (-(a.shape[1] + pb)) % bt
    ph = (-a.shape[2]) % _LANE
    if pb or ph:
        a = jnp.pad(a, ((0, 0), (0, pb), (0, ph)))
    return a


def _pad_state(a: jnp.ndarray, b_target: int, h_target: int) -> jnp.ndarray:
    pb, ph = b_target - a.shape[0], h_target - a.shape[1]
    if pb or ph:
        a = jnp.pad(a, ((0, pb), (0, ph)))
    return a


@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward_tm(z_tm, f_tm, h0, interpret: bool = False):
    T, B, H = z_tm.shape
    dtype = z_tm.dtype
    sub = _sublane(dtype.itemsize)
    bp = -(-B // sub) * sub
    bt = _pick_block_b(bp, T, dtype.itemsize, n_streams=3)
    z_p = _pad_tm(z_tm, bt, sub)
    # zero-padded f and z -> padded lanes run h = 0*h + 1*0 = 0; the
    # padded region is sliced away below and h0's padding is also zero,
    # so no invariant depends on the padded values
    f_p = _pad_tm(f_tm, bt, sub)
    Bp, Hp = z_p.shape[1], z_p.shape[2]
    h0_p = _pad_state(h0.astype(dtype), Bp, Hp)

    grid = (Bp // bt, Hp // _LANE)
    seq_spec = pl.BlockSpec((T, bt, _LANE), lambda i, j: (0, i, j),
                            memory_space=pltpu.VMEM)
    state_spec = pl.BlockSpec((bt, _LANE), lambda i, j: (i, j),
                              memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, seq_len=T),
        grid=grid,
        in_specs=[seq_spec, seq_spec, state_spec],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((T, Bp, Hp), dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(z_p, f_p, h0_p)
    return out[:, :B, :H]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward_tm_ragged(z_tm, f_tm, h0, valid_lens, interpret: bool = False):
    """Ragged forward (time-major). Inference only — no VJP: the ragged
    path exists for the serve loop, which never differentiates."""
    T, B, H = z_tm.shape
    dtype = z_tm.dtype
    sub = _sublane(dtype.itemsize)
    bp = -(-B // sub) * sub
    bt = _pick_block_b(bp, T, dtype.itemsize, n_streams=3)
    z_p = _pad_tm(z_tm, bt, sub)
    f_p = _pad_tm(f_tm, bt, sub)
    Bp, Hp = z_p.shape[1], z_p.shape[2]
    h0_p = _pad_state(h0.astype(dtype), Bp, Hp)
    # padding rows carry valid 0: dead lanes, never recurrence work
    valid_p = jnp.zeros((Bp,), jnp.int32).at[:B].set(
        valid_lens.astype(jnp.int32).reshape(-1))
    valid2d = jnp.broadcast_to(valid_p[:, None], (Bp, _LANE))

    grid = (Bp // bt, Hp // _LANE)
    seq_spec = pl.BlockSpec((T, bt, _LANE), lambda i, j: (0, i, j),
                            memory_space=pltpu.VMEM)
    state_spec = pl.BlockSpec((bt, _LANE), lambda i, j: (i, j),
                              memory_space=pltpu.VMEM)
    valid_spec = pl.BlockSpec((bt, _LANE), lambda i, j: (i, 0),
                              memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel_ragged, seq_len=T),
        grid=grid,
        in_specs=[seq_spec, seq_spec, state_spec, valid_spec],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((T, Bp, Hp), dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(z_p, f_p, h0_p, valid2d)
    return out[:, :B, :H]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward_tm(z_tm, f_tm, h_tm, h0, g_tm, interpret: bool = False):
    T, B, H = z_tm.shape
    dtype = z_tm.dtype
    sub = _sublane(dtype.itemsize)
    bp = -(-B // sub) * sub
    bt = _pick_block_b(bp, T, dtype.itemsize, n_streams=6)
    z_p = _pad_tm(z_tm, bt, sub)
    f_p = _pad_tm(f_tm, bt, sub)
    h_p = _pad_tm(h_tm, bt, sub)
    g_p = _pad_tm(g_tm, bt, sub)
    Bp, Hp = z_p.shape[1], z_p.shape[2]
    h0_p = _pad_state(h0.astype(dtype), Bp, Hp)

    grid = (Bp // bt, Hp // _LANE)
    seq_spec = pl.BlockSpec((T, bt, _LANE), lambda i, j: (0, i, j),
                            memory_space=pltpu.VMEM)
    state_spec = pl.BlockSpec((bt, _LANE), lambda i, j: (i, j),
                              memory_space=pltpu.VMEM)
    dz, df, dh0 = pl.pallas_call(
        functools.partial(_bwd_kernel, seq_len=T),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, state_spec, seq_spec],
        out_specs=[seq_spec, seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((T, Bp, Hp), dtype),
            jax.ShapeDtypeStruct((T, Bp, Hp), dtype),
            jax.ShapeDtypeStruct((Bp, Hp), dtype),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(z_p, f_p, h_p, h0_p, g_p)
    return dz[:, :B, :H], df[:, :B, :H], dh0[:B, :H]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def forget_mult_fused(z_tm, f_tm, h0, time_major: bool = True,
                      interpret: bool = False):
    """Differentiable Pallas forget-mult.

    Args (``time_major=True``, the native layout): ``z``/``f``
    ``(T, B, H)``, ``h0`` ``(B, H)`` (required — pass zeros for a cold
    start); returns ``(T, B, H)``. With ``time_major=False`` the wrapper
    transposes at the HBM boundary (three extra passes — prefer feeding
    time-major, which the gate einsum emits for free; see
    ``ops.qrnn.qrnn_layer``).
    """
    if not time_major:
        return _forward_tm(z_tm.swapaxes(0, 1), f_tm.swapaxes(0, 1), h0,
                           interpret=interpret).swapaxes(0, 1)
    return _forward_tm(z_tm, f_tm, h0, interpret=interpret)


def _fused_fwd(z, f, h0, time_major, interpret):
    out = forget_mult_fused(z, f, h0, time_major, interpret)
    return out, (z, f, h0, out)


def _fused_bwd(time_major, interpret, res, g):
    z, f, h0, h = res
    if not time_major:
        z, f, h, g = (a.swapaxes(0, 1) for a in (z, f, h, g))
    dz, df, dh0 = _backward_tm(z, f, h, h0, g, interpret=interpret)
    if not time_major:
        dz, df = dz.swapaxes(0, 1), df.swapaxes(0, 1)
    return dz, df, dh0.astype(h0.dtype)


forget_mult_fused.defvjp(_fused_fwd, _fused_bwd)


_warned_budget = False


def _warn_budget_once(seq_len: int, itemsize: int) -> None:
    global _warned_budget
    if not _warned_budget:
        import logging

        logging.getLogger(__name__).warning(
            "forget-mult T=%d itemsize=%d exceeds the Pallas VMEM stream "
            "budget at the minimum tile; falling back to the associative "
            "scan for this shape", seq_len, itemsize)
        _warned_budget = True


def forget_mult_pallas(
    z: jnp.ndarray,
    f: jnp.ndarray,
    h0: Optional[jnp.ndarray] = None,
    block_b: int = 0,  # kept for API compat; tile choice is automatic now
    interpret: bool = False,
    time_major: bool = False,
    valid_lens: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Drop-in replacement for :func:`ops.qrnn.forget_mult` on TPU
    (batch-major ``(B, T, H)`` by default, matching the scan's contract).
    Differentiable via the fused Pallas adjoint.

    Shapes whose streamed blocks cannot fit the VMEM budget even at the
    minimum batch tile (long-T bf16) fall back to the
    associative scan instead of failing Mosaic compilation; the decision
    is static in T/dtype, so it is jit-trace safe.

    ``valid_lens`` (``(B,) int32``, inference only — no VJP) selects the
    length-aware ragged kernel: a time-block tile whose rows are all
    exhausted does no recurrence work. Ragged contract: positions
    ``t < valid`` match the dense kernel exactly; positions beyond are
    unspecified-but-FINITE (the ragged kernel holds each row's frozen
    carry there — so ``out[-1]`` is the state after ``min(valid, T)``
    real steps — while the scan fallback leaves its dense values) —
    consumers mask by length, so only finiteness is promised beyond the
    prefix. On a budget fallback the scan runs dense: ragged is an
    optimization, never a shape error.
    """
    del block_b
    T = z.shape[0] if time_major else z.shape[1]
    if not fits_stream_budget(T, z.dtype.itemsize):
        from code_intelligence_tpu.ops.qrnn import forget_mult

        _warn_budget_once(T, z.dtype.itemsize)
        if time_major:
            out = forget_mult(z.swapaxes(0, 1), f.swapaxes(0, 1), h0)
            return out.swapaxes(0, 1)
        return forget_mult(z, f, h0)
    if h0 is None:
        B = z.shape[1] if time_major else z.shape[0]
        h0 = jnp.zeros((B, z.shape[2]), z.dtype)
    if valid_lens is not None:
        if time_major:
            return _forward_tm_ragged(z, f, h0, valid_lens,
                                      interpret=interpret)
        return _forward_tm_ragged(
            z.swapaxes(0, 1), f.swapaxes(0, 1), h0, valid_lens,
            interpret=interpret).swapaxes(0, 1)
    return forget_mult_fused(z, f, h0, time_major, interpret)


def forget_mult_auto(z, f, h0=None, prefer_pallas: bool = False,
                     time_major: bool = False):
    """Select the forget-mult implementation.

    The associative scan stays the default (log-depth but fully
    parallel); ``prefer_pallas``
    opts into the single-pass fused kernel (reachable via
    ``AWDLSTMConfig(qrnn_use_pallas=True)``) — compiled on TPU, interpret
    mode elsewhere, the SAME routing as ``qrnn_layer``'s fused branch so
    the two selectors cannot diverge. Both paths are parity-tested
    against each other, values and gradients (tests/test_pallas.py); no
    benchmark cell runs the kernel yet (ROADMAP S8).
    """
    from code_intelligence_tpu.ops.qrnn import _warn_interpret_once, forget_mult

    if prefer_pallas:
        interpret = jax.default_backend() != "tpu"
        if interpret:
            _warn_interpret_once()
        return forget_mult_pallas(z, f, h0, time_major=time_major,
                                  interpret=interpret)
    if time_major:
        out = forget_mult(z.swapaxes(0, 1), f.swapaxes(0, 1), h0)
        return out.swapaxes(0, 1)
    return forget_mult(z, f, h0)
