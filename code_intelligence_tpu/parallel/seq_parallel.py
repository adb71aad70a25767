"""Sequence/context parallelism for the QRNN recurrence.

The reference handles long sequences with truncated BPTT + carried state
only (SURVEY.md §2.5: SP/CP "absent"; §5: "if sequence-dim sharding is
ever wanted, QRNN/blockwise scan is the natural form"). This module IS
that form, TPU-first: the forget-mult recurrence

    h_t = f_t * h_{t-1} + (1 - f_t) * z_t

is an affine map in ``h``, and affine maps compose associatively — so the
TIME axis itself can be sharded over a mesh axis. Each device runs a
log-depth local prefix scan over its time block, the per-block summaries
``(A, B)`` (product of gates, block output from zero state) are
all-gathered over ICI — 2·B·H values per device, tiny — and the carry
into each block is composed locally; one fused correction
``h = B_t + A_t·h_in`` finishes the job. Total comms: one all-gather of
``(B, H)`` pairs per layer per window, no ring required (an LSTM cannot
do this — its recurrence is non-linear in ``h``, which is why the LSTM
path shards batch-of-streams instead).

``window=2`` convolutions exchange a one-step halo with ``ppermute``
(each device sends its last timestep to its right neighbor), keeping the
fastai layer-0 convolution exact across shard boundaries.

Everything is built on ``shard_map`` + XLA collectives over the mesh —
differentiable end to end, value AND gradient parity tested against the
single-device scan (`tests/test_seq_parallel.py`). Compiled programs are
cached per ``(mesh, axis, window)`` so repeated calls (per layer, per
BPTT window) hit the jit cache instead of retracing.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def _local_prefix(z: jnp.ndarray, f: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-position (A_t, B_t) of the affine composition over the local
    block, from zero initial state: ``h_t = B_t + A_t * h_in``."""
    a = f
    b = (1.0 - f) * z

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    A, B = lax.associative_scan(combine, (a, b), axis=1)
    return A, B


def _carry_fold(A: jnp.ndarray, Bv: jnp.ndarray, h0_rep: jnp.ndarray, axis: str):
    """The cross-device carry composition both entry points share: gather
    per-block summaries, fold blocks-before-mine into ``h_in``, fold ALL
    blocks into the global final state ``h_T`` (replicated)."""
    a_seg, b_seg = A[:, -1], Bv[:, -1]  # (B, H) block summary
    n = lax.psum(1, axis)
    idx = lax.axis_index(axis)
    a_all = lax.all_gather(a_seg, axis)  # (n, B, H)
    b_all = lax.all_gather(b_seg, axis)

    def fold(k, h):
        return jnp.where(k < idx, a_all[k] * h + b_all[k], h)

    h_in = lax.fori_loop(0, n, fold, h0_rep)

    def fold_all(k, hh):
        return a_all[k] * hh + b_all[k]

    h_T = lax.fori_loop(0, n, fold_all, h0_rep)
    return h_in, h_T


# program cache: (kind, mesh, axis, window) -> jitted shard_map callable.
# Bounded LRU (serve_shard.ProgramCache): the keys hold live Mesh
# objects, and the old unbounded dict pinned every distinct mesh's
# compiled programs (and its device references) forever — a sweep or a
# test suite building many meshes grew it without end. An evicted key
# costs one re-trace on reuse, never a correctness change.
from code_intelligence_tpu.parallel.serve_shard import ProgramCache

_PROGRAM_CACHE_SIZE = 16
_PROGRAMS = ProgramCache(maxsize=_PROGRAM_CACHE_SIZE)


def _forget_mult_program(mesh: Mesh, axis: str, batch_axis: Optional[str] = None):
    key = ("fm", mesh, axis, batch_axis)

    def build():
        def body(z_blk, f_blk, h0_rep):
            A, Bv = _local_prefix(z_blk, f_blk)
            h_in, _ = _carry_fold(A, Bv, h0_rep, axis)
            return Bv + A * h_in[:, None, :]

        spec = P(batch_axis, axis, None)
        # check_vma=False: the carry fold mixes replicated (h0) and
        # gathered values, which the varying-axes checker can't type
        return jax.jit(
            jax.shard_map(
                body, mesh=mesh, in_specs=(spec, spec, P(batch_axis, None)),
                out_specs=spec, check_vma=False,
            )
        )

    return _PROGRAMS.get(key, build)


def _qrnn_program(mesh: Mesh, axis: str, window: int,
                  batch_axis: Optional[str] = None):
    key = ("qrnn", mesh, axis, window, batch_axis)

    def build():
        def body(x_blk, w, b, h0_rep, x_prev_rep):
            if window == 2:
                n = lax.psum(1, axis)
                idx = lax.axis_index(axis)
                # halo: receive the previous device's last timestep
                last = x_blk[:, -1]
                from_left = lax.ppermute(
                    last, axis, [(i, (i + 1) % n) for i in range(n)]
                )
                first = jnp.where(idx == 0, x_prev_rep, from_left)
                prev = jnp.concatenate([first[:, None], x_blk[:, :-1]], axis=1)
                x_in = jnp.concatenate([prev, x_blk], axis=-1)
            else:
                x_in = x_blk
            gates = jnp.einsum("bti,gi->btg", x_in, w) + b
            z, fg, o = jnp.split(gates, 3, axis=-1)
            z = jnp.tanh(z)
            fg = jax.nn.sigmoid(fg)
            o = jax.nn.sigmoid(o)

            A, Bv = _local_prefix(z, fg)
            h_in, h_T = _carry_fold(A, Bv, h0_rep, axis)
            h = Bv + A * h_in[:, None, :]
            return o * h, h_T

        spec = P(batch_axis, axis, None)
        return jax.jit(
            jax.shard_map(
                body, mesh=mesh,
                in_specs=(spec, P(None, None), P(None,),
                          P(batch_axis, None), P(batch_axis, None)),
                out_specs=(spec, P(batch_axis, None)), check_vma=False,
            )
        )

    return _PROGRAMS.get(key, build)


def forget_mult_seq_parallel(
    z: jnp.ndarray,
    f: jnp.ndarray,
    h0: Optional[jnp.ndarray] = None,
    *,
    mesh: Mesh,
    axis: str = "seq",
    batch_axis: Optional[str] = None,
) -> jnp.ndarray:
    """forget-mult with the TIME axis sharded over ``mesh[axis]``.

    Args:
      z, f: ``(B, T, H)`` global arrays, sharded ``P(batch_axis, axis, None)``.
      h0: optional ``(B, H)`` initial state (replicated over ``axis``).
      batch_axis: optional mesh axis the batch dim is sharded over (DP x SP
        composition — each batch shard runs its own independent carry fold).

    Returns ``(B, T, H)`` hidden states, same sharding as ``z``.
    """
    B, _, H = z.shape
    if h0 is None:
        h0 = jnp.zeros((B, H), z.dtype)
    return _forget_mult_program(mesh, axis, batch_axis)(z, f, h0)


def qrnn_layer_seq_parallel(
    x: jnp.ndarray,
    params: dict,
    h0: Optional[jnp.ndarray] = None,
    *,
    mesh: Mesh,
    axis: str = "seq",
    window: int = 1,
    x_prev: Optional[jnp.ndarray] = None,
    batch_axis: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One QRNN layer (fo-pooling) with the time axis sharded.

    Same contract as `ops.qrnn.qrnn_layer`; gate projections run
    time-parallel on each shard (weights replicated), ``window=2`` gets
    its ``x_{t-1}`` from a right-shift ppermute halo exchange.
    ``batch_axis`` composes with data parallelism (see
    `forget_mult_seq_parallel`).
    """
    B, T, in_dim = x.shape
    H = params["w"].shape[0] // 3
    if h0 is None:
        h0 = jnp.zeros((B, H), x.dtype)
    if x_prev is None:
        x_prev = jnp.zeros((B, in_dim), x.dtype)
    if window not in (1, 2):
        raise ValueError(f"window must be 1 or 2, got {window}")
    return _qrnn_program(mesh, axis, window, batch_axis)(
        x, params["w"], params["b"], h0, x_prev)


def shard_time(x: jnp.ndarray, mesh: Mesh, axis: str = "seq") -> jnp.ndarray:
    """Place ``(B, T, ...)`` with the time axis sharded over ``mesh[axis]``."""
    return jax.device_put(x, NamedSharding(mesh, P(None, axis, None)))
