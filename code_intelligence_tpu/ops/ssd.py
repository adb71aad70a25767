"""State-space layer pieces with MATRIX state (Mamba-2 / SSD).

The recurrence, per head ``h`` with state ``S[h]`` of shape ``(P, N)``:

    S_t[h] = exp(dt_t[h] * A[h]) * S_{t-1}[h] + dt_t[h] * outer(x_t[h], B_t)
    y_t[h] = S_t[h] @ C_t + D[h] * x_t[h]

is linear in ``S``, so a chunk of ``Q`` tokens needs the state only at
its edges (Dao & Gu 2024, "state space duality"): inside a chunk the
outputs are a masked ``(Q, Q)`` attention-like product on the MXU, and
the state moves from chunk to chunk by one decay-and-add. ``ssd_scan``
takes the state in and hands it back, so a document can cross compiled
programs (`inference/engine.py`'s chunk loop) as well as chunks.

``causal_conv1d`` is the depthwise convolution in front of the scan; its
carry is the last ``K - 1`` inputs.

float32 holds the decay's ``exp``, the cumulative sums and the state;
the ``(Q, Q)`` product's inputs go to the MXU in ``mxu_dtype``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST


def ssd_scan(
    x: jnp.ndarray,      # (b, T, H, P)
    dt: jnp.ndarray,     # (b, T, H) float32, after softplus
    A: jnp.ndarray,      # (H,) float32, negative
    B: jnp.ndarray,      # (b, T, N)   (one group: shared by all heads)
    C: jnp.ndarray,      # (b, T, N)
    D: jnp.ndarray,      # (H,)
    state: jnp.ndarray,  # (b, H, P, N) float32
    chunk: int,
    mxu_dtype=jnp.bfloat16,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(y (b, T, H, P) float32, new state)``: the recurrence above over
    ``T`` tokens in chunks of ``chunk``, starting from ``state``."""
    b, T, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        # dt = 0 past the end: decay 1 and no input, the state stands
        x, dt, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                       for a in (x, dt, B, C))
    nc = (T + pad) // Q
    f32 = jnp.float32
    xc = x.reshape(b, nc, Q, H, P).astype(f32)
    dtc = dt.reshape(b, nc, Q, H).astype(f32)
    Bc = B.reshape(b, nc, Q, N)
    Cc = C.reshape(b, nc, Q, N)

    cs = jnp.cumsum(dtc * A.astype(f32), axis=2)       # (b, nc, Q, H), <= 0
    xdt = xc * dtc[..., None]                           # (b, nc, Q, H, P)

    # inside a chunk: y_i += sum_{j <= i} (C_i . B_j) exp(cs_i - cs_j) dt_j x_j
    G = jnp.einsum("bcin,bcjn->bcij", Cc.astype(mxu_dtype),
                   Bc.astype(mxu_dtype), preferred_element_type=f32)
    csh = cs.transpose(0, 1, 3, 2)                      # (b, nc, H, Q)
    seg = csh[..., :, None] - csh[..., None, :]         # (b, nc, H, Q, Q)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    M = (G[:, :, None] * L).astype(mxu_dtype)
    y = jnp.einsum("bchij,bcjhp->bcihp", M, xdt.astype(mxu_dtype),
                   preferred_element_type=f32)

    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)             # (b, nc, Q, H)
    S_local = jnp.einsum("bcjhp,bcjn->bchpn", xdt * to_end[..., None],
                         Bc.astype(f32), precision=_HIGHEST)
    chunk_decay = jnp.exp(cs[:, :, -1, :])              # (b, nc, H)

    # the state at each chunk's start (nc is 1 or 2 at serve shapes)
    S_in = []
    for c in range(nc):
        S_in.append(state)
        state = state * chunk_decay[:, c, :, None, None] + S_local[:, c]
    S_in = jnp.stack(S_in, axis=1)                      # (b, nc, H, P, N)
    y = y + jnp.einsum("bcin,bchpn->bcihp", Cc.astype(f32), S_in,
                       precision=_HIGHEST) * jnp.exp(cs)[..., None]
    y = y + D.astype(f32)[:, None] * xc
    return y.reshape(b, nc * Q, H, P)[:, :T], state


def ssd_recurrence(x, dt, A, B, C, D, state):
    """The same layer token by token (a ``lax.scan`` over ``T``), all in
    float32: what ``ssd_scan`` is tested against."""
    f32 = jnp.float32
    hi = _HIGHEST

    def step(S, inp):
        xt, dtt, Bt, Ct = inp                           # (b,H,P) (b,H) (b,N)
        decay = jnp.exp(dtt * A)[..., None, None]
        S = decay * S + jnp.einsum("bhp,bn->bhpn", xt * dtt[..., None], Bt,
                                   precision=hi)
        yt = jnp.einsum("bhpn,bn->bhp", S, Ct, precision=hi) \
            + D[:, None] * xt
        return S, yt

    seq = (x.astype(f32).swapaxes(0, 1), dt.astype(f32).swapaxes(0, 1),
           B.astype(f32).swapaxes(0, 1), C.astype(f32).swapaxes(0, 1))
    state, ys = lax.scan(step, state.astype(f32), seq)
    return ys.swapaxes(0, 1), state


def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, bias: jnp.ndarray,
                  tail: jnp.ndarray, lengths=None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal convolution over time. ``x`` ``(b, T, C)``, ``w``
    ``(C, K)`` (``w[:, K-1]`` meets the current token), ``tail``
    ``(b, K-1, C)``: the inputs just before ``x``. Returns the float32
    output ``(b, T, C)`` and the new tail (``x``'s dtype): the last
    ``K - 1`` inputs, or with ``lengths`` ``(b,)`` the ``K - 1`` before
    each row's valid end (a row of padding alone keeps its tail)."""
    K = w.shape[1]
    T = x.shape[1]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # (b, T+K-1, C)
    wf = w.astype(jnp.float32)
    out = bias.astype(jnp.float32)
    for k in range(K):
        out = out + xp[:, k:k + T].astype(jnp.float32) * wf[:, k]
    if lengths is None:
        return out, xp[:, T:]
    return out, jax.vmap(
        lambda row, n: lax.dynamic_slice_in_dim(row, n, K - 1))(xp, lengths)
