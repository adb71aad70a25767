"""Delta-rule linear attention with ONE decay a head and grouped value
heads (Gated DeltaNet, arXiv:2412.06464), matrix state in and out.

The recurrence, per VALUE head with state ``S`` of shape ``(dk, dv)``:

    S'  = exp(g_t) S_{t-1}                          g_t a scalar <= 0
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T           b_t in [0, 1]
    o_t = S_t^T q_t

``Hv`` value heads read ``Hk`` key heads: value head ``j`` takes the
``q`` and ``k`` of key head ``j // (Hv // Hk)`` and its own ``v``, ``g``
and ``b``. It is linear in ``S``, so a chunk of ``C`` tokens needs the
state only at its edges. With ``G_i = sum_{j <= i} g_j`` inside a chunk
and ``L_ij = exp(G_i - G_j)`` for ``i >= j``, 0 above the diagonal:

    A = strict_tril[b_i (k_i . k_j) L_ij]
    (I + A) D = diag(b) (V - e^{G} (K S))          what each token writes
    o = e^{G} (Q S) + tril[(Q K^T) * L] D
    S_next = e^{G_C} S + (K * e^{G_C - G})^T D

``ops/kda.py`` is the same rule with a decay PER CHANNEL, which has to
be folded into the operands and rescaled a sub-block at a time. A scalar
decay is a ``(C, C)`` factor on the products instead (what
``ops/ssd.py::ssd_scan`` does for its own): **every exponent formed here
is ``<= 0``**, whatever the gate (Gated DeltaNet's ``-exp(A_log) *
softplus(.)`` has no lower bound), and nothing is rescaled. ``k_i . k_j``
and ``q_i . k_j`` are one product a KEY head; its value heads weigh the
tile by their own ``b`` and ``L``. The decays cost ``(b, T, Hv)`` float32,
not a number a channel.

The triangular system is solved once a chunk for the identity
(``kda._solve_unit_lower``, forward substitution in float32: the one
copy), which gives ``(I + A)^-1`` ``(C, C)`` before any state is known
(``C`` columns where a solve for ``[W | U]`` has ``dk + dv``); the scan
over the chunks then multiplies it into the right-hand side the state
gives.

float32 holds the decays, the cumulative sums, the solve, the state and
every product that has the state as an operand; the in-chunk products
(``K K^T``, ``Q K^T``, the tile times ``D``) take ``mxu_dtype`` inputs
and accumulate in float32 (``ops/kda.py``'s rules). A lane with ``g = 0``
and ``b = 0`` decays nothing and writes nothing: the state stands
(padding), to the bit.

One core, plain XLA: every chunk's tiles at once, then a ``lax.scan``
over the chunks with the state. Its ``(b, chunks, Hv, C, C)`` float32
tiles are 67 MB a layer of a ``(16, 512)`` program at 32 value heads and
``C`` = 64 (the per-channel rule's XLA scan moved 12 GB there). There is
no Pallas kernel of this rule yet.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
from jax import lax

from code_intelligence_tpu.ops.kda import _solve_unit_lower

_HIGHEST = lax.Precision.HIGHEST

# rows of the diagonal blocks ``_solve_unit_lower`` inverts row by row
# (the sub-blocks of ``ops/kda.py``, whose sweep it was); it divides the
# chunk or the chunk is one block
_SOLVE_SUB = 16


def gdn_scan(
    q: jnp.ndarray,      # (b, T, Hk, dk), scaled by the caller
    k: jnp.ndarray,      # (b, T, Hk, dk)
    v: jnp.ndarray,      # (b, T, Hv, dv)
    g: jnp.ndarray,      # (b, T, Hv) float32 log-decay, <= 0
    beta: jnp.ndarray,   # (b, T, Hv) float32
    state: jnp.ndarray,  # (b, Hv, dk, dv) float32
    chunk: int = 64,
    mxu_dtype=jnp.bfloat16,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(o (b, T, Hv, dv) float32, new state)``: the recurrence above
    over ``T`` tokens in chunks of ``chunk``, starting from ``state``.
    ``Hk`` divides ``Hv``."""
    b, T, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    if Hv % Hk:
        raise ValueError(f"{Hk} key heads do not divide {Hv} value heads")
    rep = Hv // Hk
    C = min(chunk, T)
    pad = -T % C
    if pad:
        # g = 0 and beta = 0 past the end: no decay, no write
        q, k, v, g, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    nc = (T + pad) // C
    f32 = jnp.float32

    def heads_first(a, H):  # (b, nc * C, H, ...) -> (b, nc, H, C, ...)
        a = a.astype(f32).reshape((b, nc, C, H) + a.shape[3:])
        return jnp.moveaxis(a, 2, 3)

    qc, kc = heads_first(q, Hk), heads_first(k, Hk)    # (b, nc, Hk, C, dk)
    # a key head's value heads beside it: (b, nc, Hk, rep, C[, dv])
    vc = heads_first(v, Hv).reshape(b, nc, Hk, rep, C, dv)
    gc = heads_first(g, Hv).reshape(b, nc, Hk, rep, C)
    bc = heads_first(beta, Hv).reshape(b, nc, Hk, rep, C)

    G = jnp.cumsum(gc, axis=-1)                        # <= 0
    ones = jnp.ones((C, C), bool)
    L = jnp.exp(jnp.where(jnp.tril(ones), G[..., :, None] - G[..., None, :],
                          -jnp.inf))                   # (b, nc, Hk, rep, C, C)

    # the two tiles, one product a KEY head
    kk, qk = (jnp.einsum("bnhic,bnhjc->bnhij", a.astype(mxu_dtype),
                         kc.astype(mxu_dtype), preferred_element_type=f32)
              for a in (kc, qc))                       # (b, nc, Hk, C, C)
    A = jnp.where(jnp.tril(ones, -1), kk[:, :, :, None] * L, 0.0) \
        * bc[..., None]
    P = (qk[:, :, :, None] * L).astype(mxu_dtype)      # L is 0 above
    sub = _SOLVE_SUB if C % _SOLVE_SUB == 0 else C
    inv = _solve_unit_lower(
        A, jnp.broadcast_to(jnp.eye(C, dtype=f32), A.shape), sub)

    to_here = jnp.exp(G)                               # e^{G_i}
    G_end = G[..., -1:]
    to_end = jnp.exp(G_end - G)                        # e^{G_C - G_i}
    decay = jnp.exp(G_end)[..., None]                  # (b, nc, Hk, rep, 1, 1)

    def chunk_step(S, xs):
        q, k, v, beta, inv, P, to_here, to_end, decay = xs
        # the two products that read the state, in one
        met = jnp.einsum("bhxic,bhrcv->bhxriv", jnp.stack([k, q], axis=2), S,
                         precision=_HIGHEST) * to_here[:, :, None, ..., None]
        R = beta[..., None] * (v - met[:, :, 0])
        D = jnp.einsum("bhrij,bhrjv->bhriv", inv, R, precision=_HIGHEST)
        o = met[:, :, 1] + jnp.einsum(
            "bhrij,bhrjv->bhriv", P, D.astype(mxu_dtype),
            preferred_element_type=f32)
        S = decay * S + jnp.einsum(
            "bhjc,bhrjv->bhrcv", k, D * to_end[..., None],
            precision=_HIGHEST)
        return S, o

    state, o = lax.scan(
        chunk_step, state.astype(f32).reshape(b, Hk, rep, dk, dv), tuple(
            jnp.moveaxis(a, 1, 0) for a in (
                qc, kc, vc, bc, inv, P, to_here, to_end, decay)))
    # (nc, b, Hk, rep, C, dv) -> (b, T, Hv, dv)
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, nc * C, Hv, dv)
    return o[:, :T], state.reshape(b, Hv, dk, dv)


def gdn_recurrence(q, k, v, g, beta, state):
    """The same layer token by token (a ``lax.scan`` over ``T``), all in
    float32: what ``gdn_scan`` is tested against."""
    f32 = jnp.float32
    rep = v.shape[2] // q.shape[2]

    def step(S, inp):
        qt, kt, vt, gt, bt = inp            # (b, Hv, d) x 3, (b, Hv) x 2
        S = jnp.exp(gt)[..., None, None] * S
        delta = bt[..., None] * (vt - jnp.einsum(
            "bhc,bhcv->bhv", kt, S, precision=_HIGHEST))
        S = S + kt[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhc,bhcv->bhv", qt, S, precision=_HIGHEST)

    seq = tuple(a.astype(f32).swapaxes(0, 1) for a in (
        jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2), v, g, beta))
    state, o = lax.scan(step, state.astype(f32), seq)
    return o.swapaxes(0, 1), state
