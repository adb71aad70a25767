"""Delta-rule linear attention with a decay PER CHANNEL (Kimi Delta
Attention, arXiv:2510.26692), matrix state in and out.

The recurrence, per head with state ``S`` of shape ``(dk, dv)``:

    S'  = diag(exp(g_t)) S_{t-1}                    g_t (dk,) <= 0
    S_t = S' + b_t k_t (v_t - k_t^T S')^T           b_t in [0, 1]
    o_t = S_t^T q_t

is linear in ``S``, so a chunk of ``C`` tokens needs the state only at
its edges. With ``G_i = sum_{j <= i} g_j`` a channel, inside a chunk:

    A = strict_tril[(b_i k_i e^{G_i}) . (k_j e^{-G_j})]
    (I + A) [W | U] = diag(b) [K * e^{G} | V]       (unit lower triangular)
    D = U - W S                                     what each token writes
    o = (Q * e^{G}) S + tril[(Q * e^{G}) (K * e^{-G})^T] D
    S_next = diag(e^{G_C}) S + (K * e^{G_C - G})^T D

``ops/ssd.py::ssd_scan`` is the nearest op here; its decay is one scalar
a head, so its in-chunk factor is a ``(C, C)`` product times a ``(C, C)``
mask. Here the decay is a vector, so it has to be folded into the
operands, and ``e^{-G_j}`` overflows float32 over a chunk (a gate bounded
below by -5 a token gives ``G`` down to -320 over 64). **The in-chunk
products are therefore taken a row sub-block of ``sub`` = 16 at a time,
against the cumulative decay at the sub-block's middle token** ``M_I``:
rows carry ``e^{G_i - M_I}`` and the columns of the row's own sub-block
``e^{M_I - G_j}``, both within ``e^{+-40}`` at the bound (half a
sub-block of steps: far from float32's and bfloat16's 3.4e38 and, on the
small side, from the denormals a reference at the sub-block's start
would push ``k e^{-80}`` into); a column of an earlier sub-block carries
``e^{M_I - G_j}`` <= 1; columns of later sub-blocks, which the causal
mask drops, are given 0 and never an ``exp``. No ``exp`` of a sum above
``sub / 2 * |lower bound|`` is formed.

The triangular system is solved by forward substitution in float32: a
sub-block's ``(sub, sub)`` diagonal block is inverted row by row, the
sub-blocks are then substituted in order with matmuls. (The Neumann
product ``(I - A)(I + A^2)(I + A^4)...`` is fewer, larger matmuls, but
its terms grow binomially where keys repeat and cancel in float32.)

float32 holds the decays, the cumulative sums, the solve, the state and
every product that has the state as an operand (as ``ssd_scan``); the
in-chunk products (``A``, the query-key tile, the tile times ``D``) take
``mxu_dtype`` inputs and accumulate in float32. A lane with ``g = 0`` and
``b = 0`` decays nothing and writes nothing: the state stands (padding).
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST


def _solve_unit_lower(A: jnp.ndarray, rhs: jnp.ndarray, sub: int):
    """``X`` with ``(I + A) X = rhs`` for strictly lower-triangular ``A``
    ``(..., C, C)``, ``rhs`` ``(..., C, n)``, ``sub`` dividing ``C``; all
    float32."""
    C = A.shape[-1]
    ns = C // sub
    lead = A.shape[:-2]
    diag = jnp.stack([A[..., I * sub:(I + 1) * sub, I * sub:(I + 1) * sub]
                      for I in range(ns)], axis=-3)     # (..., ns, sub, sub)
    # (I + a)^-1 of every diagonal block, a row at a time: row i is e_i
    # less a's row i times the rows above it
    eye = jnp.eye(sub, dtype=A.dtype)
    inv = jnp.zeros(lead + (ns, sub, sub), A.dtype)
    for i in range(sub):
        row = eye[i] - jnp.einsum("...j,...jk->...k", diag[..., i, :], inv,
                                  precision=_HIGHEST)
        inv = inv.at[..., i, :].set(row)
    X = []
    for I in range(ns):
        acc = rhs[..., I * sub:(I + 1) * sub, :]
        if I:
            acc = acc - jnp.einsum(
                "...ij,...jn->...in", A[..., I * sub:(I + 1) * sub, :I * sub],
                jnp.concatenate(X, axis=-2), precision=_HIGHEST)
        X.append(jnp.einsum("...ij,...jn->...in", inv[..., I, :, :], acc,
                            precision=_HIGHEST))
    return jnp.concatenate(X, axis=-2)


def kda_scan(
    q: jnp.ndarray,      # (b, T, H, dk), scaled by the caller
    k: jnp.ndarray,      # (b, T, H, dk)
    v: jnp.ndarray,      # (b, T, H, dv)
    g: jnp.ndarray,      # (b, T, H, dk) float32 log-decay, <= 0
    beta: jnp.ndarray,   # (b, T, H) float32
    state: jnp.ndarray,  # (b, H, dk, dv) float32
    chunk: int = 64,
    mxu_dtype=jnp.bfloat16,
    sub: int = 16,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(o (b, T, H, dv) float32, new state)``: the recurrence above
    over ``T`` tokens in chunks of ``chunk``, starting from ``state``.
    Half of ``sub`` (which divides ``chunk``) times the gate's lower
    bound must stay under float32's 88."""
    b, T, H, dk = q.shape
    dv = v.shape[-1]
    if chunk % sub:
        raise ValueError(f"sub {sub} does not divide chunk {chunk}")
    C = chunk
    pad = -T % C
    if pad:
        # g = 0 and beta = 0 past the end: no decay, no write
        q, k, v, g, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    nc, ns = (T + pad) // C, C // sub
    f32 = jnp.float32

    def heads_first(a):  # (b, nc * C, H, d) -> (b, nc, H, C, d)
        return a.astype(f32).reshape(b, nc, C, H, a.shape[-1]).transpose(
            0, 1, 3, 2, 4)

    qc, kc, vc, gc = (heads_first(a) for a in (q, k, v, g))
    bc = beta.astype(f32).reshape(b, nc, C, H).transpose(0, 1, 3, 2)

    # cumulative decays: L inside a sub-block, G from the chunk's start,
    # M = G at each sub-block's middle token
    L = jnp.cumsum(gc.reshape(b, nc, H, ns, sub, dk), axis=4)
    ends = jnp.cumsum(L[..., -1, :], axis=3)            # (b, nc, H, ns, dk)
    G = ((ends - L[..., -1, :])[..., None, :] + L).reshape(b, nc, H, C, dk)
    G_end = ends[..., -1, :]                            # (b, nc, H, dk)
    M = G.reshape(b, nc, H, ns, sub, dk)[..., sub // 2, :]

    # columns as row sub-block I meets them: k_j e^{M_I - G_j} up to the
    # end of sub-block I, 0 after it
    met = jnp.arange(C)[None, :] < (jnp.arange(ns)[:, None] + 1) * sub
    cols = (kc[:, :, :, None] * jnp.exp(jnp.where(
        met[:, :, None], M[..., None, :] - G[:, :, :, None], -jnp.inf))
    ).astype(mxu_dtype)                                 # (b, nc, H, ns, C, dk)
    decay_in = jnp.exp(L - L[..., sub // 2:sub // 2 + 1, :])

    def tile(x):
        """``(x_i e^{G_i}) . (k_j e^{-G_j})`` for ``j <= i``, ``(b, nc, H,
        C, C)`` float32; garbage above the diagonal."""
        rows = (x.reshape(b, nc, H, ns, sub, dk) * decay_in).astype(mxu_dtype)
        return jnp.einsum("bnhIic,bnhIjc->bnhIij", rows, cols,
                          preferred_element_type=f32).reshape(b, nc, H, C, C)

    ones = jnp.ones((C, C), bool)
    A = jnp.where(jnp.tril(ones, -1), tile(kc), 0.0) * bc[..., None]
    P = jnp.where(jnp.tril(ones), tile(qc), 0.0).astype(mxu_dtype)

    to_here = jnp.exp(G)
    WU = _solve_unit_lower(A, jnp.concatenate(
        [kc * to_here, vc], axis=-1) * bc[..., None], sub)
    W, U = WU[..., :dk], WU[..., dk:]
    q_in = qc * to_here
    k_out = kc * jnp.exp(G_end[..., None, :] - G)

    def step(S, xs):
        W, U, P, q_in, k_out, decay = xs
        D = U - jnp.einsum("bhic,bhcv->bhiv", W, S, precision=_HIGHEST)
        o = jnp.einsum("bhic,bhcv->bhiv", q_in, S, precision=_HIGHEST) \
            + jnp.einsum("bhij,bhjv->bhiv", P, D.astype(mxu_dtype),
                         preferred_element_type=f32)
        S = decay[..., None] * S + jnp.einsum(
            "bhjc,bhjv->bhcv", k_out, D, precision=_HIGHEST)
        return S, o

    state, o = lax.scan(step, state.astype(f32), tuple(
        jnp.moveaxis(a, 1, 0)
        for a in (W, U, P, q_in, k_out, jnp.exp(G_end))))
    # (nc, b, H, C, dv) -> (b, T, H, dv)
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, nc * C, H, dv)
    return o[:, :T], state


def kda_recurrence(q, k, v, g, beta, state):
    """The same layer token by token (a ``lax.scan`` over ``T``), all in
    float32: what ``kda_scan`` is tested against."""
    f32 = jnp.float32

    def step(S, inp):
        qt, kt, vt, gt, bt = inp            # (b, H, d) x 4, (b, H)
        S = jnp.exp(gt)[..., None] * S
        delta = bt[..., None] * (vt - jnp.einsum(
            "bhc,bhcv->bhv", kt, S, precision=_HIGHEST))
        S = S + kt[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhc,bhcv->bhv", qt, S, precision=_HIGHEST)

    seq = tuple(a.astype(f32).swapaxes(0, 1) for a in (q, k, v, g, beta))
    state, o = lax.scan(step, state.astype(f32), seq)
    return o.swapaxes(0, 1), state
