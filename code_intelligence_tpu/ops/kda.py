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

**Two cores, one arithmetic, chosen here** (``core_is_kernel``, from
what the program can observe: backend, operand type, shapes; no caller
selects one):

* the Pallas kernel (``_kernel_scan``) on the TPU for bfloat16 in-chunk
  products at head sizes that fill the lanes, for whole chunks: every
  program of the engine at the published sizes. A grid over rows and,
  in order, chunks; a step takes the chunk's ``q``, ``k``, ``v``, ``g``
  and ``beta`` of every head from the ``(b, T, H, d)`` arrays as the
  caller holds them and, a head at a time (``_chunk_step``), forms in
  VMEM the cumulative decays, the sub-block operands, the tiles ``A``
  and ``P``, solves, and multiplies; the row's states live in VMEM from
  its first chunk to its last. **What crosses HBM is ``q``, ``k``,
  ``v``, ``g``, ``beta`` in and ``o`` out a token, and a row's states
  once a call**: no ``(C, C)`` tile, no decayed operand, no ``[W | U]``,
  no state a chunk;
* the XLA scan (``_xla_scan``) everywhere else: the CPU, float32 (the
  parity tests), a ``T`` the chunk does not divide (it pads), head sizes
  under a lane. It forms every chunk's factors at once, heads first, in
  HBM (about 12 GB a layer of a ``(16, 512)`` program at the published
  sizes, 21.6 ms on the chip where the kernel takes 5.0), then scans the
  chunks.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# heads a turn of the kernel's loop over a chunk's heads takes, unrolled
# together. One sweep on the chip (v5e, standalone, one layer of a (16,
# 512) program, 32 heads of 128 | 128, bfloat16; ms a call with twenty
# calls in flight, the XLA scan 21.56 beside it; PERF.md §6, PR 37):
# 1 / 2 / 4 heads 5.08 / 5.27 / 5.03, and of a (2, 512) program 0.68 /
# 0.71 / 0.68 (XLA 1.47): level within 1 %. Tracing and lowering a whole
# forward of six such layers takes 1.2 s at one head a turn, as with the
# XLA scan, and 2.8 s at four (here, on the CPU): eight programs a
# cell's set-up, so one
_TILE_HEADS = 1

# a step holds a chunk of every head's q, k, v, g and o and a row's
# states in and out, each twice (the pipeline's two buffers): 18.9 MB at
# the published sizes; Mosaic's default scoped limit is 16 MiB of the
# v5e's 128
_KERNEL_VMEM_LIMIT = 64 * 1024 * 1024


def _kernel_tiles(H: int, dk: int, dv: int, chunk: int,
                  sub: int) -> Optional[int]:
    """Heads a turn of the kernel's head loop takes, ``None`` where the
    kernel has no tile for the shape. A function of the shapes alone:
    the most heads up to ``_TILE_HEADS`` that divide ``H``, where the
    sub-blocks are whole (16, 128) bfloat16 tiles that divide the chunk
    and a step's blocks (float32 operands) fit three quarters of the
    VMEM the kernel asks for."""
    blocks = 2 * chunk * H * (3 * dk + 2 * dv) * 4 + 4 * H * dk * dv * 4
    if sub % 16 or chunk % sub or blocks > _KERNEL_VMEM_LIMIT * 3 // 4:
        return None
    return next(n for n in range(min(H, _TILE_HEADS), 0, -1) if H % n == 0)


def core_is_kernel(backend: str, mxu_dtype, T: int, H: int, dk: int, dv: int,
                   chunk: int, sub: int) -> bool:
    """Pallas kernel or XLA scan, for ONE call of ``kda_scan``: the rule,
    from what the program can observe and nothing a user sets.

    The kernel runs on the TPU (off it the kernel is the interpreter, a
    test device); for bfloat16 in-chunk products (float32 is the parity
    tests'); for head sizes that fill the lanes' 128 (the published 128 |
    128); for whole chunks (``T`` a multiple of ``chunk``, as every
    bucket of the engine is; the XLA scan pads); and where
    ``_kernel_tiles`` has a tile."""
    return (backend == "tpu" and jnp.dtype(mxu_dtype) == jnp.bfloat16
            and dk % 128 == 0 and dv % 128 == 0 and T % chunk == 0
            and _kernel_tiles(H, dk, dv, chunk, sub) is not None)


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
    bound must stay under float32's 88. Which core runs it is
    ``core_is_kernel``'s to say."""
    _, T, H, dk = q.shape
    dv = v.shape[-1]
    if chunk % sub:
        raise ValueError(f"sub {sub} does not divide chunk {chunk}")
    if core_is_kernel(jax.default_backend(), mxu_dtype, T, H, dk, dv, chunk,
                      sub):
        return _kernel_scan(q, k, v, g, beta, state, chunk, mxu_dtype, sub,
                            _kernel_tiles(H, dk, dv, chunk, sub))
    return _xla_scan(q, k, v, g, beta, state, chunk, mxu_dtype, sub)


def _xla_scan(q, k, v, g, beta, state, chunk, mxu_dtype, sub):
    """The recurrence in plain XLA: the in-chunk factors of every chunk
    at once, heads first, then a ``lax.scan`` over the chunks with the
    state. What runs off the TPU, in float32 and at shapes with no
    tile."""
    b, T, H, dk = q.shape
    dv = v.shape[-1]
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


def _chunk_step(q, k, v, g, beta, S, sub, mxu_dtype):
    """One head's chunk, every array two-dimensional (what the kernel
    holds in VMEM): ``q``, ``k``, ``g`` ``(C, dk)``, ``v`` ``(C, dv)``,
    ``beta`` ``(C, 1)``, ``S`` ``(dk, dv)`` float32; returns ``(o (C,
    dv), S_next)``. The module docstring's scheme with one
    rearrangement: ``D = U - W S`` is solved for directly, ``(I + A) D =
    diag(b) (V - (K * e^{G}) S)``, one right-hand side of ``dv`` columns
    where the XLA scan (which solves for every chunk before it meets a
    state) has ``dk + dv``."""
    f32 = jnp.float32
    C, dk = k.shape
    ns, half = C // sub, sub // 2
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)

    # L: the cumulative decay inside a sub-block, by doubling steps
    in_block = jnp.concatenate(
        [lax.broadcasted_iota(jnp.int32, (sub, dk), 0)] * ns, axis=0)
    L, step = g.astype(f32), 1
    while step < sub:
        L = L + jnp.where(in_block >= step, pltpu.roll(L, step, 0), 0.0)
        step *= 2

    def block(a, I):
        return a[I * sub:(I + 1) * sub]

    # G from the chunk's start, M = G at each sub-block's middle token
    base = jnp.zeros((1, dk), f32)
    G, M, rows = [], [], []
    for I in range(ns):
        L_I = block(L, I)
        mid = L_I[half:half + 1]
        G.append(L_I + base)
        M.append(mid + base)
        decay_in = jnp.exp(L_I - mid)
        rows.append(jnp.concatenate(
            [(block(k, I) * decay_in).astype(mxu_dtype),
             (block(q, I) * decay_in).astype(mxu_dtype)], axis=0))
        base = base + L_I[sub - 1:]
    G_end = base

    # a row sub-block's two tiles, A's and P's, in one product against
    # the columns it meets: k_j e^{M_I - G_j} up to its own end, 0 after
    tiles = []
    for I in range(ns):
        cols = [(block(k, J) * jnp.exp(M[I] - G[J])).astype(mxu_dtype)
                for J in range(I + 1)]
        if I + 1 < ns:
            cols.append(jnp.zeros(((ns - I - 1) * sub, dk), mxu_dtype))
        tiles.append(lax.dot_general(
            rows[I], jnp.concatenate(cols, axis=0), (((1,), (1,)), ((), ())),
            preferred_element_type=f32))             # (2 sub, C)
    at = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    met = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    A = jnp.where(met < at, jnp.concatenate(
        [t[:sub] for t in tiles], axis=0), 0.0) * beta
    P = jnp.where(met <= at, jnp.concatenate(
        [t[sub:] for t in tiles], axis=0), 0.0).astype(mxu_dtype)

    G = jnp.concatenate(G, axis=0)
    to_here = jnp.exp(G)
    # the two products that read the state, in one
    met_state = jnp.dot(jnp.concatenate([k * to_here, q * to_here], axis=0),
                        S, precision=_HIGHEST, preferred_element_type=f32)
    R = beta * (v - met_state[:C])

    # (I + A) D = R by forward substitution, a sub-block at a time: what
    # earlier sub-blocks wrote goes in one product, then the sub-block's
    # own rows one after another
    D = []
    for I in range(ns):
        acc = block(R, I)
        if I:
            acc = acc - jnp.dot(
                block(A, I)[:, :I * sub], jnp.concatenate(D, axis=0),
                precision=_HIGHEST, preferred_element_type=f32)
        own = block(A, I)[:, I * sub:(I + 1) * sub]
        for j in range(sub - 1):
            # ``own``'s column j is 0 down to row j: the rows after j move
            acc = acc - own[:, j:j + 1] * acc[j:j + 1]
        D.append(acc)
    D = jnp.concatenate(D, axis=0)

    o = met_state[C:] + jnp.dot(P, D.astype(mxu_dtype),
                                preferred_element_type=f32)
    # K * e^{G_C - G} and, under it, e^{G_C}: turned once, so that the
    # decay is a column beside the state and the product needs no turn
    # of its own (0.2 ms of a (16, 512) layer's 6.4 on the chip)
    turned = jnp.concatenate(
        [k * jnp.exp(G_end - G),
         jnp.broadcast_to(jnp.exp(G_end), (8, dk))], axis=0).T  # (dk, C + 8)
    S = turned[:, C:C + 1] * S + jnp.dot(
        turned[:, :C], D, precision=_HIGHEST, preferred_element_type=f32)
    return o, S


def _kernel_scan(q, k, v, g, beta, state, chunk, mxu_dtype, sub, heads):
    """``_xla_scan``'s results from one ``pallas_call``: the grid is
    (rows, chunks), the chunks in order. A step takes a chunk of ``q``,
    ``k``, ``v``, ``g`` as they lie in HBM, every head of it, in the type
    they come in: ``(b, T, H, d)`` seen as ``(b, T * H, d)`` (the same
    bytes under the TPU's tiles; a head's chunk is every ``H``-th row of
    the block: strided loads, eight rows a register), and the chunk's
    ``beta``. A row's states stay in the new state's block in VMEM from
    the first chunk (copied from ``state``) to the last (written back
    once). ``heads`` heads a turn of a loop over the chunk's heads,
    ``_chunk_step`` makes the decays, the tiles, the solve and the
    products in VMEM; only ``o`` returns to HBM, into ``(b, T * H,
    dv)``. Off the TPU the kernel is interpreted."""
    b, T, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    if T % C or H % heads or C % sub:
        raise ValueError(f"chunk {C} / heads {heads} / sub {sub} do not "
                         f"divide T={T}, H={H}")
    f32 = jnp.float32

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_in_ref, o_ref, s_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            s_ref[...] = s_in_ref[...]

        betas = b_ref[...].astype(f32)                       # (C, H)
        head_of = lax.broadcasted_iota(jnp.int32, betas.shape, 1)

        def head(h):
            rows = pl.ds(h, C, stride=H)
            beta_h = jnp.sum(jnp.where(head_of == h, betas, 0.0), axis=1,
                             keepdims=True)                  # (C, 1)
            o, S = _chunk_step(
                q_ref[rows, :], k_ref[rows, :], v_ref[rows, :],
                g_ref[rows, :], beta_h, s_ref[h], sub, mxu_dtype)
            o_ref[rows, :] = o
            s_ref[h] = S

        def heads_together(i, carry):
            for n in range(heads):
                head(i * heads + n)
            return carry

        lax.fori_loop(0, H // heads, heads_together, 0)

    def chunk_of(d):
        return pl.BlockSpec((None, C * H, d), lambda r, c: (r, c, 0))

    states = pl.BlockSpec((None, H, dk, dv), lambda r, c: (r, 0, 0, 0))
    o, state = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, T * H, dv), f32),
                   jax.ShapeDtypeStruct((b, H, dk, dv), f32)),
        grid=(b, T // C),
        in_specs=[chunk_of(dk), chunk_of(dk), chunk_of(dv), chunk_of(dk),
                  pl.BlockSpec((None, C, H), lambda r, c: (r, c, 0)),
                  states],
        out_specs=(chunk_of(dv), states),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_LIMIT),
        interpret=jax.default_backend() != "tpu",
        name="kda_scan_core",
    )(q.reshape(b, T * H, dk), k.reshape(b, T * H, dk),
      v.reshape(b, T * H, dv), g.reshape(b, T * H, dk), beta,
      state.astype(f32))
    return o.reshape(b, T, H, dv), state


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
