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

float32 holds the decays, the cumulative sums, the solve, the state and
every product that has the state as an operand; the in-chunk products
(``K K^T``, ``Q K^T``, the tile times ``D``) take ``mxu_dtype`` inputs
and accumulate in float32 (``ops/kda.py``'s rules). A lane with ``g = 0``
and ``b = 0`` decays nothing and writes nothing: the state stands
(padding), to the bit.

**Two cores, one arithmetic, chosen here** (``core_is_kernel``, from
what the program can observe: backend, operand type, shapes; no caller
selects one):

* the Pallas kernel (``_kernel_scan``) on the TPU for bfloat16 in-chunk
  products at head sizes that fill the lanes, for whole chunks: every
  program of the engine at the published sizes. A grid over rows and,
  in order, chunks; a step takes the chunk's ``q``, ``k``, ``v`` of
  every head from the ``(b, T, H, d)`` arrays as the caller holds them
  and its ``g`` and ``beta`` and, a KEY head at a time (``_chunk_step``),
  forms in VMEM the two tiles once and, for each of the key head's value
  heads, the cumulative decay, ``L``, ``A`` and ``P``, solves ``(I + A)
  D = diag(b) (V - e^{G} (K S))`` for ``D`` by forward substitution (one
  right-hand side of ``dv`` columns, no inverse), and multiplies; the
  row's states live in VMEM from its first chunk to its last. **What
  crosses HBM is ``q``, ``k``, ``v``, ``g``, ``beta`` in and ``o`` out a
  token, and a row's states once a call**: no ``(C, C)`` tile, no
  inverse, no heads-first copy, no state a chunk;
* the XLA scan (``_xla_scan``) everywhere else: the CPU, float32 (the
  parity tests), a ``T`` the chunk does not divide (it pads), head sizes
  under a lane. It forms every chunk's tiles at once, heads first, and
  solves the triangular system once a chunk for the identity
  (``kda._solve_unit_lower``, forward substitution in float32: the one
  copy), which gives ``(I + A)^-1`` ``(C, C)`` before any state is known;
  a ``lax.scan`` over the chunks then multiplies it into the right-hand
  side the state gives. Its ``(b, chunks, Hv, C, C)`` float32 tiles are
  67 MB each a layer of a ``(16, 512)`` program at 32 value heads and
  ``C`` = 64, and what it costs on the chip is the 16 dependent row steps
  a diagonal block of the inversion and the scan's small ``HIGHEST``
  products (13.5 ms a layer there; PERF.md §6, PR 47 and 48).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from code_intelligence_tpu.ops.kda import _solve_unit_lower

_HIGHEST = lax.Precision.HIGHEST

# rows of the diagonal blocks ``_solve_unit_lower`` inverts row by row
# (the sub-blocks of ``ops/kda.py``, whose sweep it was); it divides the
# chunk or the chunk is one block
_SOLVE_SUB = 16


# a step holds a chunk of every head's q, k, v and o and a row's states in
# and out, each twice (the pipeline's two buffers): 14.2 MB at the
# published sizes; Mosaic's default scoped limit is 16 MiB of the v5e's 128
_KERNEL_VMEM_LIMIT = 64 * 1024 * 1024


def core_is_kernel(backend: str, mxu_dtype, T: int, Hk: int, Hv: int, dk: int,
                   dv: int, chunk: int) -> bool:
    """Pallas kernel or XLA scan, for ONE call of ``gdn_scan``: the rule,
    from what the program can observe and nothing a user sets.

    The kernel runs on the TPU (off it the kernel is the interpreter, a
    test device); for bfloat16 in-chunk products (float32 is the parity
    tests'); for head sizes that fill the lanes' 128 (the published 128 |
    128); for whole chunks (``T`` a multiple of ``chunk``, as every
    bucket of the engine is; the XLA scan pads) of whole (16, 128)
    bfloat16 tiles; and where a step's blocks (float32 operands) fit
    three quarters of the VMEM the kernel asks for."""
    blocks = 2 * chunk * (2 * Hk * dk + 2 * Hv * dv) * 4 + 4 * Hv * dk * dv * 4
    return (backend == "tpu" and jnp.dtype(mxu_dtype) == jnp.bfloat16
            and dk % 128 == 0 and dv % 128 == 0 and T % chunk == 0
            and chunk % 16 == 0 and blocks <= _KERNEL_VMEM_LIMIT * 3 // 4)


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
    ``Hk`` divides ``Hv``. Which core runs it is ``core_is_kernel``'s to
    say."""
    _, T, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    if Hv % Hk:
        raise ValueError(f"{Hk} key heads do not divide {Hv} value heads")
    if core_is_kernel(jax.default_backend(), mxu_dtype, T, Hk, Hv, dk, dv,
                      chunk):
        return _kernel_scan(q, k, v, g, beta, state, chunk, mxu_dtype)
    return _xla_scan(q, k, v, g, beta, state, chunk, mxu_dtype)


def _xla_scan(q, k, v, g, beta, state, chunk, mxu_dtype):
    """The recurrence in plain XLA: every chunk's tiles and the ``(C,
    C)`` inverse at once, heads first, then a ``lax.scan`` over the
    chunks with the state. What runs off the TPU, in float32 and for a
    ``T`` the chunk does not divide (it pads)."""
    b, T, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
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


def _chunk_step(q, k, vs, gs, betas, Ss, sub, mxu_dtype):
    """One KEY head's chunk and its value heads', every array
    two-dimensional (what the kernel holds in VMEM): ``q``, ``k`` ``(C,
    dk)``; a value head each of ``vs`` ``(C, dv)``, ``gs`` ``(C, 1)`` (its
    log-decays, one a token), ``betas`` ``(C, 1)`` and ``Ss`` ``(dk, dv)``
    float32; returns ``(os, Ss_next)``, a value head each. The module
    docstring's scheme with one rearrangement: ``(I + A) D = diag(b) (V -
    e^{G} (K S))`` is solved for ``D`` directly by forward substitution,
    one right-hand side of ``dv`` columns and no inverse, where the XLA
    scan (which inverts every chunk's ``I + A`` before it meets a state)
    has ``C``. The value heads' solves are written side by side: each is
    a chain of ``C - C / sub`` dependent row steps, and two chains fill
    the gaps of one."""
    f32 = jnp.float32
    C = k.shape[0]
    ns = C // sub
    q, k = q.astype(f32), k.astype(f32)
    kq = jnp.concatenate([k, q], axis=0)                    # (2 C, dk)
    kq_in = kq.astype(mxu_dtype)
    # the two tiles, one product a KEY head
    tiles = lax.dot_general(
        kq_in, kq_in[:C], (((1,), (1,)), ((), ())),
        preferred_element_type=f32)                         # (2 C, C)
    kk, qk = tiles[:C], tiles[C:]
    k_turned = k.T                                          # (dk, C)
    at = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    met = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    step_of = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    lanes = -(-C // 128) * 128

    As, Ps, Rs, from_state, to_end, decays = [], [], [], [], [], []
    for v, g, beta, S in zip(vs, gs, betas, Ss):
        # G: the cumulative decay from the chunk's start, by doubling
        G, step = g.astype(f32), 1
        while step < C:
            G = G + jnp.where(step_of >= step, pltpu.roll(G, step, 0), 0.0)
            step *= 2
        # G_j along the lanes: a token a row, turned
        G_met = jnp.broadcast_to(G, (C, lanes)).T[:C]       # (C, C)
        L = jnp.exp(jnp.where(met <= at, G - G_met, -jnp.inf))
        As.append(jnp.where(met < at, kk * L, 0.0) * beta)
        Ps.append((qk * L).astype(mxu_dtype))               # L is 0 above
        G_end = G[C - 1:]                                   # (1, 1)
        # the two products that read the state, in one
        met_state = jnp.dot(kq, S, precision=_HIGHEST,
                            preferred_element_type=f32) \
            * jnp.concatenate([jnp.exp(G)] * 2, axis=0)
        Rs.append(beta * (v.astype(f32) - met_state[:C]))
        from_state.append(met_state[C:])
        to_end.append(jnp.exp(G_end - G))
        # e^{G_C} a lane, then down the state's rows: Mosaic broadcasts one
        # way at a time
        decays.append(jnp.exp(jnp.broadcast_to(G_end, (1, S.shape[1]))))

    # (I + A) D = R, a sub-block at a time: what earlier sub-blocks wrote
    # goes in one product, then the sub-block's own rows one after another
    Ds = [[] for _ in As]
    for I in range(ns):
        rows = slice(I * sub, (I + 1) * sub)
        accs, owns = [], []
        for A, R, D in zip(As, Rs, Ds):
            acc = R[rows]
            if I:
                acc = acc - jnp.dot(
                    A[rows, :I * sub], jnp.concatenate(D, axis=0),
                    precision=_HIGHEST, preferred_element_type=f32)
            accs.append(acc)
            owns.append(A[rows, rows])
        for j in range(sub - 1):
            # ``own``'s column j is 0 down to row j: the rows after j move
            accs = [acc - own[:, j:j + 1] * acc[j:j + 1]
                    for acc, own in zip(accs, owns)]
        for D, acc in zip(Ds, accs):
            D.append(acc)

    os, Ss_next = [], []
    for n, S in enumerate(Ss):
        D = jnp.concatenate(Ds[n], axis=0)                  # (C, dv)
        os.append(from_state[n] + jnp.dot(Ps[n], D.astype(mxu_dtype),
                                          preferred_element_type=f32))
        Ss_next.append(decays[n] * S + jnp.dot(
            k_turned, D * to_end[n], precision=_HIGHEST,
            preferred_element_type=f32))
    return os, Ss_next


def _kernel_scan(q, k, v, g, beta, state, chunk, mxu_dtype):
    """``_xla_scan``'s results from one ``pallas_call``: the grid is
    (rows, chunks), the chunks in order. A step takes a chunk of ``q``,
    ``k``, ``v`` as they lie in HBM, every head of it, in the type they
    come in: ``(b, T, H, d)`` seen as ``(b, T * H, d)`` (the same bytes
    under the TPU's tiles; a head's chunk is every ``H``-th row of the
    block: strided loads, eight rows a register), and the chunk's ``g``
    and ``beta`` ``(C, Hv)``. A row's states stay in the new state's
    block in VMEM from the first chunk (copied from ``state``) to the
    last (written back once). One KEY head a turn of a loop over the
    chunk's key heads, ``_chunk_step`` makes its two tiles and, for each
    of its value heads, the decays, ``A`` and ``P``, the solve and the
    products in VMEM; only ``o`` returns to HBM, into ``(b, T * Hv,
    dv)``. Off the TPU the kernel is interpreted."""
    b, T, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    C = chunk
    sub = _SOLVE_SUB if C % _SOLVE_SUB == 0 else C
    if T % C:
        raise ValueError(f"chunk {C} does not divide T={T}")
    rep = Hv // Hk
    f32 = jnp.float32

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_in_ref, o_ref, s_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            s_ref[...] = s_in_ref[...]

        gates = g_ref[...].astype(f32)                       # (C, Hv)
        betas = b_ref[...].astype(f32)
        head_of = lax.broadcasted_iota(jnp.int32, gates.shape, 1)

        def column(a, j):  # (C, 1): value head j's
            return jnp.sum(jnp.where(head_of == j, a, 0.0), axis=1,
                           keepdims=True)

        def key_head(h, carry):
            rows = pl.ds(h, C, stride=Hk)
            js = [h * rep + r for r in range(rep)]
            lanes = [pl.ds(j, C, stride=Hv) for j in js]
            os, Ss = _chunk_step(
                q_ref[rows, :], k_ref[rows, :],
                [v_ref[at, :] for at in lanes],
                [column(gates, j) for j in js],
                [column(betas, j) for j in js],
                [s_ref[j] for j in js], sub, mxu_dtype)
            for j, at, o, S in zip(js, lanes, os, Ss):
                o_ref[at, :] = o
                s_ref[j] = S
            return carry

        # one key head a turn. Two and four unrolled together were swept
        # on the chip (v5e, standalone, one layer of a (16, 512) program,
        # bfloat16, ms a call with twenty calls in flight, the XLA scan
        # 14.53 beside it; PERF.md §6, PR 48): 1 / 2 / 4 key heads 3.38 /
        # 3.10 / 2.89, of a (2, 512) program 0.468 / 0.429 / 0.406 (XLA
        # 1.282), while Mosaic's compile of the kernel and the body Python
        # traces a layer a program double with each doubling
        lax.fori_loop(0, Hk, key_head, 0)

    def chunk_of(H, d):
        return pl.BlockSpec((None, C * H, d), lambda r, c: (r, c, 0))

    gate_of = pl.BlockSpec((None, C, Hv), lambda r, c: (r, c, 0))
    states = pl.BlockSpec((None, Hv, dk, dv), lambda r, c: (r, 0, 0, 0))
    o, state = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, T * Hv, dv), f32),
                   jax.ShapeDtypeStruct((b, Hv, dk, dv), f32)),
        grid=(b, T // C),
        in_specs=[chunk_of(Hk, dk), chunk_of(Hk, dk), chunk_of(Hv, dv),
                  gate_of, gate_of, states],
        out_specs=(chunk_of(Hv, dv), states),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_LIMIT),
        interpret=jax.default_backend() != "tpu",
        name="gdn_scan_core",
    )(q.reshape(b, T * Hk, dk), k.reshape(b, T * Hk, dk),
      v.reshape(b, T * Hv, dv), g, beta, state.astype(f32))
    return o.reshape(b, T, Hv, dv), state


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
