"""A routed expert layer that is told which experts it holds.

Expert parallelism gives a chip ``count`` of a layer's ``n_experts``
experts. The router here keeps its published width: every token is
scored against all ``n_experts``, picks its ``top_k`` among them and
normalises over all it picked, exactly as if every expert were here.
The chip then computes the part of the sum its own experts give,
``sum over chosen i in [first, first + count)  w_i * E_i(h)``, and
nothing for the rest: what the absent experts would add is another
chip's to compute and no code here stands in for it.

``route`` is DeepSeek-V3's router (``topk_method: noaux_tc``): sigmoid
scores in float32; the choice is made on ``score + bias`` (the bias
balances load and moves nothing else): experts lie in ``n_group`` groups,
a group's score is the sum of its two best, the best ``topk_group``
groups are kept, and the ``top_k`` best experts among them are chosen;
the weights are the UNBIASED scores of the chosen, normalised to sum 1
and multiplied by ``scaling``. With ``score_func="softmax"`` it is the
plain softmax router instead: the choice is made on the logits and the
weights are the softmax over the chosen logits. With
``score_func="softmax_all"`` the scores are the softmax over ALL the
router's outputs; choice on ``score + bias`` and weights the unbiased
scores of the chosen as for the sigmoid, left as they are where
``norm_topk_prob`` is false (LongCat-Flash).

**A router may be wider than the experts that have weights.** Outputs at
or past ``n_routed`` name ZERO-COMPUTE experts (identity: ``E(h) = h``).
Nobody holds them and they sort behind every held expert like an
absent chip's; ``zero_experts`` gives every token ``(sum of the weights
it gave them) * h``, one reduction over ``top_k`` and one multiply a
token, on whichever chip the token is.

**Routing and applying are two calls.** ``routed_experts`` takes a
router's choice and the tensor the experts READ, so a model whose router
reads another tensor than its experts (the layer's input, before
attention) calls ``route`` there; it may make ``assign``'s sort there
too and hand it in (``assigned``). ``expert_layer`` is the two on one
tensor.

``routed_experts`` runs the held experts as ONE grouped matmul over the
rows routed to them, twice (``[gate | up]``, then down), so device time
follows the rows routed and not a worst-case bound. **Two cores, one
arithmetic, chosen by ``ops/gmm.py::gmm_is_kernel``** from what the call
can observe (backend, the weights' type, the rows handed in, the held
experts' number and widths; no caller selects one): on the TPU in
bfloat16 two Pallas kernels of ours whose grid is the list of (group,
row tile) pairs the group sizes give, a product taken only where the
group has a row, the gate's activation in the first product's epilogue
(no ``(rows, 2 F)`` array, no pass over it), every held expert's weights
streamed once a product; everywhere else (the CPU, float32: the parity
tests' path) ``jax.lax.ragged_dot`` with the activation between the two
in XLA. The rounding points are the same: ``g`` and ``u`` to the
weights' type, the activation and their product in float32, the result
to the weights' type, the second product float32. Neither core reads an
expert without rows or multiplies a row past the last one routed here.
``experts_on_kernel`` is the rule at the rows a call of the held experts
is handed, ``kernel_layers`` a model's layers it says yes for: what an
encoder counts as ``expert_kernel_layers``.

No token is ever dropped and there is no capacity factor: assignments
are sorted by expert and taken ``N`` (the tokens' number) at a time for
as many rounds as they need (one, unless more than ``N`` assignments
land here: ``top_k * count / n_experts`` of a token's choices do on
average, ``n_experts`` the router's width, which the caller has). A
share never gathers ``top_k * N`` rows of ``x`` to use a sixteenth of
them, and a round's ``N``-row operands sit in the chip's fast memory:
larger rounds move the same bytes and lose that.

**Where every expert is held the rounds are one pass** (``one_pass``:
more than ``top_k - 1`` of a token's ``top_k`` choices land here on
average): all ``top_k * N`` assignments at once, no loop, no slice of
the sort and no update of a buffer. Neither core reads an expert
without rows, so rounds never re-read the experts; what the pass saves
is what the rounds moved beside the matmuls.

**The rows come back by a gather, not by a scatter-add** (which the TPU
runs row by row). A round writes its ``N`` weighted float32 rows into
one buffer of ``top_k * N`` rows, at the place the sort gave them; after
the last round the sort's inverse says where each of a token's ``top_k``
assignments lies there, the tokens' rows are gathered from it and a
token's ``top_k`` rows summed. The one pass's output, as the grouped
matmul leaves it, IS that buffer: its rows are weighed as they come back
(the same float32 product, the same sum), not in a pass of their own.
The buffer starts unwritten (``lax.empty``: no worst-case memset for a
share that fills a sixteenth of it): an assignment held elsewhere, or a
padding lane's, sorted behind the last held one, so whatever its place
holds (a round's zeroed tail, or nothing a round ever wrote) is selected
away and never multiplied.

``expert_layer`` is the whole block as most encoders call it (router, the
held experts' part, the shared expert, gated a token where a layer has a
``shared_gate``), and ``COUNTERS`` /
``counter_attrs`` what such encoders count on the device and how the
counts become span attributes: one copy for every model with routed
experts.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from code_intelligence_tpu.ops import gmm


# the scores a router chooses on: of each output alone, the logits (the
# softmax is taken over the chosen), or the softmax over all outputs
_SCORES = {"sigmoid": jax.nn.sigmoid, "softmax": lambda logits: logits,
           "softmax_all": lambda logits: jax.nn.softmax(logits, axis=-1)}


def route(
    h: jnp.ndarray,         # (N, E) float32
    w_router: jnp.ndarray,  # (E, n_experts)
    bias: Optional[jnp.ndarray],  # (n_experts,) float32, or None
    n_group: int,
    topk_group: int,
    top_k: int,
    scaling: float,
    norm_topk_prob: bool = True,
    score_func: str = "sigmoid",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(experts (N, top_k) int32, weights (N, top_k) float32)``. The
    published router multiplies in float32: on the TPU that takes
    ``Precision.HIGHEST`` (the default rounds the inputs to bfloat16).
    ``score_func`` ``"sigmoid"``: the module docstring's router;
    ``"softmax"``: the choice is made on the logits (+ ``bias``, where
    there is one) and the weights are the softmax over the CHOSEN logits
    (= the softmax over all, taken at the chosen and normalised:
    ``norm_topk_prob`` holds by construction); ``"softmax_all"``: the
    scores are the softmax over all ``n_experts`` outputs, chosen and
    weighed as the sigmoid's (``norm_topk_prob`` false leaves the
    weights the unbiased scores themselves)."""
    if score_func not in _SCORES:
        raise ValueError(
            f"score_func {score_func!r}: one of {sorted(_SCORES)}")
    logits = jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    scores = _SCORES[score_func](logits)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    N, n_experts = choice.shape
    if n_group > 1:
        per = choice.reshape(N, n_group, n_experts // n_group)
        group_score = lax.top_k(per, 2)[0].sum(-1)
        _, kept = lax.top_k(group_score, topk_group)
        keep = jnp.zeros((N, n_group), bool).at[
            jnp.arange(N)[:, None], kept].set(True)
        choice = jnp.where(keep[:, :, None], per, -jnp.inf).reshape(
            N, n_experts)
    _, experts = lax.top_k(choice, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if score_func == "softmax":
        weights = jax.nn.softmax(weights, axis=-1)
    elif norm_topk_prob and top_k > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * scaling


def swiglu(x, w_in, w_out, dtype):
    """``(silu(g) * u) @ W_out`` with ``[g | u] = x @ W_in``; float32
    out."""
    g, u = jnp.split(jnp.dot(x.astype(w_in.dtype), w_in,
                             preferred_element_type=dtype), 2, axis=-1)
    act = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    return jnp.dot(act.astype(w_out.dtype), w_out,
                   preferred_element_type=jnp.float32)


def assign(experts: jnp.ndarray, first: int, count: int,
           valid: Optional[jnp.ndarray] = None):
    """Assignments of tokens to the held experts ``[first, first +
    count)``, sorted by expert: ``(order, rows)`` with ``order`` ``(N *
    top_k,)`` the flat assignment indices (token ``i // top_k``, choice
    ``i % top_k``), held ones first and expert by expert, tokens in
    order within an expert; ``rows`` ``(count,)`` the rows each held
    expert gets. ``valid`` ``(N,)`` leaves tokens out (padding lanes)."""
    local = experts - first
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & valid[:, None]
    key = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rows = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                   dtype=jnp.int32)
    return order, rows


# the gate's activation of the routed experts: SwiGLU's and ReGLU's
_GATE_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}

# rows one gather brings back. The TPU writes a gather's rows out before
# the sum reads them, so a token's rows come back a block of tokens at a
# time, sized in ROWS: the four expert models' combines were fastest at
# 512 to 1536 rows of 10 to 28 KB a block (top_k 4, 6, 8) and took 1.1
# to 2.3 times as long at 8192, and one gather of all ``top_k * N`` rows
# held as much again as the buffer (TPU v5e; PERF.md section 6, PR 41)
_BACK_ROWS = 768


def one_pass(top_k: int, count: int, n_experts: int) -> bool:
    """Whether ``routed_experts`` takes all ``top_k * N`` sorted
    assignments at once: where the ``top_k * count / n_experts`` of a
    token's choices that land on ``count`` held experts of a router
    ``n_experts`` wide round up to ``top_k``, as they do where every
    expert is held. A share takes them ``N`` a round."""
    return top_k * count > (top_k - 1) * n_experts


def rounds_run(landed: jnp.ndarray, N: int, top_k: int, count: int,
               n_experts: int) -> jnp.ndarray:
    """The rounds ``routed_experts`` took for the ``landed`` assignments
    (the sum of its second result) of ``N`` tokens: what an encoder
    counts as ``expert_rounds``."""
    R = top_k * N if one_pass(top_k, count, n_experts) else N
    return (landed + R - 1) // R


def experts_on_kernel(N: int, top_k: int, w_in: jnp.ndarray,
                      n_experts: int) -> bool:
    """Whether ``routed_experts`` over ``N`` tokens runs the held experts
    ``w_in`` on ``ops/gmm.py``'s kernels: ``gmm.gmm_is_kernel`` at the
    rows a call of them is handed (``top_k * N`` in one pass, ``N`` a
    round)."""
    count, E, F2 = w_in.shape
    R = top_k * N if one_pass(top_k, count, n_experts) else N
    return gmm.gmm_is_kernel(jax.default_backend(), w_in.dtype, R, count, E,
                             F2 // 2)


def kernel_layers(layers, N: int, top_k: int) -> int:
    """The expert layers among ``layers`` (a model's leaves by layer,
    an expert layer's as ``expert_layer`` names them) whose held experts
    ``routed_experts`` over ``N`` tokens runs on the kernels: an
    encoder's ``expert_kernel_layers``."""
    return sum(
        experts_on_kernel(N, top_k, p["experts_in"], p["router"].shape[1])
        for p in layers.values() if "experts_in" in p)


def routed_experts(
    x: jnp.ndarray,        # (N, E): what the experts read
    experts: jnp.ndarray,  # (N, top_k) int32, over all n_experts
    weights: jnp.ndarray,  # (N, top_k) float32
    w_in: jnp.ndarray,     # (count, E, 2 * F): [gate | up] of held experts
    w_out: jnp.ndarray,    # (count, F, E)
    first: int,
    n_experts: int,        # the router's width
    valid: Optional[jnp.ndarray] = None,  # (N,) bool
    act: str = "silu",
    assigned: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(y (N, E) float32, rows (count,) int32)``: the held experts'
    part of every token's weighted sum, and the rows each held expert
    ran. ``n_experts`` decides between rounds of ``N`` assignments and
    one pass without a loop (``one_pass``: every expert held); ``act``
    is the gate's activation (``"silu"`` SwiGLU, ``"relu"`` ReGLU);
    ``assigned`` is ``assign(experts, first, count, valid)`` where the
    caller made it already (beside its router, ahead of what ``x`` waits
    for). Named scopes: ``dispatch`` (the sort and its inverse, each
    round's gather), ``experts`` (the grouped matmuls, whichever core
    ``gmm_is_kernel`` chose), ``combine``
    (in the loop: weigh a round's rows and write them into the float32
    buffer; after it: gather every token's ``top_k`` rows back by the
    sort's inverse, weigh them there if the one pass made them, and sum
    them, rows no held assignment wrote selected away, ``_BACK_ROWS``
    rows at a time)."""
    N, E = x.shape
    top_k = experts.shape[1]
    count = w_in.shape[0]
    dtype = w_in.dtype
    with jax.named_scope("dispatch"):
        order, per_expert = assigned if assigned is not None else assign(
            experts, first, count, valid)
        ends = jnp.cumsum(per_expert)
        total = ends[-1]
        # the sort's inverse: where assignment (token, choice) went. A
        # second sort, not a scatter of an iota: the TPU runs a scatter
        # element by element
        pos = jnp.argsort(order).astype(jnp.int32).reshape(N, top_k).T

    def held(xs, sizes):
        """The held experts over the sorted rows ``xs``, ``sizes`` rows
        an expert: float32, not weighed."""
        with jax.named_scope("experts"):
            if experts_on_kernel(N, top_k, w_in, n_experts):
                return gmm.gated_experts(xs, w_in, w_out, sizes,
                                         _GATE_ACTS[act])
            g, u = jnp.split(lax.ragged_dot(
                xs, w_in, sizes, preferred_element_type=dtype), 2, axis=-1)
            gated = _GATE_ACTS[act](g.astype(jnp.float32)) \
                * u.astype(jnp.float32)
            return lax.ragged_dot(gated.astype(dtype), w_out, sizes,
                                  preferred_element_type=jnp.float32)

    def one_round(r, rows):
        start = r * N  # N rows a round
        with jax.named_scope("dispatch"):
            picked = lax.dynamic_slice_in_dim(padded, start, N)
            token = picked // top_k
            live = start + jnp.arange(N) < total
            # this round's share of each expert's rows
            sizes = jnp.diff(jnp.clip(ends - start, 0, N), prepend=0)
            xs = jnp.take(x, token, axis=0).astype(dtype)
        out = held(xs, sizes)
        with jax.named_scope("combine"):
            # rows past the round's last assignment hold whatever the
            # grouped matmul left there: selected away, never multiplied
            out = jnp.where(live[:, None],
                            out * jnp.take(flat_w, picked)[:, None], 0.0)
            return lax.dynamic_update_slice_in_dim(rows, out, start, 0)

    if one_pass(top_k, count, n_experts):
        # every expert held: one pass, and its output as the grouped
        # matmul leaves it is what the rows come back from, each weighed
        # on its way (no pass to weigh them where they lie, no gather of
        # ``top_k * N`` single weights)
        with jax.named_scope("dispatch"):
            # in bounds as the sort made them: nothing to fill
            xs = jnp.take(x, order // top_k, axis=0,
                          mode="clip").astype(dtype)
        rows = held(xs, per_expert)
        late = weights.T
    else:
        with jax.named_scope("dispatch"):
            # padded so that every round slices N whole entries
            padded = jnp.concatenate([order, jnp.zeros((N,), jnp.int32)])
            flat_w = weights.reshape(-1)
        # the weighted rows of every round, in sorted order; a round
        # that never runs leaves its N rows unwritten
        rows = lax.fori_loop(0, (total + N - 1) // N, one_round,
                             lax.empty((top_k * N, E), jnp.float32))
        late = None  # weighed already

    def back(block):  # (top_k, tokens) positions [, weights] -> (tokens, E)
        at, w = block
        # an assignment held elsewhere, or a padding lane's, sorted
        # behind ``total``: its row is selected away like the leftovers
        here = (at < total)[:, :, None]
        got = jnp.take(rows, at, axis=0)
        if w is not None:
            got = got * w[:, :, None]
        return jnp.where(here, got, 0.0).sum(0)

    with jax.named_scope("combine"):
        # the most tokens, a power of two, within _BACK_ROWS rows
        part = math.gcd(
            N, 1 << (max(_BACK_ROWS // top_k, 1).bit_length() - 1))

        def blocks(of):  # (top_k, N) -> (N // part, top_k, part)
            return of.reshape(top_k, N // part, part).swapaxes(0, 1)

        y = lax.map(back, (blocks(pos),
                           None if late is None else blocks(late)))
    return y.reshape(N, E), per_expert


def zero_experts(
    x: jnp.ndarray,        # (N, E) float32: what the experts read
    experts: jnp.ndarray,  # (N, top_k) int32, over all the router's outputs
    weights: jnp.ndarray,  # (N, top_k) float32
    n_routed: int,
    valid: Optional[jnp.ndarray] = None,  # (N,) bool
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(y (N, E) float32, choices () int32)``: what a token's
    zero-compute (identity) experts give it, ``(sum of the weights of
    its choices at or past n_routed) * x``, and how many such choices
    the ``valid`` tokens made. Held by no chip and never sorted: every
    chip computes this for its own tokens. Named scope
    ``zero_experts``."""
    with jax.named_scope("zero_experts"):
        chosen = experts >= n_routed
        if valid is not None:
            chosen = chosen & valid[:, None]
        weight = jnp.sum(jnp.where(chosen, weights, 0.0), axis=-1)
        return weight[:, None] * x, jnp.sum(chosen, dtype=jnp.int32)


def expert_layer(
    p,                     # a layer's leaves: router, bias, experts_in/out, shared_in/out
    u: jnp.ndarray,        # (N, E) float32
    valid: Optional[jnp.ndarray],
    dtype,
    *,
    n_group: int,
    topk_group: int,
    top_k: int,
    scaling: float,
    norm_topk_prob: bool,
    first: int,
    shared: bool,
    score_func: str = "sigmoid",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One expert layer over the flat tokens ``u``: ``(the held experts'
    share + the shared expert (N, E) float32, rows each held expert
    ran)``: ``route`` and ``routed_experts`` on one tensor. A layer
    without the leaf ``bias`` routes without one; a layer with the leaf
    ``shared_gate`` ``(E, 1)`` weighs its shared expert a token by
    ``sigmoid(u w_sg)`` (float32). Named scopes ``router``,
    ``routed_experts``'s three, and ``shared_expert``."""
    with jax.named_scope("router"):
        experts, weights = route(
            u, p["router"], p.get("bias"), n_group, topk_group, top_k,
            scaling, norm_topk_prob, score_func)
    y, per_expert = routed_experts(
        u, experts, weights, p["experts_in"], p["experts_out"], first,
        p["router"].shape[1], valid)
    if shared:
        with jax.named_scope("shared_expert"):
            out = swiglu(u, p["shared_in"], p["shared_out"], dtype)
            if "shared_gate" in p:
                out = out * jax.nn.sigmoid(jnp.dot(
                    u.astype(jnp.float32),
                    p["shared_gate"].astype(jnp.float32),
                    precision=lax.Precision.HIGHEST))
            y = y + out
    return y, per_expert


# what the expert layers count on the device, summed since init_states:
# rows routed to held experts; the busiest held expert's rows of each
# expert layer of each program; programs
COUNTERS = ("routed_rows", "busiest_rows", "moe_programs")


def counter_attrs(counted: Sequence, n_moe_layers: int, held: int) -> dict:
    """Span attributes from the fetched ``COUNTERS`` of a flush's groups:
    ``routed_rows`` (assignments to held experts that ran: of valid
    tokens alone when the engine hands the lengths over, as it does),
    ``moe_programs``, and per held expert a layer a program
    ``expert_rows_mean`` and ``expert_rows_max``: the rows the MEAN and
    the BUSIEST held expert of an expert layer ran in one program, each
    averaged over layers and programs. Their ratio is each program's
    max / mean weighted by its rows: how far routing is from even WITHIN
    a program, whatever the programs' sizes."""
    rows, busiest, programs = (
        sum(int(c[i]) for c in counted) for i in range(len(COUNTERS)))
    layer_programs = programs * n_moe_layers
    if not layer_programs:
        return {}
    return {"routed_rows": rows, "moe_programs": programs,
            "expert_rows_max": busiest / layer_programs,
            "expert_rows_mean": rows / (layer_programs * held)}
