"""Plain reference of the LongCat-Flash encoder (``meituan-longcat/
LongCat-Flash-Chat``): a shortcut-connected mixture of experts. A layer
is two latent-attention sublayers and two dense SwiGLU FFNs; the routed
experts read the stream after the first sublayer's attention and are
added after the second sublayer's FFN.

A whole-document forward in float32: no cache (keys and values of every
position are expanded from the latent and met by one dense masked
softmax, a block of queries and of heads at a time), no chunks, no
grouped matmul (a loop over the held experts, each run densely over ALL
the tokens and weighted by what each token gave it), every matmul
at the caller's ``jax.default_matmul_precision("highest")``. Written
from these equations (``x`` the float32 residual, ``eps`` =
``rms_norm_eps``, ``E`` = ``hidden_size``, no biases but the router's
choice bias):

    x = Emb[ids]
    layer l = 0 .. num_layers - 1:
      x  = x + MLA_{l,0}(RMSNorm(x; g_in[l,0]))
      u0 = RMSNorm(x; g_post[l,0])
      m  = MoE_l(u0)                        # made here, added at the end
      x  = x + SwiGLU_{l,0}(u0)             # dense, ffn_hidden_size
      x  = x + MLA_{l,1}(RMSNorm(x; g_in[l,1]))
      u1 = RMSNorm(x; g_post[l,1])
      x  = x + SwiGLU_{l,1}(u1)
      x  = x + m
    out = RMSNorm(x; g_final)

    MLA(u): c_q = a_q RMSNorm(u W_qa), a_q = sqrt(E / q_lora_rank)
      [q_nope | q_pe] = c_q W_qb, a head
      [c | k_pe] = u W_kva; c_kv = a_kv RMSNorm(c), a_kv = sqrt(E /
        kv_lora_rank); k_pe ONE head for all, unscaled
      [k_nope | v] = c_kv W_kvb, a head
      rotary (rope_theta, no scaling; pairs de-interleaved before
        rotate_half, DeepSeek-V3's pairing) on q_pe and k_pe
      P = causal softmax((q_nope.k_nope + q_pe.k_pe) * (nope + rope)^-1/2)
      MLA = concat_heads(P v) W_o
    MoE(u): s = softmax(u W_r) over all n_routed_experts + zero_expert_num
      e = the moe_topk largest of s + e_score_correction_bias
      w_j = routed_scaling_factor * s[e_j]  (no bias, NOT renormalised)
      m = sum_{j: e_j < n_routed_experts} w_j SwiGLU_{e_j}(u)
        + (sum_{j: e_j >= n_routed_experts} w_j) u   # identity experts

**The share** (``experts_held: {"first", "count", "of"}``): the router is
``of + zero_expert_num`` wide; the first sum runs over the chosen experts
in ``[first, first + count)`` only, the identity part over every chosen
zero-compute expert (a token's identity part is computed where the token
is): what the other chips' experts would add is left out, here as in the
program, and the partial ``m`` goes on.

Departures from the published model, each also in the configuration's
``assumed``: no LM head (an encoder is what is pooled); ``[gate | up]``
of every SwiGLU are one fused matrix (the same numbers); the two
``mla_scale_*`` multipliers act on the normed low-rank vectors, before
``W_qb`` / ``W_kvb`` (there is no bias, so after them is the same).

Weights are read in the layout ``init_params`` makes (a dict of leaves a
layer, ``layers/layer_<l>``) and upcast to float32 a matrix (an expert)
at a time; the projections and dense FFNs run ``ROWS`` rows at a time
and attention ``Q_BLOCK`` queries of ``HEAD_BLOCK`` heads at a time
(the heads' queries, keys and values expanded from the low-rank vectors
a block of heads at a time), so a 16,384-token document fits beside the
bfloat16 weights.
Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.granite_hybrid import _stack  # seeded draws

F32 = jnp.float32
ROWS = 2048       # tokens a block of the token-wise parts
Q_BLOCK = 256     # queries a block of the dense softmax
HEAD_BLOCK = 16   # heads a block of it


def dims(model: dict) -> dict:
    held = model.get("experts_held") or {
        "first": 0, "count": model["n_routed_experts"],
        "of": model["n_routed_experts"]}
    return {
        "L": model["num_layers"], "E": model["hidden_size"],
        "H": model["num_attention_heads"], "q_rank": model["q_lora_rank"],
        "kv_rank": model["kv_lora_rank"], "nope": model["qk_nope_head_dim"],
        "rope": model["qk_rope_head_dim"], "v": model["v_head_dim"],
        "F": model["ffn_hidden_size"], "Fe": model["expert_ffn_hidden_size"],
        "k": model["moe_topk"], "first": held["first"],
        "held": held["count"], "routed": held["of"],
        "width": held["of"] + model.get("zero_expert_num", 0),
    }


# -- weights -----------------------------------------------------------------

def init_params(key, model: dict, weights: dict = None,
                dtype=jnp.float32) -> dict:
    """Seeded weights: matrices at ``1/sqrt(fan_in)`` with the tails
    ``weights`` names (drawn in row blocks of at most 2**25 numbers),
    but ``q_b`` and ``kv_b`` at ``1/sqrt(hidden_size)``: the two
    ``mla_scale_*`` multipliers exist to give a vector that comes out of
    a low rank the variance of one that comes out of ``hidden_size``
    under ONE init scale for every matrix, so with them queries, keys
    and values are of unit scale (drawn at ``1/sqrt(rank)`` they would
    be 2 and 3.5 times that, the logits seven times hotter, and the
    softmax so near a one-hot that bfloat16's rounding reads as error);
    the embedding at unit scale (there is no multiplier), norms at 1;
    the router with normal tails, as the other configurations'; and a
    non-zero ``e_score_correction_bias`` ~ N(0, (0.25 / width)^2) in
    float32: a softmax over ``width`` outputs scores about ``1 / width``,
    so a bias of that order moves some choices and not all, and "choose
    with the bias, weigh without it" is inside every comparison."""
    d = dims(model)
    keys = iter(jax.random.split(key, 32 * d["L"] + 2))

    def mat(rows, cols, std=None, n=None, tails=weights):
        blocks = 1
        while rows * cols // blocks > 2 ** 25 or rows % blocks:
            blocks += 1
        w = _stack(next(keys), (n or 1) * blocks, (rows // blocks, cols),
                   std or 1.0 / math.sqrt(rows), tails, dtype)
        return w.reshape(((n,) if n else ()) + (rows, cols))

    E, H = d["E"], d["H"]

    def ones(n):
        return jnp.ones((n,), dtype)

    def mla():
        return {
            "norm": ones(E),
            "q_a": mat(E, d["q_rank"]), "q_norm": ones(d["q_rank"]),
            "q_b": mat(d["q_rank"], H * (d["nope"] + d["rope"]),
                       std=1.0 / math.sqrt(E)),
            "kv_a": mat(E, d["kv_rank"] + d["rope"]),
            "kv_norm": ones(d["kv_rank"]),
            "kv_b": mat(d["kv_rank"], H * (d["nope"] + d["v"]),
                        std=1.0 / math.sqrt(E)),
            "o": mat(H * d["v"], E),
        }

    def mlp():
        return {"w_in": mat(E, 2 * d["F"]), "w_out": mat(d["F"], E)}

    def layer():
        return {
            "attention_0": mla(), "post_norm_0": ones(E), "mlp_0": mlp(),
            "attention_1": mla(), "post_norm_1": ones(E), "mlp_1": mlp(),
            "router": mat(E, d["width"], tails=None),
            "bias": (0.25 / d["width"]) * jax.random.normal(
                next(keys), (d["width"],), F32),
            "experts_in": mat(E, 2 * d["Fe"], n=d["held"]),
            "experts_out": mat(d["Fe"], E, n=d["held"]),
        }

    return {"embedding": mat(model["vocab_size"], E, std=1.0),
            "final_norm": ones(E),
            "layers": {f"layer_{i}": layer() for i in range(d["L"])}}


# -- layers ------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def swiglu(x, w_in, w_out):
    g, u = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_out


def by_rows(fn, x):
    """``fn`` over the rows of ``x`` ``(N, .)``, ``ROWS`` at a time where
    that divides ``N``: what acts a token at a time, in blocks. A plain
    Python loop, not ``lax.map``: a weight upcast inside a compiled loop
    is hoisted out of it, and every layer's float32 copies then lie in
    memory at once (6.9 GB of them at the cell's widths)."""
    N = x.shape[0]
    if N <= ROWS or N % ROWS:
        return fn(x)
    parts = [fn(x[at:at + ROWS]) for at in range(0, N, ROWS)]
    return jax.tree.map(lambda *ys: jnp.concatenate(ys, axis=0), *parts)


def rotary(x, model: dict):
    """Plain rotary on ``x`` ``(b, T, heads, d)`` at positions ``0 .. T -
    1``: the pairs ``(2i, 2i + 1)`` de-interleaved to ``(i, i + d / 2)``,
    then ``rotate_half``."""
    T, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / model["rope_theta"] ** (
        jnp.arange(0, d, 2, dtype=F32) / d)
    freqs = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(
        x.shape)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rotated * sin


def attention(p, u, model: dict):
    """``MLA(u)``: ``u`` ``(b, T, E)`` the normed input, ``p`` the
    sublayer's leaves. The low-rank ``c_q`` and ``c_kv`` are made for
    every token first; then, a block of heads at a time, that block's
    queries, keys and values are expanded from them, attended densely,
    and multiplied by the block's rows of ``W_o`` (``concat_heads(P v)
    W_o`` is the sum of those products over the blocks)."""
    d = dims(model)
    b, T, E = u.shape
    H, nope, rope, v_dim = d["H"], d["nope"], d["rope"], d["v"]
    eps = model["rms_norm_eps"]

    def f32(name):
        return p[name].astype(F32)

    a_q = math.sqrt(E / d["q_rank"]) if model.get("mla_scale_q_lora") else 1.0
    a_kv = math.sqrt(E / d["kv_rank"]) \
        if model.get("mla_scale_kv_lora") else 1.0

    def latents(rows):  # (n, E) -> c_q (n, q_rank), c_kv (n, kv_rank), k_pe
        c_q = a_q * rms_norm(rows @ f32("q_a"), f32("q_norm"), eps)
        kv_a = rows @ f32("kv_a")
        c_kv = a_kv * rms_norm(kv_a[:, :d["kv_rank"]], f32("kv_norm"), eps)
        return c_q, c_kv, kv_a[:, d["kv_rank"]:]

    c_q, c_kv, k_pe = by_rows(latents, u.reshape(b * T, E))
    k_pe = rotary(k_pe.reshape(b, T, 1, rope), model)[:, :, 0]
    scale = (nope + rope) ** -0.5
    j = jnp.arange(T)[None, :]
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 else H
    qb = Q_BLOCK if T > Q_BLOCK and T % Q_BLOCK == 0 else T

    def heads(out, ws):
        w_qb, w_kvb, w_o = ws  # the block's columns, columns and rows
        q = (c_q @ w_qb).reshape(b, T, hb, nope + rope)
        kv = (c_kv @ w_kvb).reshape(b, T, hb, nope + v_dim)
        q_pe = rotary(q[..., nope:], model)
        k_nope, vv = kv[..., :nope], kv[..., nope:]

        def queries(ys):
            qn, qp, t = ys  # (b, qb, hb, .), (qb,) their positions
            s = (jnp.einsum("bthd,bshd->bhts", qn, k_nope)
                 + jnp.einsum("bthr,bsr->bhts", qp, k_pe)) * scale
            s = jnp.where(j <= t[:, None], s, -jnp.inf)
            return jnp.einsum("bhts,bshd->bthd",
                              jax.nn.softmax(s, axis=-1), vv)

        def blocks(x):  # (b, T, hb, .) -> (T / qb, b, qb, hb, .)
            return x.reshape(b, T // qb, qb, hb, x.shape[-1]).swapaxes(0, 1)

        pv = jax.lax.map(queries, (blocks(q[..., :nope]), blocks(q_pe),
                                   jnp.arange(T).reshape(T // qb, qb)))
        pv = pv.swapaxes(0, 1).reshape(b * T, hb * v_dim)
        return out + pv @ w_o, None

    def by_heads(w, per_head):  # (rows, H * per_head) -> (H / hb, rows, .)
        return w.reshape(w.shape[0], H // hb, hb * per_head).swapaxes(0, 1)

    out, _ = jax.lax.scan(heads, jnp.zeros((b * T, E), F32), (
        by_heads(f32("q_b"), nope + rope), by_heads(f32("kv_b"), nope + v_dim),
        f32("o").reshape(H // hb, hb * v_dim, E)))
    return out.reshape(b, T, E)


def route(x, w_router, bias, model: dict):
    """``(experts (N, k), weights (N, k), scores (N, width))``: softmax
    over all the router's outputs, the choice on ``score + bias``, the
    weights the unbiased scores of the chosen times the factor, not
    renormalised."""
    scores = jax.nn.softmax(x @ w_router, axis=-1)
    experts = jnp.argsort(-(scores + bias), axis=-1)[:, :dims(model)["k"]]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, weights * model["routed_scaling_factor"], scores


def moe(p, x, model: dict):
    """The shortcut's branch over flat tokens ``x`` ``(N, E)``: ``(the
    held experts' part + the identity experts', the experts chosen)``."""
    d = dims(model)
    experts, weights, _ = route(x, p["router"].astype(F32), p["bias"], model)
    identity = jnp.sum(jnp.where(experts >= d["routed"], weights, 0.0), -1)

    def one(y, xs):  # each held expert over ALL the tokens, one at a time
        j, w_in, w_out = xs
        w_j = jnp.sum(jnp.where(experts == d["first"] + j, weights, 0.0), -1)
        return y + w_j[:, None] * swiglu(
            x, w_in.astype(F32), w_out.astype(F32)), None

    y, _ = jax.lax.scan(one, identity[:, None] * x, (
        jnp.arange(d["held"]), p["experts_in"], p["experts_out"]))
    return y, experts


def encode(params: dict, tokens, model: dict):
    """``(hidden (b, T, E), chosen)``: the final norm's output for every
    position, and per layer the experts every token chose ``(b * T,
    moe_topk)``."""
    if model.get("zero_expert_num") and \
            model.get("zero_expert_type", "identity") != "identity":
        raise NotImplementedError("only identity zero-compute experts")
    d = dims(model)
    eps = model["rms_norm_eps"]
    b, T = tokens.shape
    x = jnp.take(params["embedding"], tokens, axis=0).astype(F32)
    chosen = []

    def ffn(p, u):
        w_in, w_out = p["w_in"].astype(F32), p["w_out"].astype(F32)
        return by_rows(lambda rows: swiglu(rows, w_in, w_out),
                       u.reshape(b * T, -1)).reshape(b, T, -1)

    for i in range(d["L"]):
        p = params["layers"][f"layer_{i}"]
        x = x + attention(p["attention_0"], rms_norm(
            x, p["attention_0"]["norm"].astype(F32), eps), model)
        u0 = rms_norm(x, p["post_norm_0"].astype(F32), eps)
        m, experts = moe(p, u0.reshape(b * T, -1), model)
        chosen.append(experts)
        x = x + ffn(p["mlp_0"], u0)
        x = x + attention(p["attention_1"], rms_norm(
            x, p["attention_1"]["norm"].astype(F32), eps), model)
        u1 = rms_norm(x, p["post_norm_1"].astype(F32), eps)
        x = x + ffn(p["mlp_1"], u1)
        x = x + m.reshape(b, T, -1)
    return rms_norm(x, params["final_norm"].astype(F32), eps), chosen
