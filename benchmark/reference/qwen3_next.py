"""Plain reference of the Qwen3-Next encoder (``model_type: qwen3_next``):
Gated DeltaNet (arXiv:2412.06464: a delta rule with one decay a head, two
value heads a key head) in three layers of ``full_attention_interval`` =
4 and gated softmax attention in the fourth; every layer an expert layer
(softmax router, top 10 renormalised) with a sigmoid-gated shared expert.

A whole-document forward in float32: the recurrence token by token (a
``lax.scan`` over the document), no chunks and no carried state; softmax
attention with no cache (one dense masked softmax, a block of queries at
a time so that the scores of 16,384 positions never exist at once); no
grouped matmul (a loop over the held experts, each run densely over all
tokens and masked); every matmul at the caller's
``jax.default_matmul_precision("highest")``. ``eps`` = ``rms_norm_eps``:

    norm(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)      (zero-centred)
    h = E[ids]
    layer i:  h = h + mixer_i(norm(h; w1));  h = h + moe_i(norm(h; w2))
      softmax attention where (i + 1) % full_attention_interval == 0,
      Gated DeltaNet elsewhere
    out = norm(h; w_f)

    Gated DeltaNet, Hk key heads, Hv value heads of dk | dv (u the normed
    input):
      [q | k | v] = silu(conv(u W_qkv)): depthwise causal conv over time,
        linear_conv_kernel_dim taps, no bias, zeros before the document
      z = u W_z;  [b | a] = u W_ba
      q_h = q_h / sqrt(|q_h|^2 + 1e-6) / sqrt(dk);  k_h = k_h / sqrt(|k_h|^2 + 1e-6)
      beta_t = sigmoid(b_t);  g_t = -exp(A_log) * softplus(a_t + dt_bias), (Hv,)
      value head j reads key head j // (Hv / Hk):
        S' = exp(g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S_t^T q_t
      y_t = [o_t,j * rsqrt(mean(o_t,j^2) + eps) * w_o * silu(z_t,j)] W_out
    softmax attention, Hq query heads on Hkv key/value heads of d:
      [q_h | gate_h] = u W_q a head;  k = u W_k;  v = u W_v
      q = norm_d(q; w_q);  k = norm_d(k; w_k) a head
      rotary on the first partial_rotary_factor * d dims of q and k:
        theta rope_theta, pairs (i, i + half) of the slice (rotate_half)
      P = causal softmax(q . k / sqrt(d)), query head i on key/value head
        i // (Hq / Hkv)
      y = concat_heads((P v)_h * sigmoid(gate_h)) W_o
    expert layer: softmax over the chosen num_experts_per_tok largest of
      the router's logits; experts (silu(u W_g) * (u W_u)) W_d; plus
      sigmoid(u w_sg) * shared(u), the shared expert the same SwiGLU

**The share** (``experts_held: {"first", "count", "of"}``): the router
is ``of`` wide; the sum runs over the chosen experts in ``[first, first +
count)`` only, plus the gated shared expert: what the other chip's
experts would add is left out, here as in the program.

What the published config does not settle, each as the family's public
modelling code has it (the configuration lists them under ``assumed``):
the zero-centred weights of the layer norms, the final norm and the q/k
norms, the plain weight of the gated output norm; the l2 norm's 1e-6;
value head ``j`` on key head ``j // 2``; the output gate the second half
of each head's ``q_proj`` columns; the shared expert's gate. Departures:
no LM head and no multi-token-prediction module (an encoder is what is
pooled); ``[q | k | v]`` of either mixer and every SwiGLU's ``[gate |
up]`` are one fused matrix each, and the linear layers' columns lie
head-major, not interleaved by key-head group (with seeded weights a
permutation of columns: the same numbers).

Weights are read in the layout ``init_params`` makes (a dict of leaves a
layer, ``layers/layer_<i>``) and upcast to float32 a layer (an expert)
at a time. Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import rotate_half
from benchmark.reference.bailing_hybrid import conv_silu, l2_norm
from benchmark.reference.deepseek_v3 import (  # the same plain pieces
    rms_norm, routed_part, swiglu)
from benchmark.reference.granite_hybrid import _stack  # seeded draws

F32 = jnp.float32
_IMPLEMENTED = {"hidden_act": "silu", "use_sliding_window": False,
                "rope_scaling": None, "decoder_sparse_step": 1,
                "mlp_only_layers": []}


def dims(model: dict) -> dict:
    for key, value in _IMPLEMENTED.items():
        if key in model and model[key] != value:
            raise NotImplementedError(f"{key}={model[key]!r}: not guessed")
    held = model.get("experts_held") or {
        "first": 0, "count": model["num_experts"],
        "of": model["num_experts"]}
    Hk, Hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    return {
        "L": model["num_hidden_layers"],
        "period": model["full_attention_interval"],
        "E": model["hidden_size"], "Hk": Hk, "Hv": Hv, "dk": dk, "dv": dv,
        "key": Hk * dk, "value": Hv * dv, "K": model["linear_conv_kernel_dim"],
        "Hq": model["num_attention_heads"],
        "Hkv": model["num_key_value_heads"], "d": model["head_dim"],
        "rot": int(model["head_dim"] * model["partial_rotary_factor"]),
        "Fe": model["moe_intermediate_size"],
        "Fs": model["shared_expert_intermediate_size"],
        "k": model["num_experts_per_tok"],
        "first": held["first"], "held": held["count"], "experts": held["of"],
    }


def is_attention(model: dict, layer: int) -> bool:
    return (layer + 1) % model["full_attention_interval"] == 0


# -- weights -----------------------------------------------------------------

def init_params(key, model: dict, weights: dict = None,
                dtype=jnp.float32) -> dict:
    """Seeded weights: matrices at ``1/sqrt(fan_in)`` with the tails
    ``weights`` names (drawn in row blocks of at most 2**25 numbers), the
    embedding at 1, the conv at ``1/sqrt(taps)``, the router with normal
    tails (the held load then follows the seed less). The ZERO-CENTRED
    norm weights ~ N(0, 0.1): small, and not 0, so that a ``(1 + w)`` read
    as ``w`` or as 1 is inside every comparison; the gated norm's plain
    weight 1. The decay's own parameters in float32, chosen so that a
    layer holds memories of every length a 16,384-token thread can use:
    ``exp(A_log)`` uniform in [0.5, 1.5] a value head, and ``dt_bias``
    such that a zero pre-activation decays the head at a rate
    log-uniform in [1e-4, 1] a token (``exp(A_log) * softplus(dt_bias) =
    rate``); the LAST head of a layer at rate 10 (it forgets within a
    chunk: the side of the gate that has no bound). The token's own ``a``
    then moves a slow head's rate by about ``e^{+-1}`` and the fast
    head's by about a tenth."""
    d = dims(model)
    keys = iter(jax.random.split(key, 16 * d["L"] + 3))

    def mat(rows, cols, std=None, n=None, tails=weights):
        blocks = 1
        while rows * cols // blocks > 2 ** 25 or rows % blocks:
            blocks += 1
        w = _stack(next(keys), (n or 1) * blocks, (rows // blocks, cols),
                   std or 1.0 / math.sqrt(rows), tails, dtype)
        return w.reshape(((n,) if n else ()) + (rows, cols))

    def centred(n):
        return (0.1 * jax.random.normal(next(keys), (n,), F32)).astype(dtype)

    E, Hv = d["E"], d["Hv"]
    conv_dim = 2 * d["key"] + d["value"]

    def mixer(i):
        if is_attention(model, i):
            return {
                "qkv": mat(E, (2 * d["Hq"] + 2 * d["Hkv"]) * d["d"]),
                "q_norm": centred(d["d"]), "k_norm": centred(d["d"]),
                "o": mat(d["Hq"] * d["d"], E)}
        A = jax.random.uniform(next(keys), (Hv,), F32, 0.5, 1.5)
        rate = jnp.exp(jax.random.uniform(
            next(keys), (Hv,), F32, math.log(1e-4), 0.0)).at[-1].set(10.0)
        return {
            "qkv": mat(E, conv_dim),
            "conv_w": mat(conv_dim, d["K"], std=1.0 / math.sqrt(d["K"])),
            "z": mat(E, d["value"]), "ba": mat(E, 2 * Hv),
            "A_log": jnp.log(A),
            "dt_bias": jnp.log(jnp.expm1(rate / A)),   # softplus^-1
            "o_norm": jnp.ones((d["dv"],), dtype),
            "o": mat(d["value"], E)}

    def ffn():
        return {
            "router": mat(E, d["experts"], tails=None),
            "shared_in": mat(E, 2 * d["Fs"]), "shared_out": mat(d["Fs"], E),
            "shared_gate": mat(E, 1),
            "experts_in": mat(E, 2 * d["Fe"], n=d["held"]),
            "experts_out": mat(d["Fe"], E, n=d["held"])}

    return {"embedding": mat(model["vocab_size"], E, std=1.0),
            "final_norm": centred(E),
            "layers": {f"layer_{i}": dict(
                mixer(i), **ffn(), norm=centred(E), ffn_norm=centred(E))
                for i in range(d["L"])}}


# -- layers ------------------------------------------------------------------

def norm(x, w, eps):
    """The zero-centred norm: ``rms_norm`` (the plain-weight norm, the
    gated output norm's) times ``1 + w``."""
    return rms_norm(x, 1.0 + w, eps)


def delta_rule(q, k, v, g, beta):
    """``o (b, T, Hv, dv)`` of the recurrence from a zero state, a token
    at a time; ``q`` and ``k`` ``(b, T, Hv, dk)`` (each key head's
    repeated for its value heads), ``g`` and ``beta`` ``(b, T, Hv)``."""
    b, _, H, dk = q.shape

    def step(S, xs):
        qt, kt, vt, gt, bt = xs             # (b, H, d) x 3, (b, H) x 2
        S = jnp.exp(gt)[..., None, None] * S
        delta = bt[..., None] * (vt - jnp.einsum("bhc,bhcv->bhv", kt, S))
        S = S + kt[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhc,bhcv->bhv", qt, S)

    _, o = jax.lax.scan(step, jnp.zeros((b, H, dk, v.shape[-1]), F32), tuple(
        a.swapaxes(0, 1) for a in (q, k, v, g, beta)))
    return o.swapaxes(0, 1)


def linear_attention(p, x, model: dict):
    """The Gated DeltaNet mixer over the normed input ``x`` ``(b, T, E)``:
    ``(its output, [q | k | v] before the conv (b, T, channels))``."""
    d = dims(model)
    b, T, _ = x.shape
    Hk, Hv, dk, dv = d["Hk"], d["Hv"], d["dk"], d["dv"]
    pre = x @ p["qkv"]
    qkv = conv_silu(pre, p["conv_w"])
    q = qkv[..., :d["key"]].reshape(b, T, Hk, dk)
    k = qkv[..., d["key"]:2 * d["key"]].reshape(b, T, Hk, dk)
    v = qkv[..., 2 * d["key"]:].reshape(b, T, Hv, dv)
    q, k = l2_norm(q) / math.sqrt(dk), l2_norm(k)
    # value head j reads key head j // (Hv / Hk)
    q, k = (jnp.repeat(a, Hv // Hk, axis=2) for a in (q, k))
    ba = x @ p["ba"]
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., Hv:] + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    o = rms_norm(o, p["o_norm"], model["rms_norm_eps"]) \
        * jax.nn.silu(x @ p["z"]).reshape(b, T, Hv, dv)
    return o.reshape(b, T, Hv * dv) @ p["o"], pre


def rotary(x, model: dict):
    """Rotary on the first ``partial_rotary_factor * head_dim`` dims of
    ``x`` ``(b, T, heads, d)`` at positions ``0 .. T - 1``; the others
    pass."""
    T, rot = x.shape[1], dims(model)["rot"]
    inv_freq = 1.0 / model["rope_theta"] ** (
        jnp.arange(0, rot, 2, dtype=F32) / rot)
    freqs = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    turned = x[..., :rot] * cos + rotate_half(x[..., :rot]) * sin
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def attention(p, x, model: dict, q_block: int = 256):
    """The gated softmax-attention mixer over the normed input ``x``:
    ``(its output, (keys, values) (b, T, Hkv, d) as the softmax reads
    them)``."""
    dm = dims(model)
    b, T, _ = x.shape
    Hq, Hkv, d = dm["Hq"], dm["Hkv"], dm["d"]
    eps = model["rms_norm_eps"]
    qkv = x @ p["qkv"]
    qg = qkv[..., :2 * Hq * d].reshape(b, T, Hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = qkv[..., 2 * Hq * d:(2 * Hq + Hkv) * d].reshape(b, T, Hkv, d)
    v = qkv[..., (2 * Hq + Hkv) * d:].reshape(b, T, Hkv, d)
    q = rotary(norm(q, p["q_norm"], eps), model)
    k = rotary(norm(k, p["k_norm"], eps), model)
    read = (k, v)
    k = jnp.repeat(k, Hq // Hkv, axis=2)
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    j = jnp.arange(T)[None, :]

    def queries(xs):
        q_blk, t = xs  # (b, qb, Hq, d), (qb,) their positions
        s = jnp.einsum("bthd,bshd->bhts", q_blk, k) / math.sqrt(d)
        s = jnp.where(j <= t[:, None], s, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)

    qb = q_block if T > q_block and T % q_block == 0 else T
    out = jax.lax.map(queries, (
        q.reshape(b, T // qb, qb, Hq, d).swapaxes(0, 1),
        jnp.arange(T).reshape(T // qb, qb)))
    out = out.swapaxes(0, 1).reshape(b, T, Hq, d) * jax.nn.sigmoid(gate)
    return out.reshape(b, T, Hq * d) @ p["o"], read


def route(x, w_router, model: dict):
    """``(experts (N, k), weights (N, k), logits (N, experts))``: the k
    largest logits a token, and the softmax over them (= the softmax
    over all, taken at the chosen and renormalised)."""
    if not model.get("norm_topk_prob", True):
        raise NotImplementedError("norm_topk_prob false is not implemented")
    logits = x @ w_router
    experts = jnp.argsort(-logits, axis=-1)[:, :dims(model)["k"]]
    picked = jnp.take_along_axis(logits, experts, axis=-1)
    return experts, jax.nn.softmax(picked, axis=-1), logits


def moe_layer(p, x, model: dict):
    """One expert layer (its leaves ``p``) over flat tokens ``x`` ``(N,
    E)``: ``(the held share's part + the gated shared expert, the
    experts chosen)``."""
    experts, weights, _ = route(x, p["router"].astype(F32), model)
    y = routed_part(p, x, experts, weights, dims(model)["first"])
    shared = swiglu(x, p["shared_in"].astype(F32),
                    p["shared_out"].astype(F32))
    return y + jax.nn.sigmoid(x @ p["shared_gate"].astype(F32)) * shared, \
        experts


_MIXER = {True: ("qkv", "q_norm", "k_norm", "o"),
          False: ("qkv", "conv_w", "z", "ba", "A_log", "dt_bias", "o_norm",
                  "o")}


def encode(params: dict, tokens, model: dict):
    """``(hidden (b, T, E), chosen, read)``: the final norm's output for
    every position; per layer the experts every token chose ``(b * T,
    num_experts_per_tok)``; and what a LATER position reads of these
    ``T`` besides a matrix state (what a program that carries state
    hands on as it is): ``{"k", "v"}`` a softmax-attention layer's keys
    and values ``(b, T, Hkv, d)``, ``"conv"`` a linear layer's last
    ``linear_conv_kernel_dim - 1`` positions of ``[q | k | v]`` before
    the conv."""
    d = dims(model)
    eps = model["rms_norm_eps"]
    b, T = tokens.shape
    h = jnp.take(params["embedding"], tokens, axis=0).astype(F32)
    chosen, read = [], {"k": [], "v": [], "conv": []}
    for i in range(d["L"]):
        p = params["layers"][f"layer_{i}"]
        softmax = is_attention(model, i)
        mixer = {k: p[k].astype(F32) for k in _MIXER[softmax]}
        x = norm(h, p["norm"].astype(F32), eps)
        if softmax:
            y, (k, v) = attention(mixer, x, model)
            read["k"].append(k)
            read["v"].append(v)
        else:
            y, pre = linear_attention(mixer, x, model)
            read["conv"].append(pre[:, T - (d["K"] - 1):])
        h = h + y
        x = norm(h, p["ffn_norm"].astype(F32), eps)
        y, experts = moe_layer(p, x.reshape(b * T, -1), model)
        h = h + y.reshape(b, T, -1)
        chosen.append(experts)
    return norm(h, params["final_norm"].astype(F32), eps), chosen, read
