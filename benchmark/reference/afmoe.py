"""Plain reference of the AFMoE encoder (``model_type: afmoe``, Arcee
Trinity): grouped-query attention under a sliding window in the layers
``layer_types`` calls ``sliding_attention`` and over everything in its
``full_attention`` layers, per-head QK norms, a sigmoid output gate,
four norms a layer, a dense SwiGLU MLP in the first ``num_dense_layers``
layers and sigmoid-routed experts with a shared one in the others.

A whole-document forward in float32: no cache and no ring (every layer
is one dense masked softmax over the whole document, a block of queries
at a time so that the scores of 16,384 positions never exist at once),
no chunks, no grouped matmul (a loop over the held experts, each run
densely over all tokens and masked), every matmul at the caller's
``jax.default_matmul_precision("highest")``. ``eps`` = ``rms_norm_eps``:

    x = E[ids] * sqrt(hidden_size)                       (mup_enabled)
    every layer:
      a = RMSNorm(x; input_norm)
      q = RMSNorm_d(a W_q), k = RMSNorm_d(a W_k), v = a W_v, a head of
        d = head_dim each (the norm over a head's d numbers)
      a sliding layer: q, k = rotary(q, k) at absolute positions, all d
        dims, frequencies rope_theta^(-2i/d), ``rotate_half`` pairs
        (x[i], x[i + d/2]); a full layer: no rotary
      s_tj = q_t . k_j / sqrt(d), j <= t, and in a sliding layer
        t - j < sliding_window; query head h reads kv head h // (Hq/Hkv)
      o = softmax_j(s) v;  o = o * sigmoid(a W_gate)
      x = x + RMSNorm(o W_o; post_attn_norm)
      m = RMSNorm(x; pre_mlp_norm)
      a dense layer: f = (silu(g) * u) W_out, [g | u] = m W_in
      an expert layer: p = sigmoid(m W_r) in float32;
        chosen = top num_experts_per_tok of (p + expert_bias);
        w = p[chosen] / (sum p[chosen] + 1e-20) * route_scale;
        f = E_shared(m) + sum_{e chosen} w_e E_e(m)
      x = x + RMSNorm(f; post_mlp_norm)
    out = RMSNorm(x; final_norm)

**The share** (``experts_held: {"first", "count", "of"}``): the router
is ``of`` wide; the sum runs over the chosen experts in ``[first, first +
count)`` only, plus the shared expert: what the other chips' experts
would add is left out, here as in the program, and the partial result
goes on to the next layer.

Taken from the family's public modelling code, not from the config
(the configuration lists them under ``assumed``): rotary on the sliding
layers only, the output gate, and where the four norms sit. Departures:
no LM head (an encoder is what is pooled); ``[q | k | v]`` and every
SwiGLU's ``[gate | up]`` are one fused matrix each (the same numbers).

Weights are read in the layout ``init_params`` makes (a dict of leaves a
layer, ``layers/layer_<i>``) and upcast to float32 a layer (an expert)
at a time; ``rms_norm``, ``swiglu`` and the dense loop over the held
experts (``routed_part``) are ``reference/deepseek_v3.py``'s. Imports
nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import (  # the same plain pieces
    rms_norm, routed_part, swiglu)
from benchmark.reference.granite_hybrid import _stack  # seeded draws

F32 = jnp.float32
SLIDING = "sliding_attention"


def dims(model: dict) -> dict:
    held = model.get("experts_held") or {
        "first": 0, "count": model["num_experts"],
        "of": model["num_experts"]}
    return {
        "L": model["num_hidden_layers"], "D": model["num_dense_layers"],
        "E": model["hidden_size"], "Hq": model["num_attention_heads"],
        "Hkv": model["num_key_value_heads"], "d": model["head_dim"],
        "F": model["intermediate_size"], "Fe": model["moe_intermediate_size"],
        "Fs": model["moe_intermediate_size"] * model["num_shared_experts"],
        "first": held["first"], "held": held["count"], "experts": held["of"],
    }


# -- weights -----------------------------------------------------------------

def init_params(key, model: dict, weights: dict = None,
                dtype=jnp.float32) -> dict:
    """Seeded weights: matrices at ``1/sqrt(fan_in)`` with the tails
    ``weights`` names (drawn in row blocks of at most 2**25 numbers);
    the embedding at the inverse of its multiplier (``1 /
    sqrt(hidden_size)`` under ``mup_enabled``), so that the residual
    starts at unit scale and ten normed branches of unit scale are most
    of what is pooled, not a rounding beside 55 times an embedding;
    norms at 1. The router alone is drawn with normal tails, and the
    ``expert_bias`` ~ N(0, 0.005) in float32: non-zero, so that "choose
    with the bias, weigh without it" is inside every comparison (it
    moves the fourth choice of about a token in three), but small, as a
    bias that has done its work of evening the load is: with heavy-tailed
    router columns and N(0, 0.02) the held experts' share of the
    assignments, and with it the device's work, moved by +-11 % from
    seed to seed (PERF.md, PR 32)."""
    d = dims(model)
    keys = iter(jax.random.split(key, 16 * d["L"] + 2))

    def mat(rows, cols, std=None, n=None, tails=weights):
        blocks = 1
        while rows * cols // blocks > 2 ** 25 or rows % blocks:
            blocks += 1
        w = _stack(next(keys), (n or 1) * blocks, (rows // blocks, cols),
                   std or 1.0 / math.sqrt(rows), tails, dtype)
        return w.reshape(((n,) if n else ()) + (rows, cols))

    E, hd = d["E"], d["d"]

    def ones(n):
        return jnp.ones((n,), dtype)

    def layer(i):
        p = {
            "input_norm": ones(E), "post_attn_norm": ones(E),
            "pre_mlp_norm": ones(E), "post_mlp_norm": ones(E),
            "qkv": mat(E, (d["Hq"] + 2 * d["Hkv"]) * hd),
            "q_norm": ones(hd), "k_norm": ones(hd),
            "gate": mat(E, d["Hq"] * hd), "o": mat(d["Hq"] * hd, E),
        }
        if i < d["D"]:
            return dict(p, w_in=mat(E, 2 * d["F"]), w_out=mat(d["F"], E))
        return dict(
            p, router=mat(E, d["experts"], tails=None),
            bias=0.005 * jax.random.normal(next(keys), (d["experts"],), F32),
            shared_in=mat(E, 2 * d["Fs"]), shared_out=mat(d["Fs"], E),
            experts_in=mat(E, 2 * d["Fe"], n=d["held"]),
            experts_out=mat(d["Fe"], E, n=d["held"]))

    emb_std = 1.0 / math.sqrt(E) if model.get("mup_enabled") else 1.0
    return {"embedding": mat(model["vocab_size"], E, std=emb_std),
            "final_norm": ones(E),
            "layers": {f"layer_{i}": layer(i) for i in range(d["L"])}}


# -- layers ------------------------------------------------------------------

def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary(x, model: dict):
    """``apply_rotary_pos_emb`` on ``x`` ``(b, T, heads, d)`` at
    positions ``0 .. T - 1``."""
    T, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / model["rope_theta"] ** (
        jnp.arange(0, d, 2, dtype=F32) / d)
    freqs = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    return x * cos + rotate_half(x) * sin


def attention(p, a, model: dict, sliding: bool, q_block: int = 256):
    """``Attn`` of one layer, before its post-norm: ``a`` ``(b, T, E)``
    the normed input."""
    d = dims(model)
    b, T, _ = a.shape
    Hq, Hkv, hd = d["Hq"], d["Hkv"], d["d"]
    eps = model["rms_norm_eps"]
    qkv = a @ p["qkv"]
    q = rms_norm(qkv[..., :Hq * hd].reshape(b, T, Hq, hd), p["q_norm"], eps)
    k = rms_norm(qkv[..., Hq * hd:(Hq + Hkv) * hd].reshape(b, T, Hkv, hd),
                 p["k_norm"], eps)
    v = qkv[..., (Hq + Hkv) * hd:].reshape(b, T, Hkv, hd)
    if sliding:
        q, k = rotary(q, model), rotary(k, model)
    k = jnp.repeat(k, Hq // Hkv, axis=2)
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    j = jnp.arange(T)[None, :]

    def queries(xs):
        q_blk, t = xs  # (b, qb, Hq, hd), (qb,) their positions
        s = jnp.einsum("bthd,bshd->bhts", q_blk, k) / math.sqrt(hd)
        seen = j <= t[:, None]
        if sliding:
            seen = seen & (t[:, None] - j < model["sliding_window"])
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)

    qb = q_block if T > q_block and T % q_block == 0 else T
    out = jax.lax.map(queries, (
        q.reshape(b, T // qb, qb, Hq, hd).swapaxes(0, 1),
        jnp.arange(T).reshape(T // qb, qb)))
    out = out.swapaxes(0, 1).reshape(b, T, Hq * hd)
    return (out * jax.nn.sigmoid(a @ p["gate"])) @ p["o"]


def route(x, w_router, bias, model: dict):
    """``(experts (N, k), weights (N, k), scores (N, experts))``: one
    group, so the top k of all experts by ``score + bias``."""
    k = model["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ w_router)
    experts = jnp.argsort(-(scores + bias), axis=-1)[:, :k]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if model.get("route_norm", True) and k > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts, weights * model["route_scale"], scores


def moe_layer(p, x, model: dict):
    """One expert layer (its leaves ``p``) over flat tokens ``x`` ``(N,
    E)``: ``(the held share's part + the shared expert, the experts
    chosen)``."""
    experts, weights, _ = route(x, p["router"].astype(F32), p["bias"], model)
    y = routed_part(p, x, experts, weights, dims(model)["first"])
    if model["num_shared_experts"]:
        y = y + swiglu(x, p["shared_in"].astype(F32),
                       p["shared_out"].astype(F32))
    return y, experts


def encode(params: dict, tokens, model: dict):
    """``(hidden (b, T, E), chosen)``: the final norm's output for every
    position, and per expert layer the experts every token chose
    ``(b * T, num_experts_per_tok)``."""
    d = dims(model)
    eps = model["rms_norm_eps"]
    b, T = tokens.shape
    x = jnp.take(params["embedding"], tokens, axis=0).astype(F32)
    if model.get("mup_enabled", False):
        x = x * math.sqrt(d["E"])
    chosen = []
    for i, kind in enumerate(model["layer_types"]):
        p = params["layers"][f"layer_{i}"]

        def f32(name, p=p):
            return p[name].astype(F32)

        attn = {n: f32(n) for n in ("qkv", "q_norm", "k_norm", "gate", "o")}
        o = attention(attn, rms_norm(x, f32("input_norm"), eps), model,
                      sliding=kind == SLIDING)
        x = x + rms_norm(o, f32("post_attn_norm"), eps)
        m = rms_norm(x, f32("pre_mlp_norm"), eps)
        if i < d["D"]:
            f = swiglu(m, f32("w_in"), f32("w_out"))
        else:
            y, experts = moe_layer(p, m.reshape(b * T, -1), model)
            f = y.reshape(b, T, -1)
            chosen.append(experts)
        x = x + rms_norm(f, f32("post_mlp_norm"), eps)
    return rms_norm(x, params["final_norm"].astype(F32), eps), chosen
