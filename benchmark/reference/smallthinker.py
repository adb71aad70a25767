"""Plain reference of the SmallThinker encoder (``model_name:
smallthinker_*``, PowerInfer SmallThinker-21BA3B): a router that reads
the layer's INPUT, before attention; grouped-query attention, global and
without rotary where ``rope_layout`` / ``sliding_window_layout`` say 0,
rotary and under a sliding window where they say 1; softmax-routed ReGLU
experts applied to the post-attention stream; two norms a layer; no
shared expert, no dense layer, no bias anywhere.

A whole-document forward in float32: no cache and no ring (every layer
is one dense masked softmax over the whole document, a block of queries
at a time so that the scores of 16,384 positions never exist at once),
no chunks, no sort and no grouped matmul (every held expert run densely
over all tokens under a mask of the tokens that chose it), every matmul
at the caller's ``jax.default_matmul_precision("highest")``. ``eps`` =
``rms_norm_eps``, ``k`` = ``moe_num_active_primary_experts``:

    x = E[ids]                                          (no multiplier)
    layer i:
      z = x W_r                       (the residual as the layer gets it,
                                       BEFORE input_norm)
      (e, l) = the k largest of z and where;  w = softmax(l), float32
      a = RMSNorm(x; input_norm)
      q = a W_q, k = a W_k, v = a W_v, a head of d = head_dim each
      rope_layout[i] = 1: q, k = rotary(q, k) at absolute positions, all
        d dims, frequencies rope_theta^(-2j/d), ``rotate_half`` pairs
        (x[j], x[j + d/2]); 0: no rotary
      s_tj = q_t . k_j / sqrt(d), j <= t, and where
        sliding_window_layout[i] = 1 also t - j < sliding_window_size;
        query head h reads key/value head h // (Hq / Hkv)
      x = x + softmax_j(s) v W_o
      m = RMSNorm(x; post_norm)
      x = x + sum_{j<k} w_j Down_{e_j}(relu(Gate_{e_j} m) * Up_{e_j} m)
    out = RMSNorm(x; final_norm)

**The share** (``experts_held: {"first", "count", "of"}``): the router
is ``of`` wide; the sum runs over the chosen experts in ``[first, first +
count)`` only. The published deployment holds all of them.

What the config does not settle is listed in the configuration's file
under ``assumed``: the router's place before ``input_norm``, ReGLU (no
key names the activation), no "secondary" experts. Departures: no LM
head (an encoder is what is pooled); ``[q | k | v]`` and every expert's
``[gate | up]`` are one fused matrix each (the same numbers).

Weights are read in the layout ``init_params`` makes (a dict of leaves a
layer, ``layers/layer_<i>``) and upcast to float32 a layer (an expert)
at a time. Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import rotary  # rotate_half, plain theta
from benchmark.reference.deepseek_v3 import rms_norm
from benchmark.reference.granite_hybrid import _stack  # seeded draws

F32 = jnp.float32


def dims(model: dict) -> dict:
    held = model.get("experts_held") or {
        "first": 0, "count": model["moe_num_primary_experts"],
        "of": model["moe_num_primary_experts"]}
    return {
        "L": model["num_hidden_layers"], "E": model["hidden_size"],
        "Hq": model["num_attention_heads"],
        "Hkv": model["num_key_value_heads"], "d": model["head_dim"],
        "Fe": model["moe_ffn_hidden_size"],
        "k": model["moe_num_active_primary_experts"],
        "first": held["first"], "held": held["count"], "experts": held["of"],
    }


# -- weights -----------------------------------------------------------------

def init_params(key, model: dict, weights: dict = None,
                dtype=jnp.float32) -> dict:
    """Seeded weights: matrices at ``1/sqrt(fan_in)`` with the tails
    ``weights`` names (drawn in row blocks of at most 2**25 numbers);
    the embedding at unit scale (there is no multiplier), norms at 1;
    the router alone with normal tails, as the other configurations'."""
    d = dims(model)
    keys = iter(jax.random.split(key, 8 * d["L"] + 2))

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
        return {
            "input_norm": ones(E), "post_norm": ones(E),
            "qkv": mat(E, (d["Hq"] + 2 * d["Hkv"]) * hd),
            "o": mat(d["Hq"] * hd, E),
            "router": mat(E, d["experts"], tails=None),
            "experts_in": mat(E, 2 * d["Fe"], n=d["held"]),
            "experts_out": mat(d["Fe"], E, n=d["held"]),
        }

    return {"embedding": mat(model["vocab_size"], E, std=1.0),
            "final_norm": ones(E),
            "layers": {f"layer_{i}": layer(i) for i in range(d["L"])}}


# -- layers ------------------------------------------------------------------

def attention(p, a, model: dict, rope: bool, sliding: bool,
              q_block: int = 256):
    """The attention branch of one layer: ``a`` ``(b, T, E)`` the normed
    input."""
    d = dims(model)
    b, T, _ = a.shape
    Hq, Hkv, hd = d["Hq"], d["Hkv"], d["d"]
    qkv = a @ p["qkv"]
    q = qkv[..., :Hq * hd].reshape(b, T, Hq, hd)
    k = qkv[..., Hq * hd:(Hq + Hkv) * hd].reshape(b, T, Hkv, hd)
    v = qkv[..., (Hq + Hkv) * hd:].reshape(b, T, Hkv, hd)
    if rope:
        q, k = rotary(q, model), rotary(k, model)
    k = jnp.repeat(k, Hq // Hkv, axis=2)
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    j = jnp.arange(T)[None, :]

    def queries(xs):
        q_blk, t = xs  # (b, qb, Hq, hd), (qb,) their positions
        s = jnp.einsum("bthd,bshd->bhts", q_blk, k) / math.sqrt(hd)
        seen = j <= t[:, None]
        if sliding:
            seen = seen & (t[:, None] - j < model["sliding_window_size"])
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)

    qb = q_block if T > q_block and T % q_block == 0 else T
    out = jax.lax.map(queries, (
        q.reshape(b, T // qb, qb, Hq, hd).swapaxes(0, 1),
        jnp.arange(T).reshape(T // qb, qb)))
    return out.swapaxes(0, 1).reshape(b, T, Hq * hd) @ p["o"]


def route(x, w_router, model: dict):
    """``(experts (N, k), weights (N, k), logits (N, experts))``: the k
    largest logits a token, and the softmax over them."""
    if not model.get("moe_primary_router_apply_softmax", True):
        raise NotImplementedError(
            "moe_primary_router_apply_softmax false is not implemented")
    logits = x @ w_router
    experts = jnp.argsort(-logits, axis=-1)[:, :dims(model)["k"]]
    picked = jnp.take_along_axis(logits, experts, axis=-1)
    return experts, jax.nn.softmax(picked, axis=-1), logits


def reglu(x, w_in, w_out):
    g, u = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.relu(g) * u) @ w_out


def routed_part(p, m, experts, weights, first: int):
    """``sum over chosen j in [first, first + count)  w_j E_j(m)``: each
    held expert run over ALL tokens and weighted by what each token gave
    it (0 for a token that did not choose it)."""
    def one(y, xs):
        j, w_in, w_out = xs
        w_j = jnp.sum(jnp.where(experts == first + j, weights, 0.0), axis=-1)
        return y + w_j[:, None] * reglu(
            m, w_in.astype(F32), w_out.astype(F32)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        jnp.arange(p["experts_in"].shape[0]), p["experts_in"],
        p["experts_out"]))
    return y


def encode(params: dict, tokens, model: dict):
    """``(hidden (b, T, E), chosen)``: the final norm's output for every
    position, and per layer the experts every token chose ``(b * T,
    k)``."""
    if model.get("rope_scaling") is not None:
        raise NotImplementedError("rope_scaling is not implemented")
    d = dims(model)
    eps = model["rms_norm_eps"]
    b, T = tokens.shape
    x = jnp.take(params["embedding"], tokens, axis=0).astype(F32)
    chosen = []
    for i in range(d["L"]):
        p = params["layers"][f"layer_{i}"]

        def f32(name, p=p):
            return p[name].astype(F32)

        experts, weights, _ = route(x.reshape(b * T, -1), f32("router"),
                                    model)
        chosen.append(experts)
        x = x + attention(
            {"qkv": f32("qkv"), "o": f32("o")},
            rms_norm(x, f32("input_norm"), eps), model,
            rope=bool(model["rope_layout"][i]),
            sliding=bool(model["sliding_window_layout"][i]))
        m = rms_norm(x, f32("post_norm"), eps)
        x = x + routed_part(p, m.reshape(b * T, -1), experts, weights,
                            d["first"]).reshape(b, T, -1)
    return rms_norm(x, params["final_norm"].astype(F32), eps), chosen
