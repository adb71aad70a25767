"""Plain reference of the GLM-5 encoder (``model_type: glm_moe_dsa``):
multi-head latent attention whose every query attends only the
``index_topk`` positions a learned indexer picks, a dense SwiGLU MLP in
the first ``first_k_dense_replace`` layers, sigmoid-routed experts with
a shared one in the others.

A whole-document forward in float32: no cache, no chunk programs, no
threshold (``lax.top_k`` a query on the reference's OWN index scores,
the chosen positions scattered into a mask, one dense masked softmax),
no grouped matmul (a loop over the held experts), every matmul at the
caller's ``jax.default_matmul_precision("highest")``. Written from the
layer equations (``eps`` = ``rms_norm_eps``):

    h = E[ids]
    every layer:  u = RMSNorm(h); h = h + DSA(u); h = h + FFN(RMSNorm(h))
    out = RMSNorm(h)

    DSA(u): c_q = RMSNorm(u W_qa); q = c_q W_qb, a head [q_nope | q_pe]
      [c_kv | k_pe] = u W_kva; c_kv = RMSNorm(c_kv); k_pe one head for all
      [k_nope | v] = c_kv W_kvb, a head
      rotary on q_pe, k_pe: plain frequencies theta^(-2i/rope), the pair
        (x[2i], x[2i+1]) turned by position * f_i, IN PLACE
      indexer: qI = c_q W_Iq (index_n_heads heads of index_head_dim)
        kI = LayerNorm(u W_Ik) with mean, weight and bias, eps 1e-6
        the first `rope` dims of each qI head and of kI turned likewise
        w = (u W_Iw) * index_n_heads^-0.5 * index_head_dim^-0.5
        I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s]),  -inf for s > t
      S_t = lax.top_k(I[t], index_topk) (ties: the lower position), cut
        to s <= t: the min(index_topk, t + 1) best
      P = softmax over S_t of (q_nope.k_nope + q_pe.k_pe) * (nope + rope)^-0.5
      DSA = concat_heads(P v) W_o
    FFN, layer < first_k_dense_replace: (silu(g) * u) W_out, [g | u] = x W_in
    FFN, the others: s = sigmoid(x W_g); the num_experts_per_tok best of
      s + e_score_correction_bias over ALL experts (n_group 1: no group
      step); w = s of the chosen (WITHOUT the bias), normalised to sum 1,
      times routed_scaling_factor; FFN = sum_i w_i E_i(x) + E_shared(x)

**The share** (``experts_held: {"first", "count", "of"}``): the router
is ``of`` wide; the sum runs over the chosen experts in ``[first, first +
count)`` only, plus the shared expert, here as in the program.

What the config's keys do not settle (the LayerNorm's eps, which dims of
an index head turn, ``w``'s two factors, the tie rule; no Hadamard
rotation, no FP8 index keys; no LM head, no multi-token-prediction
module) is the configuration's ``assumed``.

Weights are read in the layout ``init_params`` makes (a dict of leaves a
layer) and upcast to float32 a layer (an expert) at a time; index scores
go a block of queries at a time and attention a group of heads at a time,
so a 32,768-token document fits beside bfloat16 weights of 3.8 B
parameters. Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.granite_hybrid import _stack  # seeded draws

F32 = jnp.float32
INDEX_NORM_EPS = 1e-6


def dims(model: dict) -> dict:
    held = model.get("experts_held") or {
        "first": 0, "count": model["n_routed_experts"],
        "of": model["n_routed_experts"]}
    L, D = model["num_hidden_layers"], model["first_k_dense_replace"]
    return {
        "L": L, "D": D, "E": model["hidden_size"],
        "H": model["num_attention_heads"], "q_rank": model["q_lora_rank"],
        "kv_rank": model["kv_lora_rank"], "nope": model["qk_nope_head_dim"],
        "rope": model["qk_rope_head_dim"], "v": model["v_head_dim"],
        "Hi": model["index_n_heads"], "di": model["index_head_dim"],
        "topk": model["index_topk"],
        "F": model["intermediate_size"], "Fe": model["moe_intermediate_size"],
        "Fs": model["moe_intermediate_size"] * model["n_shared_experts"],
        "first": held["first"], "held": held["count"], "experts": held["of"],
    }


# -- weights -----------------------------------------------------------------

def init_params(key, model: dict, weights: dict = None,
                dtype=jnp.float32) -> dict:
    """Seeded weights: matrices at ``1/sqrt(fan_in)`` with the tails
    ``weights`` names (drawn in row blocks of at most 2**25 numbers),
    the embedding at 1, RMSNorm weights at 1, a non-zero
    ``e_score_correction_bias`` ~ N(0, 0.02) in float32, and the
    indexer's LayerNorm at weight 1 + N(0, 0.1), bias N(0, 0.1), so that
    its mean, weight and bias are inside every comparison."""
    d = dims(model)
    keys = iter(jax.random.split(key, 24 * d["L"] + 2))

    def mat(rows, cols, std=None, n=None):
        blocks = 1
        while rows * cols // blocks > 2 ** 25 or rows % blocks:
            blocks += 1
        w = _stack(next(keys), (n or 1) * blocks, (rows // blocks, cols),
                   std or 1.0 / math.sqrt(rows), weights, dtype)
        return w.reshape(((n,) if n else ()) + (rows, cols))

    E, H = d["E"], d["H"]

    def layer(i):
        p = {
            "norm": jnp.ones((E,), dtype),
            "q_a": mat(E, d["q_rank"]),
            "q_norm": jnp.ones((d["q_rank"],), dtype),
            "q_b": mat(d["q_rank"], H * (d["nope"] + d["rope"])),
            "kv_a": mat(E, d["kv_rank"] + d["rope"]),
            "kv_norm": jnp.ones((d["kv_rank"],), dtype),
            "kv_b": mat(d["kv_rank"], H * (d["nope"] + d["v"])),
            "o": mat(H * d["v"], E),
            "index_q": mat(d["q_rank"], d["Hi"] * d["di"]),
            "index_k": mat(E, d["di"]),
            "index_k_norm": (1.0 + 0.1 * jax.random.normal(
                next(keys), (d["di"],), F32)).astype(dtype),
            "index_k_bias": (0.1 * jax.random.normal(
                next(keys), (d["di"],), F32)).astype(dtype),
            "index_w": mat(E, d["Hi"]),
            "ffn_norm": jnp.ones((E,), dtype),
        }
        if i < d["D"]:
            return dict(p, w_in=mat(E, 2 * d["F"]), w_out=mat(d["F"], E))
        return dict(
            p, router=mat(E, d["experts"]),
            bias=0.02 * jax.random.normal(next(keys), (d["experts"],), F32),
            shared_in=mat(E, 2 * d["Fs"]), shared_out=mat(d["Fs"], E),
            experts_in=mat(E, 2 * d["Fe"], n=d["held"]),
            experts_out=mat(d["Fe"], E, n=d["held"]))

    return {"embedding": mat(model["vocab_size"], E, std=1.0),
            "final_norm": jnp.ones((E,), dtype),
            "layers": {f"layer_{i}": layer(i) for i in range(d["L"])}}


# -- pieces ------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + bias


def swiglu(x, w_in, w_out):
    g, u = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_out


def rotary(x, model: dict):
    """The leading ``qk_rope_head_dim`` dims of ``x`` ``(b, T, ..., d)``
    turned at positions ``0 .. T - 1``: the pair ``(x[2i], x[2i+1])`` by
    the angle ``position * theta^(-2i / rope)``, written back where it
    was; the dims after them pass."""
    rope = model["qk_rope_head_dim"]
    theta = model["rope_parameters"]["rope_theta"]
    T = x.shape[1]
    freq = 1.0 / theta ** (jnp.arange(0, rope, 2, dtype=F32) / rope)
    ang = jnp.arange(T, dtype=F32)[:, None] * freq[None, :]
    ang = ang.reshape((1, T) + (1,) * (x.ndim - 3) + (rope // 2,))
    pairs = x[..., :rope].reshape(x.shape[:-1] + (rope // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return jnp.concatenate(
        [turned.reshape(x.shape[:-1] + (rope,)), x[..., rope:]], axis=-1)


def index_keys(p, u, model: dict):
    """``kI (b, T, index_head_dim)``, normed and turned."""
    return rotary(layer_norm(u @ p["index_k"], p["index_k_norm"],
                             p["index_k_bias"], INDEX_NORM_EPS), model)


def selected(p, u, c_q, model: dict, q_block: int = 128):
    """``(b, T, T)`` bool: the positions each query attends, ``S_t``."""
    d = dims(model)
    b, T, _ = u.shape
    Hi, di, k = d["Hi"], d["di"], min(d["topk"], T)
    q_i = rotary((c_q @ p["index_q"]).reshape(b, T, Hi, di), model)
    k_i = index_keys(p, u, model)
    w = (u @ p["index_w"]) * (Hi ** -0.5 * di ** -0.5)
    qb = q_block if T % q_block == 0 else T
    at = jnp.arange(T)

    def block(first):
        q = jax.lax.dynamic_slice_in_dim(q_i, first, qb, axis=1)
        w_b = jax.lax.dynamic_slice_in_dim(w, first, qb, axis=1)
        s = jnp.einsum("bthd,bsd->bths", q, k_i)
        scores = jnp.sum(w_b[..., None] * jax.nn.relu(s), axis=2)
        causal = at[None, :] <= (first + jnp.arange(qb))[:, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        _, chosen = jax.lax.top_k(scores, k)              # (b, qb, k)
        rows = jnp.arange(b)[:, None, None]
        cols = jnp.arange(qb)[None, :, None]
        mask = jnp.zeros((b, qb, T), bool).at[rows, cols, chosen].set(True)
        return mask & causal

    masks = jax.lax.map(block, jnp.arange(T // qb) * qb)  # (nq, b, qb, T)
    return masks.transpose(1, 0, 2, 3).reshape(b, T, T)


def attention(p, u, model: dict, head_block: int = 16, q_block: int = 256):
    """``(DSA(u) (b, T, E), the latent rows [c_kv | k_pe])``. Heads go
    ``head_block`` at a time from the projections on (queries, keys and
    values of all 64 heads of 32,768 positions are 5.9 GB of float32)
    and their part of ``W_o``'s product is summed as they come."""
    d = dims(model)
    b, T, _ = u.shape
    H, nope, rope, v_dim = d["H"], d["nope"], d["rope"], d["v"]
    eps = model["rms_norm_eps"]
    c_q = rms_norm(u @ p["q_a"], p["q_norm"], eps)
    kv_a = u @ p["kv_a"]
    c_kv = rms_norm(kv_a[..., :d["kv_rank"]], p["kv_norm"], eps)
    k_pe = rotary(kv_a[..., d["kv_rank"]:], model)
    scale = (nope + rope) ** -0.5
    mask = selected(p, u, c_q, model)
    qb = q_block if T % q_block == 0 else T
    hb = head_block if H % head_block == 0 else H

    def heads(out, xs):
        w_q, w_kv, w_o = xs
        q = (c_q @ w_q).reshape(b, T, hb, nope + rope)
        kv = (c_kv @ w_kv).reshape(b, T, hb, nope + v_dim)
        qn, qp = q[..., :nope], rotary(q[..., nope:], model)
        kn, vv = kv[..., :nope], kv[..., nope:]

        def queries(first):
            sl = lambda x: jax.lax.dynamic_slice_in_dim(x, first, qb, axis=1)
            s = (jnp.einsum("bthd,bshd->bhts", sl(qn), kn)
                 + jnp.einsum("bthr,bsr->bhts", sl(qp), k_pe)) * scale
            s = jnp.where(sl(mask)[:, None], s, -jnp.inf)
            return jnp.einsum("bhts,bshd->bthd",
                              jax.nn.softmax(s, axis=-1), vv)

        o = jax.lax.map(queries, jnp.arange(T // qb) * qb)
        o = o.transpose(1, 0, 2, 3, 4).reshape(b, T, hb * v_dim)
        return out + o @ w_o, None

    def by_group(w, width):  # (rows, H * width) -> (H / hb, rows, hb * width)
        return w.reshape(w.shape[0], H // hb, hb * width).swapaxes(0, 1)

    out, _ = jax.lax.scan(
        heads, jnp.zeros_like(u),
        (by_group(p["q_b"], nope + rope), by_group(p["kv_b"], nope + v_dim),
         p["o"].reshape(H // hb, hb * v_dim, -1)))
    return out, jnp.concatenate([c_kv, k_pe], axis=-1)


def route(x, w_router, bias, model: dict):
    """``(experts (N, k), weights (N, k))``: no group step."""
    if model["n_group"] != 1 or model["topk_group"] != 1:
        raise ValueError("the reference routes without a group step")
    k = model["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ w_router)
    experts = jnp.argsort(-(scores + bias), axis=-1)[:, :k]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if model.get("norm_topk_prob", True) and k > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts, weights * model["routed_scaling_factor"]


def routed_part(p, x, experts, weights, first: int):
    """``sum over chosen i in [first, first + count)  w_i E_i(x)``: each
    held expert run over ALL tokens and weighted by what each token gave
    it (0 for a token that did not choose it)."""
    def one(y, xs):
        j, w_in, w_out = xs
        w_j = jnp.sum(jnp.where(experts == first + j, weights, 0.0), axis=-1)
        return y + w_j[:, None] * swiglu(
            x, w_in.astype(F32), w_out.astype(F32)), None

    count = p["experts_in"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(count), p["experts_in"],
                         p["experts_out"]))
    return y


def moe_layer(p, x, model: dict):
    """One expert layer (its leaves ``p``) over flat tokens ``x`` ``(N,
    E)``: ``(the held share's part + the shared expert, the experts
    chosen)``."""
    experts, weights = route(x, p["router"].astype(F32), p["bias"], model)
    y = routed_part(p, x, experts, weights, dims(model)["first"])
    if model["n_shared_experts"]:
        y = y + swiglu(x, p["shared_in"].astype(F32),
                       p["shared_out"].astype(F32))
    return y, experts


_ATTENTION = ("q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o",
              "index_q", "index_k", "index_k_norm", "index_k_bias", "index_w")


def encode(params: dict, tokens, model: dict):
    """``(hidden (b, T, E), handed)``: the final norm's output for every
    position, and what a cached program would hand on: per layer the
    latent rows ``[c_kv | k_pe] (b, T, kv_rank + rope)`` and the index
    keys ``(b, T, index_head_dim)``."""
    d = dims(model)
    eps = model["rms_norm_eps"]
    b, T = tokens.shape
    h = jnp.take(params["embedding"], tokens, axis=0).astype(F32)
    latent, index = [], []
    for i in range(d["L"]):
        p = params["layers"][f"layer_{i}"]
        a = {k: p[k].astype(F32) for k in _ATTENTION}
        u = rms_norm(h, p["norm"].astype(F32), eps)
        out, rows = attention(a, u, model)
        latent.append(rows)
        index.append(index_keys(a, u, model))
        h = h + out
        x = rms_norm(h, p["ffn_norm"].astype(F32), eps)
        if i < d["D"]:
            h = h + swiglu(x, p["w_in"].astype(F32), p["w_out"].astype(F32))
        else:
            y, _ = moe_layer(p, x.reshape(b * T, -1), model)
            h = h + y.reshape(b, T, -1)
    return rms_norm(h, params["final_norm"].astype(F32), eps), \
        {"latent": latent, "index": index}
