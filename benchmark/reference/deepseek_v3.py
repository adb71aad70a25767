"""Plain reference of the DeepSeek-V3 encoder (``model_type:
deepseek_v3``): multi-head latent attention in every layer, a dense
SwiGLU MLP in the first ``first_k_dense_replace`` layers, sigmoid-routed
experts with a shared one in the others.

A whole-document forward in float32: no cache (keys and values of every
position are expanded from the latent and met by one dense masked
softmax), no chunks, no grouped matmul (a loop over the held experts,
each run densely over all tokens and masked), every matmul at the
caller's ``jax.default_matmul_precision("highest")``. It follows the
published ``modeling_deepseek_v3``; ``eps`` = ``rms_norm_eps``:

    h = E[ids]
    every layer:  h = h + MLA(RMSNorm(h));  h = h + FFN(RMSNorm(h))
    out = RMSNorm(h)

    MLA(u): c_q = RMSNorm(u W_qa); q = c_q W_qb, a head [q_nope | q_pe]
      [c_kv | k_pe] = u W_kva; c_kv = RMSNorm(c_kv); k_pe one head for all
      [k_nope | v] = c_kv W_kvb, a head
      q_pe, k_pe = rotary(q_pe, k_pe): YaRN's inverse frequencies, pairs
        de-interleaved before ``rotate_half`` as published
      P = causal softmax((q_nope.k_nope + q_pe.k_pe) * scale),
        scale = (nope + rope)^-0.5 * (0.1 mscale_all_dim ln(factor) + 1)^2
      MLA = concat_heads(P v) W_o
    FFN, layer < first_k_dense_replace: (silu(g) * u) W_out, [g | u] = x W_in
    FFN, the others: s = sigmoid(x W_g) in float32;
      choice on s + e_score_correction_bias: experts in n_group groups,
      a group's score the sum of its two best, the best topk_group groups
      kept, the best num_experts_per_tok experts among them chosen;
      w = s of the chosen (WITHOUT the bias), normalised to sum 1, times
      routed_scaling_factor;  FFN = sum_i w_i E_i(x) + E_shared(x)

**The share** (``experts_held: {"first", "count", "of"}``): the router
is ``of`` wide; the sum runs over the chosen experts in ``[first, first +
count)`` only, plus the shared expert: what the other chips' experts
would add is left out, here as in the program, and the partial result
goes on to the next layer.

Departures from the published model, each also in the configuration's
``assumed``: no LM head and no multi-token-prediction module (an encoder
is what is pooled); experts outside the kept groups are masked with
``-inf`` where the published code writes 0.0 (the same choice unless a
kept expert's biased score is negative, when 0.0 would let a masked
expert in: the paper's rule is the top-k AMONG the kept groups);
``[gate | up]`` of every SwiGLU are one fused matrix (the same numbers).

Weights are read in the layout ``init_params`` makes (a dict of leaves
a layer, ``layers/layer_<i>``) and upcast to float32 a layer (an expert)
at a time, so the reference fits beside
bfloat16 weights of 4.4 B parameters; attention goes sixteen heads at a
time for the same reason. Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.granite_hybrid import _stack  # seeded draws

F32 = jnp.float32


def dims(model: dict) -> dict:
    held = model.get("experts_held") or {
        "first": 0, "count": model["n_routed_experts"],
        "of": model["n_routed_experts"]}
    L, D = model["num_hidden_layers"], model["first_k_dense_replace"]
    return {
        "L": L, "D": D, "M": L - D, "E": model["hidden_size"],
        "H": model["num_attention_heads"], "q_rank": model["q_lora_rank"],
        "kv_rank": model["kv_lora_rank"], "nope": model["qk_nope_head_dim"],
        "rope": model["qk_rope_head_dim"], "v": model["v_head_dim"],
        "F": model["intermediate_size"], "Fe": model["moe_intermediate_size"],
        "Fs": model["moe_intermediate_size"] * model["n_shared_experts"],
        "first": held["first"], "held": held["count"], "experts": held["of"],
    }


# -- weights -----------------------------------------------------------------

def init_params(key, model: dict, weights: dict = None,
                dtype=jnp.float32) -> dict:
    """Seeded weights: matrices at ``1/sqrt(fan_in)`` with the tails
    ``weights`` names (drawn in row blocks of at most 2**25 numbers),
    the embedding at 1, norms at 1, and a non-zero
    ``e_score_correction_bias`` ~ N(0, 0.02) in float32, so that "choose
    with the bias, weigh without it" is inside every comparison."""
    d = dims(model)
    keys = iter(jax.random.split(key, 16 * d["L"] + 2))

    def mat(rows, cols, std=None, n=None):
        blocks = 1
        while rows * cols // blocks > 2 ** 25 or rows % blocks:
            blocks += 1
        w = _stack(next(keys), (n or 1) * blocks, (rows // blocks, cols),
                   std or 1.0 / math.sqrt(rows), weights, dtype)
        return w.reshape(((n,) if n else ()) + (rows, cols))

    E, H = d["E"], d["H"]
    V = model["vocab_size"]

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

    return {"embedding": mat(V, E, std=1.0),
            "final_norm": jnp.ones((E,), dtype),
            "layers": {f"layer_{i}": layer(i) for i in range(d["L"])}}


# -- rotary ------------------------------------------------------------------

def yarn_get_mscale(scale=1.0, mscale=1.0):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(model: dict):
    """``DeepseekV3YarnRotaryEmbedding``'s inverse frequencies."""
    dim, base = model["qk_rope_head_dim"], model["rope_theta"]
    rs = model.get("rope_scaling")
    exponent = jnp.arange(0, dim, 2, dtype=F32) / dim
    freq_extra = 1.0 / base ** exponent
    if not rs:
        return freq_extra
    freq_inter = 1.0 / (rs["factor"] * base ** exponent)
    orig = rs["original_max_position_embeddings"]

    def find_correction_dim(num_rotations):
        return dim * math.log(orig / (num_rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(find_correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def rotary(x, model: dict):
    """``apply_rotary_pos_emb`` on ``x`` ``(b, T, heads, d)`` at
    positions ``0 .. T - 1``."""
    T, d = x.shape[1], x.shape[-1]
    freqs = jnp.arange(T, dtype=F32)[:, None] * inv_freq(model)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    rs = model.get("rope_scaling") or {}
    m = yarn_get_mscale(rs.get("factor", 1), rs.get("mscale", 1)) \
        / yarn_get_mscale(rs.get("factor", 1), rs.get("mscale_all_dim", 0))
    cos, sin = (jnp.cos(emb) * m)[:, None, :], (jnp.sin(emb) * m)[:, None, :]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(
        x.shape)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rotated * sin


# -- layers ------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def swiglu(x, w_in, w_out):
    g, u = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_out


def attention(p, u, model: dict, head_block: int = 16):
    d = dims(model)
    b, T, _ = u.shape
    H, nope, rope, v_dim = d["H"], d["nope"], d["rope"], d["v"]
    eps = model["rms_norm_eps"]
    q = (rms_norm(u @ p["q_a"], p["q_norm"], eps) @ p["q_b"]).reshape(
        b, T, H, nope + rope)
    kv_a = u @ p["kv_a"]
    c_kv = rms_norm(kv_a[..., :d["kv_rank"]], p["kv_norm"], eps)
    k_pe = rotary(kv_a[..., d["kv_rank"]:][:, :, None, :], model)[:, :, 0]
    kv = (c_kv @ p["kv_b"]).reshape(b, T, H, nope + v_dim)
    q_pe = rotary(q[..., nope:], model)
    scale = (nope + rope) ** -0.5
    rs = model.get("rope_scaling")
    if rs and rs.get("mscale_all_dim", 0):
        scale *= yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    causal = jnp.tril(jnp.ones((T, T), bool))

    def heads(xs):
        qn, qp, kn, vv = xs  # (b, T, hb, .) each
        s = (jnp.einsum("bthd,bshd->bhts", qn, kn)
             + jnp.einsum("bthr,bsr->bhts", qp, k_pe)) * scale
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), vv)

    hb = head_block if H % head_block == 0 else H

    def split(x):  # (b, T, H, d) -> (H / hb, b, T, hb, d)
        return x.reshape(b, T, H // hb, hb, x.shape[-1]).transpose(
            2, 0, 1, 3, 4)

    out = jax.lax.map(heads, (split(q[..., :nope]), split(q_pe),
                              split(kv[..., :nope]), split(kv[..., nope:])))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, T, H * v_dim)
    return out @ p["o"]


def route(x, w_router, bias, model: dict):
    """``(experts (N, k), weights (N, k), scores (N, experts))``."""
    k, groups, kept = (model["num_experts_per_tok"], model["n_group"],
                       model["topk_group"])
    scores = jax.nn.sigmoid(x @ w_router)
    choice = scores + bias
    N, n = choice.shape
    per_group = choice.reshape(N, groups, n // groups)
    group_score = jnp.sort(per_group, axis=-1)[..., -2:].sum(-1)
    best = jnp.argsort(-group_score, axis=-1)[:, :kept]
    keep = (best[:, :, None] == jnp.arange(groups)[None, None, :]).any(1)
    masked = jnp.where(keep[:, :, None], per_group, -jnp.inf).reshape(N, n)
    experts = jnp.argsort(-masked, axis=-1)[:, :k]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if model.get("norm_topk_prob", True) and k > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts, weights * model["routed_scaling_factor"], scores


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
    first = dims(model)["first"]
    experts, weights, _ = route(x, p["router"].astype(F32), p["bias"], model)
    y = routed_part(p, x, experts, weights, first)
    if model["n_shared_experts"]:
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
    h = jnp.take(params["embedding"], tokens, axis=0).astype(F32)
    chosen = []
    for i in range(d["L"]):
        p = params["layers"][f"layer_{i}"]
        mla = {k: p[k].astype(F32) for k in (
            "q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o")}
        h = h + attention(mla, rms_norm(h, p["norm"].astype(F32), eps), model)
        x = rms_norm(h, p["ffn_norm"].astype(F32), eps)
        if i < d["D"]:
            h = h + swiglu(x, p["w_in"].astype(F32), p["w_out"].astype(F32))
        else:
            y, experts = moe_layer(p, x.reshape(b * T, -1), model)
            h = h + y.reshape(b, T, -1)
            chosen.append(experts)
    return rms_norm(h, params["final_norm"].astype(F32), eps), chosen
