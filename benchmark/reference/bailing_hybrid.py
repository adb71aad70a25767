"""Plain reference of the bailing-hybrid encoder (``model_type:
bailing_hybrid``, Ling 3.0): delta-rule linear attention with a decay
per channel (Kimi Delta Attention, arXiv:2510.26692) in five layers of
``layer_group_size`` = 6 and multi-head latent attention in the sixth, a
dense SwiGLU MLP in the first ``first_k_dense_replace`` layers and
sigmoid-routed experts with a shared one in the others.

A whole-document forward in float32: the recurrence token by token (a
``lax.scan`` over the document), no chunks and no carried state; latent
attention with no cache (keys and values of every position are expanded
from the latent and met by one dense masked softmax, a block of queries
at a time so that the scores of 16,384 positions never exist at once);
no grouped matmul (a loop over the held experts, each run densely over
all tokens and masked); every matmul at the caller's
``jax.default_matmul_precision("highest")``. ``eps`` = ``rms_norm_eps``:

    h = E[ids]
    layer i:  h = h + mixer_i(RMSNorm(h));  h = h + FFN_i(RMSNorm(h))
      a latent layer where (i + 1) % layer_group_size == 0, else a linear one
    out = RMSNorm(h)

    linear layer, H heads of d (x the normed input):
      q~, k~, v~ = x W_q, x W_k, x W_v
      q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~)): depthwise
        causal conv over time, short_conv_kernel_size taps, zeros before
        the document
      q_h = q_h / sqrt(|q_h|^2 + 1e-6) / sqrt(d);  k_h = k_h / sqrt(|k_h|^2 + 1e-6)
      g_t = kda_lower_bound * sigmoid(exp(A_log_h) * (x W_f + dt_bias)), (H, d)
      b_t = sigmoid(x W_b), (H,)
      S' = diag(exp(g_t)) S_{t-1};  S_t = S' + b_t k_t (v_t - k_t^T S')^T
      o_t = S_t^T q_t
      y_t = [RMSNorm_d(o_t,h; o_norm) * sigmoid(x W_g)] W_o
    latent layer, H heads:
      q = x W_q, a head [q_nope | q_pe]     (one matrix: q_lora_rank null)
      [c | k_pe] = x W_dkv;  c = RMSNorm(c);  k_pe one head for all
      [k_nope | v] = c W_ukv, a head
      q_pe, k_pe = rotary(q_pe, k_pe): theta rope_theta, pairs (2i, 2i + 1)
        de-interleaved before ``rotate_half`` (rope_interleave), no scaling
      P = causal softmax((q_nope.k_nope + q_pe.k_pe) / sqrt(nope + rope))
      y = concat_heads((P v)_h * sigmoid(x w_h)) W_o     (head-wise gate)
    FFN, layer < first_k_dense_replace: (silu(g) * u) W_out, [g | u] = x W_in
    FFN, the others: DeepSeek-V3's router and sum (reference/deepseek_v3.py:
      ``route``, ``routed_part``), plus the shared expert

**The share** (``experts_held: {"first", "count", "of"}``): the router
is ``of`` wide; the sum runs over the chosen experts in ``[first, first +
count)`` only, plus the shared expert: what the other chips' experts
would add is left out, here as in the program.

What the published config does not settle (the configuration lists each
under ``assumed``): ``use_qk_norm`` is the linear layers' L2 norm of
``q`` and ``k`` (the mechanism's published form), the latent layers norm
``c`` only; no rotary in the linear layers; the head-wise gate is the
latent layers', the linear layers' output gate a full matrix inside the
gated norm (``no_kda_lora``); ``A_log`` ``(H,)`` and ``dt_bias`` ``(H
d,)``; layer ``i`` is latent where ``(i + 1) % layer_group_size == 0``.
Departures: no LM head and no multi-token-prediction module (an encoder
is what is pooled); a non-zero SwiGLU limit is refused, not guessed;
``[q | k | v]``, the three gates ``[f | g | b]`` and every SwiGLU's
``[gate | up]`` are one fused matrix each (the same numbers).

Weights are read in the layout ``init_params`` makes (a dict of leaves a
layer, ``layers/layer_<i>``) and upcast to float32 a layer (an expert)
at a time. Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import (  # the same plain pieces
    rms_norm, rotary, route, routed_part, swiglu)
from benchmark.reference.granite_hybrid import _stack  # seeded draws

F32 = jnp.float32
_LIMITS = ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list")


def dims(model: dict) -> dict:
    held = model.get("experts_held") or {
        "first": 0, "count": model["num_experts"],
        "of": model["num_experts"]}
    L = model["num_hidden_layers"]
    for name in _LIMITS:
        if any(list(model.get(name) or [])[:L]):
            raise NotImplementedError(
                f"{name} is non-zero in a layer held: not guessed")
    return {
        "L": L, "D": model["first_k_dense_replace"],
        "period": model["layer_group_size"], "E": model["hidden_size"],
        "H": model["num_attention_heads"], "d": model["head_dim"],
        "K": model["short_conv_kernel_size"],
        "rank": model["kv_lora_rank"], "nope": model["qk_nope_head_dim"],
        "rope": model["qk_rope_head_dim"], "v": model["v_head_dim"],
        "F": model["intermediate_size"], "Fe": model["moe_intermediate_size"],
        "Fs": model["moe_shared_expert_intermediate_size"]
        * model["num_shared_experts"],
        "first": held["first"], "held": held["count"], "experts": held["of"],
    }


def is_latent(model: dict, layer: int) -> bool:
    return (layer + 1) % model["layer_group_size"] == 0


# -- weights -----------------------------------------------------------------

def init_params(key, model: dict, weights: dict = None,
                dtype=jnp.float32) -> dict:
    """Seeded weights: matrices at ``1/sqrt(fan_in)`` with the tails
    ``weights`` names (drawn in row blocks of at most 2**25 numbers), the
    embedding at 1, norms at 1, the conv at ``1/sqrt(taps)``. The decay
    gate's own parameters in float32, chosen so that a layer holds
    memories of every length a 16,384-token thread can use: ``exp(A_log)``
    uniform in [0.5, 1.5] a head, and ``dt_bias`` a channel such that a
    zero pre-activation decays that channel at a rate log-uniform in
    [1e-4, 1] a token (``kda_lower_bound * sigmoid(exp(A_log) * dt_bias)
    = -rate``); the token's own ``x W_f`` then moves the rate by about
    ``e^{+-1}``. The router is drawn with normal tails and the
    ``e_score_correction_bias`` ~ N(0, 0.005) in float32: non-zero, so
    that "choose with the bias, weigh without it" is inside every
    comparison, small, as a bias that has evened the load is
    (``reference/afmoe.py`` has the measurement behind both)."""
    d = dims(model)
    keys = iter(jax.random.split(key, 16 * d["L"] + 2))

    def mat(rows, cols, std=None, n=None, tails=weights):
        blocks = 1
        while rows * cols // blocks > 2 ** 25 or rows % blocks:
            blocks += 1
        w = _stack(next(keys), (n or 1) * blocks, (rows // blocks, cols),
                   std or 1.0 / math.sqrt(rows), tails, dtype)
        return w.reshape(((n,) if n else ()) + (rows, cols))

    E, H, hd = d["E"], d["H"], d["d"]
    D = H * hd
    bound = -float(model["kda_lower_bound"])

    def ones(n):
        return jnp.ones((n,), dtype)

    def mixer(i):
        if is_latent(model, i):
            return {
                "q": mat(E, H * (d["nope"] + d["rope"])),
                "kv_a": mat(E, d["rank"] + d["rope"]),
                "kv_norm": ones(d["rank"]),
                "kv_b": mat(d["rank"], H * (d["nope"] + d["v"])),
                "gate": mat(E, H), "o": mat(H * d["v"], E)}
        rate = jax.random.uniform(next(keys), (H,), F32, 0.5, 1.5)
        decay = jnp.exp(jax.random.uniform(
            next(keys), (H, hd), F32, math.log(1e-4), 0.0))
        share = decay / bound                    # sigmoid's value wanted
        return {
            "qkv": mat(E, 3 * D),
            "conv_w": mat(3 * D, d["K"], std=1.0 / math.sqrt(d["K"])),
            "gates": mat(E, 2 * D + H),
            "A_log": jnp.log(rate),
            "dt_bias": ((jnp.log(share) - jnp.log1p(-share))
                        / rate[:, None]).reshape(D),
            "o_norm": ones(hd), "o": mat(D, E)}

    def ffn(i):
        if i < d["D"]:
            return {"w_in": mat(E, 2 * d["F"]), "w_out": mat(d["F"], E)}
        return {
            "router": mat(E, d["experts"], tails=None),
            "bias": 0.005 * jax.random.normal(next(keys), (d["experts"],),
                                              F32),
            "shared_in": mat(E, 2 * d["Fs"]), "shared_out": mat(d["Fs"], E),
            "experts_in": mat(E, 2 * d["Fe"], n=d["held"]),
            "experts_out": mat(d["Fe"], E, n=d["held"])}

    return {"embedding": mat(model["vocab_size"], E, std=1.0),
            "final_norm": ones(E),
            "layers": {f"layer_{i}": dict(
                mixer(i), **ffn(i), norm=ones(E), ffn_norm=ones(E))
                for i in range(d["L"])}}


# -- layers ------------------------------------------------------------------

def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def conv_silu(x, w):
    """``silu`` of the depthwise causal conv of ``x`` ``(b, T, C)`` with
    ``w`` ``(C, K)`` (``w[:, K - 1]`` meets the current token), zeros
    before the document."""
    K, T = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, j:j + T] * w[:, j] for j in range(K)))


def delta_rule(q, k, v, g, beta):
    """``o (b, T, H, d)`` of the recurrence from a zero state, a token
    at a time."""
    b, _, H, d = q.shape

    def step(S, xs):
        qt, kt, vt, gt, bt = xs                 # (b, H, d) x 4, (b, H)
        S = jnp.exp(gt)[..., None] * S
        delta = bt[..., None] * (vt - jnp.einsum("bhc,bhcv->bhv", kt, S))
        S = S + kt[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhc,bhcv->bhv", qt, S)

    _, o = jax.lax.scan(step, jnp.zeros((b, H, d, v.shape[-1]), F32), tuple(
        a.swapaxes(0, 1) for a in (q, k, v, g, beta)))
    return o.swapaxes(0, 1)


def linear_attention(p, x, model: dict):
    """The linear layer's mixer over the normed input ``x`` ``(b, T, E)``."""
    dm = dims(model)
    b, T, _ = x.shape
    H, d = dm["H"], dm["d"]
    D = H * d
    qkv = conv_silu(x @ p["qkv"], p["conv_w"])
    q, k, v = (qkv[..., j * D:(j + 1) * D].reshape(b, T, H, d)
               for j in range(3))
    q, k = l2_norm(q) / math.sqrt(d), l2_norm(k)
    fgb = x @ p["gates"]
    g = model["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None]
        * (fgb[..., :D] + p["dt_bias"]).reshape(b, T, H, d))
    beta = jax.nn.sigmoid(fgb[..., 2 * D:])
    o = delta_rule(q, k, v, g, beta)
    o = rms_norm(o, p["o_norm"], model["rms_norm_eps"]) \
        * jax.nn.sigmoid(fgb[..., D:2 * D]).reshape(b, T, H, d)
    return o.reshape(b, T, D) @ p["o"]


def latent_attention(p, x, model: dict, q_block: int = 256):
    """The latent layer's mixer over the normed input ``x``."""
    dm = dims(model)
    b, T, _ = x.shape
    H, nope, rope, v_dim = dm["H"], dm["nope"], dm["rope"], dm["v"]
    q = (x @ p["q"]).reshape(b, T, H, nope + rope)
    kv_a = x @ p["kv_a"]
    c = rms_norm(kv_a[..., :dm["rank"]], p["kv_norm"], model["rms_norm_eps"])
    k_pe = rotary(kv_a[..., dm["rank"]:][:, :, None, :], model)[:, :, 0]
    kv = (c @ p["kv_b"]).reshape(b, T, H, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_pe = q[..., :nope], rotary(q[..., nope:], model)
    j = jnp.arange(T)[None, :]

    def queries(xs):
        qn, qp, t = xs          # (b, qb, H, .) x 2, (qb,) their positions
        s = (jnp.einsum("bthd,bshd->bhts", qn, k_nope)
             + jnp.einsum("bthr,bsr->bhts", qp, k_pe)) \
            / math.sqrt(nope + rope)
        s = jnp.where(j <= t[:, None], s, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)

    qb = q_block if T > q_block and T % q_block == 0 else T

    def blocks(a):
        return a.reshape(b, T // qb, qb, H, a.shape[-1]).swapaxes(0, 1)

    out = jax.lax.map(queries, (blocks(q_nope), blocks(q_pe),
                                jnp.arange(T).reshape(T // qb, qb)))
    out = out.swapaxes(0, 1).reshape(b, T, H, v_dim)
    out = out * jax.nn.sigmoid(x @ p["gate"])[..., None]
    return out.reshape(b, T, H * v_dim) @ p["o"]


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


_MIXER = {True: ("q", "kv_a", "kv_norm", "kv_b", "gate", "o"),
          False: ("qkv", "conv_w", "gates", "A_log", "dt_bias", "o_norm",
                  "o")}


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
        latent = is_latent(model, i)
        mixer = {k: p[k].astype(F32) for k in _MIXER[latent]}
        x = rms_norm(h, p["norm"].astype(F32), eps)
        h = h + (latent_attention if latent else linear_attention)(
            mixer, x, model)
        x = rms_norm(h, p["ffn_norm"].astype(F32), eps)
        if i < d["D"]:
            h = h + swiglu(x, p["w_in"].astype(F32), p["w_out"].astype(F32))
        else:
            y, experts = moe_layer(p, x.reshape(b * T, -1), model)
            h = h + y.reshape(b, T, -1)
            chosen.append(experts)
    return rms_norm(h, params["final_norm"].astype(F32), eps), chosen
