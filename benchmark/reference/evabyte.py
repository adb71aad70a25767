"""Plain reference of the EvaByte encoder (``model_type: evabyte``): a
byte-level dense transformer whose attention is EVA (Zheng, Yuan, Wang,
Kong, "Efficient Attention via Control Variates", ICLR 2023,
arXiv:2302.04542): a block of ``window_size`` positions attended exactly
beside one summary for every ``chunk_size`` positions of everything
before it, under one softmax.

A whole-document forward in float32: no cache, no chunk programs, no
running softmax. The two sets a query attends to are MASKS over all
``n`` positions and over all ``n / chunk_size`` chunks, a block of
queries at a time so that 32,768 positions fit; every matmul at the
caller's ``jax.default_matmul_precision("highest")``. ``eps`` =
``rms_norm_eps``, ``s = head_dim ** -0.5``:

    norm(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)     (norm_add_unit_offset)
    h = E[ids]
    layer l:  h = h + attn_l(norm(h; w1));  h = h + mlp_l(norm(h; w2))
    out = norm(h; w_f)
    mlp(u) = (silu(u W_g) * (u W_u)) W_d

    attn(u), H heads of d = hidden_size / H:
      q = u W_q, k = u W_k, v = u W_v; rotary on all d dims of q and k at
        the position's index, theta rope_theta, pairs (i, i + d/2)
        (rotate_half); keys are turned BEFORE they are summarised
      chunk c = positions chunk_size c .. chunk_size c + chunk_size - 1:
        a_m = softmax_{m in c}(s phi_h . k_m)
        ksum_c = sum_m a_m k_m + mu_h;  vsum_c = sum_m a_m v_m
      query i, block B(i) = i // window_size:
        E_i = {j : B(j) = B(i), j <= i}
        C_i = {c : (chunk_size c) // window_size < B(i)}
        o_i = softmax over [s q_i.k_j, j in E_i | s q_i.ksum_c, c in C_i]
              times [v_j | vsum_c]
      attn = concat_heads(o) W_o

What the published config does not settle (the configuration lists each
under ``assumed``): the ``rotate_half`` pairing; keys turned before they
are pooled; ``phi_h`` and ``mu_h``, two learned vectors a head, standing
where the paper draws ``w ~ N(mu_c, I)``: nothing is sampled at
inference. Departures: no LM head and none of the ``num_pred_heads``
multi-byte heads (an encoder is what is pooled); ``[q | k | v]`` and the
SwiGLU's ``[gate | up]`` are one fused matrix each (the same numbers).
Padding: the caller pads a document on the right; a query before the
padding sees none of it (``E_i`` is causal, ``C_i`` holds whole chunks of
earlier blocks), so the reference asks for no lengths.

Weights are read in the layout ``init_params`` makes (a dict of leaves a
layer, ``layers/layer_<i>``) and upcast to float32 a layer at a time.
Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import rotary            # rotate_half pairs
from benchmark.reference.deepseek_v3 import rms_norm, swiglu
from benchmark.reference.granite_hybrid import _stack   # seeded draws

F32 = jnp.float32
_IMPLEMENTED = {"attention_class": "eva", "hidden_act": "silu",
                "rope_scaling": None, "attention_bias": False,
                "norm_add_unit_offset": True, "num_chunks": None}
_LEAVES = ("norm", "mlp_norm", "qkv", "phi", "mu", "o", "w_in", "w_out")


def dims(model: dict) -> dict:
    for key, value in _IMPLEMENTED.items():
        if key in model and model[key] != value:
            raise NotImplementedError(f"{key}={model[key]!r}: not guessed")
    H = model["num_attention_heads"]
    if model["num_key_value_heads"] != H:
        raise NotImplementedError("a summary a key/value head a query head")
    return {"L": model["num_hidden_layers"], "E": model["hidden_size"],
            "F": model["intermediate_size"], "H": H,
            "d": model["hidden_size"] // H, "W": model["window_size"],
            "c": model["chunk_size"]}


# -- weights -----------------------------------------------------------------

def init_params(key, model: dict, weights: dict = None,
                dtype=jnp.float32) -> dict:
    """Seeded weights: matrices at ``1/sqrt(fan_in)`` with the tails
    ``weights`` names (drawn in row blocks of at most 2**25 numbers), the
    embedding at 1. The norm weights ~ N(0, 0.1): small, and not 0, so
    that a ``(1 + w)`` read as ``w`` or as 1 is inside every comparison.
    ``phi`` ~ N(0, ``phi_scale``^2) a dim (default 2): ``s phi . k`` then
    spreads by about ``phi_scale`` over a chunk's 16 keys of unit
    coordinates, so a chunk's weights are far from even (a mean in its
    place shows) and far from one-hot. ``mu`` ~ N(0, ``mu_scale``^2) a
    dim (default 0.5): a summary key moves by half a key's own norm."""
    d = dims(model)
    weights = weights or {}
    keys = iter(jax.random.split(key, 8 * d["L"] + 2))

    def mat(rows, cols, std=None):
        blocks = 1
        while rows * cols // blocks > 2 ** 25 or rows % blocks:
            blocks += 1
        w = _stack(next(keys), blocks, (rows // blocks, cols),
                   std or 1.0 / math.sqrt(rows), weights or None, dtype)
        return w.reshape(rows, cols)

    def normal(shape, std):
        return (std * jax.random.normal(next(keys), shape, F32)).astype(dtype)

    E, F, H, hd = d["E"], d["F"], d["H"], d["d"]
    return {"embedding": mat(model["vocab_size"], E, std=1.0),
            "final_norm": normal((E,), 0.1),
            "layers": {f"layer_{i}": {
                "norm": normal((E,), 0.1), "mlp_norm": normal((E,), 0.1),
                "qkv": mat(E, 3 * H * hd),
                "phi": normal((H, hd), float(weights.get("phi_scale", 2.0))),
                "mu": normal((H, hd), float(weights.get("mu_scale", 0.5))),
                "o": mat(H * hd, E),
                "w_in": mat(E, 2 * F), "w_out": mat(F, E)}
                for i in range(d["L"])}}


# -- layers ------------------------------------------------------------------

def norm(x, w, eps):
    """``rms_norm`` (the plain-weight norm) times ``1 + w``."""
    return rms_norm(x, 1.0 + w, eps)


def summaries(k, v, phi, mu, model: dict):
    """``(ksum, vsum)`` ``(b, n / c, H, d)`` of the turned keys ``k`` and
    the values ``v`` ``(b, n, H, d)``: a softmax over each chunk's ``c``
    positions a head."""
    dm = dims(model)
    b, n, H, d = k.shape
    c = dm["c"]
    kc, vc = (a.reshape(b, n // c, c, H, d) for a in (k, v))
    a = jax.nn.softmax(
        jnp.einsum("bnmhd,hd->bnmh", kc, phi) / math.sqrt(d), axis=2)
    return (jnp.einsum("bnmh,bnmhd->bnhd", a, kc) + mu,
            jnp.einsum("bnmh,bnmhd->bnhd", a, vc))


def attention(p, u, model: dict, q_block: int = 256):
    """The EVA mixer over the normed input ``u`` ``(b, n, E)``: ``(its
    output, what a later position reads of these n: the turned keys, the
    values, the chunks' summary keys and values)``."""
    dm = dims(model)
    b, n, _ = u.shape
    H, d, W, c = dm["H"], dm["d"], dm["W"], dm["c"]
    if n % c:
        raise ValueError(f"{n} positions are not whole chunks of {c}")
    q, k, v = (a.reshape(b, n, H, d)
               for a in jnp.split(u @ p["qkv"], 3, axis=-1))
    q, k = rotary(q, model), rotary(k, model)
    ksum, vsum = summaries(k, v, p["phi"], p["mu"], model)
    key_at = jnp.arange(n)[None, :]
    chunk_block = (jnp.arange(n // c) * c // W)[None, :]

    def queries(xs):
        q_blk, i = xs  # (b, qb, H, d), (qb,) their positions
        i = i[:, None]
        own = (key_at // W == i // W) & (key_at <= i)       # E_i
        passed = chunk_block < i // W                        # C_i
        s = jnp.concatenate([
            jnp.where(own, jnp.einsum("bthd,bshd->bhts", q_blk, k),
                      -jnp.inf),
            jnp.where(passed, jnp.einsum("bthd,bshd->bhts", q_blk, ksum),
                      -jnp.inf)], axis=-1) / math.sqrt(d)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bshd->bthd", w[..., :n], v) \
            + jnp.einsum("bhts,bshd->bthd", w[..., n:], vsum)

    qb = q_block if n > q_block and n % q_block == 0 else n
    out = jax.lax.map(queries, (
        q.reshape(b, n // qb, qb, H, d).swapaxes(0, 1),
        jnp.arange(n).reshape(n // qb, qb)))
    out = out.swapaxes(0, 1).reshape(b, n, H * d)
    return out @ p["o"], {"k": k, "v": v, "k_sum": ksum, "v_sum": vsum}


def encode(params: dict, tokens, model: dict):
    """``(hidden (b, n, E), read)``: the final norm's output for every
    position, and per layer what a LATER position reads of these ``n``
    (what a program that carries state hands on as it is): ``"k"``,
    ``"v"`` ``(b, n, H, d)`` the turned keys and the values, ``"k_sum"``,
    ``"v_sum"`` ``(b, n / c, H, d)`` the chunks' summaries."""
    d = dims(model)
    eps = model["rms_norm_eps"]
    h = jnp.take(params["embedding"], tokens, axis=0).astype(F32)
    read = {"k": [], "v": [], "k_sum": [], "v_sum": []}
    for i in range(d["L"]):
        p = {name: params["layers"][f"layer_{i}"][name].astype(F32)
             for name in _LEAVES}
        y, handed = attention(p, norm(h, p["norm"], eps), model)
        for name, leaf in handed.items():
            read[name].append(leaf)
        h = h + y
        h = h + swiglu(norm(h, p["mlp_norm"], eps), p["w_in"], p["w_out"])
    return norm(h, params["final_norm"].astype(F32), eps), read
