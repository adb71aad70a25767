"""Plain reference of the Granite 4.0-H hybrid encoder (``model_type:
granitemoehybrid`` with ``num_local_experts: 0``): Mamba-2 layers with a
GQA attention layer where ``layer_types`` says so, a gated MLP after
every mixer, pre-norm residual blocks with Granite's multipliers.

A whole-document forward in float32: the state-space recurrence token by
token (a ``lax.scan`` over time, no chunks), attention as one dense
masked softmax (no cache), every matmul at the caller's
``jax.default_matmul_precision("highest")``. Equations, ``eps`` =
``rms_norm_eps``, no biases but the conv's:

    h0 = embedding_multiplier * E[ids]
    every layer:  h = h + residual_multiplier * mixer(RMSNorm(h))
                  h = h + residual_multiplier * mlp(RMSNorm(h))
    mlp(u) = (silu(g) * v) @ W_out,   [g, v] = split(u @ W_in)
    out = RMSNorm(h)

    Mamba-2 mixer:  [z, xBC, dt] = split(u @ W_in_proj)
      xBC = silu(causal_depthwise_conv1d(xBC));  [x, B, C] = split(xBC)
      dt = softplus(dt + dt_bias);  A = -exp(A_log)
      S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] outer(x_t[h], B_t)
      y_t[h] = S_t[h] @ C_t + D[h] x_t[h]
      mixer = (RMSNorm(y * silu(z)) * w_norm) @ W_out_proj
    attention mixer:  scores = attention_multiplier * q k^T, causal,
      softmax, query head i reads key/value head i // (Hq / Hkv), no
      rotary embedding (``position_embedding_type: nope``)

Departures from the published model: the tied LM head (logits /
``logits_scaling``) is not run, an encoder is what is pooled; no
dropout (the published config has none at evaluation either).

Weights are read in the layout ``init_params`` makes (the Mamba layers'
leaves stacked on a leading axis, the attention layers' on another) and
upcast to float32 a layer at a time, so the reference fits beside
bfloat16 weights of 3 B parameters. Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


# -- weights -----------------------------------------------------------------

def _t_tails(key, shape, weights):
    """Unit-variance draws: normal, or Student-t (df 4) built from five
    normals (``n0 / sqrt(mean of four squares)``; ``jax.random.t``'s
    gamma sampler is a rejection loop an element, minutes at 3 B)."""
    if not weights or weights["dist"] == "normal":
        return jax.random.normal(key, shape, F32)
    if weights["dist"] != "student_t" or int(weights["df"]) != 4:
        raise ValueError(f"weights {weights!r}: normal or student_t df 4")
    n = jax.random.normal(key, (5,) + tuple(shape), F32)
    t = n[0] * jax.lax.rsqrt(jnp.mean(n[1:] ** 2, axis=0))
    return t / math.sqrt(2.0)  # var of t(4) is df / (df - 2) = 2


def _stack(key, n, shape, std, weights, dtype):
    """``(n,) + shape`` drawn a slice at a time, cast as it is made."""
    return jax.lax.map(
        lambda k: (_t_tails(k, shape, weights) * std).astype(dtype),
        jax.random.split(key, n))


def dims(model: dict) -> dict:
    kinds = list(model["layer_types"])
    d_inner = model["mamba_n_heads"] * model["mamba_d_head"]
    return {
        "mamba": kinds.count("mamba"), "attention": kinds.count("attention"),
        "E": model["hidden_size"], "F": model["shared_intermediate_size"],
        "d_inner": d_inner, "N": model["mamba_d_state"],
        "conv_dim": d_inner + 2 * model["mamba_n_groups"]
        * model["mamba_d_state"],
        "H": model["mamba_n_heads"], "P": model["mamba_d_head"],
        "K": model["mamba_d_conv"],
        "Hq": model["num_attention_heads"],
        "Hkv": model["num_key_value_heads"],
        "d": model["hidden_size"] // model["num_attention_heads"],
    }


def init_params(key, model: dict, weights: dict = None,
                dtype=jnp.float32) -> dict:
    """Seeded weights: matrices at ``1/sqrt(fan_in)`` with the tails
    ``weights`` names, norms at 1, and the scan's own parameters as
    Mamba-2 publishes them: ``A`` uniform in [1, 16], ``dt`` log-uniform
    in [1e-3, 1e-1] through ``dt_bias``'s inverse softplus, ``D`` = 1."""
    D = dims(model)
    M, A, E, F = D["mamba"], D["attention"], D["E"], D["F"]
    keys = iter(jax.random.split(key, 20))

    def mat(n, rows, cols):
        return _stack(next(keys), n, (rows, cols), 1.0 / math.sqrt(rows),
                      weights, dtype)

    V = model["vocab_size"]
    blocks = V // 1024 if V % 1024 == 0 else 1
    embedding = _stack(next(keys), blocks, (V // blocks, E),
                       1.0 / model["embedding_multiplier"], weights,
                       dtype).reshape(V, E)
    dt = jnp.exp(jax.random.uniform(
        next(keys), (M, D["H"]), F32, math.log(1e-3), math.log(1e-1)))
    a = jax.random.uniform(next(keys), (M, D["H"]), F32, 1.0, 16.0)
    kvd = D["Hkv"] * D["d"]
    return {
        "embedding": embedding,
        "final_norm": jnp.ones((E,), dtype),
        "mamba": {
            "norm": jnp.ones((M, E), dtype),
            "in_proj": mat(M, E, D["d_inner"] + D["conv_dim"] + D["H"]),
            "conv_w": _stack(next(keys), M, (D["conv_dim"], D["K"]),
                             1.0 / math.sqrt(D["K"]), weights, dtype),
            "conv_b": jnp.zeros((M, D["conv_dim"]), dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(a).astype(dtype),
            "D": jnp.ones((M, D["H"]), dtype),
            "gated_norm": jnp.ones((M, D["d_inner"]), dtype),
            "out_proj": mat(M, D["d_inner"], E),
            "mlp_norm": jnp.ones((M, E), dtype),
            "mlp_in": mat(M, E, 2 * F), "mlp_out": mat(M, F, E),
        },
        "attention": {
            "norm": jnp.ones((A, E), dtype),
            "q": mat(A, E, E), "k": mat(A, E, kvd), "v": mat(A, E, kvd),
            "o": mat(A, E, E),
            "mlp_norm": jnp.ones((A, E), dtype),
            "mlp_in": mat(A, E, 2 * F), "mlp_out": mat(A, F, E),
        },
    }


# -- layers ------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mlp(p, u):
    g, v = jnp.split(u @ p["mlp_in"], 2, axis=-1)
    return (jax.nn.silu(g) * v) @ p["mlp_out"]


def mamba_mixer(p, u, model, S0=None, tail=None):
    """``u`` ``(b, T, E)``; returns the mixer's output, the final state
    ``(b, H, P, N)`` and the conv's last ``K - 1`` inputs."""
    D = dims(model)
    b, T, _ = u.shape
    di, N, H, P, K = D["d_inner"], D["N"], D["H"], D["P"], D["K"]
    zxd = u @ p["in_proj"]
    z, xBC, dt = zxd[..., :di], zxd[..., di:di + D["conv_dim"]], \
        zxd[..., di + D["conv_dim"]:]
    if tail is None:
        tail = jnp.zeros((b, K - 1, D["conv_dim"]), F32)
    xp = jnp.concatenate([tail, xBC], axis=1)
    conv = p["conv_b"] + sum(xp[:, k:k + T] * p["conv_w"][:, k]
                             for k in range(K))
    xBC = jax.nn.silu(conv)
    x = xBC[..., :di].reshape(b, T, H, P)
    B, C = xBC[..., di:di + N], xBC[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    def step(S, inp):
        xt, dtt, Bt, Ct = inp
        S = jnp.exp(dtt * A)[..., None, None] * S \
            + (dtt[..., None] * xt)[..., None] * Bt[:, None, None, :]
        return S, jnp.einsum("bhpn,bn->bhp", S, Ct) + p["D"][:, None] * xt

    if S0 is None:
        S0 = jnp.zeros((b, H, P, N), F32)
    S, ys = jax.lax.scan(step, S0, tuple(
        a.swapaxes(0, 1) for a in (x, dt, B, C)))
    y = ys.swapaxes(0, 1).reshape(b, T, di) * jax.nn.silu(z)
    y = rms_norm(y, p["gated_norm"], model["rms_norm_eps"])
    return y @ p["out_proj"], S, xp[:, T:]


def attention_mixer(p, u, model):
    D = dims(model)
    b, T, _ = u.shape
    Hq, Hkv, d = D["Hq"], D["Hkv"], D["d"]
    q = (u @ p["q"]).reshape(b, T, Hq, d)
    k = jnp.repeat((u @ p["k"]).reshape(b, T, Hkv, d), Hq // Hkv, axis=2)
    v = jnp.repeat((u @ p["v"]).reshape(b, T, Hkv, d), Hq // Hkv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * model["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    return out.reshape(b, T, Hq * d) @ p["o"]


def encode(params: dict, tokens, model: dict, states=None):
    """``(hidden (b, T, E), states)``: the final norm's output for every
    position and, per Mamba layer, the final SSM state and conv tail.
    ``states`` (the same structure, or ``None`` for zeros) start the
    Mamba layers; attention always starts from nothing, so a document is
    handed over whole."""
    eps, res = model["rms_norm_eps"], model["residual_multiplier"]
    h = jnp.take(params["embedding"], tokens, axis=0).astype(F32) \
        * model["embedding_multiplier"]

    def block(p, h, mixer):
        out, *state = mixer(p, rms_norm(h, p["norm"], eps))
        h = h + res * out
        return h + res * mlp(p, rms_norm(h, p["mlp_norm"], eps)), state

    def layer_of(stack, i):
        return jax.tree.map(lambda a: a[i].astype(F32), stack)

    new_states = []
    at = {"mamba": 0, "attention": 0}
    kinds = list(model["layer_types"])
    i = 0
    while i < len(kinds):
        kind = kinds[i]
        n = 1
        while i + n < len(kinds) and kinds[i + n] == kind:
            n += 1
        first = at[kind]
        if kind == "mamba":
            # one scan over the run's layers: the same body n times
            def body(h, xs):
                j, S0, tail = xs
                h, (S, tail) = block(
                    layer_of(params["mamba"], j), h,
                    lambda p, u: mamba_mixer(p, u, model, S0, tail))
                return h, (S, tail)

            D = dims(model)
            b = tokens.shape[0]
            if states is None:
                S0 = jnp.zeros((n, b, D["H"], D["P"], D["N"]), F32)
                tails = jnp.zeros((n, b, D["K"] - 1, D["conv_dim"]), F32)
            else:
                S0 = jnp.stack([s[0] for s in states[first:first + n]])
                tails = jnp.stack([s[1] for s in states[first:first + n]])
            h, (S, tails) = jax.lax.scan(
                body, h, (jnp.arange(first, first + n), S0, tails))
            new_states += [(S[j], tails[j]) for j in range(n)]
        else:
            for j in range(first, first + n):
                h, _ = block(
                    layer_of(params["attention"], j), h,
                    lambda p, u: (attention_mixer(p, u, model),))
        at[kind] += n
        i += n
    return rms_norm(h, params["final_norm"].astype(F32), eps), new_states
