"""Plain reference of the QRNN variant (Bradbury et al. 2016; fastai's
``AWD_LSTM(qrnn=True)``): embedding, N quasi-recurrent layers with
fo-pooling, gate order z, f, o; the first layer convolves over a window
of two tokens, the rest over one. ``c_t = f_t c_{t-1} + (1 - f_t) z_t``,
``h_t = o_t c_t``. Evaluation semantics (no dropout, no zoneout).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common


def init_params(key, model: dict, weights: dict = None) -> dict:
    keys = iter(jax.random.split(key, 1 + 2 * model["n_layers"]))
    params = {"embedding": common.draw(
        next(keys), (model["vocab_size"], model["emb_sz"]), 0.1)}
    for li in range(model["n_layers"]):
        in_dim = model["emb_sz"] if li == 0 else model["n_hid"]
        h = common.layer_size(model, li)
        window = 2 if li == 0 else 1
        params[f"qrnn_{li}_w"] = common.draw(
            next(keys), (3 * h, window * in_dim), common.inv_sqrt(h), weights)
        next(keys)
        params[f"qrnn_{li}_b"] = jnp.zeros((3 * h,), jnp.float32)
    return params


def layer(x, w, b, c0, window: int, x_prev=None):
    if window == 2:
        first = jnp.zeros_like(x[:, :1]) if x_prev is None \
            else x_prev[:, None]
        x = jnp.concatenate(
            [jnp.concatenate([first, x[:, :-1]], axis=1), x], axis=-1)
    gates = x @ w.T + b
    z, f, o = jnp.split(gates, 3, axis=-1)
    z, f, o = jnp.tanh(z), jax.nn.sigmoid(f), jax.nn.sigmoid(o)

    def step(c, zf):
        zt, ft = zf
        c = ft * c + (1.0 - ft) * zt
        return c, c

    c_last, cs = jax.lax.scan(step, c0, (z.swapaxes(0, 1), f.swapaxes(0, 1)))
    return o * cs.swapaxes(0, 1), c_last


def encode(params: dict, tokens, model: dict, states=None):
    x = common.embed(params, tokens)
    new_states = []
    for li in range(model["n_layers"]):
        h = common.layer_size(model, li)
        window = 2 if li == 0 else 1
        if states is None:
            c0, x_prev = jnp.zeros((tokens.shape[0], h), jnp.float32), None
        else:
            c0, x_prev = states[li]
        x_in = x
        x, c = layer(x, params[f"qrnn_{li}_w"], params[f"qrnn_{li}_b"], c0,
                     window, x_prev)
        new_states.append((c, x_in[:, -1]))
    return x, tuple(new_states)
