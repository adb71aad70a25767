"""Plain reference of the AWD-LSTM encoder and language model (Merity et
al. 2017; fastai's ``AWD_LSTM`` as the source configuration builds it):
embedding, N LSTM layers with gate order i, f, g, o, the last layer
``emb_sz`` wide, tied decoder. Evaluation semantics (no dropout).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common


def init_params(key, model: dict, weights: dict = None) -> dict:
    """Encoder weights in the program's layout, at fastai's init ranges
    (embedding U(-0.1, 0.1), LSTM U(-1/sqrt(H), 1/sqrt(H)))."""
    keys = iter(jax.random.split(key, 1 + 3 * model["n_layers"]))
    params = {"embedding": common.draw(
        next(keys), (model["vocab_size"], model["emb_sz"]), 0.1)}
    for li in range(model["n_layers"]):
        in_dim = model["emb_sz"] if li == 0 else model["n_hid"]
        h = common.layer_size(model, li)
        s = common.inv_sqrt(h)
        params[f"lstm_{li}_w_ih"] = common.draw(
            next(keys), (4 * h, in_dim), s, weights)
        params[f"lstm_{li}_w_hh"] = common.draw(
            next(keys), (4 * h, h), s, weights)
        params[f"lstm_{li}_bias"] = common.draw(next(keys), (4 * h,), s)
    return params


def layer(x, w_ih, w_hh, bias, h0, c0):
    """One LSTM layer over ``x (B, T, in)``; returns ``(B, T, H)`` and
    the final ``(h, c)``."""
    def step(carry, xt):
        h, c = carry
        gates = xt @ w_ih.T + h @ w_hh.T + bias
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    (h, c), out = jax.lax.scan(step, (h0, c0), x.swapaxes(0, 1))
    return out.swapaxes(0, 1), (h, c)


def encode(params: dict, tokens, model: dict, states=None):
    """``tokens (B, T)`` -> last layer's hidden states ``(B, T, emb_sz)``
    and the final per-layer states, from zero states unless given."""
    x = common.embed(params, tokens)
    new_states = []
    for li in range(model["n_layers"]):
        h = common.layer_size(model, li)
        if states is None:
            h0 = c0 = jnp.zeros((tokens.shape[0], h), jnp.float32)
        else:
            h0, c0 = states[li]
        x, st = layer(x, params[f"lstm_{li}_w_ih"], params[f"lstm_{li}_w_hh"],
                      params[f"lstm_{li}_bias"], h0, c0)
        new_states.append(st)
    return x, tuple(new_states)
