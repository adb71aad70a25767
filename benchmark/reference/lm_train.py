"""Plain reference of the language-model training step: the AWD-LSTM
objective with its five dropouts, cross-entropy plus the AR/TAR
activation regularisers, gradients by ``jax.grad``, and AdamW under the
one-cycle schedules, written out by hand in float32 at "highest"
precision. It follows the program through its first steps.

The dropout masks must be the program's own draws or no loss could be
compared, so the reference derives each mask's key the way the
program's framework does (flax ``make_rng``: the step key folded with
the first four bytes of SHA-1 over the module path and a call counter)
and draws with ``jax.random.bernoulli``; the order of the draws is the
encoder's: embedding rows, input, then per layer the recurrent weights
and the layer output, last the decoder input. Nothing of the program is
imported; a test pins this derivation against it at a tiny size.

The control computes the same steps in the nearest precision below
bfloat16 (``lower="int8"``): matmul weights through symmetric
per-channel int8 and back, activations in bfloat16.
"""

from __future__ import annotations

import hashlib
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference import common


def dropout_key(step_key, call: int, path: Tuple[str, ...] = ("encoder",),
                separator: bool = False):
    m = hashlib.sha1()
    for x in path + (call,):
        if separator:
            m.update(b"\00")
        m.update(x.encode("utf-8") if isinstance(x, str)
                 else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        step_key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def _mask(key, p: float, shape, dtype):
    return jax.random.bernoulli(key, 1.0 - p, shape).astype(dtype) / (1.0 - p)


def one_cycle_lr(step, total: int, peak: float, pct_start: float = 0.3,
                 div: float = 25.0, final_div: float = 1e4):
    """Cosine warm-up ``peak/div -> peak`` over ``pct_start`` of the
    cycle, then cosine down to ``peak/div/final_div`` (optax's
    ``cosine_onecycle_schedule``, which the program's schedule wraps)."""
    init = peak / div
    end = init / final_div
    b = int(pct_start * total)
    up = init + (peak - init) * 0.5 * (1 - jnp.cos(jnp.pi * step / b))
    down = peak + (end - peak) * 0.5 * (
        1 - jnp.cos(jnp.pi * (step - b) / (total - b)))
    return jnp.where(step < b, up, down)


def one_cycle_momentum(step, total: int, lo: float = 0.85, hi: float = 0.95,
                       pct_start: float = 0.3):
    split = pct_start * total
    down = hi + (lo - hi) * 0.5 * (1 - jnp.cos(jnp.pi * jnp.clip(
        step / split, 0.0, 1.0)))
    up = lo + (hi - lo) * 0.5 * (1 - jnp.cos(jnp.pi * jnp.clip(
        (step - split) / (total - split), 0.0, 1.0)))
    return jnp.where(step < split, down, up)


def make_loss(arch, model: dict, train: dict, lower: str = None,
              separator: bool = False):
    """``loss(params, x, y, states, step_key) -> (loss, new_states)`` for
    the LSTM architecture module ``arch`` (its ``layer``)."""
    drop = train["dropout"]
    dt = jnp.bfloat16 if lower else jnp.float32

    def w_of(w):
        return common.fake_quant_int8(w).astype(dt) if lower else w

    def loss(params, x, y, states, step_key):
        enc = params["encoder"]
        calls = iter(range(1, 64))

        def key():
            return dropout_key(step_key, next(calls), separator=separator)

        B = x.shape[0]
        table = enc["embedding"]
        if drop["embed_p"] > 0:
            keep = jax.random.bernoulli(
                key(), 1.0 - drop["embed_p"], (model["vocab_size"], 1))
            table = table * keep / (1.0 - drop["embed_p"])
        h = jnp.take(table, x, axis=0).astype(dt)
        if drop["input_p"] > 0:
            h = h * _mask(key(), drop["input_p"],
                          (B, 1, model["emb_sz"]), dt)
        new_states = []
        for li in range(model["n_layers"]):
            hid = common.layer_size(model, li)
            w_hh = w_of(enc[f"lstm_{li}_w_hh"])
            if drop["weight_p"] > 0:
                w_hh = w_hh * _mask(key(), drop["weight_p"], w_hh.shape, dt)
            h0, c0 = states[li]
            h, st = arch.layer(h, w_of(enc[f"lstm_{li}_w_ih"]), w_hh,
                               enc[f"lstm_{li}_bias"].astype(dt),
                               h0.astype(dt), c0.astype(dt))
            new_states.append(st)
            if li < model["n_layers"] - 1 and drop["hidden_p"] > 0:
                h = h * _mask(key(), drop["hidden_p"], (B, 1, hid), dt)
        raw, dropped = h, h
        if drop["output_p"] > 0:
            dropped = raw * _mask(key(), drop["output_p"],
                                  (B, 1, model["emb_sz"]), dt)
        # the tied decoder reads the embedding table as stored, not the
        # row-dropped copy the lookup used
        logits = jnp.einsum("bte,ve->btv", dropped,
                            w_of(enc["embedding"])) \
            + params["decoder_b"].astype(dt)
        ce = common.cross_entropy(logits.astype(jnp.float32), y)
        ar = train.get("alpha", 2.0) * jnp.mean(
            jnp.square(dropped.astype(jnp.float32)))
        tar = train.get("beta", 1.0) * jnp.mean(jnp.square(
            (raw[:, 1:] - raw[:, :-1]).astype(jnp.float32)))
        return ce + ar + tar, jax.lax.stop_gradient(tuple(new_states))

    return loss


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def follow(arch, model: dict, train: dict, params: dict, xs, ys,
           run_key, steps: int = 3, lower: str = None,
           separator: bool = False) -> Dict[str, List[float]]:
    """The first ``steps`` training steps from ``params`` on windows
    ``xs[k], ys[k]``: each step's loss, the global norm of its gradient
    as the optimizer gets it, and the parameters' global norm after it.
    ``run_key`` is the key the program's state carries (the step key is
    it folded with the step number)."""
    loss_fn = make_loss(arch, model, train, lower, separator)
    total = int(train["steps_per_epoch"]) * int(train.get("cycle_len", 1))
    peak = 2.0 * float(train["lr"])
    wd, eps, b2 = float(train.get("wd", 0.01)), 1e-7, 0.99

    @jax.jit
    def step(params, mu, nu, states, x, y, k, run_key):
        # the run's key is an argument, not a captured constant: a
        # constant would put the seed into the program and no run would
        # find it in the compile cache
        key = jax.random.fold_in(run_key, k)
        (loss, states), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, x, y, states, key)
        if train.get("one_cycle", True):
            lr = one_cycle_lr(k, total, peak)
            b1 = one_cycle_momentum(k, total)
        else:
            lr, b1 = float(train["lr"]), 0.95
        count = (k + 1).astype(jnp.float32)
        mu = jax.tree.map(lambda m, gi: b1 * m + (1 - b1) * gi, mu, g)
        nu = jax.tree.map(lambda v, gi: b2 * v + (1 - b2) * gi * gi, nu, g)
        new = jax.tree.map(
            lambda p, m, v: p - lr * (
                (m / (1 - b1 ** count)) / (jnp.sqrt(v / (1 - b2 ** count))
                                           + eps) + wd * p),
            params, mu, nu)
        return new, mu, nu, states, loss, global_norm(g), global_norm(new)

    B = xs.shape[1]
    states = tuple(
        (jnp.zeros((B, common.layer_size(model, li)), jnp.float32),) * 2
        for li in range(model["n_layers"]))
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    out = {"loss": [], "grad_norm": [], "param_norm": [], "seconds": []}
    with jax.default_matmul_precision("highest"):
        for k in range(steps):
            t0 = time.perf_counter()
            params, mu, nu, states, loss, gn, pn = step(
                params, mu, nu, states, jnp.asarray(xs[k]),
                jnp.asarray(ys[k]), jnp.int32(k), jnp.asarray(run_key))
            out["loss"].append(float(loss))
            out["grad_norm"].append(float(gn))
            out["param_norm"].append(float(pn))
            out["seconds"].append(round(time.perf_counter() - t0, 2))
    return out
