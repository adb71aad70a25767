"""Driver ``bulk_gdn_moe``: ``bulk_encoder``'s protocol, unchanged (set-up,
window, the check against the reference's whole-document forward), for
an encoder whose row holds state of FIXED size (the matrix states and
conv tails of a scalar-decay delta rule) beside a grouped-query
key/value cache that grows, every layer an expert layer of which half
the experts are held. The check is ``bulk_kda_moe``'s (a document at a
time at the power of two that holds it; the whole sample, its rows that
crossed chunk programs, ``_carried``, and its rows of more than 8
hand-overs of the state, ``_long``); what this driver adds are the
controls such a model needs, each changing the PROGRAM only (the
reference keeps the configuration as its file states it); three numbers
of what a chunk program hands the next one to READ (``_handed_on``: one
layer of four reads the cache, so a row moves less by a cache in float8
than by the seed, and the rows' numbers alone cannot hold
``state_dtype``); a capture that opens BEFORE the window's first call
(so that a call that outlasts the window is inside it); and, in a traced
run, which cores ran among the line's ``counters``.

Controls (``overrides``; the benchmark's own runs never set one), beside
``precision=int8`` (``bulk_moe``'s walk over a dict of leaves a layer)
and ``state_dtype`` (the conv tails' and the key/value cache's):
``gdn_state=zeroed`` hands every chunk program zeroed matrix states;
``decay=off`` runs the recurrence with ``g = 0``; ``delta=off`` writes
``b k v^T`` without the delta (token by token: the chunked form has no
such switch); ``conv=off`` leaves the short conv out;
``norm_weight=plain`` reads every zero-centred norm weight ``(1 + w)``
as ``w``; ``rope=all`` turns all 256 dims of a head; ``out_gate=off``
leaves the attention's output gate out (its columns of ``q_proj``
zeroed in the program: ``sigmoid(0)`` is a constant 1/2 on the branch);
``qk_norm=off`` leaves the q/k norms out; ``shared_gate=off`` leaves the
shared expert's gate out; ``caches=zeroed`` hands every chunk program
zeroed keys and values.
"""

from __future__ import annotations

import contextlib
import sys

import numpy as np

from benchmark.harness.cell import load_driver

# the jax.named_scope names the compiled forward carries; the grouped
# matmul's kernels reach the trace under XLA's own name ("ragged-dot-none:")
# where ``ops/gmm.py`` is not chosen, outside any scope
PARTS = (r"embedding|gdn_\d+|attention_\d+|moe_\d+|qkv_proj|conv1d|gates|"
         r"gdn_core|gated_norm|qk_norm|rope|global_core|out_gate|o_proj|"
         r"router|dispatch|experts|shared_expert|combine|final_norm|pool|"
         r"ragged-dot-\w+:?")
CONTROLS = ("gdn_state", "decay", "delta", "conv", "norm_weight", "rope",
            "out_gate", "qk_norm", "shared_gate", "caches")
# what the finalize span says of the cores a group's programs ran
_CORES = ("attention_kernel_layers", "expert_kernel_layers")
# chunk programs the document of ``_handed_on`` takes
_HANDED_ON_PROGRAMS = 2


def program_config(ctx):
    """The program's configuration of the cell's model."""
    import jax.numpy as jnp

    from code_intelligence_tpu.models import make_config

    return make_config(
        ctx.config["architecture"], ctx.config,
        kv_positions=int(ctx.config["serve"]["kv_positions"]),
        state_dtype=jnp.dtype(ctx.overrides.get(
            "state_dtype", ctx.config["state_dtype"])))


def _no_delta(q, k, v, g, beta, state, *_, **__):
    """``ops.gdn.gdn_scan``'s signature over ``S = e^g S + b k v^T``:
    gated linear attention, the write without the delta."""
    import jax
    import jax.numpy as jnp

    rep = v.shape[2] // q.shape[2]

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[..., None, None] * S \
            + kt[..., :, None] * (bt[..., None] * vt)[..., None, :]
        return S, jnp.einsum("bhc,bhcv->bhv", qt, S,
                             precision=jax.lax.Precision.HIGHEST)

    state, o = jax.lax.scan(step, state, tuple(
        a.astype(jnp.float32).swapaxes(0, 1) for a in (
            jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2), v, g,
            beta)))
    return o.swapaxes(0, 1), state


@contextlib.contextmanager
def _program_as(on, encoder):
    """For the length of the block (one trace of the encoder), the
    program's pieces as the placement controls have them."""
    import jax.numpy as jnp

    from code_intelligence_tpu.models import blocks
    from code_intelligence_tpu.ops import gdn, mla, ssd

    model = sys.modules[type(encoder).__module__]
    cls, cfg = type(encoder), encoder.config
    real = (gdn.gdn_scan, ssd.causal_conv1d, model._centred, model.rope_qk,
            cls._qk_norm)
    scan = gdn.gdn_scan
    if on["decay"] == "off":
        gdn.gdn_scan = lambda q, k, v, g, *a, **kw: scan(
            q, k, v, jnp.zeros_like(g), *a, **kw)
    if on["delta"] == "off":
        gdn.gdn_scan = _no_delta
    if on["conv"] == "off":
        ssd.causal_conv1d = lambda x, w, bias, tail, lengths=None: (
            x.astype(jnp.float32), tail)
    if on["norm_weight"] == "plain":
        model._centred = lambda w: w.astype(jnp.float32)
    if on["rope"] == "all":
        every = mla.yarn_inv_freq(cfg.head_dim, cfg.rope_theta)
        model.rope_qk = lambda q, k, pos, inv_freq, width=None: \
            blocks.rope_qk(q, k, pos, every)
    if on["qk_norm"] == "off":
        cls._qk_norm = lambda self, p, q, k: (
            q.astype(jnp.float32), k.astype(jnp.float32))
    try:
        yield
    finally:
        (gdn.gdn_scan, ssd.causal_conv1d, model._centred, model.rope_qk,
         cls._qk_norm) = real


def build_engine(ctx, params, vocab):
    """The system under test, as ``serve`` configures it; with a control,
    the encoder's ``encode`` wrapped for the run."""
    import jax
    import jax.numpy as jnp

    from code_intelligence_tpu.inference import InferenceEngine

    serve = ctx.config["serve"]
    engine = InferenceEngine(
        params, program_config(ctx), vocab,
        batch_size=int(serve["batch_size"]),
        scheduler=serve["scheduler"], buckets=tuple(serve["buckets"]))
    on = {name: ctx.overrides.get(name) for name in CONTROLS}
    if not any(on.values()):
        return engine
    encoder = engine.encoder
    encode, cfg = encoder.encode, encoder.config
    d, Hq = cfg.head_dim, cfg.num_attention_heads

    def ungated(p):
        """An attention layer's leaves with every head's gate columns of
        the doubled ``q_proj`` zeroed."""
        if "q_norm" not in p:
            return p
        heads = p["qkv"][:, :2 * Hq * d].reshape(-1, Hq, 2 * d)
        heads = heads.at[:, :, d:].set(0).reshape(-1, 2 * Hq * d)
        return dict(p, qkv=jnp.concatenate(
            [heads, p["qkv"][:, 2 * Hq * d:]], axis=1))

    def controlled(params, tokens, states, lengths=None):
        layers = params["layers"]
        if on["out_gate"] == "off":
            layers = {name: ungated(p) for name, p in layers.items()}
        if on["shared_gate"] == "off":
            layers = {name: {k: v for k, v in p.items()
                             if k != "shared_gate"}
                      for name, p in layers.items()}
        with _program_as(on, encoder):
            out, new = encode(dict(params, layers=layers), tokens, states,
                              lengths=lengths)
        if on["gdn_state"] == "zeroed":
            new = dict(new, gdn=jax.tree.map(jnp.zeros_like, new["gdn"]))
        if on["caches"] == "zeroed":
            new = dict(new, k=jax.tree.map(jnp.zeros_like, new["k"]),
                       v=jax.tree.map(jnp.zeros_like, new["v"]))
        return out, new

    encoder.encode = controlled
    return engine


def _position_errors(got, want):
    """Over every position of every leaf ``(rows, positions, ...)``: the
    error's norm over the position's norm."""
    errs = []
    for g, w in zip(got, want):
        g, w = (np.asarray(a, np.float64).reshape(
            a.shape[0] * a.shape[1], -1) for a in (g, w))
        errs.append(np.linalg.norm(g - w, axis=1)
                    / np.linalg.norm(w, axis=1))
    return np.concatenate(errs)


def _handed_on(ctx, ref, params, encoder, ids, pad_id) -> dict:
    """What the first chunk programs of one document (``ids``, its first
    ``_HANDED_ON_PROGRAMS`` chunks) hand the next one to read as it is,
    against what the reference's later positions read of the same
    tokens: the attention layers' keys, their values, and the linear
    layers' conv tails, each by the MEDIAN over its positions (all its
    layers') of the relative error. A flipped expert choice moves its
    own token by a tenth of its norm, and the RMS over positions with it
    (keys and values: 0.017-0.019 sound against 0.033 in float8, my chip
    run, PR 47); what the stored type rounds moves every position, so
    the median reads that and not the flips. The program's one row runs
    at the serve configuration's largest bucket with the cache sized
    for these positions."""
    import jax
    import jax.numpy as jnp

    chunk = max(ctx.config["serve"]["buckets"])
    ids = np.asarray(ids[:_HANDED_ON_PROGRAMS * chunk], np.int32)
    n = len(ids)
    programs = -(-n // chunk)
    tokens = np.full((1, programs * chunk), pad_id, np.int32)
    tokens[0, :n] = ids
    program = jax.jit(encoder.encode)
    states = encoder.init_states(1, programs * chunk)
    for a in range(0, programs * chunk, chunk):
        _, states = program(
            params, jnp.asarray(tokens[:, a:a + chunk]), states,
            lengths=jnp.asarray([min(chunk, n - a)], jnp.int32))
    with jax.default_matmul_precision("highest"):  # the reference's
        want = jax.jit(lambda p, t: ref.encode(p, t, ctx.config)[2])(
            params, jnp.asarray(tokens[:, :n]))
    # a cache is head-major (rows, heads, positions, d)
    got = {name: [np.asarray(c.astype(jnp.float32))[:, :, :n].swapaxes(1, 2)
                  for c in states[name]] for name in ("k", "v")}
    got["conv"] = [np.asarray(t.astype(jnp.float32)) for t in states["conv"]]
    # keys and values apart: a fault in the keys alone (a norm, the
    # rotary) is half of their positions together, where a median is blind
    errs = {label: _position_errors(got[name], want[name])
            for label, name in (("cached_k", "k"), ("cached_v", "v"),
                                ("conv_tail", "conv"))}
    ctx.log("handed on after %d tokens, relative error a position "
            "(median, ninth decile, largest): %s" % (n, {
                name: [float("%.4g" % q)
                       for q in np.quantile(e, (0.5, 0.9, 1.0))]
                for name, e in errs.items()}))
    return {f"rel_err_p50_{name}": float(np.median(e))
            for name, e in errs.items()}


def run(ctx) -> dict:
    # a program without the architecture fails here, at once, before
    # 7.0 GB of weights are made
    program_config(ctx)
    base = load_driver("bulk_encoder", ctx.bench_dir)
    kda = load_driver("bulk_kda_moe", ctx.bench_dir)
    base.make_weights = load_driver("bulk_moe", ctx.bench_dir).make_weights
    base.PARTS = PARTS
    encoders = []

    def build(ctx, params, vocab):
        engine = build_engine(ctx, params, vocab)
        # the check runs after the engine's state is released
        encoders.append(engine.encoder)
        return engine

    def check(ctx, ref, params, pool, served, vocab):
        numbers, rows = kda._check(ctx, ref, params, pool, served, vocab)
        longest = max((doc["ids"] for k, _ in served for doc in pool[k]),
                      key=len)
        numbers.update(_handed_on(ctx, ref, params, encoders[-1], longest,
                                  vocab.pad_id))
        return numbers, rows

    base.build_engine, base._check = build, check
    # the capture, which the protocol opens once a first call is done,
    # opens before the window's first call: where a call outlasts
    # ``--seconds`` there is no second one for it to open before (set-up
    # has run every shape: nothing compiles inside it)
    step = ctx.profiler.step
    ctx.profiler.step = lambda first_done: step(first_done=True)
    result = base.run(ctx)
    # in a traced run, which cores the groups' programs ran on: no
    # accepted metric reads them
    flushes = result["spans"].by_name().get("engine.finalize", [])
    for name in _CORES:
        seen = [s.attrs[name] for s in flushes if name in s.attrs]
        if seen:
            result["counters"][name] = sum(seen) / len(seen)
    return result
