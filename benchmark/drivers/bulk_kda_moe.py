"""Driver ``bulk_kda_moe``: ``bulk_encoder``'s protocol, unchanged (set-up,
window, the check against the reference's whole-document forward), for
an encoder whose row holds state of FIXED size (the matrix states and
conv tails of delta-rule linear attention) beside a latent cache that
grows, before routed experts. What it adds: a check that reads, beside
the whole sample, its rows that crossed chunk programs (``*_carried``)
and its rows whose state was handed over more than 8 times (``*_long``:
what a hand-over loses compounds there), each sampled document padded to
the power of two that holds it (from 2048; the reference is causal, and
a 16,384-token forward for a 700-token document checks nothing); and
the controls such a model needs, each changing the PROGRAM only (the
reference keeps the configuration as its file states it).

Controls (``overrides``; the benchmark's own runs never set one), beside
``precision=int8`` (``bulk_moe``'s walk over a dict of leaves a layer):
``kda_state=zeroed`` hands every chunk program zeroed matrix states and
conv tails; ``kda_state_dtype=bfloat16`` rounds the matrix states to
bfloat16 between chunk programs (what carrying them in bfloat16 keeps);
``decay=off`` runs the recurrence with ``g = 0``; ``delta=off`` writes
``b k v^T`` without the delta (``S' + b k v^T``, token by token: the
chunked form has no such switch); ``conv=off`` leaves the short conv
out; ``caches=zeroed`` hands every chunk program a zeroed latent cache;
``gate=off`` leaves both output gates out (their matrices zeroed in the
program: ``sigmoid(0)`` is a constant 1/2 on the branch).
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import check
from benchmark.harness.cell import load_driver
from benchmark.reference import common

# the jax.named_scope names the compiled forward carries; the grouped
# matmul's kernels reach the trace under XLA's own name ("ragged-dot-none:"),
# outside any scope
PARTS = (r"embedding|kda_\d+|attention_\d+|mlp_\d+|moe_\d+|qkv_proj|conv1d|"
         r"gates|kda_core|gated_norm|q_proj|kv_latent|rope|mla_core|gate|"
         r"o_proj|router|dispatch|experts|shared_expert|combine|final_norm|"
         r"pool|ragged-dot-\w+:?")
CONTROLS = ("kda_state", "kda_state_dtype", "decay", "delta", "conv",
            "caches", "gate")
_SHORTEST_PAD = 2048
_LONG_HANDOVERS = 8


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
    """``ops.kda.kda_scan``'s signature over ``S = diag(e^g) S + b k
    v^T``: gated linear attention, the write without the delta."""
    import jax
    import jax.numpy as jnp

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[..., None] * S \
            + kt[..., :, None] * (bt[..., None] * vt)[..., None, :]
        return S, jnp.einsum("bhc,bhcv->bhv", qt, S,
                             precision=jax.lax.Precision.HIGHEST)

    state, o = jax.lax.scan(step, state, tuple(
        a.astype(jnp.float32).swapaxes(0, 1) for a in (q, k, v, g, beta)))
    return o.swapaxes(0, 1), state


def build_engine(ctx, params, vocab):
    """The system under test, as ``serve`` configures it; with a control,
    the encoder's ``encode`` wrapped for the run."""
    import jax
    import jax.numpy as jnp

    from code_intelligence_tpu.inference import InferenceEngine
    from code_intelligence_tpu.ops import kda, ssd

    serve = ctx.config["serve"]
    engine = InferenceEngine(
        params, program_config(ctx), vocab,
        batch_size=int(serve["batch_size"]),
        scheduler=serve["scheduler"], buckets=tuple(serve["buckets"]))
    on = {name: ctx.overrides.get(name) for name in CONTROLS}
    if not any(on.values()):
        return engine
    encode = engine.encoder.encode
    D = engine.encoder.config.kda_dim

    def ungated(p):
        if "gate" in p:
            return dict(p, gate=jnp.zeros_like(p["gate"]))
        return dict(p, gates=p["gates"].at[:, D:2 * D].set(0))

    def controlled(params, tokens, states, lengths=None):
        if on["gate"] == "off":
            params = dict(params, layers={
                name: ungated(p) for name, p in params["layers"].items()})
        scan, conv = kda.kda_scan, ssd.causal_conv1d
        if on["decay"] == "off":
            kda.kda_scan = lambda q, k, v, g, *a, **kw: scan(
                q, k, v, jnp.zeros_like(g), *a, **kw)
        if on["delta"] == "off":
            kda.kda_scan = _no_delta
        if on["conv"] == "off":
            ssd.causal_conv1d = lambda x, w, bias, tail, lengths=None: (
                x.astype(jnp.float32), tail)
        try:
            out, new = encode(params, tokens, states, lengths=lengths)
        finally:
            kda.kda_scan, ssd.causal_conv1d = scan, conv
        if on["kda_state"] == "zeroed":
            new = dict(new, kda=jax.tree.map(jnp.zeros_like, new["kda"]),
                       conv=jax.tree.map(jnp.zeros_like, new["conv"]))
        if on["kda_state_dtype"]:
            new = dict(new, kda=jax.tree.map(lambda s: s.astype(
                on["kda_state_dtype"]).astype(s.dtype), new["kda"]))
        if on["caches"] == "zeroed":
            new = dict(new, latent=jax.tree.map(
                jnp.zeros_like, new["latent"]))
        return out, new

    engine.encoder.encode = controlled
    return engine


def _pad_to(length: int) -> int:
    pad = _SHORTEST_PAD
    while pad < length:
        pad *= 2
    return pad


def _check(ctx, ref, params, pool, served, vocab):
    """``bulk_encoder``'s sample (seeded, the longest served document
    among it) against the reference, a document at a time at the power
    of two that holds it; the numbers of the whole sample, of its rows
    that crossed chunk programs and of its rows handed over more than
    ``_LONG_HANDOVERS`` times."""
    import jax

    t0 = time.perf_counter()
    n_sample = int(ctx.cell["check"]["sample"])
    rng = np.random.default_rng([int(ctx.seed), 0xC4EC])
    flat = [(ci, di, si) for si, (ci, rows) in enumerate(served)
            for di in range(len(rows))]
    longest = max(flat, key=lambda t: len(pool[t[0]][t[1]]["ids"]))
    picks = [flat[i] for i in rng.choice(len(flat), size=min(
        n_sample - 1, len(flat)), replace=False)]
    picks = [longest] + [p for p in picks if p != longest][:n_sample - 1]
    got = np.stack([served[si][1][di] for _, di, si in picks])
    id_seqs = [pool[ci][di]["ids"] for ci, di, _ in picks]
    nonfinite_rows = int(sum(
        (~np.isfinite(rows)).any(axis=1).sum() for _, rows in served))

    encode = jax.jit(lambda p, t: ref.encode(p, t, ctx.config)[0])
    block_rows = int(ctx.cell["check"].get("block_rows", 1))
    want = np.zeros(got.shape, np.float64)
    for pad in sorted({_pad_to(len(s)) for s in id_seqs}):
        at = [i for i, s in enumerate(id_seqs) if _pad_to(len(s)) == pad]
        want[at] = common.pooled_rows(
            encode, params, [id_seqs[i] for i in at], vocab.pad_id, pad,
            block_rows=block_rows)
    numbers = check.row_numbers(got, want)
    numbers["nonfinite_rows"] = float(nonfinite_rows)
    chunk = max(ctx.config["serve"]["buckets"])
    subsets = {
        # the rows whose documents crossed chunk programs
        "carried": chunk,
        # the rows whose state crossed more than _LONG_HANDOVERS of them
        "long": chunk * (_LONG_HANDOVERS + 1)}
    for suffix, longer_than in subsets.items():
        rows = [i for i, s in enumerate(id_seqs) if len(s) > longer_than]
        if rows:
            for name, value in check.row_numbers(
                    got[rows], want[rows]).items():
                numbers[f"{name}_{suffix}"] = value
    ctx.log("check: %d rows of %s tokens against the reference in %.1f s" % (
        len(picks), sorted(len(s) for s in id_seqs),
        time.perf_counter() - t0))
    return numbers, len(picks)


def run(ctx) -> dict:
    # a program without the architecture fails here, at once, before
    # 10.3 GB of weights are made
    program_config(ctx)
    base = load_driver("bulk_encoder", ctx.bench_dir)
    base.make_weights = load_driver("bulk_moe", ctx.bench_dir).make_weights
    base.build_engine, base.PARTS, base._check = build_engine, PARTS, _check
    return base.run(ctx)
