"""Driver ``bulk_swa_moe``: ``bulk_encoder``'s protocol, unchanged (set-up,
window, the check against the reference's whole-document forward), for
an encoder whose attention layers are of two kinds (sliding-window over
a ring cache, global over a growing one) before routed experts. What it
adds: a check that also reads the rows LONGER THAN THE WINDOW on their
own (``*_past_window``: the ring and the mask are inside it) and pads
each sampled document to the power of two that holds it (from 2048; the
reference is causal, and a 16,384-token forward for a 700-token document
is 8 s of chip time that checks nothing); and the controls such a model
needs, each changing the PROGRAM only (the reference keeps the
configuration as its file states it).

Controls (``overrides``; the benchmark's own runs never set one), beside
``precision=int8`` (``bulk_moe``'s walk over a dict of leaves a layer)
and ``state_dtype``:
``sliding_window=off`` lets the sliding layers attend to everything (a
window of ``kv_positions``: their caches then grow as the global one's,
335 MB a row, so this control alone serves 8 rows a group);
``caches=zeroed`` hands every chunk program zeroed keys and values;
``rope=off`` leaves rotary out of the sliding layers; ``gate=off``
leaves the output gate out (its matrix zeroed in the program: sigmoid(0)
is a constant 1/2, which the norm after ``o_proj`` divides away);
``num_shared_experts=0`` leaves the shared expert out; ``route_scale=1``
leaves the factor on the routed sum out.
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
PARTS = (r"embedding|attention_\d+|mlp_\d+|moe_\d+|qkv_proj|qk_norm|rope|"
         r"window_core|global_core|gate|o_proj|router|dispatch|experts|"
         r"shared_expert|combine|final_norm|pool|ragged-dot-\w+:?")
PROGRAM_ONLY = {"num_shared_experts": int, "route_scale": float}
_SHORTEST_PAD = 2048


def program_config(ctx):
    """The program's configuration of the cell's model, as the control,
    if any, changes it."""
    import jax.numpy as jnp

    from code_intelligence_tpu.models import make_config

    serve = ctx.config["serve"]
    control = {key: cast(ctx.overrides[key])
               for key, cast in PROGRAM_ONLY.items() if key in ctx.overrides}
    if ctx.overrides.get("sliding_window") == "off":
        control["sliding_window"] = int(serve["kv_positions"])
    return make_config(
        ctx.config["architecture"], ctx.config,
        kv_positions=int(serve["kv_positions"]),
        chunk_positions=max(serve["buckets"]),
        state_dtype=jnp.dtype(ctx.overrides.get(
            "state_dtype", ctx.config["state_dtype"])), **control)


def build_engine(ctx, params, vocab):
    """The system under test, as ``serve`` configures it."""
    import jax
    import jax.numpy as jnp

    from code_intelligence_tpu.inference import InferenceEngine
    from code_intelligence_tpu.ops import mla

    serve = ctx.config["serve"]
    rows = int(serve["batch_size"])
    if ctx.overrides.get("sliding_window") == "off":
        rows = min(rows, 8)  # five growing caches a row: 16 do not fit
    engine = InferenceEngine(
        params, program_config(ctx), vocab, batch_size=rows,
        scheduler=serve["scheduler"], buckets=tuple(serve["buckets"]))
    encode = engine.encoder.encode
    zeroed = ctx.overrides.get("caches") == "zeroed"
    unrotated = ctx.overrides.get("rope") == "off"
    ungated = ctx.overrides.get("gate") == "off"
    if not (zeroed or unrotated or ungated):
        return engine

    def controlled(params, tokens, states, lengths=None):
        if ungated:
            params = dict(params, layers={
                name: dict(p, gate=jnp.zeros_like(p["gate"]))
                for name, p in params["layers"].items()})
        rope = mla.apply_rope
        if unrotated:
            mla.apply_rope = lambda x, *a, **kw: x.astype(jnp.float32)
        try:
            out, new = encode(params, tokens, states, lengths=lengths)
        finally:
            mla.apply_rope = rope
        if zeroed:
            new = dict(new, k=jax.tree.map(jnp.zeros_like, new["k"]),
                       v=jax.tree.map(jnp.zeros_like, new["v"]))
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
    that crossed chunk programs and of its rows longer than the window."""
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
    subsets = {
        # the rows whose documents crossed chunk programs
        "carried": max(ctx.config["serve"]["buckets"]),
        # the rows the ring wrapped under and the window masked
        "past_window": int(ctx.config["sliding_window"])}
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
    # 8.5 GB of weights are made
    program_config(ctx)
    base = load_driver("bulk_encoder", ctx.bench_dir)
    base.make_weights = load_driver("bulk_moe", ctx.bench_dir).make_weights
    base.build_engine, base.PARTS, base._check = build_engine, PARTS, _check
    return base.run(ctx)
