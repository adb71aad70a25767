"""Driver ``bulk_early_route_moe``: ``bulk_encoder``'s protocol,
unchanged (set-up, window, the check against the reference's
whole-document forward), for an encoder whose every layer routes from
its INPUT, then attends (global without rotary, or rotary under a
sliding window over a ring), then applies the experts chosen. The check
is ``bulk_swa_moe``'s (a document at a time at the power of two that
holds it; the whole sample, its rows that crossed chunk programs
``_carried``, its rows past the window ``_past_window``); what this
driver adds are the controls such a model needs, each changing the
PROGRAM only (the reference keeps the configuration as its file states
it); a log line of the check's numbers that the cell's file sets no
limit for (the ``last`` third: one token a row, which a flipped sixth
choice moves as far as int8 weights do); and, in a traced run, the held
experts' load among the line's ``counters``.

Controls (``overrides``; the benchmark's own runs never set one), beside
``precision=int8`` (``bulk_moe``'s walk over a dict of leaves a layer)
and ``state_dtype``:
``early_router=off`` routes on the experts' own input (the post-attention
norm's output), as every other model here does;
``router_score=sigmoid`` weighs by the sigmoid of the chosen, normalised
(the sibling the softmax flag switches to);
``expert_act=silu`` runs SwiGLU experts;
``sliding_window=off`` lets the sliding layers attend to everything (a
window of ``kv_positions``: their caches then grow as the global ones',
268 MB a row, so this control alone serves 8 rows a group);
``caches=zeroed`` hands every chunk program zeroed keys and values;
``rope=off`` leaves rotary out of every layer; ``rope=all`` rotates the
global layers too.
"""

from __future__ import annotations

import contextlib
import types

from benchmark.harness.cell import load_driver, load_layer_reader

# the jax.named_scope names the compiled forward carries; the grouped
# matmul's kernels reach the trace under XLA's own name ("ragged-dot-none:"),
# outside any scope
PARTS = (r"embedding|route_\d+|attention_\d+|moe_\d+|qkv_proj|rope|"
         r"window_core|global_core|o_proj|router|dispatch|experts|combine|"
         r"final_norm|pool|ragged-dot-\w+:?")


def program_config(ctx):
    """The program's configuration of the cell's model, as the control,
    if any, changes it."""
    import jax.numpy as jnp

    from code_intelligence_tpu.models import make_config

    serve = ctx.config["serve"]
    control = {}
    if ctx.overrides.get("sliding_window") == "off":
        control["sliding_window_size"] = int(serve["kv_positions"])
    if "rope" in ctx.overrides:
        control["rope_layout"] = [int(ctx.overrides["rope"] == "all")] \
            * int(ctx.config["num_hidden_layers"])
    return make_config(
        ctx.config["architecture"], ctx.config,
        kv_positions=int(serve["kv_positions"]),
        chunk_positions=max(serve["buckets"]),
        state_dtype=jnp.dtype(ctx.overrides.get(
            "state_dtype", ctx.config["state_dtype"])), **control)


@contextlib.contextmanager
def _moe_as(overrides):
    """For the length of the block (one trace of the encoder),
    ``ops.moe``'s functions as the routing controls have them."""
    from code_intelligence_tpu.ops import moe

    real = moe.route, moe.routed_experts
    route, apply = real
    last = {}  # the layer's router and its arguments, for a late route

    def routing(h, w_router, *args, **kw):
        if overrides.get("router_score") == "sigmoid":
            kw["score_func"] = "sigmoid"
        last["route"] = (w_router, args, kw)
        return route(h, w_router, *args, **kw)

    def applying(x, experts, weights, *args, act="silu", assigned=None):
        if overrides.get("early_router") == "off":
            w_router, route_args, kw = last["route"]
            experts, weights = route(x, w_router, *route_args, **kw)
            assigned = None  # the sort follows the late choice
        if overrides.get("expert_act") == "silu":
            act = "silu"
        return apply(x, experts, weights, *args, act=act, assigned=assigned)

    moe.route, moe.routed_experts = routing, applying
    try:
        yield
    finally:
        moe.route, moe.routed_experts = real


def build_engine(ctx, params, vocab):
    """The system under test, as ``serve`` configures it."""
    import jax
    import jax.numpy as jnp

    from code_intelligence_tpu.inference import InferenceEngine

    serve = ctx.config["serve"]
    rows = int(serve["batch_size"])
    if ctx.overrides.get("sliding_window") == "off":
        rows = min(rows, 8)  # eight growing caches a row: 16 do not fit
    engine = InferenceEngine(
        params, program_config(ctx), vocab, batch_size=rows,
        scheduler=serve["scheduler"], buckets=tuple(serve["buckets"]))
    zeroed = ctx.overrides.get("caches") == "zeroed"
    routing = {"early_router", "router_score", "expert_act"} \
        & set(ctx.overrides)
    if not (zeroed or routing):
        return engine
    encode = engine.encoder.encode

    def controlled(params, tokens, states, lengths=None):
        with _moe_as(ctx.overrides):
            out, new = encode(params, tokens, states, lengths=lengths)
        if zeroed:
            new = dict(new, k=jax.tree.map(jnp.zeros_like, new["k"]),
                       v=jax.tree.map(jnp.zeros_like, new["v"]))
        return out, new

    engine.encoder.encode = controlled
    return engine


def run(ctx) -> dict:
    # a program without the architecture fails here, at once, before
    # 7.2 GB of weights are made
    program_config(ctx)
    base = load_driver("bulk_encoder", ctx.bench_dir)
    swa = load_driver("bulk_swa_moe", ctx.bench_dir)
    base.make_weights = load_driver("bulk_moe", ctx.bench_dir).make_weights
    base.build_engine, base.PARTS = build_engine, PARTS

    def check(ctx, *args):
        # ``bulk_swa_moe``'s check finds the window under its own
        # configuration's key
        numbers, rows = swa._check(types.SimpleNamespace(
            cell=ctx.cell, seed=ctx.seed, log=ctx.log, config=dict(
                ctx.config,
                sliding_window=ctx.config["sliding_window_size"])), *args)
        ctx.log("numbers that no limit holds: %s" % {
            k: v for k, v in numbers.items()
            if k not in ctx.cell["check"]["limits"]})
        return numbers, rows

    base._check = check
    result = base.run(ctx)
    # the held experts' load in a traced run: the accepted metrics that
    # read these counters list another cell alone (PERF.md §7, findings
    # 16 and 19), so the numbers go out among the line's counters
    seen = types.SimpleNamespace(spans=result["spans"])
    for name in ("expert_rows_per_program", "expert_load_max_over_mean"):
        spec, read = load_layer_reader(name, ctx.bench_dir)
        value = read(seen, spec)
        if value is not None:
            result["counters"][name] = value
    return result
