"""Driver ``bulk_scmoe``: ``bulk_encoder``'s protocol, unchanged (set-up,
window, the check against the reference's whole-document forward), for
an encoder with shortcut-connected experts: two latent-attention
sublayers and two dense FFNs a layer, the routed branch made after the
first attention and added after the second FFN, a router a third of
whose outputs are identity experts. The check is ``bulk_swa_moe``'s (a
document at a time at the power of two that holds it; the whole sample
and its rows that crossed chunk programs, ``_carried``; no row is "past
a window" here); what this driver adds are the controls such a model
needs, each changing the PROGRAM only (the reference keeps the
configuration as its file states it); a log line of the check's numbers
that the cell's file sets no limit for; a capture that opens BEFORE the
window's first call (a call of 32 threads outlasts the window, so there
is no second one for it to open before); and, in a traced run, the held
experts' load and the latent sublayers on the Pallas core among the
line's ``counters``.

Controls (``overrides``; the benchmark's own runs never set one), beside
``precision=int8`` (``bulk_moe``'s walk over a dict of leaves a layer),
``state_dtype`` and ``router_dtype=bfloat16`` (``bulk_moe``'s wrapper
around ``ops.moe.route``):
``latent_cache=dropped`` hands every chunk program eight zeroed caches;
``routed_scaling_factor=1`` leaves the factor 6 on the weights out;
``zero_experts=dropped`` leaves the identity experts' part out;
``shortcut=early`` adds the branch with the FIRST dense FFN, before the
second attention reads the stream (the sequential placement of every
other expert model here);
``mla_scale=off`` leaves both ``mla_scale_*`` multipliers out.
"""

from __future__ import annotations

import contextlib
import types

from benchmark.harness.cell import load_driver, load_layer_reader

# the jax.named_scope names the compiled forward carries; the grouped
# matmul's kernels reach the trace under XLA's own name ("ragged-dot-none:"),
# outside any scope
PARTS = (r"embedding|attention_\d+|mlp_\d+|moe_\d+|q_proj|kv_latent|rope|"
         r"mla_core|o_proj|router|dispatch|experts|zero_experts|combine|"
         r"final_norm|pool|ragged-dot-\w+:?")


def program_config(ctx):
    """The program's configuration of the cell's model, as the control,
    if any, changes it."""
    import jax.numpy as jnp

    from code_intelligence_tpu.models import make_config

    control = {}
    if "routed_scaling_factor" in ctx.overrides:
        control["routed_scaling_factor"] = float(
            ctx.overrides["routed_scaling_factor"])
    if ctx.overrides.get("mla_scale") == "off":
        control.update(mla_scale_q_lora=False, mla_scale_kv_lora=False)
    return make_config(
        ctx.config["architecture"], ctx.config,
        kv_positions=int(ctx.config["serve"]["kv_positions"]),
        state_dtype=jnp.dtype(ctx.overrides.get(
            "state_dtype", ctx.config["state_dtype"])), **control)


@contextlib.contextmanager
def _branch_as(overrides, encoder_cls):
    """For the length of the block (one trace of the encoder), the
    shortcut's branch as the placement controls have it."""
    import jax.numpy as jnp

    from code_intelligence_tpu.ops import moe

    real = moe.zero_experts, moe.swiglu, encoder_cls._moe
    zero_experts, swiglu, branch = real
    waiting = []  # a layer's branch, until its first dense FFN takes it

    def no_identity(x, *args, **kw):
        return jnp.zeros_like(x), jnp.zeros((), jnp.int32)

    def held_back(self, p, u, valid):
        m, per_expert, zeros = branch(self, p, u, valid)
        waiting.append(m)
        return jnp.zeros_like(m), per_expert, zeros

    def takes_the_branch(x, *args, **kw):
        out = swiglu(x, *args, **kw)
        return out + waiting.pop().reshape(out.shape) if waiting else out

    if overrides.get("zero_experts") == "dropped":
        moe.zero_experts = no_identity
    if overrides.get("shortcut") == "early":
        encoder_cls._moe, moe.swiglu = held_back, takes_the_branch
    try:
        yield
    finally:
        moe.zero_experts, moe.swiglu, encoder_cls._moe = real


def build_engine(ctx, params, vocab):
    """The system under test, as ``serve`` configures it."""
    import jax
    import jax.numpy as jnp

    from code_intelligence_tpu.inference import InferenceEngine

    serve = ctx.config["serve"]
    engine = InferenceEngine(
        params, program_config(ctx), vocab,
        batch_size=int(serve["batch_size"]),
        scheduler=serve["scheduler"], buckets=tuple(serve["buckets"]))
    dropped = ctx.overrides.get("latent_cache") == "dropped"
    placed = {"zero_experts", "shortcut"} & set(ctx.overrides)
    if not (dropped or placed):
        return engine
    encode = engine.encoder.encode

    def controlled(params, tokens, states, lengths=None):
        with _branch_as(ctx.overrides, type(engine.encoder)):
            out, new = encode(params, tokens, states, lengths=lengths)
        if dropped:
            new = dict(new, latent=jax.tree.map(
                jnp.zeros_like, new["latent"]))
        return out, new

    engine.encoder.encode = controlled
    return engine


def run(ctx) -> dict:
    # a program without the architecture fails here, at once, before
    # 10.1 GB of weights are made
    program_config(ctx)
    base = load_driver("bulk_encoder", ctx.bench_dir)
    swa = load_driver("bulk_swa_moe", ctx.bench_dir)
    moe_driver = load_driver("bulk_moe", ctx.bench_dir)
    base.make_weights = moe_driver.make_weights
    base.build_engine, base.PARTS = build_engine, PARTS

    def check(ctx, *args):
        # ``bulk_swa_moe``'s check asks for a window: no layer here has
        # one, so none of the sampled rows is past it
        numbers, rows = swa._check(types.SimpleNamespace(
            cell=ctx.cell, seed=ctx.seed, log=ctx.log, config=dict(
                ctx.config,
                sliding_window=ctx.config["serve"]["kv_positions"])), *args)
        ctx.log("numbers that no limit holds: %s" % {
            k: v for k, v in numbers.items()
            if k not in ctx.cell["check"]["limits"]})
        return numbers, rows

    base._check = check
    # a call of this cell outlasts ``--seconds``, so a window is ONE call,
    # and the capture, which the protocol opens once a first call is
    # done, would never open: it opens before the window's first call
    # (set-up has run every shape: nothing compiles inside it)
    step = ctx.profiler.step
    ctx.profiler.step = lambda first_done: step(first_done=True)
    dtype = ctx.overrides.get("router_dtype")
    with moe_driver.router_in(dtype) if dtype else contextlib.nullcontext():
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
    # and of the eight latent sublayers, how many a group's programs ran
    # on the Pallas core (no accepted metric reads it)
    on_kernel = [s.attrs["attention_kernel_layers"]
                 for s in result["spans"].by_name().get("engine.finalize", [])
                 if "attention_kernel_layers" in s.attrs]
    if on_kernel:
        result["counters"]["attention_kernel_layers"] = \
            sum(on_kernel) / len(on_kernel)
    return result
