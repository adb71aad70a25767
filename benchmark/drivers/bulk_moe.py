"""Driver ``bulk_moe``: ``bulk_encoder``'s protocol, unchanged (set-up,
window, the check against the reference's whole-document forward), for
an encoder with routed experts and a latent cache; what it adds are the
controls such a model needs, each changing the PROGRAM only (the
reference keeps the configuration as its file states it).

Controls (``overrides``; the benchmark's own runs never set one), beside
``bulk_encoder``'s ``precision=int8`` (its walk over the weights is
redone here for this tree's layout) and ``state_dtype``:
``n_shared_experts=0`` leaves the shared expert out;
``routed_scaling_factor=1`` leaves the factor on the routed sum out;
``router_dtype=bfloat16`` multiplies the router in bfloat16 (a wrapper
around ``ops.moe.route`` for the run: the program has no such option);
``latent_cache=dropped`` hands every chunk program a zeroed cache.
"""

from __future__ import annotations

import contextlib

from benchmark.harness.cell import load_driver
from benchmark.reference import common

# the jax.named_scope names the compiled forward carries; the grouped
# matmul's kernels reach the trace under XLA's own name, outside any scope
PARTS = (r"embedding|attention_\d+|mlp_\d+|moe_\d+|q_proj|kv_latent|rope|"
         r"mla_core|o_proj|router|dispatch|experts|shared_expert|combine|"
         r"final_norm|pool|ragged-dot-\w+")
PROGRAM_ONLY = {"n_shared_experts": int, "routed_scaling_factor": float}


def make_weights(ctx, ref, rounded_to_int8: bool = False):
    """``bulk_encoder``'s, for a tree with a dict of leaves a layer:
    with ``rounded_to_int8`` every matrix (a leaf of two or more
    dimensions: the others are norms and the router's float32 bias) goes
    through int8 levels and back, an expert or 1024 rows at a time."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(ctx.config["dtype"])

    def rounded(w):
        if w.ndim < 2:
            return w
        lead = w if w.ndim == 3 else w.reshape(
            (-1, 1024 if w.shape[0] % 1024 == 0 else w.shape[0], w.shape[1]))
        return jax.lax.map(lambda a: common.fake_quant_int8(
            a.astype(jnp.float32)).astype(dtype), lead).reshape(w.shape)

    def make(key):
        params = ref.init_params(key, ctx.config, ctx.config.get("weights"),
                                 dtype=dtype)
        return jax.tree.map(rounded, params) if rounded_to_int8 else params

    return jax.jit(make)(common.seed_key(ctx.seed))


def program_config(ctx):
    """The program's configuration of the cell's model, as the control,
    if any, changes it."""
    import jax.numpy as jnp

    from code_intelligence_tpu.models import make_config

    control = {key: cast(ctx.overrides[key])
               for key, cast in PROGRAM_ONLY.items() if key in ctx.overrides}
    return make_config(
        ctx.config["architecture"], ctx.config,
        kv_positions=int(ctx.config["serve"]["kv_positions"]),
        state_dtype=jnp.dtype(ctx.overrides.get(
            "state_dtype", ctx.config["state_dtype"])), **control)


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
    if ctx.overrides.get("latent_cache") == "dropped":
        encode = engine.encoder.encode

        def forgetful(params, tokens, states, lengths=None):
            out, new = encode(params, tokens, states, lengths=lengths)
            return out, dict(new, latent=jax.tree.map(
                jnp.zeros_like, new["latent"]))

        engine.encoder.encode = forgetful
    return engine


@contextlib.contextmanager
def router_in(dtype: str):
    """For the length of the block, ``ops.moe.route`` with both inputs
    of its matmul rounded to ``dtype`` first: bfloat16 values multiply
    exactly in float32, so this is the router multiplied in ``dtype``
    and summed in float32."""
    import jax.numpy as jnp

    from code_intelligence_tpu.ops import moe

    route = moe.route

    def rounded(h, w_router, *args, **kw):
        return route(h.astype(dtype).astype(jnp.float32),
                     w_router.astype(dtype).astype(jnp.float32), *args, **kw)

    moe.route = rounded
    try:
        yield
    finally:
        moe.route = route


def run(ctx) -> dict:
    # a program without the architecture fails here, at once, before
    # 8.9 GB of weights are made
    program_config(ctx)
    base = load_driver("bulk_encoder", ctx.bench_dir)
    base.make_weights, base.build_engine, base.PARTS = \
        make_weights, build_engine, PARTS
    dtype = ctx.overrides.get("router_dtype")
    with router_in(dtype) if dtype else contextlib.nullcontext():
        return base.run(ctx)
