"""Driver ``bulk_dsa_moe``: ``bulk_encoder``'s protocol, unchanged (set-up,
window, the check against the reference's whole-document forward), for
an encoder whose row holds TWO caches that grow a position a token, the
latent rows and the index keys a learned selection scores, before routed
experts. What it adds:

* the check: ``bulk_kda_moe``'s (a seeded sample with the longest served
  document among it, a document at a time, here at the multiple of 2,048
  that holds it: the reference's attention and index scores are dense
  squares, and 28,574 tokens at 28,672 are three quarters of the work
  at 32,768; the numbers of the whole sample, of its rows that crossed chunk
  programs, ``_carried``, and of its rows handed over more than 8 times,
  ``_long``), and two numbers of what the first two chunk programs of the
  longest served document hand the next one to READ (``_handed_on``:
  latent rows, index keys), which hold ``state_dtype`` and the index
  cache where the rows cannot;
* the controls such a model needs, each changing the PROGRAM only (the
  reference keeps the configuration as its file states it);
* a capture that opens BEFORE the window's first call (a call outlasts
  the window);
* the counts the encoder kept on the device, copied from the
  ``engine.finalize`` spans of a traced run onto the result line.

Controls (``overrides``; the benchmark's own runs never set one), beside
``precision=int8`` (``bulk_moe``'s walk over a dict of leaves a layer)
and ``state_dtype`` (both caches'): ``select=off`` admits every position
reached (DeepSeek-V3's attention); ``select=recent`` admits the last
``index_topk`` positions (a sliding window); ``topk=<n>`` selects ``n``
positions a query; ``indexer_weights=uniform`` weighs the index heads by
a constant; ``indexer_relu=off`` sums the heads' products without the
ReLU; ``indexer_rope=off`` leaves the indexer's rotary out;
``indexer_heads=first8`` scores 8 index heads of 32;
``index_cache=zeroed`` hands every chunk program a zeroed index-key
cache; ``caches=zeroed`` both caches zeroed; ``router_bias=off`` chooses
the experts without ``e_score_correction_bias``.
"""

from __future__ import annotations

import contextlib
import sys

import numpy as np

from benchmark.harness.cell import load_driver

# the jax.named_scope names the compiled forward carries; the grouped
# matmul's kernels reach the trace under XLA's own name, outside any scope
PARTS = (r"embedding|attention_\d+|mlp_\d+|moe_\d+|q_proj|kv_latent|rope|"
         r"dsa_indexer|dsa_select|mla_core|o_proj|router|dispatch|experts|"
         r"shared_expert|combine|final_norm|pool|ragged-dot-\w+")
CONTROLS = ("select", "indexer_weights", "indexer_relu", "indexer_rope",
            "indexer_heads", "index_cache", "caches", "router_bias")
# chunk programs the document of ``_handed_on`` takes
_HANDED_ON_PROGRAMS = 2
# a sampled document goes to the reference at a multiple of this
_PAD_TO = 2048
# what the encoder counts on the device and the finalize span carries
_COUNTS = ("dsa_pairs_scored", "dsa_pairs_selected", "dsa_threshold_ties",
           "dsa_kernel_layers", "expert_kernel_layers")


def program_config(ctx):
    """The program's configuration of the cell's model, as the control,
    if any, changes it."""
    import jax.numpy as jnp

    from code_intelligence_tpu.models import make_config

    control = {}
    if "topk" in ctx.overrides:
        control["index_topk"] = int(ctx.overrides["topk"])
    return make_config(
        ctx.config["architecture"], ctx.config,
        kv_positions=int(ctx.config["serve"]["kv_positions"]),
        state_dtype=jnp.dtype(ctx.overrides.get(
            "state_dtype", ctx.config["state_dtype"])), **control)


@contextlib.contextmanager
def _program_as(on, encoder):
    """For the length of the block (one trace of the encoder), the
    program's pieces as the placement controls have them."""
    import jax.numpy as jnp

    from code_intelligence_tpu.ops import dsa

    model = sys.modules[type(encoder).__module__]
    real = (dsa.select, dsa.head_scores, model.index_rope)
    if on["select"] in ("off", "recent"):
        def select(scores, pos, k, key_block=512, lanes=None):
            b, T, S = scores.shape
            admit = scores > -jnp.inf  # every position reached, causal
            if on["select"] == "recent":
                admit &= jnp.arange(S)[None, :] > (
                    pos + jnp.arange(T) - k)[:, None]
            lanes = jnp.ones((b, T), bool) if lanes is None else lanes
            return (admit, jnp.full((b, T), -jnp.inf),
                    jnp.zeros((b, T), jnp.int32),
                    jnp.sum(admit & lanes[..., None], dtype=jnp.int32))
        dsa.select = select
    head_scores = dsa.head_scores
    if on["indexer_relu"] == "off":
        dsa.head_scores = lambda s, w: jnp.sum(s * w, axis=1)
    if on["indexer_weights"] == "uniform":
        dsa.head_scores = lambda s, w: head_scores(
            s, jnp.full_like(w, 1.0 / 64))
    if on["indexer_heads"] == "first8":
        dsa.head_scores = lambda s, w: head_scores(s[:, :8], w[:, :8])
    if on["indexer_rope"] == "off":
        model.index_rope = lambda x, positions, inv_freq, width: x.astype(
            jnp.float32)
    try:
        yield
    finally:
        dsa.select, dsa.head_scores, model.index_rope = real


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
    encode = encoder.encode
    zeroed = ()  # the caches a chunk program is handed zeroed
    if on["index_cache"] == "zeroed":
        zeroed = ("index",)
    if on["caches"] == "zeroed":
        zeroed = ("latent", "index")

    def controlled(params, tokens, states, lengths=None):
        if on["router_bias"] == "off":
            params = dict(params, layers={
                name: dict(p, bias=jnp.zeros_like(p["bias"]))
                if "bias" in p else p
                for name, p in params["layers"].items()})
        with _program_as(on, encoder):
            out, new = encode(params, tokens, states, lengths=lengths)
        return out, dict(new, **{name: jax.tree.map(jnp.zeros_like, new[name])
                                 for name in zeroed})

    encoder.encode = controlled
    return engine


def _as_cached(x, turned: int):
    """The reference's rows ``(rows, positions, d)`` as the program
    caches them: the last ``turned`` dims of a latent row, the first of
    an index key, are rotary pairs the program writes de-interleaved
    (all first elements, then all second: ``ops/mla.py``)."""
    x = np.asarray(x, np.float64)
    if turned < 0:
        head, pairs, tail = x[..., :turned], x[..., turned:], x[..., :0]
    else:
        head, pairs, tail = x[..., :0], x[..., :turned], x[..., turned:]
    return np.concatenate(
        [head, pairs[..., 0::2], pairs[..., 1::2], tail], axis=-1)


def _handed_on(ctx, ref, params, encoder, ids, pad_id) -> dict:
    """What the first chunk programs of one document (``ids``, its first
    ``_HANDED_ON_PROGRAMS`` chunks) hand the next one to read as it is,
    against what the reference's later positions read of the same
    tokens: every layer's latent rows and index keys, each by the MEDIAN
    over its positions (all layers') of the relative error. What the
    stored type rounds moves every position, so the median reads it; a
    flipped expert choice or selected key moves its own token, and the
    median passes it over. The program's one row runs at the serve
    configuration's largest bucket with the caches sized for these
    positions."""
    import jax
    import jax.numpy as jnp

    position_errors = load_driver(
        "bulk_gdn_moe", ctx.bench_dir)._position_errors
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
        want = jax.jit(lambda p, t: ref.encode(p, t, ctx.config)[1])(
            params, jnp.asarray(tokens[:, :n]))
    rope = int(ctx.config["qk_rope_head_dim"])
    errs = {}
    for name, label, turned in (("latent", "latent", -rope),
                                ("index", "index_keys", rope)):
        got = [np.asarray(c.astype(jnp.float32))[:, :n]
               for c in states[name]]
        errs[label] = position_errors(
            got, [_as_cached(w, turned) for w in want[name]])
    ctx.log("handed on after %d tokens, relative error a position "
            "(median, ninth decile, largest): %s" % (n, {
                name: [float("%.4g" % q)
                       for q in np.quantile(e, (0.5, 0.9, 1.0))]
                for name, e in errs.items()}))
    return {f"rel_err_p50_{name}": float(np.median(e))
            for name, e in errs.items()}


def run(ctx) -> dict:
    # a program without the architecture fails here, at once, before
    # 7.6 GB of weights are made
    program_config(ctx)
    base = load_driver("bulk_encoder", ctx.bench_dir)
    kda = load_driver("bulk_kda_moe", ctx.bench_dir)
    kda._pad_to = lambda length: -(-length // _PAD_TO) * _PAD_TO
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
    # opens before the window's first call: a call outlasts ``--seconds``,
    # so there is no second one for it to open before (set-up has run
    # every shape: nothing compiles inside it)
    step = ctx.profiler.step
    ctx.profiler.step = lambda first_done: step(first_done=True)
    result = base.run(ctx)
    # in a traced run, what the encoder counted on the device: the pairs
    # scored and admitted and the ties are the window's sums, the two
    # ``*_kernel_layers`` a group's answer
    flushes = result["spans"].by_name().get("engine.finalize", [])
    for name in _COUNTS:
        seen = [s.attrs[name] for s in flushes if name in s.attrs]
        if seen:
            result["counters"][name] = sum(seen) / (
                len(seen) if name.endswith("_kernel_layers") else 1)
    return result
