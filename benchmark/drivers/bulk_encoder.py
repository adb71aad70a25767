"""Driver ``bulk_encoder``: ``bulk.py``'s protocol for an encoder the
program builds from the configuration's ``architecture``.

Set-up: vocabulary, weights made on the device from the seed in the
configuration's ``dtype``, the engine through the program's own encoder
factory (``models.make_config`` over the published keys, which sit at
the top level of the configuration's file), the window's documents, and
warm-up calls from a seed stream of their own. Window: whole calls of
``embed_issues`` back to back until ``--seconds`` have passed; the rate
is documents returned over the time to the last return. Check, after
the window, with the program's state released: a seeded sample of the
served documents, the longest among them, against the plain reference's
whole-document forward, each third of the row by relative RMS error,
over the whole sample and over its rows that crossed chunk programs
(``*_carried``: the carried state is inside the check).

Controls (``overrides``; the benchmark's own runs never set one):
``precision=int8`` hands the PROGRAM the weights rounded to int8 levels
(``reference/common.fake_quant_int8``) while the reference keeps the
seeded ones; ``state_dtype=bfloat16`` carries the SSM state in bfloat16.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import check, traffic, xplane, xplane_scopes
from benchmark.harness.cell import load_reference
from benchmark.harness.spans import SpanLog
from benchmark.reference import common


# the jax.named_scope names the hybrid's compiled forward carries
PARTS = (r"embedding|mamba_\d+|attention_\d+|mlp_\d+|conv1d|ssd_scan|"
         r"gated_norm|final_norm|pool")


def make_weights(ctx, ref, rounded_to_int8: bool = False):
    """The seeded weights on the device, in the configuration's dtype;
    with ``rounded_to_int8`` every matrix goes through int8 levels and
    back, a layer at a time."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(ctx.config["dtype"])

    def make(key):
        params = ref.init_params(key, ctx.config, ctx.config.get("weights"),
                                 dtype=dtype)
        if not rounded_to_int8:
            return params

        def rounded(path, w):
            stacked = w.ndim == 3          # a layer's matrix on a leading axis
            if not stacked and path[-1].key != "embedding":
                return w                   # norms, the scan's own scalars
            lead = w if stacked else w.reshape(
                (-1, 1024 if w.shape[0] % 1024 == 0 else w.shape[0],
                 w.shape[1]))
            out = jax.lax.map(lambda a: common.fake_quant_int8(
                a.astype(jnp.float32)).astype(dtype), lead)
            return out.reshape(w.shape)

        return jax.tree_util.tree_map_with_path(rounded, params)

    return jax.jit(make)(common.seed_key(ctx.seed))


def build_engine(ctx, params, vocab):
    """The system under test, as ``serve`` configures it."""
    import jax.numpy as jnp

    from code_intelligence_tpu.inference import InferenceEngine
    from code_intelligence_tpu.models import make_config

    serve = ctx.config["serve"]
    cfg = make_config(
        ctx.config["architecture"], ctx.config,
        kv_positions=int(serve["kv_positions"]),
        state_dtype=jnp.dtype(ctx.overrides.get(
            "state_dtype", ctx.config["state_dtype"])))
    return InferenceEngine(
        params, cfg, vocab, batch_size=int(serve["batch_size"]),
        scheduler=serve["scheduler"], buckets=tuple(serve["buckets"]))


def run(ctx) -> dict:
    # a program without the encoder factory fails here, at once, before
    # any weight is made
    from code_intelligence_tpu.models import make_config  # noqa: F401
    from code_intelligence_tpu.text import SPECIALS, Vocab
    from code_intelligence_tpu.utils import tracing

    serve, mix = ctx.config["serve"], ctx.mix
    ref = load_reference(ctx.config["architecture"], ctx.bench_dir)
    words = traffic.vocab_words(SPECIALS, ctx.config["vocab_size"])
    vocab = Vocab(words)
    int8 = ctx.overrides.get("precision") == "int8"
    params = make_weights(ctx, ref, rounded_to_int8=int8)
    engine = build_engine(ctx, params, vocab)
    scheduler = serve["scheduler"]

    pool = traffic.make_document_calls(
        mix, words, ctx.seed, int(mix.get("calls_pool", 8)), stream=1)
    warm = traffic.make_document_calls(
        mix, words, ctx.seed, int(mix.get("warmup_calls", 1)), stream=2)
    call_tokens = [sum(len(d["ids"]) for d in call) for call in pool]
    ctx.log("lengths of one call: %s" % traffic.length_quartiles(
        [len(d["ids"]) for d in pool[0]]))

    def issues(call):
        return [{"title": d["title"], "body": d["body"]} for d in call]

    pool_issues = [issues(call) for call in pool]
    for call in warm:
        engine.embed_issues(issues(call), scheduler=scheduler)
    ctx.log("warm-up done")

    span_log = SpanLog()
    tracer = None
    if ctx.trace:
        tracer = tracing.Tracer(max_traces=8, max_live=4 * len(pool[0]))
        tracer.on_trace(span_log.ingest)
    watch = ctx.compile_counter()

    served = []  # (call index in pool, rows)
    prof = ctx.profiler
    ctx.window_opens()
    t0 = time.perf_counter()
    done_at = []
    while not done_at or done_at[-1] < ctx.seconds:
        k = len(served) % len(pool)
        prof.step(first_done=bool(done_at))
        roots = ctxs = None
        if tracer is not None:
            roots = [tracer.start_span("bench.doc") for _ in pool[k]]
            ctxs = [r.context for r in roots]
        with prof.annotate("bench.call"):
            rows = engine.embed_issues(pool_issues[k], scheduler=scheduler,
                                       ctxs=ctxs)
        if roots is not None:
            for r in roots:
                r.end()
        done_at.append(time.perf_counter() - t0)
        served.append((k, rows))
    prof.stop()
    window_s = done_at[-1]
    call_s = np.diff([0.0] + done_at)
    ctx.log("seconds a call: %s" % [round(float(c), 3) for c in call_s])
    n_docs = sum(len(r) for _, r in served)
    compiles = watch.new()
    peak = ctx.memory_peak_bytes()

    # -- correctness, outside the window: the program's state goes first
    ctx.release(engine)
    if int8:
        # the reference keeps the seeded weights; the two trees do not
        # fit the chip together, so the rounded one goes first
        del params
        params = make_weights(ctx, ref)
    numbers, sample_n = _check(ctx, ref, params, pool, served, vocab)
    verdict = check.judge(numbers, ctx.cell["check"]["limits"])
    ctx.log("compared: %s" % verdict["compared"])

    xplane_path = xplane.find_xplane(prof.dir) if prof.dir else None
    if xplane_path:
        parts = xplane_scopes.by_named_part(xplane_path, PARTS)
        ctx.log("device seconds by named scope: %s" % {
            k: round(v, 4) for k, v in sorted(
                parts.items(), key=lambda kv: -kv[1])})

    return {
        "correct": verdict["correct"],
        "compared": verdict["compared"],
        "attempted": n_docs, "failed": int(numbers["nonfinite_rows"]),
        "end_to_end": {"docs_per_s": n_docs / window_s},
        "window_s": window_s, "memory_peak_bytes": peak,
        "counters": {"compiles_in_window": compiles,
                     "docs": n_docs, "calls": len(served),
                     "tokens": sum(call_tokens[i] for i, _ in served),
                     "checked_rows": sample_n,
                     "slowest_call_s": float(call_s.max()),
                     "fastest_call_s": float(call_s.min())},
        "spans": span_log,
        # for the readers of device time by named scope
        "xplane_path": xplane_path,
    }


def _check(ctx, ref, params, pool, served, vocab):
    import jax

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

    pad_to = int(ctx.mix["length"]["max"])
    encode = jax.jit(lambda p, t: ref.encode(p, t, ctx.config)[0])
    want = common.pooled_rows(
        encode, params, id_seqs, vocab.pad_id, pad_to,
        block_rows=int(ctx.cell["check"].get("block_rows", 4)))
    numbers = check.row_numbers(got, want)
    numbers["nonfinite_rows"] = float(nonfinite_rows)
    # the rows whose documents crossed chunk programs, on their own
    carried = [i for i, s in enumerate(id_seqs)
               if len(s) > max(ctx.config["serve"]["buckets"])]
    if carried:
        for name, value in check.row_numbers(
                got[carried], want[carried]).items():
            numbers[f"{name}_carried"] = value
    return numbers, len(picks)
