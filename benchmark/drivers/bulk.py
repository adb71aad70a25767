"""Driver ``bulk``: documents handed to ``InferenceEngine.embed_issues``
in calls, back to back, for the whole window.

Set-up: vocabulary, weights on the device from the seed, the engine as
the configuration's ``serve`` block builds it, the window's documents,
and warm-up calls from a seed stream of their own (every compiled shape
the mix's lengths need). Window: calls until ``--seconds`` have passed;
the rate is documents returned over the time to the last return. Check:
a seeded sample of the served documents, the longest among them, row by
row against the plain reference, after the window has closed.
"""

from __future__ import annotations

import time
import numpy as np

from benchmark.harness import check, traffic
from benchmark.harness.cell import load_reference
from benchmark.harness.spans import SpanLog
from benchmark.reference import common


def build_engine(ctx, params, vocab):
    """The system under test, as ``serve`` configures it."""
    import jax.numpy as jnp

    from code_intelligence_tpu.inference import InferenceEngine
    from code_intelligence_tpu.models import AWDLSTMConfig

    model, serve = ctx.config["model"], ctx.config["serve"]
    cfg = AWDLSTMConfig(
        vocab_size=model["vocab_size"], emb_sz=model["emb_sz"],
        n_hid=model["n_hid"], n_layers=model["n_layers"],
        pad_id=vocab.pad_id, qrnn=bool(model.get("qrnn", False)),
        tie_weights=bool(model.get("tie_weights", True)),
        dtype=jnp.dtype(model["dtype"]))
    kw = {}
    if "buckets" in serve:
        kw["buckets"] = tuple(serve["buckets"])
    return InferenceEngine(
        params, cfg, vocab, batch_size=int(serve["batch_size"]),
        scheduler=serve["scheduler"], lstm_pallas=serve.get("lstm_pallas"),
        precision=ctx.overrides.get("precision", serve.get("precision", "f32")),
        **kw)


def run(ctx) -> dict:
    import jax

    from code_intelligence_tpu.text import SPECIALS, Vocab
    from code_intelligence_tpu.utils import tracing

    model, serve, mix = ctx.config["model"], ctx.config["serve"], ctx.mix
    ref = load_reference(ctx.config["architecture"], ctx.bench_dir)
    words = traffic.vocab_words(SPECIALS, model["vocab_size"])
    vocab = Vocab(words)

    # weights: one jitted call on the device, from the seed, float32 as
    # the program's exports hold them (it casts to the compute type
    # inside its compiled step)
    params = jax.jit(lambda k: ref.init_params(
        k, model, ctx.config.get("weights")))(
        common.seed_key(ctx.seed))
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

    # -- traced runs: a tracer of the program's own kind, one root span a
    # document (its cap is 512 spans a trace), kept for the readers
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
    n_docs = sum(len(r) for _, r in served)
    compiles = watch.new()
    peak = ctx.memory_peak_bytes()

    # -- correctness, outside the window: the program's state goes first
    numbers, sample_n = _check(ctx, ref, params, engine, pool, served, vocab)
    verdict = check.judge(numbers, ctx.cell["check"]["limits"])
    ctx.log("compared: %s" % verdict["compared"])

    failed = int(numbers["nonfinite_rows"])
    return {
        "correct": verdict["correct"],
        "compared": verdict["compared"],
        "attempted": n_docs, "failed": failed,
        "end_to_end": {"docs_per_s": n_docs / window_s},
        "window_s": window_s, "memory_peak_bytes": peak,
        "counters": {"compiles_in_window": compiles,
                     "docs": n_docs, "calls": len(served),
                     "tokens": sum(call_tokens[i] for i, _ in served),
                     "checked_rows": sample_n},
        "spans": span_log,
    }


def _check(ctx, ref, params, engine, pool, served, vocab):
    import jax

    model = ctx.config["model"]
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

    # free the program's device state before the reference runs
    ctx.release(engine)
    pad_to = int(ctx.mix["length"]["max"])
    encode = jax.jit(lambda p, t: ref.encode(p, t, model)[0])
    want = common.pooled_rows(encode, params, id_seqs, vocab.pad_id, pad_to,
                              block_rows=n_sample)
    numbers = check.row_numbers(got, want)
    numbers["nonfinite_rows"] = float(nonfinite_rows)
    return numbers, len(picks)
