"""Driver ``bulk_eva``: ``bulk_encoder``'s protocol, unchanged (set-up,
window, the check against the reference's whole-document forward), for a
BYTE encoder whose row holds a block of keys and values that empties at
every multiple of the window beside chunk summaries that grow at a
sixteenth of the document's rate. What it adds:

* the vocabulary. The traffic is the other cells' (GitHub-shaped issues
  over ``mix.words.vocabulary`` pseudo-words), the program reads it
  through a ``ByteVocab``, and the ids a document must tokenise to are
  built here from the plan and not read back from the program: ``<bos>``
  then the UTF-8 bytes of ``" ".join(words[i] for i in ids[1:])`` plus
  the specials' offset;
* the check: ``bulk_kda_moe``'s sample (seeded, the longest served
  document among it), a document at a time at the power of two that
  holds it; the numbers of the whole sample, of its rows that crossed a
  block (``_carried``: they read summaries) and of its rows of more than
  8 blocks (``_long``); and four numbers of what the first two chunk
  programs of the longest served document hand the next one to READ
  (``_handed_on``: block keys, block values, summary keys, summary
  values), which hold ``state_dtype`` where the rows cannot;
* the controls such a model needs, each changing the PROGRAM only (the
  reference keeps the configuration as its file states it);
* a capture that opens BEFORE the window's first call (a call outlasts
  the window).

Controls (``overrides``; the benchmark's own runs never set one), beside
``precision=int8`` (``bulk_moe``'s walk over a dict of leaves a layer)
and ``state_dtype`` (the four caches'): ``summaries=zeroed`` hands every
chunk program zeroed summary caches (no remote context);
``summaries=mean`` weighs a chunk's positions evenly (``phi = 0``);
``summaries=early`` lets a query see the summaries of its OWN block's
chunks before its program; ``mu=off`` leaves the pooled key's offset
out; ``window=sliding`` admits ``query - key < window`` in place of the
block (the slots after a query's own hold exactly those keys);
``rope=off`` leaves the rotary out; ``norm_weight=plain`` reads every
norm weight ``(1 + w)`` as ``w``; ``caches=zeroed`` hands every chunk
program zeroed block and summary caches.
"""

from __future__ import annotations

import contextlib
import sys
import time
import types

import numpy as np

from benchmark.harness import check, traffic
from benchmark.harness.cell import load_driver
from benchmark.reference import common

# the jax.named_scope names the compiled forward carries
PARTS = (r"embedding|attention_\d+|mlp_\d+|qkv_proj|rope|eva_summaries|"
         r"eva_core|o_proj|final_norm|pool")
CONTROLS = ("summaries", "mu", "window", "rope", "norm_weight", "caches")
CACHES = ("k", "v", "k_sum", "v_sum")
# chunk programs the document of ``_handed_on`` takes
_HANDED_ON_PROGRAMS = 2
# rows of more blocks than this are ``_long``
_LONG_BLOCKS = 8


def _vocab():
    from code_intelligence_tpu.text import ByteVocab

    return ByteVocab()


def byte_traffic(mix: dict):
    """``harness/traffic.py`` as ``bulk_encoder.run`` calls it, for a byte
    model: the words are the mix's own vocabulary (the model's 320 ids
    are bytes, not words), and a document's ``ids`` are the byte ids its
    text must tokenise to."""
    vocab = _vocab()

    def vocab_words(specials, vocab_size):
        return traffic.vocab_words(specials, int(mix["words"]["vocabulary"]))

    def expected(doc, words):
        text = " ".join(words[i] for i in doc["ids"][1:]).encode("utf-8")
        return np.concatenate([
            [vocab.bos_id],
            np.frombuffer(text, np.uint8).astype(np.int32) + vocab.n_special
        ]).astype(np.int32)

    def make_document_calls(mix, words, seed, n_calls, stream=0):
        calls = traffic.make_document_calls(mix, words, seed, n_calls, stream)
        return [[dict(doc, ids=expected(doc, words)) for doc in call]
                for call in calls]

    return types.SimpleNamespace(
        vocab_words=vocab_words, make_document_calls=make_document_calls,
        length_quartiles=traffic.length_quartiles)


def program_config(ctx):
    """The program's configuration of the cell's model."""
    import jax.numpy as jnp

    from code_intelligence_tpu.models import make_config

    return make_config(
        ctx.config["architecture"], ctx.config,
        kv_positions=int(ctx.config["serve"]["kv_positions"]),
        state_dtype=jnp.dtype(ctx.overrides.get(
            "state_dtype", ctx.config["state_dtype"])))


@contextlib.contextmanager
def _program_as(on, encoder):
    """For the length of the block (one trace of the encoder), the
    program's pieces as the placement controls have them."""
    import jax.numpy as jnp

    from code_intelligence_tpu.ops import eva

    model = sys.modules[type(encoder).__module__]
    real = (eva._reach, model._unit_offset, model.rope_qk)
    reach = eva._reach
    if on["window"] == "sliding":
        def sliding(pos, T, W, window, chunk):
            slots, _, seen = reach(pos, T, W, window, chunk)
            wrapped = pos >= window  # the slots hold the block before
            return jnp.where(wrapped, W, slots), wrapped, seen
        eva._reach = sliding
    if on["summaries"] == "early":
        def early(pos, T, W, window, chunk):
            slots, stale, _ = reach(pos, T, W, window, chunk)
            return slots, stale, pos // chunk
        eva._reach = early
    if on["norm_weight"] == "plain":
        model._unit_offset = lambda w: w.astype(jnp.float32)
    if on["rope"] == "off":
        model.rope_qk = lambda q, k, pos, inv_freq: (
            q.astype(jnp.float32), k.astype(jnp.float32))
    try:
        yield
    finally:
        eva._reach, model._unit_offset, model.rope_qk = real


def build_engine(ctx, params, vocab):
    """The system under test, as ``serve`` configures it, reading bytes;
    with a control, the encoder's ``encode`` wrapped for the run."""
    import jax
    import jax.numpy as jnp

    from code_intelligence_tpu.inference import InferenceEngine

    serve = ctx.config["serve"]
    engine = InferenceEngine(
        params, program_config(ctx), _vocab(),
        batch_size=int(serve["batch_size"]),
        scheduler=serve["scheduler"], buckets=tuple(serve["buckets"]))
    on = {name: ctx.overrides.get(name) for name in CONTROLS}
    if not any(on.values()):
        return engine
    encoder = engine.encoder
    encode = encoder.encode
    zeroed = ()  # the caches a chunk program is handed zeroed
    if on["summaries"] == "zeroed":
        zeroed = ("k_sum", "v_sum")
    if on["caches"] == "zeroed":
        zeroed = CACHES
    without = [leaf for leaf, off in (("phi", on["summaries"] == "mean"),
                                      ("mu", on["mu"] == "off")) if off]

    def controlled(params, tokens, states, lengths=None):
        layers = {name: dict(p, **{leaf: jnp.zeros_like(p[leaf])
                                   for leaf in without})
                  for name, p in params["layers"].items()}
        with _program_as(on, encoder):
            out, new = encode(dict(params, layers=layers), tokens, states,
                              lengths=lengths)
        return out, dict(new, **{name: jax.tree.map(jnp.zeros_like, new[name])
                                 for name in zeroed})

    encoder.encode = controlled
    return engine


def _check(ctx, ref, params, pool, served):
    """``bulk_kda_moe``'s sample and padding; the subsets are this
    model's: rows that crossed a block, rows of more than
    ``_LONG_BLOCKS`` blocks."""
    import jax

    pad_to = load_driver("bulk_kda_moe", ctx.bench_dir)._pad_to
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
    want = np.zeros(got.shape, np.float64)
    for pad in sorted({pad_to(len(s)) for s in id_seqs}):
        at = [i for i, s in enumerate(id_seqs) if pad_to(len(s)) == pad]
        want[at] = common.pooled_rows(
            encode, params, [id_seqs[i] for i in at], _vocab().pad_id, pad,
            block_rows=int(ctx.cell["check"].get("block_rows", 1)))
    numbers = check.row_numbers(got, want)
    numbers["nonfinite_rows"] = float(nonfinite_rows)
    window = int(ctx.config["window_size"])
    for suffix, longer_than in (("carried", window),
                                ("long", window * _LONG_BLOCKS)):
        rows = [i for i, s in enumerate(id_seqs) if len(s) > longer_than]
        if rows:
            for name, value in check.row_numbers(
                    got[rows], want[rows]).items():
                numbers[f"{name}_{suffix}"] = value
    ctx.log("check: %d rows of %s bytes against the reference in %.1f s" % (
        len(picks), sorted(len(s) for s in id_seqs),
        time.perf_counter() - t0))
    return numbers, len(picks)


def _handed_on(ctx, ref, params, encoder, ids) -> dict:
    """What the first chunk programs of one document (``ids``, its first
    ``_HANDED_ON_PROGRAMS`` chunks) hand the next one to read as it is,
    against what the reference's later positions read of the same bytes:
    every layer's block keys and values a position and summary keys and
    values a chunk, each by the MEDIAN over its positions (all layers')
    of the relative error. What the stored type rounds moves every
    position, so the median reads it. The program's one row runs at the
    serve configuration's largest bucket with the caches sized for these
    positions."""
    import jax
    import jax.numpy as jnp

    position_errors = load_driver(
        "bulk_gdn_moe", ctx.bench_dir)._position_errors
    chunk = max(ctx.config["serve"]["buckets"])
    ids = np.asarray(ids[:_HANDED_ON_PROGRAMS * chunk], np.int32)
    n = len(ids) - len(ids) % int(ctx.config["chunk_size"])  # whole chunks
    programs = -(-n // chunk)
    tokens = np.full((1, programs * chunk), _vocab().pad_id, np.int32)
    tokens[0, :n] = ids[:n]
    program = jax.jit(encoder.encode)
    states = encoder.init_states(1, programs * chunk)
    for a in range(0, programs * chunk, chunk):
        _, states = program(
            params, jnp.asarray(tokens[:, a:a + chunk]), states,
            lengths=jnp.asarray([min(chunk, n - a)], jnp.int32))
    with jax.default_matmul_precision("highest"):  # the reference's
        want = jax.jit(lambda p, t: ref.encode(p, t, ctx.config)[1])(
            params, jnp.asarray(tokens[:, :n]))
    errs = {}
    for name, label in zip(CACHES, ("block_k", "block_v", "sum_k", "sum_v")):
        # a cache is head-major (rows, heads, slots, d); the block cache
        # holds the positions of the last block begun, from slot 0
        slots = states[name][0].shape[2]
        m = want[name][0].shape[1]  # positions, or chunks
        first = (m - 1) // slots * slots if name in ("k", "v") else 0
        got = [np.asarray(c.astype(jnp.float32))[:, :, :m - first].swapaxes(
            1, 2) for c in states[name]]
        errs[label] = position_errors(
            got, [w[:, first:] for w in want[name]])
    ctx.log("handed on after %d bytes, relative error a position "
            "(median, ninth decile, largest): %s" % (n, {
                name: [float("%.4g" % q)
                       for q in np.quantile(e, (0.5, 0.9, 1.0))]
                for name, e in errs.items()}))
    return {f"rel_err_p50_{name}": float(np.median(e))
            for name, e in errs.items()}


def run(ctx) -> dict:
    # a program without the architecture fails here, at once, before
    # 3.2 GB of weights are made
    program_config(ctx)
    base = load_driver("bulk_encoder", ctx.bench_dir)
    base.make_weights = load_driver("bulk_moe", ctx.bench_dir).make_weights
    base.PARTS, base.traffic = PARTS, byte_traffic(ctx.mix)
    encoders = []

    def build(ctx, params, vocab):
        engine = build_engine(ctx, params, vocab)
        # the check runs after the engine's state is released
        encoders.append(engine.encoder)
        return engine

    def checked(ctx, ref, params, pool, served, vocab):
        numbers, rows = _check(ctx, ref, params, pool, served)
        longest = max((doc["ids"] for k, _ in served for doc in pool[k]),
                      key=len)
        numbers.update(_handed_on(ctx, ref, params, encoders[-1], longest))
        return numbers, rows

    base.build_engine, base._check = build, checked
    # the capture, which the protocol opens once a first call is done,
    # opens before the window's first call: a call outlasts ``--seconds``,
    # so there is no second one for it to open before (set-up has run
    # every shape: nothing compiles inside it)
    step = ctx.profiler.step
    ctx.profiler.step = lambda first_done: step(first_done=True)
    return base.run(ctx)
