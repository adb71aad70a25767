"""The EvaByte encoder (a byte-level dense transformer whose attention
is a block attended exactly beside chunk summaries of everything before
it, under one softmax), the encoder contract's ninth member, and the
byte vocabulary behind ``Vocab``'s interface.

Small on the CPU, the published structure (hidden 64, 4 heads of 16, a
SwiGLU of 96, 2 layers; a block of 32 positions in chunks of 4), every
comparison against the plain reference (`benchmark/reference/evabyte.py`)
on seeded weights: whole documents of four blocks through chunk programs
of several lengths, in float32 and bfloat16, with padding; the block and
summary state through the engine's normal path and on its spans; the
device counts against the mask's arithmetic; the contract's numbers at
the published widths.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common
from benchmark.reference import evabyte as ref
from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.models import (
    ChunkEncoder, EvaByteConfig, EvaByteEncoder, build_encoder, make_config)
from code_intelligence_tpu.models import contract
from code_intelligence_tpu.text import (
    SPECIALS, ByteVocab, Tokenizer, Vocab, build_issue_text)
from code_intelligence_tpu.utils import tracing
from encoder_programs import compiled, seeded

ROOT = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads(
    (ROOT / "benchmark/configs/evabyte_6_5b_pp4_stage0.json").read_text())
MODEL = {
    "vocab_size": 320, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "window_size": 32, "chunk_size": 4,
    "rms_norm_eps": 1e-5, "rope_theta": 100000, "rope_scaling": None,
    "attention_class": "eva", "attention_bias": False, "hidden_act": "silu",
    "norm_add_unit_offset": True, "fp32_skip_add": True, "mixedp_attn": True,
    "num_chunks": None, "max_position_embeddings": 128,
    "model_type": "evabyte", "num_pred_heads": 8}
TAILS = {"dist": "student_t", "df": 4}
LENGTHS = (128, 100, 77, 9)   # four blocks, and a partial last chunk
W, C = 32, 4


@pytest.fixture(scope="module")
def params():
    return seeded(ref, 51, MODEL, TAILS)


def config(**extra):
    return make_config("evabyte", MODEL, **dict(
        {"kv_positions": 128, "chunk_positions": 32,
         "state_dtype": jnp.float32}, **extra))


@pytest.fixture(scope="module")
def encoder(params):
    return build_encoder(config(), params)


@pytest.fixture(scope="module")
def engine(params):
    return InferenceEngine(params, config(), ByteVocab(), buckets=(16, 32),
                           batch_size=4)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (4, 128), 64, 320)


def reference(params, tokens, model=MODEL):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: ref.encode(p, t, model))(params, tokens)


@pytest.fixture(scope="module")
def want(params, tokens):
    return reference(params, tokens)


def streamed(enc, params, tokens, T, lengths=LENGTHS):
    """``tokens`` through chunk programs of ``T``, each told its rows'
    valid lengths."""
    b, n = tokens.shape
    states = enc.init_states(b, n)
    outs = []
    for a in range(0, n, T):
        with jax.default_matmul_precision("highest"):
            out, states = compiled(enc)(
                params, tokens[:, a:a + T], states, lengths=jnp.asarray(
                    [max(0, min(T, m - a)) for m in lengths], jnp.int32))
        outs.append(out)
    return jnp.concatenate(outs, axis=1), states


# -- the encoder against the reference -------------------------------------------

@pytest.mark.parametrize("T", [8, 16, 32])
def test_chunk_programs_equal_the_whole_document_forward(
        params, encoder, tokens, want, T):
    """Four blocks through 16, 8 and 4 chunk programs, rows that end
    early: float32 against float32 at ``highest``; what is left is the
    order of the sums (a running softmax over key blocks, a program's
    matmuls) through 2 layers: 2e-5 of outputs of order 1."""
    got, _ = streamed(encoder, params, tokens, T)
    for r, n in enumerate(LENGTHS):
        np.testing.assert_allclose(got[r, :n], want[0][r, :n], atol=2e-5)


@pytest.mark.parametrize("T", [16, 32])
def test_what_a_chunk_program_hands_on_is_what_the_reference_reads(
        params, encoder, tokens, want, T):
    """The last block's keys and values from slot 0, and a summary for
    every chunk of the document, as the reference's later positions read
    them."""
    _, states = streamed(encoder, params, tokens, T, (128,) * 4)
    for layer in range(MODEL["num_hidden_layers"]):
        for name in ("k", "v"):
            np.testing.assert_allclose(
                states[name][layer].swapaxes(1, 2),
                want[1][name][layer][:, 128 - W:], atol=1e-5)
        for name in ("k_sum", "v_sum"):
            np.testing.assert_allclose(
                states[name][layer].swapaxes(1, 2), want[1][name][layer],
                atol=1e-5)


def test_the_device_counts_are_the_masks_arithmetic(encoder, params, tokens):
    """A position ``p`` meets ``p % 32 + 1`` keys of its own block and 8
    summaries for each block before it; a chunk with a valid lane is
    written once."""
    _, states = streamed(encoder, params, tokens, 16)
    got = dict(zip(encoder.counts.names, np.asarray(states["counts"])))
    every = [p for n in LENGTHS for p in range(n)]
    assert got["eva_singleton_pairs"] == sum(p % W + 1 for p in every)
    assert got["eva_summary_pairs"] == sum(p // W * (W // C) for p in every)
    assert got["eva_summaries_written"] == sum(-(-n // C) for n in LENGTHS)
    assert got["eva_kernel_layers"] == 0
    assert int(states["pos"]) == 128


def test_bfloat16_program_against_the_float32_reference(tokens):
    """bfloat16 weights, matmul inputs and caches through 4 programs of
    32 against the float32 reference on the same (rounded) weights: each
    third of the pooled row by relative RMS, a few 1e-3 a matmul through
    2 layers; 0.02 holds it with room and is 10 x under a dropped cache."""
    weights = seeded(ref, 51, MODEL, TAILS, dtype=jnp.bfloat16)
    enc = build_encoder(config(state_dtype=jnp.bfloat16), weights)
    assert enc.dtype == jnp.bfloat16
    out, states = streamed(enc, weights, tokens, 32)
    assert states["k"][0].dtype == states["k_sum"][0].dtype == jnp.bfloat16
    raw = reference(weights, tokens)[0]
    for r, n in enumerate(LENGTHS):
        err = np.asarray(out[r, :n], np.float64) - np.asarray(raw[r, :n])
        assert np.sqrt((err ** 2).mean()) \
            / np.sqrt((np.asarray(raw[r, :n]) ** 2).mean()) < 0.02


@pytest.mark.parametrize("leaves", [("k", "v"), ("k_sum", "v_sum")])
def test_dropped_state_of_either_kind_is_seen(params, encoder, tokens, want,
                                              leaves):
    b, n = tokens.shape
    states = encoder.init_states(b, n)
    outs = []
    for a in range(0, n, 16):
        out, states = compiled(encoder)(
            params, tokens[:, a:a + 16], states,
            lengths=jnp.full((b,), 16, jnp.int32))
        states = dict(states, **{name: jax.tree.map(
            jnp.zeros_like, states[name]) for name in leaves})
        outs.append(out)
    got = jnp.concatenate(outs, axis=1)
    assert float(jnp.abs(got[:, 64:] - want[0][:, 64:]).max()) > 1e-2
    # the first program reads no state of either kind
    np.testing.assert_allclose(got[:, :16], want[0][:, :16], atol=2e-5)


# -- through the engine ------------------------------------------------------------

def reference_rows(params, id_seqs):
    encode = jax.jit(lambda p, t: ref.encode(p, t, MODEL)[0])
    return common.pooled_rows(encode, params, id_seqs, ByteVocab.pad_id, 128,
                              block_rows=4)


def _traced_finalize(engine, seqs):
    log = []
    tracer = tracing.Tracer(max_traces=4, max_live=16)
    tracer.on_trace(log.append)
    roots = [tracer.start_span("doc") for _ in seqs]
    rows = engine.embed_ids_batch(seqs, ctxs=[r.context for r in roots])
    for r in roots:
        r.end()
    spans = [s for t in log for s in t["spans"]]
    (fin,) = [s for s in spans if s["name"] == "engine.finalize"]
    return rows, spans, fin["attrs"]


def test_through_the_engine_with_narrowing_and_counts_on_the_span(
        params, engine):
    """One group of four at bucket 32: 120, 9, 40 and 70 bytes: the
    batch narrows 4, 2, 1, 1 and the longest document passes three
    blocks; every row is the reference's whole-document forward for that
    document alone, and the flush's span carries what the cores met."""
    rng = np.random.default_rng(7)
    seqs = [rng.integers(64, 320, n).astype(np.int32)
            for n in (120, 9, 40, 70)]
    got, spans, a = _traced_finalize(engine, seqs)
    assert got.shape == (4, 3 * 64) == (4, engine.embed_dim)
    np.testing.assert_allclose(got, reference_rows(params, seqs),
                               rtol=1e-4, atol=5e-5)
    every = [p for s in seqs for p in range(len(s))]
    assert a["eva_singleton_pairs"] == sum(p % W + 1 for p in every)
    assert a["eva_summary_pairs"] == sum(p // W * (W // C) for p in every)
    assert a["eva_summaries_written"] == sum(-(-len(s) // C) for s in seqs)
    assert a["eva_kernel_layers"] == 0
    (group,) = [s for s in spans if s["name"] == "engine.group"]
    g = group["attrs"]
    # the engine's counts are upper bounds here: allocated for 128
    # positions of summaries and a block of 32 slots
    assert (g["chunks"], g["kv_positions"], g["kv_positions_window"]) \
        == (4, 128, 32)
    assert g["state_bytes"] == 4 * (2 * 2 * 64 * (32 + 32) * 4)
    # (rows x positions reached, a bucket's queries a row), for what the
    # cores met
    assert a["eva_singleton_pairs"] + a["eva_summary_pairs"] \
        < g["window_steps_run"] * g["bucket"] \
        < g["cache_steps_run"] * g["bucket"]


def test_through_the_engine_on_the_pallas_core(monkeypatch, params):
    """The same four documents through chunk programs of 16 (eight for
    the longest, two key blocks of the 32-slot block cache and four of
    the summaries) with every layer's joint core, and its write of the
    chunk into the block cache, on the Pallas kernel,
    interpreted: the rule answers as it would on the chip for what it is
    shown here (a block cache of more than one program), the rows are the
    reference's, the pairs the kernel's masks admitted are the mask's
    arithmetic, and the span says both layers took the kernel."""
    from code_intelligence_tpu.ops import eva

    monkeypatch.setattr(eva, "core_is_kernel",
                        lambda backend, dtype, T, W, S, d: W > T)
    monkeypatch.setattr(eva, "_kernel_tiles", lambda *a: (2, 8))
    engine = InferenceEngine(
        params, config(chunk_positions=16), ByteVocab(), buckets=(8, 16),
        batch_size=4)
    rng = np.random.default_rng(7)
    seqs = [rng.integers(64, 320, n).astype(np.int32)
            for n in (120, 9, 40, 70)]
    got, spans, a = _traced_finalize(engine, seqs)
    np.testing.assert_allclose(got, reference_rows(params, seqs),
                               rtol=1e-4, atol=5e-5)
    every = [p for s in seqs for p in range(len(s))]
    assert a["eva_kernel_layers"] == MODEL["num_hidden_layers"] == 2
    assert a["eva_singleton_pairs"] == sum(p % W + 1 for p in every)
    assert a["eva_summary_pairs"] == sum(p // W * (W // C) for p in every)
    (group,) = [s for s in spans if s["name"] == "engine.group"]
    assert (group["attrs"]["chunks"], group["attrs"]["bucket"]) == (8, 16)


def test_short_documents_take_one_program_and_no_summary(params, engine):
    seqs = [np.arange(64, 64 + n, dtype=np.int32) for n in (5, 12, 16)]
    got, _, a = _traced_finalize(engine, seqs)
    np.testing.assert_allclose(got, reference_rows(params, seqs),
                               rtol=1e-4, atol=5e-5)
    assert a["eva_summary_pairs"] == 0


def test_a_document_past_the_summaries_is_refused(engine):
    with pytest.raises(ValueError, match="kv_positions=128"):
        engine.embed_ids_batch([np.full(130, 70, np.int32)])


@pytest.mark.parametrize("scheduler", ["slots", "ragged"])
def test_other_schedulers_refuse_it_by_name(engine, scheduler):
    with pytest.raises(ValueError) as e:
        engine.embed_issues([{"title": "w1", "body": "w2"}],
                            scheduler=scheduler)
    assert scheduler in str(e.value) and "EvaByte" in str(e.value)


def test_the_engine_has_no_branch_for_it():
    from code_intelligence_tpu.inference import engine as module

    text = open(module.__file__).read().lower()
    assert "evabyte" not in text and "eva_" not in text


def test_embed_text_reads_bytes(params, engine):
    """The product's own entry point: the field marks are spelled out,
    ``xxbos`` is ``<bos>``."""
    text = build_issue_text("héllo wörld", "a `b` c")
    ids = engine.numericalize(text)
    assert ids[0] == ByteVocab.bos_id and ids.min() >= 1
    np.testing.assert_allclose(
        engine.embed_text(text), reference_rows(params, [ids])[0],
        rtol=1e-4, atol=5e-5)


# -- the contract ------------------------------------------------------------------

@pytest.mark.parametrize("positions,block,summaries", [
    (None, 32, 32), (128, 32, 32), (100, 32, 32), (40, 32, 16),
    (32, 32, 8), (16, 16, 4), (9, 9, 3)])
def test_state_bytes_are_the_arrays_it_allocates(encoder, positions, block,
                                                 summaries):
    states = encoder.init_states(3, positions)
    assert states["k"][0].shape == states["v"][1].shape == (3, 4, block, 16)
    assert states["k_sum"][0].shape == states["v_sum"][1].shape \
        == (3, 4, summaries, 16)
    caches = sum(leaf.nbytes for name in ("k", "v", "k_sum", "v_sum")
                 for leaf in states[name])
    assert encoder.state_bytes_per_row(positions) * 3 == caches
    assert encoder.window_positions(positions) == block
    assert -(-encoder.cache_positions(positions) // C) == summaries


def test_it_satisfies_the_contract(encoder):
    assert isinstance(encoder, ChunkEncoder) and encoder.out_dim == 64
    assert encoder.state_counters(encoder.init_states(1)).shape == (7,)
    assert encoder.counter_attrs([]) == {}
    with pytest.raises(ValueError, match="kv_positions=128"):
        encoder.init_states(1, 129)


def test_published_widths_carry_537_megabytes_a_row():
    """The benchmark configuration's keys through the table: 8 layers of
    2 x 32 x (2048 + 2048) x 128 bfloat16 at 32,768 positions."""
    cfg = make_config(
        PUBLISHED["architecture"], PUBLISHED,
        kv_positions=PUBLISHED["serve"]["kv_positions"],
        state_dtype=jnp.dtype(PUBLISHED["state_dtype"]))
    assert isinstance(cfg, EvaByteConfig)
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size,
            cfg.num_attention_heads, cfg.head_dim, cfg.window_size,
            cfg.chunk_size, cfg.vocab_size, cfg.rope_theta) == (
        8, 4096, 11008, 32, 128, 2048, 16, 320, 100000)
    enc = build_encoder(cfg)
    assert enc.state_bytes_per_row(32768) == 536870912
    assert enc.state_bytes_per_row(17000) == 536870912
    assert enc.state_bytes_per_row(16384) == 8 * 2 * 4096 * (2048 + 1024) * 2
    assert enc.state_bytes_per_row(1786) == 8 * 2 * 4096 * (2048 + 128) * 2
    assert (enc.cache_positions(30400), enc.window_positions(30400)) \
        == (32768, 2048)
    shapes = jax.eval_shape(lambda: enc.init_states(8, 32768))
    assert shapes["k"][7].shape == shapes["v_sum"][0].shape \
        == (8, 32, 2048, 128)


def test_config_from_the_published_keys():
    cfg = config()
    assert hash(cfg) == hash(config()) and cfg.head_dim == 16
    assert cfg.ring_positions == 32
    with pytest.raises(ValueError, match="num_key_value_heads"):
        dataclasses.replace(cfg, num_key_value_heads=2)
    with pytest.raises(ValueError, match="whole chunks"):
        dataclasses.replace(cfg, window_size=30)
    with pytest.raises(ValueError, match="whole blocks"):
        dataclasses.replace(cfg, kv_positions=100)


@pytest.mark.parametrize("key,other", [
    ("attention_class", "softmax"), ("hidden_act", "gelu"),
    ("rope_scaling", {"type": "yarn"}), ("attention_bias", True),
    ("norm_add_unit_offset", False), ("num_chunks", 4)])
def test_a_switch_it_implements_one_value_of_is_refused_by_name(key, other):
    with pytest.raises(ValueError, match=key):
        make_config("evabyte", dict(MODEL, **{key: other}))
    with pytest.raises(NotImplementedError, match=key):
        ref.dims(dict(MODEL, **{key: other}))


def test_the_table_has_a_ninth_row():
    assert len(contract.ENCODERS) >= 9 and "evabyte" in contract.ENCODERS
    assert contract.ENCODERS["evabyte"][0] is EvaByteConfig
    assert isinstance(build_encoder(config()), EvaByteEncoder)


def test_export_round_trip_in_bfloat16_with_the_byte_vocabulary(tmp_path):
    from code_intelligence_tpu.training.checkpoint import export_encoder

    cfg = make_config("evabyte", MODEL, kv_positions=64, chunk_positions=16)
    weights = seeded(ref, 1, MODEL, dtype=jnp.bfloat16)
    export_encoder(tmp_path, weights, cfg, ByteVocab())
    eng = InferenceEngine.from_export(tmp_path, buckets=(16,), batch_size=2)
    assert eng.config == cfg and eng.encoder.dtype == jnp.bfloat16
    assert isinstance(eng.vocab, ByteVocab)
    direct = InferenceEngine(weights, cfg, ByteVocab(), buckets=(16,),
                             batch_size=2)
    issue = [{"title": "crash on start", "body": "see `log` :\n- item"}]
    np.testing.assert_array_equal(eng.embed_issues(issue),
                                  direct.embed_issues(issue))


# -- the byte vocabulary -----------------------------------------------------------

@pytest.mark.parametrize("tokens,ids", [
    (["xxbos", "ab"], [1, 64 + 97, 64 + 98]),
    (["xxbos", "a", "b"], [1, 64 + 97, 64 + 32, 64 + 98]),
    (["é"], [64 + 0xC3, 64 + 0xA9]),
    (["日本"], [64 + b for b in "日本".encode()]),
    (["a", "xxbos", "b"], [64 + 97, 1, 64 + 98]),
    ([], []),
])
def test_byte_vocab_spells_tokens_in_utf8(tokens, ids):
    got = ByteVocab().numericalize(tokens)
    assert got.dtype == np.int32 and got.tolist() == ids


def test_byte_vocab_has_vocabs_interface(tmp_path):
    vocab = ByteVocab()
    assert (len(vocab), vocab.pad_id, vocab.bos_id) == (320, 0, 1)
    toks = Tokenizer(backend="auto").tokenize(
        build_issue_text("héllo", "some `code` body"))
    ids = vocab.numericalize(toks)
    assert ids[0] == vocab.bos_id and ids.max() < len(vocab)
    assert bytes((ids[1:] - 64).tolist()).decode() == " ".join(toks[1:])
    vocab.save(tmp_path / "vocab.json")
    for loaded in (Vocab.load(tmp_path / "vocab.json"),
                   ByteVocab.load(tmp_path / "vocab.json")):
        assert isinstance(loaded, ByteVocab) and len(loaded) == 320
        assert loaded.content_hash() == vocab.content_hash()
    table = Vocab(SPECIALS + ["a", "b"])
    table.save(tmp_path / "table.json")
    assert type(Vocab.load(tmp_path / "table.json")) is Vocab
    assert vocab.content_hash() not in (
        table.content_hash(), Vocab(SPECIALS).content_hash(),
        ByteVocab(32).content_hash())
    with pytest.raises(ValueError, match="two special ids"):
        ByteVocab(1)
