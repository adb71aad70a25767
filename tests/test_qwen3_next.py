"""The Qwen3-Next encoder (Gated DeltaNet in three layers of four, gated
softmax attention in the fourth, every layer an expert layer with a
sigmoid-gated shared expert, of which this chip holds half the experts)
and the encoder contract's eighth member.

Small on the CPU, the published structure (hidden 64; 4 layers 3 : 1; 2
key and 4 value heads of 16 in the linear layers; 4 query heads on 2
key/value heads of 32 with rotary on a quarter of them; 16 experts, 4 a
token, of which 4..11 are held), every comparison against the plain
reference (`benchmark/reference/qwen3_next.py`) on seeded weights: whole
and across 2, 3 and 5 chunk programs, in float32 and bfloat16, with
padding; the pieces that are new in a model file each inside the
comparison; the two half shares adding up to the uncut layer; both kinds
of state through the engine's normal path and on its spans; the
contract's numbers at the published widths.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.reference import common
from benchmark.reference import qwen3_next as ref
from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.models import (
    ChunkEncoder, Qwen3NextConfig, Qwen3NextEncoder, build_encoder,
    make_config)
from code_intelligence_tpu.models import blocks, contract, qwen3_next
from code_intelligence_tpu.ops import attention, gdn, mla, moe
from code_intelligence_tpu.text import SPECIALS, Vocab
from code_intelligence_tpu.utils import tracing
from encoder_programs import (
    compiled, seeded, the_rule_says_grouped_kernels)

MODEL = {
    "vocab_size": 300, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "rope_scaling": None, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_experts": 8, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "use_sliding_window": False,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "max_position_embeddings": 262144, "model_type": "qwen3_next",
    "tie_word_embeddings": False,
    "experts_held": {"first": 4, "count": 8, "of": 16}}
UNCUT = dict(MODEL, num_experts=16,
             experts_held={"first": 0, "count": 16, "of": 16})
TAILS = {"dist": "student_t", "df": 4}
T_DOC = 200   # four chunks of the recurrence (64), the last one short


@pytest.fixture(scope="module")
def params():
    return seeded(ref, 47, MODEL, TAILS)


def config(**extra):
    return make_config("qwen3_next", MODEL, **dict(
        {"kv_positions": 256, "state_dtype": jnp.float32}, **extra))


@pytest.fixture(scope="module")
def encoder(params):
    return build_encoder(config(), params)


@pytest.fixture(scope="module")
def vocab():
    return Vocab(traffic.vocab_words(SPECIALS, 300))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, T_DOC), 0, 300)


def reference(params, tokens, model=MODEL):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: ref.encode(p, t, model))(params, tokens)


@pytest.fixture(scope="module")
def want(params, tokens):
    return reference(params, tokens)[0]


def streamed(enc, params, tokens, programs, between=None):
    """``tokens`` through ``programs`` chunk programs of equal length,
    the last one padded and told its valid lengths."""
    b, T = tokens.shape
    size = -(-T // programs)
    states = enc.init_states(b, size * programs)
    outs = []
    for a in range(0, T, size):
        chunk = tokens[:, a:a + size]
        n = chunk.shape[1]
        chunk = jnp.pad(chunk, ((0, 0), (0, size - n)))
        with jax.default_matmul_precision("highest"):
            out, states = compiled(enc)(
                params, chunk, states, lengths=jnp.full((b,), n, jnp.int32))
        if between is not None:
            states = between(states)
        outs.append(out[:, :n])
    return jnp.concatenate(outs, axis=1), states


# -- the encoder against the reference ---------------------------------------------

def test_encoder_equals_the_reference(params, encoder, tokens, want):
    """float32 on both sides: what differs is the order of sums (chunks
    against token by token, a cache against a dense softmax, a grouped
    matmul against a masked loop); values are O(5)."""
    with jax.default_matmul_precision("highest"):
        got, states = compiled(encoder)(params, tokens,
                                        encoder.init_states(2, T_DOC))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=5e-5)
    assert int(states["pos"]) == T_DOC


@pytest.mark.parametrize("programs", [2, 3, 5])
def test_a_document_across_chunk_programs_equals_one_program(
        params, encoder, tokens, want, programs):
    """State in, state out: matrix states and conv tails of three layers
    and one key/value cache handed over ``programs - 1`` times, the last
    program padded."""
    got, states = streamed(encoder, params, tokens, programs)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=5e-5)
    # both rows still going in every program after the first
    attrs = encoder.counter_attrs([np.asarray(states["counts"])])
    assert attrs["gdn_state_handovers"] == 2 * (programs - 1)
    assert attrs["moe_programs"] == programs


def test_the_encoder_on_the_grouped_matmul_kernels_equals_the_reference(
        monkeypatch, params, tokens, want):
    """Every expert layer's two grouped products through ``ops/gmm.py``'s
    kernels (interpreted), three chunk programs, the last one padded,
    and the count says four layers."""
    the_rule_says_grouped_kernels(monkeypatch)
    enc = build_encoder(config(), params)
    got, states = streamed(enc, params, tokens, 3)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=5e-5)
    assert enc.counter_attrs([np.asarray(states["counts"])])[
        "expert_kernel_layers"] == 4


@pytest.mark.parametrize("programs", [2, 3, 5])
def test_what_a_chunk_program_hands_on_is_what_the_reference_reads(
        params, encoder, tokens, programs):
    """The state a later program reads as it is: the attention layer's
    cache holds the reference's keys and values (normed, turned) at the
    document's positions, a linear layer's conv tail its last three
    positions of ``[q | k | v]`` before the conv."""
    _, states = streamed(encoder, params, tokens, programs)
    read = reference(params, tokens)[2]
    for name in ("k", "v"):
        (cache,), (want,) = states[name], read[name]
        np.testing.assert_allclose(
            cache[:, :, :T_DOC].swapaxes(1, 2), want, rtol=2e-5, atol=2e-5)
    assert len(states["conv"]) == len(read["conv"]) == 3
    for tail, want in zip(states["conv"], read["conv"]):
        np.testing.assert_allclose(tail, want, rtol=2e-5, atol=2e-5)


def test_the_encoder_on_the_attention_kernel_equals_the_reference(
        monkeypatch, params, tokens, want):
    """The attention layer's core through the Pallas kernel
    (interpreted) at two query heads a key/value head, four chunk
    programs, and the count says one layer."""
    monkeypatch.setattr(attention, "core_is_kernel", lambda *a: True)
    monkeypatch.setattr(attention, "_kernel_tiles", lambda *a: (2, 8))
    enc = build_encoder(config(), params)
    got, states = streamed(enc, params, tokens, 4)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=5e-5)
    attrs = enc.counter_attrs([np.asarray(states["counts"])])
    assert attrs["attention_kernel_layers"] == 1


def test_padding_lanes_leave_state_and_tails_as_they_were(
        params, encoder, tokens):
    """A program of padding alone (``lengths`` 0) after a real one: the
    matrix states and the conv tails come back bit for bit; a row with 3
    valid tokens of 8 ends where the same 3 tokens alone end."""
    step = compiled(encoder)
    with jax.default_matmul_precision("highest"):
        _, before = step(params, tokens[:, :64], encoder.init_states(2, 256))
        _, after = step(params, tokens[:, 64:72], before,
                        lengths=jnp.zeros((2,), jnp.int32))
        _, part = step(params, tokens[:, 64:72], before,
                       lengths=jnp.full((2,), 3, jnp.int32))
        _, alone = step(params, tokens[:, 64:67], before)
    for kind in ("gdn", "conv"):
        for a, b in zip(before[kind], after[kind]):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(part[kind], alone[kind]):
            np.testing.assert_allclose(a, b, atol=2e-6)
    assert float(jnp.abs(part["gdn"][0] - before["gdn"][0]).max()) > 1e-3
    # a program of padding alone hands nothing over that is read
    count = encoder.counts.names.index("gdn_state_handovers")
    assert int(after["counts"][count]) == int(before["counts"][count]) == 0
    assert int(part["counts"][count]) == 2


def _differs(got, want, start):
    return float(jnp.abs(got[:, start:] - want[:, start:]).max())


def test_dropped_state_of_either_kind_is_seen(params, encoder, tokens, want):
    def without(*kinds):
        return lambda s: dict(s, **{kind: jax.tree.map(
            jnp.zeros_like, s[kind]) for kind in kinds})

    for kinds in (("gdn",), ("conv",), ("k", "v")):
        got, _ = streamed(encoder, params, tokens, 4,
                          between=without(*kinds))
        np.testing.assert_allclose(got[:, :50], want[:, :50], atol=5e-5)
        assert _differs(got, want, 50) > 1e-2, kinds


def test_bfloat16_program_against_the_float32_reference(tokens):
    """bfloat16 weights, matmul inputs, conv tails and key/value cache
    against float32 over the same (bfloat16-valued) weights, every
    expert held and every token choosing ALL of them: top-k is discrete
    and a flipped choice moves a token by O(1) and, through the
    recurrence, every token after it, which would drown what is measured
    here: 8 bits of mantissa through 8 residual branches and three
    hand-overs of the state. Relative RMS error, as the benchmark's
    check reads it; looser than float32's 2e-5 by the 2^-8 of a bfloat16
    product's inputs at a hidden size of 64."""
    every = dict(UNCUT, num_experts_per_tok=16)
    weights = seeded(ref, 47, every, TAILS, jnp.bfloat16)
    enc = build_encoder(make_config(
        "qwen3_next", every, kv_positions=256), weights)
    assert enc.dtype == enc.config.state_dtype == jnp.bfloat16
    got, states = streamed(enc, weights, tokens, 4)
    assert [s.dtype for s in states["gdn"]] == [jnp.float32] * 3
    assert states["k"][0].dtype == states["conv"][0].dtype == jnp.bfloat16
    same = reference(jax.tree.map(lambda w: w.astype(jnp.float32), weights),
                     tokens, every)[0]
    rel = float(jnp.sqrt(jnp.mean((got - same) ** 2) / jnp.mean(same ** 2)))
    print("bfloat16 against float32: rel", rel)
    assert 1e-4 < rel < 0.04, rel


@pytest.mark.parametrize("piece", [
    "centred_norm", "rotary_on_every_dim", "no_rotary", "output_gate",
    "qk_norm", "shared_gate", "value_head_order"])
def test_the_new_pieces_are_in_the_comparison(monkeypatch, params, tokens,
                                              want, piece):
    """Each placement the config does not settle, moved: the encoder
    leaves the reference."""
    layers = params["layers"]
    if piece == "centred_norm":
        monkeypatch.setattr(qwen3_next, "_centred",
                            lambda w: w.astype(jnp.float32))
    elif piece == "rotary_on_every_dim":
        every = mla.yarn_inv_freq(32, 10000000.0)
        monkeypatch.setattr(
            qwen3_next, "rope_qk", lambda q, k, pos, inv_freq, width=None:
            blocks.rope_qk(q, k, pos, every))
    elif piece == "no_rotary":
        monkeypatch.setattr(
            qwen3_next, "rope_qk", lambda q, k, pos, inv_freq, width=None:
            (q, k))
    elif piece == "qk_norm":
        monkeypatch.setattr(
            Qwen3NextEncoder, "_qk_norm", lambda self, p, q, k: (
                q.astype(jnp.float32), k.astype(jnp.float32)))
    elif piece == "output_gate":
        qkv = layers["layer_3"]["qkv"]
        heads = qkv[:, :256].reshape(64, 4, 64).at[:, :, 32:].set(0)
        layers = dict(layers, layer_3=dict(
            layers["layer_3"], qkv=jnp.concatenate(
                [heads.reshape(64, 256), qkv[:, 256:]], axis=1)))
    elif piece == "shared_gate":
        layers = {name: {k: v for k, v in p.items() if k != "shared_gate"}
                  for name, p in layers.items()}
    elif piece == "value_head_order":
        # value head j on key head j % 2, not j // 2
        real = qwen3_next.gdn.gdn_scan
        monkeypatch.setattr(
            qwen3_next.gdn, "gdn_scan", lambda q, k, v, g, beta, S, *a, **kw:
            real(jnp.tile(q, (1, 1, 2, 1)), jnp.tile(k, (1, 1, 2, 1)), v, g,
                 beta, S, *a, **kw))
    enc = build_encoder(config(), params)
    with jax.default_matmul_precision("highest"):   # traced patched
        got, _ = jax.jit(enc.encode)(dict(params, layers=layers), tokens,
                                     enc.init_states(2, T_DOC))
    assert _differs(got, want, 8) > 1e-2, piece


# -- what the shared layers learnt ------------------------------------------------

def test_rope_on_a_leading_slice_turns_the_slice_and_passes_the_rest():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 4, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 2, 32))
    inv = mla.yarn_inv_freq(8, 10000000.0)
    pos = jnp.int32(5)
    qr, kr = blocks.rope_qk(q, k, pos, inv, width=8)
    positions = 5 + jnp.arange(6)
    np.testing.assert_array_equal(qr[..., 8:], q[..., 8:])
    np.testing.assert_array_equal(kr[..., 8:], k[..., 8:])
    np.testing.assert_array_equal(qr[..., :8], mla.apply_rope(
        q[..., :8], positions, inv, interleaved=False))
    # pairs (i, i + 4) of the slice: a rotation keeps each pair's length
    np.testing.assert_allclose(
        kr[..., :4] ** 2 + kr[..., 4:8] ** 2,
        k[..., :4] ** 2 + k[..., 4:8] ** 2, rtol=1e-5)
    # and against the reference's own rotary
    with jax.default_matmul_precision("highest"):
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 6, 4, 32))
        got, _ = blocks.rope_qk(x, x, jnp.int32(0), inv, width=8)
        np.testing.assert_allclose(got, ref.rotary(x, MODEL), atol=1e-5)
    # the whole head stays the default, to the bit
    full = mla.yarn_inv_freq(32, 10000.0)
    a, b = blocks.rope_qk(q, k, pos, full)
    np.testing.assert_array_equal(a, mla.apply_rope(
        q, positions, full, interleaved=False))
    np.testing.assert_array_equal(b, mla.apply_rope(
        k, positions, full, interleaved=False))


def test_the_shared_experts_gate_is_the_leafs(params):
    """``expert_layer`` weighs the shared expert a token where the layer
    has ``shared_gate``, and is what it was where it has not."""
    p = params["layers"]["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 64))
    kw = dict(n_group=1, topk_group=1, top_k=4, scaling=1.0,
              norm_topk_prob=True, first=4, score_func="softmax")
    with jax.default_matmul_precision("highest"):
        gated, _ = moe.expert_layer(p, x, None, jnp.float32, shared=True,
                                    **kw)
        routed, _ = moe.expert_layer(p, x, None, jnp.float32, shared=False,
                                     **kw)
        plain, _ = moe.expert_layer(
            {k: v for k, v in p.items() if k != "shared_gate"}, x, None,
            jnp.float32, shared=True, **kw)
        shared = moe.swiglu(x, p["shared_in"], p["shared_out"], jnp.float32)
        gate = jax.nn.sigmoid(x @ p["shared_gate"])
    np.testing.assert_allclose(plain - routed, shared, atol=2e-6)
    np.testing.assert_allclose(gated - routed, gate * shared, atol=2e-6)
    assert 0.05 < float(gate.min()) and float(gate.max()) < 0.95
    assert float(jnp.abs(gate - 0.5).max()) > 0.1


# -- the share -----------------------------------------------------------------

def test_the_two_half_shares_add_up_to_the_uncut_layer():
    """One expert layer, 16 experts: the routed parts of the two shares
    of 8 (and of the four of 4) summed, plus the GATED shared expert
    ONCE, equal the uncut reference's whole layer."""
    whole = seeded(ref, 4, UNCUT, TAILS, layer="layer_1")
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = jax.jit(lambda p, x: ref.moe_layer(p, x, UNCUT))(
            whole, x)
        shared = jax.nn.sigmoid(x @ whole["shared_gate"]) * ref.swiglu(
            x, whole["shared_in"], whole["shared_out"])
    # ``first`` is traced: one program a share's size, not one a share
    share = jax.jit(lambda held, first: moe.expert_layer(
        held, x, None, jnp.float32, n_group=1, topk_group=1, top_k=4,
        scaling=1.0, norm_topk_prob=True, first=first, shared=False,
        score_func="softmax"))
    for count in (8, 4):
        total, rows = shared, 0
        for first in range(0, 16, count):
            held = dict(whole, experts_in=whole["experts_in"][
                first:first + count], experts_out=whole["experts_out"][
                first:first + count])
            part, per_expert = share(held, jnp.int32(first))
            total = total + part
            rows += int(per_expert.sum())
        assert rows == 40 * 4          # every choice lands on one share
        np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # one share alone is NOT the layer: what is left out is real
    assert float(jnp.abs(part + shared - want).max()) > 1e-2
    experts, weights = moe.route(x, whole["router"], None, 1, 1, 4, 1.0,
                                 score_func="softmax")
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)


# -- through the engine's normal path -------------------------------------------

@pytest.fixture(scope="module")
def engine(params, vocab):
    return InferenceEngine(params, config(), vocab, buckets=(16,),
                           batch_size=4)


def reference_rows(params, id_seqs, pad_id):
    encode = jax.jit(lambda p, t: ref.encode(p, t, MODEL)[0])
    return common.pooled_rows(encode, params, id_seqs, pad_id, 160,
                              block_rows=4)


def test_chunked_through_both_kinds_of_state_with_narrowing(
        params, engine, vocab):
    """One group of four at bucket 16: lengths 150, 9, 40 and 70: the
    batch narrows 4, 2 .. 2, 1 .. and the longest document's state is
    handed over nine times; every row is the reference's whole-document
    forward for that document alone. No branch of the engine knows the
    encoder."""
    rng = np.random.default_rng(7)
    seqs = [rng.integers(20, 300, n).astype(np.int32)
            for n in (150, 9, 40, 70)]
    got = engine.embed_ids_batch(seqs)
    assert got.shape == (4, 3 * 64) == (4, engine.embed_dim)
    want = reference_rows(params, seqs, vocab.pad_id)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=5e-5)
    _, counts = engine._embed_group_device(sorted(seqs, key=len))
    assert counts["chunks"] == 10
    assert (counts["kv_positions"], counts["kv_positions_window"]) \
        == (256, 0)
    assert counts["window_steps_run"] == 0
    # three matrix states of 4 x 16 x 16 float32, three tails of 3 x 128,
    # keys and values of 2 heads x 256 x 32 each, float32
    assert counts["state_bytes"] == 4 * (
        3 * 4 * 16 * 16 * 4 + 3 * 3 * 128 * 4 + 2 * 2 * 256 * 32 * 4)


def _traced_finalize(engine, seqs):
    """The spans of one traced ``embed_ids_batch`` call, and its
    ``engine.finalize`` among them."""
    log = []
    tracer = tracing.Tracer(max_traces=4, max_live=16)
    tracer.on_trace(log.append)
    roots = [tracer.start_span("doc") for _ in seqs]
    engine.embed_ids_batch(seqs, ctxs=[r.context for r in roots])
    for r in roots:
        r.end()
    spans = [s for t in log for s in t["spans"]]
    (fin,) = [s for s in spans if s["name"] == "engine.finalize"]
    return spans, fin["attrs"]


def test_counts_ride_the_spans(params, engine):
    rng = np.random.default_rng(11)
    seqs = [rng.integers(20, 300, n).astype(np.int32) for n in (5, 30, 40)]
    spans, a = _traced_finalize(engine, seqs)
    want = 0
    for s in seqs:
        _, chosen, _ = reference(params, jnp.asarray(s)[None])
        want += sum(int(((c >= 4) & (c < 12)).sum()) for c in chosen)
    assert a["routed_rows"] == want > 0
    assert a["moe_programs"] == 3       # chunks of 16: rows 4, 2, 2
    # what the three rules say here: the CPU, float32, sizes under a lane
    assert (a["gdn_kernel_layers"], a["attention_kernel_layers"],
            a["expert_kernel_layers"]) == (0, 0, 0)
    # two documents still going in the second program, one in the third
    assert a["gdn_state_handovers"] == 3
    # a half share of top 4: two of a token's choices land here
    assert 1 <= a["expert_rounds_mean"] <= 3
    (group,) = [s for s in spans if s["name"] == "engine.group"]
    g = group["attrs"]
    assert (g["chunks"], g["kv_positions"]) == (3, 48)
    programs = [s for s in spans if s["name"] == "engine.program"]
    assert len(programs) == 3


def test_gdn_kernel_layers_is_what_the_rule_says(params, vocab, monkeypatch):
    """The rule patched true and chunk programs of 64 tokens (one whole
    chunk of the recurrence): every linear layer of every program runs
    the interpreted kernel, the count says ``len(cfg.gdn_layers)``, and
    the rows are the XLA scan's."""
    seqs = [np.random.default_rng(12).integers(20, 300, n).astype(np.int32)
            for n in (50, 100)]

    def build():
        return InferenceEngine(params, config(), vocab, buckets=(64,),
                               batch_size=2)

    want = build().embed_ids_batch(seqs)
    monkeypatch.setattr(gdn, "core_is_kernel", lambda *a: True)
    ran = []
    real = gdn._kernel_scan
    monkeypatch.setattr(gdn, "_kernel_scan",
                        lambda *a: ran.append(a[0].shape) or real(*a))
    engine = build()
    _, a = _traced_finalize(engine, seqs)
    assert a["gdn_kernel_layers"] == len(engine.config.gdn_layers) == 3
    assert ran and len(ran) % 3 == 0
    np.testing.assert_allclose(engine.embed_ids_batch(seqs), want,
                               rtol=2e-4, atol=2e-5)


def test_a_document_past_the_cache_is_refused(engine):
    with pytest.raises(ValueError, match="kv_positions=256"):
        engine.embed_ids_batch([np.full(260, 25, np.int32)])


@pytest.mark.parametrize("scheduler", ["slots", "ragged"])
def test_other_schedulers_refuse_it_by_name(engine, scheduler):
    with pytest.raises(ValueError) as e:
        engine.embed_issues([{"title": "w1", "body": "w2"}],
                            scheduler=scheduler)
    assert scheduler in str(e.value) and "Qwen3Next" in str(e.value)


def test_the_engine_has_no_branch_for_it():
    from code_intelligence_tpu.inference import engine as module

    text = open(module.__file__).read().lower()
    assert "qwen" not in text and "gdn" not in text


# -- the contract ----------------------------------------------------------------

def test_it_satisfies_the_contract_and_counts_its_state(encoder):
    assert isinstance(encoder, ChunkEncoder)
    assert encoder.out_dim == 64
    fixed = 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    # the cache is a short document's own length and the configured
    # maximum for everything past a quarter of it
    # (`blocks.GrowingCache`); nothing attends under a window
    assert [encoder.cache_positions(n) for n in (5, 16, 64, 65, 256)] \
        == [5, 16, 64, 256, 256]
    assert encoder.cache_positions() == 256
    assert [encoder.window_positions(n) for n in (None, 5, 256)] == [0, 0, 0]
    for n in (16, 100, 256):
        assert encoder.state_bytes_per_row(n) \
            == fixed + encoder.cache_positions(n) * 2 * 2 * 32 * 4
        states = encoder.init_states(2, n)
        got = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(states))
        # less the position counter and the eight counts, which
        # ``carried_state_mb_per_row`` never counted
        assert encoder.counts.names[-3:] == (
            "gdn_kernel_layers", "attention_kernel_layers",
            "expert_kernel_layers")
        assert got - 4 - 8 * 4 == 2 * encoder.state_bytes_per_row(n)
    assert encoder.state_bytes_per_row() == encoder.state_bytes_per_row(256)
    with pytest.raises(ValueError, match="kv_positions=256"):
        encoder.cache_positions(257)
    states = encoder.init_states(2, 64)
    assert [s.dtype for s in states["gdn"]] == [jnp.float32] * 3
    assert states["k"][0].shape == states["v"][0].shape == (2, 2, 64, 32)
    # no attention layer in the cut: no cache that grows
    short = build_encoder(config(num_hidden_layers=3))
    assert short.cache_positions(100) == 0
    assert short.init_states(1, 100)["k"] == ()


def test_published_widths_carry_40_megabytes_a_row():
    """Shapes only, no weights: three (32, 128, 128) float32 matrices,
    three conv tails of 3 x 8192 and keys and values of 2 heads x 16,384
    x 256 in bfloat16."""
    published = dict(vocab_size=75968, num_hidden_layers=4, num_experts=256,
                     experts_held={"first": 0, "count": 256, "of": 512})
    enc = build_encoder(make_config("qwen3_next", published))
    cfg = enc.config
    assert (cfg.num_experts, cfg.experts_held) == (512, (0, 256))
    assert (cfg.gdn_layers, cfg.attention_layers) == ((0, 1, 2), (3,))
    assert (cfg.key_dim, cfg.value_dim, cfg.conv_dim, cfg.rotary_dim) == (
        2048, 4096, 8192, 64)
    assert enc.state_bytes_per_row(16384) == 3 * 2097152 + 3 * 49152 \
        + 33554432 == 39993344
    # the short group of the cell: 6 chunks of 512, its own length
    assert enc.cache_positions(3072) == 3072
    shapes = jax.eval_shape(lambda: enc.init_states(16, 16384))
    assert [s.shape for s in shapes["gdn"]] == [(16, 32, 128, 128)] * 3
    assert [s.shape for s in shapes["conv"]] == [(16, 3, 8192)] * 3
    assert [(s.shape, s.dtype) for s in shapes["k"] + shapes["v"]] \
        == [((16, 2, 16384, 256), jnp.bfloat16)] * 2
    # a half share of top 10 runs rounds of N, about five of them
    assert not moe.one_pass(10, 256, 512)
    assert int(moe.rounds_run(jnp.int32(5 * 8192), 8192, 10, 256, 512)) == 5
    # the whole model: twelve attention layers of 48
    assert len(dataclasses.replace(
        cfg, num_hidden_layers=48).attention_layers) == 12
    # the attention core's rule at the cell's shapes: the kernel on the
    # chip for the long group's cache, at head 256 and 8 heads a group
    assert attention.core_is_kernel("tpu", jnp.bfloat16, 512, 16384, 8, 256)
    assert attention._kernel_tiles(512, 16384, 8) == (256, 1024)
    assert not attention.core_is_kernel("cpu", jnp.bfloat16, 512, 16384, 8,
                                        256)
    # and the recurrence's: the kernel on the chip in every program of
    # the cell (16 | 32 heads of 128 | 128, chunks of 64, bucket 512)
    sizes = (512, cfg.linear_num_key_heads, cfg.linear_num_value_heads,
             cfg.linear_key_head_dim, cfg.linear_value_head_dim,
             qwen3_next._GDN_CHUNK)
    assert sizes == (512, 16, 32, 128, 128, 64)
    assert gdn.core_is_kernel("tpu", jnp.bfloat16, *sizes)
    assert not gdn.core_is_kernel("cpu", jnp.bfloat16, *sizes)


def test_config_from_the_published_keys_and_the_share():
    cfg = config()
    assert (cfg.num_experts, cfg.experts_held) == (16, (4, 8))
    assert cfg.n_moe_layers == 4 and cfg.rotary_dim == 8
    assert hash(cfg) == hash(config())
    whole = make_config("qwen3_next", {
        k: v for k, v in UNCUT.items() if k != "experts_held"})
    assert whole.experts_held == (0, 16)
    with pytest.raises(ValueError, match="not the count"):
        make_config("qwen3_next", dict(MODEL, num_experts=16))
    with pytest.raises(ValueError, match="outside the router"):
        dataclasses.replace(cfg, experts_held=(12, 8))
    with pytest.raises(ValueError, match="linear_num_key_heads"):
        dataclasses.replace(cfg, linear_num_value_heads=3)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        dataclasses.replace(cfg, norm_topk_prob=False)


@pytest.mark.parametrize("key,other", [
    ("hidden_act", "gelu"), ("use_sliding_window", True),
    ("rope_scaling", {"type": "yarn"}), ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0])])
def test_a_switch_it_implements_one_value_of_is_refused_by_name(key, other):
    with pytest.raises(ValueError, match=key):
        make_config("qwen3_next", dict(MODEL, **{key: other}))
    with pytest.raises(NotImplementedError, match=key):
        ref.dims(dict(MODEL, **{key: other}))


def test_the_table_has_an_eighth_row():
    assert type(config()) is Qwen3NextConfig
    assert len(contract.ENCODERS) >= 8 and "qwen3_next" in contract.ENCODERS
    assert contract.ENCODERS["qwen3_next"][0] is Qwen3NextConfig
    enc = build_encoder(config())
    assert isinstance(enc, Qwen3NextEncoder)
    assert isinstance(enc, ChunkEncoder)
    assert enc.state_counters(enc.init_states(1)).shape == (8,)
    assert enc.counter_attrs([]) == {}


def test_export_round_trip_in_bfloat16(tmp_path, vocab):
    from code_intelligence_tpu.training.checkpoint import export_encoder

    cfg = make_config("qwen3_next", MODEL, kv_positions=64)
    weights = seeded(ref, 1, MODEL, dtype=jnp.bfloat16)
    export_encoder(tmp_path, weights, cfg, vocab)
    eng = InferenceEngine.from_export(tmp_path, buckets=(8,), batch_size=2)
    assert eng.config == cfg and eng.encoder.dtype == jnp.bfloat16
    layer = eng._enc_params["params"]["layers"]["layer_2"]
    assert layer["A_log"].dtype == layer["dt_bias"].dtype == jnp.float32
    direct = InferenceEngine(weights, cfg, vocab, buckets=(8,), batch_size=2)
    seqs = [np.arange(20, 45, dtype=np.int32)]
    np.testing.assert_array_equal(eng.embed_ids_batch(seqs),
                                  direct.embed_ids_batch(seqs))
