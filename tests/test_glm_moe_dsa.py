"""The GLM-5 encoder (latent attention over the positions a learned indexer
selects, sigmoid-routed experts without a group step, of which this chip
holds a share) and the encoder contract's tenth large member.

Small on the CPU (hidden 32, 4 heads of 12 + 4 | 16, q rank 24, kv rank
16, 4 index heads of 16, 8 positions a query, 8 experts of which 2..5 are
held, 2 a token, 1 dense + 2 expert layers), every comparison against the
plain reference (`benchmark/reference/glm_moe_dsa.py`: ``lax.top_k`` on
its own scores, a scattered mask, one dense softmax) on seeded weights:
the encoder through chunk programs of several lengths with documents of
several ``k``; the selected SETS equal exactly; a document shorter than
``k`` equal to the same weights through ``latent_block`` without
selection; the configuration round trip; the state's bytes; the device
counts; the shares adding up to the uncut expert layer.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.reference import common
from benchmark.reference import glm_moe_dsa as ref
from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.models import (
    ChunkEncoder, GlmMoeDsaConfig, GlmMoeDsaEncoder, build_encoder,
    make_config)
from code_intelligence_tpu.models import blocks, contract
from code_intelligence_tpu.ops import dsa, mla, moe
from code_intelligence_tpu.text import SPECIALS, Vocab
from code_intelligence_tpu.utils import tracing
from encoder_programs import compiled, seeded

MODEL = {
    "vocab_size": 300, "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 4, "v_head_dim": 16, "head_dim": 4,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8,
    "rope_interleave": True, "indexer_rope_interleave": True,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "max_position_embeddings": 202752, "num_nextn_predict_layers": 1,
    "experts_held": {"first": 2, "count": 4, "of": 8}}
UNCUT = dict(MODEL, n_routed_experts=16,
             experts_held={"first": 0, "count": 16, "of": 16})
TAILS = {"dist": "student_t", "df": 4}
K = MODEL["index_topk"]


@pytest.fixture(scope="module")
def params():
    return seeded(ref, 53, MODEL, TAILS)


def config(**extra):
    return make_config("glm_moe_dsa", MODEL, **dict(
        {"kv_positions": 64, "state_dtype": jnp.float32}, **extra))


@pytest.fixture(scope="module")
def encoder(params):
    return build_encoder(config(), params)


@pytest.fixture(scope="module")
def vocab():
    return Vocab(traffic.vocab_words(SPECIALS, 300))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (3, 48), 0, 300)


def reference(params, tokens, model=MODEL):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: ref.encode(p, t, model))(params, tokens)


def through(enc, params, tokens, cuts, positions=64):
    """``tokens`` through chunk programs of the lengths ``cuts``."""
    run, states, out, at = compiled(enc), None, [], 0
    states = enc.init_states(tokens.shape[0], positions)
    with jax.default_matmul_precision("highest"):
        for n in cuts:
            h, states = run(params, tokens[:, at:at + n], states)
            out.append(h)
            at += n
    return jnp.concatenate(out, axis=1), states


# -- the encoder against the reference ---------------------------------------

@pytest.mark.parametrize("cuts", [(48,), (16, 16, 16), (8, 8, 16, 16),
                                  (4, 4, 8, 32)])
def test_chunk_programs_equal_the_reference(params, encoder, tokens, cuts):
    """48 positions are six times ``index_topk``: every program after the
    first selects, across one to four chunk programs. float32 tolerances:
    the same equations in two orders of summation (a running softmax over
    key blocks against one dense softmax; ``W_kvb`` a block at a time)
    through three layers; a selected set that differed would move a row
    by 1e-2, a thousand times the tolerance."""
    want, handed = reference(params, tokens)
    got, states = through(encoder, params, tokens, cuts)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    # what is handed on: latent rows and index keys, the rotary pairs as
    # the program lays them (first elements, then second)
    rope = MODEL["qk_rope_head_dim"]
    for i in range(3):
        w = np.asarray(handed["latent"][i])
        want_l = np.concatenate(
            [w[..., :-rope], w[..., -rope::2], w[..., -rope + 1::2]], -1)
        np.testing.assert_allclose(states["latent"][i][:, :48], want_l,
                                   rtol=1e-4, atol=2e-5)
        w = np.asarray(handed["index"][i])
        want_i = np.concatenate(
            [w[..., 0:rope:2], w[..., 1:rope:2], w[..., rope:]], -1)
        np.testing.assert_allclose(states["index"][i][:, :48], want_i,
                                   rtol=1e-4, atol=2e-5)
    assert int(states["pos"]) == 48


def test_the_selected_sets_are_the_references(params, tokens):
    """Layer 0 (both read the same stream there): the program's admitted
    mask, chunk by chunk, against the reference's ``lax.top_k`` sets."""
    p = {k: v.astype(jnp.float32)
         for k, v in params["layers"]["layer_0"].items()}
    h = jnp.take(params["embedding"], tokens, axis=0)
    u = ref.rms_norm(h, p["norm"], MODEL["rms_norm_eps"])
    c_q = ref.rms_norm(u @ p["q_a"], p["q_norm"], MODEL["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda: ref.selected(p, u, c_q, MODEL, q_block=16))()
    enc = build_encoder(config(), params)
    seen = []
    real = dsa.select

    def watched(scores, pos, k, key_block=512, lanes=None):
        out = real(scores, pos, k, key_block, lanes)
        seen.append(out[0])
        return out

    dsa.select = watched
    try:
        states = enc.init_states(3, 64)
        masks = []
        with jax.default_matmul_precision("highest"):
            for a in range(0, 48, 16):
                seen.clear()
                run = jax.jit(lambda t, s: (enc.encode(params, t, s),
                                            seen[0])[::-1])
                admit, (_, states) = run(tokens[:, a:a + 16], states)
                masks.append(admit[:, :, :48])
    finally:
        dsa.select = real
    got = np.concatenate(masks, axis=1)
    np.testing.assert_array_equal(got, np.asarray(want))
    assert got.sum(-1).tolist() == [[min(K, t + 1) for t in range(48)]] * 3


def test_a_document_shorter_than_k_is_latent_attention_without_selection(
        params):
    """7 positions against ``index_topk`` 8: the same weights through
    ``latent_block`` with no ``attend`` hook (DeepSeek-V3's program)."""
    enc = build_encoder(config(), params)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 7), 0, 300)
    with jax.default_matmul_precision("highest"):
        got, _ = compiled(enc)(params, toks, enc.init_states(2, 7))

    cfg = enc.config

    def plain(params, toks):
        h = blocks.embed(params, toks)
        for i in range(cfg.num_hidden_layers):
            p = params["layers"][f"layer_{i}"]
            out, _ = blocks.latent_block(
                p, h, jnp.zeros((2, 7, cfg.latent_dim)), jnp.int32(0),
                jnp.float32, heads=4, nope=12, rope=4, v_dim=16, rank=16,
                eps=cfg.rms_norm_eps, inv_freq=enc._inv_freq,
                rope_factor=1.0, scale=enc._scale)
            h = h + out
            u = blocks.rms_norm(h, p["ffn_norm"], cfg.rms_norm_eps)
            if i < 1:
                h = h + moe.swiglu(u, p["w_in"], p["w_out"], jnp.float32)
            else:
                y, _ = moe.expert_layer(
                    p, u.reshape(14, -1), None, jnp.float32, n_group=1,
                    topk_group=1, top_k=2, scaling=2.5, norm_topk_prob=True,
                    first=2, shared=True)
                h = h + y.reshape(2, 7, -1)
        return blocks.rms_norm(h, params["final_norm"], cfg.rms_norm_eps)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(plain)(params, toks)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# -- through the engine ------------------------------------------------------

@pytest.fixture(scope="module")
def engine(params, vocab):
    return InferenceEngine(params, config(), vocab, buckets=(8, 16),
                           batch_size=4)


def reference_rows(params, id_seqs, pad_id):
    encode = jax.jit(lambda p, t: ref.encode(p, t, MODEL)[0])
    return common.pooled_rows(encode, params, id_seqs, pad_id, 64,
                              block_rows=4)


def test_chunked_through_both_caches_with_narrowing(params, engine, vocab):
    """One group of four at bucket 16: lengths 5 (under ``k``), 20, 37
    and 60 (7.5 times ``k``, four chunk programs): the batch narrows 4,
    4, 2, 1; every row is the reference's whole-document forward for that
    document alone. A second group of short documents runs one program a
    bucket."""
    rng = np.random.default_rng(7)
    seqs = [rng.integers(20, 300, n).astype(np.int32)
            for n in (60, 5, 20, 37)]
    got = engine.embed_ids_batch(seqs)
    assert got.shape == (4, 3 * 32) == (4, engine.embed_dim)
    want = reference_rows(params, seqs, vocab.pad_id)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    _, counts = engine._embed_group_device(sorted(seqs, key=len))
    assert counts["chunks"] == 4
    assert counts["lane_steps_run"] == (4 + 4 + 2 + 1) * 16
    assert counts["kv_positions"] == 64 and counts["kv_positions_window"] == 0
    # two caches of 16 + 4 and 16 wide, 3 layers, float32
    assert counts["state_bytes"] == 4 * 3 * 64 * 36 * 4
    short = [rng.integers(20, 300, n).astype(np.int32) for n in (3, 7, 12)]
    np.testing.assert_allclose(
        engine.embed_ids_batch(short),
        reference_rows(params, short, vocab.pad_id), rtol=1e-4, atol=2e-5)


def test_counts_ride_the_finalize_span(params, engine):
    """What the selection met, counted on the device over valid lanes and
    summed over the three layers, against the arithmetic: a query at
    ``t`` is scored against ``t + 1`` positions and attends ``min(k, t +
    1)``, where the engine's ``cache_steps_run`` counts positions
    reached."""
    rng = np.random.default_rng(11)
    lengths = (5, 12, 20, 45)
    seqs = [rng.integers(20, 300, n).astype(np.int32) for n in lengths]
    tracer = tracing.Tracer(max_traces=4, max_live=16)
    seen = []
    tracer.on_trace(seen.append)
    roots = [tracer.start_span("doc") for _ in seqs]
    engine.embed_ids_batch(seqs, ctxs=[r.context for r in roots])
    for r in roots:
        r.end()
    spans = [s for t in seen for s in t["spans"]
             if s["name"] == "engine.finalize"]
    attrs = spans[0]["attrs"]
    assert attrs["dsa_pairs_scored"] == 3 * sum(
        n * (n + 1) // 2 for n in lengths)
    assert attrs["dsa_pairs_selected"] == 3 * sum(
        min(K, t + 1) for n in lengths for t in range(n))
    assert attrs["dsa_threshold_ties"] >= 0
    assert attrs["dsa_kernel_layers"] == attrs["expert_kernel_layers"] == 0
    assert attrs["moe_programs"] == 3 and attrs["expert_rounds_mean"] >= 1
    assert not {k for k in attrs if k.endswith("_x65536")}
    group = next(s for t in seen for s in t["spans"]
                 if s["name"] == "engine.group")["attrs"]
    # an upper bound for what this core admits (PERF.md section 7)
    assert group["cache_steps_run"] * 3 > attrs["dsa_pairs_selected"] / 16


def test_the_wide_counts_pass_an_int32():
    """Two slots a pair count, ``n % 65536`` and ``n // 65536`` of every
    program's own count: 40 programs of 130 M pairs sum past 2**31 and
    come back whole."""
    enc = GlmMoeDsaEncoder(config())
    n = 130_000_001
    counts = enc.counts.zeros()
    zero = jnp.zeros((), jnp.int32)
    for _ in range(40):
        counts = enc.counts.update(
            counts, zero, zero, jnp.int32(1), expert_rounds=zero,
            dsa_pairs_scored=jnp.int32(n % 65536),
            dsa_pairs_scored_x65536=jnp.int32(n // 65536),
            dsa_pairs_selected=zero, dsa_pairs_selected_x65536=zero,
            dsa_threshold_ties=zero, expert_kernel_layers=0,
            dsa_kernel_layers=0)
    attrs = enc.counter_attrs([np.asarray(counts)])
    assert attrs["dsa_pairs_scored"] == 40 * n > 2 ** 31
    assert attrs["dsa_pairs_selected"] == 0


def test_a_document_past_the_caches_is_refused(engine):
    with pytest.raises(ValueError, match="latent and index-key cache"):
        engine.embed_ids_batch([np.arange(20, 90, dtype=np.int32)])


# -- the contract ------------------------------------------------------------

def test_it_satisfies_the_contract_and_counts_its_state(encoder):
    assert isinstance(encoder, ChunkEncoder)
    assert encoder.out_dim == 32
    # 3 layers x positions x ((16 + 4) + 16) float32
    assert encoder.state_bytes_per_row(16) == 3 * 16 * 36 * 4
    assert encoder.state_bytes_per_row(17) == \
        encoder.state_bytes_per_row() == 3 * 64 * 36 * 4
    assert encoder.cache_positions(16) == 16
    assert encoder.cache_positions(17) == encoder.cache_positions() == 64
    assert encoder.window_positions(64) == 0
    states = encoder.init_states(2, 16)
    got = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(states))
    counts = len(encoder.counts.names)
    assert counts == 3 + 1 + 5 + 2
    assert got - 4 - counts * 4 == 2 * encoder.state_bytes_per_row(16)
    assert [c.shape[-1] for c in (states["latent"][0], states["index"][0])] \
        == [20, 16]


def test_published_sizes_carry_231_megabytes_a_row():
    published = dict(
        vocab_size=19360, num_hidden_layers=5, first_k_dense_replace=1,
        experts_held={"first": 0, "count": 16, "of": 256},
        n_routed_experts=16,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"})
    enc = build_encoder(make_config("glm_moe_dsa", published,
                                    kv_positions=32768))
    cfg = enc.config
    assert (cfg.n_routed_experts, cfg.experts_held) == (256, (0, 16))
    assert (cfg.latent_dim, cfg.q_head_dim, cfg.index_head_dim) \
        == (576, 256, 128)
    assert cfg.rope_theta == 1e6 and enc._scale == 256 ** -0.5
    assert enc.state_bytes_per_row(32768) == 5 * 32768 * 1408 == 230686720
    assert enc.state_bytes_per_row(7168) == 5 * 7168 * 1408


def test_config_from_the_published_keys_and_the_share():
    cfg = config()
    assert (cfg.n_routed_experts, cfg.experts_held) == (8, (2, 4))
    assert cfg.index_topk == 8 and hash(cfg) == hash(config())
    assert type(cfg) is GlmMoeDsaConfig and cfg.architecture == "glm_moe_dsa"
    assert "glm_moe_dsa" in contract.ENCODERS
    with pytest.raises(ValueError, match="not the count"):
        make_config("glm_moe_dsa", dict(MODEL, n_routed_experts=16))
    with pytest.raises(ValueError, match="rope_type"):
        make_config("glm_moe_dsa", dict(MODEL, rope_parameters={
            "rope_theta": 1e6, "rope_type": "yarn"}))
    with pytest.raises(ValueError, match="interleaved"):
        dataclasses.replace(cfg, indexer_rope_interleave=False)
    with pytest.raises(ValueError, match="sigmoid"):
        dataclasses.replace(cfg, scoring_func="softmax")


def test_the_benchmarks_configuration_file_round_trips():
    import json
    from pathlib import Path

    model = json.loads((Path(__file__).parents[1] / "benchmark" / "configs"
                        / "glm_5_ep16_share.json").read_text())
    cfg = make_config(model["architecture"], model, kv_positions=32768,
                      state_dtype=jnp.bfloat16)
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.n_routed_experts, cfg.experts_held, cfg.vocab_size) \
        == (5, 1, 256, (0, 16), 19360)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.n_group, cfg.topk_group) == (32, 128, 2048, 1, 1)
    enc = build_encoder(cfg)
    assert isinstance(enc, GlmMoeDsaEncoder) and enc.out_dim == 6144


def test_export_round_trip_in_bfloat16(tmp_path, vocab):
    from code_intelligence_tpu.training.checkpoint import export_encoder

    cfg = make_config("glm_moe_dsa", MODEL, kv_positions=64)
    weights = seeded(ref, 1, MODEL, dtype=jnp.bfloat16)
    export_encoder(tmp_path, weights, cfg, vocab)
    eng = InferenceEngine.from_export(tmp_path, buckets=(8, 16),
                                      batch_size=2)
    assert eng.config == cfg and eng.encoder.dtype == jnp.bfloat16
    direct = InferenceEngine(weights, cfg, vocab, buckets=(8, 16),
                             batch_size=2)
    seqs = [np.arange(20, 65, dtype=np.int32)]
    np.testing.assert_array_equal(eng.embed_ids_batch(seqs),
                                  direct.embed_ids_batch(seqs))


# -- the share ---------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """One expert layer, 16 experts and no group step: the routed parts
    of all 16 shares of one expert each (and of the four of 4) summed,
    plus the shared expert ONCE, equal the uncut reference's whole
    layer."""
    whole = seeded(ref, 4, UNCUT, TAILS, layer="layer_1")
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 32))
    with jax.default_matmul_precision("highest"):
        want, chosen = jax.jit(lambda p, x: ref.moe_layer(p, x, UNCUT))(
            whole, x)
        shared = ref.swiglu(x, whole["shared_in"].astype(jnp.float32),
                            whole["shared_out"].astype(jnp.float32))
    experts, weights = moe.route(
        x, whole["router"], whole["bias"], 1, 1, 2, 2.5)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    # ``first`` is traced: one program a share's size, not one a share
    share = jax.jit(lambda w_in, w_out, first: moe.routed_experts(
        x, experts, weights, w_in, w_out, first, 16))
    for count in (1, 4):
        total, rows = shared, 0
        for first in range(0, 16, count):
            part, per_expert = share(
                whole["experts_in"][first:first + count],
                whole["experts_out"][first:first + count], jnp.int32(first))
            total = total + part
            rows += int(per_expert.sum())
        assert rows == 40 * 2          # every choice lands on one share
        np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # one share alone is NOT the layer: what is left out is real
    assert float(jnp.abs(part + shared - want).max()) > 1e-2


def test_the_bias_moves_the_choice_and_not_the_weight():
    """No group step: the choice is the top 2 of ``sigmoid + bias`` over
    all 8 outputs, the weights the unbiased scores of the chosen,
    normalised and times 2.5."""
    x = jax.random.normal(jax.random.PRNGKey(9), (12, 32))
    w = jax.random.normal(jax.random.PRNGKey(10), (32, 8)) / 6
    bias = jnp.zeros((8,)).at[3].set(10.0)
    experts, weights = moe.route(x, w, bias, 1, 1, 2, 2.5)
    assert (np.asarray(experts) == 3).any(axis=1).all()
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST)))
    picked = np.take_along_axis(scores, np.asarray(experts), axis=1)
    np.testing.assert_allclose(
        weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
