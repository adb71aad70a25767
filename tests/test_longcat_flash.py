"""The LongCat-Flash encoder (shortcut-connected experts: two latent
attention sublayers and two dense FFNs a layer, the routed branch made
after the first attention and added after the second FFN; a softmax
router a third of whose outputs are identity experts) and the encoder
contract's seventh member.

Small on the CPU (hidden 64, 4 heads, q rank 24, kv rank 16, head sizes
8 | 4 | 8, dense FFN 96, experts of 32; 2 layers = 4 sublayers; a router
of 32 + 16 outputs, 4 a token, experts 8..11 held), every comparison
against the plain reference (`benchmark/reference/longcat_flash.py`) on
seeded weights: the encoder whole and through chunk programs; the 32
shares adding up to the uncut branch with the identity part counted
once; the router's third score on a hand-worked table; tokens all of
whose choices are identity experts, or all held; identity choices
behind the held experts in the sort; what the comparison must SEE (the branch
added early, the multipliers left out, a dropped cache, the identity
experts left out); the contract's numbers at the published sizes.
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.reference import common
from benchmark.reference import longcat_flash as ref
from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.models import (
    ChunkEncoder, LongcatFlashConfig, LongcatFlashEncoder, build_encoder,
    make_config)
from code_intelligence_tpu.models import blocks, contract
from code_intelligence_tpu.ops import mla, moe
from code_intelligence_tpu.text import SPECIALS, Vocab
from code_intelligence_tpu.utils import tracing
from encoder_programs import (
    compiled, seeded, the_rule_says_grouped_kernels)

MODEL = {
    "vocab_size": 300, "hidden_size": 64, "ffn_hidden_size": 96,
    "expert_ffn_hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "n_routed_experts": 4, "zero_expert_num": 16,
    "zero_expert_type": "identity", "moe_topk": 4,
    "routed_scaling_factor": 6, "attention_method": "MLA",
    "attention_bias": False, "rms_norm_eps": 1e-5, "rope_theta": 10000000,
    "max_position_embeddings": 131072,
    "experts_held": {"first": 8, "count": 4, "of": 32}}
UNCUT = dict(MODEL, n_routed_experts=32,
             experts_held={"first": 0, "count": 32, "of": 32})
TAILS = {"dist": "student_t", "df": 4}


@pytest.fixture(scope="module")
def params():
    return seeded(ref, 43, MODEL, TAILS)


def config(model=MODEL, **extra):
    return make_config("longcat_flash", model, **dict(
        {"kv_positions": 64, "state_dtype": jnp.float32}, **extra))


@pytest.fixture(scope="module")
def encoder(params):
    return build_encoder(config(), params)


@pytest.fixture(scope="module")
def vocab():
    return Vocab(traffic.vocab_words(SPECIALS, 300))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (3, 24), 0, 300)


def reference(params, tokens, model=MODEL):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: ref.encode(p, t, model))(params, tokens)


def route(x, p, top_k=4, scaling=6.0):
    return moe.route(x, p["router"], p["bias"], 1, 1, top_k, scaling,
                     norm_topk_prob=False, score_func="softmax_all")


# -- ops: the router's third score -------------------------------------------

def test_the_third_score_on_a_hand_worked_table():
    """Four outputs, two a token. Logits ln 1, ln 2, ln 3, ln 4: the
    softmax over ALL is 0.1, 0.2, 0.3, 0.4. Without a bias the choice is
    outputs 3 and 2 at 0.4 and 0.3; a bias of +0.25 on output 0 makes
    its 0.35 pass 0.3: the choice moves, the weight of output 0 stays
    its unbiased 0.1. Nothing is renormalised (0.4 + 0.1 is not 1), and
    the factor 6 multiplies each."""
    x = jnp.eye(1, 4)
    w = jnp.zeros((4, 4)).at[0].set(jnp.log(jnp.arange(1.0, 5.0)))
    experts, weights = moe.route(x, w, None, 1, 1, 2, 6.0,
                                 norm_topk_prob=False,
                                 score_func="softmax_all")
    assert experts.tolist() == [[3, 2]]
    np.testing.assert_allclose(weights, [[2.4, 1.8]], rtol=1e-6)
    bias = jnp.array([0.25, 0.0, 0.0, 0.0])
    experts, weights = moe.route(x, w, bias, 1, 1, 2, 6.0,
                                 norm_topk_prob=False,
                                 score_func="softmax_all")
    assert experts.tolist() == [[3, 0]]
    np.testing.assert_allclose(weights, [[2.4, 0.6]], rtol=1e-6)
    # normalised over the chosen, where a model says so
    _, normed = moe.route(x, w, bias, 1, 1, 2, 6.0, norm_topk_prob=True,
                          score_func="softmax_all")
    np.testing.assert_allclose(normed, [[4.8, 1.2]], rtol=1e-6)
    with pytest.raises(ValueError, match="softmax_all"):
        moe.route(x, w, None, 1, 1, 2, 1.0, score_func="tanh")


def test_the_seeded_bias_moves_some_choices_and_not_all():
    """``e_score_correction_bias`` ~ N(0, (0.25 / 48)^2) against scores
    of about 1 / 48: over 400 tokens it changes a token's set of experts
    for some tokens and leaves it for others (DeepSeek's N(0, 0.02)
    here would pick one set for every token); where the set is the same
    the weights are the same: the bias weighs nothing."""
    p = seeded(ref, 3, MODEL, TAILS, layer="layer_0")
    x = jax.random.normal(jax.random.PRNGKey(4), (400, 64))
    with_bias, w_with = route(x, p)
    without, w_without = route(x, dict(p, bias=None))
    same = (np.sort(with_bias, -1) == np.sort(without, -1)).all(-1)
    assert 0.1 < same.mean() < 0.9, same.mean()
    np.testing.assert_allclose(np.sort(w_with, -1)[same],
                               np.sort(w_without, -1)[same], rtol=1e-6)
    # the reference's choice and weights, token by token
    with jax.default_matmul_precision("highest"):
        r_experts, r_weights, scores = jax.jit(
            lambda x: ref.route(x, p["router"], p["bias"], MODEL))(x)
    np.testing.assert_array_equal(np.sort(with_bias, -1),
                                  np.sort(r_experts, -1))
    np.testing.assert_allclose(np.sort(w_with, -1), np.sort(r_weights, -1),
                               rtol=1e-5)
    # the unbiased scores of the chosen times 6: a token's weights sum to
    # 6 x the softmax mass it chose, under 6 and never 1 by construction
    np.testing.assert_allclose(
        w_with, 6 * jnp.take_along_axis(scores, with_bias, axis=-1),
        rtol=1e-5)
    total = np.asarray(w_with).sum(-1)
    assert (total < 6).all() and (np.abs(total - 1) > 1e-3).all()
    # a bias as far above the scores as N(0, 0.02) is above 1 / 768
    # picks nearly one set for every token
    loud, _ = route(x, dict(p, bias=p["bias"] * (0.02 * 768 / 0.25)))
    assert len({tuple(sorted(row)) for row in loud.tolist()}) < 40


# -- ops: identity experts, the held part, the share -------------------------

def test_the_shares_add_up_to_the_uncut_branch():
    """One layer's branch, 32 experts with weights and 16 without: the
    held parts of the 32 shares of one expert (and of the 8 of four, and
    of the 2 of sixteen) summed, plus the identity part ONCE, equal the
    uncut reference's ``m``."""
    whole = seeded(ref, 4, UNCUT, TAILS, layer="layer_1")
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = jax.jit(lambda p, x: ref.moe(p, x, UNCUT))(whole, x)
    experts, weights = route(x, whole)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    identity, zero_choices = moe.zero_experts(x, experts, weights, 32)
    assert int(zero_choices) == int((np.asarray(chosen) >= 32).sum()) > 0
    # ``first`` is traced: one program a share's size, not one a share
    share = jax.jit(lambda w_in, w_out, first: moe.routed_experts(
        x, experts, weights, w_in, w_out, first, 48))
    for count in (1, 4, 16):
        total, rows = identity, 0
        for first in range(0, 32, count):
            part, per_expert = share(
                whole["experts_in"][first:first + count],
                whole["experts_out"][first:first + count], jnp.int32(first))
            total = total + part
            rows += int(per_expert.sum())
        # every choice lands on one share or on an identity expert
        assert rows + int(zero_choices) == 40 * 4
        np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # one share alone is NOT the branch, nor is the branch without the
    # identity part: what is left out is real
    assert float(jnp.abs(part + identity - want).max()) > 1e-2
    assert float(jnp.abs(total - identity - want).max()) > 1e-2


def test_a_token_of_identity_experts_alone_routes_no_row():
    """A bias that sends all four choices of every token to identity
    experts: ``m = (sum w) u``, no row reaches a held expert, and the
    rounds' loop does not turn."""
    p = seeded(ref, 6, MODEL, TAILS, layer="layer_0")
    bias = jnp.full((48,), -1.0).at[32:].set(1.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    experts, weights = route(x, dict(p, bias=bias))
    assert (np.asarray(experts) >= 32).all()
    got, per_expert = jax.jit(lambda x, e, w: moe.routed_experts(
        x, e, w, p["experts_in"], p["experts_out"], 8, 48))(
            x, experts, weights)
    assert per_expert.tolist() == [0, 0, 0, 0]
    assert float(jnp.abs(got).max()) == 0.0
    z, choices = moe.zero_experts(x, experts, weights, 32)
    np.testing.assert_allclose(z, weights.sum(-1, keepdims=True) * x,
                               rtol=1e-6)
    assert int(choices) == 24 * 4
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(dict(p, bias=bias), x, MODEL)
    np.testing.assert_allclose(got + z, want, rtol=2e-5, atol=2e-5)
    # padding lanes are given no expert of either kind
    valid = jnp.arange(24) < 17
    z, choices = moe.zero_experts(x, experts, weights, 32, valid)
    assert int(choices) == 17 * 4 and float(jnp.abs(z[17:]).max()) == 0.0


@pytest.mark.parametrize("lanes", [24, 17], ids=["all_valid", "padded"])
def test_no_token_is_dropped_when_every_choice_is_held(lanes):
    """A bias that sends all four choices of every token to the four
    held experts: 4 x 24 assignments through four rounds of 24 rows, or,
    with seven padding lanes left out, 4 x 17 through three rounds that
    cut an expert's rows in two; equal to the reference's dense loop."""
    p = seeded(ref, 6, MODEL, TAILS, layer="layer_0")
    bias = jnp.full((48,), -1.0).at[8:12].set(1.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(dict(p, bias=bias), x, MODEL)
    experts, weights = route(x, dict(p, bias=bias))
    assert sorted(set(np.asarray(experts).ravel())) == [8, 9, 10, 11]
    got, per_expert = jax.jit(lambda x, e, w, valid: moe.routed_experts(
        x, e, w, p["experts_in"], p["experts_out"], 8, 48, valid))(
            x, experts, weights, jnp.arange(24) < lanes)
    assert per_expert.tolist() == [lanes] * 4
    np.testing.assert_allclose(got[:lanes], want[:lanes], rtol=2e-5,
                               atol=2e-5)
    assert lanes == 24 or float(jnp.abs(got[lanes:]).max()) == 0.0


def test_identity_choices_sort_behind_the_held_experts():
    """A choice at or past ``n_routed`` is to ``routed_experts`` what an
    absent chip's is: behind the last held row of the sort, in no
    expert's count, and the held part is the same whichever expert not
    held here it names. The program is PR 41's: the ``top_k x N`` buffer
    and no scatter."""
    p = seeded(ref, 8, MODEL, TAILS, layer="layer_1")
    x = jax.random.normal(jax.random.PRNGKey(9), (512, 64))
    experts, weights = route(x, p)
    zero = np.asarray(experts) >= 32
    assert zero.any() and not zero.all()
    order, per_expert = moe.assign(experts, 8, 4)
    landed = int(per_expert.sum())
    assert 0 < landed < 512
    assert not zero.ravel()[np.asarray(order)[:landed]].any()
    held = jax.jit(lambda x, e, w: moe.routed_experts(
        x, e, w, p["experts_in"], p["experts_out"], 8, 48))
    text = held.lower(x, experts, weights).as_text()
    assert "2048x64xf32" in text and "scatter" not in text
    got, rows = held(x, experts, weights)
    elsewhere, rows_e = held(x, jnp.where(zero, 0, experts), weights)
    assert rows.tolist() == rows_e.tolist() == per_expert.tolist()
    np.testing.assert_array_equal(got, elsewhere)


# -- the encoder against the reference ---------------------------------------

def test_encoder_equals_the_reference(params, tokens):
    enc = LongcatFlashEncoder(config(), jnp.float32)
    want, _ = reference(params, tokens)
    got, states = jax.jit(enc.encode)(params, tokens, enc.init_states(3, 24))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert int(states["pos"]) == 24
    assert len(states["latent"]) == 4  # two caches a layer


def test_the_encoder_on_the_grouped_matmul_kernels_equals_the_reference(
        monkeypatch, params, tokens):
    """Every expert layer's two grouped products through ``ops/gmm.py``'s
    kernels (interpreted), and the count says two layers."""
    the_rule_says_grouped_kernels(monkeypatch)
    enc = build_encoder(config(), params)
    want, _ = reference(params, tokens)
    got, states = jax.jit(enc.encode)(params, tokens, enc.init_states(3, 24))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert enc.counter_attrs([np.asarray(states["counts"])])[
        "expert_kernel_layers"] == 2


def test_the_uncut_encoder_equals_the_uncut_reference(tokens):
    """All 32 experts with weights held, of a router 48 wide: the buffer
    form, several rounds a layer."""
    whole = seeded(ref, 44, UNCUT, TAILS)
    enc = build_encoder(config(UNCUT), whole)
    assert enc.config.experts_held == (0, 32)
    want, _ = reference(whole, tokens, UNCUT)
    got, states = compiled(enc)(whole, tokens, enc.init_states(3, 24))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    counts = dict(zip(enc.counts.names, np.asarray(states["counts"])))
    assert not moe.one_pass(4, 32, 48)  # 2.7 of a token's 4 land here
    assert counts["expert_rounds"] > 2  # more than one round a layer


@pytest.mark.parametrize("cuts", [(8, 16), (5, 6, 20), (16,)])
def test_one_program_equals_chunk_programs(params, encoder, tokens, cuts):
    want, _ = reference(params, tokens)
    states = encoder.init_states(3, 64)
    outs, lo = [], 0
    for hi in cuts + (24,):
        out, states = compiled(encoder)(params, tokens[:, lo:hi], states)
        outs.append(out)
        lo = hi
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), want,
                               rtol=2e-5, atol=2e-5)
    assert int(states["pos"]) == 24


def test_a_dropped_cache_is_seen(params, encoder, tokens):
    want, _ = reference(params, tokens)
    step = compiled(encoder)
    _, states = step(params, tokens[:, :16], encoder.init_states(3, 64))
    fresh = dict(encoder.init_states(3, 64), pos=states["pos"])
    dropped, _ = step(params, tokens[:, 16:], fresh)
    kept, _ = step(params, tokens[:, 16:], states)
    np.testing.assert_allclose(kept, want[:, 16:], rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(dropped - want[:, 16:]).max()) > 0.05
    # ONE of the four caches dropped (the second sublayer's of layer 1)
    one = dict(states, latent=states["latent"][:3] + (
        jnp.zeros_like(states["latent"][3]),))
    partly, _ = step(params, tokens[:, 16:], one)
    assert float(jnp.abs(partly - want[:, 16:]).max()) > 0.01


def _rel_rms(got, want):
    return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                          / jnp.mean(want ** 2)))


def test_the_multipliers_left_out_are_seen(params, tokens):
    want, _ = reference(params, tokens)
    for off in ({"mla_scale_q_lora": False}, {"mla_scale_kv_lora": False}):
        enc = build_encoder(config(dict(MODEL, **off)), params)
        got, _ = compiled(enc)(params, tokens, enc.init_states(3, 24))
        assert _rel_rms(got, want) > 0.02, off
        # the reference without it is the program without it
        without, _ = reference(params, tokens, dict(MODEL, **off))
        np.testing.assert_allclose(got, without, rtol=2e-5, atol=2e-5)


def test_the_identity_experts_left_out_are_seen(monkeypatch, params,
                                                tokens):
    want, _ = reference(params, tokens)
    monkeypatch.setattr(moe, "zero_experts", lambda x, *a, **kw: (
        jnp.zeros_like(x), jnp.zeros((), jnp.int32)))
    enc = build_encoder(config(), params)  # traced after the patch
    got, _ = jax.jit(enc.encode)(params, tokens, enc.init_states(3, 24))
    assert _rel_rms(got, want) > 0.02


def test_the_branch_added_early_is_seen(monkeypatch, params, tokens):
    """``m`` added with the FIRST dense FFN (before the second attention
    reads the stream) instead of after the second: the sequential
    placement every other expert model here has. The second sublayer
    then reads another stream, and the comparison sees it."""
    want, _ = reference(params, tokens)
    held = []
    real_moe, real_swiglu = LongcatFlashEncoder._moe, moe.swiglu

    def branch(self, p, u, valid):
        m, per_expert, zeros = real_moe(self, p, u, valid)
        held.append(m)
        return jnp.zeros_like(m), per_expert, zeros

    def first_ffn_takes_it(x, *a, **kw):
        out = real_swiglu(x, *a, **kw)
        return out + held.pop().reshape(out.shape) if held else out

    monkeypatch.setattr(LongcatFlashEncoder, "_moe", branch)
    monkeypatch.setattr(moe, "swiglu", first_ffn_takes_it)
    enc = build_encoder(config(), params)
    got, _ = jax.jit(enc.encode)(params, tokens, enc.init_states(3, 24))
    assert not held
    assert _rel_rms(got, want) > 0.01
    # the last layer's early m changes what ITS second sublayer read,
    # nothing else: a one-layer model shows the placement alone
    one = dict(MODEL, num_layers=1)
    p1 = seeded(ref, 45, one, TAILS)
    enc = build_encoder(config(one), p1)
    got, _ = jax.jit(enc.encode)(p1, tokens, enc.init_states(3, 24))
    assert _rel_rms(got, reference(p1, tokens, one)[0]) > 0.01


def _chosen_by_the_program(monkeypatch, enc, params, tokens):
    """The experts every layer's router picked, run eagerly with
    ``moe.route`` listened to."""
    seen = []
    real = moe.route

    def listening(*a, **kw):
        experts, weights = real(*a, **kw)
        seen.append(np.asarray(experts))
        return experts, weights

    monkeypatch.setattr(moe, "route", listening)
    enc.encode(params, tokens, enc.init_states(*tokens.shape))
    return seen


def test_float32_routing_is_the_references(monkeypatch, params, encoder,
                                           tokens):
    _, want = reference(params, tokens)
    got = _chosen_by_the_program(monkeypatch, encoder, params, tokens)
    assert len(got) == len(want) == 2
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.sort(g, -1), np.sort(w, -1))


def test_bfloat16_flips_few_assignments(monkeypatch, params):
    """bfloat16 weights and matmul inputs against the float32 reference
    over the same (bfloat16-valued) weights: top-k is discrete, so a
    near-tie can go the other way; few do."""
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                        if a.ndim > 1 else a, params)
    toks = jax.random.randint(jax.random.PRNGKey(9), (8, 72), 0, 300)
    _, want = reference(jax.tree.map(lambda a: a.astype(jnp.float32), half),
                        toks)
    enc = build_encoder(config(state_dtype=jnp.bfloat16, kv_positions=128),
                        half)
    assert enc.dtype == jnp.bfloat16
    got = _chosen_by_the_program(monkeypatch, enc, half, toks)
    total = flipped = 0
    for g, w in zip(got, want):
        same = (np.sort(g, -1) == np.sort(np.asarray(w), -1)).all(-1)
        flipped += sum(len(set(a) - set(b)) for a, b in zip(
            g[~same].tolist(), np.asarray(w)[~same].tolist()))
        total += g.size
    assert 0 < flipped / total < 0.08, flipped / total


def test_the_program_names_its_parts(params, encoder, tokens):
    """The published ``layer_idx`` of a sublayer names its attention and
    its dense FFN; the branch is ``moe_<layer>`` with ``zero_experts``
    beside the routed experts' three."""
    text = jax.jit(encoder.encode).lower(
        params, tokens, encoder.init_states(3, 24)).as_text(debug_info=True)
    paths = [set(re.split(r"[/()]", path))
             for path in re.findall(r'"(jit\([^"]*)"', text)]
    names = set().union(*paths)
    assert {"embedding", "final_norm", "mla_core", *(
        f"{kind}_{i}" for kind in ("attention", "mlp")
        for i in range(4))} <= names
    for i in range(2):
        for part in ("router", "dispatch", "experts", "combine",
                     "zero_experts"):
            assert any({f"moe_{i}", part} <= path for path in paths), (
                i, part)
    assert not {"moe_2", "attention_4", "mlp_4"} & names


# -- through the engine's normal path ----------------------------------------

@pytest.fixture(scope="module")
def engine(params, vocab):
    return InferenceEngine(params, config(), vocab, buckets=(8,),
                           batch_size=4)


def reference_rows(params, id_seqs, pad_id):
    encode = jax.jit(lambda p, t: ref.encode(p, t, MODEL)[0])
    return common.pooled_rows(encode, params, id_seqs, pad_id, 32,
                              block_rows=4)


def test_chunked_through_the_caches_with_narrowing(params, engine, vocab):
    """One group of four at bucket 8: lengths 3 (ends in the first
    chunk), 9 (one token into the second), 17 and 24 (three chunks): the
    batch narrows 4, 4, 2 through all four caches; every row is the
    reference's whole-document forward for that document alone."""
    rng = np.random.default_rng(7)
    seqs = [rng.integers(20, 300, n).astype(np.int32)
            for n in (24, 3, 9, 17)]
    got = engine.embed_ids_batch(seqs)
    assert got.shape == (4, 3 * 64) == (4, engine.embed_dim)
    want = reference_rows(params, seqs, vocab.pad_id)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    _, counts = engine._embed_group_device(sorted(seqs, key=len))
    assert counts["chunks"] == 3 and counts["lane_steps_run"] == 10 * 8
    assert counts["kv_positions"] == 64
    # 4 rows x 4 caches x 64 positions x (16 + 4) float32
    assert counts["state_bytes"] == 4 * 4 * 64 * 20 * 4


def test_counts_ride_the_finalize_span(params, engine):
    """``routed_rows`` and ``zero_choices`` as the reference's choices
    count them for the valid tokens (padding lanes are given no expert),
    ``valid_choices`` 4 a valid token a layer, the sublayers on the
    kernel 0 here (the rule sees the CPU)."""
    rng = np.random.default_rng(11)
    seqs = [rng.integers(20, 300, n).astype(np.int32) for n in (5, 12, 20)]
    log = []
    tracer = tracing.Tracer(max_traces=4, max_live=16)
    tracer.on_trace(log.append)
    roots = [tracer.start_span("doc") for _ in seqs]
    engine.embed_ids_batch(seqs, ctxs=[r.context for r in roots])
    for r in roots:
        r.end()
    spans = [s for t in log for s in t["spans"]]
    (fin,) = [s for s in spans if s["name"] == "engine.finalize"]
    held = zero = 0
    for s in seqs:
        _, chosen = reference(params, jnp.asarray(s)[None])
        held += sum(int(((c >= 8) & (c < 12)).sum()) for c in chosen)
        zero += sum(int((c >= 32).sum()) for c in chosen)
    a = fin["attrs"]
    assert a["routed_rows"] == held > 0
    assert a["zero_choices"] == zero > 0
    assert a["valid_choices"] == (5 + 12 + 20) * 4 * 2
    assert a["moe_programs"] == 3       # chunks of 8: rows 4, 4, 2
    assert a["expert_rows_mean"] == pytest.approx(held / (3 * 2 * 4))
    assert 0 < a["expert_rounds_mean"] <= 1  # a thin share: a round at most
    assert a["attention_kernel_layers"] == 0
    assert engine.encoder.counter_attrs([]) == {}


def test_a_document_past_the_cache_is_refused(engine):
    with pytest.raises(ValueError, match="kv_positions=64"):
        engine.embed_ids_batch([np.full(70, 25, np.int32)])


@pytest.mark.parametrize("scheduler", ["slots", "ragged"])
def test_other_schedulers_refuse_it_by_name(engine, scheduler):
    with pytest.raises(ValueError) as e:
        engine.embed_issues([{"title": "w1", "body": "w2"}],
                            scheduler=scheduler)
    assert scheduler in str(e.value) and "LongcatFlash" in str(e.value)


# -- the contract ------------------------------------------------------------

def test_it_satisfies_the_contract_and_counts_its_state(encoder):
    assert isinstance(encoder, ChunkEncoder)
    assert isinstance(encoder, blocks.GrowingCache)
    assert encoder.out_dim == 64
    # 4 sublayers x positions x (16 + 4) float32
    assert encoder.state_bytes_per_row(16) == 4 * 16 * 20 * 4
    assert encoder.state_bytes_per_row(17) == \
        encoder.state_bytes_per_row() == 4 * 64 * 20 * 4
    assert encoder.cache_positions(16) == 16
    assert encoder.cache_positions(17) == encoder.cache_positions() == 64
    assert encoder.window_positions(64) == 0
    states = encoder.init_states(2, 16)
    got = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(states))
    assert got - 4 - 8 * 4 == 2 * encoder.state_bytes_per_row(16)
    with pytest.raises(ValueError, match="kv_positions=64"):
        encoder.cache_positions(65)


def test_published_sizes_carry_151_megabytes_a_row():
    """The cell's configuration: 4 layers = 8 latent caches of 576 a
    position in bfloat16; the multipliers 2 and sqrt(12); plain rotary at
    theta 1e7; and the Pallas latent core's rule takes its shapes."""
    published = dict(
        vocab_size=16384, num_layers=4, n_routed_experts=16,
        experts_held={"first": 0, "count": 16, "of": 512})
    enc = build_encoder(make_config("longcat_flash", published,
                                    kv_positions=16384))
    cfg = enc.config
    assert (cfg.n_routed_experts, cfg.experts_held) == (512, (0, 16))
    assert (cfg.zero_expert_num, cfg.moe_topk) == (256, 12)
    assert cfg.latent_dim == 576 and cfg.q_head_dim == 192
    assert cfg.n_sublayers == 8 and cfg.n_moe_layers == 4
    assert enc.state_bytes_per_row(16384) == 8 * 16384 * 576 * 2 \
        == 150_994_944
    assert enc.state_bytes_per_row(4096) == 8 * 4096 * 576 * 2
    assert enc._q_scale == 2.0 and enc._kv_scale == pytest.approx(
        math.sqrt(12))
    assert enc._scale == 192 ** -0.5
    np.testing.assert_allclose(
        enc._inv_freq, 1e7 ** (-np.arange(0, 64, 2) / 64), rtol=1e-12)
    for S in (4096, 16384):
        assert mla.core_is_kernel("tpu", jnp.bfloat16, 512, S, 64, 128, 128,
                                  512)
    assert not mla.core_is_kernel("cpu", jnp.bfloat16, 512, 4096, 64, 128,
                                  128, 512)


def test_config_from_the_published_keys_and_the_share():
    cfg = config()
    assert (cfg.n_routed_experts, cfg.experts_held) == (32, (8, 4))
    assert cfg.zero_expert_num == 16 and hash(cfg) == hash(config())
    whole = make_config("longcat_flash", {
        k: v for k, v in UNCUT.items() if k != "experts_held"})
    assert whole.experts_held == (0, 32)
    with pytest.raises(ValueError, match="not the count"):
        make_config("longcat_flash", dict(MODEL, n_routed_experts=16))
    with pytest.raises(ValueError, match="outside the router"):
        dataclasses.replace(cfg, experts_held=(30, 4))
    with pytest.raises(ValueError, match="identity"):
        dataclasses.replace(cfg, zero_expert_type="copy")
    with pytest.raises(ValueError, match="MLA"):
        dataclasses.replace(cfg, attention_method="GQA")


def test_the_table_from_architecture_to_config_and_encoder():
    cfg = make_config("longcat_flash", MODEL)
    assert type(cfg) is LongcatFlashConfig
    assert LongcatFlashConfig.architecture == "longcat_flash"
    assert "longcat_flash" in contract.ENCODERS
    enc = build_encoder(cfg)
    assert type(enc) is LongcatFlashEncoder and enc.dtype == jnp.bfloat16
    assert isinstance(enc, ChunkEncoder)
    assert enc.state_counters(enc.init_states(1)).shape == (8,)
    assert enc.counter_attrs([]) == {}
    with pytest.raises(ValueError) as e:
        make_config("longcat", {})
    assert "longcat_flash" in str(e.value)


def test_export_round_trip_in_bfloat16(tmp_path, vocab):
    from code_intelligence_tpu.training.checkpoint import export_encoder

    cfg = make_config("longcat_flash", MODEL, kv_positions=64)
    weights = seeded(ref, 1, MODEL, dtype=jnp.bfloat16)
    export_encoder(tmp_path, weights, cfg, vocab)
    eng = InferenceEngine.from_export(tmp_path, buckets=(8, 16),
                                      batch_size=2)
    assert eng.config == cfg and eng.encoder.dtype == jnp.bfloat16
    assert eng._enc_params["params"]["layers"]["layer_1"]["bias"].dtype \
        == jnp.float32
    direct = InferenceEngine(weights, cfg, vocab, buckets=(8, 16),
                             batch_size=2)
    seqs = [np.arange(20, 45, dtype=np.int32)]
    np.testing.assert_array_equal(eng.embed_ids_batch(seqs),
                                  direct.embed_ids_batch(seqs))
