"""The bailing-hybrid encoder (delta-rule linear attention with a decay
per channel in five layers of six, latent attention in the sixth,
sigmoid-routed experts of which this chip holds a share) and the encoder
contract's fifth member.

Small on the CPU (hidden 64, 4 heads of 16, 1 dense + 6 expert layers of
which layer 5 is latent, 16 experts in 4 groups of which 4..11 are held,
4 a token), every comparison against the plain reference
(`benchmark/reference/bailing_hybrid.py`) on seeded weights: the chunked
recurrence against the token-by-token one, at the gate's lower bound,
with padding; a document across 2, 3 and 5 chunk programs; the latent
block with one query matrix against a whole-document softmax; the four
shares adding up to the uncut layer; both kinds of state through the
engine's normal path and on its spans; the contract's numbers at the
published widths.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.reference import bailing_hybrid as ref
from benchmark.reference import common
from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.models import (
    BailingHybridConfig, BailingHybridEncoder, ChunkEncoder, build_encoder,
    make_config)
from code_intelligence_tpu.models import contract
from code_intelligence_tpu.models.blocks import latent_block
from code_intelligence_tpu.ops import kda, mla, moe
from code_intelligence_tpu.ops.ssd import causal_conv1d
from code_intelligence_tpu.text import SPECIALS, Vocab
from code_intelligence_tpu.utils import tracing
from encoder_programs import (
    compiled, seeded, the_rule_says_grouped_kernels)

MODEL = {
    "vocab_size": 300, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
    "num_hidden_layers": 7, "first_k_dense_replace": 1,
    "layer_group_size": 6, "num_attention_heads": 4, "head_dim": 16,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5, "kda_safe_gate": True,
    "no_kda_lora": True, "use_qk_norm": True, "group_norm_size": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 8,
    "num_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "score_function": "sigmoid", "topk_method": "noaux_tc",
    "rms_norm_eps": 1e-6, "rope_theta": 6000000, "rope_scaling": None,
    "rope_interleave": True, "max_position_embeddings": 262144,
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "experts_held": {"first": 4, "count": 8, "of": 16}}
UNCUT = dict(MODEL, num_experts=16,
             experts_held={"first": 0, "count": 16, "of": 16})
TAILS = {"dist": "student_t", "df": 4}
T_DOC = 200   # four chunks of the recurrence (64), the last one short


@pytest.fixture(scope="module")
def params():
    return seeded(ref, 36, MODEL, TAILS)


def config(**extra):
    return make_config("bailing_hybrid", MODEL, **dict(
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


# -- the recurrence ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("b", "T", "H", "dk", "dv",
                                              "at_bound"))
def kda_inputs(seed, b, T, H=3, dk=16, dv=8, at_bound=False):
    """One compiled program a shape (`tests/test_kda_kernel.py` draws
    from it too): drawn op by op, every ``normal`` of a new shape is a
    compilation of its own."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, T, H, dk))
    k = jax.random.normal(ks[1], (b, T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, T, H, dv))
    g = -5.0 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (b, T, H, dk)))
    if at_bound:
        g = jnp.full_like(g, -5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, H, dk, dv))


recurrence = jax.jit(kda.kda_recurrence)


@pytest.mark.parametrize("T,chunk,sub", [
    (128, 64, 16),    # chunks divide T
    (100, 64, 16),    # the last chunk is padded
    (37, 32, 8),      # one chunk, padded; other sub-blocks
    (200, 64, 16),
])
def test_chunked_equals_token_by_token_with_state_in(T, chunk, sub):
    inputs = kda_inputs(T, 2, T)
    o, S = jax.jit(functools.partial(
        kda.kda_scan, chunk=chunk, mxu_dtype=jnp.float32, sub=sub))(*inputs)
    o_want, S_want = recurrence(*inputs)
    # float32 sums in another order; outputs are O(0.3), states O(1)
    np.testing.assert_allclose(o, o_want, atol=5e-6)
    np.testing.assert_allclose(S, S_want, atol=5e-6)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-6),
                                        (jnp.bfloat16, 3e-3)])
def test_gates_at_the_lower_bound_over_a_whole_chunk_stay_finite(dtype, atol):
    """``g = -5`` at every token and channel: ``G`` reaches -320 inside
    a chunk, ``e^{320}`` is not a float32; the sub-blocks keep every
    ``exp`` within ``e^{+-40}``."""
    inputs = kda_inputs(7, 1, 128, at_bound=True)
    o, S = jax.jit(functools.partial(kda.kda_scan, mxu_dtype=dtype))(*inputs)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    o_want, S_want = recurrence(*inputs)
    np.testing.assert_allclose(o, o_want, atol=atol)
    np.testing.assert_allclose(S, S_want, atol=atol)


def test_no_exp_of_a_large_sum_is_formed(monkeypatch):
    """Every argument ``kda_scan`` hands ``exp`` is at most half a
    sub-block of steps at the bound."""
    seen = []
    real = jnp.exp

    def listening(x):
        seen.append(float(jnp.max(x)))
        return real(x)

    monkeypatch.setattr(kda.jnp, "exp", listening)
    kda.kda_scan(*kda_inputs(8, 1, 128, at_bound=True),
                 mxu_dtype=jnp.float32)
    monkeypatch.undo()
    assert seen and max(seen) <= 16 // 2 * 5.0


def test_repeated_keys_do_not_cancel_in_the_solve():
    """The same key at every token with ``b = 1`` and no decay: ``A`` is
    all ones below the diagonal, whose powers grow binomially (a Neumann
    product loses float32 there); forward substitution is exact."""
    q, k, v, g, beta, S = kda_inputs(9, 1, 128)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    o, S1 = jax.jit(functools.partial(kda.kda_scan, mxu_dtype=jnp.float32))(
        q, k, v, jnp.zeros_like(g), jnp.ones_like(beta), S)
    o_want, S_want = recurrence(
        q, k, v, jnp.zeros_like(g), jnp.ones_like(beta), S)
    np.testing.assert_allclose(o, o_want, atol=5e-6)
    np.testing.assert_allclose(S1, S_want, atol=5e-6)


def test_the_solve_is_the_inverse():
    A = 0.2 * jnp.tril(
        jax.random.normal(jax.random.PRNGKey(0), (3, 32, 32)), -1)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (3, 32, 5))
    X = kda._solve_unit_lower(A, rhs, 8)
    np.testing.assert_allclose(X + A @ X, rhs, atol=1e-4)


def test_the_conv_tail_stops_at_a_rows_valid_end():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 6, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (5, 4))
    tail = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 5))
    out, whole = causal_conv1d(x, w, jnp.zeros(5), tail)
    out2, cut = causal_conv1d(x, w, jnp.zeros(5), tail,
                              lengths=jnp.array([6, 2, 0]))
    np.testing.assert_array_equal(out, out2)
    np.testing.assert_array_equal(cut[0], whole[0])            # all valid
    np.testing.assert_array_equal(cut[1], jnp.concatenate(
        [tail[1, 2:], x[1, :2]]))                              # 2 valid
    np.testing.assert_array_equal(cut[2], tail[2])             # padding


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
    """State in, state out: matrix states and conv tails of six layers
    and one latent cache handed over ``programs - 1`` times, the last
    program padded."""
    got, _ = streamed(encoder, params, tokens, programs)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=5e-5)


def test_the_encoder_on_the_grouped_matmul_kernels_equals_the_reference(
        monkeypatch, params, tokens, want):
    """Every expert layer's two grouped products through ``ops/gmm.py``'s
    kernels (interpreted), three chunk programs, the last one padded
    (its padding lanes sort behind every routed row, where no tile is
    visited), and the count says six layers."""
    the_rule_says_grouped_kernels(monkeypatch)
    enc = build_encoder(config(), params)
    got, states = streamed(enc, params, tokens, 3)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=5e-5)
    assert enc.counter_attrs([np.asarray(states["counts"])])[
        "expert_kernel_layers"] == 6


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
    for kind in ("kda", "conv"):
        for a, b in zip(before[kind], after[kind]):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(part[kind], alone[kind]):
            np.testing.assert_allclose(a, b, atol=2e-6)
    assert float(jnp.abs(part["kda"][0] - before["kda"][0]).max()) > 1e-3


def _differs(got, want, start):
    return float(jnp.abs(got[:, start:] - want[:, start:]).max())


def test_dropped_state_of_either_kind_is_seen(params, encoder, tokens, want):
    def without(kind):
        return lambda s: dict(s, **{kind: jax.tree.map(
            jnp.zeros_like, s[kind])})

    for kind in ("kda", "conv", "latent"):
        got, _ = streamed(encoder, params, tokens, 4, between=without(kind))
        np.testing.assert_allclose(got[:, :50], want[:, :50], atol=5e-5)
        assert _differs(got, want, 50) > 1e-2, kind


def test_bfloat16_program_against_the_float32_reference(tokens):
    """bfloat16 weights, matmul inputs, conv tails and latent cache
    against float32 over the same (bfloat16-valued) weights, every layer
    with a dense MLP: top-k is discrete and a flipped choice moves a
    token by O(1) and, through the recurrence, every token after it
    (`tests/test_deepseek_v3.py` counts such flips), which would drown
    what is measured here: 8 bits of mantissa through 14 residual
    branches and four hand-overs of the state. Relative RMS error, as
    the benchmark's check reads it: measured 1.8 %."""
    dense = dict(MODEL, first_k_dense_replace=7)
    weights = seeded(ref, 36, dense, TAILS, jnp.bfloat16)
    enc = build_encoder(make_config(
        "bailing_hybrid", dense, kv_positions=256), weights)
    assert enc.dtype == enc.config.state_dtype == jnp.bfloat16
    got, _ = streamed(enc, weights, tokens, 4)
    same = reference(jax.tree.map(lambda w: w.astype(jnp.float32), weights),
                     tokens, dense)[0]
    rel = float(jnp.sqrt(jnp.mean((got - same) ** 2) / jnp.mean(same ** 2)))
    assert 1e-4 < rel < 0.04, rel


def test_the_latent_block_without_a_low_rank_query_is_a_whole_softmax(params):
    """``latent_block(q_low_rank=False, head_gate=True)`` through a cache
    in three chunks against the reference's dense masked softmax."""
    p = {k: v for k, v in params["layers"]["layer_5"].items()}
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 64))
    eps = MODEL["rms_norm_eps"]
    # one program for the three chunks (``pos`` is traced), one ungated
    block = jax.jit(lambda h, cache, pos, head_gate: latent_block(
        p, h, cache, pos, jnp.float32, heads=4, nope=16, rope=8, v_dim=16,
        rank=32, eps=eps, inv_freq=mla.yarn_inv_freq(8, 6000000.0),
        rope_factor=1.0, scale=24 ** -0.5, q_low_rank=False,
        head_gate=head_gate), static_argnums=3)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda h: ref.latent_attention(
            p, ref.rms_norm(h, p["norm"], eps), MODEL))(h)
        cache = jnp.zeros((2, 64, 40))
        outs = []
        for a in range(0, 48, 16):
            out, cache = block(h[:, a:a + 16], cache, jnp.int32(a), True)
            outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=2e-5)
    # the gate is in the comparison
    with jax.default_matmul_precision("highest"):
        ungated, _ = block(h[:, :16], jnp.zeros((2, 64, 40)), jnp.int32(0),
                           False)
    assert float(jnp.abs(ungated - want[:, :16]).max()) > 1e-2


def test_the_mechanisms_are_in_the_comparison(monkeypatch, params, tokens,
                                              want):
    """No decay, no delta, no conv, no L2 norm: each moves the encoder
    away from the reference."""
    real = kda.kda_scan

    def no_decay(q, k, v, g, beta, *a, **kw):
        return real(q, k, v, jnp.zeros_like(g), beta, *a, **kw)

    def no_norm(q, k, *a, **kw):
        return real(q * 3.0, k * 3.0, *a, **kw)

    for broken in (no_decay, no_norm):
        monkeypatch.setattr(kda, "kda_scan", broken)
        enc = build_encoder(config(), params)
        with jax.default_matmul_precision("highest"):   # traced patched
            got, _ = compiled(enc)(params, tokens,
                                   enc.init_states(2, T_DOC))
        monkeypatch.undo()
        assert _differs(got, want, 8) > 1e-2, broken.__name__


# -- the share -----------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """One expert layer, 16 experts in 4 groups: the routed parts of the
    four shares of 4 (one whole routing group each; and of the two of 8)
    summed, plus the shared expert ONCE, equal the uncut reference's
    whole layer."""
    whole = seeded(ref, 4, UNCUT, TAILS, layer="layer_1")
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = jax.jit(lambda p, x: ref.moe_layer(p, x, UNCUT))(
            whole, x)
        shared = ref.swiglu(x, whole["shared_in"], whole["shared_out"])
    # ``first`` is traced: one program a share's size, not one a share
    share = jax.jit(lambda held, first: moe.expert_layer(
        held, x, None, jnp.float32, n_group=4, topk_group=2, top_k=4,
        scaling=2.5, norm_topk_prob=True, first=first, shared=False))
    for count in (4, 8):
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
    experts, _ = moe.route(x, whole["router"], whole["bias"], 4, 2, 4, 2.5)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))


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
    # six matrix states of 4 x 16 x 16 float32, six tails of 3 x 192,
    # one latent cache of 256 x 40, float32
    assert counts["state_bytes"] == 4 * (
        6 * 4 * 16 * 16 * 4 + 6 * 3 * 192 * 4 + 256 * 40 * 4)


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
        _, chosen = reference(params, jnp.asarray(s)[None])
        want += sum(int(((c >= 4) & (c < 12)).sum()) for c in chosen)
    assert a["routed_rows"] == want > 0
    assert a["moe_programs"] == 3       # chunks of 16: rows 4, 2, 2
    # what the two rules say here: the CPU, float32, sizes under a lane
    assert (a["kda_layers"], a["kda_kernel_layers"],
            a["attention_kernel_layers"]) == (6, 0, 0)
    assert a["expert_kernel_layers"] == 0
    (group,) = [s for s in spans if s["name"] == "engine.group"]
    g = group["attrs"]
    assert (g["chunks"], g["kv_positions"]) == (3, 64)
    programs = [s for s in spans if s["name"] == "engine.program"]
    assert len(programs) == 3


def test_kda_kernel_layers_is_what_the_rule_says(params, vocab, monkeypatch):
    """The rule patched true, tiles for the tiny shapes and chunk
    programs of 64 tokens (one whole chunk of the recurrence): every KDA
    layer of every program runs the interpreted kernel, the count says
    6, and the rows are the XLA scan's."""
    seqs = [np.random.default_rng(12).integers(20, 300, n).astype(np.int32)
            for n in (50, 64)]

    def build():
        return InferenceEngine(params, config(), vocab, buckets=(64,),
                               batch_size=2)

    want = build().embed_ids_batch(seqs)
    monkeypatch.setattr(kda, "core_is_kernel", lambda *a: True)
    monkeypatch.setattr(kda, "_kernel_tiles", lambda *a: 2)
    ran = []
    real = kda._kernel_scan
    monkeypatch.setattr(kda, "_kernel_scan",
                        lambda *a: ran.append(a[0].shape) or real(*a))
    engine = build()
    _, a = _traced_finalize(engine, seqs)
    assert (a["kda_layers"], a["kda_kernel_layers"]) == (6, 6)
    assert ran and len(ran) % 6 == 0
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
    assert scheduler in str(e.value) and "BailingHybrid" in str(e.value)


def test_the_engine_has_no_branch_for_it():
    from code_intelligence_tpu.inference import engine as module

    assert "bailing" not in open(module.__file__).read().lower()


# -- the contract ----------------------------------------------------------------

def test_it_satisfies_the_contract_and_counts_its_state(encoder):
    assert isinstance(encoder, ChunkEncoder)
    assert encoder.out_dim == 64
    fixed = 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    # the latent cache moves with the positions, on a grid: kv_positions
    # halved while it holds them; nothing attends under a window
    assert [encoder.cache_positions(n) for n in (5, 16, 17, 64, 65, 256)] \
        == [8, 16, 32, 64, 128, 256]
    assert encoder.cache_positions() == 256
    assert [encoder.window_positions(n) for n in (None, 5, 256)] == [0, 0, 0]
    for n in (16, 100, 256):
        assert encoder.state_bytes_per_row(n) \
            == fixed + encoder.cache_positions(n) * 40 * 4
        states = encoder.init_states(2, n)
        got = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(states))
        # less the position counter and the five counts
        assert got - 4 - 6 * 4 == 2 * encoder.state_bytes_per_row(n)
    assert encoder.state_bytes_per_row() == encoder.state_bytes_per_row(256)
    with pytest.raises(ValueError, match="kv_positions=256"):
        encoder.cache_positions(257)
    states = encoder.init_states(2, 64)
    assert [s.dtype for s in states["kda"]] == [jnp.float32] * 6
    assert states["latent"][0].shape == (2, 64, 40)
    # no latent layer in the cut: no cache that grows
    short = build_encoder(config(num_hidden_layers=5))
    assert short.cache_positions(100) == 0
    assert short.init_states(1, 100)["latent"] == ()


def test_published_widths_carry_32_megabytes_a_row():
    """Shapes only, no weights: six (32, 128, 128) float32 matrices, six
    conv tails of 3 x 12288 and one latent cache of 16,384 x 576 in
    bfloat16."""
    published = dict(vocab_size=39296, num_hidden_layers=7,
                     first_k_dense_replace=1, num_experts=128,
                     experts_held={"first": 0, "count": 128, "of": 512})
    enc = build_encoder(make_config("bailing_hybrid", published))
    cfg = enc.config
    assert (cfg.num_experts, cfg.experts_held) == (512, (0, 128))
    assert (cfg.kda_layers, cfg.latent_layers) == ((0, 1, 2, 3, 4, 6), (5,))
    assert enc.state_bytes_per_row(16384) == 12582912 + 442368 + 18874368 \
        == 31899648
    # the short group of the cell: 6 chunks of 512 on the 4096 grid
    assert enc.cache_positions(3072) == 4096
    shapes = jax.eval_shape(lambda: enc.init_states(16, 16384))
    assert [s.shape for s in shapes["kda"]] == [(16, 32, 128, 128)] * 6
    assert [s.shape for s in shapes["conv"]] == [(16, 3, 12288)] * 6
    assert [(s.shape, s.dtype) for s in shapes["latent"]] \
        == [((16, 16384, 576), jnp.bfloat16)]
    # the whole model: seven latent layers of 42
    assert len(dataclasses.replace(
        cfg, num_hidden_layers=42).latent_layers) == 7


def test_config_from_the_published_keys_and_the_share():
    cfg = config()
    assert (cfg.num_experts, cfg.experts_held) == (16, (4, 8))
    assert cfg.n_moe_layers == 6 and cfg.kda_dim == 64
    assert hash(cfg) == hash(config())
    # the held layers' limits alone are read, and they are 0
    assert cfg.expert_swiglu_limit_list == (0,) * 7
    whole = make_config("bailing_hybrid", {
        k: v for k, v in UNCUT.items() if k != "experts_held"})
    assert whole.experts_held == (0, 16)
    with pytest.raises(ValueError, match="not the count"):
        make_config("bailing_hybrid", dict(MODEL, num_experts=16))
    with pytest.raises(ValueError, match="outside the router"):
        dataclasses.replace(cfg, experts_held=(12, 8))
    for key, other in (("q_lora_rank", 1536), ("kda_safe_gate", False),
                       ("rope_scaling", {"type": "yarn"}),
                       ("use_qk_norm", False),
                       ("score_function", "softmax")):
        with pytest.raises(ValueError, match=key):
            make_config("bailing_hybrid", dict(MODEL, **{key: other}))


@pytest.mark.parametrize("key", ["expert_swiglu_limit_list",
                                 "share_expert_swiglu_limit_list"])
def test_a_non_zero_swiglu_limit_is_refused_by_name(key):
    """The published limits are non-zero from layer 34 on only; a cut
    that held one of those layers is refused, program and reference."""
    clamped = dict(MODEL, **{key: [0, 0, 4, 0, 0, 0, 0]})
    with pytest.raises(NotImplementedError, match=key):
        make_config("bailing_hybrid", clamped)
    with pytest.raises(NotImplementedError, match=key):
        ref.dims(clamped)
    with pytest.raises(NotImplementedError, match="swiglu_limit_list"):
        make_config("bailing_hybrid", dict(MODEL, num_hidden_layers=42,
                                           first_k_dense_replace=2))


def test_the_table_has_a_fifth_row():
    assert type(config()) is BailingHybridConfig
    assert "bailing_hybrid" in contract.ENCODERS
    assert contract.ENCODERS["bailing_hybrid"][0] is BailingHybridConfig
    enc = build_encoder(config())
    assert isinstance(enc, BailingHybridEncoder)
    assert isinstance(enc, ChunkEncoder)
    assert enc.state_counters(enc.init_states(1)).shape == (6,)
    assert enc.counter_attrs([]) == {}


def test_export_round_trip_in_bfloat16(tmp_path, vocab):
    from code_intelligence_tpu.training.checkpoint import export_encoder

    cfg = make_config("bailing_hybrid", MODEL, kv_positions=64)
    weights = seeded(ref, 1, MODEL, dtype=jnp.bfloat16)
    export_encoder(tmp_path, weights, cfg, vocab)
    eng = InferenceEngine.from_export(tmp_path, buckets=(8,), batch_size=2)
    assert eng.config == cfg and eng.encoder.dtype == jnp.bfloat16
    layer = eng._enc_params["params"]["layers"]["layer_2"]
    assert layer["bias"].dtype == layer["A_log"].dtype == jnp.float32
    direct = InferenceEngine(weights, cfg, vocab, buckets=(8,), batch_size=2)
    seqs = [np.arange(20, 45, dtype=np.int32)]
    np.testing.assert_array_equal(eng.embed_ids_batch(seqs),
                                  direct.embed_ids_batch(seqs))
