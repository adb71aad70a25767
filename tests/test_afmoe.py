"""The AFMoE encoder (sliding-window and global GQA layers mixed, gated
attention, sandwich norms, sigmoid-routed experts of which this chip
holds a share) and the encoder contract's fourth member.

Small on the CPU (hidden 64, 4 / 2 heads of 8, window 8, 1 dense + 4
expert layers of which the fourth is global, 16 experts of which 4..11
are held, 4 a token), every comparison against the plain reference
(`benchmark/reference/afmoe.py`) on seeded weights: the windowed,
ring-cached core against a dense masked softmax; a document streamed in
chunks of 4 through rings of 12 slots that wrap three times; what a
dropped ring, a ring one chunk too short, a missing window and rotary on
the global layer each do to it; the eight shares adding up to the uncut
layer; the two kinds of state through the engine's normal path and on
its spans; the contract's numbers at the published widths.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.reference import afmoe as ref
from benchmark.reference import common
from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.models import (
    AfmoeConfig, AfmoeEncoder, ChunkEncoder, build_encoder, make_config)
from code_intelligence_tpu.models import contract
from code_intelligence_tpu.ops import mla, moe
from code_intelligence_tpu.ops import attention
from code_intelligence_tpu.ops.attention import gqa_cached
from code_intelligence_tpu.text import SPECIALS, Vocab
from code_intelligence_tpu.utils import tracing
from encoder_programs import (
    compiled, seeded, the_rule_says_grouped_kernels)

SLIDING, FULL = "sliding_attention", "full_attention"
MODEL = {
    "vocab_size": 300, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "num_dense_layers": 1,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL, SLIDING],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "sliding_window": 8, "num_experts": 8, "num_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
    "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid",
    "mup_enabled": True, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "rope_scaling": None, "max_position_embeddings": 262144,
    "global_attn_every_n_layers": 4,
    "experts_held": {"first": 4, "count": 8, "of": 16}}
UNCUT = dict(MODEL, num_experts=16,
             experts_held={"first": 0, "count": 16, "of": 16})
TAILS = {"dist": "student_t", "df": 4}
T_DOC = 40   # five windows: a ring of 8 + 4 slots wraps three times


@pytest.fixture(scope="module")
def params():
    return seeded(ref, 32, MODEL, TAILS)


def config(**extra):
    return make_config("afmoe", MODEL, **dict(
        {"kv_positions": 64, "chunk_positions": 4,
         "state_dtype": jnp.float32}, **extra))


@pytest.fixture(scope="module")
def encoder(params):
    return build_encoder(config(), params)


@pytest.fixture(scope="module")
def vocab():
    return Vocab(traffic.vocab_words(SPECIALS, 300))


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (3, T_DOC), 0, 300)


def reference(params, tokens, model=MODEL):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: ref.encode(p, t, model))(params, tokens)


@pytest.fixture(scope="module")
def want(params, tokens):
    return reference(params, tokens)[0]


def streamed(enc, params, tokens, chunk=4, between=None):
    """``tokens`` through ``enc`` in chunk programs of ``chunk``."""
    states = enc.init_states(tokens.shape[0], tokens.shape[1])
    step = compiled(enc)
    outs = []
    for lo in range(0, tokens.shape[1], chunk):
        out, states = step(params, tokens[:, lo:lo + chunk], states)
        if between is not None:
            states = between(states)
        outs.append(out)
    return jnp.concatenate(outs, 1), states


# -- ops: the windowed, ring-cached core --------------------------------------

def _dense(q, k, v, scale, window=None):
    T, rep = q.shape[1], q.shape[2] // k.shape[2]
    s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, rep, axis=2)) * scale
    t, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= t
    if window is not None:
        seen = seen & (t - j < window)
    s = jnp.where(seen, s, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1),
                      jnp.repeat(v, rep, axis=2))


@functools.partial(jax.jit, static_argnames=("total", "seed", "b", "Hq",
                                              "Hkv", "d"))
def _qkv(total, seed=0, b=2, Hq=4, Hkv=2, d=8):
    """One compiled program a shape: drawn op by op, every ``normal`` of
    a new shape is a compilation of its own."""
    k = jax.random.split(jax.random.PRNGKey(seed + total), 3)
    return (jax.random.normal(k[0], (b, total, Hq, d)),
            jax.random.normal(k[1], (b, total, Hkv, d)),
            jax.random.normal(k[2], (b, total, Hkv, d)))


def _through_the_cache(q, k, v, S, T, dtype=jnp.float32, jitted=True,
                       **kw):
    """ONE compiled program for the chunks (``pos`` is traced, as the
    encoders hand it over); ``jitted=False`` for the test that reads
    the loops' concrete bounds."""
    b, total, _, d = q.shape
    kc = vc = jnp.zeros((b, k.shape[2], S, d), dtype)     # head-major

    def step(q, k, v, kc, vc, pos):
        return gqa_cached(q, k, v, kc, vc, pos, 0.3, mxu_dtype=dtype, **kw)

    if jitted:
        step = jax.jit(step)
    outs = []
    for lo in range(0, total, T):
        out, kc, vc = step(q[:, lo:lo + T], k[:, lo:lo + T], v[:, lo:lo + T],
                           kc, vc, jnp.int32(lo))
        outs.append(out)
    return jnp.concatenate(outs, 1)


@pytest.mark.parametrize("window,S,T,key_block,q_block", [
    (8, 12, 4, 512, 128),    # one key block: the plain softmax over a ring
    (8, 12, 4, 4, 128),      # three key blocks under the running softmax
    (8, 16, 8, 4, 4),        # a ring of two chunks, two query blocks
    (24, 32, 8, 8, 4),       # the ring fills before it wraps
    (None, 64, 8, 16, 4),    # no window: a growing cache, bounded work
    (None, 64, 16, 512, 128),
], ids=["ring", "ring_blocked", "ring_q_blocks", "ring_fills", "global",
        "global_one_block"])
def test_the_core_equals_a_dense_masked_softmax(window, S, T, key_block,
                                                q_block):
    """48 positions through the cache a chunk at a time: with a window
    the ring of ``S`` < 48 slots wraps up to four times."""
    q, k, v = _qkv(48)
    got = _through_the_cache(q, k, v, S, T, window=window,
                             key_block=key_block, q_block=q_block)
    np.testing.assert_allclose(got, _dense(q, k, v, 0.3, window),
                               rtol=2e-5, atol=2e-5)


def _the_rule_says_kernel(monkeypatch, tiles):
    """The rule's answer steered from the test (it sees the CPU and
    float32 here), and tiles that divide the tiny shapes; the kernel
    itself asks the real backend and is interpreted."""
    monkeypatch.setattr(attention, "core_is_kernel", lambda *a: True)
    monkeypatch.setattr(attention, "_kernel_tiles", lambda *a: tiles)


# (window, S, T, (q_block, key_block), heads (Hq, Hkv, d), dtype): the
# table above through the kernel, then the two cells' head counts in
# bfloat16, and a cache whose first query blocks stop short of its last
# key block; every chunk after the first comes at a ``pos`` that is not 0
@pytest.mark.parametrize("window,S,T,tiles,heads,dtype", [
    (8, 12, 4, (4, 12), (4, 2, 8), jnp.float32),
    (8, 12, 4, (4, 4), (4, 2, 8), jnp.float32),
    (8, 16, 8, (4, 4), (4, 2, 8), jnp.float32),
    (24, 32, 8, (4, 8), (4, 2, 8), jnp.float32),
    (None, 64, 8, (4, 16), (4, 2, 8), jnp.float32),
    (16, 32, 16, (16, 16), (12, 2, 128), jnp.bfloat16),
    (None, 64, 16, (8, 32), (8, 2, 64), jnp.bfloat16),
    (8, 16, 8, (8, 8), (4, 2, 8), jnp.float32),
    (None, 64, 8, (4, 8), (4, 2, 8), jnp.float32),
], ids=["ring", "ring_blocked", "ring_q_blocks", "ring_fills", "global",
        "rep6_d128_bf16", "rep4_d64_bf16", "one_q_block", "live_stops_short"])
def test_the_kernel_equals_a_dense_masked_softmax(monkeypatch, window, S, T,
                                                  tiles, heads, dtype):
    """``gqa_cached`` with its core on the Pallas kernel, interpreted."""
    _the_rule_says_kernel(monkeypatch, tiles)
    Hq, Hkv, d = heads
    q, k, v = _qkv(48, Hq=Hq, Hkv=Hkv, d=d)
    got = _through_the_cache(q, k, v, S, T, dtype=dtype, window=window)
    if dtype == jnp.bfloat16:     # the products' operands are rounded
        q, k, v = (x.astype(dtype).astype(jnp.float32) for x in (q, k, v))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, _dense(q, k, v, 0.3, window),
                               rtol=tol, atol=tol)


def test_the_kernel_fetches_only_the_key_blocks_reached(monkeypatch):
    """The index map of the keys, read off the ``BlockSpec``: a 64-slot
    cache in key blocks of 8 with 16 positions cached and 8 arriving:
    the first query block of 4 reaches blocks 0..2 and is handed block 2
    again for the five steps after (no new fetch, and ``pl.when`` skips
    the step); a wrapped ring is handed all of its blocks."""
    from jax.experimental import pallas as pl

    maps = []
    real = pl.BlockSpec

    def recording(shape, index_map):
        maps.append(index_map)
        return real(shape, index_map)

    monkeypatch.setattr(pl, "BlockSpec", recording)
    q, k, v = _qkv(8)
    cache = jnp.zeros((2, 2, 64, 8))
    attention._kernel_core(q, cache, cache, jnp.int32(16), 0.3, None,
                           jnp.float32, (4, 8))
    keys = maps[1]
    pos = np.asarray([16], np.int32)
    assert [int(keys(0, 0, 0, j, pos)[2]) for j in range(8)] \
        == [0, 1, 2, 2, 2, 2, 2, 2]
    assert [int(keys(0, 0, 1, j, pos)[2]) for j in range(8)] \
        == [0, 1, 2, 2, 2, 2, 2, 2]     # queries 20..23: still block 2
    del maps[:]
    ring = jnp.zeros((2, 2, 16, 8))
    attention._kernel_core(q, ring, ring, jnp.int32(24), 0.3, 8,
                           jnp.float32, (4, 8))
    assert [int(maps[1](0, 0, 0, j, np.asarray([24], np.int32))[2])
            for j in range(2)] == [0, 1]


def test_the_core_meets_only_the_key_blocks_reached(monkeypatch):
    """A 64-slot cache in key blocks of 8: the first chunk of 8 queries
    runs one key block, the fourth four; a wrapped ring runs all."""
    from jax import lax

    trips = []
    real = lax.fori_loop

    def counting(lo, hi, body, init):
        trips.append(int(hi))
        return real(lo, hi, body, init)

    monkeypatch.setattr(lax, "fori_loop", counting)
    q, k, v = _qkv(32)
    _through_the_cache(q, k, v, 64, 8, jitted=False, key_block=8,
                       q_block=128)
    assert trips == [1, 2, 3, 4]
    del trips[:]
    _through_the_cache(q, k, v, 16, 8, jitted=False, window=8,
                       key_block=8, q_block=128)
    assert trips == [1, 2, 2, 2]


def test_a_ring_takes_whole_chunks_only():
    q, k, v = _qkv(6)
    with pytest.raises(ValueError, match="whole chunks of 6"):
        _through_the_cache(q, k, v, 16, 6, window=8)


def test_plain_rotary_is_rotate_half():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 3, 8))
    got = mla.apply_rope(x, 7 + jnp.arange(5), mla.yarn_inv_freq(8, 10000),
                         interleaved=False)
    # the reference rotates positions 0..T-1: prepend 7 of them
    padded = jnp.concatenate([jnp.zeros((2, 7, 3, 8)), x], axis=1)
    np.testing.assert_allclose(got, ref.rotary(padded, MODEL)[:, 7:],
                               rtol=1e-5, atol=1e-6)


# -- the encoder against the reference ----------------------------------------

def test_encoder_equals_the_reference(params, tokens, want):
    """The whole document as ONE chunk: every sliding layer masks five
    windows' worth of keys."""
    enc = build_encoder(config(chunk_positions=T_DOC), params)
    got, states = compiled(enc)(params, tokens, enc.init_states(3, T_DOC))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert int(states["pos"]) == T_DOC


def test_streamed_through_rings_that_wrap_equals_the_whole_document(
        params, encoder, tokens, want):
    got, states = streamed(encoder, params, tokens)
    assert [c.shape[2] for c in states["k"]] == [12, 12, 12, 64, 12]
    assert T_DOC // 12 >= 3      # every ring wrapped three times
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert int(states["counts"][2]) == T_DOC // 4


def _differs(got, want, start=12):
    """Largest difference over the positions past the first window."""
    return float(jnp.abs(got - want)[:, start:].max())


def test_dropped_rings_are_seen(params, encoder, tokens, want):
    def dropped(states):
        kinds = MODEL["layer_types"]
        return dict(states, **{name: tuple(
            jnp.zeros_like(c) if kind == SLIDING else c
            for c, kind in zip(states[name], kinds)) for name in "kv"})

    got, _ = streamed(encoder, params, tokens, between=dropped)
    assert _differs(got, want) > 0.05


def test_a_ring_one_chunk_too_short_is_seen(monkeypatch, params, tokens,
                                            want):
    """A ring of the window alone (8 slots, chunks of 4): a chunk
    overwrites keys its own first queries still see."""
    monkeypatch.setattr(AfmoeConfig, "ring_positions", 8)
    enc = build_encoder(config(), params)
    got, states = streamed(enc, params, tokens)
    assert states["k"][0].shape[2] == 8
    assert _differs(got, want) > 0.05


def test_a_missing_window_is_seen(params, tokens, want):
    enc = build_encoder(config(sliding_window=1 << 20), params)
    got, _ = streamed(enc, params, tokens)
    np.testing.assert_allclose(got[:, :8], want[:, :8], rtol=2e-5,
                               atol=2e-5)    # inside the first window
    assert _differs(got, want) > 0.05


def test_rotary_on_the_global_layer_is_seen(params, tokens):
    """A reference whose fourth layer is a sliding one IS the published
    model with rotary on its global layer, once no window bites (give
    both a window no document reaches). The two references differ, and
    the program is equal to the published one."""
    wide = dict(MODEL, sliding_window=1 << 20)
    plain = reference(params, tokens, wide)[0]
    rotated = reference(params, tokens,
                        dict(wide, layer_types=[SLIDING] * 5))[0]
    assert float(jnp.abs(rotated - plain).max()) > 0.05
    enc = build_encoder(config(sliding_window=1 << 20,
                               chunk_positions=T_DOC), params)
    got, _ = compiled(enc)(params, tokens, enc.init_states(3, T_DOC))
    np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-5)


def test_the_gate_the_norms_and_the_multiplier_are_in_the_comparison(
        params, tokens, want):
    """Each assumed piece, left out of the PROGRAM's weights: the row
    moves."""
    enc = build_encoder(config(chunk_positions=T_DOC), params)

    def run(p):
        return compiled(enc)(p, tokens, enc.init_states(3, T_DOC))[0]

    def with_layer(i, **leaves):
        layers = dict(params["layers"])
        layers[f"layer_{i}"] = dict(layers[f"layer_{i}"], **leaves)
        return dict(params, layers=layers)

    l2 = params["layers"]["layer_2"]
    for changed in (
            with_layer(2, gate=jnp.zeros_like(l2["gate"])),   # sigmoid = 1/2
            with_layer(2, post_attn_norm=2 * l2["post_attn_norm"]),
            with_layer(2, post_mlp_norm=2 * l2["post_mlp_norm"]),
            with_layer(2, q_norm=2 * l2["q_norm"])):
        assert float(jnp.abs(run(changed) - want).max()) > 0.01
    flat = build_encoder(config(chunk_positions=T_DOC, mup_enabled=False),
                         params)
    got = compiled(flat)(params, tokens, flat.init_states(3, T_DOC))[0]
    assert float(jnp.abs(got - want).max()) > 0.01


# -- the share -----------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert layer, 16 experts: the routed parts of the eight
    shares of 2 (and of the two of 8) summed, plus the shared expert
    ONCE, equal the uncut reference's whole layer."""
    whole = seeded(ref, 4, UNCUT, TAILS, layer="layer_1")
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = jax.jit(lambda p, x: ref.moe_layer(p, x, UNCUT))(
            whole, x)
        shared = ref.swiglu(x, whole["shared_in"], whole["shared_out"])
    # ``first`` is traced: one program a share's size, not one a share
    share = jax.jit(lambda held, first: moe.expert_layer(
        held, x, None, jnp.float32, n_group=1, topk_group=1, top_k=4,
        scaling=2.448, norm_topk_prob=True, first=first, shared=False))
    for count in (2, 8):
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
    experts, _ = moe.route(x, whole["router"], whole["bias"], 1, 1, 4,
                           2.448)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))


def test_float32_routing_is_the_references(monkeypatch, params, tokens):
    _, want = reference(params, tokens)
    seen = []
    real = moe.route

    def listening(*a, **kw):
        experts, weights = real(*a, **kw)
        seen.append(np.asarray(experts))
        return experts, weights

    monkeypatch.setattr(moe, "route", listening)
    enc = build_encoder(config(chunk_positions=T_DOC), params)
    enc.encode(params, tokens, enc.init_states(3, T_DOC))
    assert len(seen) == len(want) == 4
    for g, w in zip(seen, want):
        np.testing.assert_array_equal(np.sort(g, -1), np.sort(w, -1))


# -- through the engine's normal path -------------------------------------------

@pytest.fixture(scope="module")
def engine(params, vocab):
    return InferenceEngine(params, config(), vocab, buckets=(4,),
                           batch_size=4)


def reference_rows(params, id_seqs, pad_id):
    encode = jax.jit(lambda p, t: ref.encode(p, t, MODEL)[0])
    return common.pooled_rows(encode, params, id_seqs, pad_id, T_DOC,
                              block_rows=4)


def test_chunked_through_both_kinds_of_state_with_narrowing(
        params, engine, vocab):
    """One group of four at bucket 4: lengths 3, 9, 22 and 40: the batch
    narrows 4, 4, 4 (2 of them alive), 2 .. 2, 1 .. and the longest
    document's rings wrap three times; every row is the reference's
    whole-document forward for that document alone."""
    rng = np.random.default_rng(7)
    seqs = [rng.integers(20, 300, n).astype(np.int32)
            for n in (40, 3, 9, 22)]
    got = engine.embed_ids_batch(seqs)
    assert got.shape == (4, 3 * 64) == (4, engine.embed_dim)
    want = reference_rows(params, seqs, vocab.pad_id)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    _, counts = engine._embed_group_device(sorted(seqs, key=len))
    rows = [4, 4, 4, 2, 2, 2, 1, 1, 1, 1]
    assert counts["chunks"] == 10
    assert counts["lane_steps_run"] == 4 * sum(rows)
    assert (counts["kv_positions"], counts["kv_positions_window"]) \
        == (64, 12)
    assert counts["cache_steps_run"] == sum(
        r * 4 * (i + 1) for i, r in enumerate(rows))
    assert counts["window_steps_run"] == sum(
        r * min(4 * (i + 1), 12) for i, r in enumerate(rows))
    # 4 rings of 12 slots and one cache of 64, keys and values of
    # 2 heads x 8 float32
    assert counts["state_bytes"] == 4 * (4 * 12 + 64) * 2 * 2 * 8 * 4


def test_short_groups_allocate_both_kinds_alike(engine):
    """One chunk holds the group: a sliding and a full layer's cache are
    the chunk's length, and the window count is the cache count."""
    _, counts = engine._embed_group_device(
        [np.arange(20, 23, dtype=np.int32)])
    assert (counts["kv_positions"], counts["kv_positions_window"]) == (4, 4)
    assert counts["window_steps_run"] == counts["cache_steps_run"] == 16


def test_counts_ride_the_spans(params, engine):
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
    want = 0
    for s in seqs:
        _, chosen = reference(params, jnp.asarray(s)[None])
        want += sum(int(((c >= 4) & (c < 12)).sum()) for c in chosen)
    a = fin["attrs"]
    assert a["routed_rows"] == want > 0
    assert a["moe_programs"] == 5       # chunks of 4: rows 4, 4, 2, 1, 1
    assert a["expert_rows_mean"] == pytest.approx(want / (5 * 4 * 8))
    (group,) = [s for s in spans if s["name"] == "engine.group"]
    g = group["attrs"]
    assert (g["kv_positions"], g["kv_positions_window"]) == (32, 12)
    assert g["window_steps_run"] < g["cache_steps_run"]


def test_the_kernel_count_rides_the_finalize_span(params, engine):
    """``attention_kernel_layers``: 0 here (the rule sees the CPU)."""
    log = []
    tracer = tracing.Tracer(max_traces=4, max_live=16)
    tracer.on_trace(log.append)
    root = tracer.start_span("doc")
    engine.embed_ids_batch([np.arange(20, 32, dtype=np.int32)],
                           ctxs=[root.context])
    root.end()
    (fin,) = [s for t in log for s in t["spans"]
              if s["name"] == "engine.finalize"]
    assert fin["attrs"]["attention_kernel_layers"] == 0
    assert fin["attrs"]["moe_programs"] == 3


def test_the_encoder_on_the_kernel_equals_the_reference(
        monkeypatch, params, tokens, want):
    """Every layer's core through the Pallas kernel (interpreted), the
    rings wrapping three times, and the count says five layers."""
    _the_rule_says_kernel(monkeypatch, (4, 4))
    enc = build_encoder(config(), params)
    got, states = streamed(enc, params, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert enc.counter_attrs([np.asarray(states["counts"])])[
        "attention_kernel_layers"] == 5


def test_the_encoder_on_the_grouped_matmul_kernels_equals_the_reference(
        monkeypatch, params, tokens, want):
    """Every expert layer's two grouped products through ``ops/gmm.py``'s
    kernels (interpreted), and the count says four layers."""
    the_rule_says_grouped_kernels(monkeypatch)
    enc = build_encoder(config(), params)
    got, states = streamed(enc, params, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert enc.counter_attrs([np.asarray(states["counts"])])[
        "expert_kernel_layers"] == 4


# -- which core: the rule ------------------------------------------------------

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("backend,dtype,T,S,rep,d,kernel", [
    ("tpu", BF16, 512, 4096, 6, 128, True),    # the cell's short group
    ("tpu", BF16, 512, 4608, 6, 128, True),    # its rings
    ("tpu", BF16, 512, 16384, 6, 128, True),   # its global layer
    ("cpu", BF16, 512, 4608, 6, 128, False),
    ("tpu", F32, 512, 4608, 6, 128, False),    # the parity tests' type
    ("tpu", BF16, 512, 512, 6, 128, False),    # one key block
    ("tpu", BF16, 64, 64, 6, 128, False),      # a single-chunk document
    ("tpu", BF16, 500, 4000, 6, 128, False),   # no tile divides it
    ("tpu", BF16, 512, 2048, 4, 64, True),     # granite: PERF.md §6, PR 33
], ids=["short_group", "ring", "global", "cpu", "float32", "one_key_block",
        "single_chunk", "no_tile", "granite_d64"])
def test_the_rule_reads_observables_alone(backend, dtype, T, S, rep, d,
                                          kernel):
    assert attention.core_is_kernel(backend, dtype, T, S, rep, d) is kernel


def test_the_kernels_tiles_are_a_function_of_the_shapes():
    """Aligned to bfloat16's (16, 128) tiles and dividing chunk and
    cache, at every shape the cell runs."""
    for S in (4096, 4608, 16384):
        qb, kb = attention._kernel_tiles(512, S, 6)
        assert 512 % qb == 0 and qb % 16 == 0 and qb & (qb - 1) == 0
        assert S % kb == 0 and kb % 128 == 0 and S > kb
    assert attention._kernel_tiles(500, 4000, 6) is None
    assert [attention._kernel_tiles(512, S, 6) for S in (4096, 4608, 16384)] \
        == [(512, 1024), (512, 1536), (512, 1024)]
    assert attention._kernel_tiles(512, 2048, 4) == (512, 1024)   # granite


def test_no_name_selects_a_core():
    """The core is the code's choice: nothing a caller, a configuration
    or a command line can say names one."""
    import inspect
    import json
    import re
    from pathlib import Path

    from code_intelligence_tpu.models.granite_hybrid import (
        GraniteHybridConfig)

    assert list(inspect.signature(gqa_cached).parameters) == [
        "q", "k", "v", "k_cache", "v_cache", "pos", "scale", "q_block",
        "mxu_dtype", "window", "key_block"]
    words = {"pallas", "kernel", "core", "xla", "interpret", "tile", "tiles"}
    for cls in (AfmoeConfig, GraniteHybridConfig):
        assert not [f.name for f in dataclasses.fields(cls)
                    if words & set(f.name.split("_"))]
    root = Path(attention.__file__).resolve().parents[2]
    for name in ("trinity_large_ep8_share", "granite_4_0_h_micro"):
        serve = json.loads((root / "benchmark" / "configs" /
                            f"{name}.json").read_text())["serve"]
        assert sorted(serve) == ["batch_size", "buckets", "kv_positions",
                                 "scheduler"]
    options = re.compile(r'add_argument\(\s*"--([a-z_0-9-]+)"')
    for cli in ("training/cli.py", "sweep/cli.py", "serving/server.py"):
        names = options.findall(
            (root / "code_intelligence_tpu" / cli).read_text())
        assert names and not [n for n in names
                              if re.search("attention|gqa|core", n)]
    source = Path(attention.__file__).read_text()
    assert "environ" not in source and "getenv" not in source


def test_a_document_past_the_cache_is_refused(engine):
    with pytest.raises(ValueError, match="kv_positions=64"):
        engine.embed_ids_batch([np.full(70, 25, np.int32)])


@pytest.mark.parametrize("scheduler", ["slots", "ragged"])
def test_other_schedulers_refuse_it_by_name(engine, scheduler):
    with pytest.raises(ValueError) as e:
        engine.embed_issues([{"title": "w1", "body": "w2"}],
                            scheduler=scheduler)
    assert scheduler in str(e.value) and "Afmoe" in str(e.value)


# -- the contract ----------------------------------------------------------------

def test_it_satisfies_the_contract_and_counts_its_state(encoder):
    assert isinstance(encoder, ChunkEncoder)
    assert encoder.out_dim == 64
    per_slot = 2 * 2 * 8 * 4           # keys and values, 2 heads x 8 float32
    # one chunk holds it: both kinds at the document's length
    assert (encoder.cache_positions(4), encoder.window_positions(4)) == (4, 4)
    assert encoder.state_bytes_per_row(4) == 5 * 4 * per_slot
    # a grid, not a size a length: kv_positions halved while it holds
    assert [encoder.cache_positions(n) for n in (5, 8, 9, 16, 17, 33, 64)] \
        == [8, 8, 16, 16, 32, 64, 64]
    assert encoder.cache_positions() == 64
    # the ring stops at the window and one chunk
    assert [encoder.window_positions(n) for n in (5, 9, 17, 64)] \
        == [8, 12, 12, 12]
    assert encoder.window_positions() == 12
    assert encoder.state_bytes_per_row(40) == encoder.state_bytes_per_row() \
        == (4 * 12 + 64) * per_slot
    states = encoder.init_states(2, 40)
    got = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(states))
    assert got - 4 - 5 * 4 == 2 * encoder.state_bytes_per_row(40)
    with pytest.raises(ValueError, match="kv_positions=64"):
        encoder.cache_positions(65)
    # no sliding layer: no ring
    full = build_encoder(config(layer_types=[FULL] * 5))
    assert full.window_positions(40) == 0


def test_published_widths_carry_142_6_megabytes_a_row():
    """Shapes only, no weights: 16,384 positions of the one global layer
    and four rings of 4096 + 512 slots, 8 heads of 128 in bfloat16."""
    published = dict(
        vocab_size=25024, num_hidden_layers=5, num_dense_layers=1,
        layer_types=[SLIDING, SLIDING, SLIDING, FULL, SLIDING],
        num_experts=32, experts_held={"first": 0, "count": 32, "of": 256})
    enc = build_encoder(make_config("afmoe", published, kv_positions=16384))
    cfg = enc.config
    assert (cfg.num_experts, cfg.experts_held) == (256, (0, 32))
    assert (cfg.sliding_window, cfg.ring_positions) == (4096, 4608)
    # a window that is no whole number of chunks takes the next one
    assert dataclasses.replace(cfg, sliding_window=4000).ring_positions \
        == 4608
    assert enc.state_bytes_per_row(16384) == 142606336 \
        == 67108864 + 4 * 18874368
    # every layer global would be 335.5 MB
    assert 5 * 67108864 == 335544320
    # the short group of the cell: 6 chunks of 512 on the 4096 grid
    assert (enc.cache_positions(3072), enc.window_positions(3072)) \
        == (4096, 4096)
    assert enc.state_bytes_per_row(3072) == 5 * 4096 * 4096 == 83886080
    shapes = jax.eval_shape(lambda: enc.init_states(16, 16384))
    assert [k.shape[1:3] for k in shapes["k"]] == \
        [(8, 4608)] * 3 + [(8, 16384), (8, 4608)]      # head-major
    assert shapes["k"][0].dtype == jnp.bfloat16


def test_config_from_the_published_keys_and_the_share():
    cfg = config()
    assert (cfg.num_experts, cfg.experts_held) == (16, (4, 8))
    assert cfg.count(SLIDING) == 4 and cfg.n_moe_layers == 4
    assert hash(cfg) == hash(config())
    whole = make_config("afmoe", {k: v for k, v in UNCUT.items()
                                  if k != "experts_held"})
    assert whole.experts_held == (0, 16)
    with pytest.raises(ValueError, match="not the count"):
        make_config("afmoe", dict(MODEL, num_experts=16))
    with pytest.raises(ValueError, match="outside the router"):
        dataclasses.replace(cfg, experts_held=(12, 8))
    with pytest.raises(ValueError, match="sigmoid"):
        dataclasses.replace(cfg, score_func="softmax")
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(cfg, layer_types=("mamba",) * 5)
    with pytest.raises(ValueError, match="rope_scaling"):
        dataclasses.replace(cfg, rope_scaling={"type": "yarn"})


def test_the_table_has_a_fourth_row():
    assert type(config()) is AfmoeConfig
    assert contract.ENCODERS["afmoe"][0] is AfmoeConfig
    enc = build_encoder(config())
    assert isinstance(enc, AfmoeEncoder) and isinstance(enc, ChunkEncoder)
    assert enc.state_counters(enc.init_states(1)).shape == (5,)
    assert enc.counter_attrs([]) == {}


def test_export_round_trip_in_bfloat16(tmp_path, vocab):
    from code_intelligence_tpu.training.checkpoint import export_encoder

    cfg = make_config("afmoe", MODEL, kv_positions=64, chunk_positions=8)
    weights = seeded(ref, 1, MODEL, dtype=jnp.bfloat16)
    export_encoder(tmp_path, weights, cfg, vocab)
    eng = InferenceEngine.from_export(tmp_path, buckets=(8,), batch_size=2)
    assert eng.config == cfg and eng.encoder.dtype == jnp.bfloat16
    assert eng._enc_params["params"]["layers"]["layer_2"]["bias"].dtype \
        == jnp.float32
    direct = InferenceEngine(weights, cfg, vocab, buckets=(8,), batch_size=2)
    seqs = [np.arange(20, 45, dtype=np.int32)]
    np.testing.assert_array_equal(eng.embed_ids_batch(seqs),
                                  direct.embed_ids_batch(seqs))
