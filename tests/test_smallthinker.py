"""The SmallThinker encoder (a router that reads the layer's input before
attention, softmax-routed ReGLU experts all held, one NoPE global GQA
layer to three rotary sliding-window ones at 7 query heads a key/value
head) and the encoder contract's sixth member; ``ops/moe.py``'s softmax
score, its ReGLU experts and a sort made ahead of the apply.

Small on the CPU (hidden 64, 14 / 2 heads of 8, window 8, two periods of
four layers, 16 experts all held, 6 a token), every comparison against
the plain reference (`benchmark/reference/smallthinker.py`) on seeded
weights, in float32 unless said.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark.harness import traffic
from benchmark.harness.cell import load_driver
from benchmark.reference import common
from benchmark.reference import smallthinker as ref
from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.models import (
    ChunkEncoder, SmallThinkerConfig, SmallThinkerEncoder, build_encoder,
    make_config)
from code_intelligence_tpu.models import contract
from code_intelligence_tpu.ops import attention, moe
from code_intelligence_tpu.text import SPECIALS, Vocab
from code_intelligence_tpu.utils import tracing
from encoder_programs import (
    compiled, seeded, the_rule_says_grouped_kernels)

ROOT = Path(__file__).resolve().parents[1]
PERIOD = [0, 1, 1, 1]
MODEL = {
    "vocab_size": 300, "hidden_size": 64, "num_hidden_layers": 8,
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 8,
    "moe_ffn_hidden_size": 16, "moe_num_primary_experts": 16,
    "moe_num_active_primary_experts": 6,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rope_layout": PERIOD * 2, "sliding_window_layout": PERIOD * 2,
    "sliding_window_size": 8, "rms_norm_eps": 1e-6, "rope_theta": 1500000,
    "rope_scaling": None, "max_position_embeddings": 16384,
    "model_name": "tiny", "tie_word_embeddings": False}
TAILS = {"dist": "student_t", "df": 4}
T_DOC = 40   # five windows: a ring of 8 + 4 slots wraps three times


@pytest.fixture(scope="module")
def params():
    return seeded(ref, 39, MODEL, TAILS)


def config(**extra):
    return make_config("smallthinker", MODEL, **dict(
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


def whole(enc_config, params, tokens):
    """The document as ONE chunk program."""
    enc = build_encoder(enc_config, params)
    return jax.jit(enc.encode)(
        params, tokens, enc.init_states(*tokens.shape))[0]


# -- ops/moe.py: the softmax score, route and apply apart ----------------------

def test_the_softmax_score_is_a_softmax_over_all_the_top_six_renormalised():
    x = jax.random.normal(jax.random.PRNGKey(2), (50, 64))
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 16)) / 8
    experts, weights = moe.route(x, w, None, 1, 1, 6, 1.0,
                                 score_func="softmax")
    with jax.default_matmul_precision("highest"):
        every = jax.nn.softmax(x @ w, axis=-1)
        r_experts, r_weights, _ = ref.route(x, w, MODEL)
    top, where = lax.top_k(every, 6)
    np.testing.assert_array_equal(experts, where)
    np.testing.assert_array_equal(experts, r_experts)
    np.testing.assert_allclose(weights, top / top.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(weights, r_weights, rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="score_func 'tanh'"):
        moe.route(x, w, None, 1, 1, 6, 1.0, score_func="tanh")


def _expert_layer_as_it_was(p, u, valid, dtype, *, n_group, topk_group,
                            top_k, scaling, norm_topk_prob, first, shared):
    """``ops/moe.py::expert_layer`` as PR 37's tree had it, its
    ``routed_experts`` written out: route, sort and apply on one
    tensor."""
    experts, weights = moe.route(
        u, p["router"], p["bias"], n_group, topk_group, top_k, scaling,
        norm_topk_prob)
    x, w_in, w_out = u, p["experts_in"], p["experts_out"]
    N, E = x.shape
    count = w_in.shape[0]
    order, per_expert = moe.assign(experts, first, count, valid)
    ends = jnp.cumsum(per_expert)
    total = ends[-1]
    order = jnp.concatenate([order, jnp.zeros((N,), jnp.int32)])
    flat_w = weights.reshape(-1)

    def one_round(r, y):
        start = r * N
        picked = lax.dynamic_slice_in_dim(order, start, N)
        token = picked // top_k
        live = start + jnp.arange(N) < total
        sizes = jnp.diff(jnp.clip(ends - start, 0, N), prepend=0)
        xs = jnp.take(x, token, axis=0).astype(dtype)
        g, v = jnp.split(lax.ragged_dot(
            xs, w_in, sizes, preferred_element_type=dtype), 2, axis=-1)
        act = jax.nn.silu(g.astype(jnp.float32)) * v.astype(jnp.float32)
        out = lax.ragged_dot(act.astype(dtype), w_out, sizes,
                             preferred_element_type=jnp.float32)
        out = jnp.where(live[:, None],
                        out * jnp.take(flat_w, picked)[:, None], 0.0)
        return y.at[token].add(out)

    y = lax.fori_loop(0, (total + N - 1) // N, one_round,
                      jnp.zeros((N, E), jnp.float32))
    if shared:
        y = y + moe.swiglu(u, p["shared_in"], p["shared_out"], dtype)
    return y, per_expert


# (n_group, topk_group, top_k, scaling, shared, (first, count), lanes left
# out): the arguments of the three sigmoid models' encoders
@pytest.mark.parametrize("n_group,topk_group,top_k,scaling,shared,held,pad", [
    (4, 2, 4, 2.5, True, (4, 8), 0),      # models/deepseek_v3.py
    (4, 2, 4, 2.5, True, (4, 8), 7),
    (1, 1, 4, 2.448, True, (0, 16), 0),   # models/afmoe.py
    (1, 1, 4, 2.448, False, (8, 8), 5),
    (4, 2, 8, 2.5, True, (0, 8), 0),      # models/bailing_hybrid.py
    (4, 2, 8, 2.5, True, (0, 16), 3),     # every expert held: one pass
    (4, 2, 8, 2.5, True, (0, 12), 3),     # more than N land here: rounds
], ids=["deepseek", "deepseek_padded", "afmoe_whole", "afmoe_no_shared",
        "bailing", "bailing_one_pass", "bailing_rounds"])
def test_route_sort_and_apply_on_one_tensor_is_expert_layer_as_it_was(
        n_group, topk_group, top_k, scaling, shared, held, pad):
    """The three callers of ``expert_layer`` compute what they computed
    when the rows came back by a scatter-add (the frozen copy above):
    the rows each expert ran to the bit, the values to float32's last
    places, since a token's rows are now summed in another order."""
    first, count = held
    k = jax.random.split(jax.random.PRNGKey(7), 7)
    p = {"router": jax.random.normal(k[0], (64, 16)) / 8,
         "bias": 0.05 * jax.random.normal(k[1], (16,)),
         "experts_in": jax.random.normal(k[2], (count, 64, 32)) / 8,
         "experts_out": jax.random.normal(k[3], (count, 16, 64)) / 4,
         "shared_in": jax.random.normal(k[4], (64, 32)) / 8,
         "shared_out": jax.random.normal(k[5], (16, 64)) / 4}
    u = jax.random.normal(k[6], (24, 64))
    valid = (jnp.arange(24) < 24 - pad) if pad else None
    kw = dict(n_group=n_group, topk_group=topk_group, top_k=top_k,
              scaling=scaling, norm_topk_prob=True, first=first,
              shared=shared)
    want, want_rows = jax.jit(lambda p, u: _expert_layer_as_it_was(
        p, u, valid, jnp.float32, **kw))(p, u)
    got, rows = jax.jit(lambda p, u: moe.expert_layer(
        p, u, valid, jnp.float32, **kw))(p, u)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(rows, want_rows)
    # and the steps by hand, the sort made beside the router
    experts, weights = moe.route(u, p["router"], p["bias"], n_group,
                                 topk_group, top_k, scaling)
    assigned = moe.assign(experts, first, count, valid)
    assert assigned[0].shape == (24 * top_k,)
    by_hand, _ = moe.routed_experts(
        u, experts, weights, p["experts_in"], p["experts_out"], first, 16,
        assigned=assigned)
    if shared:
        by_hand = by_hand + moe.swiglu(u, p["shared_in"], p["shared_out"],
                                       jnp.float32)
    np.testing.assert_allclose(by_hand, want, rtol=1e-6, atol=1e-6)


def _dense_held_part(x, experts, weights, w_in, w_out, first, valid, act):
    """``sum over chosen j in [first, first + count)  w_j E_j(x)`` a
    token, every held expert run over ALL tokens: no sort, no rounds."""
    gate = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act]
    y = jnp.zeros(x.shape, jnp.float32)
    for j in range(w_in.shape[0]):
        g, u = jnp.split(x @ w_in[j], 2, axis=-1)
        w_j = jnp.where(experts == first + j, weights, 0.0).sum(-1)
        y = y + w_j[:, None] * ((gate(g) * u) @ w_out[j])
    return y if valid is None else jnp.where(valid[:, None], y, 0.0)


# name: (n_experts, top_k, (first, count), padding lanes, act, the N = 24
# tokens a round takes, 1 for a share and ``top_k`` where every expert is
# held (``moe.one_pass``), rounds, rows of the last round). The router may
# be wider than the experts a token chooses among (the first 16): its
# width decides between rounds and one pass, the choices fill the rounds
_COMBINE_CASES = {
    "all_held_one_pass": (16, 6, (0, 16), 0, "relu", 6, 1, 144),
    "one_pass_part_full": (16, 6, (0, 16), 7, "relu", 6, 1, 102),
    "six_rounds_of_a_wide_router": (64, 6, (0, 16), 0, "relu", 1, 6, 24),
    "last_round_part_full": (64, 6, (0, 16), 7, "relu", 1, 5, 6),
    "a_share_in_one_round": (16, 4, (4, 2), 0, "silu", 1, 1, None),
    "a_share_in_three_rounds": (16, 8, (0, 8), 0, "silu", 1, None, None),
    "nothing_lands_here": (16, 2, (12, 4), 0, "silu", 1, 0, None),
    "padding_lanes_in_a_share": (16, 4, (8, 8), 9, "relu", 1, None, None),
    "every_lane_padding": (16, 6, (0, 16), 24, "relu", 6, 0, None),
}


def _combine_case(name):
    n_experts, top_k, (first, count), pad, act, m, rounds, last = \
        _COMBINE_CASES[name]
    assert moe.one_pass(top_k, count, n_experts) == (m == top_k)
    k = jax.random.split(jax.random.PRNGKey(41), 5)
    x = jax.random.normal(k[0], (24, 64))
    # top_k distinct experts a token, weights that sum to 1
    experts = jnp.argsort(jax.random.uniform(k[1], (24, 16)),
                          axis=-1)[:, :top_k].astype(jnp.int32)
    if name == "nothing_lands_here":
        experts = experts % first    # all under the held range
    weights = jax.nn.softmax(jax.random.normal(k[2], (24, top_k)), axis=-1)
    w_in = jax.random.normal(k[3], (count, 64, 32)) / 8
    w_out = jax.random.normal(k[4], (count, 16, 64)) / 4
    valid = (jnp.arange(24) < 24 - pad) if pad else None
    return (x, experts, weights, w_in, w_out, first, n_experts, valid,
            act), 24 * m, rounds, last


@pytest.mark.parametrize("name", list(_COMBINE_CASES))
def test_the_rows_come_back_as_the_dense_weighted_sum(name):
    """Whatever the rounds (none, one pass, several, a last one part
    full), every token gets ``sum_k w_k E_k(x)`` over the held experts it
    chose, a token whose choices fell in different rounds too, and a
    padding lane exact zeros."""
    args, size, rounds, last = _combine_case(name)
    x, experts, weights, w_in, w_out, first, n_experts, valid, act = args
    got, rows = jax.jit(lambda *a: moe.routed_experts(
        *a, first, n_experts, valid, act))(x, experts, weights, w_in, w_out)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: _dense_held_part(
            *a, first, valid, act))(x, experts, weights, w_in, w_out)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    total = int(rows.sum())
    held = (experts >= first) & (experts < first + w_in.shape[0])
    if valid is not None:
        held = held & valid[:, None]
        assert float(jnp.abs(got[~np.asarray(valid)]).max()) == 0.0
    assert total == int(held.sum())
    assert int(moe.rounds_run(rows.sum(), 24, experts.shape[1],
                              w_in.shape[0], n_experts)) == -(-total // size)
    if rounds is not None:
        assert -(-total // size) == rounds
    if last is not None:
        assert total - (rounds - 1) * size == last
    if total == 0:
        assert float(jnp.abs(got).max()) == 0.0
    if total > size:
        # some token's rows were made in different rounds
        order, _ = moe.assign(experts, first, w_in.shape[0], valid)
        at = np.argsort(np.asarray(order)).reshape(24, -1) // size
        at = np.where(np.asarray(held), at, at.max(-1, keepdims=True))
        assert (at.min(-1) < at.max(-1)).any()


@pytest.mark.parametrize("name", ["all_held_one_pass",
                                  "six_rounds_of_a_wide_router",
                                  "a_share_in_one_round",
                                  "padding_lanes_in_a_share"])
def test_a_sort_handed_in_and_one_made_inside_give_the_same_bits(name):
    args, _, _, _ = _combine_case(name)
    x, experts, weights, w_in, w_out, first, n_experts, valid, act = args
    inside = jax.jit(lambda *a: moe.routed_experts(
        *a, first, n_experts, valid, act))
    handed = jax.jit(lambda *a: moe.routed_experts(
        *a, first, n_experts, valid, act,
        assigned=moe.assign(a[1], first, w_in.shape[0], valid)))
    for got, want in zip(handed(x, experts, weights, w_in, w_out),
                         inside(x, experts, weights, w_in, w_out)):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", ["a_share_in_one_round",
                                  "last_round_part_full",
                                  "nothing_lands_here"])
def test_rows_no_round_wrote_never_reach_the_sum(monkeypatch, name):
    """The buffer of weighted rows starts unwritten: with NaN in every
    row a round did not write, the sum is what it was."""
    args, _, _, _ = _combine_case(name)
    want, _ = moe.routed_experts(*args)
    monkeypatch.setattr(moe.lax, "empty", lambda shape, dtype: jnp.full(
        shape, jnp.nan, dtype))
    got, _ = moe.routed_experts(*args)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("pad", [0, 5], ids=["whole", "padded"])
@pytest.mark.parametrize("name,rounds", [
    ("all_held_one_pass", ""), ("six_rounds_of_a_wide_router", "while/body/")])
def test_the_combine_lowers_to_a_gather_and_the_scopes_stand(name, rounds,
                                                             pad):
    """The combine is a gather by the sort's inverse: the lowered program
    holds no scatter, and the scopes the benchmark's readers match are
    where they were, under the loop where there are rounds and beside
    it where every expert is held."""
    args, _, _, _ = _combine_case(name)
    x, experts, weights, w_in, w_out, first, n_experts, _, act = args
    valid = (jnp.arange(24) < 24 - pad) if pad else None

    def layer(*a):
        with jax.named_scope("moe_7"):
            return moe.routed_experts(*a, first, n_experts, valid, act)

    lowered = jax.jit(layer).lower(x, experts, weights, w_in, w_out)
    compiled_text = lowered.compile().as_text()
    assert "scatter" not in lowered.as_text()
    assert "scatter" not in compiled_text
    text = lowered.as_text(debug_info=True)
    for scope in ("moe_7/dispatch", f"moe_7/{rounds}dispatch",
                  f"moe_7/{rounds}experts", f"moe_7/{rounds}combine",
                  "moe_7/combine"):
        assert scope in text, scope
    assert "f32[144,64]" in compiled_text  # the buffer: float32 rows


def test_all_held_six_a_token_is_one_pass_and_drops_nothing(
        monkeypatch, params):
    """Every expert held: 6 x 24 assignments in one pass and no loop's
    turn, as with seven padding lanes left out (6 x 17 = 102 rows);
    equal to the reference's dense loop."""
    p = params["layers"]["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    m = jax.random.normal(jax.random.PRNGKey(8), (24, 64))
    with jax.default_matmul_precision("highest"):
        r_experts, r_weights, _ = ref.route(x, p["router"], MODEL)
        want = ref.routed_part(p, m, r_experts, r_weights, 0)
    trips = []
    real = lax.fori_loop

    def counting(lo, hi, body, init):
        trips.append(int(hi))
        return real(lo, hi, body, init)

    monkeypatch.setattr(lax, "fori_loop", counting)
    experts, weights = moe.route(x, p["router"], None, 1, 1, 6, 1.0,
                                 score_func="softmax")
    got, per_expert = moe.routed_experts(
        m, experts, weights, p["experts_in"], p["experts_out"], 0, 16,
        act="relu", assigned=moe.assign(experts, 0, 16))
    assert int(per_expert.sum()) == 6 * 24 and trips == []
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    valid = jnp.arange(24) < 17
    got, per_expert = moe.routed_experts(
        m, experts, weights, p["experts_in"], p["experts_out"], 0, 16, valid,
        act="relu")
    assert int(per_expert.sum()) == 6 * 17 and trips == []
    np.testing.assert_allclose(got[:17], want[:17], rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(got[17:]).max()) == 0.0


def test_two_shares_of_half_the_experts_add_up_to_the_whole_layer(params):
    """The layer is still told what it holds: the parts of experts 0..7
    and of 8..15, each applied to its own half, sum to the
    uncut reference's routed part; one half alone does not."""
    p = params["layers"]["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(9), (40, 64))
    m = jax.random.normal(jax.random.PRNGKey(10), (40, 64))
    @jax.jit
    def the_references(p, x, m):
        r_experts, r_weights, _ = ref.route(x, p["router"], MODEL)
        return ref.routed_part(p, m, r_experts, r_weights, 0)

    with jax.default_matmul_precision("highest"):
        want = the_references(p, x, m)
    experts, weights = moe.route(x, p["router"], None, 1, 1, 6, 1.0,
                                 score_func="softmax")
    # ``first`` is traced: one program for both halves
    half = jax.jit(lambda w_in, w_out, first: moe.routed_experts(
        m, experts, weights, w_in, w_out, first, 16, act="relu"))
    total, rows = 0.0, 0
    for first in (0, 8):
        part, per_expert = half(p["experts_in"][first:first + 8],
                                p["experts_out"][first:first + 8],
                                jnp.int32(first))
        total = total + part
        rows += int(per_expert.sum())
    assert rows == 40 * 6
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(part - want).max()) > 1e-2
    # and through the encoder's configuration of a share
    cfg = make_config("smallthinker", dict(
        MODEL, moe_num_primary_experts=8,
        experts_held={"first": 8, "count": 8, "of": 16}))
    assert (cfg.moe_num_primary_experts, cfg.experts_held) == (16, (8, 8))


def test_the_gates_activation_reaches_the_routed_experts():
    """One expert held and chosen by every token: ``act="relu"`` is the
    reference's ReGLU, the default SwiGLU."""
    k = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(k[0], (10, 64))
    w_in = jax.random.normal(k[1], (1, 64, 32)) / 8
    w_out = jax.random.normal(k[2], (1, 16, 64)) / 4
    experts = jnp.zeros((10, 1), jnp.int32)
    weights = jnp.ones((10, 1), jnp.float32)
    with jax.default_matmul_precision("highest"):
        g, u = jnp.split(x @ w_in[0], 2, axis=-1)
        np.testing.assert_allclose(
            moe.routed_experts(x, experts, weights, w_in, w_out, 0, 1,
                               act="relu")[0],
            ref.reglu(x, w_in[0], w_out[0]), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            moe.routed_experts(x, experts, weights, w_in, w_out, 0, 1)[0],
            (jax.nn.silu(g) * u) @ w_out[0], rtol=2e-5, atol=2e-5)


# -- the encoder against the reference ------------------------------------------

def test_encoder_equals_the_reference(params, tokens, want):
    """The whole document as ONE chunk, float32: what is left is the
    order of float32 sums (the grouped matmul's, the blocked softmax's)
    through 16 residual branches."""
    got = whole(config(chunk_positions=T_DOC), params, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_bfloat16_program_is_near_the_reference(params, tokens, want):
    """bfloat16 weights, matmul inputs and caches against the float32
    reference on the SAME (bfloat16-rounded) weights: 8 bits of mantissa
    through 16 branches, and a top-6 choice that a rounding can flip, so
    the bound is on the relative RMS error, not on the worst element."""
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    want = reference(half, tokens)[0]
    enc = build_encoder(config(state_dtype=jnp.bfloat16), half)
    assert enc.dtype == jnp.bfloat16
    got, _ = streamed(enc, half, tokens)
    err = float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))
    assert 1e-4 < err < 0.05


@pytest.mark.parametrize("chunk", [20, 8, 4], ids=["2", "5", "10"])
def test_streamed_through_rings_that_wrap_equals_one_program(
        params, tokens, want, chunk):
    """The document in 2, 5 and 10 chunk programs: rings of the window
    in whole chunks + one chunk (28, 16, 12 slots) that wrap up to three
    times beside two caches that grow."""
    enc = build_encoder(config(chunk_positions=chunk), params)
    got, states = streamed(enc, params, tokens, chunk=chunk)
    ring = chunk * (-(-8 // chunk) + 1)
    assert [c.shape[2] for c in states["k"]] == [64, ring, ring, ring] * 2
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    counts = np.asarray(states["counts"])
    assert counts[2] == T_DOC // chunk           # programs
    assert counts[0] == 8 * 6 * 3 * T_DOC        # every assignment ran
    assert counts[3] == 8 * (T_DOC // chunk)     # one pass a layer


def _differs(got, want, start=12):
    """Largest difference over the positions past the first window."""
    return float(jnp.abs(got - want)[:, start:].max())


def test_dropped_rings_are_seen(params, encoder, tokens, want):
    def dropped(states):
        return dict(states, **{name: tuple(
            jnp.zeros_like(c) if sliding else c
            for c, sliding in zip(states[name], PERIOD * 2))
            for name in "kv"})

    got, _ = streamed(encoder, params, tokens, between=dropped)
    assert _differs(got, want) > 0.05


def test_a_missing_window_is_seen(params, tokens, want):
    got = whole(config(sliding_window_size=1 << 20, chunk_positions=T_DOC),
                params, tokens)
    np.testing.assert_allclose(got[:, :8], want[:, :8], rtol=2e-5,
                               atol=2e-5)    # inside the first window
    assert _differs(got, want) > 0.05


def test_rotary_on_the_global_layers_is_seen(params, tokens, want):
    got = whole(config(rope_layout=[1] * 8, chunk_positions=T_DOC), params,
                tokens)
    assert _differs(got, want, 0) > 0.05
    got = whole(config(rope_layout=[0] * 8, chunk_positions=T_DOC), params,
                tokens)
    assert _differs(got, want, 0) > 0.05


@pytest.mark.parametrize("control", [
    {"early_router": "off"}, {"router_score": "sigmoid"},
    {"expert_act": "silu"}], ids=lambda c: "=".join(*c.items()))
def test_the_routing_controls_are_seen(params, tokens, want, control):
    """The benchmark driver's three program-only routing controls, at
    the encoder: a router that reads the experts' input, sigmoid weights
    and SwiGLU experts each move the rows; the wrappers last one
    trace."""
    driver = load_driver("bulk_early_route_moe")
    real = moe.route, moe.routed_experts
    enc = build_encoder(config(chunk_positions=T_DOC), params)
    with driver._moe_as(control):      # traced under the wrappers
        got = compiled(enc)(params, tokens, enc.init_states(3, T_DOC))[0]
    assert (moe.route, moe.routed_experts) == real
    assert _differs(got, want, 0) > 0.02


def test_float32_routing_is_the_references_and_reads_the_layers_input(
        monkeypatch, params, tokens):
    """The experts chosen, layer by layer, are the reference's; and the
    tensor the router read is the residual stream as the layer got it:
    the embedding itself in layer 0."""
    _, want = reference(params, tokens)
    seen, read = [], []
    real = moe.route

    def listening(h, *a, **kw):
        experts, weights = real(h, *a, **kw)
        seen.append(np.asarray(experts))
        read.append(np.asarray(h))
        return experts, weights

    monkeypatch.setattr(moe, "route", listening)
    enc = build_encoder(config(chunk_positions=T_DOC), params)
    enc.encode(params, tokens, enc.init_states(3, T_DOC))
    assert len(seen) == len(want) == 8
    for g, w in zip(seen, want):
        np.testing.assert_array_equal(np.sort(g, -1), np.sort(w, -1))
    np.testing.assert_array_equal(
        read[0], np.asarray(params["embedding"])[np.asarray(tokens)].reshape(
            -1, 64))


def test_the_sort_is_made_before_attention_in_program_order(params, tokens):
    """``route_<i>`` (router, top-k, sort) comes before ``attention_<i>``
    and takes nothing from it: in the jaxpr the layer's sort precedes
    its first cache update."""
    enc = build_encoder(config(), params)
    text = str(jax.make_jaxpr(enc.encode)(
        params, tokens[:, :4], enc.init_states(3, T_DOC)))
    first_sort = text.index(" sort[")
    first_cache = text.index("dynamic_update_slice")
    assert first_sort < first_cache
    lowered = jax.jit(enc.encode).lower(
        params, tokens[:, :4], enc.init_states(3, T_DOC)).as_text(
            debug_info=True)
    for name in ("route_0/router", "route_0/dispatch", "attention_0/qkv_proj",
                 "attention_0/global_core", "attention_1/rope",
                 "attention_1/window_core", "attention_1/o_proj",
                 "moe_7/dispatch", "moe_7/experts", "moe_7/combine"):
        assert name in lowered, name
    assert "attention_0/rope" not in lowered     # NoPE
    assert "moe_7/while/body/experts" not in lowered  # one pass, no loop


# -- the attention kernel at seven heads a group --------------------------------

def test_the_kernels_tile_at_seven_heads_a_group():
    """7 x 512 = 3584 rows pass ``_TILE_ROWS``: query blocks of 256."""
    assert [attention._kernel_tiles(512, S, 7) for S in (4096, 4608, 16384)] \
        == [(256, 1024), (256, 1536), (256, 1024)]
    for S in (4096, 4608, 16384):
        assert attention.core_is_kernel("tpu", jnp.bfloat16, 512, S, 7, 128)
    assert not attention.core_is_kernel("cpu", jnp.bfloat16, 512, 4608, 7,
                                        128)


@pytest.mark.parametrize("window,S,T,tiles,dtype", [
    (8, 16, 8, (4, 8), jnp.float32),
    (None, 64, 8, (4, 16), jnp.float32),
    (16, 32, 16, (16, 16), jnp.bfloat16),
], ids=["ring", "global", "ring_bf16"])
def test_the_kernel_at_seven_heads_a_group_equals_a_dense_masked_softmax(
        monkeypatch, window, S, T, tiles, dtype):
    """``gqa_cached`` on the Pallas kernel, interpreted, 14 / 2 heads:
    the row-to-query mask (``& (q_block - 1)``) over 7 x q_block rows."""
    monkeypatch.setattr(attention, "core_is_kernel", lambda *a: True)
    monkeypatch.setattr(attention, "_kernel_tiles", lambda *a: tiles)
    k = jax.random.split(jax.random.PRNGKey(48), 3)
    q = jax.random.normal(k[0], (2, 48, 14, 8))
    kk = jax.random.normal(k[1], (2, 48, 2, 8))
    v = jax.random.normal(k[2], (2, 48, 2, 8))
    kc = vc = jnp.zeros((2, 2, S, 8), dtype)
    outs = []
    for lo in range(0, 48, T):
        out, kc, vc = attention.gqa_cached(
            q[:, lo:lo + T], kk[:, lo:lo + T], v[:, lo:lo + T], kc, vc,
            jnp.int32(lo), 0.3, mxu_dtype=dtype, window=window)
        outs.append(out)
    got = jnp.concatenate(outs, 1)
    if dtype == jnp.bfloat16:     # the products' operands are rounded
        q, kk, v = (x.astype(dtype).astype(jnp.float32) for x in (q, kk, v))
    s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(kk, 7, axis=2)) * 0.3
    t, j = jnp.arange(48)[:, None], jnp.arange(48)[None, :]
    seen = (j <= t) & ((t - j < window) if window else True)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(
        jnp.where(seen, s, -jnp.inf), -1), jnp.repeat(v, 7, axis=2))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_the_encoder_on_the_kernel_equals_the_reference(
        monkeypatch, params, tokens, want):
    monkeypatch.setattr(attention, "core_is_kernel", lambda *a: True)
    monkeypatch.setattr(attention, "_kernel_tiles", lambda *a: (4, 4))
    enc = build_encoder(config(), params)
    got, states = streamed(enc, params, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert enc.counter_attrs([np.asarray(states["counts"])])[
        "attention_kernel_layers"] == 8


def test_the_encoder_on_the_grouped_matmul_kernels_equals_the_reference(
        monkeypatch, params, tokens, want):
    """Every expert layer's two grouped products through ``ops/gmm.py``'s
    kernels (interpreted), and the count says eight layers."""
    the_rule_says_grouped_kernels(monkeypatch)
    enc = build_encoder(config(), params)
    got, states = streamed(enc, params, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert enc.counter_attrs([np.asarray(states["counts"])])[
        "expert_kernel_layers"] == 8


# -- through the engine's normal path -------------------------------------------

@pytest.fixture(scope="module")
def engine(params, vocab):
    return InferenceEngine(params, config(), vocab, buckets=(4,),
                           batch_size=4)


def test_chunked_through_both_kinds_of_state_with_narrowing(
        params, engine, vocab):
    """One group of four at bucket 4: lengths 3, 9, 22 and 40; the batch
    narrows and the longest document's rings wrap three times; every row
    is the reference's whole-document forward for that document alone."""
    rng = np.random.default_rng(7)
    seqs = [rng.integers(20, 300, n).astype(np.int32)
            for n in (40, 3, 9, 22)]
    got = engine.embed_ids_batch(seqs)
    assert got.shape == (4, 3 * 64) == (4, engine.embed_dim)
    encode = jax.jit(lambda p, t: ref.encode(p, t, MODEL)[0])
    want = common.pooled_rows(encode, params, seqs, vocab.pad_id, T_DOC,
                              block_rows=4)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    _, counts = engine._embed_group_device(sorted(seqs, key=len))
    rows = [4, 4, 4, 2, 2, 2, 1, 1, 1, 1]
    assert counts["chunks"] == 10
    assert (counts["kv_positions"], counts["kv_positions_window"]) \
        == (64, 12)
    assert counts["window_steps_run"] == sum(
        r * min(4 * (i + 1), 12) for i, r in enumerate(rows))
    # 6 rings of 12 slots and two caches of 64, keys and values of
    # 2 heads x 8 float32
    assert counts["state_bytes"] == 4 * (6 * 12 + 2 * 64) * 2 * 2 * 8 * 4


def test_counts_ride_the_finalize_span(params, engine):
    """Every assignment of every valid token ran (6 a token a layer, all
    held), in one pass a layer a program whatever its valid tokens:
    chunks of 4 in programs of rows 4, 4, 2, 1, 1."""
    rng = np.random.default_rng(11)
    seqs = [rng.integers(20, 300, n).astype(np.int32) for n in (5, 12, 20)]
    log = []
    tracer = tracing.Tracer(max_traces=4, max_live=16)
    tracer.on_trace(log.append)
    roots = [tracer.start_span("doc") for _ in seqs]
    engine.embed_ids_batch(seqs, ctxs=[r.context for r in roots])
    for r in roots:
        r.end()
    (fin,) = [s for t in log for s in t["spans"]
              if s["name"] == "engine.finalize"]
    a = fin["attrs"]
    assert a["routed_rows"] == 8 * 6 * (5 + 12 + 20)
    assert a["moe_programs"] == 5
    assert a["expert_rows_mean"] == pytest.approx(
        8 * 6 * 37 / (5 * 8 * 16))
    assert a["expert_rounds_mean"] == 1.0
    assert a["attention_kernel_layers"] == 0     # the rule sees the CPU
    assert a["expert_kernel_layers"] == 0


def test_a_document_past_the_cache_is_refused(engine):
    with pytest.raises(ValueError, match="kv_positions=64"):
        engine.embed_ids_batch([np.full(70, 25, np.int32)])


@pytest.mark.parametrize("scheduler", ["slots", "ragged"])
def test_other_schedulers_refuse_it_by_name(engine, scheduler):
    with pytest.raises(ValueError) as e:
        engine.embed_issues([{"title": "w1", "body": "w2"}],
                            scheduler=scheduler)
    assert scheduler in str(e.value) and "SmallThinker" in str(e.value)


def test_the_engine_has_no_branch_for_it():
    source = (ROOT / "code_intelligence_tpu/inference/engine.py").read_text()
    assert "smallthinker" not in source.lower()


# -- the contract ----------------------------------------------------------------

def test_it_satisfies_the_contract_and_counts_its_state(encoder):
    assert isinstance(encoder, ChunkEncoder)
    assert encoder.out_dim == 64
    per_slot = 2 * 2 * 8 * 4           # keys and values, 2 heads x 8 float32
    assert (encoder.cache_positions(4), encoder.window_positions(4)) == (4, 4)
    assert encoder.state_bytes_per_row(4) == 8 * 4 * per_slot
    assert [encoder.cache_positions(n) for n in (5, 8, 9, 16, 17, 33, 64)] \
        == [8, 8, 16, 16, 32, 64, 64]
    assert [encoder.window_positions(n) for n in (5, 9, 17, 64)] \
        == [8, 12, 12, 12]
    assert encoder.state_bytes_per_row(40) == encoder.state_bytes_per_row() \
        == (6 * 12 + 2 * 64) * per_slot
    states = encoder.init_states(2, 40)
    got = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(states))
    assert got - 4 - 6 * 4 == 2 * encoder.state_bytes_per_row(40)
    with pytest.raises(ValueError, match="kv_positions=64"):
        encoder.cache_positions(65)
    # no sliding layer: no ring
    full = build_encoder(config(sliding_window_layout=[0] * 8))
    assert full.window_positions(40) == 0


def test_published_widths_carry_123_7_megabytes_a_row():
    """Shapes only, no weights: 16,384 positions of the two global
    layers and six rings of 4096 + 512 slots, 4 heads of 128 in
    bfloat16; the configuration's file, as the cell's driver reads it."""
    file = json.loads((ROOT / "benchmark/configs/"
                       "smallthinker_21ba3b_pp7_stage0.json").read_text())
    enc = build_encoder(make_config("smallthinker", file,
                                    kv_positions=16384))
    cfg = enc.config
    assert (cfg.moe_num_primary_experts, cfg.experts_held) == (64, (0, 64))
    assert (cfg.sliding_window_size, cfg.ring_positions) == (4096, 4608)
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.moe_ffn_hidden_size,
            cfg.moe_num_active_primary_experts, cfg.rope_theta) == (
        2560, 28, 4, 128, 768, 6, 1500000)
    assert enc.state_bytes_per_row(16384) == 123731968 \
        == 2 * 33554432 + 6 * 9437184
    assert file["parameters"]["state_bytes_a_row_at_16384"] == 123731968
    # the short group of the cell: 6 chunks of 512 on the 4096 grid
    assert (enc.cache_positions(3072), enc.window_positions(3072)) \
        == (4096, 4096)
    assert enc.state_bytes_per_row(3072) == 8 * 4096 * 2048 == 67108864
    shapes = jax.eval_shape(lambda: enc.init_states(16, 16384))
    assert [k.shape[1:3] for k in shapes["k"]] == \
        [(4, 16384), (4, 4608), (4, 4608), (4, 4608)] * 2   # head-major
    assert shapes["k"][0].dtype == jnp.bfloat16
    # the derived list the accepted readers read IS the published layout
    assert file["layer_types"] == [
        "sliding_attention" if v else "full_attention"
        for v in file["sliding_window_layout"]]
    assert file["rope_layout"] == file["sliding_window_layout"] == PERIOD * 2


def test_config_refuses_what_it_does_not_implement_by_name():
    cfg = config()
    assert hash(cfg) == hash(config())
    assert cfg.experts_held == (0, 16)
    assert cfg.sliding_layers == (False, True, True, True) * 2
    with pytest.raises(ValueError, match="moe_primary_router_apply_softmax"):
        config(moe_primary_router_apply_softmax=False)
    with pytest.raises(ValueError, match="rope_scaling"):
        config(rope_scaling={"type": "yarn", "factor": 4.0})
    with pytest.raises(ValueError, match="norm_topk_prob"):
        config(norm_topk_prob=False)
    with pytest.raises(ValueError, match="rope_layout"):
        config(rope_layout=[0, 1, 1])
    with pytest.raises(ValueError, match="sliding_window_layout"):
        config(sliding_window_layout=[0, 2] * 4)
    with pytest.raises(ValueError, match="outside the router"):
        dataclasses.replace(cfg, experts_held=(12, 8))
    with pytest.raises(ValueError, match="not the count"):
        make_config("smallthinker", dict(
            MODEL, experts_held={"first": 0, "count": 8, "of": 16}))
    with jax.default_matmul_precision("highest"):
        with pytest.raises(NotImplementedError, match="apply_softmax"):
            ref.route(jnp.zeros((2, 64)), jnp.zeros((64, 16)), dict(
                MODEL, moe_primary_router_apply_softmax=False))


def test_the_table_has_the_row():
    assert type(config()) is SmallThinkerConfig
    assert "smallthinker" in contract.ENCODERS
    assert contract.ENCODERS["smallthinker"][0] is SmallThinkerConfig
    enc = build_encoder(config())
    assert isinstance(enc, SmallThinkerEncoder)
    assert enc.state_counters(enc.init_states(1)).shape == (6,)
    assert enc.counter_attrs([]) == {}


def test_export_round_trip_in_bfloat16(tmp_path, vocab, params):
    from code_intelligence_tpu.training.checkpoint import export_encoder

    cfg = make_config("smallthinker", MODEL, kv_positions=64,
                      chunk_positions=8)
    weights = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    export_encoder(tmp_path, weights, cfg, vocab)
    eng = InferenceEngine.from_export(tmp_path, buckets=(8,), batch_size=2)
    assert eng.config == cfg and eng.encoder.dtype == jnp.bfloat16
    direct = InferenceEngine(weights, cfg, vocab, buckets=(8,), batch_size=2)
    seqs = [np.arange(20, 45, dtype=np.int32)]
    np.testing.assert_array_equal(eng.embed_ids_batch(seqs),
                                  direct.embed_ids_batch(seqs))
