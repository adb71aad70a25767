"""How ``ops/moe.py::routed_experts`` takes the sorted assignments: ``N``
a round for a share of the router's outputs, all ``top_k x N`` in one pass
without a loop where every expert is held (``one_pass``), decided from the
router's width, which the callers hand in and nothing else.
"""

import ast
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.cell import load_reference
from benchmark.reference import smallthinker as plain
from code_intelligence_tpu import models
from code_intelligence_tpu.models import build_encoder, make_config
from code_intelligence_tpu.ops import gmm, moe

ROOT = Path(__file__).resolve().parents[1]
N, E, F, TOP_K, WIDTH = 24, 64, 16, 8, 16


def _case(first, count, pad, draw):
    """24 tokens that choose 8 of 16 experts. ``draw`` ``"even"``: any 8;
    ``"skewed"``: the held experts before all others, so ``min(count,
    8)`` of every token's choices land here; ``"one_idle"``: the last
    held expert is nobody's choice."""
    k = jax.random.split(jax.random.PRNGKey(44), 5)
    liking = jax.random.uniform(k[0], (N, WIDTH))
    held = (jnp.arange(WIDTH) >= first) & (jnp.arange(WIDTH) < first + count)
    if draw == "skewed":
        liking = liking + held
    if draw == "one_idle":
        liking = liking.at[:, first + count - 1].set(-1.0)
    experts = jnp.argsort(-liking, axis=-1)[:, :TOP_K].astype(jnp.int32)
    weights = jax.nn.softmax(jax.random.normal(k[1], (N, TOP_K)), axis=-1)
    x = jax.random.normal(k[2], (N, E))
    w_in = jax.random.normal(k[3], (count, E, 2 * F)) / 8
    w_out = jax.random.normal(k[4], (count, F, E)) / 4
    valid = (jnp.arange(N) < N - pad) if pad else None
    return x, experts, weights, w_in, w_out, first, valid


# held share of a router 16 wide with 8 choices a token: (first, count);
# 15 of 16 held is where the mean of a token's choices that land, 7.5,
# rounds up to all 8
SHARES = {"1/16": (5, 1), "1/4": (4, 4), "3/8": (8, 6), "7/8": (2, 14),
          "15/16": (0, 15), "1": (0, 16)}


@pytest.mark.parametrize("draw", ["even", "skewed", "one_idle"])
@pytest.mark.parametrize("pad", [0, 7], ids=["whole", "padded"])
@pytest.mark.parametrize("share", list(SHARES))
def test_rounds_and_one_pass_give_the_plain_weighted_sum(share, pad, draw):
    """A share in rounds of ``N`` rows (none, one, several, a last one
    part full) and every expert held in one pass: every token gets the
    plain reference's sum (`benchmark/reference/smallthinker.py`: every
    held ReGLU expert over ALL tokens, no sort, no rounds), a padding
    lane exact zeros, and an expert nobody chose runs no row."""
    first, count = SHARES[share]
    m = TOP_K if moe.one_pass(TOP_K, count, WIDTH) else 1
    assert (m == TOP_K) == (count >= 15)
    x, experts, weights, w_in, w_out, first, valid = _case(
        first, count, pad, draw)
    got, rows = jax.jit(lambda *a: moe.routed_experts(
        *a, first, WIDTH, valid, "relu"))(x, experts, weights, w_in, w_out)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, e, w, w_in, w_out: plain.routed_part(
            {"experts_in": w_in, "experts_out": w_out}, x, e, w, first))(
                x, experts, weights, w_in, w_out)
    np.testing.assert_allclose(got[:N - pad], want[:N - pad], rtol=2e-5,
                               atol=2e-5)
    held = (experts >= first) & (experts < first + count)
    if pad:
        held = held & valid[:, None]
        assert float(jnp.abs(got[N - pad:]).max()) == 0.0
    np.testing.assert_array_equal(rows, [
        int((held & (experts == first + j)).sum()) for j in range(count)])
    total, lanes = int(rows.sum()), N - pad
    if draw == "skewed":
        assert total == min(count, TOP_K) * lanes
    if draw == "one_idle":
        assert int(rows[-1]) == 0
        assert total > 0 or count == 1
    rounds = int(moe.rounds_run(rows.sum(), N, TOP_K, count, WIDTH))
    assert rounds == -(-total // (m * N)) <= -(-TOP_K // m)
    if m == TOP_K:
        assert rounds == (total > 0)


@pytest.mark.parametrize("pad", [0, 7], ids=["whole", "padded"])
@pytest.mark.parametrize("share", ["1/4", "3/8", "7/8", "1"])
def test_the_grouped_matmul_kernels_give_the_plain_weighted_sum(
        monkeypatch, share, pad):
    """``held`` on ``ops/gmm.py``'s kernels (interpreted: the rule's
    answer and a tile of 8 rows are the test's, so a share's rounds, its
    last part-full one and the one pass of every expert held all meet
    groups that straddle tiles and rows past the last one routed): the
    plain reference's sum at the tightness of the case above."""
    first, count = SHARES[share]
    monkeypatch.setattr(gmm, "gmm_is_kernel", lambda *a: True)
    monkeypatch.setattr(gmm, "_kernel_tiles", lambda *a: (8, 8, F, E // 2))
    x, experts, weights, w_in, w_out, first, valid = _case(
        first, count, pad, "skewed")
    lowered = jax.jit(lambda *a: moe.routed_experts(
        *a, first, WIDTH, valid, "relu")).lower(
            x, experts, weights, w_in, w_out)
    assert "ragged_dot" not in lowered.as_text()
    assert "gated_gmm/pallas_call" in lowered.as_text(debug_info=True)
    got, rows = lowered.compile()(x, experts, weights, w_in, w_out)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, e, w, w_in, w_out: plain.routed_part(
            {"experts_in": w_in, "experts_out": w_out}, x, e, w, first))(
                x, experts, weights, w_in, w_out)
    np.testing.assert_allclose(got[:N - pad], want[:N - pad], rtol=2e-5,
                               atol=2e-5)
    if pad:
        assert float(jnp.abs(got[N - pad:]).max()) == 0.0
    assert int(rows.sum()) == min(count, TOP_K) * (N - pad)


def _lowered(first, count, n_experts):
    x, experts, weights, w_in, w_out, first, _ = _case(first, count, 0,
                                                       "even")
    return jax.jit(lambda *a: moe.routed_experts(
        *a, first, n_experts)).lower(x, experts, weights, w_in, w_out)


def test_every_expert_held_is_one_pass_without_a_loop():
    """The one loop left in the one-pass program is the combine's (a
    block of tokens' rows gathered back at a time): the grouped matmuls
    stand outside any ``while``, nothing is sliced out of the sort and
    no round's rows are written into a buffer; a share of the same
    experts under a wider router keeps the rounds' loop."""
    one, rounds = _lowered(0, 16, WIDTH), _lowered(0, 16, 4 * WIDTH)
    assert not moe.one_pass(TOP_K, 16, 4 * WIDTH)
    assert one.as_text().count("stablehlo.while") == 1
    assert rounds.as_text().count("stablehlo.while") == 2
    text = one.as_text(debug_info=True)
    assert "combine/while/body" in text
    for scope in ("while/body/experts", "while/body/dispatch",
                  "while/body/combine"):
        assert scope not in text
        assert scope in rounds.as_text(debug_info=True)
    assert "ragged_dot" in text
    compiled = one.compile().as_text()
    assert compiled.count(" while(") == 1
    assert rounds.compile().as_text().count(" while(") == 2
    # the pass's weighted output is the buffer the combine gathers from
    assert f"f32[{TOP_K * N},{E}]" in compiled


@pytest.mark.parametrize("pad", [0, 7], ids=["whole", "padded"])
def test_a_rounds_size_moves_no_bit(pad):
    """The same choices of the same held experts under a router 16 wide
    (one pass) and one 32 wide (rounds of ``N``, 8 of them): the same
    float32 product a row and the same sum a token, wherever the rows
    are weighed."""
    x, experts, weights, w_in, w_out, first, valid = _case(0, 16, pad,
                                                           "even")
    got = [jax.jit(lambda *a, n=n: moe.routed_experts(
        *a, first, n, valid, "relu"))(x, experts, weights, w_in, w_out)
        for n in (WIDTH, 2 * WIDTH)]
    assert [moe.one_pass(TOP_K, 16, n * WIDTH) for n in (1, 2)] == [
        True, False]
    for y, rows in got[1:]:
        np.testing.assert_array_equal(y, got[0][0])
        np.testing.assert_array_equal(rows, got[0][1])


def test_a_thin_share_is_the_program_it_was():
    """Rounds of ``N`` rows in a buffer of ``top_k x N``, the sort padded
    by one round."""
    text = _lowered(5, 1, WIDTH).as_text()
    assert f"tensor<{TOP_K * N + N}xi32>" in text
    assert f"tensor<{TOP_K * N}x{E}xf32>" in text
    assert f"tensor<{N}x{E}xf32>" in text


# -- the five expert configurations ------------------------------------------

# configuration file: of a token's ``top_k`` choices, those that land on
# the held share on average, rounded up (ISSUE 44's ``m``)
CONFIGS = {"deepseek_v3_ep16_share": 1, "trinity_large_ep8_share": 1,
           "ling_3_0_flash_ep4_share": 2,
           "smallthinker_21ba3b_pp7_stage0": 6,
           "longcat_flash_ep32_share": 1}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_one_pass_follows_the_share_each_configuration_holds(
        monkeypatch, name):
    """Each cell's configuration at its own widths, traced and never
    run: every expert layer hands ``routed_experts`` its router's width,
    the held experts are its weights' leading axis, ``ceil(top_k x count
    / n_experts)`` is 1, 1, 2, 6, 1, and the one configuration where it
    reaches ``top_k`` (every expert held) is the one that runs one
    pass."""
    model = json.loads(
        (ROOT / "benchmark" / "configs" / f"{name}.json").read_text())
    model = dict(model, num_hidden_layers=2) \
        if "num_hidden_layers" in model else dict(model, num_layers=1)
    if "first_k_dense_replace" in model:
        model["first_k_dense_replace"] = 1
    for key in ("layer_types", "rope_layout", "sliding_window_layout"):
        if key in model:
            model[key] = model[key][:2]
    ref = load_reference(model["architecture"])
    params = jax.eval_shape(lambda key: ref.init_params(
        key, model, model.get("weights"), dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    enc = build_encoder(make_config(
        model["architecture"], model, kv_positions=2048,
        **({"chunk_positions": 512}
           if "sliding_window_size" in model or "sliding_window" in model
           else {})), params)
    seen = []
    real = moe.routed_experts

    def spy(x, experts, weights, w_in, w_out, first, n_experts, *a, **kw):
        seen.append((experts.shape[1], w_in.shape[0], n_experts))
        return real(x, experts, weights, w_in, w_out, first, n_experts,
                    *a, **kw)

    monkeypatch.setattr(moe, "routed_experts", spy)
    jax.eval_shape(
        enc.encode, params, jax.ShapeDtypeStruct((2, 512), jnp.int32),
        jax.eval_shape(lambda: enc.init_states(2, 2048)),
        jax.ShapeDtypeStruct((2,), jnp.int32))
    assert seen
    _, count = enc.config.experts_held
    routers = {leaf.shape[1] for path, leaf in
               jax.tree_util.tree_leaves_with_path(params)
               if path[-1].key == "router"}
    for top_k, held, n_experts in seen:
        assert type(n_experts) is int and {n_experts} == routers
        assert held == count
        landing = -(-top_k * held // n_experts)
        assert landing == CONFIGS[name]
        assert moe.one_pass(top_k, held, n_experts) == (landing == top_k) \
            == (name == "smallthinker_21ba3b_pp7_stage0")


def _calls(tree, name):
    return [node for node in ast.walk(tree) if isinstance(node, ast.Call)
            and getattr(node.func, "attr", getattr(node.func, "id", None))
            == name]


def test_no_caller_hands_a_name_or_a_flag():
    """What sizes a round reaches ``routed_experts`` as ONE positional
    argument, the width of the layer's own router
    (``p["router"].shape[1]``), from each of its three callers; the
    function has no parameter beside the tensors, ``first``, that width,
    ``valid``, ``act`` and ``assigned``."""
    import inspect

    assert list(inspect.signature(moe.routed_experts).parameters) == [
        "x", "experts", "weights", "w_in", "w_out", "first", "n_experts",
        "valid", "act", "assigned"]
    assert list(inspect.signature(moe.one_pass).parameters) == [
        "top_k", "count", "n_experts"]
    package = Path(models.__file__).resolve().parents[1]
    calls = {}
    for path in sorted(package.rglob("*.py")):
        for call in _calls(ast.parse(path.read_text()), "routed_experts"):
            calls.setdefault(path.name, []).append(call)
    assert sorted(calls) == ["longcat_flash.py", "moe.py", "smallthinker.py"]
    for name, (call,) in calls.items():
        assert ast.unparse(call.args[6]) == "p['router'].shape[1]", name
        assert {kw.arg for kw in call.keywords} <= {"act", "assigned"}
