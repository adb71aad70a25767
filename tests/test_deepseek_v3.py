"""The DeepSeek-V3 encoder (latent attention + sigmoid-routed experts, of
which this chip holds a share) and the encoder contract's third member.

Small on the CPU (hidden 64, 4 heads, q rank 24, kv rank 16, head sizes
8 | 4 | 8, 16 experts in 4 groups of which the top 2 are kept, 4 a
token, 1 dense + 2 expert layers, experts 4..11 held), every comparison
against the plain reference (`benchmark/reference/deepseek_v3.py`) on
seeded weights: the two algebraic forms of the attention core; YaRN's
frequencies against hand-computed values; the router on a hand-worked
score table; the shares adding up to the uncut layer; a document through
the latent cache in three chunk programs with narrowing; no token
dropped however many land here; float32 routing identical to the
reference's and the bfloat16 flips counted; the contract's numbers.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.reference import common
from benchmark.reference import deepseek_v3 as ref
from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.models import (
    AWDLSTMConfig, ChunkEncoder, DeepseekV3Config, DeepseekV3Encoder,
    GraniteHybridConfig, build_encoder, make_config)
from code_intelligence_tpu.models import contract
from code_intelligence_tpu.ops import mla, moe
from code_intelligence_tpu.text import SPECIALS, Vocab
from code_intelligence_tpu.utils import tracing
from encoder_programs import (
    compiled, seeded, the_rule_says_grouped_kernels)

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
MODEL = {
    "vocab_size": 300, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN,
    "max_position_embeddings": 163840, "num_nextn_predict_layers": 1,
    "experts_held": {"first": 4, "count": 8, "of": 16}}
UNCUT = dict(MODEL, n_routed_experts=16,
             experts_held={"first": 0, "count": 16, "of": 16})
TAILS = {"dist": "student_t", "df": 4}


@pytest.fixture(scope="module")
def params():
    return seeded(ref, 30, MODEL, TAILS)


def config(**extra):
    return make_config("deepseek_v3", MODEL, **dict(
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


# -- ops: rotary and the attention core --------------------------------------

def test_yarn_frequencies_at_the_published_numbers():
    """dim 64, theta 10000, factor 40, 4096 original positions, beta 32
    and 1: the correction dimensions are 10.47 and 22.51, so dimensions
    0..10 keep ``theta ** (-2i / 64)``, 23..31 have it divided by 40,
    and those between blend by ``(i - 10) / 13``."""
    f = mla.yarn_inv_freq(64, 10000, YARN)
    assert f.shape == (32,)
    plain = lambda i: 10.0 ** (-4 * 2 * i / 64)  # noqa: E731
    for i in (0, 5, 10):
        assert f[i] == pytest.approx(plain(i), rel=1e-12)
    for i in (23, 27, 31):
        assert f[i] == pytest.approx(plain(i) / 40, rel=1e-12)
    assert f[16] == pytest.approx(0.01 * (6 / 13 / 40 + 7 / 13), rel=1e-12)
    assert f[22] == pytest.approx(plain(22) * (12 / 13 / 40 + 1 / 13),
                                  rel=1e-12)
    np.testing.assert_allclose(
        f, ref.inv_freq({"qk_rope_head_dim": 64, "rope_theta": 10000,
                         "rope_scaling": YARN}), rtol=2e-6)
    # no scaling: plain rotary
    np.testing.assert_allclose(mla.yarn_inv_freq(8, 10000),
                               [1, 0.1, 0.01, 0.001], rtol=1e-12)
    # mscale = mscale_all_dim: cos and sin unscaled, the softmax scaled
    assert mla.rope_factor(YARN) == 1.0
    assert mla.softmax_scale(192, YARN) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2) \
        == pytest.approx(0.135234, rel=1e-5)
    assert mla.softmax_scale(192, None) == 192 ** -0.5


def test_rope_follows_the_published_pairing():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 3, 8))
    model = {"qk_rope_head_dim": 8, "rope_theta": 10000,
             "rope_scaling": YARN}
    got = mla.apply_rope(x, 7 + jnp.arange(5), mla.yarn_inv_freq(
        8, 10000, YARN))
    # the reference rotates positions 0..T-1: prepend 7 of them
    padded = jnp.concatenate([jnp.zeros((2, 7, 3, 8)), x], axis=1)
    np.testing.assert_allclose(got, ref.rotary(padded, model)[:, 7:],
                               rtol=1e-5, atol=1e-6)


@functools.partial(jax.jit, static_argnames=(
    "b", "T", "S", "H", "rank", "nope", "rope", "v"))
def _core_inputs(b=2, T=16, S=32, H=4, rank=16, nope=8, rope=4, v=8):
    """One compiled program a shape: drawn op by op, every ``normal`` of
    a new shape is a compilation of its own."""
    k = iter(jax.random.split(jax.random.PRNGKey(T * 7 + S), 8))
    return dict(
        q_nope=jax.random.normal(next(k), (b, T, H, nope)),
        q_pe=jax.random.normal(next(k), (b, T, H, rope)),
        latent=jax.random.normal(next(k), (b, T, rank + rope)),
        cache=jax.random.normal(next(k), (b, S, rank + rope)),
        w_kvb=jax.random.normal(next(k), (rank, H * (nope + v))) / 4)


@pytest.mark.parametrize("pos", [0, 8, 16])
def test_absorbed_equals_expanded_and_both_the_dense_softmax(pos):
    """From a non-empty cache, blocked over heads, queries and key
    prefixes (S = 32 in key blocks of 8: the core runs over 16 + pos
    keys rounded up). The program runs the expanded form; the absorbed
    one (``W_kvb`` folded into query and output, the latent met
    directly) is derived here."""
    a = _core_inputs()
    exp, cache = jax.jit(lambda a: mla.mla_cached(
        **a, pos=jnp.int32(pos), scale=0.3, v_dim=8, head_block=2,
        q_block=8, key_block=8, mxu_dtype=jnp.float32))(a)
    # dense: every cached position up to pos + T, one softmax
    n = pos + 16
    np.testing.assert_array_equal(cache[:, pos:n], a["latent"])
    np.testing.assert_array_equal(cache[:, n:], a["cache"][:, n:])
    lat = cache[:, :n]
    seen = jnp.arange(n)[None, :] <= (pos + jnp.arange(16))[:, None]
    s_pe = jnp.einsum("bthr,bsr->bhts", a["q_pe"], lat[..., 16:])

    def softmax(s_nope):
        return jax.nn.softmax(
            jnp.where(seen, (s_nope + s_pe) * 0.3, -jnp.inf), axis=-1)

    kv = (lat[..., :16] @ a["w_kvb"]).reshape(2, n, 4, 16)
    want = jnp.einsum("bhts,bshd->bthd", softmax(jnp.einsum(
        "bthd,bshd->bhts", a["q_nope"], kv[..., :8])), kv[..., 8:])
    np.testing.assert_allclose(exp, want, rtol=2e-5, atol=2e-5)
    w = a["w_kvb"].reshape(16, 4, 16)
    q_abs = jnp.einsum("bthd,chd->bthc", a["q_nope"], w[..., :8])
    o_lat = jnp.einsum("bhts,bsc->bthc", softmax(jnp.einsum(
        "bthc,bsc->bhts", q_abs, lat[..., :16])), lat[..., :16])
    absorbed = jnp.einsum("bthc,chd->bthd", o_lat, w[..., 8:])
    np.testing.assert_allclose(exp, absorbed, rtol=2e-5, atol=2e-5)


def test_expanded_is_the_cheaper_form_for_every_shape_the_engine_runs():
    """ISSUE 30's arithmetic at the published sizes: 33.5 MFLOP to
    expand a cached position (expanded) or a query (absorbed), 320
    against 1088 multiply-adds a pair a head. A chunk against a cache no
    longer than four chunks is cheaper expanded; a few queries against a
    long cache (a decode step, which nothing here runs) absorbed."""
    heads, rank, nope, rope, v = 128, 512, 128, 64, 128
    w_kvb = 2 * rank * heads * (nope + v)
    assert w_kvb == 33554432

    def expanded(T, S):
        return S * w_kvb + 2 * T * S * heads * (nope + rope + v)

    def absorbed(T, S):
        return T * w_kvb + 2 * T * S * heads * (2 * rank + rope)

    assert expanded(1, 1) - w_kvb == 2 * 320 * 128
    assert absorbed(1, 1) - w_kvb == 2 * 1088 * 128
    for T in (32, 64, 128, 256, 512):
        assert expanded(T, T) < absorbed(T, T)
    assert round(expanded(512, 2048) / 1e9) == 155
    assert round(absorbed(512, 2048) / 1e9) == 309
    assert absorbed(1, 2048) < expanded(1, 2048)
    assert absorbed(128, 2048) < expanded(128, 2048)


# -- ops: the core as one Pallas kernel, and the rule that picks it -----------

def _the_rule_says_kernel(monkeypatch, tiles):
    """The rule's answer steered from the test (it sees the CPU and
    float32 here), and tiles that divide the tiny shapes; the kernel
    itself asks the real backend and is interpreted."""
    monkeypatch.setattr(mla, "core_is_kernel", lambda *a: True)
    monkeypatch.setattr(mla, "_kernel_tiles", lambda *a: tiles)


# the kernel's two neighbours as compiled programs (the kernel itself is
# interpreted, and what it costs here is its run)
_xla_core = jax.jit(mla._xla_core, static_argnums=tuple(range(5, 11)))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _dense_core(a, cache, pos, scale, dtype):
    """One softmax over every cached position up to ``pos + T``, the
    operands of the three products rounded to ``dtype`` as the cores
    round them (the expanded keys and values and ``p`` among them)."""
    r = lambda x: x.astype(dtype).astype(jnp.float32)  # noqa: E731
    b, T, H, nope = a["q_nope"].shape
    rank = cache.shape[-1] - a["q_pe"].shape[-1]
    n = pos + T
    lat = r(cache[:, :n])
    kv = r(jnp.einsum("bsc,cn->bsn", lat[..., :rank], r(a["w_kvb"]),
                      precision="highest")).reshape(b, n, H, -1)
    s = jnp.einsum("bthd,bshd->bhts", r(a["q_nope"]), kv[..., :nope],
                   precision="highest") \
        + jnp.einsum("bthr,bsr->bhts", r(a["q_pe"]), lat[..., rank:],
                     precision="highest")
    seen = jnp.arange(n)[None, :] <= (pos + jnp.arange(T))[:, None]
    p = jax.nn.softmax(jnp.where(seen, s * scale, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", r(p), kv[..., nope:],
                      precision="highest")


# (pos, T, S, (q_block, key_block, heads), sizes, dtype): from an empty
# and from a filled cache; query blocks that stop short of the last key
# block allocated; one key block (a single-chunk document); one row;
# every head in one step; the published head sizes in bfloat16, where
# the expanded keys are rounded before they meet the queries
TINY = dict(H=4, rank=16, nope=8, rope=4, v=8)
WIDE = dict(H=2, rank=128, nope=128, rope=64, v=128)


@pytest.mark.parametrize("pos,T,S,tiles,sizes,dtype", [
    (0, 16, 32, (16, 8, 2), dict(TINY, b=2), jnp.float32),
    (8, 16, 32, (16, 8, 2), dict(TINY, b=2), jnp.float32),
    (16, 16, 32, (8, 16, 1), dict(TINY, b=2), jnp.float32),
    (8, 8, 64, (4, 8, 2), dict(TINY, b=2), jnp.float32),
    (0, 16, 16, (16, 16, 2), dict(TINY, b=2), jnp.float32),
    (16, 16, 32, (16, 32, 4), dict(TINY, b=1), jnp.float32),
    (0, 16, 32, (16, 16, 2), dict(WIDE, b=2), jnp.bfloat16),
    (16, 16, 32, (16, 16, 1), dict(WIDE, b=2), jnp.bfloat16),
], ids=["empty_cache", "filled", "q_blocks", "live_stops_short",
        "one_key_block", "one_row_all_heads", "bf16_first_chunk",
        "bf16_filled"])
def test_the_kernel_equals_the_xla_core_and_a_dense_softmax(
        pos, T, S, tiles, sizes, dtype):
    """``_kernel_core`` interpreted, on the cache the chunk is written
    into, against the XLA core at the same operand type and against one
    dense softmax."""
    a = _core_inputs(T=T, S=S, **sizes)
    cache = jax.lax.dynamic_update_slice_in_dim(
        a["cache"], a["latent"], pos, axis=1).astype(dtype)
    args = (a["q_nope"], a["q_pe"], cache, jnp.int32(pos), a["w_kvb"], 0.3,
            sizes["v"], dtype)
    got = mla._kernel_core(*args, tiles)
    assert got.shape == (sizes["b"], T, sizes["H"], sizes["v"])
    assert got.dtype == jnp.float32
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, _xla_core(*args, 2, 8, 8),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(got, _dense_core(a, cache, pos, 0.3, dtype),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("pos", [0, 8, 16])
def test_mla_cached_on_the_kernel_writes_the_cache_and_attends(
        monkeypatch, pos):
    """``mla_cached`` itself with the rule steered: the same cache write,
    the same result as with the rule left alone (the XLA core)."""
    a = _core_inputs()
    call = lambda: jax.jit(lambda a: mla.mla_cached(  # noqa: E731
        **a, pos=jnp.int32(pos), scale=0.3, v_dim=8, head_block=2,
        q_block=8, key_block=8, mxu_dtype=jnp.float32))(a)
    want, want_cache = call()
    _the_rule_says_kernel(monkeypatch, (8, 8, 2))
    got, cache = call()
    np.testing.assert_array_equal(cache, want_cache)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_kernel_fetches_only_the_key_blocks_reached(monkeypatch):
    """The index maps, read off the ``BlockSpec``s: a 64-position cache
    in key blocks of 8 with 16 positions cached and 8 arriving: the
    first query block of 4 reaches blocks 0..2 and is handed block 2
    again for the five steps after (no new fetch, and ``pl.when`` skips
    the step); the latent and the rotary key move together; a group of
    heads is a block of the transposed queries' heads, of the output's
    and of ``W_kvb``'s key half's columns, and of the rows of its
    transposed value half."""
    from jax.experimental import pallas as pl

    maps = []
    real = pl.BlockSpec

    def recording(shape, index_map):
        maps.append((shape, index_map))
        return real(shape, index_map)

    monkeypatch.setattr(pl, "BlockSpec", recording)
    a = _core_inputs(T=8, S=64)
    mla._kernel_core(a["q_nope"], a["q_pe"], a["cache"], jnp.int32(16),
                     a["w_kvb"], 0.3, 8, jnp.float32, (4, 8, 2))
    (q, latent, pe, w_k, w_v, out) = maps
    pos = np.asarray([16], np.int32)
    for _, keys in (latent, pe):
        assert [tuple(int(v) for v in keys(1, 0, 0, j, pos))
                for j in range(8)] == [(1, min(j, 2), 0) for j in range(8)]
        assert [int(keys(0, 1, 1, j, pos)[1]) for j in range(8)] \
            == [0, 1, 2, 2, 2, 2, 2, 2]     # queries 20..23: still block 2
        assert [int(keys(0, 0, 0, j, np.asarray([40], np.int32))[1])
                for j in range(8)] == [0, 1, 2, 3, 4, 5, 5, 5]
    assert latent[0] == (None, 8, 16)       # c_kv: the first 16 columns
    assert pe[0] == (None, 8, 4 + 116)      # k_pe, padded as the queries
    # the queries lie (rows, heads, [nope | rope | 0], T): queries across
    assert q[0] == (None, 2, 128, 4) and out[0] == (None, 4, 2 * 8)
    assert tuple(int(v) for v in q[1](1, 1, 1, 5, pos)) == (1, 1, 0, 1)
    assert tuple(int(v) for v in out[1](1, 1, 1, 5, pos)) == (1, 1, 1)
    # W_kvb's key half by columns, its value half transposed by rows
    assert w_k[0] == (16, 2 * 8) and w_v[0] == (2 * 8, 16)
    assert tuple(int(v) for v in w_k[1](1, 1, 1, 5, pos)) == (0, 1)
    assert tuple(int(v) for v in w_v[1](1, 1, 1, 5, pos)) == (1, 0)


BF16, F32 = jnp.bfloat16, jnp.float32
PUBLISHED = (128, 128, 128, 512)  # heads, nope, v, rank


# the cell's eleven programs a call: the multi-chunk group's four (512
# queries against the 2048-position cache) and the single-chunk groups of
# buckets 512 and 256 on the kernel, buckets 128 and 64 on the XLA core
@pytest.mark.parametrize("backend,dtype,T,S,sizes,kernel", [
    ("tpu", BF16, 512, 2048, PUBLISHED, True),
    ("tpu", BF16, 512, 512, PUBLISHED, True),
    ("tpu", BF16, 256, 256, PUBLISHED, True),
    ("tpu", BF16, 128, 128, PUBLISHED, False),
    ("tpu", BF16, 64, 64, PUBLISHED, False),
    ("cpu", BF16, 512, 2048, PUBLISHED, False),
    ("tpu", F32, 512, 2048, PUBLISHED, False),    # the parity tests' type
    ("tpu", BF16, 500, 2000, PUBLISHED, False),   # no tile divides it
    ("tpu", BF16, 512, 1280, PUBLISHED, False),   # nor this cache
    ("tpu", BF16, 512, 2048, (4, 8, 8, 16), False),  # sizes under a lane
], ids=["multi_chunk", "bucket_512", "bucket_256", "bucket_128", "bucket_64",
        "cpu", "float32", "no_tile", "no_key_block", "tiny_heads"])
def test_the_rule_reads_observables_alone(backend, dtype, T, S, sizes,
                                          kernel):
    assert mla.core_is_kernel(backend, dtype, T, S, *sizes) is kernel


def test_the_kernels_tiles_are_a_function_of_the_shapes():
    """The whole chunk's queries, key blocks of 512 positions (the
    chunks' length: no block of a first chunk lies unwritten) or a
    single-chunk cache whole, eight heads a step; aligned to bfloat16's
    (16, 128) tiles and dividing chunk, cache and heads."""
    assert [mla._kernel_tiles(T, S, 128) for T, S in (
        (512, 2048), (512, 512), (256, 256), (256, 1024))] \
        == [(512, 512, 8), (512, 512, 8), (256, 256, 8), (256, 512, 8)]
    for T, S in ((512, 2048), (512, 512), (256, 256)):
        qb, kb, hs = mla._kernel_tiles(T, S, 128)
        assert qb == T and qb % 128 == 0 and S % kb == 0 and kb % 16 == 0
        assert 128 % hs == 0
    assert mla._kernel_tiles(512, 2048, 4) == (512, 512, 4)
    assert mla._kernel_tiles(512, 2048, 12) == (512, 512, 6)
    for T, S in ((128, 128), (64, 64), (500, 2000), (1024, 2048),
                 (512, 1280), (384, 384)):
        assert mla._kernel_tiles(T, S, 128) is None


def test_no_name_selects_a_core():
    """The core is the code's choice: nothing a caller, a configuration
    or a command line can say names one."""
    import inspect
    import json
    import re
    from pathlib import Path

    assert list(inspect.signature(mla.mla_cached).parameters) == [
        "q_nope", "q_pe", "latent", "cache", "pos", "w_kvb", "scale",
        "v_dim", "head_block", "q_block", "key_block", "mxu_dtype",
        "admit"]    # PR 53: WHAT a query attends (a mask), not which core
    words = {"pallas", "kernel", "core", "xla", "interpret", "tile", "tiles"}
    assert not [f.name for f in dataclasses.fields(DeepseekV3Config)
                if words & set(f.name.split("_"))]
    root = Path(mla.__file__).resolve().parents[2]
    serve = json.loads((root / "benchmark" / "configs" /
                        "deepseek_v3_ep16_share.json").read_text())["serve"]
    assert sorted(serve) == ["batch_size", "buckets", "kv_positions",
                             "scheduler"]
    options = re.compile(r'add_argument\(\s*"--([a-z_0-9-]+)"')
    for cli in ("training/cli.py", "sweep/cli.py", "serving/server.py"):
        names = options.findall(
            (root / "code_intelligence_tpu" / cli).read_text())
        assert names and not [n for n in names
                              if re.search("attention|mla|latent|core", n)]
    source = Path(mla.__file__).read_text()
    assert "environ" not in source and "getenv" not in source
    model = Path(contract.ENCODERS["deepseek_v3"][1].__module__.replace(
        ".", "/") + ".py")
    assert "core_is_kernel" in (root / model).read_text()


# -- ops: the router and the share -------------------------------------------

def _route(scores, bias, **kw):
    """``moe.route`` on given SIGMOID scores: an identity router fed
    their logits."""
    scores = jnp.asarray(scores, jnp.float32)
    n = scores.shape[1]
    logits = jnp.log(scores) - jnp.log1p(-scores)
    args = dict(n_group=4, topk_group=2, top_k=2, scaling=2.5)
    args.update(kw)
    return moe.route(logits, jnp.eye(n), jnp.asarray(bias, jnp.float32),
                     **args)


def test_group_limited_selection_on_a_hand_worked_table():
    """8 experts in 4 groups of 2, the 2 best groups kept (a group's
    score = the sum of its 2 best = both), 2 experts a token.

    token 0: groups score 0.9+0.1, 0.6+0.5, 0.2+0.3, 0.55+0.05 -> groups
    1 (1.1) and 0 (1.0) kept, so expert 6 (0.55) is out though it beats
    expert 3 (0.5); chosen 0 (0.9) and 2 (0.6); weights 0.9, 0.6 over 1.5
    times 2.5."""
    table = [[0.9, 0.1, 0.6, 0.5, 0.2, 0.3, 0.55, 0.05]]
    experts, weights = _route(table, np.zeros(8))
    assert experts.tolist() == [[0, 2]]
    np.testing.assert_allclose(weights, [[1.5, 1.0]], rtol=1e-5)
    # without normalisation the raw scores times the factor
    _, raw = _route(table, np.zeros(8), norm_topk_prob=False)
    np.testing.assert_allclose(raw, [[2.25, 1.5]], rtol=1e-5)
    # no groups: the plain top 2
    experts, _ = _route(table, np.zeros(8), n_group=1, topk_group=1)
    assert experts.tolist() == [[0, 2]]
    experts, _ = _route([[0.1, 0.2, 0.3, 0.4, 0.5, 0.95, 0.9, 0.6]],
                        np.zeros(8), n_group=1, topk_group=1)
    assert experts.tolist() == [[5, 6]]


def test_the_bias_moves_the_choice_and_not_the_weight():
    table = [[0.9, 0.1, 0.6, 0.5, 0.2, 0.3, 0.55, 0.05]]
    # +0.2 on expert 3: it now beats expert 2 inside the kept group 1
    bias = np.zeros(8)
    bias[3] = 0.2
    experts, weights = _route(table, bias)
    assert experts.tolist() == [[0, 3]]
    np.testing.assert_allclose(weights, [[2.5 * 0.9 / 1.4, 2.5 * 0.5 / 1.4]],
                               rtol=1e-5)
    # +0.5 on expert 7 lifts group 3 to 1.1 + ... and throws group 0 out
    bias = np.zeros(8)
    bias[7] = 0.6
    experts, weights = _route(table, bias)
    assert sorted(experts[0].tolist()) == [2, 7]
    got = dict(zip(experts[0].tolist(), weights[0].tolist()))
    assert got[7] == pytest.approx(2.5 * 0.05 / 0.65, rel=1e-5)  # unbiased
    # the reference's router reads the same table the same way
    model = dict(num_experts_per_tok=2, n_group=4, topk_group=2,
                 routed_scaling_factor=2.5)
    scores = jnp.asarray(table, jnp.float32)
    r_experts, r_weights, _ = ref.route(
        jnp.log(scores) - jnp.log1p(-scores), jnp.eye(8),
        jnp.asarray(bias, jnp.float32), model)
    assert sorted(r_experts[0].tolist()) == [2, 7]
    np.testing.assert_allclose(sorted(r_weights[0].tolist()),
                               sorted(weights[0].tolist()), rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """One expert layer, 16 experts: the routed parts of the two shares
    of 8 (and of the four of 4) summed, plus the shared expert ONCE,
    equal the uncut reference's whole layer."""
    whole = seeded(ref, 4, UNCUT, TAILS, layer="layer_1")
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = jax.jit(lambda p, x: ref.moe_layer(p, x, UNCUT))(
            whole, x)
        shared = ref.swiglu(x, whole["shared_in"], whole["shared_out"])
    experts, weights = moe.route(
        x, whole["router"], whole["bias"], 4, 2, 4, 2.5)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    # ``first`` is traced: one program a share's size, not one a share
    share = jax.jit(lambda w_in, w_out, first: moe.routed_experts(
        x, experts, weights, w_in, w_out, first, 16))
    for count in (8, 4):
        total, rows = shared, 0
        for first in range(0, 16, count):
            part, per_expert = share(
                whole["experts_in"][first:first + count],
                whole["experts_out"][first:first + count], jnp.int32(first))
            total = total + part
            rows += int(per_expert.sum())
        assert rows == 40 * 4          # every choice lands on one share
        np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # one share alone is NOT the layer: what is left out is real
    assert float(jnp.abs(part + shared - want).max()) > 1e-2


def test_no_token_is_dropped_when_every_choice_lands_here():
    """A bias that sends all four choices of every token to the held
    experts: 4 x 24 assignments through four rounds of 24 rows, then,
    with seven padding lanes left out, 4 x 17 through three rounds that
    cut an expert's rows in two; equal to the reference's dense loop."""
    model = dict(MODEL, experts_held={"first": 4, "count": 4, "of": 16})
    p = seeded(ref, 6, model, TAILS, layer="layer_1")
    bias = jnp.full((16,), -1.0).at[4:8].set(1.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (24, 64))

    @jax.jit
    def the_references(p, x):
        r_experts, r_weights, _ = ref.route(x, p["router"], bias, model)
        return ref.routed_part(p, x, r_experts, r_weights, 4)

    with jax.default_matmul_precision("highest"):
        want = the_references(p, x)
    experts, weights = moe.route(x, p["router"], bias, 4, 2, 4, 2.5)
    assert sorted(set(np.asarray(experts).ravel())) == [4, 5, 6, 7]
    got, per_expert = jax.jit(lambda x, e, w: moe.routed_experts(
        x, e, w, p["experts_in"], p["experts_out"], 4, 16))(
            x, experts, weights)
    assert per_expert.tolist() == [24, 24, 24, 24]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    valid = jnp.arange(24) < 17
    got, per_expert = jax.jit(lambda x, e, w, valid: moe.routed_experts(
        x, e, w, p["experts_in"], p["experts_out"], 4, 16, valid))(
            x, experts, weights, valid)
    assert per_expert.tolist() == [17, 17, 17, 17]
    np.testing.assert_allclose(got[:17], want[:17], rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(got[17:]).max()) == 0.0


# -- the encoder against the reference ---------------------------------------

def test_encoder_equals_the_reference(params, tokens):
    enc = DeepseekV3Encoder(config(), jnp.float32)
    want, _ = reference(params, tokens)
    got, states = jax.jit(enc.encode)(params, tokens, enc.init_states(3, 24))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert int(states["pos"]) == 24


@pytest.mark.parametrize("cuts", [(8, 16), (5, 6, 20), (16,)])
def test_one_program_equals_chunk_programs(params, encoder, tokens, cuts):
    want, _ = reference(params, tokens)
    states = encoder.init_states(3, 64)
    outs, lo = [], 0
    for hi in cuts + (24,):
        out, states = compiled(encoder)(params, tokens[:, lo:hi], states)
        outs.append(out)
        lo = hi
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, rtol=2e-5,
                               atol=2e-5)
    assert int(states["pos"]) == 24
    assert int(states["counts"][2]) == len(cuts) + 1


def test_the_encoder_on_the_kernel_equals_the_reference(
        monkeypatch, params, tokens):
    """Every layer's core through the Pallas kernel (interpreted), three
    chunk programs of 8 against the 64-position cache, and the count
    says three layers."""
    _the_rule_says_kernel(monkeypatch, (4, 16, 2))
    enc = build_encoder(config(), params)
    want, _ = reference(params, tokens)
    states = enc.init_states(3, 64)
    outs = []
    for lo in (0, 8, 16):
        out, states = compiled(enc)(params, tokens[:, lo:lo + 8], states)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, rtol=2e-5,
                               atol=2e-5)
    assert enc.counter_attrs([np.asarray(states["counts"])])[
        "attention_kernel_layers"] == 3
    assert int(states["counts"][2]) == 3    # programs: summed, not set


def test_the_encoder_on_the_grouped_matmul_kernels_equals_the_reference(
        monkeypatch, params, tokens):
    """Every expert layer's two grouped products through ``ops/gmm.py``'s
    kernels (interpreted), and the count says two layers."""
    the_rule_says_grouped_kernels(monkeypatch)
    enc = build_encoder(config(), params)
    want, _ = reference(params, tokens)
    states = enc.init_states(3, 64)
    outs = []
    for lo in (0, 8, 16):
        out, states = compiled(enc)(params, tokens[:, lo:lo + 8], states)
        outs.append(out)
    got = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert enc.counter_attrs([np.asarray(states["counts"])])[
        "expert_kernel_layers"] == 2


def test_a_dropped_cache_is_seen(params, encoder, tokens):
    want, _ = reference(params, tokens)
    step = compiled(encoder)
    _, states = step(params, tokens[:, :16], encoder.init_states(3, 64))
    fresh = dict(encoder.init_states(3, 64), pos=states["pos"])
    dropped, _ = step(params, tokens[:, 16:], fresh)
    kept, _ = step(params, tokens[:, 16:], states)
    np.testing.assert_allclose(kept, want[:, 16:], rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(dropped - want[:, 16:]).max()) > 0.05


def _chosen_by_the_program(monkeypatch, enc, params, tokens):
    """The experts every expert layer's router picked, run eagerly with
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
    near-tie can go the other way. Measured here (72 x 8 tokens, 2
    layers, 4 of 16 a token): 1.4 % of assignments differ; the check on
    the chip has its tolerance above what such flips cost (PERF.md §2).
    """
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
        # an assignment that differs, counted once a token-choice
        flipped += sum(len(set(a) - set(b)) for a, b in zip(
            g[~same].tolist(), np.asarray(w)[~same].tolist()))
        total += g.size
    assert 0 < flipped / total < 0.05, flipped / total


# -- through the engine's normal path ----------------------------------------

@pytest.fixture(scope="module")
def engine(params, vocab):
    return InferenceEngine(params, config(), vocab, buckets=(8,),
                           batch_size=4)


def reference_rows(params, id_seqs, pad_id):
    encode = jax.jit(lambda p, t: ref.encode(p, t, MODEL)[0])
    return common.pooled_rows(encode, params, id_seqs, pad_id, 32,
                              block_rows=4)


def test_chunked_through_the_cache_with_narrowing(params, engine, vocab):
    """One group of four at bucket 8: lengths 3 (ends in the first
    chunk), 9 (one token into the second), 17 and 24 (three chunks): the
    batch narrows 4, 4, 2; every row is the reference's whole-document
    forward for that document alone."""
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
    assert counts["state_bytes"] == 4 * 3 * 64 * 20 * 4


def test_the_engine_hands_the_lengths_over(params, engine, monkeypatch):
    """The contract's fourth argument: an encoder that names ``lengths``
    gets each row's valid tokens; one that does not is called as
    before (the stand-ins older tests patch in)."""
    assert engine._encode_takes_lengths
    seqs = [np.arange(20, 31, dtype=np.int32)]
    with_lengths = engine.embed_ids_batch(seqs)
    seen = {}
    real = DeepseekV3Encoder.encode

    def three(self, params, tokens, states):
        seen["called"] = True
        return real(self, params, tokens, states)

    monkeypatch.setattr(DeepseekV3Encoder, "encode", three)
    eng = InferenceEngine(params, config(), engine.vocab, buckets=(8,),
                          batch_size=2)
    assert not eng._encode_takes_lengths
    got = eng.embed_ids_batch(seqs)
    assert seen["called"]
    # padding lanes routed or not, a valid token's row is the same
    np.testing.assert_allclose(got, with_lengths, rtol=1e-5, atol=1e-6)


def test_counts_ride_the_finalize_span(params, engine):
    """``routed_rows`` = assignments of valid tokens to held experts
    over both expert layers, as the reference's choices count them
    (padding lanes are not routed); ``expert_rows_max`` = the busiest
    held expert's rows, averaged over layers and programs."""
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
    assert "routed_rows_run" not in a   # one count until lanes can differ
    assert a["moe_programs"] == 3       # chunks of 8: rows 4, 4, 2
    assert a["expert_rows_mean"] == pytest.approx(want / (3 * 2 * 8))
    # 6 (layer, program) pairs, each with a busiest expert: at least the
    # mean's rows, at most all of the pair's
    assert a["expert_rows_mean"] <= a["expert_rows_max"] <= want / 6
    assert (a["expert_rows_max"] * 6) == pytest.approx(
        round(a["expert_rows_max"] * 6))
    (group,) = [s for s in spans if s["name"] == "engine.group"]
    assert group["attrs"]["state_bytes"] == 4 * 3 * 64 * 20 * 4
    assert group["attrs"]["kv_positions"] == 64
    # an untraced call fetches nothing and records nothing
    assert engine.encoder.counter_attrs([]) == {}


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
    assert fin["attrs"]["expert_kernel_layers"] == 0
    assert fin["attrs"]["moe_programs"] == 2
    enc = engine.encoder
    assert enc.state_counters(enc.init_states(1)).shape == (5,)


def test_a_document_past_the_cache_is_refused(engine):
    with pytest.raises(ValueError, match="kv_positions=64"):
        engine.embed_ids_batch([np.full(70, 25, np.int32)])


@pytest.mark.parametrize("scheduler", ["slots", "ragged"])
def test_other_schedulers_refuse_it_by_name(engine, scheduler):
    with pytest.raises(ValueError) as e:
        engine.embed_issues([{"title": "w1", "body": "w2"}],
                            scheduler=scheduler)
    assert scheduler in str(e.value) and "DeepseekV3" in str(e.value)


# -- the contract ------------------------------------------------------------

def test_it_satisfies_the_contract_and_counts_its_state(encoder):
    assert isinstance(encoder, ChunkEncoder)
    assert encoder.out_dim == 64
    # 3 layers x positions x (16 + 4) float32
    assert encoder.state_bytes_per_row(16) == 3 * 16 * 20 * 4
    assert encoder.state_bytes_per_row(17) == \
        encoder.state_bytes_per_row() == 3 * 64 * 20 * 4
    assert encoder.cache_positions(16) == 16
    assert encoder.cache_positions(17) == encoder.cache_positions() == 64
    states = encoder.init_states(2, 16)
    got = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(states))
    assert got - 4 - 5 * 4 == 2 * encoder.state_bytes_per_row(16)
    with pytest.raises(ValueError, match="kv_positions=64"):
        encoder.cache_positions(65)


def test_published_sizes_carry_eleven_point_eight_megabytes_a_row():
    published = dict(
        vocab_size=16160, num_hidden_layers=5, first_k_dense_replace=1,
        experts_held={"first": 0, "count": 16, "of": 256},
        n_routed_experts=16, rope_scaling=YARN)
    enc = build_encoder(make_config("deepseek_v3", published,
                                    kv_positions=2048))
    cfg = enc.config
    assert (cfg.n_routed_experts, cfg.experts_held) == (256, (0, 16))
    assert cfg.latent_dim == 576 and cfg.q_head_dim == 192
    assert enc.state_bytes_per_row(2048) == 5 * 2048 * 576 * 2 == 11796480
    # 128 full key/value heads would take 71 times as much
    full = 128 * (192 + 128) * 2
    assert full == 81920 and full * 2048 * 5 // 11796480 == 71


def test_config_from_the_published_keys_and_the_share():
    cfg = config()
    assert (cfg.n_routed_experts, cfg.experts_held) == (16, (4, 8))
    assert cfg.rope["factor"] == 40 and hash(cfg) == hash(config())
    whole = make_config("deepseek_v3", {k: v for k, v in UNCUT.items()
                                        if k != "experts_held"})
    assert whole.experts_held == (0, 16)
    with pytest.raises(ValueError, match="not the count"):
        make_config("deepseek_v3", dict(MODEL, n_routed_experts=16))
    with pytest.raises(ValueError, match="outside the router"):
        dataclasses.replace(cfg, experts_held=(12, 8))
    with pytest.raises(ValueError, match="sigmoid"):
        dataclasses.replace(cfg, scoring_func="softmax")


@pytest.mark.parametrize("architecture,cls", [
    ("awd_lstm", AWDLSTMConfig), ("granite_hybrid", GraniteHybridConfig),
    ("deepseek_v3", DeepseekV3Config)])
def test_one_table_from_architecture_to_config_and_encoder(architecture,
                                                          cls):
    models = {
        "awd_lstm": {"vocab_size": 50, "emb_sz": 8, "n_hid": 12,
                     "n_layers": 2, "dtype": "float32"},
        "granite_hybrid": {"vocab_size": 50, "hidden_size": 16,
                           "num_hidden_layers": 1, "layer_types": ["mamba"],
                           "mamba_n_heads": 4, "mamba_d_head": 8,
                           "mamba_d_state": 4},
        "deepseek_v3": MODEL}
    cfg = make_config(architecture, models[architecture])
    assert type(cfg) is cls and cls.architecture == architecture
    assert {"awd_lstm", "deepseek_v3", "granite_hybrid"} <= set(
        contract.ENCODERS)
    enc = build_encoder(cfg)
    assert enc.window_positions(64) == 0     # no ring in any of the three
    assert isinstance(enc, ChunkEncoder)
    counts = enc.state_counters(enc.init_states(1))
    assert (counts is None) == (architecture != "deepseek_v3")
    assert enc.counter_attrs([]) == {}
    with pytest.raises(ValueError) as e:
        make_config("transformer_xl", {})
    assert all(name in str(e.value) for name in contract.ENCODERS)
    with pytest.raises(ValueError, match="no encoder for a dict"):
        build_encoder({})


def test_export_round_trip_in_bfloat16(tmp_path, vocab):
    from code_intelligence_tpu.training.checkpoint import export_encoder

    cfg = make_config("deepseek_v3", MODEL, kv_positions=64)
    weights = seeded(ref, 1, MODEL, dtype=jnp.bfloat16)
    export_encoder(tmp_path, weights, cfg, vocab)
    eng = InferenceEngine.from_export(tmp_path, buckets=(8, 16),
                                      batch_size=2)
    assert eng.config == cfg and eng.encoder.dtype == jnp.bfloat16
    assert eng.config.state_dtype == jnp.bfloat16
    assert eng._enc_params["params"]["layers"]["layer_2"]["bias"].dtype \
        == jnp.float32
    direct = InferenceEngine(weights, cfg, vocab, buckets=(8, 16),
                             batch_size=2)
    seqs = [np.arange(20, 45, dtype=np.int32)]
    np.testing.assert_array_equal(eng.embed_ids_batch(seqs),
                                  direct.embed_ids_batch(seqs))
