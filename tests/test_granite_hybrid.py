"""The hybrid (Mamba-2 + GQA attention) encoder and the encoder contract.

Small on the CPU (hidden 64, 2 Mamba + 1 attention + 1 Mamba layers,
scan chunk 8, vocabulary 300): the chunked scan against the
token-by-token recurrence; the program's encoder against the plain
reference (`benchmark/reference/granite_hybrid.py`); a document as ONE
program against the same document through 2, 3 and 4 chunk programs with
carried conv tail, SSM state and key/value cache; rows that end beside
rows that go on; `embed_issues` through the engine's normal `groups`
path; the AWD encoders through the same contract, bit-identical to the
forward the engine compiled before it; what the other schedulers and
the capacity planner do with an encoder outside theirs.
"""

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.reference import common
from benchmark.reference import granite_hybrid as ref
from code_intelligence_tpu.inference import InferenceEngine
from code_intelligence_tpu.models import (
    AWDLSTMConfig, AWDLSTMEncoder, ChunkEncoder, build_encoder,
    init_lstm_states, make_config)
from code_intelligence_tpu.ops.attention import gqa_cached
from code_intelligence_tpu.ops.ssd import (
    causal_conv1d, ssd_recurrence, ssd_scan)
from code_intelligence_tpu.text import SPECIALS, Vocab
from encoder_programs import compiled, seeded

MODEL = {
    "vocab_size": 300, "hidden_size": 64, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 128, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_n_groups": 1,
    "mamba_expand": 2, "mamba_chunk_size": 8, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
    "rms_norm_eps": 1e-5, "logits_scaling": 8, "rope_theta": 10000}
FIXTURE = (Path(__file__).resolve().parents[1] / "code_intelligence_tpu"
           / "inference" / "fixtures" / "ragged_lengths.json")


@pytest.fixture(scope="module")
def params():
    return seeded(ref, 26, MODEL, {"dist": "student_t", "df": 4})


@pytest.fixture(scope="module")
def encoder(params):
    return build_encoder(
        make_config("granite_hybrid", MODEL, kv_positions=128), params)


@pytest.fixture(scope="module")
def vocab():
    # the benchmark's pseudo-words: one token each through the tokeniser
    return Vocab(traffic.vocab_words(SPECIALS, 300))


def reference_hidden(params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, t: ref.encode(p, t, MODEL)[0])(params, tokens))


# -- ops ---------------------------------------------------------------------

@pytest.mark.parametrize("T,chunk", [(8, 8), (24, 8), (21, 8), (5, 8),
                                     (32, 16)])
def test_chunked_scan_equals_the_recurrence(T, chunk):
    """From a NON-ZERO state, any length (a ragged last chunk is padded
    with dt = 0): outputs and the state handed back."""
    b, H, P, N = 2, 3, 4, 5

    @jax.jit       # the draws as one program, the scan one a length
    def inputs(key):
        k = iter(jax.random.split(key, 8))
        return (jax.random.normal(next(k), (b, T, H, P)),
                jax.nn.softplus(jax.random.normal(next(k), (b, T, H)) - 2.0),
                -jax.random.uniform(next(k), (H,), minval=1.0, maxval=16.0),
                jax.random.normal(next(k), (b, T, N)),
                jax.random.normal(next(k), (b, T, N)),
                jax.random.normal(next(k), (H,)),
                jax.random.normal(next(k), (b, H, P, N)))

    x, dt, A, B, C, D, S0 = inputs(jax.random.PRNGKey(T * 31 + chunk))
    scan = jax.jit(functools.partial(ssd_scan, mxu_dtype=jnp.float32),
                   static_argnums=7)
    want_y, want_S = jax.jit(ssd_recurrence)(x, dt, A, B, C, D, S0)
    got_y, got_S = scan(x, dt, A, B, C, D, S0, chunk)
    np.testing.assert_allclose(got_y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_S, want_S, rtol=2e-5, atol=2e-5)
    # and in two calls, the state carried between them
    if T > 3:
        cut = T // 2 + 1  # not a multiple of the chunk
        y1, S1 = scan(x[:, :cut], dt[:, :cut], A, B[:, :cut], C[:, :cut],
                      D, S0, chunk)
        y2, S2 = scan(x[:, cut:], dt[:, cut:], A, B[:, cut:], C[:, cut:],
                      D, S1, chunk)
        np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), want_y,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(S2, want_S, rtol=2e-5, atol=2e-5)


def test_conv_carries_its_last_inputs():
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k[0], (2, 11, 6))
    w = jax.random.normal(k[1], (6, 4))
    bias = jax.random.normal(k[2], (6,))
    zero = jnp.zeros((2, 3, 6))
    whole, tail = causal_conv1d(x, w, bias, zero)
    a, t = causal_conv1d(x[:, :5], w, bias, zero)
    b, t = causal_conv1d(x[:, 5:], w, bias, t)
    np.testing.assert_allclose(jnp.concatenate([a, b], 1), whole, atol=1e-6)
    np.testing.assert_array_equal(t, tail)
    # out[t] = b + sum_k w[:, k] x[t - 3 + k]
    np.testing.assert_allclose(
        whole[:, 3], bias + sum(w[:, k] * x[:, k] for k in range(4)),
        atol=1e-6)


@pytest.mark.parametrize("T", [6, 256])   # one block; two query blocks
def test_cached_attention_equals_dense_causal(T):
    b, Hq, Hkv, d, S = 2, 4, 2, 8, 2 * T + 8
    k = jax.random.split(jax.random.PRNGKey(T), 6)
    q = jax.random.normal(k[0], (b, 2 * T, Hq, d))
    kk = jax.random.normal(k[1], (b, 2 * T, Hkv, d))
    v = jax.random.normal(k[2], (b, 2 * T, Hkv, d))
    s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(kk, 2, axis=2)) * 0.3
    s = jnp.where(jnp.tril(jnp.ones((2 * T, 2 * T), bool)), s, -jnp.inf)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1),
                      jnp.repeat(v, 2, axis=2))
    kc = vc = jnp.zeros((b, Hkv, S, d))     # head-major
    pos = jnp.zeros((), jnp.int32)
    step = jax.jit(functools.partial(       # ``pos`` traced: one program
        gqa_cached, scale=0.3, q_block=128, mxu_dtype=jnp.float32))
    outs = []
    for lo in (0, T):  # two chunks through the cache
        out, kc, vc = step(
            q[:, lo:lo + T], kk[:, lo:lo + T], v[:, lo:lo + T], kc, vc,
            pos + lo)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want,
                               rtol=2e-5, atol=2e-5)


# -- the encoder against the reference --------------------------------------

def test_encoder_equals_the_reference(params, encoder):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 44), 0, 300)
    want = reference_hidden(params, tokens)
    got, _ = compiled(encoder)(params, tokens, encoder.init_states(3, 44))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    assert got.shape == (3, 44, encoder.out_dim)


@pytest.mark.parametrize("cuts,kernel", [
    ((19,), False), ((13, 30), False), ((7, 18, 33), False),
    ((16, 32), True)],
    ids=["2_programs", "3_programs", "4_programs", "3_on_the_kernel"])
def test_one_program_equals_chunk_programs(monkeypatch, params, encoder,
                                           cuts, kernel):
    """Boundaries that are no multiple of the scan's chunk (8): the conv
    tail, the SSM state and the key/value cache all cross them. Once
    with the chunk programs' attention on the Pallas core (interpreted
    here; the rule's answer and tiles that divide 16, 12 and the 128
    slots come from the test): the whole document stays the XLA core's."""
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 44), 0, 300)
    whole, whole_states = compiled(encoder)(
        params, tokens, encoder.init_states(2, 44))
    np.testing.assert_allclose(whole, reference_hidden(params, tokens),
                               rtol=1e-4, atol=2e-5)
    states = encoder.init_states(2)        # the whole cache
    step = compiled(encoder)
    if kernel:
        from code_intelligence_tpu.ops import attention
        monkeypatch.setattr(attention, "core_is_kernel", lambda *a: True)
        monkeypatch.setattr(attention, "_kernel_tiles", lambda *a: (4, 32))
        real, traced = attention._kernel_core, []
        monkeypatch.setattr(
            attention, "_kernel_core",
            lambda *a, **kw: traced.append(a[0].shape[1]) or real(*a, **kw))
        # an encoder and a jit of its own a program, built after the
        # patch: every trace sees it, and ``traced`` reads one attention
        # layer a program
        step = lambda *a: jax.jit(  # noqa: E731
            build_encoder(encoder.config, params).encode)(*a)
    parts = []
    for lo, hi in zip((0,) + cuts, cuts + (44,)):
        out, states = step(params, tokens[:, lo:hi], states)
        parts.append(out)
    np.testing.assert_allclose(jnp.concatenate(parts, 1), whole,
                               rtol=1e-4, atol=2e-5)
    if kernel:      # the one attention layer of each program
        assert traced == [16, 16, 12]
    assert int(states["pos"]) == 44
    for a, b in zip(states["ssm"], whole_states["ssm"]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)
    for a, b in zip(states["conv"], whole_states["conv"]):
        np.testing.assert_allclose(a, b, atol=1e-6)
    np.testing.assert_allclose(states["k"][:, :, :, :44],
                               whole_states["k"][:, :, :, :44], atol=1e-5)


def test_a_dropped_carry_is_seen(params, encoder):
    """The seeded scan parameters make the state matter: the second
    chunk from a zero state is far from the second half of the whole."""
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 300)
    step = compiled(encoder)
    whole, _ = step(params, tokens, encoder.init_states(2, 32))
    fresh, _ = step(params, tokens[:, 16:], encoder.init_states(2, 16))
    err = np.sqrt(np.mean((np.asarray(fresh - whole[:, 16:])) ** 2))
    assert err > 0.05 * np.sqrt(np.mean(np.asarray(whole) ** 2))


def test_reference_states_match_the_programs(params, encoder):
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 20), 0, 300)
    with jax.default_matmul_precision("highest"):
        _, want = jax.jit(lambda p, t: ref.encode(p, t, MODEL))(
            params, tokens)
    _, got = compiled(encoder)(params, tokens, encoder.init_states(2, 20))
    flat = [got["ssm"][r][i] for r in range(len(got["ssm"]))
            for i in range(got["ssm"][r].shape[0])]
    assert len(flat) == len(want) == 3
    for g, (S, tail) in zip(flat, want):
        np.testing.assert_allclose(g, S, rtol=1e-4, atol=2e-5)


# -- through the engine's normal path ---------------------------------------

@pytest.fixture(scope="module")
def engine(params, vocab):
    cfg = make_config("granite_hybrid", MODEL, kv_positions=128)
    return InferenceEngine(params, cfg, vocab, buckets=(8, 16),
                           batch_size=4)


def reference_rows(params, id_seqs, pad_id):
    encode = jax.jit(lambda p, t: ref.encode(p, t, MODEL)[0])
    return common.pooled_rows(encode, params, id_seqs, pad_id, 128,
                              block_rows=4)


def test_rows_that_end_beside_rows_that_go_on(params, engine, vocab):
    """One group of four: lengths 3 (ends in the first chunk), 17 (one
    token into the second), 33 and 40 (three chunks of 16); each row's
    pooled embedding is the reference's for that document alone."""
    rng = np.random.default_rng(7)
    seqs = [rng.integers(20, 300, n).astype(np.int32)
            for n in (40, 3, 17, 33)]
    got = engine.embed_ids_batch(seqs)
    assert got.shape == (4, 3 * 64) == (4, engine.embed_dim)
    want = reference_rows(params, seqs, vocab.pad_id)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


def test_embed_issues_equals_the_references_pooled_rows(params, engine):
    rng = np.random.default_rng(8)
    words = engine.vocab.itos[len(SPECIALS) + 3:]

    def text(n):
        return " ".join(words[j] for j in rng.integers(0, len(words), n))

    issues = [{"title": text(3), "body": text(n)}
              for n in (2, 30, 9, 14, 5, 21, 40, 11, 3)]
    got = engine.embed_issues(issues)
    from code_intelligence_tpu.text import build_issue_text
    seqs = [engine.numericalize(build_issue_text(d["title"], d["body"]))
            for d in issues]
    assert max(len(s) for s in seqs) > 16   # some cross chunk programs
    want = reference_rows(params, seqs, engine.vocab.pad_id)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(
        engine.embed_text(text(5)).shape, (engine.embed_dim,))


def test_group_span_carries_state_bytes_and_kv_positions(engine):
    _, counts = engine._embed_group_device(
        [np.arange(20, 25, dtype=np.int32), np.arange(20, 60, dtype=np.int32)])
    assert counts["chunks"] == 3 and counts["kv_positions"] == 128
    assert counts["state_bytes"] == 4 * engine.encoder.state_bytes_per_row(48)
    _, short = engine._embed_group_device([np.arange(20, 28, dtype=np.int32)])
    assert short["chunks"] == 1 and short["kv_positions"] == 8
    assert short["state_bytes"] < counts["state_bytes"]


def test_a_document_past_the_cache_is_refused(engine):
    with pytest.raises(ValueError, match="kv_positions=128"):
        engine.embed_ids_batch([np.full(130, 25, np.int32)])


@pytest.mark.parametrize("scheduler", ["slots", "ragged"])
def test_other_schedulers_refuse_the_hybrid_by_name(params, engine, vocab,
                                                    scheduler):
    for call in (
            lambda: engine.embed_issues([{"title": "w1", "body": "w2"}],
                                        scheduler=scheduler),
            lambda: engine.slot_scheduler(ragged=scheduler == "ragged"),
            lambda: InferenceEngine(params, engine.config, vocab,
                                    scheduler=scheduler)):
        with pytest.raises(ValueError) as e:
            call()
        assert scheduler in str(e.value)
        assert "GraniteHybridEncoder" in str(e.value)


def test_awd_only_knobs_refuse_the_hybrid(params, engine, vocab):
    for kw in ({"precision": "int8"}, {"lstm_pallas": True}):
        with pytest.raises(ValueError, match="AWD-LSTM encoder only"):
            InferenceEngine(params, engine.config, vocab, **kw)


def test_serve_dtype_follows_the_weights(params, vocab):
    cfg = make_config("granite_hybrid", MODEL, kv_positions=64)
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    eng = InferenceEngine(half, cfg, vocab, buckets=(8, 16), batch_size=2)
    assert eng.encoder.dtype == jnp.bfloat16
    states = eng.encoder.init_states(2, 16)
    assert states["k"].dtype == states["conv"][0].dtype == jnp.bfloat16
    assert states["ssm"][0].dtype == jnp.float32
    seqs = [np.arange(20, 50, dtype=np.int32)]
    got = eng.embed_ids_batch(seqs)
    want = reference_rows(jax.tree.map(lambda a: a.astype(jnp.float32), half),
                          seqs, vocab.pad_id)
    assert np.isfinite(got).all()
    assert np.sqrt(np.mean((got - want) ** 2)) < 0.05 * np.sqrt(
        np.mean(want ** 2))


# -- the contract ------------------------------------------------------------

def awd_engine(qrnn, vocab, **kw):
    cfg = AWDLSTMConfig(vocab_size=300, emb_sz=8, n_hid=12, n_layers=3,
                        qrnn=qrnn, pad_id=vocab.pad_id)
    params = jax.jit(AWDLSTMEncoder(cfg).init)(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 4), np.int32),
        init_lstm_states(cfg, 1))["params"]
    return InferenceEngine(params, cfg, vocab, **{
        "buckets": (8, 16, 32), "batch_size": 4, **kw})


@pytest.mark.parametrize("qrnn", [False, True], ids=["lstm", "qrnn"])
def test_awd_rows_are_bit_identical_through_the_contract(vocab, qrnn):
    """The committed mixed-length fixture through the engine, against
    the forward the engine compiled BEFORE the contract: Flax ``apply``
    on ``init_lstm_states`` and the shared pooling, group by group."""
    eng = awd_engine(qrnn, vocab)
    lengths = json.loads(FIXTURE.read_text())["lengths"][:24]
    rng = np.random.default_rng(0)
    seqs = [rng.integers(20, 300, n).astype(np.int32) for n in lengths]
    got = eng.embed_ids_batch(seqs)

    @jax.jit
    def old_fwd(p, tokens, lens, states, pool):
        raw, _, new = eng.encoder.apply(p, tokens, states,
                                        deterministic=True)
        return InferenceEngine._accumulate_pool(raw, lens, pool), new

    want = np.zeros_like(got)
    order = np.argsort([len(s) for s in seqs], kind="stable")
    B = eng.batch_size
    for start in range(0, len(order), B):
        idx = order[start:start + B]
        group = [seqs[i] for i in idx]
        longest = max(len(s) for s in group)
        bucket = eng._bucket_for(longest) if longest <= eng.buckets[-1] \
            else eng.chunk_len
        states = init_lstm_states(eng.config, B)
        pool = eng._init_pool_state(B)
        for ci in range(max(1, -(-longest // bucket))):
            tokens = np.full((B, bucket), vocab.pad_id, np.int32)
            lens = np.zeros((B,), np.int32)
            for r, s in enumerate(group):
                chunk = s[ci * bucket:(ci + 1) * bucket]
                tokens[r, :len(chunk)] = chunk
                lens[r] = len(chunk)
            pool, states = old_fwd(eng._enc_params, tokens, lens, states,
                                   pool)
        want[idx] = eng._finalize(pool)[:len(idx)]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("qrnn", [False, True], ids=["lstm", "qrnn"])
def test_awd_encoder_satisfies_the_contract(vocab, qrnn):
    eng = awd_engine(qrnn, vocab)
    enc = eng.encoder
    assert isinstance(enc, ChunkEncoder)
    assert enc.out_dim == 8 and eng.embed_dim == 24
    assert enc.cache_positions(512) == 0
    states = enc.init_states(3, 999)
    assert jax.tree.structure(states) == jax.tree.structure(
        init_lstm_states(eng.config, 3))
    # (h, c) or (h, x_prev) a layer, float32
    widths = [12, 12, 8]
    second = [8, 12, 12] if qrnn else widths
    assert enc.state_bytes_per_row() == enc.state_bytes_per_row(2048) == \
        4 * (sum(widths) + sum(second))
    hidden, new = enc.encode(eng._enc_params["params"],
                             np.zeros((3, 5), np.int32), states)
    assert hidden.shape == (3, 5, 8)
    assert jax.tree.structure(new) == jax.tree.structure(states)
    _, counts = eng._embed_group_device([np.arange(20, 60, dtype=np.int32)])
    assert counts["kv_positions"] == 0
    assert counts["state_bytes"] == 4 * enc.state_bytes_per_row()


def test_hybrid_satisfies_the_contract_and_counts_its_state(encoder):
    assert isinstance(encoder, ChunkEncoder)
    cfg = encoder.config
    assert [(k, n) for k, _, n in cfg.runs()] == \
        [("mamba", 2), ("attention", 1), ("mamba", 1)]
    fixed = 3 * (8 * 16 * 16 * 4 + 3 * (128 + 32) * 4)
    assert encoder.state_bytes_per_row(16) == fixed + 16 * 2 * 2 * 16 * 4
    assert encoder.state_bytes_per_row(33) == \
        encoder.state_bytes_per_row() == fixed + 128 * 2 * 2 * 16 * 4
    states = encoder.init_states(2, 16)
    got = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(states)) - 4
    assert got == 2 * encoder.state_bytes_per_row(16)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(cfg, num_hidden_layers=5)
    with pytest.raises(ValueError, match="unknown architecture"):
        make_config("transformer_xl", {})


def test_export_round_trip_rebuilds_the_hybrid(tmp_path, vocab):
    from code_intelligence_tpu.training.checkpoint import export_encoder

    cfg = make_config("granite_hybrid", MODEL, kv_positions=64)
    weights = seeded(ref, 1, MODEL, dtype=jnp.bfloat16)
    export_encoder(tmp_path, weights, cfg, vocab)
    eng = InferenceEngine.from_export(tmp_path, buckets=(8, 16),
                                      batch_size=2)
    assert eng.config == cfg and eng.encoder.dtype == jnp.bfloat16
    direct = InferenceEngine(weights, cfg, vocab, buckets=(8, 16),
                             batch_size=2)
    seqs = [np.arange(20, 45, dtype=np.int32)]
    np.testing.assert_array_equal(eng.embed_ids_batch(seqs),
                                  direct.embed_ids_batch(seqs))


def test_capacity_report_reads_state_bytes_from_the_contract(engine, vocab):
    from code_intelligence_tpu.utils.memtrack import DeviceMemoryLedger

    for eng in (engine, awd_engine(False, vocab)):
        geometry = eng.state_geometry()
        assert geometry["state_bytes_per_row"] == \
            eng.encoder.state_bytes_per_row()
        assert geometry["out_dim"] == eng.encoder.out_dim
        ledger = DeviceMemoryLedger()
        ledger.note_geometry(**geometry)
        cap = ledger.capacity_report(budget_bytes=1 << 30)
        assert cap["rows_fit"] == cap["headroom_bytes"] \
            // geometry["state_bytes_per_row"]
    assert DeviceMemoryLedger().capacity_report(
        budget_bytes=1 << 20)["rows_fit"] is None


def test_server_serves_the_hybrid_on_groups(tmp_path, vocab):
    """The real CLI: an export of the hybrid behind ``--scheduler groups``
    answers POST /text with the engine's own row, and notes the
    encoder's state bytes for the capacity planner; the default
    scheduler (``slots``) refuses it by name before binding."""
    import threading
    import urllib.request

    from code_intelligence_tpu.serving.server import build_server
    from code_intelligence_tpu.training.checkpoint import export_encoder

    cfg = make_config("granite_hybrid", MODEL, kv_positions=64)
    weights = seeded(ref, 2, MODEL)
    export_encoder(tmp_path, weights, cfg, vocab)
    with pytest.raises(ValueError, match="'slots'.*GraniteHybridEncoder"):
        build_server(["--model_dir", str(tmp_path), "--port", "0"])
    srv = build_server(["--model_dir", str(tmp_path), "--port", "0",
                        "--host", "127.0.0.1", "--scheduler", "groups",
                        "--batch_size", "2"])
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        words = vocab.itos[len(SPECIALS) + 3:]
        title, body = " ".join(words[:3]), " ".join(words[5:30])
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.server_address[1]}/text",
            data=json.dumps({"title": title, "body": body}).encode())
        with urllib.request.urlopen(req) as r:
            row = np.frombuffer(r.read(), "<f4")
        assert row.shape == (3 * 64,)
        np.testing.assert_allclose(row, srv.engine.embed_issue(title, body),
                                   rtol=1e-5, atol=1e-6)
        cap = srv.ledger.capacity_report(budget_bytes=1 << 30)
        assert cap["geometry"]["state_bytes_per_row"] == \
            srv.engine.encoder.state_bytes_per_row()
        assert cap["rows_fit"] > 0
    finally:
        srv.shutdown()


def test_state_backpressure_changes_no_row(engine, monkeypatch):
    """With the in-flight budget at nothing the host waits for every
    group but the newest; the rows are the same rows."""
    rng = np.random.default_rng(9)
    seqs = [rng.integers(20, 300, n).astype(np.int32)
            for n in (5, 40, 9, 33, 12, 3, 20, 17, 8, 25)]
    want = engine.embed_ids_batch(seqs)
    monkeypatch.setattr(type(engine), "_STATE_BYTES_IN_FLIGHT", 0)
    np.testing.assert_array_equal(engine.embed_ids_batch(seqs), want)
