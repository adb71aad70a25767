"""The plain references against the program at float32 on seeded
weights, tiny, on the CPU: LSTM and QRNN encoders (one pass and chunked
with carried state), the pooled rows through the engine, the documents'
planned token ids against the program's tokeniser, and the one-cycle
schedules against optax."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.reference import awd_lstm, awd_qrnn, common, lm_train

MODEL = {"vocab_size": 300, "emb_sz": 12, "n_hid": 20, "n_layers": 3,
         "tie_weights": True, "dtype": "float32"}
ARCH = {"awd_lstm": awd_lstm, "awd_qrnn": awd_qrnn}


def program_encoder(qrnn: bool):
    from code_intelligence_tpu.models import AWDLSTMConfig, AWDLSTMEncoder

    cfg = AWDLSTMConfig(vocab_size=MODEL["vocab_size"],
                        emb_sz=MODEL["emb_sz"], n_hid=MODEL["n_hid"],
                        n_layers=MODEL["n_layers"], qrnn=qrnn,
                        dtype=jnp.float32)
    return cfg, AWDLSTMEncoder(cfg)


@pytest.mark.parametrize("arch", ["awd_lstm", "awd_qrnn"])
@pytest.mark.parametrize("weights", [None, {"dist": "student_t", "df": 4}],
                         ids=["uniform", "student_t"])
def test_encoder_reference_matches_the_program(arch, weights):
    from code_intelligence_tpu.models import init_lstm_states

    qrnn = arch == "awd_qrnn"
    model = dict(MODEL, qrnn=qrnn)
    cfg, enc = program_encoder(qrnn)
    params = ARCH[arch].init_params(common.seed_key(2**31 + 3), model,
                                    weights)
    # the layout is the program's own
    shapes = jax.eval_shape(
        lambda k: enc.init({"params": k}, jnp.zeros((2, 4), jnp.int32),
                           init_lstm_states(cfg, 2)),
        jax.random.PRNGKey(0))["params"]
    assert jax.tree.map(lambda a: a.shape, params) == \
        jax.tree.map(lambda a: a.shape, dict(shapes))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (5, 24), 0,
                                model["vocab_size"])
    want, want_states = ARCH[arch].encode(params, tokens, model)
    got, _, got_states = enc.apply({"params": params}, tokens,
                                   init_lstm_states(cfg, 5))
    np.testing.assert_allclose(got, want, atol=2e-6)
    # chunked with carried state = one pass (what the engine relies on)
    a, st = ARCH[arch].encode(params, tokens[:, :10], model)
    b, st2 = ARCH[arch].encode(params, tokens[:, 10:], model, states=st)
    np.testing.assert_allclose(jnp.concatenate([a, b], 1), want, atol=2e-6)
    for g, w in zip(jax.tree.leaves(got_states), jax.tree.leaves(st2)):
        np.testing.assert_allclose(g, w, atol=2e-6)


def test_student_t_weights_keep_the_inits_variance():
    u = common.draw(jax.random.PRNGKey(0), (400, 500), 0.02)
    t = common.draw(jax.random.PRNGKey(0), (400, 500), 0.02,
                    {"dist": "student_t", "df": 4})
    assert float(jnp.std(t)) == pytest.approx(float(jnp.std(u)), rel=0.1)
    assert float(jnp.abs(t).max()) > 3 * float(jnp.abs(u).max())


def test_pool_rows_is_mean_max_last_of_the_valid_prefix():
    raw = np.arange(2 * 4 * 3, dtype=np.float64).reshape(2, 4, 3)
    rows = common.pool_rows(raw, [2, 4])
    np.testing.assert_allclose(rows[0], np.concatenate(
        [raw[0, :2].mean(0), raw[0, :2].max(0), raw[0, 1]]))
    np.testing.assert_allclose(rows[1, 6:], raw[1, 3])


def test_fake_quant_int8_is_per_channel_and_coarse():
    w = jnp.asarray([[1.0, 0.5, -0.004], [100.0, -50.0, 0.3]])
    q = common.fake_quant_int8(w)
    assert float(q[0, 0]) == 1.0 and float(q[1, 0]) == 100.0
    assert float(q[1, 2]) == 0.0  # under half a step of its channel
    assert float(jnp.abs(q - w).max()) <= 100.0 / 127 / 2 + 1e-6


def test_planned_ids_are_what_the_program_tokenises():
    from code_intelligence_tpu.text import (SPECIALS, Tokenizer, Vocab,
                                            build_issue_text)

    words = traffic.vocab_words(SPECIALS, 2000)
    vocab, tok = Vocab(words), Tokenizer(backend="python")
    mix = {"docs_per_call": 40, "length": {
        "dist": "lognormal", "median": 120, "sigma": 1.0, "min": 8,
        "max": 2048}}
    for call in traffic.make_document_calls(mix, words, 2**31 + 9, 1):
        for d in call:
            ids = vocab.numericalize(
                tok.tokenize(build_issue_text(d["title"], d["body"])))
            assert ids.tolist() == d["ids"].tolist()
    plan = traffic.DocumentPlan(words)
    rng = np.random.default_rng(0)
    for length in list(range(3, 40)) + [333, 1024, 2048]:
        ids = traffic._no_adjacent_repeats(
            rng.integers(plan.first_word, 2000, length + 8),
            plan.first_word, 2000)
        d = plan.build(length, ids)
        got = vocab.numericalize(
            tok.tokenize(build_issue_text(d["title"], d["body"])))
        assert got.tolist() == d["ids"].tolist() and len(got) == length


def test_one_cycle_schedules_are_the_programs():
    from code_intelligence_tpu.training import schedules

    lr = schedules.one_cycle_lr(1000, 2.6e-3)
    mom = schedules.one_cycle_momentum(1000, 0.85, 0.95)
    for step in (0, 1, 2, 150, 299, 300, 301, 700, 999):
        assert float(lm_train.one_cycle_lr(step, 1000, 2.6e-3)) == \
            pytest.approx(float(lr(step)), rel=1e-5, abs=1e-9)
        assert float(lm_train.one_cycle_momentum(step, 1000)) == \
            pytest.approx(float(mom(step)), rel=1e-6)
