"""Inference-engine + embedding-server tests.

The key invariants: pooled output == hand-computed [mean, max, last] over
the final hidden states (`inference.py:89-93`); chunked long-doc forward ==
one full forward; batch order preserved through length-sorting; the REST
wire contract (raw '<f4' bytes, `app.py:69`).
"""

import json
import threading
import urllib.request

import jax
import numpy as np
import pytest

from code_intelligence_tpu.inference import EMBED_TRUNCATE_DIM, InferenceEngine
from code_intelligence_tpu.models import AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states
from code_intelligence_tpu.text import SPECIALS, Vocab


def make_engine(**kw):
    cfg = AWDLSTMConfig(vocab_size=200, emb_sz=8, n_hid=12, n_layers=2)
    enc = AWDLSTMEncoder(cfg)
    tokens = np.zeros((1, 4), np.int32)
    params = enc.init(
        {"params": jax.random.PRNGKey(0)}, tokens, init_lstm_states(cfg, 1)
    )["params"]
    words = [f"w{i}" for i in range(150)]
    vocab = Vocab(SPECIALS + words)
    return InferenceEngine(params, cfg, vocab, **kw)


@pytest.fixture(scope="module")
def engine():
    return make_engine(buckets=(8, 16), batch_size=4)


def test_engine_lstm_pallas_override_is_tpu_gated():
    eng = make_engine(buckets=(8,), batch_size=1, lstm_pallas=True)
    # on the CPU backend the override must NOT enable the TPU-only kernel
    assert eng.config.lstm_use_pallas == (jax.default_backend() == "tpu")
    assert eng.embed_text("hello world").shape == (24,)


class TestPooling:
    def test_matches_manual_pool(self, engine):
        ids = np.array([30, 31, 32, 33, 34], np.int32)
        emb = engine.embed_ids_batch([ids])[0]
        # manual full forward
        states = init_lstm_states(engine.config, 1)
        raw, _, _ = engine.encoder.apply(
            engine._enc_params, ids[None, :], states, deterministic=True
        )
        raw = np.asarray(raw, np.float32)[0]
        manual = np.concatenate([raw.mean(0), raw.max(0), raw[-1]])
        np.testing.assert_allclose(emb, manual, rtol=1e-5, atol=1e-6)

    def test_embedding_dim(self, engine):
        e = engine.embed_text("w1 w2 w3")
        assert e.shape == (3 * engine.config.emb_sz,)

    def test_chunked_long_doc_equals_full(self, engine):
        # doc longer than the biggest bucket (16) -> chunked path with state
        # carry; must equal a single full-length forward.
        rng = np.random.RandomState(0)
        ids = rng.randint(20, 150, 45).astype(np.int32)
        emb = engine.embed_ids_batch([ids])[0]
        states = init_lstm_states(engine.config, 1)
        raw, _, _ = engine.encoder.apply(
            engine._enc_params, ids[None, :], states, deterministic=True
        )
        raw = np.asarray(raw, np.float32)[0]
        manual = np.concatenate([raw.mean(0), raw.max(0), raw[-1]])
        np.testing.assert_allclose(emb, manual, rtol=1e-4, atol=1e-5)

    def test_padding_is_masked(self, engine):
        # Same doc alone vs batched with a longer doc: embedding must match.
        a = np.array([40, 41, 42], np.int32)
        b = np.array([50, 51, 52, 53, 54, 55, 56], np.int32)
        solo = engine.embed_ids_batch([a])[0]
        batched = engine.embed_ids_batch([a, b])[0]
        np.testing.assert_allclose(solo, batched, rtol=1e-5, atol=1e-6)

    def test_batch_order_preserved(self, engine):
        rng = np.random.RandomState(1)
        seqs = [rng.randint(20, 150, rng.randint(2, 14)).astype(np.int32) for _ in range(9)]
        batch = engine.embed_ids_batch(seqs)
        for i, s in enumerate(seqs):
            solo = engine.embed_ids_batch([s])[0]
            np.testing.assert_allclose(batch[i], solo, rtol=1e-5, atol=1e-6, err_msg=str(i))

    def test_state_reset_between_docs(self, engine):
        # Embedding must not depend on what was embedded before
        # (encoder.reset() semantics, inference.py:60,70).
        ids = np.array([60, 61, 62], np.int32)
        e1 = engine.embed_ids_batch([ids])[0]
        engine.embed_ids_batch([np.array([100, 101, 102, 103], np.int32)])
        e2 = engine.embed_ids_batch([ids])[0]
        np.testing.assert_array_equal(e1, e2)

    def test_expired_deadline_never_dispatches(self, engine):
        # resilience backstop: budget-dead work raises before any device
        # program is enqueued (the serve path maps this to a 429 shed)
        from code_intelligence_tpu.utils import resilience

        ids = np.array([30, 31, 32], np.int32)
        dl = resilience.Deadline(-1.0)
        with resilience.deadline_scope(dl):
            with pytest.raises(resilience.DeadlineExceeded):
                engine.embed_ids_batch([ids])
        # a live budget passes through untouched
        with resilience.deadline_scope(resilience.Deadline(60.0)):
            assert engine.embed_ids_batch([ids]).shape == (1, engine.embed_dim)

    def test_truncate_contract(self, engine):
        out = engine.embed_issues([{"title": "t", "body": "b"}], truncate=12)
        assert out.shape == (1, 12)
        assert EMBED_TRUNCATE_DIM == 1600

    def test_empty_text(self, engine):
        e = engine.embed_text("")
        assert np.all(np.isfinite(e))

    def test_chunk_len_honored(self):
        # Review regression: chunk_len was a dead parameter.
        cfg = AWDLSTMConfig(vocab_size=200, emb_sz=8, n_hid=12, n_layers=1)
        enc = AWDLSTMEncoder(cfg)
        params = enc.init(
            {"params": jax.random.PRNGKey(0)},
            np.zeros((1, 4), np.int32),
            init_lstm_states(cfg, 1),
        )["params"]
        vocab = Vocab(SPECIALS + [f"w{i}" for i in range(150)])
        eng = InferenceEngine(params, cfg, vocab, buckets=(8, 16), batch_size=2, chunk_len=8)
        ids = np.arange(30, 70, dtype=np.int32)  # longer than biggest bucket
        emb = eng.embed_ids_batch([ids])[0]
        # chunked at 8, not 16; the first chunk at the batch, the rest
        # at the one live row
        assert set(eng._fwd_cache) == {(2, 8), (1, 8)}
        # and numerically equal to the full forward
        states = init_lstm_states(cfg, 1)
        raw, _, _ = enc.apply({"params": params}, ids[None, :], states, deterministic=True)
        raw = np.asarray(raw, np.float32)[0]
        manual = np.concatenate([raw.mean(0), raw.max(0), raw[-1]])
        np.testing.assert_allclose(emb, manual, rtol=1e-4, atol=1e-5)


def worded_issues(lengths, adversarial=False):
    """``n`` body words of three characters each; with ``adversarial`` a
    one-character-repeated title (three tokens at any length) that grows
    as the body shrinks, so the raw size misorders the documents."""
    return [{"title": "x" * (600 - 8 * n) if adversarial else "w7",
             "body": " ".join(f"w{10 + (i + j) % 90}" for j in range(n))}
            for i, n in enumerate(lengths)]


class TestStreamedBulk:
    """``embed_issues`` on the groups path prepares a call's documents
    shortest first by their raw size and enqueues groups as they fill:
    the rows are ``embed_ids_batch``'s whatever that order was."""

    LENGTHS = [2, 30, 5, 9, 1, 14, 3, 40, 7, 4, 22, 11, 6, 17, 50, 8, 13]

    @pytest.mark.parametrize("order,adversarial", [
        ("shuffled", False), ("sorted", False), ("shuffled", True),
        ("sorted", True)],
        ids=["shuffled", "sorted", "adversarial", "adversarial-descending"])
    def test_rows_are_those_of_the_ids(self, engine, order, adversarial):
        from code_intelligence_tpu.text import build_issue_text

        lengths = sorted(self.LENGTHS) if order == "sorted" else self.LENGTHS
        docs = worded_issues(lengths, adversarial)
        ids = [engine.numericalize(build_issue_text(d["title"], d["body"]))
               for d in docs]
        assert len(docs) > 2 * engine.batch_size  # several groups
        if adversarial:  # the raw size really is the wrong way round
            raw = [len(d["title"]) + len(d["body"]) for d in docs]
            assert np.argsort(raw).tolist() == np.argsort(
                [-len(s) for s in ids]).tolist()
        want = engine.embed_ids_batch(ids, scheduler="groups")
        got = engine.embed_issues(docs, scheduler="groups")
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        for row, seq in zip(got, ids):  # and each row is its own document's
            np.testing.assert_allclose(
                row, engine.embed_ids_batch([seq])[0], rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("n", [0, 1], ids=["empty", "one"])
    def test_tiny_calls(self, engine, n):
        docs = worded_issues(self.LENGTHS[:n])
        rows = engine.embed_issues(docs, scheduler="groups")
        assert rows.shape == (n, engine.embed_dim)
        assert np.all(np.isfinite(rows))
        assert engine.embed_ids_batch([], scheduler="groups").shape \
            == (0, engine.embed_dim)

    def test_fields_that_are_not_strings_still_embed(self, engine):
        docs = [{"title": None, "body": "w11 w12"}, {"body": 7}, {}]
        rows = engine.embed_issues(docs + worded_issues(self.LENGTHS),
                                   scheduler="groups")
        np.testing.assert_allclose(
            rows[0], engine.embed_issue(None, "w11 w12"), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            rows[2], engine.embed_issue("", ""), rtol=1e-5, atol=1e-6)

    def test_spent_deadline_raises_before_anything_is_prepared(
            self, engine, monkeypatch):
        from code_intelligence_tpu.utils import resilience

        touched = []
        monkeypatch.setattr(engine, "numericalize",
                            lambda text: touched.append("tokenise"))
        monkeypatch.setattr(engine, "_embed_group_device",
                            lambda seqs: touched.append("enqueue"))
        with resilience.deadline_scope(resilience.Deadline(-1.0)):
            with pytest.raises(resilience.DeadlineExceeded):
                engine.embed_issues(worded_issues(self.LENGTHS),
                                    scheduler="groups")
        assert touched == []


class TestServer:
    @pytest.fixture(scope="class")
    def server(self, request):
        cfg = AWDLSTMConfig(vocab_size=200, emb_sz=8, n_hid=12, n_layers=2)
        enc = AWDLSTMEncoder(cfg)
        params = enc.init(
            {"params": jax.random.PRNGKey(0)},
            np.zeros((1, 4), np.int32),
            init_lstm_states(cfg, 1),
        )["params"]
        vocab = Vocab(SPECIALS + [f"w{i}" for i in range(100)])
        engine = InferenceEngine(params, cfg, vocab, buckets=(8, 16), batch_size=2)
        from code_intelligence_tpu.serving import make_server

        srv = make_server(engine, host="127.0.0.1", port=0)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        request.addfinalizer(srv.shutdown)
        return srv

    def _url(self, server, path):
        return f"http://127.0.0.1:{server.server_address[1]}{path}"

    def test_healthz(self, server):
        with urllib.request.urlopen(self._url(server, "/healthz")) as r:
            assert r.status == 200
            assert json.loads(r.read())["status"] == "ok"

    def test_post_text_raw_float32(self, server):
        req = urllib.request.Request(
            self._url(server, "/text"),
            data=json.dumps({"title": "Crash on start", "body": "It fails"}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as r:
            raw = r.read()
        emb = np.frombuffer(raw, dtype="<f4")  # the documented client decode
        assert emb.shape == (24,)  # 3 * emb_sz(8)
        assert np.all(np.isfinite(emb))

    def test_post_deterministic(self, server):
        def fetch():
            req = urllib.request.Request(
                self._url(server, "/text"),
                data=json.dumps({"title": "a", "body": "b"}).encode(),
            )
            with urllib.request.urlopen(req) as r:
                return r.read()

        assert fetch() == fetch()

    def test_bad_json_is_400(self, server):
        req = urllib.request.Request(self._url(server, "/text"), data=b"{not json")
        try:
            urllib.request.urlopen(req)
            assert False, "expected 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400

    def test_unknown_route_404(self, server):
        try:
            urllib.request.urlopen(self._url(server, "/nope"))
            assert False
        except urllib.error.HTTPError as e:
            assert e.code == 404

    def test_batched_server_matches_unbatched(self):
        import concurrent.futures

        cfg = AWDLSTMConfig(vocab_size=200, emb_sz=8, n_hid=12, n_layers=2)
        enc = AWDLSTMEncoder(cfg)
        params = enc.init(
            {"params": jax.random.PRNGKey(0)},
            np.zeros((1, 4), np.int32),
            init_lstm_states(cfg, 1),
        )["params"]
        vocab = Vocab(SPECIALS + [f"w{i}" for i in range(100)])
        engine = InferenceEngine(params, cfg, vocab, buckets=(8, 16), batch_size=8)
        from code_intelligence_tpu.serving import make_server

        srv = make_server(engine, host="127.0.0.1", port=0, batch_window_ms=10.0)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        url = f"http://127.0.0.1:{srv.server_address[1]}/text"

        def fetch(i):
            req = urllib.request.Request(
                url, data=json.dumps({"title": f"w{i} crash", "body": f"w{i+1}"}).encode()
            )
            with urllib.request.urlopen(req) as r:
                return np.frombuffer(r.read(), "<f4")

        with concurrent.futures.ThreadPoolExecutor(12) as ex:
            batched = list(ex.map(fetch, range(12)))
        # fan-out results must equal direct single-doc embeddings
        for i, emb in enumerate(batched):
            direct = engine.embed_issue(f"w{i} crash", f"w{i+1}")
            np.testing.assert_allclose(emb, direct, rtol=1e-5, atol=1e-6, err_msg=str(i))
        assert srv.batcher.requests_served == 12
        assert srv.batcher.batches_run < 12  # actually batched some requests
        # batch-size histogram observed every device program
        m = srv.metrics.render()
        assert f"embedding_batch_size_count {float(srv.batcher.batches_run)}" in m
        assert f"embedding_batch_size_sum {float(srv.batcher.requests_served)}" in m
        srv.shutdown()
        # review regression: post-close submits fail fast instead of hanging
        with pytest.raises(RuntimeError):
            srv.batcher.embed_issue("late", "request")

    def test_auth_token(self):
        cfg = AWDLSTMConfig(vocab_size=60, emb_sz=4, n_hid=6, n_layers=1)
        enc = AWDLSTMEncoder(cfg)
        params = enc.init(
            {"params": jax.random.PRNGKey(0)},
            np.zeros((1, 2), np.int32),
            init_lstm_states(cfg, 1),
        )["params"]
        vocab = Vocab(SPECIALS + ["a"])
        engine = InferenceEngine(params, cfg, vocab, buckets=(8,), batch_size=1)
        from code_intelligence_tpu.serving import make_server

        srv = make_server(engine, host="127.0.0.1", port=0, auth_token="sekrit")
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        url = f"http://127.0.0.1:{srv.server_address[1]}/text"
        body = json.dumps({"title": "a", "body": "a"}).encode()
        try:
            urllib.request.urlopen(urllib.request.Request(url, data=body))
            assert False
        except urllib.error.HTTPError as e:
            assert e.code == 403
        # non-ASCII token bytes (latin-1-decoded by http.server) must 403,
        # not crash the handler (compare_digest rejects non-ASCII str)
        bad = urllib.request.Request(url, data=body, headers={"X-Auth-Token": "caf\xe9"})
        try:
            urllib.request.urlopen(bad)
            assert False
        except urllib.error.HTTPError as e:
            assert e.code == 403
        req = urllib.request.Request(url, data=body, headers={"X-Auth-Token": "sekrit"})
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
        # /metrics exports the request counters + latency histogram
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.server_address[1]}/metrics"
        ) as r:
            metrics = r.read().decode()
            assert r.headers["Content-Type"].startswith("text/plain")
        assert 'embedding_requests_total{code="200",route="/text"} 1.0' in metrics
        assert 'embedding_requests_total{code="403",route="/text"} 2.0' in metrics
        assert "embedding_request_seconds_count 3.0" in metrics
        # unknown POST paths are bucketed, not recorded verbatim (label
        # cardinality must stay bounded against scanners)
        for i in range(3):
            try:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{srv.server_address[1]}/scan{i}", data=b"{}"))
            except urllib.error.HTTPError:
                pass
        m2 = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.server_address[1]}/metrics").read().decode()
        assert "/scan" not in m2
        assert 'embedding_requests_total{code="404",route="other"} 3.0' in m2
        srv.shutdown()

    def test_auth_token_non_ascii(self):
        # a client sending the UTF-8 bytes of a non-ASCII token must
        # authenticate: the stdlib parser hands us those bytes
        # latin-1-decoded, and the comparison must recover them (ADVICE r2:
        # utf-8 re-encode produced different bytes -> permanent 403)
        cfg = AWDLSTMConfig(vocab_size=60, emb_sz=4, n_hid=6, n_layers=1)
        enc = AWDLSTMEncoder(cfg)
        params = enc.init(
            {"params": jax.random.PRNGKey(0)},
            np.zeros((1, 2), np.int32),
            init_lstm_states(cfg, 1),
        )["params"]
        vocab = Vocab(SPECIALS + ["a"])
        engine = InferenceEngine(params, cfg, vocab, buckets=(8,), batch_size=1)
        from code_intelligence_tpu.serving import make_server

        srv = make_server(engine, host="127.0.0.1", port=0, auth_token="café-sekrit")
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        url = f"http://127.0.0.1:{srv.server_address[1]}/text"
        body = json.dumps({"title": "a", "body": "a"}).encode()
        # wire bytes = UTF-8 of the token; urllib latin-1-encodes header
        # strs, so present each byte as a latin-1 char
        wire = "café-sekrit".encode("utf-8").decode("latin-1")
        req = urllib.request.Request(url, data=body, headers={"X-Auth-Token": wire})
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
        # the latin-1-decoded *str* form is the wrong bytes: must 403
        try:
            urllib.request.urlopen(urllib.request.Request(
                url, data=body, headers={"X-Auth-Token": "caf\xe9-sekrit"}))
            raised = False
        except urllib.error.HTTPError as e:
            raised = e.code == 403
        assert raised
        srv.shutdown()
