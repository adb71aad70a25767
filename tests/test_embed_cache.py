"""Content-addressed embedding cache + single-flight coalescing
(serving/embed_cache.py) and its serve-path wiring.

Everything here but `TestRealEngine` is device-free: the cache is jax-free
by design, and the engines are deterministic stubs with call counters —
the two acceptance pins (cache stampede: N concurrent requests for a
never-seen document cost exactly ONE device pass; hot-swap staleness: zero
responses served from a retired version's entries) must be provable
without a chip.
"""

import threading
import time

import numpy as np
import pytest

from code_intelligence_tpu.registry.promotion import SmokeEngine
from code_intelligence_tpu.serving.embed_cache import (
    EmbedCache,
    cached_embed,
    content_hash,
    request_key,
    text_hash,
)
from code_intelligence_tpu.serving.rollout import RolloutManager
from code_intelligence_tpu.utils import resilience
from code_intelligence_tpu.utils.metrics import Registry
from code_intelligence_tpu.utils.storage import LocalStorage


class VersionedEngine(SmokeEngine):
    """SmokeEngine plus the identity the cache keys on. ``salt`` shifts
    every embedding so two versions provably produce different rows —
    the staleness pin reads WHICH engine's bytes a response carries."""

    def __init__(self, version="v1", salt=0.0, **kw):
        super().__init__(**kw)
        self.version = version
        self.vocab_hash = f"vh-{version}"
        self.salt = float(salt)

    def embed_issues(self, issues, **kw):
        return super().embed_issues(issues, **kw) + self.salt


def _direct(engine, title, body):
    return np.asarray(engine.embed_issue(title, body), np.float32)


def k(content="c", version="v1", vocab="vh"):
    return (content, version, vocab)


def row(fill=1.0, dim=16):
    return np.full(dim, fill, np.float32)


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------


class TestKeys:
    def test_content_hash_deterministic_and_distinct(self):
        a = content_hash([1, 2, 3])
        assert a == content_hash(np.array([1, 2, 3], np.int64))  # dtype-normalized
        assert a != content_hash([1, 2, 4])
        assert a != content_hash([1, 2])

    def test_text_hash_separator_safe(self):
        # ("ab", "c") and ("a", "bc") must not collide
        assert text_hash("ab", "c") != text_hash("a", "bc")
        assert text_hash("t", "b") == text_hash("t", "b")

    def test_request_key_prefers_token_content(self):
        class Tok(VersionedEngine):
            def numericalize(self, text):
                return np.array([len(text)], np.int32)

        eng = Tok("v9")
        key = request_key(eng, "t", "b")
        assert key[1] == "v9" and key[2] == "vh-v9"
        # same tokenization => same key, even for different raw text of
        # equal length (token identity IS document identity to the device)
        assert key[0] == request_key(eng, "x", "y")[0]

    def test_request_key_text_fallback(self):
        eng = VersionedEngine("v1")  # no numericalize
        assert request_key(eng, "t", "b")[0] == text_hash("t", "b")

    def test_versions_and_vocabs_never_alias(self):
        class Tok(VersionedEngine):
            def numericalize(self, text):
                return np.array([1], np.int32)

        a, b = Tok("v1"), Tok("v2")
        assert request_key(a, "t", "b") != request_key(b, "t", "b")
        b.version, b.vocab_hash = "v1", "other-vocab"  # same version string
        assert request_key(a, "t", "b") != request_key(b, "t", "b")


class TestVocabHash:
    def test_vocab_content_hash_order_sensitive(self):
        from code_intelligence_tpu.text import SPECIALS, Vocab

        v1 = Vocab(SPECIALS + ["a", "b"])
        v2 = Vocab(SPECIALS + ["b", "a"])
        assert v1.content_hash() == Vocab(SPECIALS + ["a", "b"]).content_hash()
        assert v1.content_hash() != v2.content_hash()

    def test_engine_exposes_vocab_hash(self):
        import jax

        from code_intelligence_tpu.inference import InferenceEngine
        from code_intelligence_tpu.models import (
            AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states)
        from code_intelligence_tpu.text import SPECIALS, Vocab

        cfg = AWDLSTMConfig(vocab_size=16, emb_sz=4, n_hid=6, n_layers=1)
        enc = AWDLSTMEncoder(cfg)
        params = enc.init(
            {"params": jax.random.PRNGKey(0)},
            np.zeros((1, 2), np.int32), init_lstm_states(cfg, 1))["params"]
        vocab = Vocab(SPECIALS + [f"w{i}" for i in range(16 - len(SPECIALS))])
        eng = InferenceEngine(params, cfg, vocab, batch_size=2)
        assert eng.vocab_hash == vocab.content_hash()
        assert len(eng.vocab_hash) == 16


class TestRealEngine:
    def test_duplicates_cost_no_device_pass_and_rows_are_bitwise_equal(self):
        """On a real engine and a seeded workload with duplicates (a few
        hot issues, a tail seen once): the cached side runs the device for
        EXACTLY the unique documents by token content, every cached row is
        byte-identical to the uncached one, and the cache adds no host
        sync and no recompile to the slot loop it wraps."""
        from smoke_engine import make_smoke_engine

        from code_intelligence_tpu.analysis import runtime as audit

        engine = make_smoke_engine(batch_size=4)
        rng = np.random.RandomState(0)
        pool = [{"title": f"w{i} in w{i + 1}",
                 "body": " ".join(f"w{j}" for j in rng.randint(5, 150, n))}
                for i, n in enumerate(rng.choice([5, 20, 60], size=12))]
        # two texts that tokenise alike are one document to the device
        pool.append({"title": pool[0]["title"].upper(),
                     "body": pool[0]["body"]})
        issues = [pool[int((r - 1) % len(pool))]
                  for r in np.random.RandomState(1).zipf(1.3, size=32)]
        issues += pool  # every document at least once
        passes = [0]

        def embed_fn(eng, title, body):
            passes[0] += 1
            return eng.embed_issues([{"title": title, "body": body}],
                                    scheduler="slots")[0]

        uncached = [embed_fn(engine, d["title"], d["body"]) for d in issues]
        n_unique = len({request_key(engine, d["title"], d["body"])
                        for d in issues})
        assert n_unique < len({(d["title"], d["body"]) for d in issues})
        cache = EmbedCache()
        passes[0] = 0
        with audit.recompile_guard(fn="slots.step", budget=0), \
                audit.no_implicit_transfers():
            cached = [cached_embed(cache, engine, d["title"], d["body"],
                                   embed_fn)[0] for d in issues]
        assert passes[0] == n_unique == cache.stats()["misses"]
        assert cache.stats()["hits"] == len(issues) - n_unique
        assert all(np.array_equal(a, b) for a, b in zip(uncached, cached))


# ---------------------------------------------------------------------------
# memory tier
# ---------------------------------------------------------------------------


class TestMemoryTier:
    def test_roundtrip_and_counts(self):
        c = EmbedCache(max_bytes=1 << 20)
        assert c.get(k()) is None
        assert c.put(k(), row(2.0))
        got = c.get(k())
        np.testing.assert_array_equal(got, row(2.0))
        s = c.stats()
        assert (s["hits"], s["misses"], s["entries"]) == (1, 1, 1)

    def test_returned_rows_are_private_copies(self):
        c = EmbedCache()
        c.put(k(), row(1.0))
        c.get(k())[:] = 99.0  # a caller scribbling on its response
        np.testing.assert_array_equal(c.get(k()), row(1.0))

    def test_byte_budget_evicts_lru_first(self):
        c = EmbedCache(max_bytes=3 * row().nbytes)
        for i in range(3):
            c.put(k(f"c{i}"), row(i))
        c.get(k("c0"))  # refresh c0: c1 becomes the eviction victim
        c.put(k("c3"), row(3))
        assert c.get(k("c1"), count=False) is None
        assert c.get(k("c0"), count=False) is not None
        assert c.evictions == 1
        assert c.stats()["bytes"] <= c.max_bytes

    def test_overwrite_same_key_does_not_leak_bytes(self):
        c = EmbedCache()
        c.put(k(), row(1.0))
        c.put(k(), row(2.0))
        assert c.stats()["bytes"] == row().nbytes
        np.testing.assert_array_equal(c.get(k()), row(2.0))

    def test_non_finite_rows_refused(self):
        c = EmbedCache()
        bad = row()
        bad[3] = np.nan
        assert not c.put(k(), bad)
        assert c.get(k(), count=False) is None

    def test_invalidate_version_drops_only_that_version(self):
        c = EmbedCache()
        c.put(k("c1", "v1"), row(1))
        c.put(k("c2", "v1"), row(2))
        c.put(k("c1", "v2"), row(3))
        assert c.invalidate_version("v1") == 2
        assert c.resident_versions() == ["v2"]
        assert c.get(k("c1", "v2"), count=False) is not None

    def test_metrics_land_on_registry(self):
        reg = Registry()
        c = EmbedCache(max_bytes=row().nbytes, registry=reg)
        c.put(k("a"), row())
        c.put(k("b"), row())  # evicts a
        c.get(k("b"))
        c.get(k("a"))
        text = reg.render()
        for name in ("cache_hits_total", "cache_misses_total",
                     "cache_evictions_total", "cache_bytes",
                     "cache_hit_ratio"):
            assert name in text, name


# ---------------------------------------------------------------------------
# persistent tier
# ---------------------------------------------------------------------------


class TestPersistentTier:
    def test_survives_process_restart(self, tmp_path):
        store = LocalStorage(tmp_path)
        EmbedCache(storage=store).put(k(), row(5.0))
        fresh = EmbedCache(storage=LocalStorage(tmp_path))  # "new process"
        got = fresh.get(k())
        np.testing.assert_array_equal(got, row(5.0))
        assert fresh.stats()["hits"] == 1  # a persistent hit, not a miss

    def test_corrupt_entry_is_a_miss_never_a_wrong_answer(self, tmp_path):
        store = LocalStorage(tmp_path)
        c = EmbedCache(storage=store)
        c.put(k(), row(5.0))
        path = EmbedCache._persist_path(k())
        blob = bytearray(store.read_bytes(path))
        blob[-1] ^= 0xFF  # bit-rot in the payload
        store.write_bytes_atomic(path, bytes(blob))
        fresh = EmbedCache(storage=store)
        assert fresh.get(k()) is None
        assert fresh.persist_errors == 1
        # truncation (a torn write) is equally tolerated
        store.write_bytes_atomic(path, bytes(blob[:7]))
        assert EmbedCache(storage=store).get(k()) is None

    def test_path_accepts_hostile_version_strings(self, tmp_path):
        c = EmbedCache(storage=LocalStorage(tmp_path))
        key = ("abc", "../..//etc: passwd", "vh")
        c.put(key, row(1.0))
        got = EmbedCache(storage=LocalStorage(tmp_path)).get(key)
        np.testing.assert_array_equal(got, row(1.0))
        assert not (tmp_path.parent / "etc").exists()


# ---------------------------------------------------------------------------
# single flight
# ---------------------------------------------------------------------------


class CountingEngine(VersionedEngine):
    """Device-pass accounting: ``docs`` counts documents embedded (the
    thing the cache must minimize), ``gate`` optionally blocks the pass
    so a test can hold a flight open deterministically."""

    def __init__(self, gate=None, delay_s=0.0, **kw):
        super().__init__(**kw)
        self.docs = 0
        self.gate = gate
        self._count_lock = threading.Lock()
        self.delay_s2 = delay_s

    def embed_issues(self, issues, **kw):
        if self.gate is not None:
            assert self.gate.wait(timeout=10.0)
        if self.delay_s2:
            time.sleep(self.delay_s2)
        with self._count_lock:
            self.docs += len(issues)
        return super().embed_issues(issues, **kw)


class TestSingleFlight:
    def test_stampede_one_device_pass(self):
        """THE stampede pin: N threads request the same never-seen doc
        concurrently — exactly one device pass, N identical responses,
        zero deadline violations (each caller has a generous budget)."""
        n = 8
        eng = CountingEngine(delay_s=0.15)
        cache = EmbedCache()
        barrier = threading.Barrier(n)
        rows, outcomes, errors = [], [], []
        lock = threading.Lock()

        def worker():
            try:
                barrier.wait(timeout=10)
                with resilience.deadline_scope(resilience.Deadline(30.0)):
                    r, outcome = cached_embed(cache, eng, "hot", "doc",
                                              _direct)
                with lock:
                    rows.append(r)
                    outcomes.append(outcome)
            except BaseException as e:  # pragma: no cover - the failure arm
                with lock:
                    errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20)
        assert not errors
        assert eng.docs == 1  # exactly ONE device pass
        assert len(rows) == n
        for r in rows[1:]:
            np.testing.assert_array_equal(r, rows[0])
        assert outcomes.count("miss") == 1
        assert set(outcomes) <= {"miss", "coalesced", "hit"}
        assert cache.stats()["in_flight"] == 0

    def test_follower_deadline_expires_without_touching_device(self):
        gate = threading.Event()
        eng = CountingEngine(gate=gate)
        cache = EmbedCache()
        leader_done = []

        def leader():
            leader_done.append(cached_embed(cache, eng, "t", "b", _direct))

        t = threading.Thread(target=leader)
        t.start()
        deadline = time.time() + 5.0
        while cache.stats()["in_flight"] == 0 and time.time() < deadline:
            time.sleep(0.005)
        # follower with an almost-spent budget: must give up fast, and
        # must NOT run the engine itself
        t0 = time.perf_counter()
        with resilience.deadline_scope(resilience.Deadline(0.05)):
            with pytest.raises(resilience.DeadlineExceeded):
                cached_embed(cache, eng, "t", "b", _direct)
        assert time.perf_counter() - t0 < 2.0
        gate.set()  # the leader's pass continues unharmed...
        t.join(timeout=10)
        assert eng.docs == 1
        # ...and fills the cache for everyone after
        assert leader_done[0][1] == "miss"
        assert cached_embed(cache, eng, "t", "b", _direct)[1] == "hit"

    def test_leader_failure_propagates_then_next_retry_is_fresh(self):
        cache = EmbedCache()
        eng = CountingEngine()
        boom = RuntimeError("device fell over")

        def failing(engine, title, body):
            raise boom

        with pytest.raises(RuntimeError):
            cached_embed(cache, eng, "t", "b", failing)
        # the flight was retired with the failure: a later request leads
        # a NEW flight instead of inheriting the corpse
        r, outcome = cached_embed(cache, eng, "t", "b", _direct)
        assert outcome == "miss" and eng.docs == 1
        np.testing.assert_array_equal(r, _direct(eng, "t", "b"))

    def test_no_cache_is_passthrough(self):
        eng = CountingEngine()
        r, outcome = cached_embed(None, eng, "t", "b", _direct)
        assert outcome is None and eng.docs == 1


# ---------------------------------------------------------------------------
# micro-batcher wiring
# ---------------------------------------------------------------------------


class WindowEngine(VersionedEngine):
    """Records the document list of every device window."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.windows = []

    def embed_issues(self, issues, **kw):
        self.windows.append([d["title"] for d in issues])
        return super().embed_issues(issues)


class TestBatcherWiring:
    def _batcher(self, eng, cache=None, window_ms=30.0):
        from code_intelligence_tpu.serving.batcher import MicroBatcher

        return MicroBatcher(eng, max_batch=8, window_ms=window_ms,
                            scheduler="groups", cache=cache)

    def test_in_window_duplicates_share_one_slot(self):
        eng = WindowEngine()
        cache = EmbedCache()
        b = self._batcher(eng, cache)
        try:
            results = [None] * 6
            titles = ["a", "a", "a", "b", "a", "b"]

            def submit(i):
                results[i] = b.embed_issue(titles[i], "body")

            threads = [threading.Thread(target=submit, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            # every window that ran saw each document at most once
            for w in eng.windows:
                assert len(w) == len(set(w))
            # 2 unique documents => at most 2 device docs, however the
            # submissions landed across windows
            assert sum(len(w) for w in eng.windows) == 2
            for i, title in enumerate(titles):
                np.testing.assert_array_equal(
                    results[i], eng.embed_issue(title, "body"))
        finally:
            b.close()

    def test_cross_window_hits_skip_device(self):
        eng = WindowEngine()
        cache = EmbedCache()
        b = self._batcher(eng, cache, window_ms=1.0)
        try:
            r1, o1 = b.embed_issue_cached("t", "b")
            r2, o2 = b.embed_issue_cached("t", "b")
            assert (o1, o2) == ("miss", "hit")
            np.testing.assert_array_equal(r1, r2)
            assert sum(len(w) for w in eng.windows) == 1
        finally:
            b.close()

    def test_cacheless_batcher_unchanged(self):
        eng = WindowEngine()
        b = self._batcher(eng, cache=None, window_ms=1.0)
        try:
            r, outcome = b.embed_issue_cached("t", "b")
            assert outcome is None
            b.embed_issue("t", "b")
            assert sum(len(w) for w in eng.windows) == 2
        finally:
            b.close()

    def test_device_failure_fails_only_unserved_waiters(self):
        eng = WindowEngine()
        cache = EmbedCache()
        b = self._batcher(eng, cache, window_ms=1.0)
        try:
            b.embed_issue("cached", "doc")  # resident

            def boom(issues, **kw):
                raise RuntimeError("window died")

            eng.embed_issues = boom
            # the hit is served even though the same window's miss fails
            assert b.embed_issue_cached("cached", "doc")[1] == "hit"
            with pytest.raises(RuntimeError):
                b.embed_issue("fresh", "doc")
        finally:
            b.close()


# ---------------------------------------------------------------------------
# hot-swap staleness
# ---------------------------------------------------------------------------


class TestHotSwapStaleness:
    def _serve(self, mgr, cache, title, body):
        def fn(eng, t, bd):
            return cached_embed(cache, eng, t, bd, _direct)[0]

        return mgr.serve(title, body, fn)

    def test_promote_invalidates_incumbent_entries(self):
        cache = EmbedCache()
        a, b = VersionedEngine("v1"), VersionedEngine("v2", salt=1.0)
        mgr = RolloutManager(a, version="v1")
        mgr.bind_cache(cache)
        for i in range(4):
            self._serve(mgr, cache, f"t{i}", "b")
        assert "v1" in cache.resident_versions()
        mgr.start_canary("v2", b, pct=1.0)
        mgr.promote()
        # atomically: zero v1 entries remain servable (or even resident)
        assert "v1" not in cache.resident_versions()
        emb, version = self._serve(mgr, cache, "t0", "b")
        assert version == "v2"
        np.testing.assert_array_equal(emb, _direct(b, "t0", "b"))

    def test_abort_canary_invalidates_candidate_entries(self):
        cache = EmbedCache()
        a, b = VersionedEngine("v1"), VersionedEngine("v2", salt=1.0)
        mgr = RolloutManager(a, version="v1")
        mgr.bind_cache(cache)
        cache.put(k("c", "v2", "vh-v2"), row())  # a canary-era entry
        mgr.start_canary("v2", b, pct=1.0)
        mgr.abort_canary(reason="test")
        assert "v2" not in cache.resident_versions()

    def test_promote_mid_load_zero_stale_responses(self):
        """THE staleness pin: sustained concurrent load across a
        promote — every response whose request STARTED after promote()
        returned must carry the new version's bytes, never a pre-swap
        entry."""
        cache = EmbedCache()
        a = VersionedEngine("v1", salt=0.0)
        b = VersionedEngine("v2", salt=1.0)
        mgr = RolloutManager(a, version="v1")
        mgr.bind_cache(cache)
        docs = [(f"doc{i}", "body") for i in range(6)]
        records, errors = [], []
        lock = threading.Lock()
        stop = threading.Event()

        def client(cid):
            i = cid
            while not stop.is_set():
                t0 = time.monotonic()
                try:
                    emb, version = self._serve(mgr, cache, *docs[i % len(docs)])
                except BaseException as e:  # pragma: no cover
                    with lock:
                        errors.append(e)
                    return
                with lock:
                    records.append((t0, docs[i % len(docs)], emb, version))
                i += 1

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(3)]
        for t in threads:
            t.start()
        try:
            time.sleep(0.15)
            mgr.start_canary("v2", b, pct=1.0)
            mgr.promote()
            t_promoted = time.monotonic()
            time.sleep(0.15)
        finally:
            # set unconditionally: a raise above must not leave the
            # clients spinning forever (they'd hang the whole session)
            stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors
        post = [r for r in records if r[0] > t_promoted]
        assert post, "no post-promote traffic recorded"
        for _, (title, body), emb, version in post:
            assert version == "v2"
            # the salt proves WHOSE entry produced the bytes: a stale
            # pre-swap (v1) row would be off by exactly 1.0
            np.testing.assert_array_equal(emb, _direct(b, title, body))


# ---------------------------------------------------------------------------
# client-side tiers
# ---------------------------------------------------------------------------


class TestClientTiers:
    def test_local_embedder_caches(self):
        from code_intelligence_tpu.labels.embed_client import LocalEmbedder

        eng = CountingEngine()
        emb = LocalEmbedder(eng, cache=EmbedCache())
        r1 = emb.embed_issue("t", "b")
        r2 = emb.embed_issue("t", "b")
        assert eng.docs == 1
        np.testing.assert_array_equal(r1, r2)

    def _client(self, versions):
        """EmbeddingClient whose wire is a stub: pops (row, version)
        responses and counts fetches."""
        from code_intelligence_tpu.labels.embed_client import EmbeddingClient

        client = EmbeddingClient("http://test", cache_entries=64)
        fetches = []

        def fake_fetch_once(payload, headers):
            i = min(len(fetches), len(versions) - 1)
            fetches.append(payload)
            # (raw, version, fleet_versions): no X-Fleet-Versions header
            # on a single-server wire -> None (the original flush rule)
            return row(float(i), dim=2400).tobytes(), versions[i], None

        client._fetch_once = fake_fetch_once
        return client, fetches

    def test_wire_cache_dedupes_fetches(self):
        client, fetches = self._client(["v1", "v1", "v1"])
        client.embed_issue("t", "b")  # learns the server version
        client.embed_issue("t2", "b")
        n = len(fetches)
        client.embed_issue("t2", "b")  # now a version-scoped hit
        assert len(fetches) == n

    def test_wire_cache_flushes_on_version_change(self):
        client, fetches = self._client(["v1", "v2", "v2"])
        client.embed_issue("t", "b")
        client.embed_issue("t", "b")   # cached under v1
        client.embed_issue("t2", "b")  # server hot-swapped to v2 -> flush
        client.embed_issue("t", "b")   # must refetch: v1 entry retired
        assert len(fetches) == 3
        assert client._cache.resident_versions() == ["v2"]
