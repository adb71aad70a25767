"""Request tracing (utils/tracing.py): span trees, thread handoff through
the batcher/slot scheduler, W3C traceparent propagation, slow-request
capture, Chrome export, metrics roll-up, and the never-raise guarantee."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from code_intelligence_tpu.utils import tracing
from code_intelligence_tpu.utils.metrics import Registry
from code_intelligence_tpu.utils.tracing import Tracer


class TestSpanTree:
    def test_nesting_forms_tree_in_ring(self):
        t = Tracer()
        with t.span("root", route="/text") as root:
            with t.span("child"):
                with t.span("grandchild"):
                    pass
            with t.span("sibling"):
                pass
        traces = t.traces()
        assert len(traces) == 1
        tr = traces[0]
        assert tr["root"] == "root"
        by = {s["name"]: s for s in tr["spans"]}
        assert by["child"]["parent_id"] == by["root"]["span_id"]
        assert by["grandchild"]["parent_id"] == by["child"]["span_id"]
        assert by["sibling"]["parent_id"] == by["root"]["span_id"]
        assert by["root"]["parent_id"] is None
        assert by["root"]["attrs"]["route"] == "/text"
        assert tr["duration_s"] >= by["child"]["duration_s"] >= 0

    def test_exception_annotated_not_swallowed(self):
        t = Tracer()
        with pytest.raises(ValueError):
            with t.span("root"):
                with t.span("inner"):
                    raise ValueError("boom")
        by = {s["name"]: s for s in t.traces()[0]["spans"]}
        assert by["inner"]["attrs"]["error"] == "ValueError"

    def test_ring_bounded(self):
        t = Tracer(max_traces=4)
        for i in range(10):
            with t.span(f"r{i}"):
                pass
        got = [tr["root"] for tr in t.traces()]
        assert got == ["r9", "r8", "r7", "r6"]  # most recent first

    def test_span_cap_keeps_root(self):
        t = Tracer()
        with t.span("root"):
            for _ in range(tracing.MAX_SPANS_PER_TRACE + 10):
                with t.span("c"):
                    pass
        tr = t.traces()[0]
        assert tr["dropped_spans"] > 0
        assert any(s["name"] == "root" for s in tr["spans"])
        assert tr["duration_s"] > 0

    def test_straggler_span_amends_finished_trace(self):
        # a span that STARTED before the root ended but finishes after
        # (the fleet router's hedge loser) lands in the already-rendered
        # tree — the ring holds the same dict, so the amendment shows
        # everywhere the trace was already visible
        t = Tracer()
        with t.span("root") as root:
            straggler = t.start_span("late.attempt", parent=root.context,
                                     member="m1:80")
        assert "late.attempt" not in [
            s["name"] for s in t.traces()[0]["spans"]]
        straggler.end()
        spans = {s["name"]: s for s in t.traces()[0]["spans"]}
        assert spans["late.attempt"]["attrs"]["member"] == "m1:80"
        assert spans["late.attempt"]["parent_id"] == \
            spans["root"]["span_id"]

    def test_ancient_handoff_still_dropped(self):
        # the closing window is bounded: a span from a trace evicted out
        # of it is dropped, never resurrected into unbounded memory
        t = Tracer()
        with t.span("root") as root:
            straggler = t.start_span("too.late", parent=root.context)
        for _ in range(tracing.MAX_CLOSING_TRACES + 2):
            with t.span("other"):
                pass
        straggler.end()
        old = [tr for tr in t.traces() if tr["trace_id"] == root.trace_id]
        assert old and "too.late" not in [
            s["name"] for s in old[0]["spans"]]


class TestThreadHandoff:
    def test_explicit_parent_and_record_span(self):
        t = Tracer()
        with t.span("root") as root:
            ctx = root.context

            def work():
                with t.span("offthread", parent=ctx):
                    time.sleep(0.002)
                tracing.record_span("timed", 1.0, 1.25, ctx, steps=3)

            th = threading.Thread(target=work)
            th.start()
            th.join()
        tr = t.traces()[0]
        by = {s["name"]: s for s in tr["spans"]}
        assert by["offthread"]["parent_id"] == by["root"]["span_id"]
        assert by["offthread"]["thread"] != by["root"]["thread"]
        assert by["timed"]["attrs"]["steps"] == 3
        assert by["timed"]["duration_s"] == pytest.approx(0.25)

    def test_survives_microbatcher_handoff(self):
        # the satellite contract: a span tree crosses the handler-thread ->
        # batcher-thread -> slot-scheduler handoff intact
        from test_slot_scheduler import make_engine

        from code_intelligence_tpu.serving.batcher import MicroBatcher

        engine = make_engine(batch_size=2, buckets=(8,))
        batcher = MicroBatcher(engine, max_batch=2, window_ms=1.0)
        t = Tracer()
        try:
            with t.span("request") as root:
                emb = batcher.embed_issue("crash in w3", "w4 w5 " * 30)
            assert emb.shape == (24,)
        finally:
            batcher.close()
        tr = t.traces()[0]
        names = {s["name"] for s in tr["spans"]}
        assert {"request", "batcher.queue_wait", "engine.tokenize",
                "slots.queue_wait", "slots.device_steps",
                "slots.pool_emit"} <= names
        by = {s["name"]: s for s in tr["spans"]}
        root_id = by["request"]["span_id"]
        # every handed-off span parents back to the request's root
        for name in ("batcher.queue_wait", "slots.device_steps"):
            assert by[name]["parent_id"] == root_id
        # and genuinely ran on another thread
        assert by["batcher.queue_wait"]["thread"] != by["request"]["thread"]
        assert by["slots.device_steps"]["attrs"]["steps"] >= 1

    def test_stage_durations_sum_consistently(self):
        # acceptance: queue-wait + device-steps + emit + tokenize stay
        # within the measured request latency (children can overlap the
        # root but not exceed it wildly)
        from test_slot_scheduler import make_engine

        engine = make_engine(batch_size=2, buckets=(8,))
        t = Tracer()
        with t.span("request") as root:
            engine.embed_issues(
                [{"title": "w3", "body": "w4 w5 " * 20}], scheduler="slots")
        tr = t.traces()[0]
        by = {s["name"]: s for s in tr["spans"]}
        root_dur = by["request"]["duration_s"]
        staged = sum(by[n]["duration_s"] for n in
                     ("engine.tokenize", "slots.queue_wait",
                      "slots.device_steps", "slots.pool_emit"))
        assert 0 < staged <= root_dur * 1.05 + 1e-3


class TestTraceparent:
    def test_round_trip(self):
        t = Tracer()
        with t.span("root") as root:
            tp = root.context.traceparent()
        t2 = Tracer()
        ctx = t2.extract({"traceparent": tp})
        assert ctx is not None
        assert ctx.trace_id == root.trace_id
        assert ctx.sampled

    def test_continue_trace_preserves_trace_id(self):
        t = Tracer()
        tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        with t.continue_trace("server.root", {"traceparent": tp}) as sp:
            with t.span("inner"):
                pass
        tr = t.traces()[0]
        assert tr["trace_id"] == "ab" * 16
        by = {s["name"]: s for s in tr["spans"]}
        # the local root parents to the REMOTE span id
        assert by["server.root"]["parent_id"] == "cd" * 8
        assert by["inner"]["parent_id"] == by["server.root"]["span_id"]

    @pytest.mark.parametrize("bad", [
        "garbage", "00-short-deadbeefdeadbeef-01", "", None,
        "zz-" + "ab" * 16 + "-" + "cd" * 8 + "-01",
        "00-" + "00" * 16 + "-" + "cd" * 8 + "-01",  # all-zero trace id
    ])
    def test_malformed_ignored(self, bad):
        t = Tracer()
        assert t.extract({"traceparent": bad} if bad is not None else {}) is None

    def test_unsampled_flag_suppresses_recording(self):
        t = Tracer()
        tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-00"  # flags: not sampled
        with t.continue_trace("root", {"traceparent": tp}):
            pass
        assert t.traces() == []

    def test_inject_stamps_current_context(self):
        t = Tracer()
        with t.span("outbound") as sp:
            headers = tracing.inject({"Authorization": "x"})
            assert headers["Authorization"] == "x"
            assert headers["traceparent"] == sp.context.traceparent()
        assert "traceparent" not in tracing.inject({})

    def test_transport_injects(self):
        # github/transport.py stamps the header on real outbound requests;
        # the injection helper path is what it calls
        t = Tracer()
        seen = {}

        def fake_urlopen(req, timeout=None):
            seen.update(dict(req.header_items()))
            raise RuntimeError("stop here")

        from code_intelligence_tpu.github import transport as tp_mod
        import urllib.request as ur

        orig = ur.urlopen
        ur.urlopen = fake_urlopen
        try:
            with t.span("worker.write_back"):
                with pytest.raises(RuntimeError):
                    tp_mod.urllib_transport("http://example.invalid/x")
        finally:
            ur.urlopen = orig
        assert any(k.lower() == "traceparent" for k in seen)


class TestSamplingAndSafety:
    def test_sample_rate_zero_records_nothing(self):
        t = Tracer(sample_rate=0.0)
        with t.span("root") as sp:
            assert not sp.sampled
            with t.span("child"):
                pass
        assert t.traces() == []

    def test_unsampled_children_inherit(self):
        t = Tracer(sample_rate=0.0)
        with t.span("root") as root:
            ctx = root.context
        t.record_span("late", 0.0, 1.0, ctx)
        assert t.traces() == []

    def test_broken_registry_never_raises(self):
        class BadRegistry:
            def histogram(self, *a, **kw):
                pass

            def observe(self, *a, **kw):
                raise RuntimeError("registry down")

        t = Tracer(registry=BadRegistry())
        with t.span("root"):
            with t.span("child"):
                pass
        assert t.traces()[0]["root"] == "root"

    def test_max_live_raisable_for_wide_fanout(self):
        # the bench holds one root per in-flight document; a fan-out wider
        # than the default live cap must not silently truncate
        n = tracing.MAX_LIVE_TRACES + 40
        t = Tracer(max_traces=n + 8, max_live=n + 8)
        roots = [t.start_span("request") for _ in range(n)]
        for r in roots:
            r.end()
        assert len(t.traces()) == n
        assert t.traces_dropped == 0

    def test_ctxs_length_mismatch_raises(self):
        # a short ctxs list must fail loudly, not silently drop documents
        from test_slot_scheduler import make_engine

        engine = make_engine(batch_size=2, buckets=(8,))
        t = Tracer()
        with t.span("root") as root:
            ctx = root.context
        seqs = [np.arange(3, dtype=np.int32)] * 3
        with pytest.raises(ValueError, match="ctxs"):
            engine.embed_ids_batch(seqs, scheduler="slots", ctxs=[ctx])
        with pytest.raises(ValueError, match="ctxs"):
            engine.embed_issues([{"title": "a", "body": "b"}] * 2,
                                ctxs=[ctx])

    def test_ambient_span_no_trace_is_free_noop(self):
        with tracing.span("orphan") as sp:
            assert sp.context is None
        # and record_span with no parent is a no-op
        tracing.record_span("x", 0.0, 1.0, None)


class TestSlowCapture:
    def test_slow_ring_pins_over_threshold(self):
        t = Tracer(max_traces=2, slow_threshold_s=0.0)
        for i in range(5):
            with t.span(f"r{i}"):
                pass
        # ring churned to the last 2; slow ring pinned (maxlen 32) keeps more
        assert len(t.traces()) == 2
        assert len(t.slow_traces()) == 5

    def test_fast_requests_not_pinned(self):
        t = Tracer(slow_threshold_s=60.0)
        with t.span("fast"):
            pass
        assert len(t.traces()) == 1
        assert t.slow_traces() == []


class TestExports:
    def test_chrome_trace_events(self):
        t = Tracer()
        with t.span("root"):
            with t.span("child"):
                pass
        ch = tracing.to_chrome(t.traces())
        assert "traceEvents" in ch
        xs = [e for e in ch["traceEvents"] if e.get("ph") == "X"]
        assert {e["name"] for e in xs} == {"root", "child"}
        assert all(e["dur"] > 0 for e in xs)
        json.dumps(ch)  # serializable

    def test_registry_rollup_histogram(self):
        r = Registry()
        t = Tracer(registry=r)
        with t.span("http.request"):
            with t.span("slots.device_steps"):
                pass
        out = r.render()
        assert 'trace_span_seconds_bucket{span="http.request"' in out
        assert 'trace_span_seconds_bucket{span="slots.device_steps"' in out
        assert "# TYPE trace_span_seconds histogram" in out

    def test_every_document_of_a_slots_call_gets_each_stage(self):
        # one trace a document, every root open at once while the slot
        # scheduler has them all in flight (`ctxs=`): each document's
        # trace carries each stage of the slot pipeline exactly once
        from collections import Counter

        from test_slot_scheduler import make_engine

        engine = make_engine(batch_size=4, buckets=(8, 16))
        issues = [{"title": f"w{i}", "body": "w4 w5 " * (1 + 3 * i)}
                  for i in range(8)]
        t = Tracer(max_traces=16, max_live=16)
        roots = [t.start_span("request", doc=i) for i in range(len(issues))]
        engine.embed_issues(issues, scheduler="slots",
                            ctxs=[r.context for r in roots])
        for r in roots:
            r.end()
        traces = t.traces()
        assert len(traces) == len(issues)
        for trace in traces:
            names = Counter(s["name"] for s in trace["spans"])
            for stage in ("engine.tokenize", "slots.queue_wait",
                          "slots.device_steps", "slots.pool_emit"):
                assert names[stage] == 1, (stage, names)


class TestDebugEndpoints:
    def test_metrics_server_serves_debug_traces(self):
        from code_intelligence_tpu.utils.metrics import start_metrics_server

        r = Registry()
        t = Tracer(registry=r, slow_threshold_s=0.0)
        with t.span("worker.handle_event"):
            pass
        srv = start_metrics_server(r, port=0, host="127.0.0.1", tracer=t)
        base = f"http://127.0.0.1:{srv.port}"
        try:
            with urllib.request.urlopen(base + "/debug/traces") as resp:
                dbg = json.loads(resp.read())
            assert dbg["traces"][0]["root"] == "worker.handle_event"
            assert dbg["slow"], "threshold 0 pins everything"
            with urllib.request.urlopen(
                    base + "/debug/traces?format=chrome") as resp:
                ch = json.loads(resp.read())
            assert any(e.get("ph") == "X" for e in ch["traceEvents"])
        finally:
            srv.shutdown()

    def test_metrics_server_404_without_tracer(self):
        from code_intelligence_tpu.utils.metrics import start_metrics_server

        srv = start_metrics_server(Registry(), port=0, host="127.0.0.1")
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/debug/traces")
            assert exc.value.code == 404
        finally:
            srv.shutdown()

    def test_embedding_server_end_to_end(self):
        from test_slot_scheduler import make_engine

        from code_intelligence_tpu.serving import make_server

        engine = make_engine(batch_size=2, buckets=(8, 16))
        srv = make_server(engine, host="127.0.0.1", port=0,
                          slow_trace_ms=0.0)
        port = srv.server_address[1]
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            body = json.dumps({"title": "crash in w3",
                               "body": "w4 w5 " * 30}).encode()
            tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/text", data=body,
                headers={"Content-Type": "application/json",
                         "traceparent": tp})
            with urllib.request.urlopen(req, timeout=60) as resp:
                raw = resp.read()
            assert np.frombuffer(raw, dtype="<f4").shape[0] == 24
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/traces",
                    timeout=10) as resp:
                dbg = json.loads(resp.read())
            tr = dbg["traces"][0]
            # joins the client's W3C trace
            assert tr["trace_id"] == "ab" * 16
            names = {s["name"] for s in tr["spans"]}
            assert {"http.request", "engine.tokenize", "slots.queue_wait",
                    "slots.device_steps", "slots.pool_emit"} <= names
            root = next(s for s in tr["spans"] if s["name"] == "http.request")
            assert root["attrs"]["code"] == 200
            # roll-up rides the same /metrics the gauges use
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
                m = resp.read().decode()
            assert 'trace_span_seconds_bucket{span="http.request"' in m
        finally:
            srv.shutdown()
            srv.server_close()


class TestWorkerTracing:
    def make_worker(self):
        from code_intelligence_tpu.worker.worker import LabelWorker

        class Pred:
            def predict(self, spec):
                return {"kind/bug": 0.9}

        class Client:
            def add_labels(self, *a):
                pass

            def create_comment(self, *a):
                pass

        return LabelWorker(
            predictor_factory=lambda: Pred(),
            issue_client_factory=lambda o, r: Client(),
            config_fetcher=lambda o, r: None,
            issue_fetcher=lambda o, r, n: {
                "labels": [], "removed_labels": [], "comment_authors": []},
        )

    class Msg:
        def __init__(self, attrs):
            self.attributes = attrs
            self.acked = False

        def ack(self):
            self.acked = True

    def test_event_trace_spans_and_outcome(self):
        w = self.make_worker()
        tp = "00-" + "12" * 16 + "-" + "34" * 8 + "-01"
        w.handle_message(self.Msg({"repo_owner": "o", "repo_name": "r",
                                   "issue_num": "1", "traceparent": tp}))
        tr = w.tracer.traces()[0]
        assert tr["trace_id"] == "12" * 16  # joined the publisher's trace
        names = {s["name"] for s in tr["spans"]}
        assert {"worker.handle_event", "worker.predict",
                "worker.config_fetch", "worker.issue_fetch",
                "worker.write_back"} <= names
        root = next(s for s in tr["spans"]
                    if s["name"] == "worker.handle_event")
        assert root["attrs"]["outcome"] == "ok"
        assert root["attrs"]["repo"] == "o/r"

    def test_error_event_traced_with_outcome(self):
        from code_intelligence_tpu.worker.worker import LabelWorker

        def boom(o, r, n):
            raise RuntimeError("fetch down")

        w = LabelWorker(
            predictor_factory=lambda: type(
                "P", (), {"predict": lambda self, s: {"kind/bug": 0.9}})(),
            issue_client_factory=lambda o, r: None,
            config_fetcher=lambda o, r: None,
            issue_fetcher=boom,
        )
        m = self.Msg({"repo_owner": "o", "repo_name": "r", "issue_num": "2"})
        w.handle_message(m)
        assert m.acked  # always-ack policy unchanged by tracing
        root = next(s for s in w.tracer.traces()[0]["spans"]
                    if s["name"] == "worker.handle_event")
        assert root["attrs"]["outcome"] == "error"
