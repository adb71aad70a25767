"""Embedding REST server.

Rebuild of the reference's Flask app (`Issue_Embeddings/flask_app/
app.py:20-128`) with the same wire contract, on the stdlib HTTP server
(no Flask in the image, and the serving surface is tiny):

* ``POST /text`` with JSON ``{"title": ..., "body": ...}`` returns the
  pooled embedding as **raw little-endian float32 bytes** — clients decode
  with ``np.frombuffer(resp.content, dtype='<f4')``
  (`app.py:69`; client contract `Issue_Embeddings/README.md:36`).
* ``GET /healthz`` returns 200 once the model is loaded (`app.py:37-40`) —
  the k8s readiness probe target
  (`Issue_Embeddings/deployment/base/deployments.yaml:20-25`).
* The md5 of every embedding is logged for drift debugging
  (`app.py:72-75`).
* ``GET /metrics`` exports Prometheus text metrics (request counts by
  route/status, request-latency histogram, micro-batcher batch sizes,
  per-span-name ``trace_span_seconds`` roll-ups) — observability the
  reference's server lacks; format matches its chatbot exporter
  (`chatbot/pkg/server.go:25-30,61-66`).
* ``GET /debug/traces`` serves recent request traces (span trees:
  tokenize, batcher queue-wait, slot queue-wait/device-steps/pool-emit)
  as JSON; ``?slow=1`` serves the pinned slow-request ring and
  ``?format=chrome`` a Perfetto-loadable dump. Inbound W3C
  ``traceparent`` headers are honored, so a worker's embedding call
  joins the worker's event trace. Knobs: ``--trace_sample``,
  ``--slow_trace_ms``.
* ``GET /debug/flight`` serves the process's XLA compile ledger
  (utils/flight_recorder.py): compile wall time, cost_analysis flops,
  and memory_analysis HBM footprint per compiled shape of the slot
  step — the "why was that request 30s" answer when it paid a compile.
* Device work is serialized with a lock — same effect as the reference
  forcing Flask single-threaded (`app.py:123-128`), but reads stay
  concurrent. (JAX is thread-safe; the lock keeps per-request latency
  predictable instead of interleaving device programs.)
* **Admission control** (utils/resilience.py vocabulary): at most
  ``max_pending`` ``/text`` requests may be in flight; excess load is
  shed with ``429`` + a ``Retry-After`` hint *before* touching the
  request body or the device lock, so ``ThreadingHTTPServer`` can't
  stack unbounded threads onto serialized device work until latency
  collapses. ``GET /readyz`` flips to 503 at ~80% of the bound — the
  back-pressure signal a load balancer reads *before* the server starts
  shedding — while ``/healthz`` stays the liveness probe. A request
  arriving with an already-expired ``x-deadline-ms`` budget is shed too:
  its caller has stopped waiting. Knobs: ``--max_pending``,
  ``--shed_retry_after_s``; gauges ``embedding_pending_requests`` and
  counter ``embedding_shed_total{reason=...}`` on ``/metrics``.

* **Embedding cache** (serving/embed_cache.py, RUNBOOK §21): a
  content-addressed two-tier cache keyed by ``(token-content hash,
  engine.version, vocab hash)`` with single-flight coalescing — a
  repeated document never runs the device twice, and N concurrent
  requests for the same never-seen document share one pass. Outcomes
  ride the ``X-Cache`` response header, request spans, and the
  ``cache_*`` metrics. Knobs: ``--cache_mb`` (0 disables),
  ``--cache_dir`` (persistent tier).

An auth token can be required via ``X-Auth-Token`` (the reference deployed
behind cluster-internal networking only; this is the hardening knob for
anything else).
"""

from __future__ import annotations

import hashlib
import hmac
import json
import logging
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import TYPE_CHECKING, Optional

import numpy as np

if TYPE_CHECKING:  # annotation-only: the HTTP layer itself is jax-free,
    # so jax-less tooling (the fake replicas of the fleet gates) can
    # import it
    from code_intelligence_tpu.inference import InferenceEngine

from code_intelligence_tpu.serving.slo import (
    ServeSLO, SLOObjective, debug_slo_response)
from code_intelligence_tpu.utils import profiling, resilience
from code_intelligence_tpu.utils.metrics import Registry
from code_intelligence_tpu.utils.tracing import Tracer, debug_traces_response

log = logging.getLogger(__name__)


class EmbeddingServer(ThreadingHTTPServer):
    daemon_threads = True

    def __init__(
        self,
        addr,
        engine: InferenceEngine,
        auth_token: Optional[str] = None,
        batch_window_ms: Optional[float] = None,
        max_batch: int = 32,
        scheduler: str = "slots",
        trace_sample: float = 1.0,
        slow_trace_ms: float = 1000.0,
        max_pending: int = 64,
        shed_retry_after_s: float = 1.0,
        ready_shed_fraction: float = 0.8,
        rollout=None,
        drain_timeout_s: float = 30.0,
        cache=None,
        slo=None,
        slo_p99_ms: float = 250.0,
        slo_error_rate: float = 0.01,
        slo_fast_window_s: float = 300.0,
        slo_slow_window_s: float = 3600.0,
        profile_dir: Optional[str] = None,
        profile_max_seconds: float = 30.0,
        autoloop=None,
    ):
        self.engine = engine
        self.auth_token = auth_token
        # delivery/autoloop.AutoLoop co-located with this serving
        # process: /debug/autoloop serves its state, POST /trigger
        # (token-guarded) arms its manual trigger, and every served
        # embedding row feeds its drift detectors
        self.autoloop = autoloop
        self.model_lock = threading.Lock()
        self.ready = True
        self.batcher = None
        # content-addressed embedding cache + single-flight coalescing
        # (serving/embed_cache.py): hit/miss/coalesced outcomes land on
        # request spans and the cache_* metrics below
        self.cache = cache
        # canary rollout manager (serving/rollout.py): when present, /text
        # routes per request between resident engine versions, stamps
        # X-Model-Version, and feeds the serve-health sentinels
        self.rollout = rollout
        # SIGTERM graceful drain: stop admitting, finish resident work,
        # flush — set by drain(), read by try_admit()/readyz
        self.draining = False
        self.drain_timeout_s = float(drain_timeout_s)
        # fail at bind time, not on the first request: an unknown value
        # would otherwise silently run the groups path
        self.scheduler = engine._check_scheduler(scheduler)
        # admission control: bound the /text requests in flight so the
        # device lock never accumulates an unbounded thread pileup
        if max_pending < 1:
            raise ValueError("max_pending must be >= 1")
        self.max_pending = int(max_pending)
        self.shed_retry_after_s = float(shed_retry_after_s)
        # /readyz flips at this fill fraction — before shedding starts
        self.ready_threshold = max(1, int(self.max_pending * ready_shed_fraction))
        self._pending = 0
        self._pending_lock = threading.Lock()
        self.metrics = Registry()
        self.metrics.counter("embedding_requests_total", "requests by route and status")
        self.metrics.histogram("embedding_request_seconds", "end-to-end request latency")
        self.metrics.gauge("embedding_pending_requests",
                           "in-flight /text requests (admission-control depth)")
        self.metrics.counter("embedding_shed_total",
                             "requests shed by admission control, by reason")
        if cache is not None:
            cache.bind_registry(self.metrics)
        if rollout is not None:
            rollout.bind_registry(self.metrics)
            rollout.on_swap(self._on_default_swap)
            if getattr(rollout, "journal", None) is None:
                # default in-memory delivery journal so a standalone
                # member's /debug/journal answers (and a router's
                # /fleet/journal merge sees rollout events) without
                # autoloop wiring; a loop-attached persistent journal
                # takes precedence and is never overwritten
                from code_intelligence_tpu.utils.eventlog import (
                    EventJournal)

                rollout.journal = EventJournal(registry=self.metrics)
            if cache is not None:
                # promote/rollback must atomically stop serving the
                # retired version's entries (keys are version-scoped, so
                # this frees bytes and makes the guarantee observable)
                rollout.bind_cache(cache)
        # request tracing: every span duration also rolls up into
        # trace_span_seconds on this registry; traces land on
        # /debug/traces (slow ones pinned past ring churn)
        self.tracer = Tracer(registry=self.metrics, sample_rate=trace_sample,
                             slow_threshold_s=slow_trace_ms / 1000.0)
        # SLO observatory (serving/slo.py, RUNBOOK §22): streaming
        # latency/stage digests fed from finished request traces,
        # multi-window burn-rate sentinels on /metrics + /debug/slo.
        # Pass slo=False to disable, or a prebuilt ServeSLO to share
        # one across components. NOTE: the observatory only sees
        # SAMPLED requests — at --trace_sample < 1 its counts are a
        # sample, its quantiles remain unbiased estimates.
        if slo is False:
            self.slo = None
        else:
            self.slo = slo if slo is not None else ServeSLO(
                objective=SLOObjective(p99_ms=slo_p99_ms,
                                       max_error_rate=slo_error_rate),
                fast_window_s=slo_fast_window_s,
                slow_window_s=slo_slow_window_s)
            self.slo.bind_registry(self.metrics)
            self.tracer.on_trace(self.slo.ingest_trace)
            if rollout is not None:
                # burn alerts land in the rollout event history: a
                # promotion decision made while the process is burning
                # its error budget should see that in /debug/promotion
                self.slo.on_burn(
                    lambda trip, rec: rollout._note(
                        "slo_burn", sentinel=trip.sentinel,
                        reason=trip.reason))
        # on-demand device profiling (/debug/profile?seconds=N):
        # single-flight, bounded, Perfetto/TensorBoard-viewable capture
        self.profiler = profiling.ProfileCapture(
            base_dir=profile_dir, max_seconds=profile_max_seconds)
        self.metrics.counter("profile_captures_total",
                             "/debug/profile captures by HTTP status")
        # device-memory observatory (utils/memtrack.py, RUNBOOK §31): ONE
        # ledger per process attributes every live device buffer to a
        # registered owner — engine params per resident version (via the
        # rollout), slot state arenas + pool/paged pool, the embed
        # cache's host tier — and serves /debug/memory; hbm_* gauges
        # refresh on every snapshot
        from code_intelligence_tpu.utils.memtrack import DeviceMemoryLedger

        self.ledger = DeviceMemoryLedger(registry=self.metrics)
        if cache is not None:
            cache.register_memory_owner(self.ledger)
        if rollout is not None:
            rollout.bind_ledger(self.ledger)
        else:
            # no rollout: the default engine's weights still need an owner
            self.ledger.register(
                "engine.params",
                lambda: getattr(self.engine, "_enc_params", None))
        geometry = getattr(engine, "state_geometry", None)
        if geometry is not None:  # test doubles have none
            self.ledger.note_geometry(**geometry())
        super().__init__(addr, _Handler)  # bind first: a bind failure must
        if batch_window_ms is not None:  # not leak a running batcher thread
            from code_intelligence_tpu.serving.batcher import MicroBatcher

            self.batcher = MicroBatcher(
                engine, max_batch=max_batch, window_ms=batch_window_ms,
                registry=self.metrics, scheduler=scheduler, cache=cache,
            )
        if self.scheduler in ("slots", "ragged"):
            # slot occupancy / queue-depth / wasted-lane land on /metrics
            # even without the micro-batcher in front; force creation here
            # (idempotent — cached per mode) so the scheduler's arenas are
            # ledger-attributed from the first request, batcher or not
            sched = engine.slot_scheduler(registry=self.metrics,
                                          ragged=self.scheduler == "ragged")
            sched.register_memory_owners(self.ledger)

    # -- admission control ---------------------------------------------

    def try_admit(self) -> bool:
        """Admit a /text request or refuse (the caller sheds with 429).
        Must be paired with :meth:`release` when True."""
        with self._pending_lock:
            if self.draining or self._pending >= self.max_pending:
                return False
            self._pending += 1
            # gauge write stays under the lock: out-of-order sets would
            # let the overload signal report a stale depth
            self.metrics.set("embedding_pending_requests", self._pending)
        return True

    def release(self) -> None:
        with self._pending_lock:
            self._pending = max(self._pending - 1, 0)
            self.metrics.set("embedding_pending_requests", self._pending)

    def count_shed(self, reason: str) -> None:
        self.metrics.inc("embedding_shed_total", labels={"reason": reason})

    def saturated(self) -> bool:
        """True once pending depth crosses the readiness threshold — the
        /readyz signal that flips BEFORE shedding starts."""
        with self._pending_lock:
            return self._pending >= self.ready_threshold

    def embed(self, title: str, body: str):
        if self.batcher is not None:
            # the batcher serializes device work itself; no lock needed
            return self.batcher.embed_issue(title, body)
        with self.model_lock:
            return self.engine.embed_issues(
                [{"title": title, "body": body}], scheduler=self.scheduler)[0]

    def _on_default_swap(self, version, engine) -> None:
        """Rollout promote() hook: rebind the direct default-engine
        references (this server's non-routed ``embed`` path and the
        batcher's fallback) so the old incumbent is released once its
        in-flight requests finish, and ``drain()`` polls the slot
        scheduler that new work actually lands on. Plain attribute
        stores — atomic, and requests already routed keep the engine
        reference they resolved."""
        self.engine = engine
        if self.batcher is not None:
            self.batcher.engine = engine

    def _embed_on(self, engine, title: str, body: str):
        """Run ONE engine for one request — the embed_fn the rollout
        manager routes through (it owns version choice and health
        observation; this owns batching/locking)."""
        if self.batcher is not None:
            return self.batcher.embed_issue(title, body, engine=engine)
        with self.model_lock:
            return engine.embed_issues(
                [{"title": title, "body": body}], scheduler=self.scheduler)[0]

    def _embed_on_cached(self, engine, title: str, body: str):
        """(row, cache_outcome) for one request on one engine. With a
        batcher the cache lives inside its window loop (which serializes
        identical concurrent requests itself); the direct path wraps the
        device-lock embed with the single-flight protocol so N handler
        threads asking for the same never-seen document share ONE pass."""
        if self.cache is None:
            return self._embed_on(engine, title, body), None
        if self.batcher is not None:
            return self.batcher.embed_issue_cached(title, body, engine=engine)
        from code_intelligence_tpu.serving.embed_cache import cached_embed

        return cached_embed(self.cache, engine, title, body, self._embed_on)

    def embed_routed(self, title: str, body: str):
        """(embedding, model_version, cache_outcome) via the rollout
        manager; falls back to the single-engine path when no rollout is
        configured. The cache sits INSIDE the routed call so the canary
        and the incumbent each hit their own version-scoped entries (and
        a canary-failure fallback re-enters the cache on the incumbent's
        key)."""
        outcome_box = [None]

        def fn(engine, t, b):
            row, outcome = self._embed_on_cached(engine, t, b)
            outcome_box[0] = outcome
            return row

        if self.rollout is None:
            return fn(self.engine, title, body), None, outcome_box[0]
        emb, version = self.rollout.serve(title, body, fn)
        return emb, version, outcome_box[0]

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Graceful drain (the SIGTERM path): stop admitting via the
        admission gate (new requests shed, /readyz flips), wait for the
        resident in-flight requests to finish their slots, then flush
        the batcher. Returns True when everything finished inside the
        timeout — zero dropped in-flight requests either way (a request
        past the gate always runs to completion; the timeout only stops
        the WAIT, for supervisors that enforce their own grace period)."""
        self.draining = True
        with self._pending_lock:
            admitted = self._pending
        log.info("drain: admission closed, waiting for %d in-flight",
                 admitted)
        deadline = time.monotonic() + (self.drain_timeout_s
                                       if timeout_s is None else timeout_s)

        def resident() -> int:
            # admitted HTTP requests, plus anything still queued or
            # slot-resident in the scheduler (normally zero once pending
            # is zero — slot work is synchronous within a request — but
            # a direct embed_ids caller outside the HTTP path counts too)
            with self._pending_lock:
                n = self._pending
            for attr in ("_slot_scheduler", "_ragged_scheduler"):
                sched = getattr(self.engine, attr, None)
                if sched is not None:
                    n += sched.in_flight()
            return n

        while time.monotonic() < deadline and resident() > 0:
            time.sleep(0.02)
        drained = resident() == 0
        # flush the batcher only when everything finished: closing it
        # with requests still in flight would fail admitted waiters with
        # "batcher closed" — exactly the drop this method promises not
        # to cause. On timeout the supervisor's kill path (shutdown/
        # server_close) owns the final close.
        if drained and self.batcher is not None:
            self.batcher.close()
        if self.cache is not None:
            # let queued write-behind persistent fills land so the next
            # process starts warm (advisory: a drop is only a cold start)
            self.cache.flush_persistent(timeout_s=2.0)
        log.info("drain: %s", "complete" if drained
                 else "timed out with requests still in flight")
        return drained

    def shutdown(self):
        if self.batcher is not None:
            self.batcher.close()
        super().shutdown()

    def server_close(self):
        # server_close is the cleanup path that works without serve_forever
        # (context-manager exit, bind-and-abort); it must stop the batcher
        # thread too.
        if self.batcher is not None:
            self.batcher.close()
        super().server_close()


class _Handler(BaseHTTPRequestHandler):
    server: EmbeddingServer

    def log_message(self, fmt, *args):  # route through logging, not stderr
        log.info("%s %s", self.address_string(), fmt % args)

    def _send(self, code: int, body: bytes,
              content_type: str = "application/octet-stream",
              headers: Optional[dict] = None):
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        self.wfile.write(body)

    def _send_json(self, code: int, obj) -> None:
        self._send(code, json.dumps(obj).encode(), "application/json")

    def do_GET(self):
        path, _, query = self.path.partition("?")
        if path == "/healthz":
            if self.server.ready:
                self._send_json(200, {"status": "ok"})
            else:
                self._send_json(503, {"status": "loading"})
        elif path == "/readyz":
            # readiness = liveness AND headroom AND not draining: flips to
            # 503 at ~80% of the admission bound so the balancer backs off
            # BEFORE this replica starts shedding with 429s, and
            # immediately on SIGTERM so it stops routing here at all
            if self.server.draining:
                self._send_json(503, {"status": "draining"})
            elif self.server.ready and not self.server.saturated():
                self._send_json(200, {"status": "ok"})
            else:
                self._send_json(503, {"status": "saturated" if self.server.ready
                                      else "loading"})
        elif path == "/metrics":
            if self.server.slo is not None:
                # windowed burn gauges must DECAY after traffic stops,
                # not freeze at their last written (incident-era) value
                self.server.slo.refresh_gauges()
            self._send(200, self.server.metrics.render().encode(),
                       "text/plain; version=0.0.4")
        elif path == "/debug/traces":
            code, body, ctype = debug_traces_response(self.server.tracer, query)
            self._send(code, body, ctype)
        elif path == "/debug/flight":
            # serving has no step ring; this surfaces the process's XLA
            # compile ledger (the slot step's compile_seconds /
            # compiled_hbm_bytes per shape)
            from code_intelligence_tpu.utils.flight_recorder import (
                debug_flight_response)

            code, body, ctype = debug_flight_response(None, query=query)
            self._send(code, body, ctype)
        elif path == "/debug/slo":
            # the SLO observatory: objective, windowed burn rates,
            # per-stage quantile table, serialized digests (perfwatch
            # snapshots diff on these)
            code, body, ctype = debug_slo_response(self.server.slo, query)
            self._send(code, body, ctype)
        elif path == "/debug/profile":
            # on-demand device profiling: blocks for the (bounded)
            # capture window, single-flight — a concurrent pull gets
            # 409. Unlike the read-only debug routes this one does
            # heavy side-effectful work (process-wide profiler capture
            # + a dir on disk), so when the server has an auth token,
            # the route requires it (same X-Auth-Token check as /text)
            if not self._auth_ok():
                code, body, ctype = 403, json.dumps(
                    {"error": "bad auth token"}).encode(), \
                    "application/json"
                self.server.metrics.inc("profile_captures_total",
                                        labels={"code": str(code)})
                self._send(code, body, ctype)
                return
            code, body, ctype = profiling.debug_profile_response(
                self.server.profiler, query)
            self.server.metrics.inc("profile_captures_total",
                                    labels={"code": str(code)})
            self._send(code, body, ctype)
        elif path == "/debug/promotion":
            # rollout post-mortem surface: current split, resident
            # versions, promotion event history, sentinel trips — the
            # serve-side twin of /debug/flight
            ro = self.server.rollout
            self._send_json(200, {
                "rollout": ro.debug_state() if ro is not None else None,
                "draining": self.server.draining,
            })
        elif path == "/debug/autoloop":
            # the delivery loop's state machine + trigger/cool-down
            # status (RUNBOOK §27), when an AutoLoop rides this process
            al = self.server.autoloop
            if al is None:
                self._send_json(404, {"error": "no autoloop attached"})
            else:
                self._send_json(200, al.debug_state())
        elif path == "/debug/journal":
            # the delivery event journal (RUNBOOK §29): cross-subsystem
            # timeline + per-phase duration digests. Reached through
            # whichever delivery component rides this process.
            from code_intelligence_tpu.utils.eventlog import (
                debug_journal_response)

            journal = getattr(self.server.autoloop, "journal", None)
            if journal is None:
                journal = getattr(self.server.rollout, "journal", None)
            code, body, ctype = debug_journal_response(journal, query)
            self._send(code, body, ctype)
        elif path == "/debug/memory":
            # the device-memory observatory (RUNBOOK §31): live-buffer
            # ledger attributed per owner/device, leak-sentinel record,
            # capacity planner (?budget_bytes=N overrides the default
            # per-device budget) — perfwatch --memory snapshots diff this
            from code_intelligence_tpu.utils.memtrack import (
                debug_memory_response)

            code, body, ctype = debug_memory_response(self.server.ledger,
                                                      query)
            self._send(code, body, ctype)
        else:
            self._send_json(404, {"error": f"no route {self.path}"})

    def do_POST(self):
        t0 = time.perf_counter()
        # known routes only: raw client paths would grow label cardinality
        # (and registry memory) without bound
        route = "/text" if self.path == "/text" else "other"
        # root span: honors an inbound W3C traceparent (a worker's predict
        # call joins its event's trace); everything the handler thread and
        # the batcher/slot threads do for this request hangs off it
        with self.server.tracer.continue_trace(
                "http.request", self.headers, route=route) as sp:
            code, body, ctype, extra_headers = self._handle_post()
            sp.set(code=code)
            if extra_headers and "X-Model-Version" in extra_headers:
                # the canary split on the trace: which engine version
                # actually served this request
                sp.set(model_version=extra_headers["X-Model-Version"])
            if extra_headers and "X-Cache" in extra_headers:
                # hit/miss/coalesced on the trace: the first question in
                # any "why was that request slow/fast" post-mortem
                sp.set(cache=extra_headers["X-Cache"])
        # Record metrics BEFORE the response bytes go out: a client that
        # receives its response and immediately scrapes /metrics must see
        # its own request counted (observed round-2 flake under load —
        # tests/test_inference.py::TestServer::test_auth_token).
        self.server.metrics.inc(
            "embedding_requests_total", labels={"route": route, "code": str(code)}
        )
        self.server.metrics.observe(
            "embedding_request_seconds", time.perf_counter() - t0
        )
        self._send(code, body, ctype, headers=extra_headers)

    def _auth_ok(self) -> bool:
        """Token check shared by ``/text`` and ``/debug/profile`` (true
        when no token is configured). The stdlib http parser decodes
        header bytes as latin-1, so recover the raw wire bytes by
        re-encoding latin-1 and compare against the token's UTF-8
        bytes — a client sending the UTF-8 bytes of a non-ASCII token
        must authenticate. ('ignore' only triggers on impossible >0xFF
        chars -> safe deny.)"""
        token = self.server.auth_token
        if token is None:
            return True
        received = self.headers.get("X-Auth-Token") or ""
        return hmac.compare_digest(
            received.encode("latin-1", "ignore"), token.encode("utf-8"))

    @staticmethod
    def _json_body(code: int, obj, headers: Optional[dict] = None
                   ) -> tuple[int, bytes, str, Optional[dict]]:
        return code, json.dumps(obj).encode(), "application/json", headers

    def _handle_trigger(self) -> tuple[int, bytes, str, Optional[dict]]:
        """``POST /trigger``: arm the co-located autoloop's manual
        trigger. Token-guarded like ``/debug/profile`` — it starts a
        retrain pipeline, not a read. Auth + body semantics live in
        the ONE shared implementation (delivery/autoloop.py)."""
        al = self.server.autoloop
        if al is None:
            return self._json_body(404, {"error": "no autoloop attached"})
        from code_intelligence_tpu.delivery.autoloop import (
            handle_trigger_post)

        code, obj = handle_trigger_post(al, self.headers, self.rfile,
                                        self.server.auth_token)
        return self._json_body(code, obj)

    def _shed(self, reason: str) -> tuple[int, bytes, str, Optional[dict]]:
        """429 + Retry-After, without touching the body or the device."""
        self.server.count_shed(reason)
        return self._json_body(
            429,
            {"error": "server overloaded, retry later", "reason": reason},
            headers={"Retry-After": f"{self.server.shed_retry_after_s:g}"},
        )

    def _handle_post(self) -> tuple[int, bytes, str, Optional[dict]]:
        """Compute the full response without writing it — the caller records
        metrics first, then sends."""
        if self.path == "/trigger":
            return self._handle_trigger()
        if self.path != "/text":
            return self._json_body(404, {"error": f"no route {self.path}"})
        if not self._auth_ok():
            return self._json_body(403, {"error": "bad auth token"})
        # admission control BEFORE reading the body or queueing device
        # work: shed responses must stay cheap under overload
        deadline = resilience.Deadline.from_headers(self.headers)
        if deadline is not None and deadline.expired():
            # the caller's x-deadline-ms budget is spent: it has stopped
            # waiting, so doing the work would only burn the device
            return self._shed("deadline_expired")
        if self.server.draining:
            # 503 (not 429): this replica is going away — the balancer
            # should retry elsewhere, not here later
            self.server.count_shed("draining")
            return self._json_body(
                503, {"error": "server draining"},
                headers={"Retry-After":
                         f"{self.server.shed_retry_after_s:g}"})
        if not self.server.try_admit():
            return self._shed("overload")
        try:
            try:
                length = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(length) or b"{}")
                if not isinstance(payload, dict):
                    raise ValueError("payload must be a JSON object")
                title = payload.get("title", "")
                body = payload.get("body", "")
            except (ValueError, json.JSONDecodeError) as e:
                return self._json_body(400, {"error": f"bad request body: {e}"})
            try:
                with resilience.deadline_scope(deadline):
                    emb, model_version, cache_outcome = \
                        self.server.embed_routed(title, body)
            except resilience.DeadlineExceeded:
                # the budget expired while the request waited its turn —
                # the engine's backstop kept it off the device; tell the
                # caller to retry like any other shed
                return self._shed("deadline_expired")
            except Exception:
                log.exception("embedding failed")
                return self._json_body(500, {"error": "embedding failed"})
        finally:
            self.server.release()
        if self.server.autoloop is not None:
            # the drift detectors watch the LIVE serve stream; the feed
            # is guarded inside observe_embedding — it never raises
            # into the request path
            self.server.autoloop.observe_embedding(emb)
        raw = np.ascontiguousarray(emb, dtype="<f4").tobytes()
        # md5 drift log, app.py:72-75.
        log.info(
            "embedding md5=%s dim=%d title_len=%d model_version=%s",
            hashlib.md5(raw).hexdigest(),
            emb.shape[-1],
            len(title),
            model_version,
        )
        headers = {}
        if model_version:
            headers["X-Model-Version"] = model_version
        if cache_outcome:
            # hit/miss/coalesced on the wire: clients and load tests can
            # A/B on it without scraping /metrics
            headers["X-Cache"] = cache_outcome
        if deadline is not None:
            # echo the remaining budget: the caller (and the fleet
            # router's --check_fleet gate) gets wire-level PROOF that
            # x-deadline-ms propagated to the replica that served it
            headers["X-Deadline-Ms"] = deadline.header_value()
        return 200, raw, "application/octet-stream", headers or None


def make_server(
    engine: InferenceEngine,
    host: str = "0.0.0.0",
    port: int = 8080,
    auth_token: Optional[str] = None,
    batch_window_ms: Optional[float] = None,
    max_batch: int = 32,
    scheduler: str = "slots",
    trace_sample: float = 1.0,
    slow_trace_ms: float = 1000.0,
    max_pending: int = 64,
    shed_retry_after_s: float = 1.0,
    rollout=None,
    drain_timeout_s: float = 30.0,
    cache=None,
    slo=None,
    slo_p99_ms: float = 250.0,
    slo_error_rate: float = 0.01,
    profile_dir: Optional[str] = None,
    profile_max_seconds: float = 30.0,
    autoloop=None,
) -> EmbeddingServer:
    return EmbeddingServer(
        (host, port),
        engine,
        auth_token=auth_token,
        batch_window_ms=batch_window_ms,
        max_batch=max_batch,
        scheduler=scheduler,
        trace_sample=trace_sample,
        slow_trace_ms=slow_trace_ms,
        max_pending=max_pending,
        shed_retry_after_s=shed_retry_after_s,
        rollout=rollout,
        drain_timeout_s=drain_timeout_s,
        cache=cache,
        slo=slo,
        slo_p99_ms=slo_p99_ms,
        slo_error_rate=slo_error_rate,
        profile_dir=profile_dir,
        profile_max_seconds=profile_max_seconds,
        autoloop=autoloop,
    )


def build_server(argv=None) -> EmbeddingServer:
    """Parse the CLI, load and warm the engine(s), bind the socket.
    Returns the server ready for ``serve_forever`` — :func:`main` is this
    plus the SIGTERM drain and the serve loop (``chip_smoke.py`` drives
    the same function from a thread, where a signal handler cannot be
    installed)."""
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model_dir", required=True, help="export_encoder directory")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--batch_size", type=int, default=32)
    p.add_argument("--auth_token", default=None)
    p.add_argument(
        "--batch_window_ms", type=float, default=None,
        help="enable cross-request micro-batching with this collect window",
    )
    p.add_argument(
        "--scheduler", choices=("slots", "groups", "ragged"),
        default="slots",
        help="slots = continuous in-flight batching (one compiled step "
             "shape, per-document completion); ragged = the same slot "
             "loop with paged state and a length-aware page-sized step "
             "(mixed-length batches cost ~sum-of-tokens — RUNBOOK §23); "
             "groups = the reference-shaped length-sorted lock-step path",
    )
    p.add_argument(
        "--mesh", default=None,
        help="shard the serve step over a device mesh, e.g. 'data,model' "
             "or 'data=4,model=2' (RUNBOOK §26): batch rows split over "
             "data, encoder params over model — per-replica capacity "
             "xN chips on a multi-chip host. Default off = today's "
             "single-chip step, bit-for-bit",
    )
    p.add_argument(
        "--trace_sample", type=float, default=1.0,
        help="fraction of requests traced (per-request decision at the "
             "root span; memory stays bounded either way)",
    )
    p.add_argument(
        "--slow_trace_ms", type=float, default=1000.0,
        help="requests slower than this are pinned in the slow-trace "
             "ring on /debug/traces?slow=1, surviving ring churn",
    )
    p.add_argument(
        "--max_pending", type=int, default=64,
        help="admission-control bound: /text requests in flight beyond "
             "this are shed with 429 + Retry-After instead of queueing "
             "onto the device lock (/readyz flips to 503 at ~80%%)",
    )
    p.add_argument(
        "--shed_retry_after_s", type=float, default=1.0,
        help="Retry-After hint (seconds) on shed responses",
    )
    p.add_argument(
        "--lstm_pallas", action=argparse.BooleanOptionalAction, default=None,
        help="serve on the weights-resident Pallas LSTM cell (TPU only; "
             "1.2-1.8x the scan at the flagship shape, RUNBOOK §11); "
             "--no-lstm_pallas forces the scan even if the exported "
             "config enables the kernel",
    )
    p.add_argument(
        "--precision", choices=("f32", "int8"), default="f32",
        help="serve-path weight precision (RUNBOOK §28): int8 quantizes "
             "the encoder weights at load (symmetric per-channel, "
             "ops/quantize.py) — ~3.5x smaller resident weights, dequant "
             "fused into the matmuls, parity/AUC gated by runbook_ci "
             "--check_int8; exports stay f32 either way",
    )
    p.add_argument(
        "--model_version", default="incumbent",
        help="version label for the default engine (stamped on responses "
             "as X-Model-Version, /metrics, and trace spans)",
    )
    p.add_argument(
        "--candidate_dir", default=None,
        help="export_encoder directory of a CANARY candidate: loaded as a "
             "second resident engine and given --canary_pct of traffic "
             "(the promotion controller drives this programmatically; "
             "the flag is the manual/static form)",
    )
    p.add_argument(
        "--candidate_version", default="candidate",
        help="version label for --candidate_dir",
    )
    p.add_argument(
        "--canary_pct", type=float, default=5.0,
        help="percent of traffic routed to the candidate engine "
             "(deterministic md5 hash split over request content)",
    )
    p.add_argument(
        "--shadow_ring", type=int, default=256,
        help="recorded-traffic ring capacity (recent requests kept for "
             "shadow replay against promotion candidates)",
    )
    p.add_argument(
        "--drain_timeout_s", type=float, default=30.0,
        help="SIGTERM grace: how long drain() waits for in-flight "
             "requests before giving up the wait (requests past the "
             "admission gate always run to completion)",
    )
    p.add_argument(
        "--cache_mb", type=float, default=256.0,
        help="in-memory embedding-cache budget (content-addressed, "
             "single-flight coalesced; RUNBOOK §21); 0 disables caching",
    )
    p.add_argument(
        "--cache_dir", default=None,
        help="persistent embedding-cache tier (a directory or gs:// "
             "URI); entries survive restarts and are corruption-"
             "tolerant — omit for memory-only",
    )
    p.add_argument(
        "--slo_p99_ms", type=float, default=250.0,
        help="latency objective: requests over this burn the error "
             "budget; burn rates + per-stage quantiles land on "
             "/metrics (slo_*, stage_*) and /debug/slo (RUNBOOK §22)",
    )
    p.add_argument(
        "--slo_error_rate", type=float, default=0.01,
        help="error-rate objective (fraction); errors burn the same "
             "budget as latency breaches",
    )
    p.add_argument(
        "--profile_dir", default=None,
        help="where /debug/profile?seconds=N writes its capture dirs "
             "(default: <tmp>/ci_tpu_profiles); captures are single-"
             "flight and bounded",
    )
    p.add_argument(
        "--profile_max_seconds", type=float, default=30.0,
        help="upper clamp on a /debug/profile capture window — an HTTP "
             "caller can never park the profiler longer than this",
    )
    args = p.parse_args(argv)
    if args.mesh and args.scheduler == "groups":
        # fail at the CLI, not silently serve unsharded: only the
        # slot/ragged schedulers run the sharded step — the groups
        # path's compiled forwards never shard (RUNBOOK §26)
        p.error("--mesh requires --scheduler slots or ragged (the "
                "groups path runs unsharded compiled forwards)")
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")

    from code_intelligence_tpu.inference import InferenceEngine
    from code_intelligence_tpu.serving.rollout import RolloutManager
    from code_intelligence_tpu.utils import devices

    log.info("compile cache: %s", devices.enable_compile_cache())
    log.info("devices: %s", devices.describe())
    engine = InferenceEngine.from_export(
        args.model_dir, batch_size=args.batch_size,
        lstm_pallas=args.lstm_pallas, version=args.model_version,
        mesh=args.mesh, precision=args.precision)
    # Compile the step of the scheduler this server serves, so the first
    # request isn't a flagship-shape compile.
    engine.warmup(scheduler=args.scheduler)
    rollout = RolloutManager(engine, version=args.model_version,
                             ring_capacity=args.shadow_ring)
    cache = None
    if args.cache_mb > 0:
        from code_intelligence_tpu.serving.embed_cache import EmbedCache

        # write-behind: persistent fills must never head-of-line block
        # the batcher's window loop on storage latency
        cache = EmbedCache(max_bytes=int(args.cache_mb * (1 << 20)),
                           storage=args.cache_dir, write_behind=True)
    srv = make_server(
        engine, args.host, args.port, auth_token=args.auth_token,
        batch_window_ms=args.batch_window_ms, max_batch=args.batch_size,
        scheduler=args.scheduler, trace_sample=args.trace_sample,
        slow_trace_ms=args.slow_trace_ms, max_pending=args.max_pending,
        shed_retry_after_s=args.shed_retry_after_s, rollout=rollout,
        drain_timeout_s=args.drain_timeout_s, cache=cache,
        slo_p99_ms=args.slo_p99_ms, slo_error_rate=args.slo_error_rate,
        profile_dir=args.profile_dir,
        profile_max_seconds=args.profile_max_seconds,
    )
    if args.candidate_dir:
        candidate = InferenceEngine.from_export(
            args.candidate_dir, batch_size=args.batch_size,
            lstm_pallas=args.lstm_pallas, version=args.candidate_version,
            mesh=args.mesh,  # the canary serves on the SAME mesh
            precision=args.precision)  # ...and the same precision
        candidate.warmup(scheduler=args.scheduler)  # compile off-path
        rollout.start_canary(args.candidate_version, candidate,
                             args.canary_pct)
    return srv


def main(argv=None) -> None:
    """CLI: ``python -m code_intelligence_tpu.serving.server --model_dir ...``"""
    import signal

    srv = build_server(argv)

    def _sigterm(signum, frame):
        # drain in a worker thread: the handler must not block the main
        # thread serve_forever loop that's still finishing requests
        def _go():
            srv.drain()
            srv.shutdown()  # blocks until serve_forever exits
            srv.server_close()

        threading.Thread(target=_go, daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    log.info("embedding server listening on %s:%d", *srv.server_address[:2])
    srv.serve_forever()


if __name__ == "__main__":
    main()
