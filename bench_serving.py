"""Serving-path benchmark: embedding latency + throughput, engine and HTTP.

The reference's serving story has no published latency numbers (SURVEY §6) —
its anchors are structural: a single-threaded Flask server
(`flask_app/app.py:127`), a bulk path "stable at bs=200 on a V100"
(`inference.py:149-151`), and replica scale-out. This harness produces the
numbers the reference lacks, on the same wire contract:

* engine-direct single-document latency (p50/p95/p99 over warm buckets),
* engine-direct bulk throughput (`embed_issues`, docs/sec),
* a scheduler A/B — continuous slot batching (`--scheduler slots`) vs the
  group-synchronous reference path (`--scheduler groups`) — on the same
  mixed-length workload fed in ARRIVAL order in micro-batch windows (the
  serving pattern: no global length sort is possible at serve time, so a
  group window pays its longest member's bucket while slots pay only each
  document's own chunks),
* HTTP `POST /text` end-to-end latency under concurrency, micro-batcher
  ON vs OFF (the ON/OFF ratio is the measured micro-batch win).

One JSON line on stdout (bench.py's convention):

    PYTHONPATH=. python bench_serving.py --model_dir /tmp/quality_r03/lm/encoder_export

``--smoke`` runs the scheduler A/B on a tiny in-process engine (no model
artifact needed); tests/test_bench_serving.py pins that path.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional

import numpy as np

from code_intelligence_tpu.utils.digest import QuantileDigest

def _stamp(out: Dict) -> Dict:
    """Provenance on EVERY emitted line: a dashboard must never mistake
    an error datapoint for a measurement — freshness is stamped, not
    inferred from field absence."""
    out["provenance"] = ("fresh" if out.get("error") is None
                         else "no_measurement_available")
    out["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    return out


def _finish(out: Dict) -> Dict:
    """Print the one stamped line. A phase that raised (``error``) or an
    A/B that did not hold (``ok`` false) is a non-zero exit: the line
    keeps the metric series, the exit code fails the step."""
    print(json.dumps(_stamp(out)))
    if out.get("error") is not None or out.get("ok") is False:
        sys.exit(1)
    return out


def _percentiles(samples_s: List[float]) -> Dict[str, float]:
    a = np.asarray(samples_s) * 1e3
    return {
        "p50_ms": round(float(np.percentile(a, 50)), 2),
        "p95_ms": round(float(np.percentile(a, 95)), 2),
        "p99_ms": round(float(np.percentile(a, 99)), 2),
        "mean_ms": round(float(a.mean()), 2),
    }


def _digest_line(samples_s: List[float], kind: str) -> Dict:
    """Per-request latencies as the SLO observatory's own estimator
    (utils/digest.py): the serialized sketch plus its p50/p90/p99. A
    bench line carrying ``latency_digest`` is directly diffable by
    perfwatch against a live ``/debug/slo`` pull — identical DDSketch
    math on both sides, never histogram-vs-sorted-array bucket
    arithmetic (RUNBOOK §22). ``kind`` names WHAT was measured
    (``http_e2e`` vs ``engine_single_doc``): perfwatch refuses to diff
    mismatched kinds — an engine-direct smoke p50 gated against an
    HTTP e2e p50 would be a false verdict either way."""
    d = QuantileDigest()
    d.add_many(samples_s)
    return {"latency_digest": d.to_dict(),
            "latency_digest_ms": d.summary_ms(),
            "latency_kind": kind}


def make_issues(n: int, seed: int = 0,
                zipf_a: Optional[float] = None) -> List[Dict[str, str]]:
    """Deterministic GitHub-issue-shaped payloads with a realistic length
    spread (short bug reports through long stack-trace dumps).

    Without ``zipf_a`` every document is unique — which means the bench
    could never exercise the duplication that dominates real label
    traffic (the same issue re-embedded on every event and edit). With
    ``zipf_a`` (> 1), the ``n`` documents are drawn from a unique pool by
    a seeded Zipf rank distribution — a few hot issues dominate, a long
    tail appears once — so a duplicate-aware serve path (the embedding
    cache, RUNBOOK §21) has something honest to measure against. The
    realized duplication is reported by :func:`workload_stats`, never
    assumed from the parameter."""
    rng = np.random.RandomState(seed)
    words = ["error", "deploy", "pipeline", "cluster", "training", "panic",
             "timeout", "upgrade", "config", "tensor", "shape", "node",
             "worker", "notebook", "gpu", "memory", "crash", "retry"]
    issues = []
    for i in range(n):
        n_body = int(rng.choice([20, 60, 150, 400], p=[0.4, 0.3, 0.2, 0.1]))
        title = f"{rng.choice(words)} in {rng.choice(words)} #{i}"
        body_words = rng.choice(words, size=n_body)
        body = " ".join(body_words)
        if rng.rand() < 0.3:  # markdown surface like real issues
            body += "\n```\nTraceback (most recent call last):\n  " \
                    + " ".join(rng.choice(words, size=8)) + "\n```"
        issues.append({"title": title, "body": body})
    if zipf_a is None:
        return issues
    if zipf_a <= 1.0:
        raise ValueError(f"zipf_a must be > 1, got {zipf_a}")
    # rank-sample the unique pool: rank r appears with p ~ r**-a, folded
    # into the pool so the workload length stays exactly n. The pool is
    # in generation order, so rank 1 = issue #0 deterministically.
    ranks = np.random.RandomState(seed + 1).zipf(zipf_a, size=n)
    return [issues[int((r - 1) % n)] for r in ranks]


def make_mixed_length_ids(engine, n: int, seed: int = 0,
                          zipf_a: float = 1.35,
                          max_len: int = 400) -> List[np.ndarray]:
    """Seeded Zipf TOKEN-LENGTH workload, already numericalized — the
    ragged A/B's experimental variable is per-document length, so the
    workload controls lengths directly instead of going through the
    tokenizer (whose inflation would blur the distribution). A few
    documents are long stack-trace dumps; the bulk are short bug
    reports — the regime where the dense slot step's rows×chunk_len
    cost wastes the most lanes."""
    rng = np.random.RandomState(seed)
    lens = np.minimum(rng.zipf(zipf_a, size=n), max_len)
    hi = max(6, min(150, engine.config.vocab_size - 1))
    return [rng.randint(5, hi, int(l)).astype(np.int32) for l in lens]


def bench_ragged_ab(engine, n_docs: int = 64, seed: int = 0,
                    zipf_a: float = 1.5, max_len: int = 150,
                    audit: bool = True, reps: int = 3) -> Dict:
    """Ragged paged scheduler vs dense slot scheduler on the SAME
    mixed-length workload in the SAME arrival order (RUNBOOK §23).
    Reports, per side:

    * achieved tokens/s and docs/s (best-of-``reps``, the noise-robust
      convention shared with the other A/Bs),
    * the realized wasted-lane fraction (masked ÷ stepped tokens, from
      the schedulers' host-side lane counters — the same numbers behind
      the ``slots_wasted_lane_fraction`` gauge),
    * AOT ``cost_analysis`` flops-per-token: the ONE compiled step's
      flops × steps actually run ÷ valid tokens actually staged —
      device-free, so the ragged flops claim is provable on CPU.

    Honesty pins riding the measurement: allclose parity between the
    two paths (a scheduler that changes answers is not a scheduler),
    and the ragged steady-state pass audited under
    ``no_implicit_transfers()`` + ``recompile_guard(budget=0)`` — the
    page table and valid lengths must ride the packed staging block,
    never their own per-step transfers, and the step must stay ONE
    compiled shape.

    The CI gate (``inference/ragged_check.py``, ``runbook_ci
    --check_ragged``) is this harness's package-internal twin on a
    committed fixture — keep their accounting in step when changing
    either."""
    ids = make_mixed_length_ids(engine, n_docs, seed=seed, zipf_a=zipf_a,
                                max_len=max_len)
    total_tokens = int(sum(len(s) for s in ids))
    # warm both paths (compiles both single step shapes) + parity pin
    dense_emb = engine.embed_ids_batch(ids, scheduler="slots")
    ragged_emb = engine.embed_ids_batch(ids, scheduler="ragged")
    parity = float(np.max(np.abs(dense_emb - ragged_emb))) if ids else 0.0

    audited = False
    if audit:
        from code_intelligence_tpu.analysis import runtime as audit_rt

        with audit_rt.recompile_guard(fn="slots.step_ragged", budget=0), \
                audit_rt.no_implicit_transfers():
            engine.embed_ids_batch(ids, scheduler="ragged")
        audited = True

    def timed_side(policy: str, sched) -> Dict:
        steps0 = sched.steps_run
        stepped0, valid0 = sched.tokens_stepped, sched.tokens_valid
        best = float("inf")
        for _ in range(max(reps, 1)):
            t0 = time.perf_counter()
            engine.embed_ids_batch(ids, scheduler=policy)
            best = min(best, time.perf_counter() - t0)
        steps = sched.steps_run - steps0
        stepped = sched.tokens_stepped - stepped0
        valid = sched.tokens_valid - valid0
        flops = sched.step_cost_analysis()["flops"]
        return {
            "docs_per_sec": round(len(ids) / max(best, 1e-9), 1),
            "tokens_per_sec": round(total_tokens / max(best, 1e-9), 1),
            "steps_run": steps,
            "wasted_lane_fraction": round(1.0 - valid / max(stepped, 1), 4),
            "step_flops": flops,
            "flops_per_token": round(flops * steps / max(valid, 1), 1),
        }

    dense = timed_side("slots", engine.slot_scheduler())
    ragged = timed_side("ragged", engine.slot_scheduler(ragged=True))
    rs = engine.slot_scheduler(ragged=True)
    return {
        "n_docs": len(ids),
        "total_tokens": total_tokens,
        "chunk_len": engine.slot_scheduler().chunk_len,
        "page_len": rs.page_len,
        "dense": dense,
        "ragged": ragged,
        # the acceptance ratio: < 1 means mixed lengths cost closer to
        # sum-of-tokens than rows×chunk_len
        "flops_per_token_ratio": round(
            ragged["flops_per_token"] / max(dense["flops_per_token"], 1e-9),
            4),
        "tokens_per_sec_speedup": round(
            ragged["tokens_per_sec"] / max(dense["tokens_per_sec"], 1e-9),
            2),
        "parity_max_abs_diff": parity,
        "ragged_compiled_step_shapes": rs.compiled_step_shapes(),
        "audited": audited,
    }


def bench_precision_ab(f32_engine, int8_engine, n_docs: int = 64,
                       seed: int = 0, zipf_a: float = 1.5,
                       max_len: int = 150, audit: bool = True,
                       reps: int = 3) -> Dict:
    """Int8 quantize-at-load engine vs the f32 engine over the SAME
    params on the SAME Zipf mixed-length workload (RUNBOOK §28), both
    sides on the ragged scheduler. Reports per side docs/s and
    tokens/s (best-of-``reps``) plus:

    * the resident encoder weight footprint per side and the ratio —
      the ~3.5x HBM shrink that raises per-replica model-version and
      tenant-head capacity (the bench's headline number; throughput
      parity is the *acceptance floor*, not the claim, on CPU where the
      int8 path pays dequant without the HBM-bandwidth win),
    * allclose parity within the quantization band (a precision that
      changes answers beyond band is a regression, not a mode),
    * the int8 steady-state pass audited under
      ``no_implicit_transfers()`` + ``recompile_guard(budget=0)`` —
      int8 changes leaf dtypes, never shapes, so the ONE compiled step
      shape must survive.

    The CI gate (``inference/int8_check.py``, ``runbook_ci
    --check_int8``) is this harness's package-internal twin on a
    committed fixture — keep their accounting in step when changing
    either."""
    from code_intelligence_tpu.ops.quantize import tree_bytes

    ids = make_mixed_length_ids(f32_engine, n_docs, seed=seed,
                                zipf_a=zipf_a, max_len=max_len)
    total_tokens = int(sum(len(s) for s in ids))
    # warm both single step shapes + the parity pin
    f32_emb = f32_engine.embed_ids_batch(ids, scheduler="ragged")
    int8_emb = int8_engine.embed_ids_batch(ids, scheduler="ragged")
    parity = float(np.max(np.abs(f32_emb - int8_emb))) if ids else 0.0
    parity_ok = bool(np.allclose(int8_emb, f32_emb, atol=0.05, rtol=0.05))

    audited = False
    if audit:
        from code_intelligence_tpu.analysis import runtime as audit_rt

        with audit_rt.recompile_guard(fn="slots.step_ragged", budget=0), \
                audit_rt.no_implicit_transfers():
            int8_engine.embed_ids_batch(ids, scheduler="ragged")
        audited = True

    def timed_side(engine) -> Dict:
        best = float("inf")
        for _ in range(max(reps, 1)):
            t0 = time.perf_counter()
            engine.embed_ids_batch(ids, scheduler="ragged")
            best = min(best, time.perf_counter() - t0)
        return {
            "docs_per_sec": round(len(ids) / max(best, 1e-9), 1),
            "tokens_per_sec": round(total_tokens / max(best, 1e-9), 1),
            "weight_bytes": tree_bytes(engine._enc_params["params"]),
        }

    f32 = timed_side(f32_engine)
    int8 = timed_side(int8_engine)
    return {
        "n_docs": len(ids),
        "total_tokens": total_tokens,
        "f32": f32,
        "int8": int8,
        "weight_footprint_ratio": round(
            f32["weight_bytes"] / max(int8["weight_bytes"], 1), 4),
        "tokens_per_sec_speedup": round(
            int8["tokens_per_sec"] / max(f32["tokens_per_sec"], 1e-9), 2),
        "parity_max_abs_diff": parity,
        "parity_ok": parity_ok,
        "int8_compiled_step_shapes": int8_engine.slot_scheduler(
            ragged=True).compiled_step_shapes(),
        "audited": audited,
        "ok": bool(parity_ok and audited),
    }


def run_precision_ab(smoke: bool = False,
                     model_dir: Optional[str] = None,
                     batch_size: int = 8) -> Dict:
    """The ``--precision_ab`` CLI mode: one provenance-stamped JSON
    line. ``--smoke`` runs the tiny in-process engine pair; otherwise
    the f32 export loads once and the int8 twin quantizes-at-load from
    the SAME in-memory params (the artifact is ~1GB at flagship scale —
    never read or held twice)."""
    from code_intelligence_tpu.inference import InferenceEngine

    out: Dict = {"metric": "embedding_serving_precision_ab",
                 "unit": "docs/sec", "smoke": bool(smoke)}
    if smoke:
        f32_engine = make_smoke_engine(batch_size)
    else:
        if not model_dir:
            raise ValueError("--precision_ab needs --model_dir or --smoke")
        f32_engine = InferenceEngine.from_export(model_dir,
                                                 batch_size=batch_size)
    int8_engine = InferenceEngine(
        f32_engine._enc_params["params"], f32_engine.config,
        f32_engine.vocab, buckets=f32_engine.buckets,
        batch_size=f32_engine.batch_size, precision="int8")
    out.update(bench_precision_ab(f32_engine, int8_engine))
    out["value"] = out["int8"]["docs_per_sec"]
    return out


def bench_mesh_ab(engine, mesh, n_docs: int = 64, seed: int = 0,
                  zipf_a: float = 1.5, max_len: int = 150,
                  audit: bool = True, reps: int = 3) -> Dict:
    """Mesh-sharded ragged step vs the single-chip step on the SAME
    Zipf mixed-length workload in the SAME arrival order (RUNBOOK §26)
    — the within-replica scaling twin of ``--fleet_ab``'s across-replica
    A/B. Reports per side docs/s and tokens/s plus:

    * allclose parity (a sharding that changes answers is not a
      sharding) and a ``--mesh`` OFF ⇒ bitwise-identical pin (the
      single-chip path must be untouched by the mesh machinery),
    * the mesh side audited under ``no_implicit_transfers()`` +
      ``recompile_guard(budget=0)`` on its own step name
      (``slots.step_ragged_mesh``) — the staging block stays the ONE
      explicit sharded h2d per step, one compiled shape,
    * per-device AOT ``cost_analysis`` flops of the sharded step vs
      total/mesh_size (``flops_balance`` ≈ 1 means the work actually
      split; pinned ≤ 1.2) — provable on a forced CPU mesh.

    The CI gate (``parallel/meshserve_check.py``, ``runbook_ci
    --check_meshserve``) is this harness's package-internal twin — keep
    the pins in step when changing either.
    """
    from code_intelligence_tpu.inference.slots import RaggedSlotScheduler
    from code_intelligence_tpu.parallel import serve_shard

    ids = make_mixed_length_ids(engine, n_docs, seed=seed, zipf_a=zipf_a,
                                max_len=max_len)
    total_tokens = int(sum(len(s) for s in ids))
    # warm both sides (each compiles its ONE step shape) + parity pin.
    # The engine's own cached scheduler is the single-chip side; the
    # sharded scheduler is constructed directly so the engine cache
    # (and every other caller of it) stays untouched.
    single_emb = engine.embed_ids_batch(ids, scheduler="ragged")
    sharded = RaggedSlotScheduler(engine, mesh=mesh)
    mesh_emb = sharded.embed_ids(ids)
    parity = float(np.max(np.abs(mesh_emb - single_emb))) if ids else 0.0
    parity_ok = bool(np.allclose(mesh_emb, single_emb,
                                 atol=1e-5, rtol=1e-5))

    audited = False
    if audit:
        from code_intelligence_tpu.analysis import runtime as audit_rt

        with audit_rt.recompile_guard(fn="slots.step_ragged_mesh",
                                      budget=0), \
                audit_rt.no_implicit_transfers():
            sharded.embed_ids(ids)
        audited = True

    def best_of(fn) -> float:
        best = float("inf")
        for _ in range(max(reps, 1)):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    single_dt = best_of(
        lambda: engine.embed_ids_batch(ids, scheduler="ragged"))
    mesh_dt = best_of(lambda: sharded.embed_ids(ids))

    msize = serve_shard.mesh_size(mesh)
    per_dev = sharded.step_cost_analysis()["flops"]
    total_flops = engine.slot_scheduler(
        ragged=True).step_cost_analysis()["flops"]
    flops_balance = per_dev * msize / max(total_flops, 1e-9)
    # --mesh off ⇒ bitwise-identical to before any mesh machinery ran
    again = engine.embed_ids_batch(ids, scheduler="ragged")
    mesh_off_bitwise = bool(np.array_equal(again, single_emb))
    return {
        "n_docs": len(ids),
        "total_tokens": total_tokens,
        "page_len": sharded.page_len,
        "mesh": {str(k): int(v) for k, v in dict(mesh.shape).items()},
        "mesh_size": msize,
        "single": {
            "docs_per_sec": round(len(ids) / max(single_dt, 1e-9), 1),
            "tokens_per_sec": round(
                total_tokens / max(single_dt, 1e-9), 1),
        },
        "mesh_side": {
            "docs_per_sec": round(len(ids) / max(mesh_dt, 1e-9), 1),
            "tokens_per_sec": round(total_tokens / max(mesh_dt, 1e-9), 1),
        },
        "mesh_speedup": round(
            max(single_dt, 1e-9) / max(mesh_dt, 1e-9), 2),
        "parity_max_abs_diff": parity,
        "parity_ok": parity_ok,
        "audited": audited,
        "mesh_compiled_step_shapes": sharded.compiled_step_shapes(),
        "step_flops_per_device": per_dev,
        "step_flops_total": total_flops,
        "flops_balance": round(flops_balance, 4),
        "flops_balance_ok": bool(0.0 < flops_balance <= 1.2),
        "mesh_off_bitwise_equal": mesh_off_bitwise,
        "wasted_lane_fraction_by_shard": [
            round(sharded.shard_wasted_lane_fraction(k), 4)
            for k in range(sharded.n_data_shards)],
        "ok": bool(parity_ok and audited
                   and 0.0 < flops_balance <= 1.2 and mesh_off_bitwise),
    }


#: the forced-CPU-mesh geometry the smoke child runs under — kept in
#: step with parallel/meshserve_check.py (its package-internal twin)
_MESH_AB_SMOKE_SPEC = "data=4,model=2"
_MESH_AB_FORCED_DEVICES = 8


def run_mesh_ab(smoke: bool = False, mesh_spec: Optional[str] = None,
                model_dir: Optional[str] = None,
                forced_child: bool = False) -> Dict:
    """The ``--mesh_ab`` CLI mode: one provenance-stamped JSON line.

    ``--smoke`` re-executes this harness in a subprocess with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (a 1-device
    CI host cannot grow devices after jax init) and runs the A/B on the
    tiny in-process engine over a real ``data=4,model=2`` CPU mesh.
    Without ``--smoke`` the A/B runs on the visible devices and REFUSES
    a 1-device host with :class:`DegenerateMeshError` — a 'mesh'
    benchmark on one device silently measures nothing.
    """
    out: Dict = {"metric": "embedding_serving_mesh_ab",
                 "unit": "docs/sec", "smoke": bool(smoke)}
    if smoke and not forced_child:
        import os
        import subprocess

        # CPU-collective-timeout flags, like the meshserve gate twin: an
        # 8-way in-process rendezvous can starve past XLA's 40s abort on
        # a loaded host
        from __graft_entry__ import COLLECTIVE_TIMEOUT_FLAGS as extra_flags

        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count="
                         f"{_MESH_AB_FORCED_DEVICES}" + extra_flags,
        }
        cmd = [sys.executable, __file__, "--mesh_ab", "--smoke",
               "--_forced_child"]
        if mesh_spec:
            cmd += ["--mesh", mesh_spec]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=900, env=env)
        lines = [l for l in (proc.stdout or "").strip().splitlines() if l]
        if proc.returncode != 0 or not lines:
            raise RuntimeError(
                f"mesh_ab smoke child rc={proc.returncode}: "
                + (proc.stderr or "")[-1000:])
        child = json.loads(lines[-1])
        child.pop("provenance", None)  # the parent stamps the one line
        child.pop("measured_at", None)
        out.update(child)
        out["forced_devices"] = _MESH_AB_FORCED_DEVICES
        return out

    import jax

    from code_intelligence_tpu.parallel import serve_shard

    serve_shard.ensure_multi_device(len(jax.devices()), smoke=smoke)
    spec = mesh_spec or (_MESH_AB_SMOKE_SPEC if smoke else "data,model")
    mesh = serve_shard.build_serve_mesh(spec)
    if smoke or not model_dir:
        if not smoke and not model_dir:
            raise ValueError("--mesh_ab without --smoke requires "
                             "--model_dir (the serving artifact)")
        engine = make_smoke_engine()
    else:
        from code_intelligence_tpu.inference import InferenceEngine

        engine = InferenceEngine.from_export(model_dir)
    out["mesh_ab"] = bench_mesh_ab(engine, mesh)
    out["value"] = out["mesh_ab"]["mesh_side"]["docs_per_sec"]
    out["ok"] = out["mesh_ab"]["ok"]
    out["platform"] = jax.devices()[0].platform
    return out


def workload_stats(issues: List[Dict[str, str]]) -> Dict:
    """Realized (not parameterized) duplication of a workload — the
    number a cache A/B can honestly be judged against."""
    uniq = {(d["title"], d["body"]) for d in issues}
    return {
        "n_docs": len(issues),
        "n_unique": len(uniq),
        "dup_ratio": round(len(issues) / max(len(uniq), 1), 2),
    }


def bench_engine(engine, issues: List[Dict[str, str]],
                 n_single: int = 100) -> Dict:
    # Warm by running the measurement set once unmeasured: that compiles
    # every (batch, bucket) shape AND every chunk/remainder combination the
    # workload can hit, so the timed pass measures steady state, not XLA.
    for d in issues[:n_single]:
        engine.embed_issue(d["title"], d["body"])
    singles = []
    for d in issues[:n_single]:
        t0 = time.perf_counter()
        engine.embed_issue(d["title"], d["body"])
        singles.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    emb = engine.embed_issues(issues)
    bulk_dt = time.perf_counter() - t0
    return {
        "single": _percentiles(singles),
        "bulk_docs_per_sec": round(len(issues) / bulk_dt, 1),
        "bulk_n_docs": len(issues),
        "embed_dim": int(emb.shape[1]),
    }


def bench_scheduler_ab(engine, issues: List[Dict[str, str]],
                       window: Optional[int] = None) -> Dict:
    """Continuous-slot vs group-synchronous serve throughput.

    Both sides see the SAME documents in the SAME arrival order. The
    group side embeds them one micro-batch window at a time (what the
    group-synchronous MicroBatcher does); the slot side streams the whole
    arrival sequence through the persistent slot step with per-document
    completion and immediate refill. Also pins numerical parity between
    the two paths (atol 1e-5).
    """
    from code_intelligence_tpu.text import build_issue_text

    W = window or engine.batch_size
    ids = [engine.numericalize(
        build_issue_text(d.get("title", ""), d.get("body", "")))
        for d in issues]

    def run_groups():
        outs = []
        for i in range(0, len(ids), W):
            outs.append(engine.embed_ids_batch(ids[i:i + W],
                                               scheduler="groups"))
        return np.concatenate(outs) if outs else np.zeros((0, engine.embed_dim))

    def run_slots():
        return engine.embed_ids_batch(ids, scheduler="slots")

    # warm both paths: compiles every shape each can hit on this workload
    g_emb = run_groups()
    s_emb = run_slots()
    parity = float(np.max(np.abs(g_emb - s_emb))) if len(ids) else 0.0

    def best_of(fn, reps: int = 3) -> float:
        # min over reps: the noise-robust estimator on a contended host
        # (a single scheduler hiccup mid-run otherwise decides the A/B)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    groups_dt = best_of(run_groups)
    slots_dt = best_of(run_slots)

    sched = engine.slot_scheduler()
    return {
        "window": W,
        "n_docs": len(ids),
        "groups_docs_per_sec": round(len(ids) / max(groups_dt, 1e-9), 1),
        "slots_docs_per_sec": round(len(ids) / max(slots_dt, 1e-9), 1),
        "slots_speedup": round(max(groups_dt, 1e-9) / max(slots_dt, 1e-9), 2),
        "slot_chunk_len": sched.chunk_len,
        "slot_compiled_step_shapes": sched.compiled_step_shapes(),
        "parity_max_abs_diff": parity,
    }


def bench_cache_ab(engine, issues: List[Dict[str, str]],
                   audit: bool = True, reps: int = 3) -> Dict:
    """Cached vs uncached serve on the SAME workload in the SAME arrival
    order — the content-addressed-cache win (serving/embed_cache.py),
    measured, not assumed. Three honesty pins ride the measurement:

    * device-pass accounting: the cached side must embed EXACTLY the
      unique documents (every duplicate is a cache hit; a single extra
      pass means the key or the LRU is broken),
    * bitwise parity: a cached response must be byte-identical to the
      uncached response for the same document and engine version — a
      cache that changes answers is not a cache,
    * auditor-clean steady state: the cached pass (post-warmup) runs
      under ``no_implicit_transfers()`` + ``recompile_guard(budget=0)``
      — the cache must add zero host syncs and zero recompiles to the
      slot loop it wraps.
    """
    from code_intelligence_tpu.serving.embed_cache import (
        EmbedCache, cached_embed, request_key)

    device_docs = [0]

    def embed_fn(eng, title, body):
        device_docs[0] += 1
        return eng.embed_issues([{"title": title, "body": body}],
                                scheduler="slots")[0]

    stats = workload_stats(issues)
    # the cache keys on TOKEN content: two texts that tokenize
    # identically are one document to the device (on the smoke engine's
    # tiny vocab that collapses harder than raw text — report both
    # counts so the device-pass pin is judged against the right one)
    seen = set()
    uniques = []
    for d in issues:
        k = request_key(engine, d["title"], d["body"])
        if k not in seen:
            seen.add(k)
            uniques.append(d)
    stats["n_unique_content"] = len(uniques)
    # warm: compile every shape the workload can hit, so BOTH timed
    # passes measure steady state (XLA compile time is not a cache win)
    for d in uniques:
        embed_fn(engine, d["title"], d["body"])

    def best_of(fn):
        """(best_dt, last_rows, per_rep_device_passes) — min over reps
        is the noise-robust estimator on a contended host (the same
        convention as the scheduler A/B: one hiccup must not decide)."""
        best, rows, passes = float("inf"), None, []
        for _ in range(max(reps, 1)):
            device_docs[0] = 0
            t0 = time.perf_counter()
            rows = fn()
            best = min(best, time.perf_counter() - t0)
            passes.append(device_docs[0])
        return best, rows, passes

    uncached_dt, uncached_rows, uncached_per_rep = best_of(
        lambda: [embed_fn(engine, d["title"], d["body"]) for d in issues])

    caches = []

    def cached_pass():
        # a FRESH cache per rep: every rep measures the same first-sight
        # workload (a warm rep would measure the all-hit steady state
        # and flatter the ratio)
        cache = EmbedCache()
        caches.append(cache)
        return [cached_embed(cache, engine, d["title"], d["body"],
                             embed_fn)[0] for d in issues]

    if audit:
        from code_intelligence_tpu.analysis import runtime as audit_rt

        with audit_rt.recompile_guard(fn="slots.step", budget=0), \
                audit_rt.no_implicit_transfers():
            cached_dt, cached_rows, cached_per_rep = best_of(cached_pass)
    else:
        cached_dt, cached_rows, cached_per_rep = best_of(cached_pass)
    cache = caches[-1]
    uncached_passes = max(uncached_per_rep)
    cached_passes = max(cached_per_rep)

    bitwise_equal = all(
        np.array_equal(a, b) for a, b in zip(uncached_rows, cached_rows))
    return {
        **stats,
        "uncached_docs_per_sec": round(len(issues) / max(uncached_dt, 1e-9), 1),
        "cached_docs_per_sec": round(len(issues) / max(cached_dt, 1e-9), 1),
        "cache_speedup": round(max(uncached_dt, 1e-9) / max(cached_dt, 1e-9), 2),
        "uncached_device_passes": uncached_passes,
        "cached_device_passes": cached_passes,
        # the acceptance pin: every duplicate served without the device
        "device_passes_equal_unique": (
            cached_passes == stats["n_unique_content"]),
        "bitwise_equal": bitwise_equal,
        "audited": audit,
        "cache_stats": {k: cache.stats()[k]
                        for k in ("hits", "misses", "coalesced", "bytes")},
    }


def traced_breakdown(engine, issues: List[Dict[str, str]],
                     scheduler: str = "slots") -> Dict[str, Dict[str, float]]:
    """Per-stage latency attribution: run the workload once with one trace
    per document and aggregate span durations by stage name (tokenize /
    slot queue-wait / device steps / pool emit). Runs OUTSIDE the timed
    A/B passes, so the reported docs/sec numbers are never affected by
    the tracing pass itself."""
    from code_intelligence_tpu.utils import tracing

    # max_live must cover the whole workload: every document's root is
    # open at once, and live-trace eviction would silently truncate the
    # breakdown to the last max_live documents
    tracer = tracing.Tracer(sample_rate=1.0, max_traces=len(issues) + 8,
                            slow_threshold_s=float("inf"),
                            max_live=len(issues) + 8)
    # explicit start/end (not context managers): every document's root is
    # open at once while the scheduler has them all in flight
    roots = [tracer.start_span("request", doc=i) for i in range(len(issues))]
    engine.embed_issues(issues, scheduler=scheduler,
                        ctxs=[r.context for r in roots])
    for r in roots:
        r.end()
    return tracing.stage_breakdown(tracer.traces())


def _http_round(port: int, issue: Dict[str, str], embed_dim: int) -> float:
    body = json.dumps(issue).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/text", data=body,
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=60) as resp:
        raw = resp.read()
    dt = time.perf_counter() - t0
    vec = np.frombuffer(raw, dtype="<f4")  # the reference's wire contract
    if vec.shape[0] != embed_dim:
        raise RuntimeError(f"wire contract violated: {vec.shape} != {embed_dim}")
    return dt


def bench_http(engine, issues: List[Dict[str, str]], embed_dim: int,
               concurrency: int = 8, per_client: int = 12,
               batch_window_ms: Optional[float] = 4.0,
               scheduler: str = "slots") -> Dict:
    from code_intelligence_tpu.serving.server import make_server

    # loopback-only: the harness is its own client; no external listener
    server = make_server(engine, host="127.0.0.1", port=0,
                         batch_window_ms=batch_window_ms,
                         scheduler=scheduler)
    port = server.server_address[1]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        _http_round(port, issues[0], embed_dim)  # warm the serve path
        lat: List[float] = []
        lock = threading.Lock()
        errors: List[str] = []

        def client(cid: int):
            try:
                mine = []
                for k in range(per_client):
                    mine.append(_http_round(
                        port, issues[(cid * per_client + k) % len(issues)],
                        embed_dim))
                with lock:
                    lat.extend(mine)
            except Exception as e:  # surface, don't hang the join
                with lock:
                    errors.append(str(e)[:200])

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(concurrency)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        if errors:
            raise RuntimeError(f"{len(errors)} client errors: {errors[0]}")
        return {
            **_percentiles(lat),
            **_digest_line(lat, "http_e2e"),
            "throughput_rps": round(len(lat) / wall, 1),
            "concurrency": concurrency,
            "n_requests": len(lat),
            "batch_window_ms": batch_window_ms,
            "scheduler": scheduler,
        }
    finally:
        server.shutdown()
        server.server_close()


def run(engine, n_issues: int = 256, concurrency: int = 8,
        per_client: int = 12, pallas_engine=None,
        scheduler: str = "slots", trace: bool = False,
        zipf_a: Optional[float] = None) -> Dict:
    issues = make_issues(n_issues)
    out: Dict = {"metric": "embedding_serving_latency", "unit": "ms",
                 "scheduler": scheduler}
    if zipf_a is not None:
        # cache A/B runs on ITS OWN Zipf-duplicated workload; the
        # latency/throughput numbers above keep the all-unique one so
        # the series stays comparable across runs with/without --zipf_a
        zipf_issues = make_issues(n_issues, zipf_a=zipf_a)
        out["workload"] = {"zipf_a": zipf_a, **workload_stats(zipf_issues)}
        out["cache_ab"] = bench_cache_ab(engine, zipf_issues)
    eng = bench_engine(engine, issues)
    out["engine"] = eng
    if trace:
        out["trace_breakdown"] = traced_breakdown(engine, issues,
                                                  scheduler=scheduler)
    # slots-vs-groups A/B always reports BOTH docs/sec numbers, whatever
    # the serve knob selects — the bench must not silently regress to one
    # path (tests/test_bench_serving.py pins the fields)
    out["scheduler_ab"] = bench_scheduler_ab(engine, issues)
    # ragged paged scheduler vs dense slots on a Zipf mixed-length
    # workload (its OWN seeded workload): tokens/s, wasted-lane
    # fraction, AOT flops-per-token. Real runs (default n_issues=256)
    # always land on the fixed 128-doc fixture so the ratio is
    # comparable across runs; tiny test engines pay a smaller one
    out["ragged_ab"] = bench_ragged_ab(engine,
                                       n_docs=min(max(n_issues, 48), 128))
    if pallas_engine is not None:
        # serve-kernel A/B: same encoder, weights-resident Pallas cell
        try:
            out["engine_pallas"] = bench_engine(pallas_engine, issues)
            out["pallas_bulk_speedup"] = round(
                out["engine_pallas"]["bulk_docs_per_sec"]
                / max(eng["bulk_docs_per_sec"], 1e-9), 2)
        except Exception as e:
            out["engine_pallas_error"] = str(e).replace("\n", " | ")[:300]
    out["http_batched"] = bench_http(
        engine, issues, eng["embed_dim"], concurrency, per_client,
        batch_window_ms=4.0, scheduler=scheduler)
    out["http_unbatched"] = bench_http(
        engine, issues, eng["embed_dim"], concurrency, per_client,
        batch_window_ms=None, scheduler=scheduler)
    out["value"] = out["http_batched"]["p50_ms"]
    # hoist the batched-path digest to the top level: the shape
    # perfwatch's digests_of() reads from a bench baseline
    out["latency_digest"] = out["http_batched"]["latency_digest"]
    out["latency_digest_ms"] = out["http_batched"]["latency_digest_ms"]
    out["latency_kind"] = out["http_batched"]["latency_kind"]
    if out["http_unbatched"]["throughput_rps"] > 0:
        out["microbatch_throughput_ratio"] = round(
            out["http_batched"]["throughput_rps"]
            / out["http_unbatched"]["throughput_rps"], 2)
    return out


class _StubEngine:
    """Device-free engine stand-in for the shed-check: a fixed per-call
    latency makes overload reproducible without jax or a model artifact
    (shed requests must never reach the device anyway — that's the
    property under test)."""

    embed_dim = 8

    def __init__(self, delay_s: float = 0.05):
        self.delay_s = delay_s
        self.calls = 0

    def _check_scheduler(self, scheduler: str) -> str:
        return scheduler

    def embed_issues(self, docs, scheduler=None, ctxs=None):
        self.calls += 1
        time.sleep(self.delay_s)
        return np.zeros((len(docs), self.embed_dim), np.float32)


def run_shed_check(concurrency: int = 12, per_client: int = 2,
                   max_pending: int = 4, engine_delay_s: float = 0.05) -> Dict:
    """Overload-behavior smoke: fire ``concurrency`` clients at a server
    admitting at most ``max_pending`` — the excess must come back as 429
    with a ``Retry-After`` hint (not queue unboundedly onto the device
    lock), every admitted request must succeed with bounded latency, and
    the shed counter must land on /metrics."""
    from code_intelligence_tpu.serving.server import make_server

    engine = _StubEngine(delay_s=engine_delay_s)
    server = make_server(engine, host="127.0.0.1", port=0,
                         scheduler="groups", max_pending=max_pending,
                         shed_retry_after_s=0.05)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    admitted: List[float] = []
    shed = 0
    retry_after_seen = 0
    errors: List[str] = []
    lock = threading.Lock()

    def client(cid: int):
        nonlocal shed, retry_after_seen
        for k in range(per_client):
            body = json.dumps({"title": f"c{cid}", "body": f"r{k}"}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/text", data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
                with lock:
                    admitted.append(time.perf_counter() - t0)
            except urllib.error.HTTPError as e:
                e.read()
                with lock:
                    if e.code == 429:
                        shed += 1
                        if e.headers.get("Retry-After"):
                            retry_after_seen += 1
                    else:
                        errors.append(f"HTTP {e.code}")
            except Exception as e:  # noqa: BLE001 — keep the report shape
                with lock:
                    errors.append(str(e)[:200])

    try:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        metrics = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    finally:
        server.shutdown()
        server.server_close()

    pct = _percentiles(admitted) if admitted else {}
    # admitted latency stays bounded by the admission depth: every
    # admitted request waits at most ~max_pending device programs (wide
    # 8x margin + slack for scheduling noise on a loaded CI host — the
    # un-shed failure mode this guards against is ~concurrency*per_client
    # requests deep, an order of magnitude past this bound)
    latency_bound_ms = max_pending * engine_delay_s * 1e3 * 8 + 500.0
    ok = (shed > 0 and not errors
          and retry_after_seen == shed
          and engine.calls == len(admitted)
          and "embedding_shed_total" in metrics
          and bool(admitted) and pct["p99_ms"] <= latency_bound_ms)
    return {
        "metric": "embedding_serving_shed_check",
        "value": pct.get("p99_ms"),
        "unit": "ms",
        "ok": ok,
        "admitted": len(admitted),
        "shed": shed,
        "retry_after_seen": retry_after_seen,
        "engine_calls": engine.calls,
        "max_pending": max_pending,
        "latency_bound_ms": round(latency_bound_ms, 1),
        "admitted_latency": pct,
        "errors": errors[:3],
    }


def bench_fleet_ab(n_replicas: int = 3, n_requests: int = 240,
                   concurrency: int = 6, zipf_a: float = 1.3,
                   engine_delay_ms: float = 15.0, hedge_ms: float = 0.0,
                   model_dir: Optional[str] = None,
                   seed: int = 0) -> Dict:
    """Fleet A/B: the SAME Zipf workload against 1 replica vs
    ``n_replicas`` replicas behind the fleet router
    (serving/fleet/, RUNBOOK §24). Reports per-side docs/sec and
    approx tokens/sec plus the router's shed and hedge rates — the
    horizontal-scaling twin of the slots-vs-groups A/B.

    Device-free by default: replicas are supervisor-spawned fake
    engines (the real serving stack over the deterministic SmokeEngine,
    ``engine_delay_ms`` standing in for device time so scaling is
    measurable); pass ``model_dir`` to run real engine replicas."""
    from code_intelligence_tpu.serving.fleet.router import make_router
    from code_intelligence_tpu.serving.fleet.supervisor import (
        FleetSupervisor)

    issues = make_issues(n_requests, seed=seed, zipf_a=zipf_a)
    token_estimate = sum(
        len((d["title"] + " " + d["body"]).split()) for d in issues)

    def measure(n: int) -> Dict:
        sup = FleetSupervisor(
            n=n, engine="fake" if model_dir is None else "real",
            model_dir=model_dir, engine_delay_ms=engine_delay_ms)
        router = None
        try:
            sup.start()
            if not sup.wait_ready(60.0):
                raise RuntimeError(f"{n}-replica fleet never became ready")
            # admission sized to stay out of the way: the A/B measures
            # routing + replica scaling, not the shed path (shed/hedge
            # rates are still reported honestly from /metrics)
            router = make_router(
                sup.member_urls(), host="127.0.0.1", port=0,
                rate_per_s=10_000.0, burst=4096, hedge_ms=hedge_ms)
            port = router.server_address[1]
            threading.Thread(target=router.serve_forever,
                             daemon=True).start()
            latencies: List[float] = []
            # per-member request latencies, keyed by the router's
            # X-Fleet-Member response header: each replica gets its own
            # digest in the emitted line, so a fleet bench run is
            # perfwatch-diffable PER REPLICA (utils/fleetwatch.py) —
            # a straggler is named, not averaged away
            member_latencies: Dict[str, List[float]] = {}
            shed = 0
            errors: List[str] = []
            lock = threading.Lock()

            def client(cid: int):
                nonlocal shed
                for i in range(cid, len(issues), concurrency):
                    body = json.dumps(issues[i]).encode()
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{port}/text", data=body,
                        headers={"Content-Type": "application/json"})
                    t0 = time.perf_counter()
                    try:
                        with urllib.request.urlopen(req, timeout=120) \
                                as resp:
                            resp.read()
                            member = resp.headers.get("X-Fleet-Member")
                        elapsed = time.perf_counter() - t0
                        with lock:
                            latencies.append(elapsed)
                            if member:
                                member_latencies.setdefault(
                                    member, []).append(elapsed)
                    except urllib.error.HTTPError as e:
                        e.read()
                        with lock:
                            if e.code == 429:
                                shed += 1
                            else:
                                errors.append(f"HTTP {e.code}")
                    except Exception as e:  # noqa: BLE001 — report shape
                        with lock:
                            errors.append(str(e)[:200])

            t_start = time.perf_counter()
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(concurrency)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t_start
            mtext = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics",
                timeout=10).read().decode()
            hedges = {"fired": 0, "won": 0, "lost": 0}
            for line in mtext.splitlines():
                for k in hedges:
                    if line.startswith(
                            f'fleet_hedges_total{{outcome="{k}"}}'):
                        hedges[k] = int(float(line.rsplit(" ", 1)[1]))
            done = len(latencies)
            side = {
                "replicas": n,
                "requests_ok": done,
                "elapsed_s": round(elapsed, 3),
                "docs_per_sec": round(done / elapsed, 2) if elapsed else 0,
                "tokens_per_sec": round(
                    token_estimate * (done / max(len(issues), 1))
                    / elapsed, 1) if elapsed else 0,
                "shed": shed,
                "shed_rate": round(shed / max(len(issues), 1), 4),
                "hedges": hedges,
                "hedge_rate": round(
                    hedges["fired"] / max(len(issues), 1), 4),
                "errors": errors[:3],
                "n_errors": len(errors),
            }
            if latencies:
                side.update(_percentiles(latencies))
                side.update(_digest_line(latencies, "http_e2e"))
                member_digests = {}
                member_digests_ms = {}
                for member, samples in sorted(member_latencies.items()):
                    d = QuantileDigest()
                    d.add_many(samples)
                    member_digests[member] = d.to_dict()
                    member_digests_ms[member] = d.summary_ms()
                side["member_latency_digests"] = member_digests
                side["member_latency_digest_ms"] = member_digests_ms
            return side
        finally:
            if router is not None:
                router.shutdown()
                router.server_close()
            sup.stop_all()

    single = measure(1)
    multi = measure(n_replicas)
    return {
        "workload": {"n_requests": n_requests, "zipf_a": zipf_a,
                     **workload_stats(issues)},
        "engine_mode": "fake" if model_dir is None else "real",
        "engine_delay_ms": engine_delay_ms,
        "hedge_ms": hedge_ms,
        "single": single,
        "fleet": multi,
        "fleet_speedup": round(
            multi["docs_per_sec"] / max(single["docs_per_sec"], 1e-9), 2),
        "client_errors": single["n_errors"] + multi["n_errors"],
    }


def run_fleet_ab(smoke: bool = False, n_replicas: int = 3,
                 model_dir: Optional[str] = None,
                 zipf_a: Optional[float] = None) -> Dict:
    """The ``--fleet_ab`` CLI mode: one provenance-stamped JSON line.
    ``--smoke`` shrinks the workload and replica count (device-free
    either way when no ``model_dir`` is given)."""
    out: Dict = {"metric": "embedding_serving_fleet_ab",
                 "unit": "docs/sec", "smoke": bool(smoke)}
    kw: Dict = {"zipf_a": zipf_a if zipf_a is not None else 1.3}
    if smoke:
        # sleep-dominated fake device time: the smoke must measure the
        # ROUTING layer's scaling, which survives a contended CI host,
        # not raw host CPU throughput (which doesn't)
        kw.update(n_replicas=min(n_replicas, 2), n_requests=60,
                  concurrency=6, engine_delay_ms=25.0)
    else:
        kw.update(n_replicas=n_replicas)
    out.update(bench_fleet_ab(model_dir=model_dir, **kw))
    out["value"] = out["fleet"]["docs_per_sec"]
    # top-level digest = the FLEET side (the number this line is about),
    # same convention as run() promoting http_batched's digest
    for k in ("latency_digest", "latency_digest_ms", "latency_kind"):
        if k in out["fleet"]:
            out[k] = out["fleet"][k]
    return out


def bench_traffic(scenario: str, n_replicas: int = 2,
                  base_rate_per_s: float = 30.0, duration_s: float = 20.0,
                  seed: int = 0, engine_delay_ms: float = 10.0,
                  model_dir: Optional[str] = None) -> Dict:
    """Open-loop replay of a seeded ``serving/traffic.py`` scenario
    against a real supervisor-spawned fleet behind the router
    (RUNBOOK §30). Unlike the closed-loop ``--fleet_ab`` clients,
    arrivals here are scheduled by the seed — a flash crowd keeps
    arriving whether or not the fleet keeps up, so shed/overflow
    counts are honest overload measurements.

    Admission is sized at ~2x the scenario's base rate: diurnal peaks
    (1.7x) ride under it, a 10x flash crowd sheds visibly, and the
    retry-storm herd gets real 429 + Retry-After hints to re-arrive
    on. Device-free with fake replicas unless ``model_dir`` is given."""
    from code_intelligence_tpu.serving.fleet.router import make_router
    from code_intelligence_tpu.serving.fleet.supervisor import (
        FleetSupervisor)
    from code_intelligence_tpu.serving.traffic import (
        OpenLoopRunner, TrafficSchedule)

    sched = TrafficSchedule(scenario, base_rate_per_s=base_rate_per_s,
                            duration_s=duration_s, seed=seed)
    effective_base = (sched.base_rate_per_s
                      * sched.scenario.rate_scale)
    sup = FleetSupervisor(
        n=n_replicas, engine="fake" if model_dir is None else "real",
        model_dir=model_dir, engine_delay_ms=engine_delay_ms)
    router = None
    try:
        sup.start()
        if not sup.wait_ready(60.0):
            raise RuntimeError(
                f"{n_replicas}-replica fleet never became ready")
        # retry_storm needs real sheds to seed the herd: admit UNDER
        # the offered rate so clients hit 429 + Retry-After and
        # re-arrive synchronized. Every other scenario gets 2x
        # headroom (diurnal's 1.7x peak rides under; a 10x flash
        # crowd sheds visibly anyway).
        admit_scale = 0.6 if sched.scenario.retry_on_shed else 2.0
        router = make_router(
            sup.member_urls(), host="127.0.0.1", port=0,
            rate_per_s=max(admit_scale * effective_base, 5.0),
            burst=max(int(2.0 * admit_scale * effective_base), 8))
        port = router.server_address[1]
        threading.Thread(target=router.serve_forever,
                         daemon=True).start()

        def send(doc: Dict[str, str]) -> Dict:
            body = json.dumps(doc).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/text", data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
                    return {"ok": True, "status": resp.status}
            except urllib.error.HTTPError as e:
                e.read()
                ra = e.headers.get("Retry-After")
                return {"ok": False, "status": e.code,
                        "retry_after_s": float(ra) if ra else None}
            except Exception as e:
                return {"ok": False, "status": 0,
                        "error": f"{type(e).__name__}: {e}"[:200]}

        runner = OpenLoopRunner(sched, send)
        side = runner.run()
        side["n_replicas"] = n_replicas
        side["engine_mode"] = "fake" if model_dir is None else "real"
        side["engine_delay_ms"] = engine_delay_ms
        return side
    finally:
        if router is not None:
            router.shutdown()
            router.server_close()
        sup.stop_all()


def run_traffic(scenario: str, smoke: bool = False, n_replicas: int = 2,
                model_dir: Optional[str] = None, seed: int = 0) -> Dict:
    """The ``--traffic <scenario>`` CLI mode: one provenance-stamped
    JSON line whose ``schedule`` block (scenario/seed/rates) is enough
    to regenerate the exact offered load. ``--smoke`` compresses the
    replay to a few seconds of wall clock."""
    out: Dict = {"metric": "embedding_serving_traffic", "unit": "req/sec",
                 "smoke": bool(smoke), "scenario": scenario}
    kw: Dict = {"seed": seed}
    if smoke:
        # compressed replay: same arrival PROCESS, short horizon — the
        # smoke proves the open-loop plumbing (scheduled dispatch, shed
        # accounting, retry re-arrival), not steady-state capacity
        kw.update(n_replicas=min(n_replicas, 2), base_rate_per_s=25.0,
                  duration_s=8.0, engine_delay_ms=5.0)
    else:
        kw.update(n_replicas=n_replicas, base_rate_per_s=30.0,
                  duration_s=30.0)
    out.update(bench_traffic(scenario, model_dir=model_dir, **kw))
    out["value"] = out["achieved_rate_per_s"]
    return out


def make_smoke_engine(batch_size: int = 8, emb_sz: int = 32, n_hid: int = 96,
                      mesh=None):
    """Small randomly-initialized engine for the no-artifact smoke path.

    Sized so the forward's compute, not per-dispatch overhead, dominates
    — the regime the flagship encoder serves in. (At toy dims the A/B
    inverts: the slot path's many narrow steps pay more fixed dispatch
    cost than the group path's few wide ones, which measures the host,
    not the scheduler.)"""
    import jax

    from code_intelligence_tpu.inference import InferenceEngine
    from code_intelligence_tpu.models import (
        AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states)
    from code_intelligence_tpu.text import SPECIALS, Vocab

    cfg = AWDLSTMConfig(vocab_size=200, emb_sz=emb_sz, n_hid=n_hid, n_layers=2)
    enc = AWDLSTMEncoder(cfg)
    params = enc.init(
        {"params": jax.random.PRNGKey(0)},
        np.zeros((1, 4), np.int32), init_lstm_states(cfg, 1))["params"]
    vocab = Vocab(SPECIALS + [f"w{i}" for i in range(200 - len(SPECIALS))])
    return InferenceEngine(params, cfg, vocab, batch_size=batch_size,
                           mesh=mesh)


def run_smoke(n_issues: int = 64, batch_size: int = 8,
              trace: bool = False, zipf_a: Optional[float] = None,
              mesh=None) -> Dict:
    """Scheduler A/B on the tiny engine — the CI-pinned smoke report."""
    engine = make_smoke_engine(batch_size, mesh=mesh)
    issues = make_issues(n_issues)
    out: Dict = {"metric": "embedding_serving_scheduler_ab", "unit": "docs/sec",
                 "smoke": True, "scheduler": "both"}
    out["scheduler_ab"] = bench_scheduler_ab(engine, issues)
    out["value"] = out["scheduler_ab"]["slots_docs_per_sec"]
    # ragged mixed-length A/B: parity + flops-per-token are CPU-provable,
    # so the smoke line carries the full ragged acceptance evidence. A
    # FIXED 64-doc seeded workload (not n_issues): the flops ratio is a
    # pinned acceptance number and must not drift with the smoke size
    out["ragged_ab"] = bench_ragged_ab(engine, n_docs=64)
    # per-request single-doc latencies into the shared digest format:
    # the smoke line is perfwatch-diffable like the full run's
    sample = issues[:32]
    for d in sample:  # warm the single-doc shapes out of the timing
        engine.embed_issue(d["title"], d["body"])
    singles = []
    for d in sample:
        t0 = time.perf_counter()
        engine.embed_issue(d["title"], d["body"])
        singles.append(time.perf_counter() - t0)
    out.update(_digest_line(singles, "engine_single_doc"))
    if zipf_a is not None:
        zipf_issues = make_issues(n_issues, zipf_a=zipf_a)
        out["workload"] = {"zipf_a": zipf_a, **workload_stats(zipf_issues)}
        out["cache_ab"] = bench_cache_ab(engine, zipf_issues)
    if trace:
        # separate pass AFTER the timed A/B: tracing must not perturb the
        # reported docs/sec (acceptance: < 5% shift with --trace on)
        out["trace_breakdown"] = traced_breakdown(engine, issues)
    return out


def main(argv=None) -> Dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model_dir", default=None,
                   help="export_encoder directory (the serving artifact); "
                        "not needed with --smoke")
    p.add_argument("--n_issues", type=int, default=256)
    p.add_argument("--concurrency", type=int, default=8)
    p.add_argument("--per_client", type=int, default=12)
    p.add_argument("--batch_size", type=int, default=32)
    p.add_argument("--scheduler", choices=("slots", "groups", "ragged"),
                   default="slots",
                   help="batching policy for the HTTP serve path (the "
                        "slots-vs-groups and ragged A/Bs always run and "
                        "report all sides; see RUNBOOK §23 for --scheduler "
                        "ragged)")
    p.add_argument("--zipf_a", type=float, default=None,
                   help="Zipf rank exponent (> 1) for a seeded duplicate-"
                        "heavy workload — enables the cached-vs-uncached "
                        "A/B (serving/embed_cache.py, RUNBOOK §21) and "
                        "reports the REALIZED duplication ratio; omit for "
                        "the historical all-unique workload")
    p.add_argument("--smoke", action="store_true",
                   help="tiny in-process engine, scheduler A/B only — no "
                        "model artifact or HTTP layer")
    p.add_argument("--shed-check", dest="shed_check", action="store_true",
                   help="overload-behavior smoke: assert excess load is "
                        "shed with 429 + Retry-After (bounded admitted "
                        "latency, zero device calls for shed requests); "
                        "device-free, no model artifact needed")
    p.add_argument("--fleet_ab", action="store_true",
                   help="fleet A/B: 1 replica vs --fleet_replicas behind "
                        "the fleet router on a Zipf workload (docs/s + "
                        "tokens/s + shed/hedge rates; RUNBOOK §24). "
                        "Device-free with fake replicas by default; "
                        "combine with --model_dir for real engines and "
                        "--smoke for the tiny CI variant")
    p.add_argument("--fleet_replicas", type=int, default=3,
                   help="replica count for the fleet side of --fleet_ab")
    p.add_argument("--traffic", default=None,
                   choices=("diurnal", "flash_crowd", "retry_storm",
                            "slow_drip"),
                   help="open-loop seeded traffic replay "
                        "(serving/traffic.py, RUNBOOK §30) against a "
                        "fake-engine fleet behind the router: arrivals "
                        "fire on the seeded schedule whether or not the "
                        "fleet keeps up, so shed/overflow counts are "
                        "honest. Device-free; combine with --smoke for "
                        "a compressed replay and --seed to vary the "
                        "schedule")
    p.add_argument("--seed", type=int, default=0,
                   help="schedule seed for --traffic (same seed, same "
                        "scenario -> byte-identical offered load)")
    p.add_argument("--mesh", default=None,
                   help="serve-mesh spec, e.g. 'data,model' or "
                        "'data=4,model=2' (RUNBOOK §26): shards the "
                        "serve engine's step for the standard run, and "
                        "names the mesh geometry for --mesh_ab. REFUSED "
                        "(DegenerateMeshError) on a 1-device host "
                        "without --smoke — a 1-device 'mesh' benchmark "
                        "measures nothing")
    p.add_argument("--mesh_ab", action="store_true",
                   help="mesh A/B: the sharded ragged step vs the "
                        "single-chip step on the same Zipf mixed-length "
                        "workload (parity + audited steady state + "
                        "per-device AOT flops balance + --mesh-off "
                        "bitwise pin; RUNBOOK §26). With --smoke, runs "
                        "in a forced 8-CPU-device subprocess — no "
                        "multi-chip host or artifact needed")
    p.add_argument("--precision_ab", action="store_true",
                   help="precision A/B: the int8 quantize-at-load engine "
                        "vs f32 over the SAME params on the same Zipf "
                        "mixed-length ragged workload (docs/s + tokens/s "
                        "+ the >=3x weight-footprint ratio + parity band "
                        "+ audited steady state; RUNBOOK §28). Combine "
                        "with --smoke for the tiny in-process pair or "
                        "--model_dir for a real export")
    p.add_argument("--_forced_child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--trace", action="store_true",
                   help="per-stage latency breakdown (tokenize / slot "
                        "queue-wait / device steps / pool emit): table on "
                        "stderr, trace_breakdown in the JSON line")
    args = p.parse_args(argv)

    if args.shed_check:
        # device-free: runs before any jax import so CI can smoke the
        # overload contract without touching a backend
        try:
            out = run_shed_check()
        except Exception as e:
            out = {"metric": "embedding_serving_shed_check", "value": None,
                   "unit": "ms", "ok": False,
                   "error": str(e).replace("\n", " | ")[:400]}
        return _finish(out)

    if args.fleet_ab:
        # also jax-free in THIS process: replicas are subprocesses (fake
        # engines by default, real ones when --model_dir is given)
        try:
            out = run_fleet_ab(smoke=args.smoke,
                               n_replicas=args.fleet_replicas,
                               model_dir=args.model_dir,
                               zipf_a=args.zipf_a)
        except Exception as e:
            out = {"metric": "embedding_serving_fleet_ab", "value": None,
                   "unit": "docs/sec", "smoke": bool(args.smoke),
                   "error": str(e).replace("\n", " | ")[:400]}
        return _finish(out)

    if args.traffic:
        # jax-free in this process like --fleet_ab: replicas are
        # subprocesses, the open-loop runner is plain threads
        try:
            out = run_traffic(args.traffic, smoke=args.smoke,
                              n_replicas=args.fleet_replicas,
                              model_dir=args.model_dir, seed=args.seed)
        except Exception as e:
            out = {"metric": "embedding_serving_traffic", "value": None,
                   "unit": "req/sec", "smoke": bool(args.smoke),
                   "scenario": args.traffic,
                   "error": str(e).replace("\n", " | ")[:400]}
        return _finish(out)

    if args.mesh_ab:
        from code_intelligence_tpu.parallel.serve_shard import (
            DegenerateMeshError)

        try:
            out = run_mesh_ab(smoke=args.smoke, mesh_spec=args.mesh,
                              model_dir=args.model_dir,
                              forced_child=args._forced_child)
        except DegenerateMeshError as e:
            # named fail-fast (never a silently degenerate benchmark):
            # the error line keeps the metric series, the exit code and
            # stderr name the refusal
            print(f"DegenerateMeshError: {e}", file=sys.stderr)
            out = {"metric": "embedding_serving_mesh_ab", "value": None,
                   "unit": "docs/sec", "smoke": bool(args.smoke),
                   "error": f"DegenerateMeshError: {e}"[:400]}
            print(json.dumps(_stamp(out)))
            sys.exit(2)
        except Exception as e:
            # "ok": False explicitly — the exit check below must never
            # default a crashed A/B to green
            out = {"metric": "embedding_serving_mesh_ab", "value": None,
                   "unit": "docs/sec", "smoke": bool(args.smoke),
                   "ok": False,
                   "error": str(e).replace("\n", " | ")[:400]}
        return _finish(out)

    import jax

    from code_intelligence_tpu.inference import InferenceEngine
    from code_intelligence_tpu.utils import devices

    devices.enable_compile_cache()

    if args.precision_ab:
        if not args.smoke:
            devices.require_tpu("bench_serving.py --precision_ab")
        try:
            out = run_precision_ab(smoke=args.smoke,
                                   model_dir=args.model_dir,
                                   batch_size=min(args.batch_size, 8)
                                   if args.smoke else args.batch_size)
            out["platform"] = jax.devices()[0].platform
        except Exception as e:
            # "ok": False explicitly — the exit check below must never
            # default a crashed A/B to green
            out = {"metric": "embedding_serving_precision_ab",
                   "value": None, "unit": "docs/sec",
                   "smoke": bool(args.smoke), "ok": False,
                   "error": str(e).replace("\n", " | ")[:400]}
        return _finish(out)

    if args.mesh and args.scheduler == "groups":
        # only the slot/ragged schedulers run the sharded step; the
        # groups path would silently serve unsharded (RUNBOOK §26)
        p.error("--mesh requires --scheduler slots or ragged (the "
                "groups path runs unsharded compiled forwards)")
    if args.mesh:
        # refuse a degenerate mesh BEFORE any engine work: --mesh on a
        # 1-device host without --smoke benchmarks nothing (RUNBOOK §26)
        from code_intelligence_tpu.parallel.serve_shard import (
            DegenerateMeshError, ensure_multi_device)

        try:
            ensure_multi_device(len(jax.devices()), smoke=args.smoke)
        except DegenerateMeshError as e:
            print(f"DegenerateMeshError: {e}", file=sys.stderr)
            out = {"metric": ("embedding_serving_scheduler_ab"
                              if args.smoke
                              else "embedding_serving_latency"),
                   "value": None,
                   "unit": "docs/sec" if args.smoke else "ms",
                   "smoke": bool(args.smoke),
                   "error": f"DegenerateMeshError: {e}"[:400]}
            print(json.dumps(_stamp(out)))
            sys.exit(2)

    try:
        if args.smoke:
            out = run_smoke(min(args.n_issues, 64),
                            batch_size=min(args.batch_size, 8),
                            trace=args.trace, zipf_a=args.zipf_a,
                            mesh=args.mesh)
        else:
            if not args.model_dir:
                p.error("--model_dir is required without --smoke")
            # a time from the CPU backend is not a device metric: only
            # the --smoke modes (counts and parity) run without a chip
            devices.require_tpu("bench_serving.py (without --smoke)")
            engine = InferenceEngine.from_export(
                args.model_dir, batch_size=args.batch_size,
                mesh=args.mesh)
            # measure the weights-resident serve kernel alongside the
            # scan — reuse the loaded params/vocab (the artifact is
            # ~1GB at flagship scale; don't read or hold it twice)
            pallas_engine = InferenceEngine(
                engine._enc_params["params"], engine.config, engine.vocab,
                batch_size=args.batch_size, lstm_pallas=True)
            out = run(engine, args.n_issues, args.concurrency,
                      args.per_client, pallas_engine=pallas_engine,
                      scheduler=args.scheduler, trace=args.trace,
                      zipf_a=args.zipf_a)
        out["platform"] = jax.devices()[0].platform
        if args.trace and out.get("trace_breakdown"):
            # the table goes to STDERR: stdout stays exactly one JSON line
            from code_intelligence_tpu.utils.tracing import format_breakdown

            print("per-stage latency breakdown:", file=sys.stderr)
            print(format_breakdown(out["trace_breakdown"]), file=sys.stderr)
    except Exception as e:
        # keep the failure record on the SAME metric series the successful
        # run would have emitted, so dashboards see an error datapoint
        # instead of a gap (smoke and full mode report different metrics)
        if args.smoke:
            out = {"metric": "embedding_serving_scheduler_ab", "value": None,
                   "unit": "docs/sec", "smoke": True,
                   "error": str(e).replace("\n", " | ")[:400]}
        else:
            out = {"metric": "embedding_serving_latency", "value": None,
                   "unit": "ms", "error": str(e).replace("\n", " | ")[:400]}
    return _finish(out)


if __name__ == "__main__":
    main()
