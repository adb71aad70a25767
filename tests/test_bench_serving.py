"""Smoke-pin the serving benchmark harness on a tiny CPU engine."""

import jax
import numpy as np
import pytest

from code_intelligence_tpu.text import SPECIALS, Vocab
from code_intelligence_tpu.models import AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states
from code_intelligence_tpu.inference import InferenceEngine

import bench_serving


@pytest.fixture(scope="module")
def engine():
    cfg = AWDLSTMConfig(vocab_size=200, emb_sz=8, n_hid=12, n_layers=2)
    enc = AWDLSTMEncoder(cfg)
    tokens = np.zeros((1, 4), np.int32)
    params = enc.init(
        {"params": jax.random.PRNGKey(0)}, tokens, init_lstm_states(cfg, 1)
    )["params"]
    words = [f"w{i}" for i in range(200 - len(SPECIALS))]
    vocab = Vocab(SPECIALS + words)
    return InferenceEngine(params, cfg, vocab, buckets=(8, 16), batch_size=4)


def test_make_issues_deterministic_and_shaped():
    a = bench_serving.make_issues(16)
    b = bench_serving.make_issues(16)
    assert a == b
    assert all(set(d) == {"title", "body"} for d in a)
    lengths = {len(d["body"].split()) for d in a}
    assert len(lengths) > 1  # realistic length spread, not one shape


def test_run_emits_complete_report(engine):
    out = bench_serving.run(engine, n_issues=12, concurrency=2, per_client=3)
    assert out["engine"]["embed_dim"] == 3 * engine.config.emb_sz
    assert out["engine"]["bulk_docs_per_sec"] > 0
    assert out["engine"]["single"]["p50_ms"] > 0
    # the report names the serve-path scheduler so an A/B sweep's JSON
    # lines are self-describing
    assert out["scheduler"] == "slots"
    for key in ("http_batched", "http_unbatched"):
        assert out[key]["throughput_rps"] > 0
        assert out[key]["n_requests"] == 6
        assert out[key]["p95_ms"] >= out[key]["p50_ms"]
        assert out[key]["scheduler"] == "slots"
    assert out["value"] == out["http_batched"]["p50_ms"]
    assert "microbatch_throughput_ratio" in out
    # per-request latencies ride along as the SLO observatory's own
    # estimator: serialized digest + its p50/p90/p99, hoisted to the top
    # level where perfwatch's digests_of() reads a bench baseline
    assert out["latency_digest"] == out["http_batched"]["latency_digest"]
    assert out["latency_digest"]["kind"] == "ddsketch"
    assert out["latency_digest"]["count"] == 6
    assert out["latency_digest_ms"]["p99_ms"] >= \
        out["latency_digest_ms"]["p50_ms"]


def test_run_reports_both_schedulers(engine):
    # the slots-vs-groups A/B must always carry BOTH docs/sec numbers —
    # the bench can't silently regress to one path
    out = bench_serving.run(engine, n_issues=12, concurrency=1, per_client=2)
    ab = out["scheduler_ab"]
    assert ab["groups_docs_per_sec"] > 0
    assert ab["slots_docs_per_sec"] > 0
    assert ab["slots_speedup"] > 0
    # -1 = jit cache not introspectable on this jax (documented sentinel)
    assert ab["slot_compiled_step_shapes"] in (1, -1)
    assert ab["parity_max_abs_diff"] < 1e-5


def test_smoke_mode_runs_both_schedulers(capsys):
    # --smoke needs no model artifact and must emit the scheduler field +
    # both schedulers' throughput in one JSON line
    import json

    out = bench_serving.main(["--smoke", "--n_issues", "16",
                              "--batch_size", "4"])
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed == out
    assert out["smoke"] is True
    assert out["scheduler"] == "both"
    ab = out["scheduler_ab"]
    assert ab["groups_docs_per_sec"] > 0
    assert ab["slots_docs_per_sec"] > 0
    assert ab["parity_max_abs_diff"] < 1e-5
    assert out["value"] == ab["slots_docs_per_sec"]
    # every emitted line carries provenance (an error datapoint must
    # never read like a measurement)
    assert out["provenance"] == "fresh"
    assert "measured_at" in out
    # the smoke line is perfwatch-diffable: single-doc latencies in the
    # shared digest format, with the identical-estimator summary
    assert out["latency_digest"]["kind"] == "ddsketch"
    assert out["latency_digest"]["count"] == 16
    assert out["latency_digest_ms"]["count"] == 16
    from code_intelligence_tpu.utils import perfwatch

    e2e, stages = perfwatch.digests_of(out)
    assert e2e is not None and e2e["count"] == 16
    # the ragged mixed-length A/B rides the smoke line with the full
    # acceptance evidence: allclose parity, audited steady state, and
    # the flops-per-token acceptance bound on the production geometry
    # (chunk 64 / page 16 — ISSUE 9 pin: ragged ≤ 0.6× dense)
    rab = out["ragged_ab"]
    assert rab["parity_max_abs_diff"] < 1e-5
    assert rab["audited"] is True
    assert rab["chunk_len"] == 64 and rab["page_len"] == 16
    assert rab["flops_per_token_ratio"] <= 0.6
    assert (rab["ragged"]["wasted_lane_fraction"]
            < rab["dense"]["wasted_lane_fraction"])


def test_ragged_ab_pins(engine):
    """The ragged mixed-length A/B's honesty pins on the tiny engine:
    allclose parity, audited steady state, one compiled ragged step
    shape, and the ragged geometry strictly winning on both wasted
    lanes and AOT flops-per-token. (The ≤0.6 acceptance RATIO is pinned
    on the production-geometry smoke engine in the smoke-mode test —
    this toy geometry only pins the direction.)"""
    out = bench_serving.bench_ragged_ab(engine, n_docs=24, reps=1)
    assert out["parity_max_abs_diff"] < 1e-5
    assert out["audited"] is True
    assert out["ragged_compiled_step_shapes"] in (1, -1)
    assert out["page_len"] < out["chunk_len"]
    assert out["dense"]["steps_run"] > 0
    assert out["ragged"]["steps_run"] > out["dense"]["steps_run"]
    assert out["ragged"]["flops_per_token"] < out["dense"]["flops_per_token"]
    assert (out["ragged"]["wasted_lane_fraction"]
            < out["dense"]["wasted_lane_fraction"])
    assert out["flops_per_token_ratio"] < 1.0
    assert out["total_tokens"] > 0
    assert out["ragged"]["tokens_per_sec"] > 0


def test_make_mixed_length_ids_deterministic(engine):
    a = bench_serving.make_mixed_length_ids(engine, 16, seed=3)
    b = bench_serving.make_mixed_length_ids(engine, 16, seed=3)
    assert len(a) == 16
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    lengths = {len(x) for x in a}
    assert len(lengths) > 1  # a mixed-length spread, not one shape
    assert all(x.max() < engine.config.vocab_size for x in a if len(x))


def test_error_line_is_not_marked_fresh(monkeypatch, capsys):
    import json

    monkeypatch.setattr(bench_serving, "run_smoke",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("engine exploded")))
    # the error datapoint still lands on stdout (dashboards keep their
    # series) and the failed phase fails the process
    with pytest.raises(SystemExit) as exc:
        bench_serving.main(["--smoke"])
    assert exc.value.code == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["provenance"] == "no_measurement_available"
    assert "engine exploded" in out["error"]


def test_smoke_trace_breakdown(capsys):
    # --trace must yield a non-empty per-stage breakdown with the slot
    # pipeline's stages, on stderr as a table and in the JSON line — the
    # CI tracing smoke (verify skill) pins this contract
    import json

    out = bench_serving.main(["--smoke", "--n_issues", "8",
                              "--batch_size", "4", "--trace"])
    captured = capsys.readouterr()
    printed = json.loads(captured.out.strip().splitlines()[-1])
    assert printed == out
    bd = out["trace_breakdown"]
    assert bd, "empty per-stage breakdown"
    for stage in ("engine.tokenize", "slots.queue_wait",
                  "slots.device_steps", "slots.pool_emit"):
        assert stage in bd, (stage, sorted(bd))
        assert bd[stage]["count"] == 8
        assert bd[stage]["mean_ms"] >= 0
    # table rides stderr so stdout stays exactly one JSON line
    assert "slots.device_steps" in captured.err


def test_shed_check_smoke(capsys):
    # --shed-check is the CI overload smoke: excess load must come back
    # 429 + Retry-After (not queue unboundedly), admitted requests stay
    # bounded, shed requests never reach the engine; device-free
    import json

    out = bench_serving.main(["--shed-check"])
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed == out
    assert out["ok"] is True, out
    assert out["shed"] > 0
    assert out["retry_after_seen"] == out["shed"]
    assert out["engine_calls"] == out["admitted"]
    assert out["admitted_latency"]["p99_ms"] <= out["latency_bound_ms"]
    assert out["errors"] == []


def test_fleet_ab_smoke_contract(capsys):
    # --fleet_ab --smoke: the horizontal-scaling A/B (RUNBOOK §24) —
    # 1 vs 2 fake replicas behind the real router, Zipf workload,
    # provenance-stamped, zero client errors. Sized down here (the CLI
    # default smoke is itself pinned lean); supervisor subprocesses are
    # jax-free so this is wall-clock, not compile time.
    import json

    report = bench_serving.bench_fleet_ab(
        n_replicas=2, n_requests=24, concurrency=4,
        engine_delay_ms=10.0, zipf_a=1.3)
    assert report["client_errors"] == 0
    assert report["single"]["replicas"] == 1
    assert report["fleet"]["replicas"] == 2
    assert report["single"]["requests_ok"] == 24
    assert report["fleet"]["requests_ok"] == 24
    assert report["fleet"]["docs_per_sec"] > 0
    assert report["fleet"]["tokens_per_sec"] > 0
    assert "shed_rate" in report["fleet"]
    assert "hedge_rate" in report["fleet"]
    assert report["workload"]["dup_ratio"] > 1.0  # Zipf actually dup'd
    assert report["fleet_speedup"] > 0
    # per-member latency digests, keyed by X-Fleet-Member: each side
    # carries one serialized sketch per replica that answered, summing
    # to the side's request count — what makes a fleet bench line
    # perfwatch-diffable PER REPLICA (utils/fleetwatch.py)
    for side, n_replicas in (("single", 1), ("fleet", 2)):
        digests = report[side]["member_latency_digests"]
        assert 1 <= len(digests) <= n_replicas
        assert sum(d["count"] for d in digests.values()) \
            == report[side]["requests_ok"]
        assert all(d["kind"] == "ddsketch" for d in digests.values())
        assert report[side]["latency_kind"] == "http_e2e"
        assert report[side]["latency_digest"]["count"] \
            == report[side]["requests_ok"]
    from code_intelligence_tpu.utils import fleetwatch

    fleet_series, member_series = fleetwatch.fleet_series_of(report)
    assert "e2e" in fleet_series
    assert set(member_series) == set(
        report["fleet"]["member_latency_digests"])


@pytest.mark.slow  # boots 3 fleets (1+2 replicas x2 sides): ~12s of
# subprocess wall-clock — the full CLI smoke variant
def test_fleet_ab_cli_smoke_line(capsys):
    import json

    out = bench_serving.main(["--fleet_ab", "--smoke"])
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed == out
    assert out["metric"] == "embedding_serving_fleet_ab"
    assert out["provenance"] == "fresh"
    assert out["measured_at"]
    assert out["client_errors"] == 0
    assert out["value"] == out["fleet"]["docs_per_sec"]
    assert out["smoke"] is True


def test_mesh_ab_refuses_one_device_host(capsys, monkeypatch):
    import json
    # --mesh_ab without --smoke on a 1-device host: a NAMED fail-fast
    # (DegenerateMeshError, exit 2), never a silently degenerate mesh.
    # (The test harness forces 8 virtual devices — pin it back to 1.)
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:1])
    with pytest.raises(SystemExit) as exc:
        bench_serving.main(["--mesh_ab"])
    assert exc.value.code == 2
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert line["metric"] == "embedding_serving_mesh_ab"
    assert "DegenerateMeshError" in line["error"]
    assert line["provenance"] == "no_measurement_available"
    assert "DegenerateMeshError" in captured.err


def test_mesh_flag_refuses_one_device_host_without_smoke(capsys,
                                                         monkeypatch):
    import json
    # the standard run refuses --mesh too, BEFORE any engine work
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:1])
    with pytest.raises(SystemExit) as exc:
        bench_serving.main(["--mesh", "data,model", "--model_dir", "/x"])
    assert exc.value.code == 2
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "DegenerateMeshError" in line["error"]


def test_mesh_with_groups_scheduler_refused_at_cli():
    # only the slot/ragged schedulers run the sharded step — the groups
    # path would silently serve unsharded, so the CLI refuses (both the
    # bench here and serving.server main)
    with pytest.raises(SystemExit) as exc:
        bench_serving.main(["--mesh", "data,model", "--scheduler",
                            "groups", "--model_dir", "/x"])
    assert exc.value.code == 2
    from code_intelligence_tpu.serving.server import main as server_main

    with pytest.raises(SystemExit) as exc:
        server_main(["--model_dir", "/x", "--mesh", "data,model",
                     "--scheduler", "groups"])
    assert exc.value.code == 2


def test_mesh_ab_on_engine_one_device_mesh(engine):
    # the harness body on a real (degenerate-sized, smoke-legal) mesh:
    # all four pins must hold in-process — the 8-device twin is the
    # slow CLI test below / the --check_meshserve gate
    from code_intelligence_tpu.parallel.serve_shard import build_serve_mesh

    mesh = build_serve_mesh("data=1,model=1", devices=jax.devices()[:1])
    out = bench_serving.bench_mesh_ab(engine, mesh, n_docs=12, reps=1)
    assert out["ok"] is True
    assert out["parity_ok"] and out["audited"]
    assert out["mesh_off_bitwise_equal"] is True
    assert out["mesh"] == {"data": 1, "model": 1}
    assert 0 < out["flops_balance"] <= 1.2
    assert out["mesh_compiled_step_shapes"] in (1, -1)
    assert len(out["wasted_lane_fraction_by_shard"]) == 1


@pytest.mark.slow  # subprocess with forced 8 CPU devices compiling both
# ragged step shapes (~40s) — the acceptance-criteria command verbatim
def test_mesh_ab_smoke_cli_line():
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "bench_serving.py"), "--mesh_ab",
         "--smoke"],
        capture_output=True, text=True, timeout=900, cwd=str(repo),
        env={**os.environ, "PYTHONPATH": str(repo) + os.pathsep
             + os.environ.get("PYTHONPATH", "")})
    assert proc.returncode == 0, proc.stdout + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["metric"] == "embedding_serving_mesh_ab"
    assert line["smoke"] is True and line["provenance"] == "fresh"
    assert line["forced_devices"] == 8
    ab = line["mesh_ab"]
    assert ab["ok"] is True and ab["parity_ok"] and ab["audited"]
    assert ab["mesh"] == {"data": 4, "model": 2}
    assert 0 < ab["flops_balance"] <= 1.2
    assert ab["mesh_off_bitwise_equal"] is True
    assert ab["single"]["tokens_per_sec"] > 0
    assert ab["mesh_side"]["tokens_per_sec"] > 0


def test_run_with_pallas_engine_ab(engine):
    # on CPU the "pallas" engine override resolves to the scan (TPU-only
    # kernel) — the A/B plumbing must still produce the comparison fields
    out = bench_serving.run(engine, n_issues=8, concurrency=1, per_client=2,
                            pallas_engine=engine)
    assert "engine_pallas" in out
    assert out["pallas_bulk_speedup"] > 0


def test_engine_lstm_pallas_override_is_tpu_gated():
    from code_intelligence_tpu.inference import InferenceEngine
    import jax
    from code_intelligence_tpu.models import AWDLSTMConfig, AWDLSTMEncoder, init_lstm_states
    from code_intelligence_tpu.text import SPECIALS, Vocab
    import numpy as np

    cfg = AWDLSTMConfig(vocab_size=200, emb_sz=8, n_hid=12, n_layers=2)
    enc = AWDLSTMEncoder(cfg)
    params = enc.init({"params": jax.random.PRNGKey(0)},
                      np.zeros((1, 4), np.int32), init_lstm_states(cfg, 1))["params"]
    vocab = Vocab(SPECIALS + [f"w{i}" for i in range(180)])
    eng = InferenceEngine(params, cfg, vocab, buckets=(8,), batch_size=1,
                          lstm_pallas=True)
    # on the CPU backend the override must NOT enable the TPU-only kernel
    assert eng.config.lstm_use_pallas == (jax.default_backend() == "tpu")
    assert eng.embed_text("hello world").shape == (24,)


def test_make_issues_zipf_duplicates_seeded():
    a = bench_serving.make_issues(64, zipf_a=1.2)
    b = bench_serving.make_issues(64, zipf_a=1.2)
    assert a == b  # seeded: the workload is exactly reproducible
    stats = bench_serving.workload_stats(a)
    assert stats["n_docs"] == 64
    # a Zipf draw MUST realize duplication (the satellite bugfix: the
    # old all-unique workload could never exercise the cache at all)
    assert stats["n_unique"] < 64
    assert stats["dup_ratio"] > 1.0
    # the documents come from the same unique pool
    pool = {(d["title"], d["body"]) for d in bench_serving.make_issues(64)}
    assert all((d["title"], d["body"]) in pool for d in a)
    with pytest.raises(ValueError):
        bench_serving.make_issues(8, zipf_a=1.0)


def test_cache_ab_acceptance_pins(engine):
    """The ISSUE 7 acceptance criterion on the seeded Zipf workload:
    >= 2x docs/sec cached-vs-uncached, device-pass count EXACTLY the
    unique-(token-)document count, bitwise-equal responses, and the
    audited pass ran clean (no_implicit_transfers + recompile budget 0
    raise on violation inside bench_cache_ab)."""
    issues = bench_serving.make_issues(32, zipf_a=1.2)
    out = bench_serving.bench_cache_ab(engine, issues, reps=2)
    assert out["device_passes_equal_unique"]
    assert out["cached_device_passes"] == out["n_unique_content"]
    assert out["uncached_device_passes"] == len(issues)
    assert out["bitwise_equal"]
    assert out["audited"]
    # the >= 2x acceptance pin lives on the --smoke engine below, where
    # forward compute dominates; this tiny engine's hit path still pays
    # tokenize+hash so its margin is host-sensitive — bound loosely
    assert out["cache_speedup"] >= 1.3
    assert out["cache_stats"]["misses"] == out["n_unique_content"]


@pytest.mark.slow  # full --smoke engine + Zipf A/B: ~6s (PR 6 budget rule);
# the same pins run <2s on the module engine in test_cache_ab_acceptance_pins
def test_smoke_zipf_reports_workload_and_cache_ab(capsys):
    out = bench_serving.main(["--smoke", "--n_issues", "24", "--zipf_a",
                              "1.3"])
    assert out["workload"]["zipf_a"] == 1.3
    assert out["workload"]["dup_ratio"] >= 1.0
    assert out["cache_ab"]["cached_docs_per_sec"] > 0
    # THE acceptance criterion: on the seeded Zipf workload in --smoke,
    # cached serve is >= 2x uncached with device passes == unique docs,
    # bitwise-equal rows, audited clean (measured 3.3-3.6x on CPU)
    assert out["cache_ab"]["cache_speedup"] >= 2.0
    assert out["cache_ab"]["device_passes_equal_unique"]
    assert out["cache_ab"]["bitwise_equal"]
    assert out["cache_ab"]["audited"]
    line = capsys.readouterr().out.strip().splitlines()[-1]
    import json

    parsed = json.loads(line)
    assert parsed["workload"]["n_unique"] == out["workload"]["n_unique"]
    assert parsed["provenance"] == "fresh"
