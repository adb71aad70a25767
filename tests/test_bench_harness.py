"""The bench and smoke entry points measure on the chip or fail.

No entry point prints a measurement, or exits 0, without a TPU: a time
taken on the CPU backend or the Pallas interpreter says nothing about the
device (`/opt/skills/guides/on-chip-measurement`). These pins replace the
tests of the supervisor process and its stale-number fallback, which went
with the supervisor (PR 21).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench
import bench_pallas_lstm as pb
import bench_serving

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json_objects(stdout: str) -> list:
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict):
            out.append(obj)
    return out


@pytest.mark.parametrize("script", [
    "bench.py", "bench_pallas_lstm.py", "chip_smoke.py",
    os.path.join("scripts", "bench_eval_dispatch.py")])
def test_entry_point_without_a_tpu_fails_and_prints_no_measurement(script):
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, script)],
        capture_output=True, text=True, timeout=300, cwd=_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0, proc.stdout[-500:]
    assert _json_objects(proc.stdout) == [], proc.stdout[-500:]
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    # the driver also runs the script without the program beside it
    shutil.copy(os.path.join(_ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, cwd=tmp_path, env={**env, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _json_objects(proc.stdout) == []


def test_bench_serving_without_smoke_needs_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench_serving.py"),
         "--model_dir", str(tmp_path)],
        capture_output=True, text=True, timeout=300, cwd=_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _json_objects(proc.stdout) == []
    assert "needs a TPU" in proc.stderr


def test_precision_ab_smoke_line_is_fresh_and_gated():
    """The CPU --smoke modes keep asserting counts and parity: the
    `--precision_ab` line (RUNBOOK §28) carries the provenance stamp and
    the weight-footprint ratio, and exits 0 only when the A/B held."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench_serving.py"),
         "--precision_ab", "--smoke"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=_ROOT,
    )
    parsed = _json_objects(proc.stdout)[-1]
    assert proc.returncode == 0, proc.stderr[-500:]
    assert parsed["metric"] == "embedding_serving_precision_ab"
    assert parsed["provenance"] == "fresh" and "measured_at" in parsed
    assert parsed["ok"] is True
    assert parsed["weight_footprint_ratio"] >= 3.0
    assert parsed["f32"]["weight_bytes"] > parsed["int8"]["weight_bytes"]


def test_failed_phase_is_a_nonzero_exit(monkeypatch, capsys):
    """An error datapoint still lands (dashboards keep their series) but
    never with exit code 0 — and an A/B that did not hold fails too."""
    with pytest.raises(SystemExit) as exc:
        bench_serving._finish({"metric": "m", "value": None,
                               "error": "engine exploded"})
    assert exc.value.code == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["provenance"] == "no_measurement_available"
    with pytest.raises(SystemExit):
        bench_serving._finish({"metric": "m", "value": 1.0, "ok": False})
    out = bench_serving._finish({"metric": "m", "value": 1.0, "ok": True})
    assert out["provenance"] == "fresh"


# -- bench.py ---------------------------------------------------------------


def test_ab_measure_reports_the_winner():

    def run_variant(lstm_pallas, trace, measure_rate=True):
        return 90_000.0 if lstm_pallas else 80_000.0

    out, winner = bench._ab_measure(run_variant, 1, 4500.0, "TPU v5 lite")
    assert winner == "pallas_resident" and out["value"] == 90_000.0
    assert out["pallas_resident_tokens_per_sec"] == 90_000.0
    assert out["xla_scan_tokens_per_sec"] == 80_000.0
    assert out["platform"] == "tpu" and out["device_count"] == 1
    assert out["device_kind"] == "TPU v5 lite" and out["mfu"] > 0


def test_ab_measure_pallas_failure_fails_the_run():

    def run_variant(lstm_pallas, trace, measure_rate=True):
        if lstm_pallas:
            raise RuntimeError("Mosaic refused the kernel")
        return 80_000.0

    with pytest.raises(RuntimeError, match="Mosaic refused"):
        bench._ab_measure(run_variant, 1, 4500.0, "TPU v5 lite")


def test_unknown_device_kind_is_an_error_not_a_null_mfu():
    assert bench._peak_bf16("TPU v5 lite") == 197e12
    with pytest.raises(SystemExit, match="no peak FLOP/s entry"):
        bench._peak_bf16("TPU v9 imaginary")


def test_flag_value_parsing():
    argv = ["bench.py", "--mesh", "data=4,model=2"]
    assert bench._flag_value(argv, "--mesh") == "data=4,model=2"
    assert bench._flag_value(argv, "--trace") is None
    with pytest.raises(SystemExit):
        bench._flag_value(["bench.py", "--trace"], "--trace")


def test_flops_per_token_single_layer_is_emb_sized():
    # AWDLSTMConfig.layer_size makes the LAST layer emb-sized always; a
    # 1-layer model is therefore emb->emb, not emb->n_hid
    emb, hid, vocab = 800, 2500, 60000
    one = bench._flops_per_token(vocab, emb, hid, 1)
    expected = 3.0 * ((emb + emb) * 4 * emb * 2 + emb * vocab * 2)
    assert one == expected
    # multi-layer: layer1 emb->hid, middle hid->hid, last hid->emb
    four = bench._flops_per_token(vocab, emb, hid, 4)
    fwd = (emb + hid) * 4 * hid * 2
    fwd += 2 * (hid + hid) * 4 * hid * 2
    fwd += (hid + emb) * 4 * emb * 2
    fwd += emb * vocab * 2
    assert four == 3.0 * fwd


# -- bench_pallas_lstm.py ---------------------------------------------------


def test_tile_search_report_contract():
    """The 'B,H,bt,tc' string is parsed by ops/pallas_lstm._env_tiles —
    pin it, and pin that the product's own tile failing is fatal."""
    search = {"bt56_tc1": 5.1, "bt16_tc4": 4.2, "bt16_tc1": "error: x"}
    winners = {(56, 1): 5.1, (16, 4): 4.2}
    out = pb._search_report(search, winners, (56, 1), 104, 2500)
    assert out["measured_winner"] == "bt16_tc4"
    assert out["heuristic_pick"] == "bt56_tc1"
    assert out["winner_env"] == "104,2500,16,4"
    empty = pb._search_report({}, {}, (56, 1), 104, 2500)
    assert empty["measured_winner"] is None and empty["winner_env"] is None
    # a candidate at the budget edge may fail; the heuristic's pick (what
    # the product runs) may not
    with pytest.raises(RuntimeError, match="bt16_tc1"):
        pb._search_report(search, winners, (16, 1), 104, 2500)


def test_winner_env_round_trips_through_env_tiles(monkeypatch):
    from code_intelligence_tpu.ops.pallas_lstm import _env_tiles

    out = pb._search_report({"bt16_tc4": 4.2}, {(16, 4): 4.2}, (16, 4),
                            104, 2500)
    monkeypatch.setenv("X_TILES_TEST", out["winner_env"])
    assert _env_tiles("X_TILES_TEST", [(16, 4), (56, 1)], 104, 2500) == (16, 4)
    assert _env_tiles("X_TILES_TEST", [(16, 4)], 104, 1024) is None


# -- the one compile cache --------------------------------------------------


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    import jax

    from code_intelligence_tpu.utils import devices

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert devices.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch):
    import jax

    from code_intelligence_tpu.utils import devices

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    first = devices.enable_compile_cache()
    assert first == devices.enable_compile_cache()
    assert first == os.path.join(_ROOT, ".jax_cache")
    # the CPU backend (this test) is left uncached: the suite must not
    # leave a cache in the checkout
    assert jax.config.jax_compilation_cache_dir == before
    # and the directory is git-ignored
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_one_helper_sets_the_cache_dir():
    hits = []
    for base, _, files in os.walk(_ROOT):
        if any(part.startswith(".") for part in
               os.path.relpath(base, _ROOT).split(os.sep) if part != "."):
            continue
        for f in files:
            if f.endswith(".py") and f != "test_bench_harness.py":
                with open(os.path.join(base, f), encoding="utf-8") as fh:
                    if "jax_compilation_cache_dir" in fh.read():
                        hits.append(os.path.relpath(
                            os.path.join(base, f), _ROOT))
    assert hits == [os.path.join("code_intelligence_tpu", "utils",
                                 "devices.py")]
