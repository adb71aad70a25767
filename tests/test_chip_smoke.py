"""chip_smoke.py's legs, at a tiny size on the CPU backend.

The script itself only runs on the chip, and refuses anything else (below:
a time taken on the CPU backend or the Pallas interpreter says nothing
about the device, `/opt/skills/guides/on-chip-measurement`); this keeps
its legs from rotting between chip runs. Pallas kernels run in interpret
mode here, so nothing checks for the Mosaic custom call. The one compile
cache, which the script and the training CLI share, is pinned here too.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke

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


def test_every_leg_runs_at_tiny_widths(tmp_path):
    w = chip_smoke.Widths(vocab=3000, emb=16, hid=24, layers=3, bs=8,
                          bptt=10, steps_per_dispatch=3, serve_batch=8,
                          n_docs=10)
    legs = ("train", "serve_slots", "kernels", "serve_ragged",
            "serve_int8", "multichip")
    results = chip_smoke.run(w, legs=legs, expect_mosaic=False,
                             work=tmp_path)
    assert results["train"]["compiles"] == {"train.steps": 1,
                                            "eval.steps": 1}
    assert len(results["kernels"]) == 11  # every pallas_call in the repo
    # the conftest's 8 virtual devices: the multi-device leg ran on all
    n = len(jax.devices())
    assert results["multichip"]["devices_used"] == n >= 4
    for layout in (f"dp{n}", f"dp{n // 2}_mp2"):
        assert len(results["multichip"][layout]
                   ["live_bytes_per_device"]) == n


def test_flagship_widths_are_the_ones_the_repo_supports():
    # the benchmark's configuration is the authority on what "flagship" is
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "awd_lstm_flagship.json")) as f:
        flagship = json.load(f)
    w = chip_smoke.FLAGSHIP
    model = flagship["model"]
    assert {"vocab_size": w.vocab, "emb_sz": w.emb, "n_hid": w.hid,
            "n_layers": w.layers} == {k: model[k] for k in (
                "vocab_size", "emb_sz", "n_hid", "n_layers")}
    train = flagship["train"]
    assert (w.bs, w.bptt, w.steps_per_dispatch) == (
        train["batch_size"], train["bptt"], train["steps_per_dispatch"])
    assert w.serve_batch == 32  # the server's default, not the bulk cells'


@pytest.mark.parametrize("script,args", [
    ("chip_smoke.py", []),
    (os.path.join("benchmark", "run.py"),
     ["--workload", "lstm_bulk_mixed", "--seed", "1", "--seconds", "10"])])
def test_entry_point_without_a_tpu_fails_and_prints_no_measurement(
        script, args):
    # the two programs that need the chip (RUNBOOK §13)
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, script), *args],
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


# -- the one compile cache --------------------------------------------------


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    from code_intelligence_tpu.utils import devices

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert devices.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch):
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
            if f.endswith(".py") and f != "test_chip_smoke.py":
                with open(os.path.join(base, f), encoding="utf-8") as fh:
                    if "jax_compilation_cache_dir" in fh.read():
                        hits.append(os.path.relpath(
                            os.path.join(base, f), _ROOT))
    assert hits == [os.path.join("code_intelligence_tpu", "utils",
                                 "devices.py")]
