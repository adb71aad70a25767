"""Shared by the benchmark's tests: a tiny copy of the benchmark in a
temporary directory, and the test-only device gate. The gate lives here,
in the tests, and not in ``benchmark/run.py``: the benchmark itself has
no switch that lets it run without a chip."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_MODEL = {"vocab_size": 600, "emb_sz": 16, "n_hid": 24, "n_layers": 3,
              "tie_weights": True, "dtype": "float32", "qrnn": False}
TINY_SERVE = {"scheduler": "groups", "batch_size": 4,
              "buckets": [16, 32, 64]}
TINY_TRAIN = {"batch_size": 4, "bptt": 9, "steps_per_dispatch": 3,
              "lr": 1.3e-3, "one_cycle": True, "steps_per_epoch": 100,
              "dropout": {"output_p": 0.1, "hidden_p": 0.15, "input_p": 0.25,
                          "embed_p": 0.02, "weight_p": 0.2}}
TINY_MIX = {"name": "tiny_docs", "kind": "documents", "docs_per_call": 10,
            "calls_pool": 2, "warmup_calls": 1, "trace_seconds": 0.05,
            "length": {"dist": "lognormal", "median": 20, "sigma": 0.8,
                       "min": 8, "max": 96},
            "why": "tiny documents for the CPU tests"}


def cpu_gate(chips: int) -> dict:
    """Stands in for ``run.require_device`` in tests: whatever JAX has
    (the CPU), no compile cache."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def tiny_benchmark(tmp: Path, qrnn: bool = False, driver: str = "bulk",
                   limits=None, per_layer=()) -> Path:
    """A copy of ``benchmark/`` under ``tmp`` with one tiny configuration,
    mix and cell ADDED as files, and a manifest that names them."""
    bench = tmp / "benchmark"
    shutil.copytree(ROOT / "benchmark", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    arch = "awd_qrnn" if qrnn else "awd_lstm"
    write(bench / "configs" / "tiny.json", {
        "name": "tiny", "architecture": arch,
        "model": dict(TINY_MODEL, qrnn=qrnn), "serve": TINY_SERVE,
        "train": TINY_TRAIN, "reduced": []})
    write(bench / "mixes" / "tiny_docs.json", TINY_MIX)
    write(bench / "mixes" / "tiny_stream.json", {
        "name": "tiny_stream", "kind": "token_stream", "trace_seconds": 0.05,
        "doc_tokens": [5, 20], "why": "tiny corpus for the CPU tests"})
    mix = "tiny_stream" if driver == "train" else "tiny_docs"
    limits = limits if limits is not None else {
        "rel_rms_mean": 1e-4, "rel_rms_max": 1e-4, "rel_rms_last": 1e-4,
        "nonfinite": 0, "nonfinite_rows": 0}
    write(bench / "cells" / "tiny_cell.json", {
        "name": "tiny_cell", "config": "tiny", "mix": mix, "chips": 1,
        "driver": driver, "reduced": [],
        "check": {"sample": 4, "limits": limits}})
    e2e = "train_tokens_per_s" if driver == "train" else "docs_per_s"
    write(tmp / "BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "benchmark/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny_cell", "config": "tiny", "traffic": mix,
                       "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": e2e, "unit": "x/s", "better": "higher", "bound": 0.1,
             "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [dict(m, moves=e2e) for m in per_layer]})
    return bench
