"""Finding a cell's files by name. Nothing here lists cells, mixes,
configurations or metrics: ``BENCHMARK.json`` names them and the files
are looked up under the benchmark's directory, so a later PR adds a cell
by adding files and one manifest entry.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _load(path: Path) -> dict:
    return json.loads(path.read_text())


def load_manifest(root: Path = ROOT) -> dict:
    return _load(Path(root) / "BENCHMARK.json")


def load_cell(workload: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> dict:
    """Everything one run needs: the manifest's entry for the cell, its
    own file, its configuration, its mix, and the metrics it reports."""
    manifest = load_manifest(root)
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    cell = _load(Path(bench_dir) / "cells" / f"{workload}.json")
    config = _load(Path(root) / config_entry["file"])
    mix = _load(Path(bench_dir) / "mixes" / f"{entry['traffic']}.json")

    def mine(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    end_to_end = [m for m in manifest["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in manifest["per_layer"]
                 if mine(m) and m["moves"] in e2e_names]
    return {"name": workload, "entry": entry, "cell": cell, "config": config,
            "mix": mix, "chips": int(entry["chips"]),
            "end_to_end": end_to_end, "per_layer": per_layer,
            "bench_dir": Path(bench_dir), "root": Path(root)}


def _load_module(path: Path):
    """A module by its file, so that a copy of the benchmark elsewhere
    (the add-by-files test) runs its own files, not the imported ones."""
    name = "benchmark_file_" + "_".join(path.with_suffix("").parts[-2:])
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(kind: str, bench_dir: Path = BENCH_DIR):
    return _load_module(Path(bench_dir) / "drivers" / f"{kind}.py")


def load_reference(architecture: str, bench_dir: Path = BENCH_DIR):
    return _load_module(Path(bench_dir) / "reference" / f"{architecture}.py")


def load_layer_reader(name: str, bench_dir: Path = BENCH_DIR):
    """``(spec, read)`` of a per-layer metric: ``<name>.json`` says what
    it is; ``read(ctx)`` comes from ``<reader>.py`` beside it (by default
    the file of the metric's own name)."""
    spec = _load(Path(bench_dir) / "layer_metrics" / f"{name}.json")
    module = _load_module(Path(bench_dir) / "layer_metrics"
                          / f"{spec.get('reader', name)}.py")
    return spec, module.read
