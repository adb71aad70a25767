"""BENCHMARK.json against the contract's mechanical rules, and every
name in it against the files it must resolve to. No jax."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

CELLS = [w["name"] for w in MANIFEST["workloads"]]
E2E = [m["name"] for m in MANIFEST["end_to_end"]]
LAYER = [m["name"] for m in MANIFEST["per_layer"]]


def test_top_level_keys_are_exactly_the_contracts():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for path in MANIFEST["paths"]:
        assert (ROOT / path).is_dir()
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", path)
    assert all(len(w) <= 200 for w in MANIFEST["command"])


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_unique_and_of_allowed_characters(kind):
    names = [e["name"] for e in MANIFEST[kind]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.fullmatch(n), n


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"]
                         + MANIFEST["per_layer"], ids=E2E + LAYER)
def test_metric_entry(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric["name"] in E2E:
        allowed |= {"bound"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        allowed |= {"layer", "moves"}
        assert metric["moves"] in E2E
        assert metric["source"] in SOURCES
        assert 1 <= len(metric["layer"]) <= 200
    assert set(metric) <= allowed, set(metric) - allowed
    assert UNIT.fullmatch(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    for w in metric.get("workloads", []):
        assert w in CELLS
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_setup_s_is_reported_by_every_cell():
    setup = next(m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=CELLS)
def test_cell_files_resolve(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert NAME.fullmatch(cell["traffic"]) and NAME.fullmatch(cell["config"])
    from benchmark.harness import cell as cells

    loaded = cells.load_cell(cell["name"])
    assert loaded["cell"]["driver"]
    assert (loaded["bench_dir"] / "drivers"
            / f"{loaded['cell']['driver']}.py").is_file()
    assert (loaded["bench_dir"] / "reference"
            / f"{loaded['config']['architecture']}.py").is_file()
    assert loaded["cell"]["check"]["limits"]
    # every cell: setup_s, one more end-to-end metric, a per-layer one
    names = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert loaded["per_layer"]


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=[c["name"] for c in MANIFEST["configs"]])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in MANIFEST["paths"])
    body = json.loads((ROOT / config["file"]).read_text())
    assert body["name"] == config["name"]
    assert len(config["reduced"]) <= 16
    assert config["name"] in {w["config"] for w in MANIFEST["workloads"]}
    for key in config["reduced"]:  # never a width
        assert not re.search(r"(_dim|_rank|emb_sz|n_hid|hidden|head)", key)
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("name", LAYER)
def test_layer_metric_file_and_reader(name):
    from benchmark.harness import cell as cells

    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    spec, read = cells.load_layer_reader(name)
    assert callable(read)
    for key in ("unit", "source", "layer", "moves", "better"):
        assert spec[key] == entry[key], (name, key)


def test_four_chip_cells_within_their_share():
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)
