"""The Trinity share through the benchmark, tiny, on the CPU: a whole run
of its driver against its plain reference with documents longer than a
tiny window, through rings that wrap; every must-fail control reads not
correct; a program without the architecture fails at once; the new
per-layer readers on known inputs; the arithmetic of
``harness/flops_afmoe.py`` against ISSUE 32's table; the configuration
file against the catalog row. Pins no entry's place in the manifest and
no list's exact contents: the next configuration appends after these."""

import json
import re
from pathlib import Path

import pytest

import bm_util
from benchmark import run
from benchmark.harness import flops_afmoe
from benchmark.harness.spans import HostSpan, SpanLog

ROOT = bm_util.ROOT
TRACE = Path(__file__).parent / "data" / "tiny.xplane.pb"
CONFIG_NAME = "trinity_large_ep8_share"
CONFIG = json.loads(
    (ROOT / f"benchmark/configs/{CONFIG_NAME}.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "trinity_bulk_long_tail"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts",
           "vocab_size", "layer_types"]
NEW = ["swa_moe_fwd_roofline", "window_core_roofline",
       "global_core_roofline", "window_core_share_pct",
       "global_core_share_pct", "window_keys_met_pct"]
SHARED = ["tokenize_share_pct", "tokenize_us_per_doc",
          "text_rules_share_pct", "text_rules_us_per_doc",
          "pre_rule_passes_run_pct", "compiles_in_window",
          "encoder_fwd_us_per_token", "device_idle_pct.bulk",
          "group_dispatch_share_pct", "device_wait_share_pct",
          "padded_lane_pct", "attention_share_pct",
          "carried_state_mb_per_row"]
BY_NAME = {m["name"]: m for m in MANIFEST["per_layer"]}

SLIDING, FULL = "sliding_attention", "full_attention"
TINY = {
    "vocab_size": 600, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "num_dense_layers": 1,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL, SLIDING],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "sliding_window": 16, "num_experts": 8, "num_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
    "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid",
    "mup_enabled": True, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "rope_scaling": None,
    "experts_held": {"first": 4, "count": 8, "of": 16}}
SUFFIXES = ("", "_carried", "_past_window")
LIMITS = {f"rel_rms_{t}{s}": 4e-6 for t in ("mean", "max", "last")
          for s in SUFFIXES}
LIMITS.update(nonfinite=0, nonfinite_rows=0)


def tiny_benchmark(tmp: Path, per_layer=()) -> Path:
    """``bm_util``'s copy of the benchmark with a tiny share, its cell
    and a manifest that names them, as files. The mix's documents run to
    96 tokens: chunks of 32 under a window of 16, rings of 64 slots."""
    bench = bm_util.tiny_benchmark(tmp)
    bm_util.write(bench / "configs" / "tiny_swa.json", dict(
        TINY, name="tiny_swa", architecture="afmoe",
        dtype="float32", state_dtype="float32",
        serve={"scheduler": "groups", "batch_size": 4,
               "buckets": [16, 32], "kv_positions": 128},
        weights={"dist": "student_t", "df": 4}, reduced=[]))
    bm_util.write(bench / "cells" / "tiny_swa_cell.json", {
        "name": "tiny_swa_cell", "config": "tiny_swa",
        "mix": "tiny_docs", "chips": 1, "driver": "bulk_swa_moe",
        "reduced": [], "check": {"sample": 6, "block_rows": 1,
                                 "limits": LIMITS}})
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": "tiny_swa", "source": "test",
                            "file": "benchmark/configs/tiny_swa.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny_swa_cell", "config": "tiny_swa",
                              "traffic": "tiny_docs", "chips": 1,
                              "why": "test"}]
    manifest["per_layer"] = [dict(m, moves="docs_per_s") for m in per_layer]
    bm_util.write(tmp / "BENCHMARK.json", manifest)
    return bench


def main(tmp, *extra, **kw):
    return run.main(["--workload", "tiny_swa_cell", "--seed",
                     str(2**31 + 32), "--seconds", "0.2", *extra],
                    root=tmp, **kw)


@pytest.fixture
def gate(monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)


def numbers(line):
    return {c["name"]: c["value"] for c in line["compared"]}


def test_cell_runs_and_agrees_with_its_reference(tmp_path, gate):
    """The mix's longest document (96 tokens) takes three chunk programs
    of 32 under a window of 16: ring, mask and growing cache are inside
    the comparison, at float32 tightness."""
    per_layer = [{k: BY_NAME[n][k] for k in (
        "name", "unit", "better", "source", "layer")} for n in SHARED + NEW]
    tiny_benchmark(tmp_path, per_layer)
    line = main(tmp_path, "--trace", "0")
    assert line["correct"] and line["failed"] == 0, line["compared"]
    got = numbers(line)
    assert set(LIMITS) <= set(got)   # some sampled row passed the window
    assert got["rel_rms_mean_past_window"] < 2e-6
    assert line["counters"]["compiles_in_window"] == 0

    traced = main(tmp_path, "--trace", "1")
    assert traced["correct"]
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    # multi-chunk groups: the global layer at 64 or 128 positions (two
    # or three chunks of 32), four rings of 32 + 32 slots, keys and
    # values of 2 heads x 8 float32
    per_slot = 2 * 2 * 8 * 4 / 1e6
    assert (64 + 4 * 64) * per_slot \
        <= metrics["carried_state_mb_per_row"] <= (128 + 4 * 64) * per_slot
    assert 0 < metrics["window_keys_met_pct"] < 100
    assert 0 < metrics["padded_lane_pct"] < 100
    assert metrics["pre_rule_passes_run_pct"] > 0
    # no device plane in a CPU capture: the scope readers find nothing
    # and their metrics are left out, not reported as zero
    assert not {"swa_moe_fwd_roofline", "window_core_roofline",
                "global_core_roofline", "window_core_share_pct",
                "global_core_share_pct", "attention_share_pct"} \
        & set(metrics)


@pytest.mark.parametrize("control,overrides,floor,where", [
    ("int8_weights", {"precision": "int8"}, 1e-3, "_carried"),
    ("no_window", {"sliding_window": "off"}, 1e-3, "_past_window"),
    ("zeroed_caches", {"caches": "zeroed"}, 1e-2, "_carried"),
    ("no_rotary", {"rope": "off"}, 1e-3, "_carried"),
    ("no_gate", {"gate": "off"}, 1e-2, "_carried"),
    ("no_shared_expert", {"num_shared_experts": "0"}, 1e-2, "_carried"),
    ("no_route_scale", {"route_scale": "1"}, 1e-3, "_carried"),
])
def test_controls_are_not_correct(tmp_path, gate, control, overrides, floor,
                                  where):
    """float32 sound runs sit below 2e-6; each control far above, by a
    limit on the rows it is aimed at."""
    from code_intelligence_tpu.ops import mla

    rope = mla.apply_rope
    tiny_benchmark(tmp_path)
    line = main(tmp_path, overrides=overrides)
    assert mla.apply_rope is rope    # the rotary's stand-in lasts a trace
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if not c["inside"]}
    assert any(name.endswith(where) for name in bad), bad
    got = numbers(line)
    assert got[f"rel_rms_mean{where}"] > floor
    if control == "no_window":
        # a row inside the window never meets the mask: the sample's
        # error is the long rows'
        assert got["rel_rms_mean_past_window"] >= got["rel_rms_mean"]


def test_on_a_program_without_the_architecture_the_cell_fails_at_once(
        tmp_path, gate, monkeypatch):
    """The parent commit has no ``afmoe``: ``make_config`` raises before
    a weight is made, and nothing hangs."""
    from code_intelligence_tpu.models import contract

    tiny_benchmark(tmp_path)
    monkeypatch.delitem(contract.ENCODERS, "afmoe")
    with pytest.raises(ValueError, match="unknown architecture 'afmoe'"):
        main(tmp_path)


# -- the readers on known inputs ----------------------------------------------

def _reader_ctx(spans, modules, path=str(TRACE)):
    from benchmark.harness import cell as cells, readers

    ctx = readers.ReaderContext()
    ctx.config = CONFIG
    ctx.spans = ctx.traced_spans = SpanLog()
    ctx.spans.spans = spans
    ctx.reduced["modules"] = modules
    ctx.result = {"xplane_path": path}
    ctx.device_kind = "TPU v5 lite"
    return ctx, cells.load_layer_reader


def _steps(rows_by_chunk, cap):
    return sum(r * min(512 * (i + 1), cap)
               for i, r in enumerate(rows_by_chunk))


LONG = [16] * 11 + [8] * 7 + [4] * 7 + [2] * 7    # the cell's long group
SHORT = [16, 16, 16, 8, 8, 2]                     # and its short one
GROUPS = [
    HostSpan("engine.group", 0, 1, {
        "rows": 16, "batch": 16, "bucket": 512, "chunks": 32,
        "valid_tokens": 118484, "lane_steps": 16 * 512 * 32,
        "lane_steps_run": 512 * sum(LONG),
        "cache_steps_run": _steps(LONG, 1 << 30),
        "window_steps_run": _steps(LONG, 4608),
        "state_bytes": 16 * 142606336, "kv_positions": 16384,
        "kv_positions_window": 4608}),
    HostSpan("engine.group", 1, 2, {
        "rows": 16, "batch": 16, "bucket": 512, "chunks": 6,
        "valid_tokens": 25138, "lane_steps": 16 * 512 * 6,
        "lane_steps_run": 512 * sum(SHORT),
        "cache_steps_run": _steps(SHORT, 1 << 30),
        "window_steps_run": _steps(SHORT, 4096),
        "state_bytes": 16 * 83886080, "kv_positions": 4096,
        "kv_positions_window": 4096})]
FLUSHES = [
    HostSpan("engine.finalize", 2, 3, {
        "groups": 2, "routed_rows": 71000, "expert_rows_max": 160.0,
        "expert_rows_mean": 71000 / (38 * 4 * 32), "moe_programs": 38}),
    HostSpan("engine.finalize", 4, 5, {"groups": 1})]   # an AWD flush
DOCS = [HostSpan("engine.tokenize", 0, 0, {"n_tokens": n})
        for n in (16384, 5114, 348)]


def test_the_issues_counts_of_one_call():
    """38 chunk programs, 174,080 lane-steps, and the window meeting
    66.8 % of what the global layer meets."""
    assert len(LONG) + len(SHORT) == 38
    assert 512 * (sum(LONG) + sum(SHORT)) == 174080
    window = sum(g.attrs["window_steps_run"] for g in GROUPS)
    cache = sum(g.attrs["cache_steps_run"] for g in GROUPS)
    assert (window, cache) == (1059840, 1586176)
    assert round(100 * window / cache, 1) == 66.8
    assert 118484 + 25138 == 143622


def test_layer_readers_on_known_inputs(capsys):
    ctx, load = _reader_ctx(GROUPS + FLUSHES + DOCS,
                            {"jit_fwd": [0.5, 0.25]})
    dot = [r"(^|/)dot_general"]     # the recorded trace's one named scope
    dot_s = 3.644766e-06

    spec, read = load("window_keys_met_pct")
    assert read(ctx, spec) == pytest.approx(100 * 1059840 / 1586176)
    spec, read = load("carried_state_mb_per_row")
    assert read(ctx, spec) == pytest.approx((142.606336 + 83.88608) / 2)

    for name in ("window_core_share_pct", "global_core_share_pct"):
        spec, read = load(name)
        assert read(ctx, spec) is None     # nothing under *_core there
        assert read(ctx, dict(spec, scopes=dot)) == \
            pytest.approx(100 * dot_s / 0.75)

    rows = sum(LONG) + sum(SHORT)
    for name, layers, steps in (("window_core_roofline", 4, 1059840),
                                ("global_core_roofline", 1, 1586176)):
        spec, read = load(name)
        assert read(ctx, spec) is None
        value = read(ctx, dict(spec, scopes=dot))
        need = layers * steps * 512 * 24576
        moved = layers * (steps * 2 * 1024 * 2 + rows * 512 * 6144 * 6)
        assert need / 197e12 > moved / 819e9
        assert value == pytest.approx(100 * (need / 197e12) / dot_s)
        assert "compute-bound" in capsys.readouterr().out

    spec, read = load("swa_moe_fwd_roofline")
    need = flops_afmoe.encoder_flops(CONFIG, 143622, 71000,
                                     [16384, 5114, 348])
    assert read(ctx, spec) == pytest.approx(100 * (need / 197e12) / 0.75)

    # a program without the spans, counters or scopes gives nothing, not
    # an error; without the window's count the ratio reads 0 of the cache
    bare = [HostSpan(g.name, g.start_unix, g.end_unix, {
        k: v for k, v in g.attrs.items()
        if k not in ("cache_steps_run", "window_steps_run")})
        for g in GROUPS]
    parent, _ = _reader_ctx(bare + FLUSHES[1:] + DOCS, {"jit_fwd": [0.5]})
    empty, _ = _reader_ctx([], {}, path=None)
    for name in NEW:
        spec, read = load(name)
        assert read(parent, spec) is None, name
        assert read(empty, spec) is None, name


# -- the arithmetic -----------------------------------------------------------

def test_flops_afmoe_against_the_issues_table():
    c = CONFIG
    assert flops_afmoe.attention_params(c) == 3072 * 6144 + 2 * 3072 * 1024 \
        + 3072 * 6144 + 6144 * 3072 == 62914560
    assert flops_afmoe.expert_params(c) == 3 * 3072 * 3072 == 28311552
    assert flops_afmoe.router_params(c) == 3072 * 256 == 786432
    assert flops_afmoe.expert_layer_params(c) == 997982208
    assert flops_afmoe.dense_mlp_params(c) == 3 * 3072 * 12288
    assert flops_afmoe.dense_layer_params(c) == 176160768
    assert flops_afmoe.layer_counts(c) == (1, 4)
    assert (flops_afmoe.layers_of(c, SLIDING),
            flops_afmoe.layers_of(c, FULL)) == (4, 1)
    assert 25024 * 3072 == 76873728 and 25024 * 8 == 200192
    assert flops_afmoe.held_params(c) == 4244963328
    assert flops_afmoe.held_params(c) * 2 == 8489926656         # 8.49 GB
    assert round(100 * 8489926656 / 16909336064, 1) == 50.2
    assert flops_afmoe.weight_bytes(c) == (4244963328 - 76873728) * 2
    assert flops_afmoe.token_matmul_params(c) == 5 * 62914560 \
        + 113246208 + 4 * (786432 + 28311552)
    assert flops_afmoe.pair_flops(c) == 24576
    assert flops_afmoe.routed_flops(c, 10) == 20 * 28311552
    # one document of 3 tokens: 1 + 2 + 3 pairs in every layer
    assert flops_afmoe.attention_flops(c, [3]) == 6 * 24576 * 5
    # 4097 tokens: the last query of a sliding layer meets 4096 keys
    full = 4097 * 4098 // 2
    assert flops_afmoe.attention_flops(c, [4097]) == 24576 * (
        full + 4 * (full - 1))
    assert flops_afmoe.core_flops(c, SLIDING, 512, 2.0) == \
        4 * 2 * 512 * 24576
    assert flops_afmoe.core_flops(c, FULL, 512, 2.0) == 2 * 512 * 24576
    assert flops_afmoe.core_bytes(c, FULL, 512, 1, 512) == \
        512 * 2 * 1024 * 2 + 512 * 6144 * 6
    # the state of one row at 16,384 tokens, and with every layer global
    assert 16384 * 2 * 1024 * 2 == 67108864
    assert 4608 * 2 * 1024 * 2 == 18874368
    assert 67108864 + 4 * 18874368 == 142606336


# -- the configuration --------------------------------------------------------

def test_configuration_holds_the_catalog_rows_numbers_key_for_key():
    if not CATALOG.is_file():
        pytest.skip("the catalog of architectures is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Trinity-Large-Preview")
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if CONFIG.get(k, "absent") != v)
    assert differ == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    assert CONFIG["published"] == {k: row["config"][k] for k in differ}
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:5]


def test_reduced_names_the_cuts_and_no_width():
    """What ``test_bm_manifest.py::test_config_entry`` holds for every
    configuration, with the contract's own rule for a width: that test
    refuses every key that CONTAINS ``hidden``, so it fails for this
    configuration's depth key ``num_hidden_layers`` as it does for
    DeepSeek's (PERF.md §7, finding 11: a ``benchmark`` PR's to mend)."""
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == CONFIG_NAME)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{CONFIG_NAME}.json"
    assert CONFIG["name"] == entry["name"]
    assert entry["reduced"] == CONFIG["reduced"] == REDUCED
    assert entry["name"] in {w["config"] for w in MANIFEST["workloads"]}
    width = re.compile(
        r"(_dim|_rank)$|(hidden|intermediate|latent|state|proj\w*|head\w*)"
        r"_size$|^(emb_sz|n_hid|num_experts_per_tok|expand\w*)$")
    for key in entry["reduced"]:
        assert not width.search(key), key
    assert width.search("head_dim") and width.search("hidden_size") \
        and width.search("moe_intermediate_size")
    # every published width unchanged at the top level
    assert [CONFIG[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "moe_intermediate_size",
        "sliding_window")] == [3072, 48, 8, 128, 12288, 3072, 4096]
    assert (CONFIG["experts_held"], CONFIG["n_group"], CONFIG["topk_group"],
            CONFIG["num_experts_per_tok"], CONFIG["route_scale"],
            CONFIG["score_func"], CONFIG["num_shared_experts"]) == (
        {"first": 0, "count": 32, "of": 256}, 1, 1, 4, 2.448, "sigmoid", 1)
    assert CONFIG["deployment"]["chips_that_share_a_layer"] == 8
    assert set(CONFIG["assumed"]) >= {
        "from_the_modelling_code", "vocabulary", "pooling", "weights",
        "serve.kv_positions"}


def test_the_program_reads_the_file_as_the_share_it_states():
    from code_intelligence_tpu.models import build_encoder, make_config

    serve = CONFIG["serve"]
    enc = build_encoder(make_config(
        "afmoe", CONFIG, kv_positions=serve["kv_positions"],
        chunk_positions=max(serve["buckets"]),
        state_dtype=CONFIG["state_dtype"]))
    cfg = enc.config
    assert (cfg.num_experts, cfg.experts_held) == (256, (0, 32))
    assert (cfg.num_hidden_layers, cfg.num_dense_layers,
            cfg.n_moe_layers) == (5, 1, 4)
    assert enc.out_dim == 3072
    assert enc.state_bytes_per_row(16384) == 142606336
    cell = json.loads(
        (ROOT / "benchmark/cells" / f"{CELL}.json").read_text())
    assert cell["reduced"] == CONFIG["reduced"]
    assert cell["driver"] == "bulk_swa_moe"
    assert {f"rel_rms_{t}{s}" for t in ("mean", "max", "last")
            for s in SUFFIXES} <= set(cell["check"]["limits"])


def test_the_mix_is_the_issues():
    from benchmark.harness import traffic

    mix = json.loads((ROOT / "benchmark/mixes"
                      / "issue_threads_long_tail_c32.json").read_text())
    assert mix["kind"] == "documents" and mix["docs_per_call"] == 32
    grid = sorted(traffic.length_grid(mix["length"], 32).tolist())
    assert (grid[0], grid[-2], grid[-1], sum(grid)) == (
        348, 16031, 16384, 143622)
    past = [n for n in grid if n > 4096]
    assert len(past) == 12 and round(100 * sum(past) / sum(grid)) == 73
    assert grid[15] == 2885 and grid[16] == 3120   # the two groups of 16


# -- the manifest ---------------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_new_metrics_move_docs_per_s_in_this_cell(name):
    metric = BY_NAME[name]
    assert metric["moves"] == "docs_per_s"
    assert CELL in metric["workloads"]
    if name.endswith("_roofline"):
        assert (metric["unit"], metric["layer"]) == ("%", "kernels")


@pytest.mark.parametrize("name", SHARED + ["docs_per_s"])
def test_the_cell_joins_the_metrics_every_bulk_cell_reports(name):
    entry = BY_NAME.get(name) or next(
        m for m in MANIFEST["end_to_end"] if m["name"] == name)
    assert CELL in entry["workloads"]


def test_the_cell_entry_says_why_and_what_attention_sees():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG_NAME, "issue_threads_long_tail_c32", 1)
    assert len(entry["why"]) <= 200 and "8x share" in entry["why"]
