"""The DeepSeek-V3 share through the benchmark, tiny, on the CPU: a whole
run of its driver against its plain reference with documents that cross
chunk programs through the latent cache; every must-fail control reads
not correct; the new per-layer readers on known inputs; the arithmetic
of ``harness/flops_moe.py`` against ISSUE 30's table; the configuration
file against the catalog row."""

import json
import re
from pathlib import Path

import pytest

import bm_util
from benchmark import run
from benchmark.harness import flops_moe
from benchmark.harness.spans import HostSpan, SpanLog

ROOT = bm_util.ROOT
TRACE = Path(__file__).parent / "data" / "tiny.xplane.pb"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/deepseek_v3_ep16_share.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "deepseek_v3_bulk_mixed"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")

TINY_MOE = {
    "vocab_size": 600, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": CONFIG["rope_scaling"],
    "experts_held": {"first": 4, "count": 8, "of": 16}}
LIMITS = {f"rel_rms_{t}{s}": 2e-6 for t in ("mean", "max", "last")
          for s in ("", "_carried")}
LIMITS.update(nonfinite=0, nonfinite_rows=0)


def tiny_moe_benchmark(tmp: Path, per_layer=()) -> Path:
    """``bm_util``'s copy of the benchmark with a tiny share, its cell
    and a manifest that names them, as files."""
    bench = bm_util.tiny_benchmark(tmp)
    bm_util.write(bench / "configs" / "tiny_moe.json", dict(
        TINY_MOE, name="tiny_moe", architecture="deepseek_v3",
        dtype="float32", state_dtype="float32",
        serve={"scheduler": "groups", "batch_size": 4,
               "buckets": [16, 32], "kv_positions": 128},
        weights={"dist": "student_t", "df": 4}, reduced=[]))
    bm_util.write(bench / "cells" / "tiny_moe_cell.json", {
        "name": "tiny_moe_cell", "config": "tiny_moe",
        "mix": "tiny_docs", "chips": 1, "driver": "bulk_moe",
        "reduced": [], "check": {"sample": 6, "block_rows": 3,
                                 "limits": LIMITS}})
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": "tiny_moe", "source": "test",
                            "file": "benchmark/configs/tiny_moe.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny_moe_cell", "config": "tiny_moe",
                              "traffic": "tiny_docs", "chips": 1,
                              "why": "test"}]
    manifest["per_layer"] = [dict(m, moves="docs_per_s") for m in per_layer]
    bm_util.write(tmp / "BENCHMARK.json", manifest)
    return bench


def main(tmp, *extra, **kw):
    return run.main(["--workload", "tiny_moe_cell", "--seed",
                     str(2**31 + 30), "--seconds", "0.2", *extra],
                    root=tmp, **kw)


@pytest.fixture
def gate(monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)


def numbers(line):
    return {c["name"]: c["value"] for c in line["compared"]}


NEW_METRICS = [m for m in MANIFEST["per_layer"]
               if m.get("workloads") == [CELL]]


def test_cell_runs_and_agrees_with_its_reference(tmp_path, gate):
    """The mix's longest document (96 tokens) takes three chunk programs
    of 32: the latent cache is inside the comparison, at float32
    tightness (the same experts chosen for every token)."""
    per_layer = [{k: m[k] for k in ("name", "unit", "better", "source",
                                    "layer")}
                 for m in MANIFEST["per_layer"] if CELL in m["workloads"]]
    assert len(per_layer) == 20
    tiny_moe_benchmark(tmp_path, per_layer)
    line = main(tmp_path, "--trace", "0")
    assert line["correct"] and line["failed"] == 0, line["compared"]
    got = numbers(line)
    assert set(LIMITS) <= set(got)          # some sampled row was carried
    assert got["rel_rms_mean_carried"] < 1e-6
    assert line["counters"]["compiles_in_window"] == 0

    traced = main(tmp_path, "--trace", "1")
    assert traced["correct"]
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    # 3 layers x 128 positions x (16 + 4) float32 a row
    assert metrics["carried_state_mb_per_row"] == \
        pytest.approx(3 * 128 * 20 * 4 / 1e6)
    assert 0 < metrics["padded_lane_pct"] < 100
    # the counters of the expert layers, from the finalize spans: 8 of 16
    # experts held, 4 a token: about two rows a token a layer land here
    # over 2 x 8 (layer, expert) cells; the busiest expert of a program
    # of 64-128 lanes runs a few times the mean one's rows
    assert 0 < metrics["expert_rows_per_program"] < 128
    assert 1.0 <= metrics["expert_load_max_over_mean"] <= 8.0
    assert metrics["pre_rule_passes_run_pct"] > 0
    # no device plane in a CPU capture: the scope readers find nothing
    # and their metrics are left out, not reported as zero
    assert not {"moe_fwd_roofline", "expert_gmm_roofline",
                "mla_core_roofline", "moe_share_pct",
                "route_overhead_share_pct", "attention_share_pct"} \
        & set(metrics)


@pytest.mark.parametrize("control,overrides,carried_floor", [
    ("int8_weights", {"precision": "int8"}, 1e-3),
    ("dropped_cache", {"latent_cache": "dropped"}, 1e-2),
    ("no_shared_expert", {"n_shared_experts": "0"}, 1e-2),
    ("no_scaling_factor", {"routed_scaling_factor": "1"}, 1e-3),
    ("bf16_router", {"router_dtype": "bfloat16"}, 1e-5),
])
def test_controls_are_not_correct(tmp_path, gate, control, overrides,
                                  carried_floor):
    """float32 sound runs sit at 3e-7; each control far above. The
    bfloat16 router separates HERE, at float32 tightness, because a
    flipped assignment is the only error there is; on the chip it is
    measured against bfloat16's own flips (PERF.md §2)."""
    from code_intelligence_tpu.ops import moe

    route = moe.route
    tiny_moe_benchmark(tmp_path)
    line = main(tmp_path, overrides=overrides)
    assert moe.route is route    # the router's wrapper lasts one run
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if not c["inside"]}
    assert any(name.endswith("_carried") for name in bad)
    assert numbers(line)["rel_rms_mean_carried"] > carried_floor
    if control == "dropped_cache":
        # single-chunk rows never read the cache: the whole-sample
        # numbers move only through the carried rows
        assert numbers(line)["rel_rms_mean_carried"] > \
            numbers(line)["rel_rms_mean"]


def test_on_a_program_without_the_architecture_the_cell_fails_at_once(
        tmp_path, gate, monkeypatch):
    """The parent commit has no ``deepseek_v3``: ``make_config`` raises
    before a weight is made, and nothing hangs."""
    from code_intelligence_tpu.models import contract

    tiny_moe_benchmark(tmp_path)
    monkeypatch.delitem(contract.ENCODERS, "deepseek_v3")
    with pytest.raises(ValueError, match="unknown architecture"):
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


GROUPS = [
    # the call's last group: 16, 16, 2, 2 rows through four chunks of 512
    HostSpan("engine.group", 0, 1, {
        "rows": 16, "batch": 16, "bucket": 512, "chunks": 4,
        "valid_tokens": 10866, "lane_steps": 32768, "lane_steps_run": 18432,
        "cache_steps_run": 512 * (16 + 2 * 16 + 3 * 2 + 4 * 2),
        "state_bytes": 16 * 11796480, "kv_positions": 2048}),
    HostSpan("engine.group", 1, 2, {
        "rows": 16, "batch": 16, "bucket": 64, "chunks": 1,
        "valid_tokens": 399, "lane_steps": 1024, "lane_steps_run": 1024,
        "cache_steps_run": 1024, "state_bytes": 16 * 5 * 64 * 576 * 2,
        "kv_positions": 64})]
FLUSHES = [
    HostSpan("engine.finalize", 2, 3, {
        "groups": 2, "routed_rows": 5600, "expert_rows_max": 70.0,
        "expert_rows_mean": 5600 / (5 * 64), "moe_programs": 5}),
    HostSpan("engine.finalize", 3, 4, {
        "groups": 1, "routed_rows": 64, "expert_rows_max": 4.0,
        "expert_rows_mean": 1.0, "moe_programs": 1}),
    HostSpan("engine.finalize", 4, 5, {"groups": 1})]   # an AWD flush
DOCS = [HostSpan("engine.tokenize", 0, 0, {"n_tokens": n})
        for n in (1716, 1157, 25)]


def test_layer_readers_on_known_inputs(capsys):
    ctx, load = _reader_ctx(GROUPS + FLUSHES + DOCS,
                            {"jit_fwd": [0.5, 0.25]})
    dot = [r"(^|/)dot_general"]     # the recorded trace's one named scope
    dot_s = 3.644766e-06

    spec, read = load("carried_state_mb_per_row")
    assert read(ctx, spec) == pytest.approx(11.79648)

    # two flushes of 5 and 1 programs: weighted by their programs, so
    # the small one cannot pull the mean or the ratio to itself
    spec, read = load("expert_rows_per_program")
    assert read(ctx, spec) == pytest.approx(5664 / 6 / 64)
    spec, read = load("expert_load_max_over_mean")
    assert read(ctx, spec) == pytest.approx(
        (5 * 70.0 + 4.0) / (5 * 17.5 + 1.0))

    for name in ("moe_share_pct", "route_overhead_share_pct"):
        spec, read = load(name)
        assert read(ctx, spec) is None      # nothing under moe_* there
        assert read(ctx, dict(spec, scopes=dot)) == \
            pytest.approx(100 * dot_s / 0.75)

    spec, read = load("expert_gmm_roofline")
    value = read(ctx, dict(spec, scopes=dot))
    # 6 programs read 4 x 16 x 44,040,192 bf16 weights each: 33.8 GB
    # against 0.50 TFLOP of routed rows
    moved = 6 * 4 * 16 * 44040192 * 2
    assert flops_moe.routed_flops(CONFIG, 5664) / 197e12 < moved / 819e9
    assert value == pytest.approx(100 * (moved / 819e9) / dot_s)
    assert "memory-bound" in capsys.readouterr().out

    spec, read = load("mla_core_roofline")
    value = read(ctx, dict(spec, scopes=dot))
    steps = [(512, 512 * 62), (64, 1024)]
    need = sum(5 * s * (33554432 + q * 2 * 320 * 128) for q, s in steps)
    assert value == pytest.approx(100 * (need / 197e12) / dot_s)
    assert "compute-bound" in capsys.readouterr().out

    spec, read = load("moe_fwd_roofline")
    need = flops_moe.encoder_flops(CONFIG, 10866 + 399, 5664,
                                   [1716, 1157, 25])
    assert read(ctx, spec) == pytest.approx(100 * (need / 197e12) / 0.75)

    # a program without the spans, counters or scopes (the parent) gives
    # nothing, not an error
    bare = [HostSpan(g.name, g.start_unix, g.end_unix, {
        k: v for k, v in g.attrs.items() if k != "cache_steps_run"})
        for g in GROUPS]
    parent, _ = _reader_ctx(bare + FLUSHES[2:] + DOCS, {"jit_fwd": [0.5]})
    empty, _ = _reader_ctx([], {}, path=None)
    for m in NEW_METRICS:
        spec, read = load(m["name"])
        assert read(parent, spec) is None, m["name"]
        assert read(empty, spec) is None, m["name"]


# -- the arithmetic -----------------------------------------------------------

def test_flops_moe_against_the_issues_table():
    c = CONFIG
    assert flops_moe.mla_params(c) == 11010048 + 37748736 + 4128768 \
        + 16777216 + 117440512 == 187105280
    assert flops_moe.expert_params(c) == 3 * 7168 * 2048 == 44040192
    assert flops_moe.router_params(c) == 7168 * 256 == 1835008
    assert flops_moe.expert_layer_params(c) == 937623552
    assert flops_moe.dense_mlp_params(c) == 396361728
    assert flops_moe.dense_layer_params(c) == 583467008
    assert flops_moe.layer_counts(c) == (1, 4)
    assert flops_moe.held_params(c) == 4449796096
    assert flops_moe.held_params(c) * 2 == 8899592192          # 8.90 GB
    assert round(100 * 8899592192 / 16909336064, 1) == 52.6
    assert flops_moe.weight_bytes(c) == (4449796096 - 16160 * 7168) * 2
    assert flops_moe.held_expert_bytes(c) == 4 * 16 * 44040192 * 2
    # what every token meets: 1.17 + 4 x 0.47 GFLOP a lane-step (the
    # ISSUE's 0.51 counts half a routed expert a token on top)
    assert flops_moe.token_matmul_params(c) == 5 * 187105280 + 396361728 \
        + 4 * (1835008 + 44040192)
    assert flops_moe.expand_flops(c) == 33554432.0     # 33.5 MFLOP
    assert flops_moe.pair_flops(c) == 2 * 320 * 128
    # one document of 3 tokens: 1 + 2 + 3 pairs, 5 layers
    assert flops_moe.attention_flops(c, [3]) == 6 * 81920 * 5
    assert flops_moe.core_flops(c, 512, 1.0) == 5 * (33554432 + 512 * 81920)
    assert flops_moe.core_bytes(c, 512, 1, 512) == 5 * (
        512 * 576 * 2 + 512 * (128 * 192 * 2 + 128 * 128 * 4))
    # the latent cache of one row: 11.80 MB, a 71st of full keys and values
    assert 5 * 2048 * 576 * 2 == 11796480
    assert 128 * (192 + 128) * 2 == 81920 and 81920 // (576 * 2) == 71


# -- the configuration --------------------------------------------------------

def test_configuration_holds_the_catalog_rows_numbers_key_for_key():
    if not CATALOG.is_file():
        pytest.skip("the catalog of architectures is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "DeepSeek-V3")
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if CONFIG.get(k, "absent") != v)
    assert differ == sorted(CONFIG["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"])
    assert CONFIG["published"] == {k: row["config"][k] for k in differ}


def test_reduced_names_the_cuts_and_no_width():
    """What ``test_bm_manifest.py::test_config_entry`` holds for every
    configuration, with the contract's own rule for a width: that test
    refuses every key that CONTAINS ``hidden``, so it fails for this
    configuration's depth key ``num_hidden_layers`` (PERF.md §7, finding
    11: a ``benchmark`` PR's to mend)."""
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "deepseek_v3_ep16_share")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == "benchmark/configs/deepseek_v3_ep16_share.json"
    assert CONFIG["name"] == entry["name"]
    assert entry["reduced"] == CONFIG["reduced"] and len(entry["reduced"]) <= 16
    assert entry["name"] in {w["config"] for w in MANIFEST["workloads"]}
    width = re.compile(
        r"(_dim|_rank)$|(hidden|intermediate|latent|state|proj\w*|head\w*)"
        r"_size$|^(emb_sz|n_hid|num_experts_per_tok|expand\w*)$")
    for key in entry["reduced"]:
        assert not width.search(key), key
    assert width.search("hidden_size") and width.search("kv_lora_rank") \
        and width.search("moe_intermediate_size") \
        and width.search("qk_rope_head_dim")
    # every published width unchanged at the top level
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"]) == \
        (7168, 128)
    assert [CONFIG[k] for k in (
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim")] == [1536, 512, 128, 64, 128]
    assert (CONFIG["intermediate_size"], CONFIG["moe_intermediate_size"]) \
        == (18432, 2048)
    assert (CONFIG["experts_held"], CONFIG["n_group"], CONFIG["topk_group"],
            CONFIG["num_experts_per_tok"], CONFIG["routed_scaling_factor"],
            CONFIG["scoring_func"]) == (
        {"first": 0, "count": 16, "of": 256}, 8, 4, 8, 2.5, "sigmoid")
    assert CONFIG["deployment"]["chips_that_share_a_layer"] == 16
    assert set(CONFIG["assumed"]) >= {
        "vocabulary", "pooling", "no_mtp", "weights", "serve.batch_size"}


def test_the_pre_rule_metric_still_agrees_with_its_file():
    """What ``test_bm_pre_rule_passes.py::
    test_the_file_and_the_manifest_entry_agree`` holds, but for what
    that test pins and the contract has every later PR change (PERF.md
    §7, finding 12): the entry's PLACE (entries this PR appends come
    after it) and its cells (this PR's cell runs the pre-rules too and
    joins them). It is otherwise as PR 29 left it."""
    from benchmark.harness import cell as cells

    name = "pre_rule_passes_run_pct"
    spec, _ = cells.load_layer_reader(name)
    names = [m["name"] for m in MANIFEST["per_layer"]]
    entry = MANIFEST["per_layer"][names.index(name)]
    assert names[names.index(name) + 1:] == [m["name"] for m in NEW_METRICS]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("%", "lower", "program_counter", "tokenise",
                                "docs_per_s")
    assert spec["reader"] == "span_attr_ratio" and "complement" not in spec
    assert (spec["span"], spec["num"], spec["den"]) == (
        "engine.text_rules", "rule_passes_run", "rule_passes")
    assert entry["workloads"] == ["lstm_bulk_mixed", "qrnn_bulk_mixed",
                                  "granite_bulk_mixed", CELL]


def test_the_program_reads_the_file_as_the_share_it_states():
    from code_intelligence_tpu.models import build_encoder, make_config

    enc = build_encoder(make_config(
        "deepseek_v3", CONFIG, kv_positions=CONFIG["serve"]["kv_positions"],
        state_dtype=CONFIG["state_dtype"]))
    cfg = enc.config
    assert (cfg.n_routed_experts, cfg.experts_held) == (256, (0, 16))
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.n_moe_layers) == (5, 1, 4)
    assert enc.out_dim == 7168
    assert enc.state_bytes_per_row(2048) == 11796480
    cell = json.loads(
        (ROOT / "benchmark/cells" / f"{CELL}.json").read_text())
    assert cell["reduced"] == CONFIG["reduced"]
    assert cell["driver"] == "bulk_moe"


@pytest.mark.parametrize("metric", NEW_METRICS,
                         ids=[m["name"] for m in NEW_METRICS])
def test_new_metrics_move_docs_per_s_in_this_cell_only(metric):
    assert metric["moves"] == "docs_per_s"
    assert len(NEW_METRICS) == 7
    if metric["name"].endswith("_roofline"):
        assert (metric["unit"], metric["layer"]) == ("%", "kernels")
