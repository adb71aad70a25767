"""LongCat-Flash's share through the benchmark, tiny, on the CPU: a whole
run of its driver against its plain reference with documents that cross
chunk programs through four latent caches, a thin share of the experts
and identity experts in the router; every must-fail control reads not
correct; a program without the architecture fails at once; the new
per-layer readers on known inputs; the arithmetic of
``harness/flops_longcat.py`` against a hand count; the configuration
file against the catalog row. Pins no entry's place in the manifest and
no list's exact contents: the next configuration appends after these."""

import json
import re
from pathlib import Path

import pytest

import bm_util
from benchmark import run
from benchmark.harness import flops_longcat
from benchmark.harness.spans import HostSpan, SpanLog

ROOT = bm_util.ROOT
TRACE = Path(__file__).parent / "data" / "tiny.xplane.pb"
CONFIG_NAME = "longcat_flash_ep32_share"
CONFIG = json.loads(
    (ROOT / f"benchmark/configs/{CONFIG_NAME}.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "longcat_bulk_long_tail"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = ["num_layers", "n_routed_experts", "vocab_size"]
NEW = ["scmoe_fwd_roofline", "scmoe_experts_gmm_roofline",
       "latent_core_roofline", "zero_choice_pct"]
SHARED = ["tokenize_share_pct", "tokenize_us_per_doc",
          "text_rules_share_pct", "text_rules_us_per_doc",
          "pre_rule_passes_run_pct", "compiles_in_window",
          "encoder_fwd_us_per_token", "device_idle_pct.bulk",
          "group_dispatch_share_pct", "device_wait_share_pct",
          "padded_lane_pct", "attention_share_pct",
          "carried_state_mb_per_row", "latent_core_share_pct",
          "routed_experts_share_pct", "expert_dispatch_combine_share_pct",
          "expert_rounds_per_layer_program", "padded_device_time_pct",
          "narrow_program_time_pct", "narrow_lane_cost_ratio",
          "padded_lane_run_pct", "program_enqueue_share_pct",
          "group_self_ms"]
BY_NAME = {m["name"]: m for m in MANIFEST["per_layer"]}

TINY = {
    "vocab_size": 600, "hidden_size": 64, "ffn_hidden_size": 96,
    "expert_ffn_hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "n_routed_experts": 4, "zero_expert_num": 16,
    "zero_expert_type": "identity", "moe_topk": 4,
    "routed_scaling_factor": 6, "attention_method": "MLA",
    "rms_norm_eps": 1e-5, "rope_theta": 10000000,
    "experts_held": {"first": 8, "count": 4, "of": 32}}
SUFFIXES = ("", "_carried")
LIMITS = {f"rel_rms_{t}{s}": 4e-6 for t in ("mean", "max", "last")
          for s in SUFFIXES}
LIMITS.update(nonfinite=0, nonfinite_rows=0)


def tiny_benchmark(tmp: Path, per_layer=()) -> Path:
    """``bm_util``'s copy of the benchmark with a tiny share, its cell
    and a manifest that names them, as files. The mix's documents run to
    96 tokens: up to three chunk programs of 32 through four caches."""
    bench = bm_util.tiny_benchmark(tmp)
    bm_util.write(bench / "configs" / "tiny_scmoe.json", dict(
        TINY, name="tiny_scmoe", architecture="longcat_flash",
        dtype="float32", state_dtype="float32",
        serve={"scheduler": "groups", "batch_size": 4,
               "buckets": [16, 32], "kv_positions": 128},
        weights={"dist": "student_t", "df": 4}, reduced=[]))
    bm_util.write(bench / "cells" / "tiny_scmoe_cell.json", {
        "name": "tiny_scmoe_cell", "config": "tiny_scmoe",
        "mix": "tiny_docs", "chips": 1, "driver": "bulk_scmoe",
        "reduced": [], "check": {"sample": 6, "block_rows": 1,
                                 "limits": LIMITS}})
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": "tiny_scmoe", "source": "test",
                            "file": "benchmark/configs/tiny_scmoe.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny_scmoe_cell",
                              "config": "tiny_scmoe",
                              "traffic": "tiny_docs", "chips": 1,
                              "why": "test"}]
    manifest["per_layer"] = [dict(m, moves="docs_per_s") for m in per_layer]
    bm_util.write(tmp / "BENCHMARK.json", manifest)
    return bench


def main(tmp, *extra, **kw):
    return run.main(["--workload", "tiny_scmoe_cell", "--seed",
                     str(2**31 + 43), "--seconds", "0.2", *extra],
                    root=tmp, **kw)


@pytest.fixture
def gate(monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)


def numbers(line):
    return {c["name"]: c["value"] for c in line["compared"]}


def test_cell_runs_and_agrees_with_its_reference(tmp_path, gate):
    """The mix's longest document (96 tokens) takes three chunk programs
    of 32: four latent caches, the router's third score, the held
    experts' part and the identity experts are inside the comparison, at
    float32 tightness."""
    per_layer = [{k: BY_NAME[n][k] for k in (
        "name", "unit", "better", "source", "layer")} for n in SHARED + NEW]
    tiny_benchmark(tmp_path, per_layer)
    line = main(tmp_path, "--trace", "0")
    assert line["correct"] and line["failed"] == 0, line["compared"]
    got = numbers(line)
    assert set(LIMITS) <= set(got)
    assert "rel_rms_mean_past_window" not in got  # no layer has a window
    assert got["rel_rms_mean_carried"] < 2e-6
    assert line["counters"]["compiles_in_window"] == 0
    assert not {"expert_rows_per_program", "attention_kernel_layers"} \
        & set(line["counters"])

    traced = main(tmp_path, "--trace", "1")
    assert traced["correct"]
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    # multi-chunk groups: four caches of 128 positions x (16 + 4) float32
    assert metrics["carried_state_mb_per_row"] == pytest.approx(
        4 * 128 * 20 * 4 / 1e6)
    # 16 of the router's 48 outputs are identity experts
    assert 20 < metrics["zero_choice_pct"] < 50
    # a thin share: its assignments fit one round, where any land
    assert 0 < metrics["expert_rounds_per_layer_program"] <= 1
    assert 0 < metrics["padded_lane_pct"] < 100
    # the held experts' load, among the counters
    assert traced["counters"]["expert_rows_per_program"] > 0
    assert traced["counters"]["expert_load_max_over_mean"] >= 1
    # and the latent sublayers on the Pallas core: none on the CPU
    assert traced["counters"]["attention_kernel_layers"] == 0
    # no device plane in a CPU capture: the scope readers find nothing
    # and their metrics are left out, not reported as zero
    assert not {"scmoe_fwd_roofline", "scmoe_experts_gmm_roofline",
                "latent_core_roofline", "latent_core_share_pct",
                "routed_experts_share_pct", "attention_share_pct"} \
        & set(metrics)


@pytest.mark.parametrize("control,overrides,floor", [
    ("int8_weights", {"precision": "int8"}, 1e-3),
    ("bfloat16_caches", {"state_dtype": "bfloat16"}, 1e-4),
    ("bfloat16_router", {"router_dtype": "bfloat16"}, 1e-5),
    ("dropped_caches", {"latent_cache": "dropped"}, 1e-2),
    ("no_factor", {"routed_scaling_factor": "1"}, 1e-2),
    ("no_identity_experts", {"zero_experts": "dropped"}, 1e-2),
    ("early_shortcut", {"shortcut": "early"}, 1e-3),
    ("no_multipliers", {"mla_scale": "off"}, 1e-2),
])
def test_controls_are_not_correct(tmp_path, gate, control, overrides,
                                  floor):
    """float32 sound runs sit below 2e-6; each control far above."""
    from code_intelligence_tpu.models import LongcatFlashEncoder
    from code_intelligence_tpu.ops import moe

    real = (moe.route, moe.zero_experts, moe.swiglu,
            LongcatFlashEncoder._moe)
    tiny_benchmark(tmp_path)
    line = main(tmp_path, overrides=overrides)
    assert (moe.route, moe.zero_experts, moe.swiglu,
            LongcatFlashEncoder._moe) == real
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if not c["inside"]}
    assert any(name.endswith("_carried") for name in bad), bad
    assert numbers(line)["rel_rms_mean_carried"] > floor


def test_on_a_program_without_the_architecture_the_cell_fails_at_once(
        tmp_path, gate, monkeypatch):
    """The parent commit has no ``longcat_flash``: ``make_config``
    raises before a weight is made, and nothing hangs."""
    from code_intelligence_tpu.models import contract

    tiny_benchmark(tmp_path)
    monkeypatch.delitem(contract.ENCODERS, "longcat_flash")
    with pytest.raises(ValueError,
                       match="unknown architecture 'longcat_flash'"):
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


def _steps(rows_by_chunk):
    return sum(r * 512 * (i + 1) for i, r in enumerate(rows_by_chunk))


LONG = [16] * 11 + [8] * 7 + [4] * 7 + [2] * 7    # the mix's long group
SHORT = [16, 16, 16, 8, 8, 2]                     # and its short one
GROUPS = [
    HostSpan("engine.group", 0, 1, {
        "rows": 16, "batch": 16, "bucket": 512, "chunks": 32,
        "valid_tokens": 118484, "lane_steps": 16 * 512 * 32,
        "lane_steps_run": 512 * sum(LONG), "cache_steps_run": _steps(LONG),
        "window_steps_run": 0, "state_bytes": 16 * 150994944,
        "kv_positions": 16384, "kv_positions_window": 0}),
    HostSpan("engine.group", 1, 2, {
        "rows": 16, "batch": 16, "bucket": 512, "chunks": 6,
        "valid_tokens": 25138, "lane_steps": 16 * 512 * 6,
        "lane_steps_run": 512 * sum(SHORT), "cache_steps_run": _steps(SHORT),
        "window_steps_run": 0, "state_bytes": 16 * 37748736,
        "kv_positions": 4096, "kv_positions_window": 0})]
VALID = 143622
ROUTED = 4 * VALID // 4          # a quarter of a choice a token a layer
FLUSHES = [
    HostSpan("engine.finalize", 2, 3, {
        "groups": 2, "routed_rows": ROUTED, "expert_rows_max": 120.0,
        "expert_rows_mean": ROUTED / (38 * 4 * 16), "moe_programs": 38,
        "expert_rounds_mean": 1.0, "zero_choices": 4 * 4 * VALID,
        "valid_choices": 4 * 12 * VALID, "attention_kernel_layers": 8.0}),
    HostSpan("engine.finalize", 3, 4, {
        "groups": 1, "routed_rows": 10, "expert_rows_max": 2.0,
        "expert_rows_mean": 10 / (4 * 16), "moe_programs": 1,
        "expert_rounds_mean": 0.75, "zero_choices": 100,
        "valid_choices": 1200, "attention_kernel_layers": 0.0}),
    HostSpan("engine.finalize", 4, 5, {"groups": 1})]   # an AWD flush
DOCS = [HostSpan("engine.tokenize", 0, 0, {"n_tokens": n})
        for n in (16384, 5114, 348)]


def test_layer_readers_on_known_inputs(capsys):
    ctx, load = _reader_ctx(GROUPS + FLUSHES + DOCS,
                            {"jit_fwd_b16_l512": [0.5, 0.25]})
    dot = [r"(^|/)dot_general"]     # the recorded trace's one named scope
    dot_s = 3.644766e-06

    spec, read = load("zero_choice_pct")
    assert read(ctx, spec) == pytest.approx(
        100 * (16 * VALID + 100) / (48 * VALID + 1200))
    spec, read = load("expert_rounds_per_layer_program")
    assert read(ctx, spec) == pytest.approx((1.0 * 38 + 0.75) / 39)
    spec, read = load("carried_state_mb_per_row")
    assert read(ctx, spec) == pytest.approx((150.994944 + 37.748736) / 2)

    for name in ("routed_experts_share_pct", "latent_core_share_pct",
                 "expert_dispatch_combine_share_pct"):
        spec, read = load(name)
        assert read(ctx, spec) is None     # no such scope in that trace
        assert read(ctx, dict(spec, scopes=dot)) == \
            pytest.approx(100 * dot_s / 0.75)

    # the latent core over EIGHT sublayers, counted from this
    # configuration: 16.8 MFLOP to expand a position, 40,960 a pair
    spec, read = load("latent_core_roofline")
    assert spec["flops"] == "flops_longcat"
    assert read(ctx, spec) is None
    value = read(ctx, dict(spec, scopes=dot))
    steps = _steps(LONG) + _steps(SHORT)
    rows = sum(LONG) + sum(SHORT)
    need = 8 * steps * (16777216 + 512 * 40960)
    moved = 8 * (steps * 576 * 2
                 + rows * 512 * (64 * 192 * 2 + 64 * 128 * 4))
    assert need / 197e12 > moved / 819e9
    assert value == pytest.approx(100 * (need / 197e12) / dot_s)
    assert "compute-bound" in capsys.readouterr().out

    spec, read = load("scmoe_fwd_roofline")
    assert (spec["reader"], spec["flops"]) == (
        "reglu_moe_fwd_roofline", "flops_longcat")
    need = flops_longcat.encoder_flops(
        CONFIG, VALID, ROUTED + 10, [16384, 5114, 348])
    assert read(ctx, spec) == pytest.approx(100 * (need / 197e12) / 0.75)

    spec, read = load("scmoe_experts_gmm_roofline")
    assert (spec["reader"], spec["flops"]) == (
        "held_experts_gmm_roofline", "flops_longcat")
    assert read(ctx, spec) is None
    value = read(ctx, dict(spec, scopes=dot))
    need = 2 * (ROUTED + 10) * 37748736
    moved = 39 * 4 * 16 * 37748736 * 2
    # a thin share: the held experts are read for few rows, memory-bound
    assert moved / 819e9 > need / 197e12
    assert value == pytest.approx(100 * (moved / 819e9) / dot_s)
    assert "memory-bound" in capsys.readouterr().out

    # a program without the spans, counters or scopes gives nothing, not
    # an error: the parent commit's traced run of another cell
    bare = [HostSpan(s.name, s.start_unix, s.end_unix, {
        k: v for k, v in s.attrs.items()
        if k not in ("zero_choices", "valid_choices")}) for s in FLUSHES]
    parent, _ = _reader_ctx(GROUPS + bare + DOCS, {"jit_fwd": [0.5]})
    spec, read = load("zero_choice_pct")
    assert read(parent, spec) is None
    empty, _ = _reader_ctx([], {}, path=None)
    for name in NEW:
        spec, read = load(name)
        assert read(empty, spec) is None, name


# -- the arithmetic -----------------------------------------------------------

def test_flops_longcat_against_a_hand_count():
    c, f = CONFIG, flops_longcat
    assert f.latent_sublayers(c) == 8
    assert f.mla_params(c) == 6144 * 1536 + 1536 * 12288 + 6144 * 576 \
        + 512 * 16384 + 8192 * 6144 == 90570752
    assert f.dense_ffn_params(c) == 3 * 6144 * 12288 == 226492416
    assert f.router_width(c) == 768
    assert f.router_params(c) == 6144 * 768 == 4718592
    assert f.expert_params(c) == 3 * 6144 * 2048 == 37748736
    assert f.norm_params(c) == 2 * (2 * 6144 + 1536 + 512) == 28672
    # ISSUE 43's sizing: a layer outside its experts, a layer as held
    assert f.layer_params(c) - 16 * f.expert_params(c) == 638873600
    assert f.layer_params(c) == 1242853376
    assert f.embedding_params(c) == 16384 * 6144 == 100663296
    assert f.held_params(c) == 5072082944
    assert f.held_params(c) * 2 == 10144165888                 # 10.14 GB
    assert c["parameters"]["held"] == f.held_params(c)
    assert c["parameters"]["layer_as_held"] == f.layer_params(c)
    assert c["parameters"]["held_bytes_bfloat16"] == 2 * f.held_params(c)
    assert 0.25 < 10144165888 / 16e9 < 0.7
    assert f.weight_bytes(c) == (5072082944 - 100663296) * 2
    assert f.held_expert_bytes(c) == 4 * 16 * 37748736 * 2 == 4831838208
    assert f.token_matmul_params(c) == 4 * (
        2 * 90570752 + 2 * 226492416 + 4718592) == 2555379712
    assert f.pair_flops(c) == 2 * 64 * 320 == 40960
    assert f.expand_flops(c) == 2 * 512 * 64 * 256 == 16777216
    assert f.routed_flops(c, 10) == 20 * 37748736
    # one document of 3 tokens: 1 + 2 + 3 pairs in every sublayer
    assert f.attention_flops(c, [3]) == 6 * 40960 * 8
    assert f.core_flops(c, 512, 2.0) == 8 * 2 * (16777216 + 512 * 40960)
    assert f.core_bytes(c, 512, 1, 512) == 8 * (
        512 * 576 * 2 + 512 * (64 * 192 * 2 + 64 * 128 * 4))
    # the published model whole: 560 B parameters
    whole = dict(c, num_layers=28, n_routed_experts=512, vocab_size=131072)
    total = f.held_params(whole) + f.embedding_params(whole)  # + the head
    assert round(total / 1e9, 1) == 560.7
    # a call of the mix
    from benchmark.harness import traffic

    mix = json.loads((ROOT / "benchmark/mixes"
                      / "issue_threads_long_tail_c32.json").read_text())
    grid = traffic.length_grid(mix["length"], 32).tolist()
    tokens = sum(grid)
    assert tokens == 143622
    parts = (2.0 * f.token_matmul_params(c) * tokens,
             f.attention_flops(c, grid), f.routed_flops(c, tokens))
    assert [round(p / 1e12, 1) for p in parts] == [734.0, 197.3, 10.8]
    assert f.encoder_flops(c, tokens, tokens, grid) == sum(parts)
    # the state of one row at 16,384 tokens
    assert 8 * 16384 * 576 * 2 == 150994944 \
        == c["parameters"]["state_bytes_a_row_at_16384"]


# -- the configuration --------------------------------------------------------

def test_configuration_holds_the_catalog_rows_numbers_key_for_key():
    if not CATALOG.is_file():
        pytest.skip("the catalog of architectures is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "LongCat-Flash-Chat")
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if CONFIG.get(k, "absent") != v)
    assert differ == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    assert CONFIG["published"] == {k: row["config"][k] for k in differ}
    assert row["config"]["num_layers"] == 28


def test_reduced_names_the_cuts_and_no_width():
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == CONFIG_NAME)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{CONFIG_NAME}.json"
    assert CONFIG["name"] == entry["name"]
    assert entry["reduced"] == CONFIG["reduced"] == REDUCED
    assert entry["name"] in {w["config"] for w in MANIFEST["workloads"]}
    assert len(entry["why"]) <= 200
    width = re.compile(
        r"(_dim|_rank)$|(hidden|intermediate|latent|state|proj\w*|head\w*)"
        r"_size$|^(emb_sz|n_hid|num_experts_per_tok|moe_topk|expand\w*)$")
    for key in entry["reduced"]:
        assert not width.search(key), key
        # the manifest's own test refuses a key that CONTAINS "hidden":
        # the published depth key here does not (PERF.md §7, finding 11)
        assert "hidden" not in key
    assert width.search("expert_ffn_hidden_size")
    # every published width unchanged at the top level
    assert [CONFIG[k] for k in (
        "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "moe_topk",
        "zero_expert_num", "routed_scaling_factor", "rope_theta")] == [
        6144, 12288, 2048, 64, 1536, 512, 128, 64, 128, 12, 256, 6,
        10000000]
    # the floors: four layers, at least 8 experts, an eighth of the words
    assert CONFIG["num_layers"] >= 4 and CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= 131072
    assert CONFIG["experts_held"] == {"first": 0, "count": 16, "of": 512}
    assert CONFIG["deployment"]["chips_that_share_a_layer"] == 32
    assert set(CONFIG["assumed"]) >= {
        "keys", "n_routed_experts", "norm_topk_prob", "hidden_act",
        "rotary_pairing", "mla_scale", "shortcut", "vocabulary", "pooling",
        "weights", "dtype", "serve.batch_size", "serve.kv_positions"}


def test_the_program_reads_the_file_as_the_share_it_states():
    from code_intelligence_tpu.models import build_encoder, make_config

    serve = CONFIG["serve"]
    enc = build_encoder(make_config(
        "longcat_flash", CONFIG, kv_positions=serve["kv_positions"],
        state_dtype=CONFIG["state_dtype"]))
    cfg = enc.config
    assert (cfg.n_routed_experts, cfg.experts_held) == (512, (0, 16))
    assert (cfg.num_layers, cfg.n_sublayers, cfg.zero_expert_num) == (
        4, 8, 256)
    assert enc.out_dim == 6144
    assert enc.state_bytes_per_row(16384) == 150994944
    assert serve["scheduler"] == "groups" and serve["batch_size"] in (8, 16)
    assert serve["buckets"] == [64, 128, 256, 512]
    cell = json.loads(
        (ROOT / "benchmark/cells" / f"{CELL}.json").read_text())
    assert cell["reduced"] == CONFIG["reduced"]
    assert cell["driver"] == "bulk_scmoe"
    assert (cell["check"]["sample"], cell["check"]["block_rows"]) == (8, 1)
    limits = cell["check"]["limits"]
    assert {"rel_rms_mean", "rel_rms_max", "rel_rms_mean_carried",
            "rel_rms_max_carried", "nonfinite", "nonfinite_rows"} \
        <= set(limits)
    # every limit, and each number left without one, is written with
    # its reason; no limit is left at a placeholder
    why = cell["check"]["why"]
    assert set(limits) - {"nonfinite", "nonfinite_rows"} <= set(why)
    assert all(0 <= v < 0.5 for v in limits.values())
    for name in ("rel_rms_last", "rel_rms_last_carried"):
        assert name in limits or "NOT LIMITED" in why[name]


# -- the manifest ---------------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_new_metrics_move_docs_per_s_in_this_cell(name):
    metric = BY_NAME[name]
    assert metric["moves"] == "docs_per_s"
    assert CELL in metric["workloads"]
    if name.endswith("_roofline"):
        assert (metric["unit"], metric["layer"]) == ("%", "kernels")


OPS = ["jit(fwd)/moe_3/router/dot_general", "jit(fwd)/moe_3/dispatch/sort",
       "jit(fwd)/moe_3/experts/mul", "jit(fwd)/moe_3/combine/gather",
       "jit(fwd)/moe_3/zero_experts/mul", "ragged-dot-none.4",
       "jit(fwd)/mlp_6/dot_general", "jit(fwd)/attention_7/mla_core/while",
       "jit(fwd)/attention_7/o_proj/dot_general", "jit(fwd)/final_norm/mul"]


@pytest.mark.parametrize("name,read", [
    ("routed_experts_share_pct", OPS[:6]),
    ("expert_dispatch_combine_share_pct", [OPS[1], OPS[3]]),
    ("latent_core_share_pct", [OPS[7]]),
    ("attention_share_pct", OPS[7:9]),
])
def test_the_accepted_shares_read_this_models_scopes(name, read):
    """What the accepted shares this cell joins read of THIS model's
    scope paths. The shortcut branch's share of the forward has no
    metric of its own: ``routed_experts_share_pct`` takes every scope
    under ``moe_<l>`` (the identity experts' too) and the grouped
    matmuls' kernels, and none of the sublayers'."""
    spec = json.loads(
        (ROOT / "benchmark/layer_metrics" / f"{name}.json").read_text())
    assert spec["reader"] == "scope_time_share"
    assert [op for op in OPS
            if any(re.search(s, op) for s in spec["scopes"])] == read


@pytest.mark.parametrize("name", SHARED + ["docs_per_s"])
def test_the_cell_joins_the_metrics_its_spans_and_scopes_carry(name):
    entry = BY_NAME.get(name) or next(
        m for m in MANIFEST["end_to_end"] if m["name"] == name)
    assert CELL in entry["workloads"]


def test_the_cell_entry_says_why():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG_NAME, "issue_threads_long_tail_c32", 1)
    assert len(entry["why"]) <= 200 and "identity" in entry["why"]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 0
