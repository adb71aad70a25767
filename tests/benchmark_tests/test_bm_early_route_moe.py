"""SmallThinker's pipeline stage through the benchmark, tiny, on the CPU:
a whole run of its driver against its plain reference with documents
longer than a tiny window, through rings that wrap, every expert held
and six a token; every must-fail control reads not correct; a program
without the architecture fails at once; the new per-layer readers on
known inputs; the arithmetic of ``harness/flops_smallthinker.py``
against ISSUE 39's table; the configuration file against the catalog
row. Pins no entry's place in the manifest and no list's exact contents:
the next configuration appends after these."""

import json
import re
from pathlib import Path

import pytest

import bm_util
from benchmark import run
from benchmark.harness import flops_afmoe, flops_smallthinker
from benchmark.harness.spans import HostSpan, SpanLog

ROOT = bm_util.ROOT
TRACE = Path(__file__).parent / "data" / "tiny.xplane.pb"
CONFIG_NAME = "smallthinker_21ba3b_pp7_stage0"
CONFIG = json.loads(
    (ROOT / f"benchmark/configs/{CONFIG_NAME}.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "smallthinker_bulk_long_tail"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = ["num_hidden_layers", "rope_layout", "sliding_window_layout"]
NEW = ["reglu_moe_fwd_roofline", "held_experts_gmm_roofline",
       "routed_experts_share_pct", "expert_dispatch_combine_share_pct",
       "early_route_share_pct", "expert_rounds_per_layer_program"]
SHARED = ["tokenize_share_pct", "tokenize_us_per_doc",
          "text_rules_share_pct", "text_rules_us_per_doc",
          "pre_rule_passes_run_pct", "compiles_in_window",
          "encoder_fwd_us_per_token", "device_idle_pct.bulk",
          "group_dispatch_share_pct", "device_wait_share_pct",
          "padded_lane_pct", "attention_share_pct",
          "carried_state_mb_per_row", "window_core_roofline",
          "global_core_roofline", "window_core_share_pct",
          "global_core_share_pct", "window_keys_met_pct",
          "padded_device_time_pct", "narrow_program_time_pct",
          "narrow_lane_cost_ratio", "padded_lane_run_pct",
          "program_enqueue_share_pct", "group_self_ms"]
BY_NAME = {m["name"]: m for m in MANIFEST["per_layer"]}

PERIOD = [0, 1, 1, 1]
TINY = {
    "vocab_size": 600, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 8,
    "moe_ffn_hidden_size": 16, "moe_num_primary_experts": 16,
    "moe_num_active_primary_experts": 6,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rope_layout": PERIOD, "sliding_window_layout": PERIOD,
    "sliding_window_size": 16, "rms_norm_eps": 1e-6, "rope_theta": 1500000,
    "rope_scaling": None,
    "experts_held": {"first": 0, "count": 16, "of": 16}}
SUFFIXES = ("", "_carried", "_past_window")
LIMITS = {f"rel_rms_{t}{s}": 4e-6 for t in ("mean", "max", "last")
          for s in SUFFIXES}
LIMITS.update(nonfinite=0, nonfinite_rows=0)


def tiny_benchmark(tmp: Path, per_layer=()) -> Path:
    """``bm_util``'s copy of the benchmark with a tiny stage, its cell
    and a manifest that names them, as files. The mix's documents run to
    96 tokens: chunks of 32 under a window of 16, rings of 64 slots."""
    bench = bm_util.tiny_benchmark(tmp)
    bm_util.write(bench / "configs" / "tiny_early.json", dict(
        TINY, name="tiny_early", architecture="smallthinker",
        dtype="float32", state_dtype="float32",
        serve={"scheduler": "groups", "batch_size": 4,
               "buckets": [16, 32], "kv_positions": 128},
        weights={"dist": "student_t", "df": 4}, reduced=[]))
    bm_util.write(bench / "cells" / "tiny_early_cell.json", {
        "name": "tiny_early_cell", "config": "tiny_early",
        "mix": "tiny_docs", "chips": 1, "driver": "bulk_early_route_moe",
        "reduced": [], "check": {"sample": 6, "block_rows": 1,
                                 "limits": LIMITS}})
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": "tiny_early", "source": "test",
                            "file": "benchmark/configs/tiny_early.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny_early_cell",
                              "config": "tiny_early",
                              "traffic": "tiny_docs", "chips": 1,
                              "why": "test"}]
    manifest["per_layer"] = [dict(m, moves="docs_per_s") for m in per_layer]
    bm_util.write(tmp / "BENCHMARK.json", manifest)
    return bench


def main(tmp, *extra, **kw):
    return run.main(["--workload", "tiny_early_cell", "--seed",
                     str(2**31 + 39), "--seconds", "0.2", *extra],
                    root=tmp, **kw)


@pytest.fixture
def gate(monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)


def numbers(line):
    return {c["name"]: c["value"] for c in line["compared"]}


def test_cell_runs_and_agrees_with_its_reference(tmp_path, gate):
    """The mix's longest document (96 tokens) takes three chunk programs
    of 32 under a window of 16: ring, mask, growing cache, the early
    sort and the rounds are inside the comparison, at float32
    tightness."""
    per_layer = [{k: BY_NAME[n][k] for k in (
        "name", "unit", "better", "source", "layer")} for n in SHARED + NEW]
    tiny_benchmark(tmp_path, per_layer)
    line = main(tmp_path, "--trace", "0")
    assert line["correct"] and line["failed"] == 0, line["compared"]
    got = numbers(line)
    assert set(LIMITS) <= set(got)   # some sampled row passed the window
    assert got["rel_rms_mean_past_window"] < 2e-6
    assert line["counters"]["compiles_in_window"] == 0
    # no spans in an untraced run: the held experts' load is left out
    assert "expert_rows_per_program" not in line["counters"]

    traced = main(tmp_path, "--trace", "1")
    assert traced["correct"]
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    # multi-chunk groups: the global layer at 64 or 128 positions, three
    # rings of 32 + 32 slots, keys and values of 2 heads x 8 float32
    per_slot = 2 * 2 * 8 * 4 / 1e6
    assert (64 + 3 * 64) * per_slot \
        <= metrics["carried_state_mb_per_row"] <= (128 + 3 * 64) * per_slot
    assert 0 < metrics["window_keys_met_pct"] < 100
    assert 0 < metrics["padded_lane_pct"] < 100
    # every expert held, 6 a token: more than one round, at most six
    assert 1 < metrics["expert_rounds_per_layer_program"] <= 6
    # the held experts' load, among the counters: 6 rows a valid token
    # over 16 experts, and a busiest expert above the mean
    assert traced["counters"]["expert_rows_per_program"] > 6 / 16
    assert traced["counters"]["expert_load_max_over_mean"] > 1
    # no device plane in a CPU capture: the scope readers find nothing
    # and their metrics are left out, not reported as zero
    assert not {"reglu_moe_fwd_roofline", "held_experts_gmm_roofline",
                "routed_experts_share_pct", "early_route_share_pct",
                "expert_dispatch_combine_share_pct", "window_core_roofline",
                "global_core_roofline", "attention_share_pct"} & set(metrics)


@pytest.mark.parametrize("control,overrides,floor,where", [
    ("int8_weights", {"precision": "int8"}, 1e-3, "_carried"),
    ("bfloat16_caches", {"state_dtype": "bfloat16"}, 1e-4, "_carried"),
    ("late_router", {"early_router": "off"}, 1e-2, "_carried"),
    ("sigmoid_weights", {"router_score": "sigmoid"}, 1e-3, "_carried"),
    ("swiglu_experts", {"expert_act": "silu"}, 1e-2, "_carried"),
    ("no_window", {"sliding_window": "off"}, 1e-3, "_past_window"),
    ("zeroed_caches", {"caches": "zeroed"}, 1e-2, "_carried"),
    ("no_rotary", {"rope": "off"}, 1e-3, "_carried"),
    ("rotary_everywhere", {"rope": "all"}, 1e-3, "_carried"),
])
def test_controls_are_not_correct(tmp_path, gate, control, overrides, floor,
                                  where):
    """float32 sound runs sit below 2e-6; each control far above, by a
    limit on the rows it is aimed at."""
    from code_intelligence_tpu.ops import moe

    real = moe.route, moe.routed_experts
    tiny_benchmark(tmp_path)
    line = main(tmp_path, overrides=overrides)
    assert (moe.route, moe.routed_experts) == real
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
    """The parent commit has no ``smallthinker``: ``make_config`` raises
    before a weight is made, and nothing hangs."""
    from code_intelligence_tpu.models import contract

    tiny_benchmark(tmp_path)
    monkeypatch.delitem(contract.ENCODERS, "smallthinker")
    with pytest.raises(ValueError,
                       match="unknown architecture 'smallthinker'"):
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
        "state_bytes": 16 * 123731968, "kv_positions": 16384,
        "kv_positions_window": 4608}),
    HostSpan("engine.group", 1, 2, {
        "rows": 16, "batch": 16, "bucket": 512, "chunks": 6,
        "valid_tokens": 25138, "lane_steps": 16 * 512 * 6,
        "lane_steps_run": 512 * sum(SHORT),
        "cache_steps_run": _steps(SHORT, 1 << 30),
        "window_steps_run": _steps(SHORT, 4096),
        "state_bytes": 16 * 67108864, "kv_positions": 4096,
        "kv_positions_window": 4096})]
ROUTED = 8 * 6 * 143622         # every assignment of every valid token
FLUSHES = [
    HostSpan("engine.finalize", 2, 3, {
        "groups": 2, "routed_rows": ROUTED, "expert_rows_max": 700.0,
        "expert_rows_mean": ROUTED / (38 * 8 * 64), "moe_programs": 38,
        "expert_rounds_mean": 5.25}),
    HostSpan("engine.finalize", 3, 4, {
        "groups": 1, "routed_rows": 48, "expert_rows_max": 1.0,
        "expert_rows_mean": 48 / (8 * 64), "moe_programs": 2,
        "expert_rounds_mean": 1.0}),
    HostSpan("engine.finalize", 4, 5, {"groups": 1})]   # an AWD flush
DOCS = [HostSpan("engine.tokenize", 0, 0, {"n_tokens": n})
        for n in (16384, 5114, 348)]


def test_layer_readers_on_known_inputs(capsys):
    ctx, load = _reader_ctx(GROUPS + FLUSHES + DOCS,
                            {"jit_fwd_b16_l512": [0.5, 0.25]})
    dot = [r"(^|/)dot_general"]     # the recorded trace's one named scope
    dot_s = 3.644766e-06

    spec, read = load("expert_rounds_per_layer_program")
    assert read(ctx, spec) == pytest.approx((5.25 * 38 + 1.0 * 2) / 40)
    spec, read = load("carried_state_mb_per_row")
    assert read(ctx, spec) == pytest.approx((123.731968 + 67.108864) / 2)

    for name in ("routed_experts_share_pct", "early_route_share_pct",
                 "expert_dispatch_combine_share_pct"):
        spec, read = load(name)
        assert read(ctx, spec) is None     # no such scope in that trace
        assert read(ctx, dict(spec, scopes=dot)) == \
            pytest.approx(100 * dot_s / 0.75)

    # Trinity's two core readers compute from THIS configuration: 14,336
    # operations a pair, 6 sliding and 2 global layers, 4 key/value heads
    rows = sum(LONG) + sum(SHORT)
    for name, layers, steps in (("window_core_roofline", 6, 1059840),
                                ("global_core_roofline", 2, 1586176)):
        spec, read = load(name)
        assert read(ctx, spec) is None
        value = read(ctx, dict(spec, scopes=dot))
        need = layers * steps * 512 * 14336
        moved = layers * (steps * 2 * 512 * 2 + rows * 512 * 3584 * 6)
        assert need / 197e12 > moved / 819e9
        assert value == pytest.approx(100 * (need / 197e12) / dot_s)
        assert "compute-bound" in capsys.readouterr().out

    spec, read = load("reglu_moe_fwd_roofline")
    assert spec["flops"] == "flops_smallthinker"
    need = flops_smallthinker.encoder_flops(
        CONFIG, 143622, ROUTED + 48, [16384, 5114, 348])
    assert read(ctx, spec) == pytest.approx(100 * (need / 197e12) / 0.75)

    spec, read = load("held_experts_gmm_roofline")
    assert spec["flops"] == "flops_smallthinker"
    assert read(ctx, spec) is None
    value = read(ctx, dict(spec, scopes=dot))
    need = 2 * (ROUTED + 48) * 5898240
    moved = 40 * 8 * 64 * 5898240 * 2
    assert need / 197e12 > moved / 819e9
    assert value == pytest.approx(100 * (need / 197e12) / dot_s)
    assert "compute-bound" in capsys.readouterr().out

    # a program without the spans, counters or scopes gives nothing, not
    # an error: the parent commit's traced run of another cell
    bare = [HostSpan(s.name, s.start_unix, s.end_unix, {
        k: v for k, v in s.attrs.items() if k != "expert_rounds_mean"})
        for s in FLUSHES]
    parent, _ = _reader_ctx(GROUPS + bare + DOCS, {"jit_fwd": [0.5]})
    spec, read = load("expert_rounds_per_layer_program")
    assert read(parent, spec) is None
    empty, _ = _reader_ctx([], {}, path=None)
    for name in NEW:
        spec, read = load(name)
        assert read(empty, spec) is None, name


# -- the arithmetic -----------------------------------------------------------

def test_flops_smallthinker_against_the_issues_table():
    c, f = CONFIG, flops_smallthinker
    assert f.attention_params(c) == 2560 * 3584 + 2 * 2560 * 512 \
        + 3584 * 2560 == 20971520
    assert f.router_params(c) == 2560 * 64 == 163840
    assert f.expert_params(c) == 3 * 2560 * 768 == 5898240
    assert 64 * f.expert_params(c) == 377487360
    assert f.layer_params(c) == 398627840
    assert f.embedding_params(c) == 151936 * 2560 == 388956160
    assert f.held_params(c) == 3577981440
    assert f.held_params(c) * 2 == 7155962880                   # 7.16 GB
    assert c["parameters"] == {
        "attention_a_layer": f.attention_params(c),
        "router_a_layer": f.router_params(c),
        "expert": f.expert_params(c),
        "experts_a_layer": 64 * f.expert_params(c),
        "layer_with_its_two_norms": f.layer_params(c),
        "embedding": f.embedding_params(c), "held": f.held_params(c),
        "held_bytes_bfloat16": 2 * f.held_params(c),
        "state_bytes_a_row_at_16384": 123731968}
    assert 0.25 < 7155962880 / 16e9 < 0.5
    assert f.weight_bytes(c) == (3577981440 - 388956160) * 2
    assert f.held_expert_bytes(c) == 8 * 377487360 * 2
    assert f.token_matmul_params(c) == 8 * (20971520 + 163840)
    assert f.pair_flops(c) == 14336 == flops_afmoe.pair_flops(c)
    assert f.routed_flops(c, 10) == 20 * 5898240
    # one document of 3 tokens: 1 + 2 + 3 pairs in every layer
    assert f.attention_flops(c, [3]) == 6 * 14336 * 8
    # 4097 tokens: the last query of a sliding layer meets 4096 keys
    full = 4097 * 4098 // 2
    assert f.attention_flops(c, [4097]) == 14336 * (
        2 * full + 6 * (full - 1))
    # the accepted core readers' functions on this file's derived list
    assert (flops_afmoe.layers_of(c, "sliding_attention"),
            flops_afmoe.layers_of(c, "full_attention")) == (6, 2)
    assert flops_afmoe.core_flops(c, "full_attention", 512, 2.0) == \
        2 * 2 * 512 * 14336
    assert flops_afmoe.core_bytes(c, "full_attention", 512, 1, 512) == \
        2 * (512 * 2 * 512 * 2 + 512 * 3584 * 6)
    # a call of the mix, as ISSUE 39 counts it: 179.5 TFLOP
    from benchmark.harness import traffic

    mix = json.loads((ROOT / "benchmark/mixes"
                      / "issue_threads_long_tail_c32.json").read_text())
    grid = traffic.length_grid(mix["length"], 32).tolist()
    tokens = sum(grid)
    assert tokens == 143622
    parts = (f.routed_flops(c, 8 * 6 * tokens),
             f.attention_flops(c, grid),
             2.0 * f.token_matmul_params(c) * tokens)
    assert [round(p / 1e12, 1) for p in parts] == [81.3, 49.6, 48.6]
    assert f.encoder_flops(c, tokens, 8 * 6 * tokens, grid) == sum(parts)
    # the state of one row at 16,384 tokens
    assert 16384 * 2 * 512 * 2 == 33554432 and 4608 * 2 * 512 * 2 == 9437184
    assert 2 * 33554432 + 6 * 9437184 == 123731968


# -- the configuration --------------------------------------------------------

def test_configuration_holds_the_catalog_rows_numbers_key_for_key():
    if not CATALOG.is_file():
        pytest.skip("the catalog of architectures is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if CONFIG.get(k, "absent") != v)
    assert differ == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    assert CONFIG["published"] == {k: row["config"][k] for k in differ}
    for key in ("rope_layout", "sliding_window_layout"):
        assert CONFIG[key] == row["config"][key][:8] == PERIOD * 2
    assert row["config"]["num_hidden_layers"] == 52 == 13 * 4


def test_reduced_names_the_cuts_and_no_width():
    """What ``test_bm_manifest.py::test_config_entry`` holds for every
    configuration, with the contract's own rule for a width: that test
    refuses every key that CONTAINS ``hidden``, so it fails for this
    configuration's depth key ``num_hidden_layers`` as it does for the
    three before it (PERF.md §7, finding 11: a ``benchmark`` PR's to
    mend)."""
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
        r"_size$|^(emb_sz|n_hid|num_experts_per_tok|expand\w*)$")
    for key in entry["reduced"]:
        assert not width.search(key), key
    assert width.search("moe_ffn_hidden_size")
    # every published width unchanged at the top level
    assert [CONFIG[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "moe_ffn_hidden_size", "sliding_window_size",
        "moe_num_primary_experts", "moe_num_active_primary_experts",
        "vocab_size", "rope_theta")] == [
        2560, 28, 4, 128, 768, 4096, 64, 6, 151936, 1500000]
    assert CONFIG["experts_held"] == {"first": 0, "count": 64, "of": 64}
    assert (CONFIG["moe_primary_router_apply_softmax"],
            CONFIG["norm_topk_prob"], CONFIG["rope_scaling"]) == (
        True, True, None)
    assert CONFIG["deployment"]["chips_that_share_a_layer"] == 1
    assert "2 / 2 / 2 / 2 / 2 / 2 / 1" in CONFIG["deployment"]["pipeline"]
    assert set(CONFIG["assumed"]) >= {
        "a_router_before_input_norm", "b_early_router_and_secondary_experts",
        "c_window_and_rotary", "d_attention", "e_softmax", "f_reglu",
        "layer_types", "vocabulary", "pooling", "weights",
        "serve.kv_positions"}


def test_the_program_reads_the_file_as_the_stage_it_states():
    from code_intelligence_tpu.models import build_encoder, make_config

    serve = CONFIG["serve"]
    enc = build_encoder(make_config(
        "smallthinker", CONFIG, kv_positions=serve["kv_positions"],
        chunk_positions=max(serve["buckets"]),
        state_dtype=CONFIG["state_dtype"]))
    cfg = enc.config
    assert (cfg.moe_num_primary_experts, cfg.experts_held) == (64, (0, 64))
    assert cfg.num_hidden_layers == 8
    assert cfg.sliding_layers == (False, True, True, True) * 2
    assert enc.out_dim == 2560
    assert enc.state_bytes_per_row(16384) == 123731968
    cell = json.loads(
        (ROOT / "benchmark/cells" / f"{CELL}.json").read_text())
    assert cell["reduced"] == CONFIG["reduced"]
    assert cell["driver"] == "bulk_early_route_moe"
    limits = cell["check"]["limits"]
    # the mean and max thirds are limited; the last third (one token a
    # row: a flipped sixth choice reads up to int8's reading there) is
    # logged, and no limit is set where none lies between its readings
    assert {f"rel_rms_{t}{s}" for t in ("mean", "max")
            for s in SUFFIXES} | {"nonfinite", "nonfinite_rows"} \
        == set(limits)
    # every limit, and each number left without one, is written with
    # its reason
    why = cell["check"]["why"]
    assert set(limits) - {"nonfinite", "nonfinite_rows"} <= set(why)
    for suffix in SUFFIXES:
        assert "NOT LIMITED" in why[f"rel_rms_last{suffix}"]


# -- the manifest ---------------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_new_metrics_move_docs_per_s_in_this_cell(name):
    metric = BY_NAME[name]
    assert metric["moves"] == "docs_per_s"
    assert CELL in metric["workloads"]
    if name.endswith("_roofline"):
        assert (metric["unit"], metric["layer"]) == ("%", "kernels")


@pytest.mark.parametrize("name", SHARED + ["docs_per_s"])
def test_the_cell_joins_the_metrics_its_spans_and_scopes_carry(name):
    entry = BY_NAME.get(name) or next(
        m for m in MANIFEST["end_to_end"] if m["name"] == name)
    assert CELL in entry["workloads"]


def test_the_cell_entry_says_why():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG_NAME, "issue_threads_long_tail_c32", 1)
    assert len(entry["why"]) <= 200 and "6 rounds" in entry["why"]
