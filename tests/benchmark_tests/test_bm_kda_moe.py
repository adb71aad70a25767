"""The Ling share through the benchmark, tiny, on the CPU: a whole run of
its driver against its plain reference with documents whose state is
handed over nine times; every must-fail control reads not correct; a
program without the architecture fails at once; the new per-layer
readers on known inputs; the arithmetic of ``harness/flops_bailing.py``
against ISSUE 36's sizing; the configuration file against the catalog
row. Pins no entry's place in the manifest, no list's exact contents and
no count: the next configuration appends after these."""

import json
import re
from pathlib import Path

import pytest

import bm_util
from benchmark import run
from benchmark.harness import flops_bailing
from benchmark.harness.spans import HostSpan, SpanLog

ROOT = bm_util.ROOT
TRACE = Path(__file__).parent / "data" / "tiny.xplane.pb"
CONFIG_NAME = "ling_3_0_flash_ep4_share"
CONFIG = json.loads(
    (ROOT / f"benchmark/configs/{CONFIG_NAME}.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "ling_bulk_long_tail"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]
NEW = ["kda_moe_fwd_roofline", "kda_core_roofline", "kda_core_share_pct",
       "kda_mixer_share_pct", "latent_core_share_pct",
       "kda_state_handovers_per_doc"]
SHARED = ["tokenize_share_pct", "tokenize_us_per_doc",
          "text_rules_share_pct", "text_rules_us_per_doc",
          "pre_rule_passes_run_pct", "compiles_in_window",
          "encoder_fwd_us_per_token", "device_idle_pct.bulk",
          "group_dispatch_share_pct", "device_wait_share_pct",
          "padded_lane_pct", "attention_share_pct",
          "carried_state_mb_per_row", "padded_device_time_pct",
          "narrow_program_time_pct", "narrow_lane_cost_ratio",
          "padded_lane_run_pct", "program_enqueue_share_pct",
          "group_self_ms"]
BY_NAME = {m["name"]: m for m in MANIFEST["per_layer"]}

TINY = {
    "vocab_size": 600, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
    "num_hidden_layers": 7, "first_k_dense_replace": 1,
    "layer_group_size": 6, "num_attention_heads": 4, "head_dim": 16,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "kda_safe_gate": True, "no_kda_lora": True, "use_qk_norm": True,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 8,
    "num_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "score_function": "sigmoid", "topk_method": "noaux_tc",
    "rms_norm_eps": 1e-6, "rope_theta": 6000000, "rope_scaling": None,
    "expert_swiglu_limit_list": [0] * 7,
    "share_expert_swiglu_limit_list": [0] * 7,
    "experts_held": {"first": 4, "count": 8, "of": 16}}
SUFFIXES = ("", "_carried", "_long")
LIMITS = {f"rel_rms_{t}{s}": 2e-5 for t in ("mean", "max", "last")
          for s in SUFFIXES}
LIMITS.update(nonfinite=0, nonfinite_rows=0)


def tiny_benchmark(tmp: Path, per_layer=()) -> Path:
    """``bm_util``'s copy of the benchmark with a tiny share, its cell
    and a manifest that names them, as files. The mix's documents run to
    75 tokens: chunk programs of 8, so the longest document's state is
    handed over nine times."""
    bench = bm_util.tiny_benchmark(tmp)
    bm_util.write(bench / "configs" / "tiny_kda.json", dict(
        TINY, name="tiny_kda", architecture="bailing_hybrid",
        dtype="float32", state_dtype="float32",
        serve={"scheduler": "groups", "batch_size": 4,
               "buckets": [8], "kv_positions": 128},
        weights={"dist": "student_t", "df": 4}, reduced=[]))
    bm_util.write(bench / "cells" / "tiny_kda_cell.json", {
        "name": "tiny_kda_cell", "config": "tiny_kda",
        "mix": "tiny_docs", "chips": 1, "driver": "bulk_kda_moe",
        "reduced": [], "check": {"sample": 6, "block_rows": 1,
                                 "limits": LIMITS}})
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": "tiny_kda", "source": "test",
                            "file": "benchmark/configs/tiny_kda.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny_kda_cell", "config": "tiny_kda",
                              "traffic": "tiny_docs", "chips": 1,
                              "why": "test"}]
    manifest["per_layer"] = [dict(m, moves="docs_per_s") for m in per_layer]
    bm_util.write(tmp / "BENCHMARK.json", manifest)
    return bench


def main(tmp, *extra, **kw):
    return run.main(["--workload", "tiny_kda_cell", "--seed",
                     str(2**31 + 36), "--seconds", "0.2", *extra],
                    root=tmp, **kw)


@pytest.fixture
def gate(monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)


def numbers(line):
    return {c["name"]: c["value"] for c in line["compared"]}


def test_cell_runs_and_agrees_with_its_reference(tmp_path, gate):
    """The mix's longest document (75 tokens) takes ten chunk programs of
    8: matrix states, conv tails and the latent cache are inside the
    comparison, at float32 tightness."""
    per_layer = [{k: BY_NAME[n][k] for k in (
        "name", "unit", "better", "source", "layer")} for n in SHARED + NEW]
    tiny_benchmark(tmp_path, per_layer)
    line = main(tmp_path, "--trace", "0")
    assert line["correct"] and line["failed"] == 0, line["compared"]
    got = numbers(line)
    assert set(LIMITS) <= set(got)   # some sampled row is a long one
    assert got["rel_rms_mean_long"] < 5e-6
    assert line["counters"]["compiles_in_window"] == 0

    traced = main(tmp_path, "--trace", "1")
    assert traced["correct"]
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    # multi-chunk groups: six matrix states of 4 x 16 x 16 and six tails
    # of 3 x 192, float32, beside a latent cache of 40 numbers a position
    # at 16 to 128 positions
    fixed = 6 * (4 * 16 * 16 + 3 * 192) * 4
    assert (fixed + 16 * 40 * 4) / 1e6 \
        <= metrics["carried_state_mb_per_row"] <= (fixed + 128 * 40 * 4) / 1e6
    assert 0 < metrics["kda_state_handovers_per_doc"] <= 9
    assert 0 < metrics["padded_lane_pct"] < 100
    assert 0 < metrics["padded_lane_run_pct"] <= metrics["padded_lane_pct"]
    assert metrics["pre_rule_passes_run_pct"] > 0
    # no device plane in a CPU capture: the scope readers find nothing
    # and their metrics are left out, not reported as zero
    assert not {"kda_moe_fwd_roofline", "kda_core_roofline",
                "kda_core_share_pct", "kda_mixer_share_pct",
                "latent_core_share_pct", "attention_share_pct"} \
        & set(metrics)


@pytest.mark.parametrize("control,overrides,floor,where", [
    ("int8_weights", {"precision": "int8"}, 1e-3, "_carried"),
    ("zeroed_state", {"kda_state": "zeroed"}, 1e-2, "_long"),
    ("bfloat16_state", {"kda_state_dtype": "bfloat16"}, 1e-4, "_long"),
    ("no_decay", {"decay": "off"}, 1e-2, "_carried"),
    ("no_delta", {"delta": "off"}, 1e-3, "_carried"),
    ("no_conv", {"conv": "off"}, 1e-2, "_carried"),
    ("zeroed_caches", {"caches": "zeroed"}, 1e-3, "_carried"),
    ("no_gate", {"gate": "off"}, 1e-2, "_carried"),
])
def test_controls_are_not_correct(tmp_path, gate, control, overrides, floor,
                                  where):
    """float32 sound runs sit below 5e-6; each control far above, by a
    limit on the rows it is aimed at."""
    from code_intelligence_tpu.ops import kda, ssd

    scan, conv = kda.kda_scan, ssd.causal_conv1d
    tiny_benchmark(tmp_path)
    line = main(tmp_path, overrides=overrides)
    # the stand-ins last a trace
    assert kda.kda_scan is scan and ssd.causal_conv1d is conv
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if not c["inside"]}
    assert any(name.endswith(where) for name in bad), bad
    assert numbers(line)[f"rel_rms_mean{where}"] > floor


def test_on_a_program_without_the_architecture_the_cell_fails_at_once(
        tmp_path, gate, monkeypatch):
    """The parent commit has no ``bailing_hybrid``: ``make_config``
    raises before a weight is made, and nothing hangs."""
    from code_intelligence_tpu.models import contract

    tiny_benchmark(tmp_path)
    monkeypatch.delitem(contract.ENCODERS, "bailing_hybrid")
    with pytest.raises(ValueError,
                       match="unknown architecture 'bailing_hybrid'"):
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


LONG = [16] * 11 + [8] * 7 + [4] * 7 + [2] * 7    # the cell's long group
SHORT = [16, 16, 16, 8, 8, 2]                     # and its short one
GROUPS = [
    HostSpan("engine.group", 0, 1, {
        "rows": 16, "batch": 16, "bucket": 512, "chunks": 32,
        "valid_tokens": 118484, "lane_steps": 16 * 512 * 32,
        "lane_steps_run": 512 * sum(LONG),
        "state_bytes": 16 * 31899648, "kv_positions": 16384}),
    HostSpan("engine.group", 1, 2, {
        "rows": 16, "batch": 16, "bucket": 512, "chunks": 6,
        "valid_tokens": 25138, "lane_steps": 16 * 512 * 6,
        "lane_steps_run": 512 * sum(SHORT),
        "state_bytes": 16 * 17743872, "kv_positions": 4096})]
PROGRAMS = [HostSpan("engine.program", 0, 0, {
    "rows": rows, "batch": 16, "bucket": 512, "lane_steps": rows * 512,
    "valid_tokens": rows * 400}) for rows in LONG + SHORT]
FLUSHES = [
    HostSpan("engine.finalize", 2, 3, {
        "groups": 2, "routed_rows": 1700000, "expert_rows_max": 200.0,
        "expert_rows_mean": 1700000 / (38 * 6 * 128), "moe_programs": 38,
        "kda_layers": 6, "kda_kernel_layers": 0.0,
        "attention_kernel_layers": 1.0}),
    HostSpan("engine.finalize", 4, 5, {"groups": 1})]   # an AWD flush
DOCS = [HostSpan("engine.tokenize", 0, 0, {"n_tokens": n})
        for n in (16384, 5114, 348)]


def test_layer_readers_on_known_inputs(capsys):
    ctx, load = _reader_ctx(GROUPS + PROGRAMS + FLUSHES + DOCS,
                            {"jit_fwd": [0.5, 0.25]})
    dot = [r"(^|/)dot_general"]     # the recorded trace's one named scope
    dot_s = 3.644766e-06

    spec, read = load("kda_state_handovers_per_doc")
    assert read(ctx, spec) == pytest.approx((31 * 16 + 5 * 16) / 32) == 18.0
    spec, read = load("carried_state_mb_per_row")
    assert read(ctx, spec) == pytest.approx((31.899648 + 17.743872) / 2)

    for name in ("kda_core_share_pct", "kda_mixer_share_pct",
                 "latent_core_share_pct"):
        spec, read = load(name)
        assert read(ctx, spec) is None     # nothing under those scopes there
        assert read(ctx, dict(spec, scopes=dot)) == \
            pytest.approx(100 * dot_s / 0.75)

    # the lane-steps RUN, and a row of state a program: not the groups'
    # lane_steps as enqueued (311,296)
    steps, rows = 512 * (sum(LONG) + sum(SHORT)), sum(LONG) + sum(SHORT)
    assert (steps, rows, len(PROGRAMS)) == (174080, 340, 38)
    spec, read = load("kda_core_roofline")
    assert read(ctx, spec) is None
    value = read(ctx, dict(spec, scopes=dot))
    need = 6 * steps * 4456448
    moved = 6 * (steps * (3 * 4096 * 2 + 4 * 4096 + 4 * 32 + 4 * 4096)
                 + rows * 2 * 32 * 128 * 128 * 4)
    assert moved / 819e9 > need / 197e12
    assert value == pytest.approx(100 * (moved / 819e9) / dot_s)
    assert "memory-bound" in capsys.readouterr().out

    spec, read = load("kda_moe_fwd_roofline")
    need = flops_bailing.encoder_flops(CONFIG, 143622, 1700000,
                                       [16384, 5114, 348])
    assert read(ctx, spec) == pytest.approx(100 * (need / 197e12) / 0.75)
    assert "compute-bound" in capsys.readouterr().out

    # a program without the spans, counters or scopes gives nothing, not
    # an error
    parent, _ = _reader_ctx(FLUSHES[1:] + DOCS, {"jit_fwd": [0.5]})
    empty, _ = _reader_ctx([], {}, path=None)
    for name in NEW:
        spec, read = load(name)
        assert read(parent, spec) is None, name
        assert read(empty, spec) is None, name


# -- the arithmetic -----------------------------------------------------------

def test_flops_bailing_against_the_issues_sizing():
    c = CONFIG
    assert flops_bailing.layer_kinds(c) == (6, 1)
    assert flops_bailing.layer_counts(c) == (1, 6)
    assert flops_bailing.kda_params(c) == 2560 * (3 * 4096 + 2 * 4096 + 32) \
        + 4096 * 2560 == 62996480
    assert flops_bailing.mla_params(c) == 2560 * 6144 + 2560 * 576 \
        + 512 * 8192 + 2560 * 32 + 4096 * 2560 == 31965184
    assert flops_bailing.expert_params(c) == 3 * 2560 * 768 == 5898240
    assert 128 * 5898240 == 754974720 and 512 * 5898240 == 3019898880
    assert flops_bailing.shared_params(c) == 5898240
    assert flops_bailing.router_params(c) == 2560 * 512
    assert flops_bailing.dense_mlp_params(c) == 3 * 2560 * 6144 == 47185920
    assert 39296 * 2560 == 100597760 and 39296 * 4 == 157184
    assert flops_bailing.held_params(c) == 5130829824
    assert flops_bailing.held_params(c) * 2 == 10261659648       # 10.26 GB
    assert flops_bailing.weight_bytes(c) == (5130829824 - 100597760) * 2
    assert flops_bailing.token_matmul_params(c) == 6 * 62996480 + 31965184 \
        + 47185920 + 6 * (1310720 + 5898240) == 500383744
    # the recurrence a token a layer: 32 heads x (5 x 64 x 128 + 6 x 128^2)
    assert flops_bailing.kda_flops_per_token(c) == 32 * (40960 + 98304) \
        == 4456448
    assert flops_bailing.kda_bytes_per_token(c) == 57472
    assert flops_bailing.kda_state_bytes_per_row(c) == 2 * 2097152
    assert flops_bailing.pair_flops(c) == 2 * 32 * 320 == 20480
    assert flops_bailing.routed_flops(c, 10) == 20 * 5898240
    # one document of 3 tokens: 1 + 2 + 3 pairs in the one latent layer
    assert flops_bailing.attention_flops(c, [3]) == 6 * 20480
    assert flops_bailing.encoder_flops(c, 3, 10, [3]) == 3 * (
        2 * 500383744 + 6 * 4456448) + 20 * 5898240 + 6 * 20480
    # the whole published model counted the same way: ~125B-A5.5B
    whole = dict(c, num_hidden_layers=42, first_k_dense_replace=2,
                 num_experts=512, vocab_size=157184, experts_held=None)
    assert flops_bailing.layer_kinds(whole) == (35, 7)
    assert flops_bailing.held_params(whole) + 157184 * 2560 == 124412100608
    assert flops_bailing.token_matmul_params(whole) + 40 * 8 * 5898240 \
        + 2 * 157184 * 2560 == 5503582208
    # the state of one row at 16,384 tokens
    assert 6 * 32 * 128 * 128 * 4 + 6 * 3 * 12288 * 2 + 16384 * 576 * 2 \
        == 31899648


# -- the configuration --------------------------------------------------------

def test_configuration_holds_the_catalog_rows_numbers_key_for_key():
    if not CATALOG.is_file():
        pytest.skip("the catalog of architectures is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Ling-3.0-flash")
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if CONFIG.get(k, "absent") != v)
    assert differ == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    assert CONFIG["published"] == {k: row["config"][k] for k in differ}


def test_reduced_names_the_cuts_and_no_width():
    """What ``test_bm_manifest.py::test_config_entry`` holds for every
    configuration, with the contract's own rule for a width: that test
    refuses every key that CONTAINS ``hidden``, so it fails for this
    configuration's depth key ``num_hidden_layers`` as it does for
    DeepSeek's and Trinity's (PERF.md §7, finding 11: a ``benchmark``
    PR's to mend)."""
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
    # every published width unchanged at the top level
    assert [CONFIG[k] for k in (
        "hidden_size", "num_attention_heads", "head_dim",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "kv_lora_rank", "intermediate_size", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "num_experts_per_tok",
        "short_conv_kernel_size", "layer_group_size")] == [
        2560, 32, 128, 128, 64, 128, 512, 6144, 768, 768, 8, 4, 6]
    assert (CONFIG["experts_held"], CONFIG["n_group"], CONFIG["topk_group"],
            CONFIG["routed_scaling_factor"], CONFIG["score_function"],
            CONFIG["num_shared_experts"], CONFIG["kda_lower_bound"]) == (
        {"first": 0, "count": 128, "of": 512}, 8, 4, 2.5, "sigmoid", 1, -5)
    assert CONFIG["deployment"]["chips_that_share_a_layer"] == 4
    assert set(CONFIG["assumed"]) >= {
        "a_use_qk_norm", "b_rotary", "c_gates", "d_shapes",
        "e_which_layers", "f_swiglu_limits", "parameter_count",
        "vocabulary", "pooling", "weights", "dtype", "serve.kv_positions"}
    # the held layers' SwiGLU limits are all 0
    assert not any(CONFIG["expert_swiglu_limit_list"][:7]) \
        and not any(CONFIG["share_expert_swiglu_limit_list"][:7])


def test_the_program_reads_the_file_as_the_share_it_states():
    from code_intelligence_tpu.models import build_encoder, make_config

    serve = CONFIG["serve"]
    enc = build_encoder(make_config(
        "bailing_hybrid", CONFIG, kv_positions=serve["kv_positions"],
        state_dtype=CONFIG["state_dtype"]))
    cfg = enc.config
    assert (cfg.num_experts, cfg.experts_held) == (512, (0, 128))
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.n_moe_layers) == (7, 1, 6)
    assert (cfg.kda_layers, cfg.latent_layers) == ((0, 1, 2, 3, 4, 6), (5,))
    assert enc.out_dim == 2560
    assert enc.state_bytes_per_row(16384) == 31899648
    assert enc.state_bytes_per_row(3072) == 17743872
    cell = json.loads(
        (ROOT / "benchmark/cells" / f"{CELL}.json").read_text())
    assert cell["reduced"] == CONFIG["reduced"]
    assert cell["driver"] == "bulk_kda_moe"
    limits = cell["check"]["limits"]
    assert {f"rel_rms_{t}{s}" for t in ("mean", "max", "last")
            for s in SUFFIXES} <= set(limits)
    # a limit is a reading with room, never a stand-in
    assert all(0 < limits[k] < 0.5 for k in limits if k.startswith("rel_"))


def test_the_mix_is_trinitys_unedited():
    from benchmark.harness import traffic

    mix = json.loads((ROOT / "benchmark/mixes"
                      / "issue_threads_long_tail_c32.json").read_text())
    grid = sorted(traffic.length_grid(mix["length"], 32).tolist())
    assert (grid[0], grid[-1], sum(grid)) == (348, 16384, 143622)
    # rows of more than 8 hand-overs: longer than 9 chunk programs
    assert sum(n > 512 * 9 for n in grid) == 11
    # chunk programs a thread past 4096 crosses
    assert [-(-n // 512) for n in grid if n > 4096][::11] == [9, 32]
    trinity = next(w for w in MANIFEST["workloads"]
                   if w["name"] == "trinity_bulk_long_tail")
    mine = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert mine["traffic"] == trinity["traffic"] == mix["name"]


# -- the manifest ---------------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_new_metrics_move_docs_per_s_in_this_cell(name):
    from benchmark.harness import cell as cells

    metric = BY_NAME[name]
    assert metric["moves"] == "docs_per_s"
    assert CELL in metric["workloads"]
    if name.endswith("_roofline"):
        assert (metric["unit"], metric["layer"]) == ("%", "kernels")
    spec, read = cells.load_layer_reader(name)
    assert callable(read)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == metric[key], key
    assert name in {m["name"] for m in cells.load_cell(CELL)["per_layer"]}


@pytest.mark.parametrize("name", SHARED + ["docs_per_s"])
def test_the_cell_joins_the_metrics_every_bulk_cell_reports(name):
    entry = BY_NAME.get(name) or next(
        m for m in MANIFEST["end_to_end"] if m["name"] == name)
    assert CELL in entry["workloads"]


def test_the_cell_entry_says_why_and_what_attention_sees():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG_NAME, "issue_threads_long_tail_c32", 1)
    assert len(entry["why"]) <= 200 and "4x" in entry["why"]
