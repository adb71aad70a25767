"""Qwen3-Next's half share through the benchmark, tiny, on the CPU: a
whole run of its driver against its plain reference with documents that
cross chunk programs through three matrix states, three conv tails and a
grouped-query cache, half of the experts held; every must-fail control
reads not correct; a program without the architecture fails at once; the
new per-layer readers on known inputs; the arithmetic of
``harness/flops_qwen3_next.py`` against a hand count; the configuration
file against the catalog row. Pins no entry's place in the manifest and
no list's exact contents: the next configuration appends after these."""

import json
import re
from pathlib import Path

import pytest

import bm_util
from benchmark import run
from benchmark.harness import flops_qwen3_next
from benchmark.harness.spans import HostSpan, SpanLog

ROOT = bm_util.ROOT
TRACE = Path(__file__).parent / "data" / "tiny.xplane.pb"
CONFIG_NAME = "qwen3_next_80b_a3b_ep2_share"
CONFIG = json.loads(
    (ROOT / f"benchmark/configs/{CONFIG_NAME}.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "qwen3_next_bulk_long_tail"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW = ["gdn_core_roofline", "gdn_core_share_pct", "gdn_mixer_share_pct",
       "gdn_moe_fwd_roofline", "gdn_experts_gmm_roofline",
       "full_attn_core_roofline", "gdn_state_handovers_per_doc"]
SHARED = ["tokenize_share_pct", "tokenize_us_per_doc",
          "text_rules_share_pct", "text_rules_us_per_doc",
          "pre_rule_passes_run_pct", "compiles_in_window",
          "encoder_fwd_us_per_token", "device_idle_pct.bulk",
          "group_dispatch_share_pct", "device_wait_share_pct",
          "padded_lane_pct", "attention_share_pct",
          "carried_state_mb_per_row", "global_core_share_pct",
          "routed_experts_share_pct", "expert_dispatch_combine_share_pct",
          "expert_rounds_per_layer_program", "padded_device_time_pct",
          "narrow_program_time_pct", "narrow_lane_cost_ratio",
          "padded_lane_run_pct", "program_enqueue_share_pct",
          "group_self_ms"]
BY_NAME = {m["name"]: m for m in MANIFEST["per_layer"]}

# the published structure, small: 4 layers 3 : 1, two value heads a key
# head, 16 experts top 4 of which 8 are held, rotary on a quarter of a
# head of 32 (which no linear head's 16 can be taken for)
TINY = {
    "vocab_size": 600, "hidden_size": 64, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "rope_scaling": None,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "num_experts": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "use_sliding_window": False, "decoder_sparse_step": 1,
    "mlp_only_layers": [],
    "experts_held": {"first": 8, "count": 8, "of": 16}}
SUFFIXES = ("", "_carried")
LIMITS = {f"rel_rms_{t}{s}": 2e-5 for t in ("mean", "max", "last")
          for s in SUFFIXES}
LIMITS.update(nonfinite=0, nonfinite_rows=0, rel_err_p50_cached_k=2e-5,
              rel_err_p50_cached_v=2e-5, rel_err_p50_conv_tail=2e-5)


def tiny_benchmark(tmp: Path, per_layer=()) -> Path:
    """``bm_util``'s copy of the benchmark with a tiny half share, its
    cell and a manifest that names them, as files. The mix's documents
    run to 96 tokens: up to three chunk programs of 32 through the
    matrix states, the conv tails and the cache."""
    bench = bm_util.tiny_benchmark(tmp)
    bm_util.write(bench / "configs" / "tiny_gdn.json", dict(
        TINY, name="tiny_gdn", architecture="qwen3_next",
        dtype="float32", state_dtype="float32",
        serve={"scheduler": "groups", "batch_size": 4,
               "buckets": [16, 32], "kv_positions": 128},
        weights={"dist": "student_t", "df": 4}, reduced=[]))
    bm_util.write(bench / "cells" / "tiny_gdn_cell.json", {
        "name": "tiny_gdn_cell", "config": "tiny_gdn",
        "mix": "tiny_docs", "chips": 1, "driver": "bulk_gdn_moe",
        "reduced": [], "check": {"sample": 6, "block_rows": 1,
                                 "limits": LIMITS}})
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": "tiny_gdn", "source": "test",
                            "file": "benchmark/configs/tiny_gdn.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny_gdn_cell", "config": "tiny_gdn",
                              "traffic": "tiny_docs", "chips": 1,
                              "why": "test"}]
    manifest["per_layer"] = [dict(m, moves="docs_per_s") for m in per_layer]
    bm_util.write(tmp / "BENCHMARK.json", manifest)
    return bench


def main(tmp, *extra, **kw):
    return run.main(["--workload", "tiny_gdn_cell", "--seed",
                     str(2**31 + 47), "--seconds", "0.2", *extra],
                    root=tmp, **kw)


@pytest.fixture
def gate(monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)


def numbers(line):
    return {c["name"]: c["value"] for c in line["compared"]}


def test_cell_runs_and_agrees_with_its_reference(tmp_path, gate):
    """The mix's longest document (96 tokens) takes three chunk programs
    of 32: both kinds of state, the softmax router, the held half's part
    and the gated shared expert are inside the comparison, at float32
    tightness."""
    per_layer = [{k: BY_NAME[n][k] for k in (
        "name", "unit", "better", "source", "layer")} for n in SHARED + NEW]
    tiny_benchmark(tmp_path, per_layer)
    line = main(tmp_path, "--trace", "0")
    assert line["correct"] and line["failed"] == 0, line["compared"]
    got = numbers(line)
    assert set(LIMITS) <= set(got)
    assert got["rel_rms_mean_carried"] < 5e-6
    # what two chunk programs of 32 hand the third to read: 64 positions
    # of keys and values, three conv tails
    assert 0 < got["rel_err_p50_cached_k"] < 5e-6
    assert 0 < got["rel_err_p50_cached_v"] < 5e-6
    assert 0 < got["rel_err_p50_conv_tail"] < 5e-6
    assert line["counters"]["compiles_in_window"] == 0
    assert "attention_kernel_layers" not in line["counters"]

    traced = main(tmp_path, "--trace", "1")
    assert traced["correct"]
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    # multi-chunk groups: three matrix states and conv tails, one cache
    # of 128 positions x 2 heads x 32 twice, all float32
    assert metrics["carried_state_mb_per_row"] == pytest.approx(
        (3 * (4 * 16 * 16 + 3 * 128) * 4 + 2 * 2 * 128 * 32 * 4) / 1e6)
    # a half share of top 4: about two rounds of N a layer a program
    assert 1 <= metrics["expert_rounds_per_layer_program"] <= 3
    assert 0 < metrics["padded_lane_pct"] < 100
    # documents of up to 96 tokens in chunks of 32: at most 2 hand-overs
    assert 0 < metrics["gdn_state_handovers_per_doc"] <= 2
    # which cores ran, among the counters: none on a kernel on the CPU
    assert traced["counters"]["attention_kernel_layers"] == 0
    assert traced["counters"]["expert_kernel_layers"] == 0
    # no device plane in a CPU capture: the scope readers find nothing
    # and their metrics are left out, not reported as zero
    assert not {"gdn_core_roofline", "gdn_core_share_pct",
                "gdn_mixer_share_pct", "gdn_moe_fwd_roofline",
                "gdn_experts_gmm_roofline", "full_attn_core_roofline",
                "global_core_share_pct", "routed_experts_share_pct",
                "attention_share_pct"} & set(metrics)


@pytest.mark.parametrize("control,overrides,floor", [
    ("int8_weights", {"precision": "int8"}, 1e-3),
    ("bfloat16_caches", {"state_dtype": "bfloat16"}, 1e-4),
    ("zeroed_matrix_state", {"gdn_state": "zeroed"}, 1e-3),
    ("no_decay", {"decay": "off"}, 1e-3),
    ("no_delta", {"delta": "off"}, 1e-3),
    ("no_conv", {"conv": "off"}, 1e-2),
    ("plain_norm_weights", {"norm_weight": "plain"}, 1e-2),
    ("rotary_on_every_dim", {"rope": "all"}, 1e-4),
    ("no_output_gate", {"out_gate": "off"}, 1e-3),
    ("no_qk_norm", {"qk_norm": "off"}, 1e-4),
    ("no_shared_gate", {"shared_gate": "off"}, 1e-3),
    ("zeroed_caches", {"caches": "zeroed"}, 1e-3),
])
def test_controls_are_not_correct(tmp_path, gate, control, overrides,
                                  floor):
    """float32 sound runs sit below 5e-6; each control far above."""
    from code_intelligence_tpu.models import Qwen3NextEncoder, qwen3_next
    from code_intelligence_tpu.ops import gdn, ssd

    def pieces():
        return (gdn.gdn_scan, ssd.causal_conv1d, qwen3_next._centred,
                qwen3_next.rope_qk, Qwen3NextEncoder._qk_norm)

    real = pieces()
    tiny_benchmark(tmp_path)
    line = main(tmp_path, overrides=overrides)
    assert pieces() == real
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if not c["inside"]}
    assert any(name.endswith("_carried") for name in bad), bad
    assert numbers(line)["rel_rms_mean_carried"] > floor
    # what rounds or drops the state a later program READS shows in that
    # state itself, far above what it moves a row by
    handed_on = {"bfloat16_caches": 1e-3, "zeroed_caches": 0.99,
                 "no_qk_norm": 1e-2, "rotary_on_every_dim": 1e-2}
    if control in handed_on:
        assert numbers(line)["rel_err_p50_cached_k"] > handed_on[control]
    if control in ("bfloat16_caches", "zeroed_caches"):
        assert numbers(line)["rel_err_p50_cached_v"] > handed_on[control]
    if control == "bfloat16_caches":
        assert numbers(line)["rel_err_p50_conv_tail"] > 1e-3


def test_on_a_program_without_the_architecture_the_cell_fails_at_once(
        tmp_path, gate, monkeypatch):
    """The parent commit has no ``qwen3_next``: ``make_config`` raises
    before a weight is made, and nothing hangs."""
    from code_intelligence_tpu.models import contract

    tiny_benchmark(tmp_path)
    monkeypatch.delitem(contract.ENCODERS, "qwen3_next")
    with pytest.raises(ValueError,
                       match="unknown architecture 'qwen3_next'"):
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
ROW_BYTES = 39993344
GROUPS = [
    HostSpan("engine.group", 0, 1, {
        "rows": 16, "batch": 16, "bucket": 512, "chunks": 32,
        "valid_tokens": 118484, "lane_steps": 16 * 512 * 32,
        "lane_steps_run": 512 * sum(LONG), "cache_steps_run": _steps(LONG),
        "window_steps_run": 0, "state_bytes": 16 * ROW_BYTES,
        "kv_positions": 16384, "kv_positions_window": 0}),
    HostSpan("engine.group", 1, 2, {
        "rows": 16, "batch": 16, "bucket": 512, "chunks": 6,
        "valid_tokens": 25138, "lane_steps": 16 * 512 * 6,
        "lane_steps_run": 512 * sum(SHORT), "cache_steps_run": _steps(SHORT),
        "window_steps_run": 0, "state_bytes": 16 * 12730368,
        "kv_positions": 3072, "kv_positions_window": 0})]
PROGRAMS = [HostSpan("engine.program", 0, 0, {
    "rows": r, "batch": 16, "bucket": 512, "valid_tokens": r * 400,
    "lane_steps": r * 512}) for r in LONG + SHORT]
VALID = 143622
ROUTED = 4 * 5 * VALID           # five of ten choices a token a layer
FLUSHES = [
    HostSpan("engine.finalize", 2, 3, {
        "groups": 2, "routed_rows": ROUTED, "expert_rows_max": 300.0,
        "expert_rows_mean": ROUTED / (38 * 4 * 256), "moe_programs": 38,
        "expert_rounds_mean": 5.0, "gdn_state_handovers": 270,
        "attention_kernel_layers": 1.0,
        "expert_kernel_layers": 4.0}),
    HostSpan("engine.finalize", 3, 4, {
        "groups": 1, "routed_rows": 10, "expert_rows_max": 2.0,
        "expert_rows_mean": 10 / (4 * 256), "moe_programs": 1,
        "expert_rounds_mean": 0.75, "gdn_state_handovers": 0,
        "attention_kernel_layers": 0.0,
        "expert_kernel_layers": 0.0}),
    HostSpan("engine.finalize", 4, 5, {"groups": 1})]   # an AWD flush
DOCS = [HostSpan("engine.tokenize", 0, 0, {"n_tokens": n})
        for n in (16384, 5114, 348)]


def test_layer_readers_on_known_inputs(capsys):
    ctx, load = _reader_ctx(GROUPS + PROGRAMS + FLUSHES + DOCS,
                            {"jit_fwd_b16_l512": [0.5, 0.25]})
    dot = [r"(^|/)dot_general"]     # the recorded trace's one named scope
    dot_s = 3.644766e-06

    spec, read = load("gdn_state_handovers_per_doc")
    assert read(ctx, spec) == pytest.approx(270 / 32)
    spec, read = load("expert_rounds_per_layer_program")
    assert read(ctx, spec) == pytest.approx((5.0 * 38 + 0.75) / 39)
    spec, read = load("carried_state_mb_per_row")
    assert read(ctx, spec) == pytest.approx((39.993344 + 12.730368) / 2)

    for name in ("gdn_core_share_pct", "gdn_mixer_share_pct",
                 "global_core_share_pct", "routed_experts_share_pct"):
        spec, read = load(name)
        assert read(ctx, spec) is None     # no such scope in that trace
        assert read(ctx, dict(spec, scopes=dot)) == \
            pytest.approx(100 * dot_s / 0.75)

    # the recurrence over THREE linear layers, counted from this
    # configuration: 3,932,160 operations and 33,024 bytes a lane-step,
    # 4 MB of state in and out a row a program
    spec, read = load("gdn_core_roofline")
    assert spec["flops"] == "flops_qwen3_next"
    assert read(ctx, spec) is None
    value = read(ctx, dict(spec, scopes=dot))
    rows = sum(LONG) + sum(SHORT)
    need = 3 * rows * 512 * 3932160
    moved = 3 * (rows * 512 * 33024 + rows * 4194304)
    assert need / 197e12 < moved / 819e9
    assert value == pytest.approx(100 * (moved / 819e9) / dot_s)
    assert "memory-bound" in capsys.readouterr().out

    # the softmax-attention core of the ONE such layer: 16,384 a pair
    spec, read = load("full_attn_core_roofline")
    assert (spec["reader"], spec["flops"]) == (
        "latent_core_roofline", "flops_qwen3_next")
    assert read(ctx, spec) is None
    value = read(ctx, dict(spec, scopes=dot))
    steps = _steps(LONG) + _steps(SHORT)
    need = steps * 512 * 16384
    moved = steps * 2 * 512 * 2 + rows * 512 * 4096 * 6
    assert need / 197e12 > moved / 819e9
    assert value == pytest.approx(100 * (need / 197e12) / dot_s)
    assert "compute-bound" in capsys.readouterr().out

    spec, read = load("gdn_moe_fwd_roofline")
    assert (spec["reader"], spec["flops"]) == (
        "reglu_moe_fwd_roofline", "flops_qwen3_next")
    need = flops_qwen3_next.encoder_flops(
        CONFIG, VALID, ROUTED + 10, [16384, 5114, 348])
    assert read(ctx, spec) == pytest.approx(100 * (need / 197e12) / 0.75)

    spec, read = load("gdn_experts_gmm_roofline")
    assert (spec["reader"], spec["flops"]) == (
        "held_experts_gmm_roofline", "flops_qwen3_next")
    assert read(ctx, spec) is None
    value = read(ctx, dict(spec, scopes=dot))
    need = 2 * (ROUTED + 10) * 3145728
    moved = 39 * 4 * 256 * 3145728 * 2
    # thin experts: read once a program for 160 rows at the most
    assert moved / 819e9 > need / 197e12
    assert value == pytest.approx(100 * (moved / 819e9) / dot_s)
    assert "memory-bound" in capsys.readouterr().out

    # a program without the spans, counters or scopes gives nothing, not
    # an error: the parent commit's traced run of another cell
    bare = [HostSpan(s.name, s.start_unix, s.end_unix, {
        k: v for k, v in s.attrs.items() if not k.startswith("gdn_")})
        for s in FLUSHES]
    parent, _ = _reader_ctx(GROUPS + bare + DOCS, {"jit_fwd": [0.5]})
    spec, read = load("gdn_state_handovers_per_doc")
    assert read(parent, spec) is None
    spec, read = load("gdn_core_roofline")
    assert read(parent, dict(spec, scopes=dot)) is None   # no programs
    empty, _ = _reader_ctx([], {}, path=None)
    for name in NEW:
        spec, read = load(name)
        assert read(empty, spec) is None, name


# -- the arithmetic -----------------------------------------------------------

def test_flops_qwen3_next_against_a_hand_count():
    c, f = CONFIG, flops_qwen3_next
    assert f.layer_kinds(c) == (3, 1)
    assert f.gdn_params(c) == 2048 * (8192 + 4096 + 64) + 8192 * 4 \
        + 4096 * 2048 + 64 + 128 == 33718464
    assert f.attention_params(c) == 2048 * (8192 + 1024) + 4096 * 2048 \
        + 512 == 27263488
    assert f.expert_params(c) == 3 * 2048 * 512 == 3145728
    assert f.shared_params(c) == 3145728 + 2048
    assert f.router_params(c) == 2048 * 512 == 1048576
    # ISSUE 47's sizing: an expert layer as held, the whole share
    assert f.expert_layer_params(c) == 809502720
    assert f.embedding_params(c) == 75968 * 2048 == 155582464
    assert f.norm_params(c) == 9 * 2048
    assert f.held_params(c) == 3522030656
    assert f.held_params(c) * 2 == 7044061312                  # 7.04 GB
    assert c["parameters"]["held"] == f.held_params(c)
    assert c["parameters"]["gdn_layer"] == f.gdn_params(c)
    assert c["parameters"]["attention_layer"] == f.attention_params(c)
    assert c["parameters"]["expert_layer_as_held"] == \
        f.expert_layer_params(c)
    assert c["parameters"]["held_bytes_bfloat16"] == 2 * f.held_params(c)
    assert 0.25 < 7044061312 / 16e9 < 0.7
    assert f.weight_bytes(c) == (3522030656 - 155582464) * 2
    assert f.held_expert_bytes(c) == 4 * 256 * 3145728 * 2 == 6442450944
    assert f.token_matmul_params(c) == 3 * (
        2048 * 12352 + 4096 * 2048) + 2048 * 9216 + 4096 * 2048 \
        + 4 * (1048576 + 3147776) == 145104896
    assert f.gdn_flops_per_token(c) == 16 * 2 * 64 * 128 + 32 * (
        2 * 64 * 128 + 6 * 128 * 128) == 3932160
    assert f.gdn_bytes_per_token(c) == 8192 * 2 + 8 * 32 + 4 * 4096 == 33024
    assert f.gdn_state_bytes_per_row(c) == 2 * 32 * 128 * 128 * 4
    assert f.pair_flops(c) == 2 * 2 * 16 * 256 == 16384
    assert f.routed_flops(c, 10) == 20 * 3145728
    # one document of 3 tokens: 1 + 2 + 3 pairs in the one such layer
    assert f.attention_flops(c, [3]) == 6 * 16384
    assert f.core_flops(c, 512, 2.0) == 2 * 512 * 16384
    assert f.core_bytes(c, 512, 1, 512) == \
        512 * 2 * 512 * 2 + 512 * 4096 * 6
    # the published model whole, with its LM head: 80 B parameters
    whole = dict(c, num_hidden_layers=48, num_experts=512,
                 vocab_size=151936)
    total = f.held_params(whole) + f.embedding_params(whole)  # + the head
    assert round(total / 1e9, 1) == 79.7
    # a call of the mix
    from benchmark.harness import traffic

    mix = json.loads((ROOT / "benchmark/mixes"
                      / "issue_threads_long_tail_c32.json").read_text())
    grid = traffic.length_grid(mix["length"], 32).tolist()
    tokens = sum(grid)
    assert tokens == 143622
    parts = (tokens * (2.0 * f.token_matmul_params(c)
                       + 3 * f.gdn_flops_per_token(c)),
             f.attention_flops(c, grid), f.routed_flops(c, 20 * tokens))
    assert f.encoder_flops(c, tokens, 20 * tokens, grid) == sum(parts)
    assert [round(p / 1e12, 1) for p in parts][0] == 43.4
    # the state of one row at 16,384 tokens
    assert 3 * (2097152 + 49152) + 2 * 2 * 16384 * 256 * 2 == 39993344 \
        == c["parameters"]["state_bytes_a_row_at_16384"]


# -- the configuration --------------------------------------------------------

def test_configuration_holds_the_catalog_rows_numbers_key_for_key():
    if not CATALOG.is_file():
        pytest.skip("the catalog of architectures is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if CONFIG.get(k, "absent") != v)
    assert differ == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    assert CONFIG["published"] == {k: row["config"][k] for k in differ}
    assert row["config"]["num_hidden_layers"] == 48


def test_reduced_names_the_cuts_and_no_width():
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == CONFIG_NAME)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{CONFIG_NAME}.json"
    assert CONFIG["name"] == entry["name"]
    assert entry["reduced"] == CONFIG["reduced"] == REDUCED
    assert entry["name"] in {w["config"] for w in MANIFEST["workloads"]}
    assert len(entry["why"]) <= 200
    # the contract's widths; `num_hidden_layers` is the published DEPTH
    # key (the manifest's own test takes its "hidden" for a width:
    # PERF.md §7, finding 11) and is not renamed to dodge that
    width = re.compile(
        r"(_dim|_rank)$|(hidden|intermediate|latent|state|proj\w*|head\w*)"
        r"_size$|^(emb_sz|n_hid|num_experts_per_tok|expand\w*)$")
    for key in entry["reduced"]:
        assert not width.search(key), key
    assert width.search("moe_intermediate_size")
    # every published width unchanged at the top level
    assert [CONFIG[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "shared_expert_intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "partial_rotary_factor",
        "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "num_experts_per_tok",
        "full_attention_interval", "rope_theta")] == [
        2048, 5120, 512, 512, 16, 2, 256, 0.25, 16, 32, 128, 128, 4, 10, 4,
        10000000]
    # the floors: four layers (one whole period), at least 8 experts,
    # half of the words
    assert CONFIG["num_hidden_layers"] >= 4 and CONFIG["num_experts"] >= 8
    assert CONFIG["num_hidden_layers"] % CONFIG["full_attention_interval"] \
        == 0
    assert CONFIG["vocab_size"] * 2 == 151936
    assert CONFIG["experts_held"] == {"first": 0, "count": 256, "of": 512}
    assert CONFIG["deployment"]["chips_that_share_a_layer"] == 2
    assert set(CONFIG["assumed"]) >= {
        "keys", "num_experts", "layer_kinds", "norms", "l2_norm",
        "value_heads", "gated_norm", "attention_gate", "shared_expert_gate",
        "router", "column_layout", "conv", "vocabulary", "pooling",
        "weights", "dtype", "serve.batch_size", "serve.kv_positions"}


def test_the_program_reads_the_file_as_the_share_it_states():
    from code_intelligence_tpu.models import build_encoder, make_config

    serve = CONFIG["serve"]
    enc = build_encoder(make_config(
        "qwen3_next", CONFIG, kv_positions=serve["kv_positions"],
        state_dtype=CONFIG["state_dtype"]))
    cfg = enc.config
    assert (cfg.num_experts, cfg.experts_held) == (512, (0, 256))
    assert (cfg.gdn_layers, cfg.attention_layers) == ((0, 1, 2), (3,))
    assert (cfg.rotary_dim, cfg.conv_dim) == (64, 8192)
    assert enc.out_dim == 2048
    assert enc.state_bytes_per_row(16384) == 39993344
    assert serve["scheduler"] == "groups" and serve["batch_size"] == 16
    assert serve["buckets"] == [64, 128, 256, 512]
    cell = json.loads(
        (ROOT / "benchmark/cells" / f"{CELL}.json").read_text())
    assert cell["reduced"] == CONFIG["reduced"]
    assert cell["driver"] == "bulk_gdn_moe"
    assert (cell["check"]["sample"], cell["check"]["block_rows"]) == (8, 1)
    limits = cell["check"]["limits"]
    assert {"rel_rms_mean", "rel_rms_max", "rel_rms_mean_carried",
            "rel_rms_max_carried", "rel_rms_mean_long", "rel_rms_max_long",
            "nonfinite", "nonfinite_rows"} <= set(limits)
    # every limit, and each number left without one, is written with
    # its reason; no limit is left at a placeholder
    why = cell["check"]["why"]
    assert set(limits) - {"nonfinite", "nonfinite_rows"} <= set(why)
    assert all(0 <= v < 0.5 for v in limits.values())
    for name in ("rel_rms_last", "rel_rms_last_carried",
                 "rel_rms_last_long"):
        assert name in limits or "NOT LIMITED" in why[name]


# -- the manifest ---------------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_new_metrics_move_docs_per_s_in_this_cell(name):
    metric = BY_NAME[name]
    assert metric["moves"] == "docs_per_s"
    assert CELL in metric["workloads"]
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    spec = json.loads(
        (ROOT / "benchmark/layer_metrics" / f"{name}.json").read_text())
    assert {k: spec[k] for k in ("unit", "better", "source", "layer")} == \
        {k: metric[k] for k in ("unit", "better", "source", "layer")}
    if name.endswith("_roofline"):
        assert (metric["unit"], metric["layer"]) == ("%", "kernels")


OPS = ["jit(fwd)/gdn_0/qkv_proj/dot_general", "jit(fwd)/gdn_0/conv1d/mul",
       "jit(fwd)/gdn_0/gates/exp", "jit(fwd)/gdn_0/gdn_core/while",
       "jit(fwd)/gdn_0/gated_norm/mul", "jit(fwd)/gdn_0/o_proj/dot_general",
       "jit(fwd)/moe_0/router/dot_general", "jit(fwd)/moe_0/dispatch/sort",
       "jit(fwd)/moe_0/experts/jit(_both_products)/gated_gmm/pallas_call",
       "jit(fwd)/moe_0/combine/gather",
       "jit(fwd)/moe_0/shared_expert/dot_general", "ragged-dot-none.4",
       "jit(fwd)/attention_3/qk_norm/mul", "jit(fwd)/attention_3/rope/mul",
       "jit(fwd)/attention_3/global_core/pallas_call",
       "jit(fwd)/attention_3/out_gate/mul",
       "jit(fwd)/attention_3/o_proj/dot_general", "jit(fwd)/final_norm/mul"]


@pytest.mark.parametrize("name,read", [
    ("gdn_core_share_pct", [OPS[3]]),
    ("gdn_mixer_share_pct", OPS[1:5]),
    ("gdn_core_roofline", [OPS[3]]),
    ("gdn_experts_gmm_roofline", [OPS[8], OPS[11]]),
    ("full_attn_core_roofline", [OPS[14]]),
    ("global_core_share_pct", [OPS[14]]),
    ("routed_experts_share_pct", OPS[6:12]),
    ("expert_dispatch_combine_share_pct", [OPS[7], OPS[9]]),
    ("attention_share_pct", OPS[12:17]),
])
def test_the_shares_read_this_models_scopes(name, read):
    """What the new metrics and the accepted shares this cell joins read
    of THIS model's scope paths."""
    spec = json.loads(
        (ROOT / "benchmark/layer_metrics" / f"{name}.json").read_text())
    assert [op for op in OPS
            if any(re.search(s, op) for s in spec["scopes"])] == read


@pytest.mark.parametrize("name", SHARED + ["docs_per_s"])
def test_the_cell_joins_the_metrics_its_spans_and_scopes_carry(name):
    entry = BY_NAME.get(name) or next(
        m for m in MANIFEST["end_to_end"] if m["name"] == name)
    assert CELL in entry["workloads"]


@pytest.mark.parametrize("name", [
    "prep_overlapped_token_pct", "latent_core_share_pct", "moe_share_pct",
    "kda_core_roofline", "zero_choice_pct"])
def test_the_cell_stays_out_of_the_lists_it_has_nothing_for(name):
    assert CELL not in BY_NAME[name]["workloads"]


def test_the_cell_entry_says_why():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG_NAME, "issue_threads_long_tail_c32", 1)
    assert len(entry["why"]) <= 200
    assert "twice" in entry["why"] or "2x" in entry["why"]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 0


# -- the cell's check: every number it yields is held, each limit with its reason --

CHECK = json.loads(
    (ROOT / f"benchmark/cells/{CELL}.json").read_text())["check"]
HELD = [f"rel_rms_{t}{s}" for t in ("mean", "max", "last")
        for s in ("", "_carried", "_long")] \
    + ["rel_err_p50_cached_k", "rel_err_p50_cached_v",
       "rel_err_p50_conv_tail"]


@pytest.mark.parametrize("name", HELD)
def test_every_number_of_the_check_has_a_limit_and_its_reason(name):
    """Nothing a user receives is compared with nothing: the `last`
    third is held like the other two, and so is what a chunk program
    hands the next one to read."""
    assert 0 < CHECK["limits"][name] < 0.1
    assert len(CHECK["why"][name]) > 40
    assert "NOT LIMITED" not in CHECK["why"][name]


def test_the_flops_modules_chunk_is_the_programs():
    """``harness/flops_qwen3_next.py`` imports nothing of the program, so
    it holds the recurrence's chunk as a number of its own: this is what
    keeps the two equal."""
    from code_intelligence_tpu.models import qwen3_next

    assert flops_qwen3_next.GDN_CHUNK == qwen3_next._GDN_CHUNK
