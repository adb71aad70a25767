"""GLM-5's share through the benchmark, tiny, on the CPU: the cell's files
resolve by name; a whole run of its driver against its plain reference
with documents that pass ``index_topk`` several times over; every control
changes the program's rows; a program without the architecture fails at
once; the new per-layer readers on known inputs; the arithmetic of
``harness/flops_glm_dsa.py`` against a hand count; the configuration file
against the catalog row; the mix's grid. Pins membership, never a list's
exact contents or an entry's place."""

import json
import types
from pathlib import Path

import numpy as np
import pytest

import bm_util
from benchmark import run
from benchmark.harness import cell as cells, flops_glm_dsa, traffic
from benchmark.harness.spans import HostSpan, SpanLog

ROOT = bm_util.ROOT
TRACE = Path(__file__).parent / "data" / "tiny.xplane.pb"
CONFIG_NAME = "glm_5_ep16_share"
CONFIG = json.loads(
    (ROOT / f"benchmark/configs/{CONFIG_NAME}.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "glm5_bulk_threads_over_4k"
MIX = json.loads(
    (ROOT / "benchmark/mixes/issue_threads_over_4k_c16.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = ["dsa_moe_fwd_roofline", "dsa_indexer_roofline", "dsa_select_roofline",
       "dsa_core_roofline", "dsa_indexer_share_pct", "dsa_select_share_pct",
       "dsa_core_share_pct", "dsa_keys_selected_per_query"]
SHARED = ["tokenize_share_pct", "tokenize_us_per_doc", "compiles_in_window",
          "encoder_fwd_us_per_token", "device_idle_pct.bulk",
          "text_rules_share_pct", "text_rules_us_per_doc",
          "group_dispatch_share_pct", "device_wait_share_pct",
          "padded_lane_pct", "attention_share_pct",
          "carried_state_mb_per_row", "pre_rule_passes_run_pct",
          "padded_device_time_pct", "narrow_program_time_pct",
          "narrow_lane_cost_ratio", "padded_lane_run_pct",
          "program_enqueue_share_pct", "group_self_ms",
          "routed_experts_share_pct", "expert_dispatch_combine_share_pct",
          "expert_rounds_per_layer_program"]
BY_NAME = {m["name"]: m for m in MANIFEST["per_layer"]}
GRID = [4096, 4096, 4096, 4096, 4690, 5346, 6041, 6794, 7630, 8582, 9696,
        11052, 12790, 15203, 19095, 28574]

# the published structure, small: 8 positions a query of documents of up
# to 200, chunk programs of 16 (a row past 144 was handed over 9 times)
TINY = {
    "vocab_size": 600, "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 4, "v_head_dim": 16, "index_n_heads": 4,
    "index_head_dim": 16, "index_topk": 8, "rope_interleave": True,
    "indexer_rope_interleave": True, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "experts_held": {"first": 2, "count": 4, "of": 8}}
SERVE = {"scheduler": "groups", "batch_size": 4, "buckets": [8, 16],
         "kv_positions": 256}
TINY_MIX = dict(bm_util.TINY_MIX, name="tiny_threads", docs_per_call=6,
                length={"dist": "lognormal", "median": 60, "sigma": 0.9,
                        "min": 8, "max": 200})
SUFFIXES = ("", "_carried", "_long")
LIMITS = {f"rel_rms_{t}{s}": 2e-5 for t in ("mean", "max", "last")
          for s in SUFFIXES}
LIMITS.update(nonfinite=0, nonfinite_rows=0, rel_err_p50_latent=2e-5,
              rel_err_p50_index_keys=2e-5)


def tiny_benchmark(tmp: Path, per_layer=()) -> Path:
    """``bm_util``'s copy of the benchmark with a tiny share, its cell
    and a manifest that names them, as files. The mix's documents run to
    200 tokens: 25 times ``index_topk``, 13 chunk programs."""
    bench = bm_util.tiny_benchmark(tmp)
    bm_util.write(bench / "mixes" / "tiny_threads.json", TINY_MIX)
    bm_util.write(bench / "configs" / "tiny_dsa.json", dict(
        TINY, name="tiny_dsa", architecture="glm_moe_dsa", dtype="float32",
        state_dtype="float32", serve=SERVE,
        weights={"dist": "student_t", "df": 4}, reduced=[]))
    bm_util.write(bench / "cells" / "tiny_dsa_cell.json", {
        "name": "tiny_dsa_cell", "config": "tiny_dsa", "mix": "tiny_threads",
        "chips": 1, "driver": "bulk_dsa_moe", "reduced": [],
        "check": {"sample": 6, "block_rows": 1, "limits": LIMITS}})
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": "tiny_dsa", "source": "test",
                            "file": "benchmark/configs/tiny_dsa.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny_dsa_cell", "config": "tiny_dsa",
                              "traffic": "tiny_threads", "chips": 1,
                              "why": "test"}]
    manifest["per_layer"] = [dict(m, moves="docs_per_s") for m in per_layer]
    bm_util.write(tmp / "BENCHMARK.json", manifest)
    return bench


def main(tmp, *extra, **kw):
    return run.main(["--workload", "tiny_dsa_cell", "--seed",
                     str(2**31 + 53), "--seconds", "0.2", *extra],
                    root=tmp, **kw)


@pytest.fixture
def gate(monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)


def numbers(line):
    return {c["name"]: c["value"] for c in line["compared"]}


# -- the files ---------------------------------------------------------------------

def test_the_cells_files_resolve_by_name():
    cell = cells.load_cell(CELL)
    assert cell["config"]["name"] == CONFIG_NAME
    assert cell["mix"]["name"] == cell["entry"]["traffic"] == MIX["name"]
    assert (cell["chips"], cell["cell"]["driver"]) == (1, "bulk_dsa_moe")
    assert {m["name"] for m in cell["end_to_end"]} == {
        "docs_per_s", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | set(SHARED) <= reported
    # the five parts of set-up are pinned to nine cells (PERF.md section 7),
    # and the latent core's two metrics count every position reached
    assert not {n for n in reported if n.startswith("setup_")}
    assert not {"latent_core_share_pct", "latent_core_roofline"} & reported
    assert hasattr(cells.load_driver("bulk_dsa_moe"), "run")
    assert hasattr(cells.load_reference("glm_moe_dsa"), "encode")
    for name in reported:
        spec, read = cells.load_layer_reader(name)
        assert spec["name"] == name and callable(read)
    limits = cell["cell"]["check"]["limits"]
    assert {"nonfinite", "nonfinite_rows", "rel_rms_mean_long",
            "rel_err_p50_index_keys"} <= set(limits)
    assert set(limits) - {"nonfinite", "nonfinite_rows"} \
        <= set(cell["cell"]["check"]["why"])


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_lists_the_cell_and_agrees_with_its_file(name):
    entry = BY_NAME[name]
    spec, _ = cells.load_layer_reader(name)
    assert CELL in entry["workloads"] and entry["moves"] == "docs_per_s"
    for key in ("unit", "better", "source", "layer"):
        assert entry[key] == spec[key]
    if name.endswith("_roofline"):
        assert (entry["unit"], entry["layer"]) == ("%", "kernels")
    if name.endswith("_share_pct"):
        assert spec["reader"] == "scope_time_share"  # data files only


def test_the_configuration_is_the_catalog_row_cut_to_a_share():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "GLM-5")
    assert CONFIG["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert changed == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"}
    assert CONFIG["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880}
    assert [CONFIG[k] for k in CONFIG["reduced"]] == [5, 1, 16, 19360]
    assert CONFIG["experts_held"] == {"first": 0, "count": 16, "of": 256}
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG_NAME)
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG_NAME}.json"
    assert CONFIG["serve"] == {
        "scheduler": "groups", "batch_size": 8,
        "buckets": [64, 128, 256, 512], "kv_positions": 32768}
    assert {"head_dim", "dsa_source", "indexer_layer_norm_eps",
            "indexer_rope", "indexer_weights", "selection",
            "indexer_precision", "no_mtp", "pooling", "vocabulary",
            "weights", "serve.batch_size", "serve.kv_positions",
            "rows_per_expert"} <= set(CONFIG["assumed"])
    assert "no code stands in" in CONFIG["deployment"]["exchange"]
    assert CONFIG["deployment"]["chips_that_share_a_layer"] == 16


def test_held_params_to_the_unit():
    p = CONFIG["parameters"]
    f = flops_glm_dsa
    assert f.mla_params(CONFIG) == p["latent_attention"] == 165022208 \
        == 6144 * 2048 + 2048 + 2048 * 16384 + 6144 * 576 + 512 \
        + 512 * 28672 + 16384 * 6144
    assert f.indexer_params(CONFIG) == p["indexer"] == 9371904 \
        == 2048 * 4096 + 6144 * 128 + 256 + 6144 * 32
    assert f.dense_layer_params(CONFIG) == p["dense_layer"] == 400898816
    assert f.expert_params(CONFIG) == p["expert"] == 37748736
    assert f.router_params(CONFIG) == p["router"] == 1573120
    assert f.expert_layer_params(CONFIG) == p["expert_layer_16_held"] \
        == 817708032
    assert f.held_params(CONFIG) == p["held"] == 3790684928
    assert p["held_bytes_bfloat16"] == 2 * p["held"] == 7581369856
    assert f.weight_bytes(CONFIG) == 2 * (3790684928 - 19360 * 6144)
    assert f.index_pair_flops(CONFIG) == 8192
    assert f.pair_flops(CONFIG) == 64 * 1024
    assert f.expand_flops(CONFIG) == 2 * 512 * 64 * 448
    assert f.select_bytes(1000) == 5000
    assert p["state_bytes_a_row_at_32768"] == 5 * 32768 * 1408 == 230686720
    assert 460e6 < 2 * f.token_matmul_params(CONFIG) / 5 < 480e6


def test_the_seeded_tree_is_the_count():
    import jax
    import jax.numpy as jnp

    ref = cells.load_reference("glm_moe_dsa")
    shapes = jax.eval_shape(lambda k: ref.init_params(
        k, CONFIG, CONFIG["weights"], jnp.bfloat16), jax.random.PRNGKey(0))
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) \
        == CONFIG["parameters"]["held"]


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 5300000011])
def test_the_mixs_grid_is_the_issues_sixteen_lengths(seed):
    """Every call holds the same 16 lengths on every seed; 78.4 % of
    positions lie beyond 2,048; a query is scored against 6,994
    positions and attends 1,827 by the selection's arithmetic."""
    from code_intelligence_tpu.text import SPECIALS

    assert sorted(int(n) for n in traffic.length_grid(MIX["length"], 16)) \
        == GRID
    words = traffic.vocab_words(SPECIALS, CONFIG["vocab_size"])
    (call,) = traffic.make_document_calls(MIX, words, seed, 1, stream=1)
    assert sorted(len(d["ids"]) for d in call) == GRID
    assert max(int(d["ids"].max()) for d in call) < CONFIG["vocab_size"]
    total = sum(GRID)
    assert total == 151877
    assert sum(max(0, n - 2048) for n in GRID) / total \
        == pytest.approx(0.784, abs=5e-4)
    scored = sum(n * (n + 1) // 2 for n in GRID)
    selected = sum(min(2048, t + 1) for n in GRID for t in range(n))
    assert (scored, selected) == (1062161614, 277506048)
    assert scored / total == pytest.approx(6994, abs=1)
    assert selected / total == pytest.approx(1827, abs=1)


# -- a whole run -------------------------------------------------------------------

def test_cell_runs_and_agrees_with_its_reference(tmp_path, gate):
    """Documents of up to 25 times ``index_topk`` through chunk programs
    of 16: both caches, the selection, the share's router and the
    tokeniser are inside the comparison, at float32 tightness."""
    per_layer = [{k: BY_NAME[n][k] for k in (
        "name", "unit", "better", "source", "layer")} for n in SHARED + NEW]
    tiny_benchmark(tmp_path, per_layer)
    line = main(tmp_path, "--trace", "0")
    assert line["correct"] and line["failed"] == 0, line["compared"]
    got = numbers(line)
    assert set(LIMITS) <= set(got)
    assert got["rel_rms_mean_carried"] < 5e-6
    for name in ("latent", "index_keys"):
        assert 0 <= got[f"rel_err_p50_{name}"] < 5e-6
    assert line["counters"]["compiles_in_window"] == 0

    traced = main(tmp_path, "--trace", "1")
    assert traced["correct"]
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    # two caches of 16 + 4 and 16 wide, 3 layers, float32, at the 24 ..
    # 256 positions a multi-chunk group is allocated
    assert 3 * 24 * 36 * 4 / 1e6 <= metrics["carried_state_mb_per_row"] \
        <= 3 * 256 * 36 * 4 / 1e6
    # the selection's arithmetic over the window's documents
    from code_intelligence_tpu.text import SPECIALS
    words = traffic.vocab_words(SPECIALS, 600)
    pool = traffic.make_document_calls(TINY_MIX, words, 2**31 + 53, 2,
                                       stream=1)
    lengths = [len(d["ids"]) for k in range(traced["counters"]["calls"])
               for d in pool[k % 2]]
    assert metrics["dsa_keys_selected_per_query"] == pytest.approx(
        sum(min(8, t + 1) for n in lengths for t in range(n)) / sum(lengths))
    counters = traced["counters"]
    assert counters["dsa_pairs_scored"] == 3 * sum(
        n * (n + 1) // 2 for n in lengths)
    assert counters["dsa_pairs_selected"] == 3 * sum(
        min(8, t + 1) for n in lengths for t in range(n))
    assert counters["dsa_threshold_ties"] >= 0
    assert counters["dsa_kernel_layers"] == 0
    assert metrics["expert_rounds_per_layer_program"] >= 1
    # no device plane in a CPU capture: the scope readers find nothing
    # and their metrics are left out, not reported as zero
    assert not {n for n in NEW if n != "dsa_keys_selected_per_query"} \
        & set(metrics)


def test_a_control_reads_not_correct_through_the_limits(tmp_path, gate):
    from code_intelligence_tpu.models import glm_moe_dsa
    from code_intelligence_tpu.ops import dsa

    real = (dsa.select, dsa.head_scores, glm_moe_dsa.index_rope)
    tiny_benchmark(tmp_path)
    line = main(tmp_path, overrides={"select": "recent"})
    assert (dsa.select, dsa.head_scores, glm_moe_dsa.index_rope) == real
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if not c["inside"]}
    assert "rel_rms_mean_carried" in bad, bad
    # the rows handed on are the sound ones in the first layer; the mask
    # moved what the later layers read
    assert numbers(line)["rel_err_p50_index_keys"] > 5e-6


@pytest.fixture(scope="module")
def sound():
    """The tiny share's engine as the driver builds it, its weights, and
    the rows of three documents of 12, 5 and under 1 times
    ``index_topk``."""
    import jax

    from benchmark.reference import common
    from code_intelligence_tpu.text import SPECIALS, Vocab

    driver = cells.load_driver("bulk_dsa_moe")
    ref = cells.load_reference("glm_moe_dsa")
    config = dict(TINY, architecture="glm_moe_dsa", dtype="float32",
                  state_dtype="float32", serve=SERVE)
    params = jax.jit(lambda k: ref.init_params(
        k, config, {"dist": "student_t", "df": 4}))(common.seed_key(53))
    rng = np.random.default_rng(3)
    seqs = [rng.integers(4, 600, n).astype(np.int32) for n in (96, 40, 7)]
    vocab = Vocab(traffic.vocab_words(SPECIALS, 600))

    def rows(**overrides):
        ctx = types.SimpleNamespace(config=config, overrides=overrides)
        return driver.build_engine(ctx, params, vocab).embed_ids_batch(seqs)

    return rows, rows()


@pytest.mark.parametrize("control,floor,under_k_alone", [
    ({"select": "off"}, 1e-3, True),
    ({"select": "recent"}, 1e-3, True),
    ({"topk": "4"}, 1e-3, False),
    ({"indexer_weights": "uniform"}, 1e-4, True),
    ({"indexer_relu": "off"}, 1e-4, True),
    ({"indexer_rope": "off"}, 1e-4, True),
    ({"indexer_heads": "first8"}, 0, True),
    ({"index_cache": "zeroed"}, 1e-4, True),
    ({"caches": "zeroed"}, 1e-2, True),
    ({"router_bias": "off"}, 1e-4, False),
    ({"state_dtype": "bfloat16"}, 1e-4, False),
])
def test_every_control_moves_the_rows(sound, control, floor, under_k_alone):
    """float32 sound runs repeat to the bit; each control moves the mean
    third of the rows that passed ``index_topk`` far above rounding, and
    leaves a document shorter than ``index_topk`` (one chunk program)
    alone where it touches only the selection or what a later program
    reads. The tiny indexer has 4 heads, so ``first8`` is all of them
    and moves nothing."""
    rows, want = sound
    got = rows(**control)
    moved = np.abs(got - want)[:, :32].max(axis=1) / np.abs(want[:, :32]).max()
    if floor:
        assert moved[0] > floor and moved[1] > floor, moved
    else:
        assert moved.max() < 1e-6, moved
    assert (moved[2] < 1e-6) == under_k_alone, moved
    np.testing.assert_array_equal(rows(), want)


def test_on_a_program_without_the_architecture_the_cell_fails_at_once(
        tmp_path, gate, monkeypatch):
    """The parent commit has no ``glm_moe_dsa``: ``make_config`` raises
    before a weight is made, and nothing hangs."""
    from code_intelligence_tpu.models import contract

    tiny_benchmark(tmp_path)
    monkeypatch.delitem(contract.ENCODERS, "glm_moe_dsa")
    with pytest.raises(ValueError,
                       match="unknown architecture 'glm_moe_dsa'"):
        main(tmp_path)


# -- the readers on known inputs ---------------------------------------------------

PROGRAMS = [HostSpan("engine.program", 0, 0, {
    "rows": r, "batch": 8, "bucket": 512, "valid_tokens": r * 400,
    "lane_steps": r * 512}) for r in [8] * 36 + [4] * 10 + [2] * 8 + [1] * 16]
STEPS = 512 * (8 * 36 + 4 * 10 + 2 * 8 + 16)
MET = 3400000
GROUPS = [HostSpan("engine.group", 0, 1, {
    "rows": 8, "batch": 8, "bucket": 512, "chunks": 56,
    "valid_tokens": 150000, "lane_steps_run": STEPS,
    "cache_steps_run": MET})]
SCORED, SELECTED, ROUTED = 5 * 1060000000, 5 * 277000000, 4 * 75000
FLUSHES = [
    HostSpan("engine.finalize", 2, 3, {
        "groups": 1, "dsa_pairs_scored": SCORED,
        "dsa_pairs_selected": SELECTED, "dsa_threshold_ties": 0,
        "routed_rows": ROUTED, "dsa_kernel_layers": 0.0}),
    HostSpan("engine.finalize", 3, 4, {"groups": 1})]   # another encoder's


def test_layer_readers_on_known_inputs(capsys):
    from benchmark.harness import readers

    ctx = readers.ReaderContext()
    ctx.config = CONFIG
    ctx.spans = ctx.traced_spans = SpanLog()
    ctx.spans.spans = PROGRAMS + GROUPS + FLUSHES
    ctx.reduced["modules"] = {"jit_fwd_b8_l512": [5.0, 2.5]}
    ctx.result = {"xplane_path": str(TRACE)}
    ctx.device_kind = "TPU v5 lite"
    load = cells.load_layer_reader
    f = flops_glm_dsa
    dot = [r"(^|/)dot_general"]     # the recorded trace's one named scope
    dot_s = 3.644766e-06

    spec, read = load("dsa_keys_selected_per_query")
    assert read(ctx, spec) == pytest.approx(SELECTED / 5 / 150000)

    for name in ("dsa_indexer_share_pct", "dsa_select_share_pct",
                 "dsa_core_share_pct"):
        spec, read = load(name)
        assert read(ctx, spec) is None     # no such scope in that trace
        assert read(ctx, dict(spec, scopes=dot)) == \
            pytest.approx(100 * dot_s / 7.5)

    spec, read = load("dsa_moe_fwd_roofline")
    need = (2 * f.token_matmul_params(CONFIG) * STEPS
            + 2 * ROUTED * 37748736 + SCORED * 8192 + SELECTED * 65536
            + 5 * MET * 2 * 512 * 64 * 448)
    assert need / 197e12 > 2 * f.weight_bytes(CONFIG) / 819e9
    assert read(ctx, spec) == pytest.approx(100 * (need / 197e12) / 7.5)
    assert "compute-bound" in capsys.readouterr().out

    spec, read = load("dsa_indexer_roofline")
    assert read(ctx, spec) is None
    assert read(ctx, dict(spec, scopes=dot)) == pytest.approx(
        100 * (SCORED * 8192 / 197e12) / dot_s)

    spec, read = load("dsa_select_roofline")
    assert read(ctx, dict(spec, scopes=dot)) == pytest.approx(
        100 * (SCORED * 5 / 819e9) / dot_s)
    assert "memory-bound" in capsys.readouterr().out

    spec, read = load("dsa_core_roofline")
    need = SELECTED * 65536 + 5 * MET * 2 * 512 * 64 * 448
    assert read(ctx, dict(spec, scopes=dot)) == pytest.approx(
        100 * (need / 197e12) / dot_s)

    # a program whose spans lack the counts gives nothing to read
    ctx.spans.spans = PROGRAMS + GROUPS + FLUSHES[1:]
    for name in NEW:
        spec, read = load(name)
        if spec.get("reader") != "scope_time_share":
            assert read(ctx, dict(spec, scopes=dot)) is None
