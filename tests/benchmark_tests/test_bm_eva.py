"""EvaByte's pipeline stage through the benchmark, tiny, on the CPU: the
cell's files resolve by name; a whole run of its driver against its
plain reference with byte documents that cross blocks; every control
changes the program's rows; a program without the architecture fails at
once; the new per-layer readers on known inputs; the arithmetic of
``harness/flops_evabyte.py`` against a hand count; the configuration file
against the catalog row; the mix's byte lengths. Pins membership, never a
list's exact contents or an entry's place."""

import json
import types
from pathlib import Path

import numpy as np
import pytest

import bm_util
from benchmark import run
from benchmark.harness import cell as cells, flops_evabyte
from benchmark.harness.spans import HostSpan, SpanLog
from code_intelligence_tpu.text import SPECIALS

ROOT = bm_util.ROOT
TRACE = Path(__file__).parent / "data" / "tiny.xplane.pb"
CONFIG_NAME = "evabyte_6_5b_pp4_stage0"
CONFIG = json.loads(
    (ROOT / f"benchmark/configs/{CONFIG_NAME}.json").read_text())
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "evabyte_bulk_threads_32kb"
MIX = json.loads(
    (ROOT / "benchmark/mixes/issue_threads_32kb_c32.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = ["eva_fwd_roofline", "eva_core_roofline", "eva_summaries_roofline",
       "eva_core_share_pct", "eva_summaries_share_pct",
       "eva_keys_met_per_query"]
SHARED = ["tokenize_share_pct", "tokenize_us_per_doc", "compiles_in_window",
          "encoder_fwd_us_per_token", "device_idle_pct.bulk",
          "text_rules_share_pct", "text_rules_us_per_doc",
          "group_dispatch_share_pct", "device_wait_share_pct",
          "padded_lane_pct", "attention_share_pct",
          "carried_state_mb_per_row", "pre_rule_passes_run_pct",
          "padded_device_time_pct", "narrow_program_time_pct",
          "narrow_lane_cost_ratio", "padded_lane_run_pct",
          "program_enqueue_share_pct", "group_self_ms"]
BY_NAME = {m["name"]: m for m in MANIFEST["per_layer"]}

# the published structure, small: a block of 32 bytes in chunks of 4, and
# chunk programs of 16, so that a block is two programs
TINY = {
    "vocab_size": 320, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "window_size": 32, "chunk_size": 4,
    "rms_norm_eps": 1e-5, "rope_theta": 100000, "attention_class": "eva",
    "norm_add_unit_offset": True, "max_position_embeddings": 512}
SERVE = {"scheduler": "groups", "batch_size": 4, "buckets": [8, 16],
         "kv_positions": 512}
SUFFIXES = ("", "_carried", "_long")
LIMITS = {f"rel_rms_{t}{s}": 2e-5 for t in ("mean", "max", "last")
          for s in SUFFIXES}
LIMITS.update(nonfinite=0, nonfinite_rows=0, **{
    f"rel_err_p50_{name}": 2e-5
    for name in ("block_k", "block_v", "sum_k", "sum_v")})


def tiny_benchmark(tmp: Path, per_layer=()) -> Path:
    """``bm_util``'s copy of the benchmark with a tiny stage, its mix,
    its cell and a manifest that names them, as files. The mix's
    documents run to 96 words, about 480 bytes: 15 blocks."""
    bench = bm_util.tiny_benchmark(tmp)
    bm_util.write(bench / "configs" / "tiny_eva.json", dict(
        TINY, name="tiny_eva", architecture="evabyte", dtype="float32",
        state_dtype="float32", serve=SERVE,
        weights={"dist": "student_t", "df": 4}, reduced=[]))
    bm_util.write(bench / "mixes" / "tiny_bytes.json", dict(
        bm_util.TINY_MIX, name="tiny_bytes", words={"vocabulary": 600}))
    bm_util.write(bench / "cells" / "tiny_eva_cell.json", {
        "name": "tiny_eva_cell", "config": "tiny_eva", "mix": "tiny_bytes",
        "chips": 1, "driver": "bulk_eva", "reduced": [],
        "check": {"sample": 6, "block_rows": 1, "limits": LIMITS}})
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": "tiny_eva", "source": "test",
                            "file": "benchmark/configs/tiny_eva.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny_eva_cell", "config": "tiny_eva",
                              "traffic": "tiny_bytes", "chips": 1,
                              "why": "test"}]
    manifest["per_layer"] = [dict(m, moves="docs_per_s") for m in per_layer]
    bm_util.write(tmp / "BENCHMARK.json", manifest)
    return bench


def main(tmp, *extra, **kw):
    return run.main(["--workload", "tiny_eva_cell", "--seed",
                     str(2**31 + 51), "--seconds", "0.2", *extra],
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
    assert (cell["chips"], cell["cell"]["driver"]) == (1, "bulk_eva")
    assert {m["name"] for m in cell["end_to_end"]} == {
        "docs_per_s", "setup_s"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | set(SHARED) <= reported
    # the five parts of set-up are pinned to nine cells (PERF.md section 7)
    assert not {n for n in reported if n.startswith("setup_")}
    assert "global_core_share_pct" not in reported
    assert hasattr(cells.load_driver("bulk_eva"), "run")
    assert hasattr(cells.load_reference("evabyte"), "encode")
    for name in reported:
        spec, read = cells.load_layer_reader(name)
        assert spec["name"] == name and callable(read)
    limits = cell["cell"]["check"]["limits"]
    assert {"nonfinite", "nonfinite_rows"} <= set(limits)
    assert set(limits) - {"nonfinite", "nonfinite_rows"} \
        <= set(cell["cell"]["check"]["why"])


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_the_cells_alone_and_agrees_with_its_file(name):
    entry = BY_NAME[name]
    spec, _ = cells.load_layer_reader(name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "docs_per_s"
    for key in ("unit", "better", "source", "layer"):
        assert entry[key] == spec[key]
    if name.endswith("_roofline"):
        assert (entry["unit"], entry["layer"]) == ("%", "kernels")


def test_the_configuration_is_the_catalog_row_cut_in_depth_alone():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "EvaByte")
    assert CONFIG["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(CONFIG["reduced"])
    assert CONFIG["published"] == {"num_hidden_layers": 32}
    assert CONFIG["num_hidden_layers"] == 8
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG_NAME)
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG_NAME}.json"
    assert CONFIG["serve"] == {
        "scheduler": "groups", "batch_size": 8,
        "buckets": [64, 128, 256, 512], "kv_positions": 32768}
    assert {"head_dim", "rotary_pairing", "keys_turned_first",
            "chunk_summaries", "norm", "specials", "text", "pooling",
            "no_heads", "weights", "serve.batch_size",
            "serve.kv_positions"} <= set(CONFIG["assumed"])
    assert "no code stands in" in CONFIG["deployment"]["hand_over"]


def test_held_params_to_the_unit():
    p = CONFIG["parameters"]
    assert flops_evabyte.layer_params(CONFIG) == p["layer"] == 202391552 \
        == 4 * 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert flops_evabyte.held_params(CONFIG) == p["held"] == 1620447232
    assert p["held_bytes_bfloat16"] == 2 * p["held"] == 3240894464
    assert flops_evabyte.weight_bytes(CONFIG) == 2 * (1620447232 - 1310720)
    assert 32 * p["layer"] + 1310720 + 8 * 320 * 4096 + 4096 == 6488330240
    assert 2 * flops_evabyte.layer_matmul_params(CONFIG) == 404750336
    assert flops_evabyte.pair_flops(CONFIG) == 16384
    assert flops_evabyte.summaries_flops_per_position(CONFIG) == 24576
    assert flops_evabyte.summaries_bytes_per_position(CONFIG) == 17408
    assert p["state_bytes_a_row_at_32768"] == 8 * 67108864 == 536870912


def test_the_seeded_tree_is_the_count():
    import jax
    import jax.numpy as jnp

    ref = cells.load_reference("evabyte")
    shapes = jax.eval_shape(lambda k: ref.init_params(
        k, CONFIG, CONFIG["weights"], jnp.bfloat16), jax.random.PRNGKey(0))
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) \
        == CONFIG["parameters"]["held"]


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 5100000011])
def test_the_mixs_documents_fit_the_context_as_bytes(seed):
    """6,000 words are 30.3-30.5 KB; the same multiset of word lengths on
    every seed, bytes follow the seed a little; a query meets 1,658 keys
    on average by the mask's arithmetic."""
    shim = cells.load_driver("bulk_eva").byte_traffic(MIX)
    words = shim.vocab_words(SPECIALS, 320)
    assert len(words) == MIX["words"]["vocabulary"] == 60000
    (call,) = shim.make_document_calls(MIX, words, seed, 1, stream=1)
    lengths = sorted(len(d["ids"]) for d in call)
    assert len(lengths) == 32 and lengths[0] > 1700
    assert 30000 < lengths[-8] and lengths[-1] < 32768 - 2048
    assert 540000 < sum(lengths) < 543000
    assert sum(n > 2048 for n in lengths) == 31
    beyond = sum(max(0, n - 2048) for n in lengths) / sum(lengths)
    assert 0.87 < beyond < 0.89
    met = sum(p % 2048 + 1 + p // 2048 * 128
              for n in lengths for p in range(n)) / sum(lengths)
    assert 1640 < met < 1680
    doc = call[0]
    assert doc["ids"][0] == 1 and doc["ids"][1:].min() >= 64 + 32


# -- a whole run -------------------------------------------------------------------

def test_cell_runs_and_agrees_with_its_reference(tmp_path, gate):
    """Documents of up to 15 blocks through chunk programs of 16 (a block
    is two programs): the block cache, the summaries, the byte
    vocabulary and the tokeniser are inside the comparison, at float32
    tightness."""
    per_layer = [{k: BY_NAME[n][k] for k in (
        "name", "unit", "better", "source", "layer")} for n in SHARED + NEW]
    tiny_benchmark(tmp_path, per_layer)
    line = main(tmp_path, "--trace", "0")
    assert line["correct"] and line["failed"] == 0, line["compared"]
    got = numbers(line)
    assert set(LIMITS) <= set(got)
    assert got["rel_rms_mean_long"] < 5e-6
    # what two chunk programs of 16 hand the third to read: a block of
    # keys and values, 8 chunks' summaries (a median may round to 0)
    for name in ("block_k", "block_v", "sum_k", "sum_v"):
        assert 0 <= got[f"rel_err_p50_{name}"] < 5e-6
    assert line["counters"]["compiles_in_window"] == 0

    traced = main(tmp_path, "--trace", "1")
    assert traced["correct"]
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    # groups of more than one program: a block of 32 slots and a summary
    # for every 4 of the 64 .. 512 positions allocated, 4 heads of 16,
    # twice, 2 layers, float32
    assert 2 * 2 * 64 * (32 + 16) * 4 / 1e6 \
        < metrics["carried_state_mb_per_row"] \
        <= 2 * 2 * 64 * (32 + 128) * 4 / 1e6
    # the mask's arithmetic over the window's documents
    lengths = [n for k in range(traced["counters"]["calls"])
               for n in _byte_lengths(tmp_path, 2**31 + 51)[k % 2]]
    assert metrics["eva_keys_met_per_query"] == pytest.approx(sum(
        p % 32 + 1 + p // 32 * 8 for n in lengths for p in range(n))
        / sum(lengths))
    assert 0 < metrics["padded_lane_pct"] < 100
    # no device plane in a CPU capture: the scope readers find nothing
    # and their metrics are left out, not reported as zero
    assert not {"eva_fwd_roofline", "eva_core_roofline",
                "eva_summaries_roofline", "eva_core_share_pct",
                "eva_summaries_share_pct", "attention_share_pct"} \
        & set(metrics)


def _byte_lengths(tmp, seed):
    mix = json.loads((tmp / "benchmark/mixes/tiny_bytes.json").read_text())
    shim = cells.load_driver("bulk_eva").byte_traffic(mix)
    pool = shim.make_document_calls(
        mix, shim.vocab_words(SPECIALS, 320), seed, 2, stream=1)
    return [[len(d["ids"]) for d in call] for call in pool]


def test_a_control_reads_not_correct_through_the_limits(tmp_path, gate):
    from code_intelligence_tpu.models import evabyte
    from code_intelligence_tpu.ops import eva

    real = (eva._reach, evabyte._unit_offset, evabyte.rope_qk)
    tiny_benchmark(tmp_path)
    line = main(tmp_path, overrides={"window": "sliding"})
    assert (eva._reach, evabyte._unit_offset, evabyte.rope_qk) == real
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if not c["inside"]}
    assert "rel_rms_mean_long" in bad, bad
    # the keys and values handed on are the sound ones: the mask moved
    assert numbers(line)["rel_err_p50_block_k"] < 5e-6


@pytest.fixture(scope="module")
def sound():
    """The tiny stage's engine as the driver builds it, its weights, and
    the rows of three documents of 4, 2 and 1 blocks."""
    import jax

    from benchmark.reference import common

    driver = cells.load_driver("bulk_eva")
    ref = cells.load_reference("evabyte")
    config = dict(TINY, architecture="evabyte", dtype="float32",
                  state_dtype="float32", serve=SERVE)
    params = jax.jit(lambda k: ref.init_params(
        k, config, {"dist": "student_t", "df": 4}))(common.seed_key(51))
    rng = np.random.default_rng(3)
    seqs = [rng.integers(64, 320, n).astype(np.int32) for n in (128, 60, 20)]

    def rows(**overrides):
        ctx = types.SimpleNamespace(config=config, overrides=overrides)
        return driver.build_engine(ctx, params, None).embed_ids_batch(seqs)

    return rows, rows()


@pytest.mark.parametrize("control,floor,one_block_alone", [
    ({"summaries": "zeroed"}, 1e-2, True),
    ({"summaries": "mean"}, 1e-3, True),
    ({"summaries": "early"}, 1e-3, False),
    ({"mu": "off"}, 1e-3, True),
    ({"window": "sliding"}, 1e-2, True),
    ({"rope": "off"}, 1e-2, False),
    ({"norm_weight": "plain"}, 1e-1, False),
    ({"caches": "zeroed"}, 1e-2, False),
    ({"state_dtype": "bfloat16"}, 1e-4, False),
])
def test_every_control_moves_the_rows(sound, control, floor,
                                      one_block_alone):
    """float32 sound runs repeat to the bit; each control moves the mean
    third of the rows that crossed a block far above rounding, and
    leaves a document inside one block alone where it touches only what
    a later block reads."""
    rows, want = sound
    got = rows(**control)
    moved = np.abs(got - want)[:, :64].max(axis=1) / np.abs(want[:, :64]).max()
    assert moved[0] > floor and moved[1] > floor, moved
    assert (moved[2] < 1e-6) == one_block_alone, moved
    np.testing.assert_array_equal(rows(), want)


def test_on_a_program_without_the_architecture_the_cell_fails_at_once(
        tmp_path, gate, monkeypatch):
    """The parent commit has no ``evabyte``: ``make_config`` raises
    before a weight is made, and nothing hangs."""
    from code_intelligence_tpu.models import contract

    tiny_benchmark(tmp_path)
    monkeypatch.delitem(contract.ENCODERS, "evabyte")
    with pytest.raises(ValueError, match="unknown architecture 'evabyte'"):
        main(tmp_path)


# -- the readers on known inputs ---------------------------------------------------

PROGRAMS = [HostSpan("engine.program", 0, 0, {
    "rows": r, "batch": 8, "bucket": 512, "valid_tokens": r * 400,
    "lane_steps": r * 512}) for r in [8] * 40 + [4] * 12 + [2] * 6]
STEPS = 512 * (8 * 40 + 4 * 12 + 2 * 6)
GROUPS = [HostSpan("engine.group", 0, 1, {
    "rows": 8, "batch": 8, "bucket": 512, "chunks": 58,
    "valid_tokens": 150000, "lane_steps_run": STEPS})]
FLUSHES = [
    HostSpan("engine.finalize", 2, 3, {
        "groups": 1, "eva_singleton_pairs": 150000000,
        "eva_summary_pairs": 90000000, "eva_summaries_written": 9400,
        "eva_kernel_layers": 0.0}),
    HostSpan("engine.finalize", 3, 4, {"groups": 1})]   # another encoder's
PAIRS = 240000000


def test_layer_readers_on_known_inputs(capsys):
    from benchmark.harness import readers

    ctx = readers.ReaderContext()
    ctx.config = CONFIG
    ctx.spans = ctx.traced_spans = SpanLog()
    ctx.spans.spans = PROGRAMS + GROUPS + FLUSHES
    ctx.reduced["modules"] = {"jit_fwd_b8_l512": [0.5, 0.25]}
    ctx.result = {"xplane_path": str(TRACE)}
    ctx.device_kind = "TPU v5 lite"
    load = cells.load_layer_reader
    dot = [r"(^|/)dot_general"]     # the recorded trace's one named scope
    dot_s = 3.644766e-06

    spec, read = load("eva_keys_met_per_query")
    assert read(ctx, spec) == pytest.approx(PAIRS / 150000)

    for name in ("eva_core_share_pct", "eva_summaries_share_pct"):
        spec, read = load(name)
        assert read(ctx, spec) is None     # no such scope in that trace
        assert read(ctx, dict(spec, scopes=dot)) == \
            pytest.approx(100 * dot_s / 0.75)

    spec, read = load("eva_fwd_roofline")
    need = 8 * (STEPS * (404750336 + 24576) + PAIRS * 16384)
    assert need / 197e12 > 2 * flops_evabyte.weight_bytes(CONFIG) / 819e9
    assert read(ctx, spec) == pytest.approx(100 * (need / 197e12) / 0.75)
    assert "compute-bound" in capsys.readouterr().out

    spec, read = load("eva_core_roofline")
    assert read(ctx, spec) is None
    need = 8 * PAIRS * 16384
    moved = 8 * (PAIRS / 512 * 16384 + STEPS * 4096 * 6)
    assert need / 197e12 > moved / 819e9
    assert read(ctx, dict(spec, scopes=dot)) == \
        pytest.approx(100 * (need / 197e12) / dot_s)

    spec, read = load("eva_summaries_roofline")
    assert read(ctx, spec) is None
    moved = 8 * STEPS * 17408
    assert moved / 819e9 > 8 * STEPS * 24576 / 197e12
    assert read(ctx, dict(spec, scopes=dot)) == \
        pytest.approx(100 * (moved / 819e9) / dot_s)
    assert "memory-bound" in capsys.readouterr().out

    # a program whose spans lack the counts gives nothing to read
    ctx.spans.spans = PROGRAMS + GROUPS + FLUSHES[1:]
    for name in NEW:
        spec, read = load(name)
        if spec.get("reader") != "scope_time_share":
            assert read(ctx, dict(spec, scopes=dot)) is None
