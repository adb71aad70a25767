"""The five start-up metrics (PR 49) and their one reader,
``layer_metrics/compile_ledger.py``: what ``setup_s`` was made of, read
from the program's compile ledger (``flight_recorder.XLAAccountant``'s
stage records). On hand-made records: a function traced inside another
counts once, what ended after the window opened is left out, and an empty
ledger, a program without one (the parent commit) or a run without spans
gives nothing. Each manifest entry agrees with its file, the nine cells
list all five and ``deepseek_v3_bulk_mixed`` none, and a tiny traced run
on the CPU prints them."""

import pytest

import bm_util
from benchmark import run
from benchmark.harness import cell as cells
from test_bm_span_readers import context, log_of

NAMES = {
    "setup_jit_trace_s": ("s", "trace", "union_s"),
    "setup_jit_lower_s": ("s", "lower", "union_s"),
    "setup_compile_or_load_s": ("s", "compile", "union_s"),
    "setup_programs_compiled": ("count", "compile", "count"),
    "setup_cache_miss_programs": ("count", "compile", "miss_count"),
}
CELLS = ["lstm_bulk_mixed", "qrnn_bulk_mixed", "lstm_train_lm",
         "granite_bulk_mixed", "trinity_bulk_long_tail",
         "ling_bulk_long_tail", "smallthinker_bulk_long_tail",
         "longcat_bulk_long_tail", "qwen3_next_bulk_long_tail"]
MANIFEST = cells.load_manifest()

# the window opens at 100.0: the earliest start among the run's spans
SPANS = [("bench.doc", 100.0, 100.5, {}), ("engine.group", 100.1, 100.4, {}),
         ("bench.doc", 103.0, 103.5, {})]


def rec(stage, fn, t0, t1, **more):
    return dict(stage=stage, fn=fn, start_unix=t0, end_unix=t1, thread=1,
                **more)


LEDGER = [
    rec("trace", "multiply", 10.5, 10.7),           # inside fwd's tracing
    rec("trace", "fwd_b4_l16", 10.0, 12.0),
    rec("lower", "fwd_b4_l16", 12.0, 12.5),
    rec("compile", "fwd_b4_l16", 12.5, 20.0, cache="miss", retrieval_s=0.0),
    rec("trace", "fwd_b2_l16", 30.0, 31.0),
    rec("trace", "narrow", 30.5, 31.5),             # another thread, overlaps
    rec("lower", "fwd_b2_l16", 31.0, 31.25),
    rec("compile", "fwd_b2_l16", 31.25, 31.5, cache="hit", retrieval_s=0.2),
    rec("compile", "convert_element_type", 40.0, 40.1, cache="off",
        retrieval_s=0.0),
    rec("trace", "encode", 99.0, 100.2),            # ends inside the window
    rec("trace", "encode", 120.0, 121.0),           # the check's, after it
    rec("lower", "encode", 121.0, 121.5),
    rec("compile", "encode", 121.5, 125.0, cache="miss", retrieval_s=0.0),
]
WANT = {"setup_jit_trace_s": 2.0 + 1.5, "setup_jit_lower_s": 0.75,
        "setup_compile_or_load_s": 7.5 + 0.25 + 0.1,
        "setup_programs_compiled": 3.0, "setup_cache_miss_programs": 2.0}


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_records_that_ended_before_the_window_opened(name):
    spec, read = cells.load_layer_reader(name)
    got = read(context(log_of(*SPANS)), spec, records=LEDGER)
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("records,spans", [
    ([], SPANS), (None, SPANS), (LEDGER, [])],
    ids=["empty-ledger", "ledger-as-the-process-has-it", "no-spans"])
def test_nothing_to_read_gives_none(name, records, spans, monkeypatch):
    spec, read = cells.load_layer_reader(name)
    if records is None:
        # a program without the ledger (the parent commit): its
        # accountant has no stage records to hand over
        from code_intelligence_tpu.utils import flight_recorder

        monkeypatch.delattr(flight_recorder.XLAAccountant, "stage_records")
    assert read(context(log_of(*spans)), spec, records=records) is None


def test_a_warm_run_reads_no_miss_and_the_same_programs():
    warm = [dict(r, cache="hit") if r["stage"] == "compile" else r
            for r in LEDGER]
    ctx = context(log_of(*SPANS))
    miss_spec, read = cells.load_layer_reader("setup_cache_miss_programs")
    count_spec, _ = cells.load_layer_reader("setup_programs_compiled")
    assert read(ctx, miss_spec, records=warm) == 0.0
    assert read(ctx, count_spec, records=warm) \
        == read(ctx, count_spec, records=LEDGER) == 3.0


def test_an_unknown_stat_is_an_error():
    spec, read = cells.load_layer_reader("setup_jit_trace_s")
    with pytest.raises(ValueError, match="no stat"):
        read(context(log_of(*SPANS)), dict(spec, stat="mean"),
             records=LEDGER)


@pytest.mark.parametrize("name", NAMES)
def test_the_file_and_the_manifest_entry_agree(name):
    spec, _ = cells.load_layer_reader(name)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key]
    unit, stage, stat = NAMES[name]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == (unit, "lower", "program_counter", "start-up",
                                "setup_s")
    assert (spec["reader"], spec["stage"], spec["stat"]) == (
        "compile_ledger", stage, stat)
    assert entry["workloads"] == CELLS


def test_the_five_were_added_at_the_end_in_the_issues_order():
    assert [m["name"] for m in MANIFEST["per_layer"]][-5:] == list(NAMES)
    assert sum(m["moves"] == "setup_s" for m in MANIFEST["per_layer"]) == 5


@pytest.mark.parametrize("workload", CELLS + ["deepseek_v3_bulk_mixed"])
def test_the_nine_cells_report_them_and_deepseek_cannot_yet(workload):
    listed = [m["name"] for m in cells.load_cell(workload)["per_layer"]
              if m["name"] in NAMES]
    assert listed == (list(NAMES) if workload in CELLS else [])


@pytest.mark.parametrize("driver", ["bulk", "train"])
def test_tiny_traced_run_prints_all_five(tmp_path, monkeypatch, driver):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)
    entries = [m for m in MANIFEST["per_layer"] if m["name"] in NAMES]
    limits = None if driver == "bulk" else {"nonfinite_losses": 0}
    bm_util.tiny_benchmark(tmp_path, driver=driver, limits=limits, per_layer=[
        {k: m[k] for k in ("name", "unit", "better", "source", "layer")}
        for m in entries])
    line = run.main(["--workload", "tiny_cell", "--seed", str(2**31 + 49),
                     "--seconds", "0.2", "--trace", "1"], root=tmp_path)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == set(NAMES)
    assert {k: line["metrics"][k]["unit"] for k in got} \
        == {k: v[0] for k, v in NAMES.items()}
    # at least the engine's forwards / the scanned step were brought up
    # before the window, each through all three stages
    assert got["setup_programs_compiled"] >= 1
    assert 0 <= got["setup_cache_miss_programs"] \
        <= got["setup_programs_compiled"]
    for name in ("setup_jit_trace_s", "setup_jit_lower_s",
                 "setup_compile_or_load_s"):
        assert got[name] > 0
    assert line["counters"]["compiles_in_window"] == 0
