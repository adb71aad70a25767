"""``pre_rule_passes_run_pct`` (PR 29): a data file for the reader
``span_attr_ratio`` that was there. Of the regex scans the pre-rule
chain could make over a window's titles and bodies, the share it made
because the text held something the pattern needs: read from the two
counts the ``engine.text_rules`` spans carry, nothing from a program
whose spans carry none (the parent commit scans every text with every
pattern), and in one tiny traced run on the CPU it prints the share
that the mix's documents give by hand: a function of the seed alone."""

import pytest

import bm_util
from benchmark import run
from benchmark.harness import cell as cells
from benchmark.harness import traffic
from test_bm_span_readers import context, log_of

NAME = "pre_rule_passes_run_pct"
BULK_CELLS = ["lstm_bulk_mixed", "qrnn_bulk_mixed", "granite_bulk_mixed"]


def text_rules(t0, passes=None, run_=None):
    attrs = {"n_chars": 100}
    if passes is not None:
        attrs.update(rule_passes=passes, rule_passes_run=run_)
    return ("engine.text_rules", t0, t0 + 0.001, attrs)


@pytest.mark.parametrize("spans,want", [
    ([text_rules(101, 34, 4), text_rules(102, 34, 13), text_rules(103, 34, 0)],
     100.0 * 17 / 102),
    ([text_rules(101, 34, 0), text_rules(102, 34, 0)], 0.0),
    ([text_rules(101, 34, 34)], 100.0),
    # the parent commit: spans without the counts
    ([text_rules(101), text_rules(102)], None),
    # whole calls, not the capture: a span outside the traced window counts
    ([text_rules(50, 34, 34), text_rules(105, 34, 0)], 50.0),
    # the second application's counts (on engine.tokenize) are not read
    ([("engine.tokenize", 101, 102,
       {"n_tokens": 9, "rule_passes": 17, "rule_passes_run": 17})], None),
    ([], None),
], ids=["known-ratio", "nothing-run", "everything-run",
        "parent-has-no-count", "whole-window", "tokenize-spans-only",
        "no-spans"])
def test_reads_a_known_ratio(spans, want):
    spec, read = cells.load_layer_reader(NAME)
    got = read(context(log_of(*spans)), spec)
    assert got == (None if want is None else pytest.approx(want))


def test_the_file_and_the_manifest_entry_agree():
    spec, _ = cells.load_layer_reader(NAME)
    manifest = cells.load_manifest()
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert manifest["per_layer"][-1] is entry  # added at the end
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("%", "lower", "program_counter", "tokenise",
                                "docs_per_s")
    assert spec["reader"] == "span_attr_ratio" and "complement" not in spec
    assert (spec["span"], spec["num"], spec["den"]) == (
        "engine.text_rules", "rule_passes_run", "rule_passes")
    assert entry["workloads"] == BULK_CELLS


@pytest.mark.parametrize("workload", BULK_CELLS)
def test_every_bulk_cell_reports_it(workload):
    cell = cells.load_cell(workload)
    assert NAME in [m["name"] for m in cell["per_layer"]]
    assert NAME not in [m["name"] for m in
                        cells.load_cell("lstm_train_lm")["per_layer"]]


def test_tiny_traced_run_prints_the_share_the_documents_give(tmp_path,
                                                             monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)
    entry = next(m for m in cells.load_manifest()["per_layer"]
                 if m["name"] == NAME)
    bm_util.tiny_benchmark(tmp_path, per_layer=[
        {k: entry[k] for k in ("name", "unit", "better", "source", "layer")}])
    seed = 2**31 + 29
    line = run.main(["--workload", "tiny_cell", "--seed", str(seed),
                     "--seconds", "0.2", "--trace", "1"], root=tmp_path)
    assert line["correct"]

    # by hand: the chain over every title and body the window served,
    # counted by the rules themselves outside any engine
    from code_intelligence_tpu.text import SPECIALS, pre_process
    from code_intelligence_tpu.text import rules

    mix, model = bm_util.TINY_MIX, bm_util.TINY_MODEL
    words = traffic.vocab_words(SPECIALS, model["vocab_size"])
    pool = traffic.make_document_calls(mix, words, seed, mix["calls_pool"],
                                       stream=1)
    calls = line["counters"]["calls"]
    with rules.counting_passes() as counts:
        for k in range(calls):
            for d in pool[k % len(pool)]:
                pre_process(d["title"])
                pre_process(d["body"])
    could, made = counts
    assert could == 2 * 17 * calls * mix["docs_per_call"]
    assert 0 < made < could / 2
    assert line["metrics"][NAME]["value"] == pytest.approx(
        100.0 * made / could, abs=1e-9)
