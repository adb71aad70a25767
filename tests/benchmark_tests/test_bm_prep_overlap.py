"""``prep_overlapped_token_pct`` (PR 25): a data file for the reader
``span_attr_ratio`` that was there. It reads the share of a window's
tokens that were tokenised after their call's first group was enqueued,
0 from a program whose ``engine.tokenize`` spans carry no such count
(the parent commit: a whole call is prepared before its first dispatch),
and nothing where no document was tokenised; and in one tiny traced run
on the CPU it prints the share that the mix's documents give by hand."""

import numpy as np
import pytest

import bm_util
from benchmark import run
from benchmark.harness import cell as cells
from benchmark.harness import traffic
from test_bm_span_readers import context, log_of

NAME = "prep_overlapped_token_pct"


def tokenize(t0, n_tokens, overlapped=None):
    attrs = {"n_tokens": n_tokens}
    if overlapped is not None:
        attrs["n_tokens_overlapped"] = overlapped
    return ("engine.tokenize", t0, t0 + 0.001, attrs)


@pytest.mark.parametrize("spans,want", [
    ([tokenize(101, 10, 0), tokenize(102, 30, 30), tokenize(103, 60, 60)],
     90.0),
    ([tokenize(101, 10, 0), tokenize(102, 30, 0)], 0.0),
    ([tokenize(101, 10), tokenize(102, 30)], 0.0),
    ([tokenize(101, 25, 25)], 100.0),
    # whole calls, not the capture: a span outside the traced window counts
    ([tokenize(50, 50, 0), tokenize(105, 50, 50)], 50.0),
    ([("engine.group", 101, 102, {"valid_tokens": 5})], None),
    ([], None),
], ids=["known-ratio", "nothing-overlapped", "parent-has-no-count",
        "all-overlapped", "whole-window", "no-tokenize-spans", "no-spans"])
def test_reads_a_known_ratio(spans, want):
    spec, read = cells.load_layer_reader(NAME)
    got = read(context(log_of(*spans)), spec)
    assert got == (None if want is None else pytest.approx(want))


def test_the_file_and_the_manifest_entry_agree():
    spec, _ = cells.load_layer_reader(NAME)
    entry = next(m for m in cells.load_manifest()["per_layer"]
                 if m["name"] == NAME)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key]
    assert spec["reader"] == "span_attr_ratio"
    assert (spec["span"], spec["num"], spec["den"]) == (
        "engine.tokenize", "n_tokens_overlapped", "n_tokens")
    assert entry["workloads"] == ["lstm_bulk_mixed", "qrnn_bulk_mixed"]
    assert entry["better"] == "higher" and "complement" not in spec


def test_tiny_traced_run_prints_the_share_the_documents_give(tmp_path,
                                                             monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)
    entry = next(m for m in cells.load_manifest()["per_layer"]
                 if m["name"] == NAME)
    bm_util.tiny_benchmark(tmp_path, per_layer=[
        {k: entry[k] for k in ("name", "unit", "better", "source", "layer")}])
    seed = 2**31 + 25
    line = run.main(["--workload", "tiny_cell", "--seed", str(seed),
                     "--seconds", "0.2", "--trace", "1"], root=tmp_path)
    assert line["correct"]

    # by hand: every call's documents but the batch and a quarter of
    # smallest raw size (title + body) are tokenised after its first group
    from code_intelligence_tpu.text import SPECIALS

    serve, mix, model = bm_util.TINY_SERVE, bm_util.TINY_MIX, bm_util.TINY_MODEL
    words = traffic.vocab_words(SPECIALS, model["vocab_size"])
    pool = traffic.make_document_calls(mix, words, seed, mix["calls_pool"],
                                       stream=1)
    first = serve["batch_size"] + serve["batch_size"] // 4
    assert mix["docs_per_call"] > first
    calls = line["counters"]["calls"]
    total = late = 0
    for k in range(calls):
        call = pool[k % len(pool)]
        raw = [len(d["title"]) + len(d["body"]) for d in call]
        n_tokens = np.array([len(d["ids"]) for d in call])
        total += n_tokens.sum()
        late += n_tokens[np.argsort(raw, kind="stable")[first:]].sum()
    assert 0 < late < total
    assert line["metrics"][NAME]["value"] == pytest.approx(
        100.0 * late / total, abs=1e-9)
