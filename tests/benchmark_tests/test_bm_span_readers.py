"""The per-layer metrics that read the program's own spans (PR 24): the
one new reader, ``span_attr_ratio``, on hand-made span logs; each of the
five metric files through its reader on a hand-made traced window; and
one tiny traced run on the CPU in which all five are printed and the
padded-lane share equals what the mix's length grid gives by hand."""

import pytest

import bm_util
from benchmark import run
from benchmark.harness import cell as cells
from benchmark.harness import readers, traffic
from benchmark.harness.spans import HostSpan, SpanLog

NEW = ["text_rules_share_pct", "text_rules_us_per_doc",
       "group_dispatch_share_pct", "device_wait_share_pct",
       "padded_lane_pct"]
RATIO = {"span": "engine.group", "num": "valid_tokens", "den": "lane_steps"}


def log_of(*spans):
    log = SpanLog()
    log.spans = [HostSpan(*s) for s in spans]
    return log


def group(t0, t1, valid, lanes):
    return ("engine.group", t0, t1,
            {"valid_tokens": valid, "lane_steps": lanes})


def context(spans, window=(100.0, 110.0)):
    ctx = readers.ReaderContext()
    ctx.spans = spans
    ctx.trace_window_unix = window
    ctx.traced_spans = spans.within(*window)
    return ctx


@pytest.mark.parametrize("spec,spans,want", [
    (RATIO, [group(0, 1, 30, 100), group(1, 2, 20, 100)], 25.0),
    (dict(RATIO, complement=True),
     [group(0, 1, 30, 100), group(1, 2, 20, 100)], 75.0),
    (dict(RATIO, complement=True), [group(0, 1, 64, 64)], 0.0),
    (RATIO, [("engine.tokenize", 0, 1, {"n_tokens": 5})], None),
    (RATIO, [], None),
    (RATIO, [group(0, 1, 0, 0)], None),
    (RATIO, [("engine.group", 0, 1, {})], None),
], ids=["ratio", "complement", "no-padding", "other-spans-only", "no-spans",
        "zero-denominator", "no-attributes"])
def test_span_attr_ratio(spec, spans, want):
    _, read = cells.load_layer_reader("padded_lane_pct")
    got = read(context(log_of(*spans)), spec)
    assert got == (None if want is None else pytest.approx(want))


def test_span_attr_ratio_reads_the_whole_window_not_the_capture():
    """Every call holds the same groups, so the share over whole calls is
    one number; the capture cuts calls in two and would not be."""
    spec, read = cells.load_layer_reader("padded_lane_pct")
    ctx = context(log_of(group(90, 91, 10, 100), group(105, 106, 50, 100)))
    assert [s.start_unix for s in ctx.traced_spans.spans] == [105]
    assert read(ctx, spec) == pytest.approx(70.0)


@pytest.mark.parametrize("name,want", [
    ("text_rules_share_pct", 100.0 * 3 * 0.2 / 10),
    ("text_rules_us_per_doc", 0.2e6),
    ("group_dispatch_share_pct", 100.0 * 2 * 0.05 / 10),
    ("device_wait_share_pct", 100.0 * 4.0 / 10),
    ("padded_lane_pct", 100.0 * (1 - 150 / 400)),
])
def test_each_new_metric_on_a_known_window(name, want):
    spans = log_of(
        *[("engine.text_rules", 100 + i, 100.2 + i, {"n_chars": 9})
          for i in range(3)],
        ("engine.tokenize", 103.5, 104.0, {"n_tokens": 7}),
        group(104.0, 104.05, 50, 200), group(104.05, 104.1, 100, 200),
        ("engine.finalize", 104.1, 108.1, {"groups": 2}))
    spec, read = cells.load_layer_reader(name)
    assert read(context(spans), spec) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_finds_nothing_on_a_program_without_the_spans(name):
    """The parent commit records none of them: the line leaves the metric
    out, nothing raises."""
    spans = log_of(("engine.tokenize", 101, 102, {"n_tokens": 7}),
                   ("engine.group_embed", 102, 109, {}))
    spec, read = cells.load_layer_reader(name)
    assert read(context(spans), spec) is None


def test_tiny_traced_run_prints_all_five(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)
    manifest = cells.load_manifest()
    per_layer = [{k: m[k] for k in ("name", "unit", "better", "source",
                                    "layer")}
                 for m in manifest["per_layer"] if m["name"] in NEW]
    assert len(per_layer) == len(NEW)
    bm_util.tiny_benchmark(tmp_path, per_layer=per_layer)
    line = run.main(["--workload", "tiny_cell", "--seed", str(2**31 + 24),
                     "--seconds", "0.2", "--trace", "1"], root=tmp_path)
    assert set(NEW) <= set(line["metrics"])
    assert line["correct"]

    # the same count by hand, from the mix's grid and the serve block
    serve, mix = bm_util.TINY_SERVE, bm_util.TINY_MIX
    grid = sorted(traffic.length_grid(
        mix["length"], mix["docs_per_call"]).tolist())
    b, lanes = serve["batch_size"], 0
    for i in range(0, len(grid), b):
        longest = max(grid[i:i + b])
        bucket = next((x for x in serve["buckets"] if longest <= x),
                      serve["buckets"][-1])
        lanes += b * bucket * max(1, -(-longest // bucket))
    assert line["metrics"]["padded_lane_pct"]["value"] == pytest.approx(
        100.0 * (1 - sum(grid) / lanes), abs=1e-9)
    shares = sum(line["metrics"][n]["value"] for n in NEW if "share" in n)
    assert 0 < shares <= 100.0
