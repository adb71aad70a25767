"""What each program of a bulk call costs the device, and what of a
group is the host's own (PR 34): the reader ``program_join`` (a capture's
``jit_fwd_b<rows>_l<bucket>`` modules laid against the program's
``engine.program`` spans) and the reader ``span_self_median`` on
hand-made span logs and module tables; the six metric files against
their manifest entries, found by name, their lists checked by
membership; and one tiny traced run on the CPU that prints the three
metrics read from spans alone and leaves out the three that need a
device plane."""

import json
from pathlib import Path

import pytest

import bm_util
from benchmark import run
from benchmark.harness import cell as cells
from benchmark.harness import readers, traffic
from benchmark.harness.spans import HostSpan, SpanLog

ROOT = Path(__file__).resolve().parents[2]
JOINED = ["padded_device_time_pct", "narrow_program_time_pct",
          "narrow_lane_cost_ratio"]
FROM_SPANS = ["padded_lane_run_pct", "program_enqueue_share_pct",
              "group_self_ms"]
NEW = JOINED + FROM_SPANS
CELLS = ["lstm_bulk_mixed", "qrnn_bulk_mixed", "granite_bulk_mixed",
         "trinity_bulk_long_tail"]


def program(t0, t1, rows, valid, batch=4, bucket=16):
    return ("engine.program", t0, t1,
            {"rows": rows, "batch": batch, "bucket": bucket,
             "valid_tokens": valid, "lane_steps": rows * bucket})


def context(spans, modules=None, window=(100.0, 110.0)):
    log = SpanLog()
    log.spans = [HostSpan(*s) for s in spans]
    ctx = readers.ReaderContext()
    ctx.spans = log
    ctx.trace_window_unix = window
    ctx.traced_spans = log.within(*window)
    if modules is not None:
        ctx.reduced = dict(ctx.reduced, modules=modules, devices=1)
    return ctx


# two groups inside the capture, 4 -> 2 -> 1 rows and 4 -> 1 rows, and a
# group of an earlier call the capture did not see
PROGRAMS = [
    program(90.0, 90.1, 4, 64),
    program(101.0, 101.1, 4, 48), program(101.2, 101.3, 2, 20),
    program(101.4, 101.5, 1, 8),
    program(103.0, 103.1, 4, 32), program(103.2, 103.3, 1, 16),
]
MODULES = {"jit_fwd_b4_l16": [0.4, 0.2], "jit_fwd_b2_l16": [0.1],
           "jit_fwd_b1_l16": [0.1, 0.06], "jit_narrow": [0.001] * 3}
TOTAL = 0.4 + 0.2 + 0.1 + 0.1 + 0.06


def read(name, ctx):
    spec, reader = cells.load_layer_reader(name)
    return reader(ctx, spec)


@pytest.mark.parametrize("name,want", [
    # the k-th execution of a shape is the k-th span of that shape
    ("padded_device_time_pct",
     100.0 * (0.4 * (1 - 48 / 64) + 0.2 * (1 - 32 / 64) + 0.1 * (1 - 20 / 32)
              + 0.1 * (1 - 8 / 16) + 0.06 * (1 - 16 / 16)) / TOTAL),
    ("narrow_program_time_pct", 100.0 * (0.1 + 0.1 + 0.06) / TOTAL),
    # the smallest rows the window ran are 1: 0.16 s for 32 lane-steps
    # against 0.6 s for 128 at the whole batch
    ("narrow_lane_cost_ratio", (0.16 / 32) / (0.6 / 128)),
])
def test_program_join_on_a_known_capture(name, want):
    assert read(name, context(PROGRAMS, MODULES)) == pytest.approx(want)


@pytest.mark.parametrize("modules", [
    dict(MODULES, jit_fwd_b4_l16=[0.4]),              # an execution short
    dict(MODULES, jit_fwd_b1_l16=[0.1, 0.06, 0.05]),  # one too many
    {k: v for k, v in MODULES.items() if k != "jit_fwd_b2_l16"},
    dict(MODULES, jit_fwd_b8_l16=[0.3]),              # a shape no span has
    {"jit_fwd": [0.4, 0.2, 0.1, 0.1, 0.06], "jit_narrow": [0.001]},
    {},
    None,
], ids=["fewer-executions", "more-executions", "a-shape-without-a-module",
        "a-module-without-a-span", "modules-not-named-by-shape",
        "no-modules", "no-device-plane"])
@pytest.mark.parametrize("name", JOINED)
def test_program_join_never_guesses(name, modules):
    assert read(name, context(PROGRAMS, modules)) is None


def test_program_join_without_a_narrowed_program():
    ctx = context([program(101.0, 101.1, 4, 48), program(101.2, 101.3, 4, 16)],
                  {"jit_fwd_b4_l16": [0.3, 0.1]})
    assert read("narrow_program_time_pct", ctx) == 0.0
    assert read("narrow_lane_cost_ratio", ctx) is None
    assert read("padded_device_time_pct", ctx) == pytest.approx(
        100.0 * (0.3 * 0.25 + 0.1 * 0.75) / 0.4)


def test_program_join_takes_spans_in_start_order_and_two_buckets_apart():
    spans = [program(102.0, 102.1, 4, 60, bucket=32),
             program(101.5, 101.6, 4, 16),  # recorded later, started first
             program(101.0, 101.1, 4, 64)]
    ctx = context(spans, {"jit_fwd_b4_l16": [0.2, 0.1],
                          "jit_fwd_b4_l32": [0.5]})
    assert read("padded_device_time_pct", ctx) == pytest.approx(
        100.0 * (0.2 * 0.0 + 0.1 * 0.75 + 0.5 * (1 - 60 / 128)) / 0.8)


def test_program_join_refuses_an_unknown_statistic():
    _, reader = cells.load_layer_reader("padded_device_time_pct")
    with pytest.raises(ValueError, match="no statistic"):
        reader(context(PROGRAMS, MODULES), {"stat": "mean"})


GROUPS = [
    ("engine.group", 104.0, 104.5, {}), program(104.1, 104.2, 4, 9),
    program(104.3, 104.45, 2, 9),                       # self 0.25
    ("engine.group", 105.0, 105.2, {}), program(105.05, 105.15, 4, 9),
    ("engine.group", 106.0, 106.6, {}), program(106.0, 106.1, 4, 9),
]


def test_span_self_median_on_a_known_window():
    # 0.25, 0.1 and 0.5 s of self time: the median, in ms
    assert read("group_self_ms", context(GROUPS)) == pytest.approx(250.0)
    # a fourth group whose one program covers it whole
    more = GROUPS + [("engine.group", 107.0, 107.3, {}),
                     program(107.0, 107.3, 4, 9)]
    assert read("group_self_ms", context(more)) == pytest.approx(175.0)


def test_the_two_data_only_metrics_on_a_known_window():
    ctx = context(PROGRAMS + [("engine.group", 101.0, 101.6, {})])
    # every program of the window, the earlier call's too: counts
    lanes, valid = (4 + 4 + 2 + 1 + 4 + 1) * 16, 64 + 48 + 20 + 8 + 32 + 16
    assert read("padded_lane_run_pct", ctx) == pytest.approx(
        100.0 * (1 - valid / lanes))
    # five programs of 0.1 s inside a capture of 10 s
    assert read("program_enqueue_share_pct", ctx) == pytest.approx(5.0)


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_finds_nothing_on_a_program_without_the_spans(name):
    """The parent commit records no ``engine.program`` and names every
    forward ``jit_fwd``: the line leaves the metric out, nothing raises."""
    spans = [("engine.tokenize", 101, 102, {"n_tokens": 7}),
             ("engine.group", 102, 103, {"valid_tokens": 7, "lane_steps": 64,
                                         "lane_steps_run": 32}),
             ("engine.finalize", 103, 104, {"groups": 1})]
    assert read(name, context(spans, {"jit_fwd": [0.5], "jit_narrow": [0.1]})) \
        is None


@pytest.mark.parametrize("name", NEW)
def test_each_file_agrees_with_its_manifest_entry(name):
    manifest = cells.load_manifest()
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    spec = json.loads(
        (ROOT / "benchmark/layer_metrics" / f"{name}.json").read_text())
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["moves"] == "docs_per_s" and entry["unit"] in ("%", "x", "ms")
    for cell in CELLS:
        assert cell in entry["workloads"]
        assert name in {m["name"] for m in cells.load_cell(cell)["per_layer"]}
    # a passing test pins that cell's list of 20 (PERF.md, finding 19)
    assert "deepseek_v3_bulk_mixed" not in entry["workloads"]
    assert (ROOT / "benchmark/layer_metrics"
            / f"{spec['reader']}.py").is_file()
    assert ("device_trace" == entry["source"]) == (name in JOINED)


def halving_grid_lanes(grid, b, buckets):
    """``(valid tokens, lane-steps run, chunk programs)`` of a call's
    sorted slabs of ``b``, by hand: the first chunk program of a slab at
    ``b`` rows, each later one at the smallest of ``b`` halved up to three
    times that holds the documents still going."""
    sizes = sorted({-(-b // d) for d in (1, 2, 4, 8)})
    lanes = programs = 0
    for i in range(0, len(grid), b):
        slab = grid[i:i + b]
        bucket = next((x for x in buckets if slab[-1] <= x), buckets[-1])
        for ci in range(max(1, -(-slab[-1] // bucket))):
            alive = sum(n > ci * bucket for n in slab)
            rows = min(s for s in sizes if s >= alive) if ci else b
            lanes += rows * bucket
            programs += 1
    return sum(grid), lanes, programs


def test_tiny_traced_run_prints_the_three_span_metrics(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)
    manifest = cells.load_manifest()
    want = FROM_SPANS + JOINED + ["group_dispatch_share_pct"]
    per_layer = [{k: m[k] for k in ("name", "unit", "better", "source",
                                    "layer")}
                 for m in manifest["per_layer"] if m["name"] in want]
    assert {m["name"] for m in per_layer} == set(want)
    bm_util.tiny_benchmark(tmp_path, per_layer=per_layer)
    line = run.main(["--workload", "tiny_cell", "--seed", str(2**31 + 34),
                     "--seconds", "0.2", "--trace", "1"], root=tmp_path)
    assert line["correct"]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # no device plane on the CPU: nothing to join, nothing guessed
    assert set(FROM_SPANS) <= set(metrics) and not set(JOINED) & set(metrics)

    serve, mix = bm_util.TINY_SERVE, bm_util.TINY_MIX
    grid = sorted(traffic.length_grid(
        mix["length"], mix["docs_per_call"]).tolist())
    valid, lanes, programs = halving_grid_lanes(
        grid, serve["batch_size"], serve["buckets"])
    assert programs > -(-len(grid) // serve["batch_size"])  # some stream
    assert metrics["padded_lane_run_pct"] == pytest.approx(
        100.0 * (1 - valid / lanes), abs=1e-9)
    assert 0 < metrics["program_enqueue_share_pct"] \
        <= metrics["group_dispatch_share_pct"]
    assert metrics["group_self_ms"] > 0
