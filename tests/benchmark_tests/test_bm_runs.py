"""Whole runs of the benchmark, tiny, on the CPU, through a device gate
that lives in the tests (``bm_util.cpu_gate``): a configuration, a mix,
a cell and a layer metric ADDED as files are found and run; the chip
gate itself refuses; the control (the next precision down) and a broken
timed path both come out as not correct."""

import json

import numpy as np
import pytest

import bm_util
from benchmark import run

NEW_METRIC = {"name": "docs_counted.tiny", "layer": "bulk batching",
              "unit": "count", "better": "higher",
              "source": "program_counter", "reader": "docs_counted",
              "what": "a reader dropped in by the test"}
NEW_READER = '''
def read(ctx, spec):
    return float(ctx.counters["docs"])
'''


def main(tmp, *extra, **kw):
    return run.main(["--workload", "tiny_cell", "--seed", str(2**31 + 11),
                     "--seconds", "0.2", *extra], root=tmp, **kw)


@pytest.fixture
def gate(monkeypatch):
    monkeypatch.setattr(run, "require_device", bm_util.cpu_gate)


def test_run_refuses_without_a_chip():
    """The benchmark's own gate: no TPU = SystemExit, no result."""
    with pytest.raises(SystemExit) as e:
        run.require_device(1)
    assert e.value.code not in (0, None)


@pytest.mark.parametrize("qrnn", [False, True], ids=["lstm", "qrnn"])
def test_added_files_are_found_and_run(tmp_path, gate, capsys, qrnn):
    per_layer = [
        {"name": "docs_counted.tiny", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "bulk batching"},
        {"name": "tokenize_share_pct", "unit": "%", "better": "lower",
         "source": "program_span", "layer": "tokenise"},
        {"name": "device_idle_pct.bulk", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device"}]
    bench = bm_util.tiny_benchmark(tmp_path, qrnn=qrnn, per_layer=per_layer)
    bm_util.write(bench / "layer_metrics" / "docs_counted.tiny.json",
                  NEW_METRIC)
    (bench / "layer_metrics" / "docs_counted.py").write_text(NEW_READER)

    line = main(tmp_path, "--trace", "0")
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"docs_per_s", "setup_s"}
    assert line["metrics"]["docs_per_s"]["value"] > 0
    assert line["attempted"] == line["counters"]["docs"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    # the last line of stdout is the one JSON object
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert set(json.loads(last)) >= {"correct", "attempted", "failed",
                                     "metrics", "device"}

    traced = main(tmp_path, "--trace", "1")
    # the dropped-in reader ran; a reader with nothing to read (no device
    # plane in a CPU trace) is left out, not reported as zero
    assert traced["metrics"]["docs_counted.tiny"]["value"] == \
        traced["counters"]["docs"]
    assert "tokenize_share_pct" in traced["metrics"]
    assert "device_idle_pct.bulk" not in traced["metrics"]
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_work_a_call(tmp_path, gate):
    bm_util.tiny_benchmark(tmp_path)
    a, b = main(tmp_path), main(tmp_path)
    assert a["correct"] and b["correct"]
    assert a["counters"]["tokens"] / a["counters"]["calls"] == \
        b["counters"]["tokens"] / b["counters"]["calls"]


def test_control_int8_is_not_correct(tmp_path, gate):
    """The program's own int8 path in the program's place, at a size a
    test can hold: float32 runs sit at 1e-7, int8 weights near 1e-2, the
    limit between them."""
    bm_util.tiny_benchmark(tmp_path)
    sound = main(tmp_path)
    control = main(tmp_path, overrides={"precision": "int8"})
    worst = {c["name"]: c["value"] for c in control["compared"]}
    assert sound["correct"] and not control["correct"]
    assert worst["rel_rms_mean"] > 1e-3


def test_altered_answer_is_not_correct(tmp_path, gate, monkeypatch):
    """The timed path broken underneath: one answer altered where it is
    produced (the engine's finalize), everything else as in a run."""
    from code_intelligence_tpu.inference import InferenceEngine

    bm_util.tiny_benchmark(tmp_path)
    real = InferenceEngine._finalize

    def broken(self, pool_state):
        rows = real(self, pool_state)
        rows[:, : rows.shape[1] // 3] *= 1.01  # the mean third, 1 % off
        return rows

    monkeypatch.setattr(InferenceEngine, "_finalize", broken)
    line = main(tmp_path)
    assert not line["correct"]
    bad = {c["name"] for c in line["compared"] if not c["inside"]}
    assert bad == {"rel_rms_mean"}
