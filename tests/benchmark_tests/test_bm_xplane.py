"""The trace reducer on a small trace recorded on the chip (TPU v5 lite,
PR 23): three executions of one tiny jitted program, each inside a
``bench.call`` host annotation, 2 ms apart."""

from pathlib import Path

import pytest

from benchmark.harness import readers, xplane

TRACE = Path(__file__).parent / "data" / "tiny.xplane.pb"


@pytest.fixture(scope="module")
def events():
    return xplane.read_events(str(TRACE), host_lines=True)


def test_recorded_trace_reduces_to_its_three_executions(events):
    r = xplane.reduce_events(events)
    assert r["devices"] == 1
    assert list(r["modules"]) == ["jit_tiny_step"]
    durs = r["modules"]["jit_tiny_step"]
    assert len(durs) == 3 and all(0 < d < 1e-3 for d in durs)
    # busy is the union of the op intervals: inside the modules' time
    assert 0 < r["busy_s"] <= sum(durs) * 1.001
    assert r["busy_s"] < r["window_s"]          # 2 ms sleeps between calls
    assert len(r["gaps"]) >= 2
    assert r["device_ops"] and all(
        len(name) <= 96 and secs > 0 for name, secs in r["device_ops"])


def test_host_annotations_anchor_the_clocks(events):
    calls = sorted(e.start_s for e in events if e.name == "bench.call")
    assert len(calls) == 3
    # anchors remembered 100 s "earlier" on a wall clock: the offset is
    # recovered as the median difference, annotation by annotation
    anchors = [("bench.call", t - 100.0) for t in calls]
    assert readers._clock_offset(events, anchors) == pytest.approx(100.0)
    assert readers._clock_offset(events, []) is None


def test_device_only_read_leaves_host_lines_out():
    dev = xplane.read_events(str(TRACE))
    assert dev and all(e.device.startswith(xplane.DEVICE_PLANE_PREFIX)
                       for e in dev)
    assert {e.line for e in dev} == {xplane.MODULE_LINE, xplane.OP_LINE}
