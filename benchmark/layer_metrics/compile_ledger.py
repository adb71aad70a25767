"""What set-up was made of, from the program's own compile ledger
(``utils/flight_recorder.py::XLAAccountant``: one record a stage of a
compile, ``trace`` | ``lower`` | ``compile``, named and on the wall
clock). The records that ENDED before the window opened, which is taken
as the earliest start among the run's spans (the first ``bench.doc`` /
``train.dispatch`` begins microseconds after ``ctx.window_opens()``);
the check's compiles, after the window, are left out with the window's
own. The spec picks the ``stage`` and a ``stat``:

- ``union_s``: the length of the union of the records' intervals, so a
  function traced inside another's tracing counts once;
- ``count``: how many records;
- ``miss_count``: of them, those whose ``cache`` is not ``hit`` (a
  ``compile`` record says what the persistent cache held: ``hit``,
  ``miss``, or ``off`` where it had no say).

A program without the ledger (the parent commit), a ledger without
records, or a run without spans gives nothing to read."""

from benchmark.harness import stats


def stage_records():
    """Every stage record the process's accountant holds, or None where
    the program has no such ledger."""
    try:
        from code_intelligence_tpu.utils import flight_recorder

        return flight_recorder.get_accountant().stage_records()
    except (ImportError, AttributeError):
        return None


def read(ctx, spec, records=None):
    records = stage_records() if records is None else records
    spans = ctx.spans.spans
    if not records or not spans:
        return None
    opens = min(s.start_unix for s in spans)
    mine = [r for r in records
            if r["stage"] == spec["stage"] and r["end_unix"] <= opens]
    stat = spec["stat"]
    if stat == "union_s":
        return stats.union_seconds(
            (r["start_unix"], r["end_unix"]) for r in mine)
    if stat == "count":
        return float(len(mine))
    if stat == "miss_count":
        return float(sum(r.get("cache") != "hit" for r in mine))
    raise ValueError(f"compile_ledger: no stat {stat!r}")
