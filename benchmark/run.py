#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: require the chip (no TPU, or fewer chips than the cell asks
for = non-zero exit and no result line, never a CPU number), switch the
persistent compile cache on at its fixed path inside the checkout, hand
the cell to its driver (weights and inputs from ``--seed``, only the
cell's shapes warmed, the window, the check of the outputs after it),
and print ONE JSON object as the last line of stdout. ``--trace 0``
gives the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics and the breakdown.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()  # before the heavy imports: set-up counts them

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import cell as cells  # noqa: E402
from benchmark.harness.context import RunContext  # noqa: E402


def require_device(chips: int) -> dict:
    """The chip, or no run: ``utils/devices.py::require_tpu`` exits
    non-zero on any other platform; fewer chips than the cell needs is
    the same refusal. Also switches the persistent compile cache on."""
    import jax

    from code_intelligence_tpu.utils import devices

    # the runtime's own start (libtpu bringing the chip up in the first
    # call that needs a device) is timed apart and kept out of setup_s:
    # it read 6.5 to 14.2 s, and its median moved from 7.6 to 9.7 s
    # between two sets of the same code run one after the other, more
    # than the bound on all of set-up allows (PERF.md, section 2)
    t0 = time.time()
    jax.devices()
    runtime_start_s = time.time() - t0
    dev = devices.require_tpu("benchmark/run.py")
    if dev["count"] < chips:
        raise SystemExit(
            f"benchmark/run.py: the cell needs {chips} chips, JAX reports "
            f"{dev}")
    cache = devices.enable_compile_cache()
    # every program, however quick to compile: a second run in the same
    # checkout must find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    dev["compile_cache"] = cache
    dev["runtime_start_s"] = runtime_start_s
    return dev


def layer_metrics(cell: dict, result: dict, ctx: RunContext, dev: dict):
    """The per-layer metrics of a traced run, each from its own reader;
    ``device`` additions and the breakdown come from the same trace."""
    from benchmark.harness import readers

    rctx = readers.ReaderContext.build(cell, result, ctx, dev)
    metrics = {}
    for m in cell["per_layer"]:
        spec, read = cells.load_layer_reader(m["name"], cell["bench_dir"])
        value = read(rctx, spec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics, rctx


def main(argv=None, root: Path = ROOT, bench_dir: Path = None,
         overrides: dict = None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    bench_dir = Path(bench_dir) if bench_dir else Path(root) / "benchmark"
    cell = cells.load_cell(args.workload, Path(root), bench_dir)
    dev = require_device(cell["chips"])
    ctx = RunContext(cell, args.seed, args.seconds, bool(args.trace),
                     overrides=overrides, t_process=T_PROCESS,
                     runtime_start_s=dev.get("runtime_start_s", 0.0))
    ctx.log(f"cell {cell['name']} seed {args.seed} on {dev}")
    driver = cells.load_driver(cell["cell"]["driver"], bench_dir)
    try:
        result = driver.run(ctx)
        device = {"platform": dev["platform"], "kind": dev["kind"],
                  "count": dev["count"],
                  "memory_peak_bytes": int(result["memory_peak_bytes"])}
        line = {"correct": bool(result["correct"]),
                "attempted": int(result["attempted"]),
                "failed": int(result["failed"])}
        if args.trace:
            metrics, rctx = layer_metrics(cell, result, ctx, dev)
            device["busy_s"] = rctx.reduced["busy_s"]
            device["window_s"] = rctx.reduced["window_s"]
            line["breakdown"] = rctx.breakdown()
        else:
            values = dict(result["end_to_end"], setup_s=ctx.setup_s)
            metrics = {m["name"]: {"value": float(values[m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell["end_to_end"]}
        line.update(metrics=metrics, device=device,
                    compared=result["compared"],
                    window_s=result["window_s"],
                    runtime_start_s=ctx.runtime_start_s,
                    counters=result.get("counters", {}))
    finally:
        ctx.profiler.cleanup()
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
