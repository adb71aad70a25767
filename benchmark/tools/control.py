#!/usr/bin/env python3
"""Readings that a cell's limits are set from: sound runs of the program
and runs of its control, on several seeds, in one process on the chip.

    python benchmark/tools/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds 2] [--control precision=int8]

The control is the program with the nearest precision below the one the
configuration states switched on (``precision=int8`` for the bfloat16
serve cells: the engine's own int8 path). The benchmark's own runs never
run it; this tool and the test beside the benchmark's tests do. Prints
one line of numbers per run and, at the end, the largest sound and the
smallest control reading of each number compared.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> None:
    from benchmark import run

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--control", default="precision=int8")
    args = p.parse_args(argv)
    key, value = args.control.split("=", 1)
    readings = {"sound": {}, "control": {}}
    for seed in [int(s) for s in args.seeds.split(",")]:
        for side, overrides in (("sound", None), ("control", {key: value})):
            line = run.main(["--workload", args.workload, "--seed", str(seed),
                             "--seconds", str(args.seconds), "--trace", "0"],
                            overrides=overrides)
            nums = {c["name"]: c["value"] for c in line["compared"]}
            print("READING", json.dumps({"side": side, "seed": seed,
                                         "correct": line["correct"],
                                         "numbers": nums}), flush=True)
            for name, v in nums.items():
                readings[side].setdefault(name, []).append(v)
    summary = {}
    for name in sorted(set(readings["sound"]) | set(readings["control"])):
        s, c = readings["sound"].get(name), readings["control"].get(name)
        summary[name] = {"sound_max": max(s) if s else None,
                         "control_min": min(c) if c else None}
    print("SUMMARY", json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
