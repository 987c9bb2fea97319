#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's compared numbers over
many seeds, and the bfloat16 control's, in one process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11,12,... --control-seeds 21,22,23 --seconds 5

Each seed is one whole run of the cell (set-up, window, check) at its own
size, as ``run.py`` makes it; programs compiled for the first seed are
reused by the later ones, so only the first pays for tracing and
compiling.  One JSON line per seed: ``{"seed", "control", "correct",
"numbers", "attempted", "failed"}``, then the largest reading of each
number over the program's seeds and the smallest over the control's.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run
from harness import device, reference, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench = spec.Benchmark.load(run.ROOT / "BENCHMARK.json", run.HERE)
    cell = bench.cell(args.workload)
    run.import_program()
    run.configure_jax()
    seeds = [(int(s), False) for s in args.seeds.split(",") if s]
    seeds += [(int(s), True) for s in args.control_seeds.split(",") if s]
    readings = {False: [], True: []}
    try:
        for seed, control in seeds:
            result, _ = run.run_cell(bench, cell, seed, args.seconds, False,
                                     time.perf_counter(), control=control)
            nums = {k: v["value"] for k, v in result["check"].items()}
            readings[control].append(nums)
            print(json.dumps({"seed": seed, "control": control,
                              "correct": result["correct"], "numbers": nums,
                              "attempted": result["attempted"],
                              "failed": result["failed"]}), flush=True)
    except device.NoAccelerator as exc:
        print(f"calibrate.py: {exc}", file=sys.stderr)
        return 3
    summary = {
        "program_max": {k: max(r[k] for r in readings[False])
                        for k in reference.NUMBERS} if readings[False] else {},
        "control_min": {k: min(r[k] for r in readings[True])
                        for k in reference.NUMBERS} if readings[True] else {},
    }
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
