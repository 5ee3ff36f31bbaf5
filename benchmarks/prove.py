"""Readings for a cell's limits, many seeds in one process:

    python3 -m benchmarks.prove --workload <cell> --seeds <a,b,c,...> \\
        --seconds <s> [--control-seeds <n>]

The program is built once (its weights do not follow the seed); for each
seed the job is made anew, driven for a short window through the same
driver as a run of the benchmark, and compared with the reference: the
lower readings. For the first `--control-seeds` seeds the control is read
as well: the reference in the nearest lower precision in the program's
place, over the same inputs: the upper readings. One JSON line a seed.
The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    from benchmarks import run

    bench = run.load_benchmark()
    cell = run.find_cell(bench, args.workload, seeds[0], args.rehearse_cpu)
    dev = run.open_device(cell)
    if dev is None:
        return 2
    driver = importlib.import_module(
        f"benchmarks.drivers.{cell.traffic['driver']}"
    )
    transformer, weights = driver.build_entry(cell)
    for k, seed in enumerate(seeds):
        cell.seed = seed
        state = driver.load_job(cell, transformer, weights)
        window = driver.window(state, args.seconds)
        line = {
            "workload": cell.name,
            "seed": seed,
            "platform": dev.platform,
            "jobs": len(window.jobs),
            "program": driver.check(cell, state, window, look=True),
        }
        if k < args.control_seeds:
            line["control"] = driver.control_numbers(state)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
