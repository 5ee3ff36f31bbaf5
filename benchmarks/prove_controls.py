"""Readings of a family's further controls, for a cell's limits:

    python3 -m benchmarks.prove_controls --workload <cell> --seeds <a,b,...>

`benchmarks.prove` reads one control, the reference in the nearest
precision under the one the configuration states. A family whose
reference names more (`SECOND_CONTROL`: a precision of `outputs` that
lowers another part of the computation) has each read here the same way:
the reference at that precision in the program's place, over the sampled
inputs of the seed's job, against the reference at the stated precision.
The program is not built. One JSON line a seed.
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
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    from benchmarks import compare, run

    bench = run.load_benchmark()
    cell = run.find_cell(bench, args.workload, seeds[0], args.rehearse_cpu)
    dev = run.open_device(cell)
    if dev is None:
        return 2
    driver = importlib.import_module(
        f"benchmarks.drivers.{cell.traffic['driver']}"
    )
    reference = importlib.import_module(
        f"benchmarks.reference.{cell.config['family']}"
    )
    lower = getattr(reference, "SECOND_CONTROL", None)
    if lower is None:
        print(f"{reference.__name__} names no SECOND_CONTROL", file=sys.stderr)
        return 1
    _, weights = driver.weights_file(cell)
    for seed in seeds:
        cell.seed = seed
        state = driver.load_job(cell, None, weights)
        ref = driver.reference_answers(state)
        low = driver.reference_answers(state, lower)
        line = {
            "workload": cell.name,
            "seed": seed,
            "platform": dev.platform,
            "control": {
                "precision": lower,
                "rows_mismatched": compare.rows_mismatched(low, ref),
                **compare.error_numbers(compare.row_errors(low, ref)),
            },
        }
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
