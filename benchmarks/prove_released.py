"""Readings for the limits of a cell whose program leaves the reference
no room beside it, many seeds in one process:

    python3 -m benchmarks.prove_released --workload <cell> --seeds <a,b,...> \\
        [--control-seeds <n>] [--plant <file.py>:<function> ...] [--controls-only]

`benchmarks.prove` keeps the program on the device while the reference
runs. Here the program is built once and drives one job a seed through
the cell's own driver; then it is released, as `benchmarks.run` releases
it, and the reference answers the sampled inputs of several seeds a pass
(`ROWS_A_PASS`; its weights are made once). The lower readings are the program's
errors, seed by seed, as `check` reads them, with every sampled row's
error beside its count of words. For the first `--control-seeds` seeds both
controls are read in the program's place: the family's
`CONTROL_PRECISION` and, where it names one, its `SECOND_CONTROL`; and the
reference against every product in float32.

`--plant` reads a fault at the cell's own size: the named function is
called with `setattr` before the program is built again, and that
program drives the first `--control-seeds` seeds' jobs. The tests' own
planted faults are such functions (`tests/benchmarks/deepseek_v2_tiny.py`).

`--controls-only` builds no program and reads the controls alone, as
`benchmarks.prove_controls` does for the second one.

One JSON line a seed and reading. The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import sys
import types

#: the reference keeps every row's state on the device between layers:
#: 60 rows of 2,048 tokens at a width of 5,120 left a later pass 40 MB
ROWS_A_PASS = 32


def _planted(spec: str):
    path, name = spec.rsplit(":", 1)
    found = importlib.util.spec_from_file_location("planted_fault", path)
    module = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return getattr(module, name)


def _drive(cell, driver, seeds) -> dict:
    """{seed: (state, window of one job)} of a program built here and
    released before this returns."""
    transformer, weights = driver.build_entry(cell)
    kept = {}
    for seed in seeds:
        cell.seed = seed
        state = driver.load_job(cell, transformer, weights)
        # a job outlasts half a second: one job (the first also compiles)
        kept[seed] = (state, driver.window(state, 0.5))
    del transformer
    for state, _ in kept.values():
        driver.release(state)
    import jax

    held = (jax.local_devices()[0].memory_stats() or {}).get("bytes_in_use")
    print(json.dumps({"released": True, "bytes_in_use": held}), file=sys.stderr, flush=True)
    return kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--plant", action="append", default=[])
    ap.add_argument("--controls-only", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    low_seeds = seeds[: args.control_seeds]

    import numpy as np

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

    drives = {}
    if args.controls_only:
        weights = reference.make_weights(cell.config, cell.config["weights_seed"])
        states = {}
        for seed in low_seeds:
            cell.seed = seed
            states[seed] = driver.load_job(cell, None, weights)
        seeds = low_seeds
    else:
        drives["program"] = _drive(cell, driver, seeds)
        states = {seed: state for seed, (state, _) in drives["program"].items()}
    for spec in () if args.controls_only else args.plant:
        patched = []

        def plant(owner, name, value):
            patched.append((owner, name, getattr(owner, name)))
            setattr(owner, name, value)

        _planted(spec)(plant)
        drives[spec.rsplit(":", 1)[1]] = _drive(cell, driver, low_seeds)
        for owner, name, value in patched:
            setattr(owner, name, value)

    # the reference, the sampled inputs of several seeds a pass
    inputs = {
        seed: [x for x in state.sample_inputs if x is not None]
        for seed, state in states.items()
    }

    def answers_of(which, precision="reference"):
        out, which = {}, list(which)
        while which:
            n, rows = 0, 0
            while n < len(which) and (
                n == 0 or rows + len(inputs[which[n]]) <= ROWS_A_PASS
            ):
                rows += len(inputs[which[n]])
                n += 1
            together = types.SimpleNamespace(
                cell=cell,
                weights=states[seeds[0]].weights,
                sample_inputs=[x for seed in which[:n] for x in inputs[seed]],
            )
            got = driver.reference_answers(together, precision)
            at = np.cumsum([0] + [len(inputs[seed]) for seed in which[:n]])
            for k, seed in enumerate(which[:n]):
                out[seed] = got[at[k] : at[k + 1]]
            which = which[n:]
        return out

    ref = answers_of(seeds)
    words = {
        seed: [len(x.split()) if isinstance(x, str) else 0 for x in inputs[seed]]
        for seed in seeds
    }

    def say(**line):
        print(
            json.dumps({"workload": cell.name, "platform": dev.platform, **line}),
            flush=True,
        )

    for name, kept in drives.items():
        for seed, (state, window) in kept.items():
            answers = driver.sampled_answers(state, window, ref[seed][0].shape)
            errs = driver.sample_errors(answers, ref[seed])
            say(
                seed=seed,
                reading=name,
                jobs=len(window.jobs),
                numbers={
                    **compare.error_numbers(errs),
                    "rows_mismatched": sum(
                        compare.rows_mismatched(np.nan_to_num(got), ref[seed])
                        for got in answers
                    ),
                    "rows_misplaced": int(window.failed),
                    "ref_rows_nearest_pair": compare.nearest_pair(ref[seed]),
                    "rows_compared": int(errs.size),
                },
                rows=sorted(zip(words[seed], errs[0].tolist())),
            )

    lowered = [reference.CONTROL_PRECISION[cell.config["compute_dtype"]]]
    if getattr(reference, "SECOND_CONTROL", None):
        lowered.append(reference.SECOND_CONTROL)
    truth = answers_of(low_seeds, "highest")
    for precision in lowered:
        low = answers_of(low_seeds, precision)
        for seed in low_seeds:
            errs = compare.row_errors(low[seed], ref[seed])
            say(
                seed=seed,
                reading=f"control:{precision}",
                numbers={
                    **compare.error_numbers(errs),
                    "rows_mismatched": compare.rows_mismatched(low[seed], ref[seed]),
                },
            )
    for seed in low_seeds:
        errs = compare.row_errors(ref[seed], truth[seed])
        say(
            seed=seed,
            reading="reference_vs_highest",
            numbers=compare.error_numbers(errs),
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
