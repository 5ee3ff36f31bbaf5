"""The benchmark: `python3 -m benchmarks.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`, described by `BENCHMARK.json` at the root.

Everything that belongs to one configuration, traffic mix, driver,
kind of entry, kind of data, model family or per-layer metric is a file
of its own, found by its name: `configs/`, `traffic/`, `limits/`,
`drivers/`, `entries/`, `data/`, `counts/`, `reference/`,
`layer_metrics/`. A later cell adds files and edits none. `run.py`,
`trace_reduce.py`, `compare.py`, `traffic_gen.py` and `peaks.json` are
the yardstick. Nothing here is
imported by the program, and the plain references under `reference/`
import nothing of the program.
"""
