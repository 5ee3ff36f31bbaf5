"""One run of one cell of the benchmark.

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell is an entry of `workloads` in
`BENCHMARK.json`; by its names the harness finds
`configs/<config>.json`, `traffic/<traffic>.json` (which names its
`drivers/<driver>.py` and, in its `data` block, its `data/<kind>.py`),
`limits/<cell>.json`, for the configuration's `family` the modules
`counts/<family>.py` and `reference/<family>.py`, and for its `entry`
the module `entries/<kind>.py`.
With `--trace 1` the window runs under the jax profiler and every
per-layer metric of `BENCHMARK.json` that lists the cell is read by
`layer_metrics/<metric>.py`. Nothing in this file knows a cell, a
configuration, a traffic mix or a metric by name.

The last line of standard output is the result: `correct`, `attempted`,
`failed`, `metrics`, `device` (and `breakdown` when traced), then
`work_check`, the benchmark's own account of the window's rows beside
the program's counters (what a driver's `work` gives as `lengths_check`),
and `compared`, each number that decided `correct` beside its limit,
which is also the last thing on standard error. Without a TPU, or with fewer
chips than the cell asks for, the exit code is 2 and nothing is printed:
`--rehearse-cpu` is the explicit rehearsal of the control flow on the
CPU at the traffic file's `rehearsal` sizes, says so in its line, and
reports no device metric.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: What a run leaves behind in its checkout besides the compile cache:
#: the weights files and the last trace. Git-ignored.
WORK_DIR = os.path.join(ROOT, ".bench_work")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    seed: int
    rehearsal: bool
    work_dir: str


def _load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str, seed: int, rehearsal: bool) -> Cell:
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise SystemExit(
            f"no workload {name!r} in BENCHMARK.json "
            f"(it has {[w['name'] for w in bench['workloads']]})"
        )
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(ROOT, files[w["config"]])) as f:
        config = json.load(f)
    traffic = _load_json("traffic", f"{w['traffic']}.json")
    if rehearsal:
        traffic = _merged(traffic, traffic["rehearsal"])
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        limits=_load_json("limits", f"{name}.json")["limits"],
        seed=seed,
        rehearsal=rehearsal,
        work_dir=WORK_DIR,
    )


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merged(base[k], v) if isinstance(v, dict) else v
    return out


def metrics_of(bench: dict, group: str, cell: str) -> list:
    """The metrics of `group` that this cell has to report."""
    return [
        m for m in bench[group] if cell in m.get("workloads", [cell])
    ]


def load_reader(metric: str):
    path = os.path.join(HERE, "layer_metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + metric.replace(".", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class CompileCount:
    """Counts what jax compiles (or fetches from its persistent cache):
    the window should see none."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _duration: float, **_kw) -> None:
        self.n += event == "/jax/core/compile/backend_compile_duration"


def _device_block(jax) -> dict:
    """The device as jax reports it. The peak is that of the fullest chip:
    the allocator's peak of bytes in use (arrays: weights, staged batches,
    results) plus its peak of bytes reserved, which is where the TPU client
    keeps the temporaries of every loaded executable. Reserved bytes are
    not in `peak_bytes_in_use` and nothing else can have them while the
    executable is loaded (measured: PERF.md, Findings, PR 24)."""
    dev = jax.devices()[0]
    fullest = None
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            continue
        in_use = int(stats["peak_bytes_in_use"])
        reserved = int(stats.get("peak_bytes_reserved", 0))
        if fullest is None or in_use + reserved > fullest[0]:
            fullest = (in_use + reserved, in_use, reserved)
    block = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": fullest[0] if fullest else None,
    }
    if fullest:
        block["memory_peak_in_use_bytes"] = fullest[1]
        block["memory_peak_reserved_bytes"] = fullest[2]
    return block


def open_device(cell: Cell):
    """The cell's environment, the program from this checkout, and jax's
    first device; None, with the reason on standard error, where the
    program is another checkout's or the chips are not there."""
    for k, v in cell.config.get("env", {}).items():
        os.environ[k] = str(v)
    if cell.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import sparkdl_tpu  # noqa: F401  places the compile cache in the checkout

    if os.path.dirname(os.path.dirname(sparkdl_tpu.__file__)) != ROOT:
        print(
            f"benchmarks: sparkdl_tpu came from {sparkdl_tpu.__file__}, "
            "not from this checkout",
            file=sys.stderr,
        )
        return None
    import jax

    dev = jax.devices()[0]
    if not cell.rehearsal and (
        dev.platform != "tpu" or jax.device_count() < cell.chips
    ):
        print(
            f"benchmarks: {cell.name} needs {cell.chips} TPU chip(s); jax "
            f"found {jax.device_count()} {dev.platform!r} device(s). "
            "Nothing was run.",
            file=sys.stderr,
        )
        return None
    return dev


def run(args) -> int:
    bench = load_benchmark()
    cell = find_cell(bench, args.workload, args.seed, args.rehearse_cpu)
    dev = open_device(cell)
    if dev is None:
        return 2
    import jax

    from benchmarks import compare, host_spans, trace_reduce
    from benchmarks.peaks import peaks_for

    peaks = None if args.rehearse_cpu else peaks_for(dev.device_kind)
    driver = importlib.import_module(
        f"benchmarks.drivers.{cell.traffic['driver']}"
    )
    compiles = CompileCount()
    state = driver.setup(cell)
    setup_s = time.perf_counter() - _T0

    trace_dir = os.path.join(WORK_DIR, "trace")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before, compiled_before = driver.counters(state), compiles.n
    with jax.profiler.TraceAnnotation("bench:window"):
        window = driver.window(state, args.seconds)
    delta = {
        k: v - before.get(k, 0) for k, v in driver.counters(state).items()
    }
    compiled_in_window = compiles.n - compiled_before
    if args.trace:
        jax.profiler.stop_trace()
    device = _device_block(jax)
    driver.release(state)

    numbers = driver.check(cell, state, window)
    decided = compare.decide(numbers, cell.limits)
    # what the window completed, and whether the benchmark's own account
    # of the rows squares with the program's counters: in every run
    work = driver.work(state, delta, window)
    work_check = work.get("lengths_check")
    if work_check and not work_check["ok"]:
        print(f"benchmarks: no pair term is counted: {work_check['why']}", file=sys.stderr)

    if args.trace:
        trace = None
        if not args.rehearse_cpu:
            trace = trace_reduce.reduce_window(
                trace_reduce.load_events(trace_dir),
                "bench:window",
                "bench:",
                span_prefix=host_spans.PREFIX,
                envelopes=host_spans.ENVELOPES,
            )
            device["busy_s"] = trace.busy_s
            device["window_s"] = trace.window_s
        ctx = {
            "cell": cell,
            "trace": trace,
            "window": window,
            "counters": delta,
            "work": work,
            "peaks": peaks,
            "chips": cell.chips,
            "counts": importlib.import_module(
                f"benchmarks.counts.{cell.config['family']}"
            ),
        }
        values = {
            m["name"]: load_reader(m["name"])(ctx)
            for m in metrics_of(bench, "per_layer", cell.name)
        }
    else:
        values = {**window.end_to_end(), "setup_s": setup_s}
        values = {
            m["name"]: values.get(m["name"])
            for m in metrics_of(bench, "end_to_end", cell.name)
        }
    spec = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    for name, value in values.items():
        if value is None:  # a reader that found nothing to read
            continue
        # a CPU run can say what the program counted; no time or rate of
        # one is written under a metric's name
        if args.rehearse_cpu and spec[name]["source"] != "program_counter":
            continue
        extra = {}
        if isinstance(value, dict):
            extra = {k: v for k, v in value.items() if k != "value"}
            value = value["value"]
        metrics[name] = {"value": value, "unit": spec[name]["unit"], **extra}

    line = {
        "correct": compare.all_ok(decided),
        "attempted": window.attempted,
        "failed": window.failed,
        "metrics": metrics,
        "device": device,
    }
    if args.trace and not args.rehearse_cpu:
        line["breakdown"] = trace.breakdown()
    if args.rehearse_cpu:
        line["rehearsal"] = "cpu: control flow only, no device metric"
    line.update(
        workload=cell.name,
        seed=cell.seed,
        window_s=window.seconds,
        jobs=len(window.jobs),
        job_s=[j.end_s - j.start_s for j in window.jobs],
        rows=window.rows,
        setup_s=setup_s,
        compiled_in_window=compiled_in_window,
        work_check=work_check,
        compared=decided,
    )
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"compared": decided}), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse-cpu",
        action="store_true",
        help="rehearse the control flow on the CPU at the traffic file's "
        "rehearsal sizes; measures nothing",
    )
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
