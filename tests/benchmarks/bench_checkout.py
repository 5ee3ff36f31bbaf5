"""A checkout for the benchmark's tests: the benchmark's own files copied
into a temporary directory beside links to the program, so that what a run
leaves behind (weights files, traces) lands there and new files can be
dropped in without touching the repo."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def job_lengths_by_edge(traffic: str, edges) -> dict:
    """One job of a traffic mix as the driver's `work` gives its real
    lengths: {edge: {tokens: live rows}}, tokens = words + [CLS] + [SEP],
    each row in the least of `edges` that holds it."""
    sys.path.insert(0, ROOT)
    from benchmarks.data import texts

    with open(os.path.join(ROOT, "benchmarks", "traffic", f"{traffic}.json")) as f:
        data = json.load(f)["data"]
    words = texts.word_counts(data["rows"] - data["null_rows"], data["word_counts"])
    by_edge = {str(e): {} for e in edges}
    for n in (words + 2).tolist():
        of = by_edge[str(next(e for e in edges if n <= e))]
        of[n] = of.get(n, 0) + 1
    return by_edge


def make_checkout(directory) -> str:
    directory = str(directory)
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"),
        os.path.join(directory, "benchmarks"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), directory)
    for name in ("sparkdl_tpu", "native"):
        os.symlink(os.path.join(ROOT, name), os.path.join(directory, name))
    return directory


def run_cell(checkout: str, *argv, module="benchmarks.run", timeout=900):
    """Runs the harness there on the CPU; (exit code, parsed last line of
    standard output or None, standard error)."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)  # one CPU device, as one chip
    env["PYTHONPATH"] = checkout
    proc = subprocess.run(
        [sys.executable, "-m", module, *argv],
        cwd=checkout,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    lines = proc.stdout.strip().splitlines()
    last = None
    if lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            last = None
    return proc.returncode, last, proc.stderr
