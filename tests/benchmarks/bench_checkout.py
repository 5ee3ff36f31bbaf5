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
