"""Every example script runs end-to-end (CPU, subprocess — keeps the
examples honest the way doctests would)."""

import os
import subprocess
import sys

import pytest

_EXAMPLES = [
    "transfer_learning.py",
    "sql_scoring.py",
    "distributed_training.py",
    "multihost_inference.py",
    "model_parallelism.py",
    "streaming_featurize.py",
    "streaming_sql_scoring.py",
    "gang_training.py",
    "image_finetune.py",
    "pretrained_predict.py",
    "column_expressions.py",
    "window_analytics.py",
    "etl_functions_tour.py",
]

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script", _EXAMPLES)
def test_example_runs(script):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": _ROOT,
    }
    # runpy keeps __file__ set (exec of source would not), so examples can
    # locate the repo root and tracebacks show real filenames.
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, runpy, jax; "
         "jax.config.update('jax_platforms','cpu'); "
         "runpy.run_path(sys.argv[1], run_name='__main__')",
         os.path.join(_ROOT, "examples", script)],
        env=env,
        capture_output=True,
        text=True,
        timeout=1500,
        cwd=_ROOT,
    )
    assert r.returncode == 0, (
        f"{script} failed:\n{r.stdout[-1500:]}\n{r.stderr[-1500:]}"
    )
