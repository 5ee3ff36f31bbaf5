"""`correct` has to be able to come out false.

The control: the plain reference in the nearest precision under the one a
configuration states, put in the program's place, has to fail the cell's
limits (here at a size a test run can hold; on the chip at the cell's own
size, see PERF.md). The faults: the rest of a run is driven on the CPU
with the timed path broken underneath, an answer altered where it is
produced, and `correct` comes out false."""

import argparse
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_checkout import ROOT, load_bench  # noqa: E402

sys.path.insert(0, ROOT)

from benchmarks import compare, run, traffic_gen  # noqa: E402


def _json(*parts):
    with open(os.path.join(ROOT, "benchmarks", *parts)) as f:
        return json.load(f)


CONTROLS = [
    # cell, config, rows of the cell's own data at a size a test can hold
    ("bert-base-embed", "bert-base", 12),
]


@pytest.mark.parametrize("cell, config_name, rows", CONTROLS)
def test_control_in_lower_precision_fails_the_limits(cell, config_name, rows):
    import importlib

    config = _json("configs", f"{config_name}.json")
    traffic = {w["name"]: w["traffic"] for w in load_bench()["workloads"]}[cell]
    data = dict(_json("traffic", f"{traffic}.json")["data"], rows=rows, null_rows=0)
    limits = _json("limits", f"{cell}.json")["limits"]
    reference = importlib.import_module(f"benchmarks.reference.{config['family']}")
    lower = reference.CONTROL_PRECISION[config["compute_dtype"]]
    weights = reference.make_weights(config, config["weights_seed"])
    inputs = list(traffic_gen.make_rows(data, 2**31 + 3))
    ref = reference.outputs(config, weights, inputs, block_rows=rows)
    low = reference.outputs(config, weights, inputs, precision=lower, block_rows=rows)

    def numbers(got):
        return {
            "rows_misplaced": 0,
            "rows_mismatched": compare.rows_mismatched(got, ref),
            **compare.error_numbers(compare.row_errors(got, ref)),
        }

    decided = compare.decide(numbers(low), limits)
    assert not compare.all_ok(decided), decided
    # it is the precision that fails, by a number with a tolerance: every
    # row is still the nearest to its own reference
    assert decided["row_err_median"]["ok"] is False
    assert decided["rows_mismatched"]["value"] == 0
    # and the reference against itself passes them all
    assert compare.all_ok(compare.decide(numbers(ref), limits))


def _swap_two(outputs):
    live = [i for i, y in enumerate(outputs) if y is not None]
    if len(live) >= 2:
        a, b = live[0], live[1]
        outputs[a], outputs[b] = outputs[b], outputs[a]
    return outputs


def _scale(outputs):
    return [None if y is None else np.asarray(y) * 1.05 for y in outputs]


def _scale_a_quarter(outputs):
    """A fault in a minority of the rows: the median does not see it."""
    outputs[::4] = _scale(outputs[::4])
    return outputs


def _drop_one(outputs):
    live = [i for i, y in enumerate(outputs) if y is not None]
    outputs[live[0]] = None
    return outputs


def _run_with_fault(monkeypatch, tmp_path, capsys, cell, fault):
    """A whole rehearsal run in this process, the batch engine's answers
    passed through `fault` where they are produced."""
    from sparkdl_tpu.transformers import execution

    real = execution.run_batched_shared

    def broken(*a, **k):
        return fault(list(real(*a, **k)))

    monkeypatch.setattr(execution, "run_batched_shared", broken)
    monkeypatch.setattr(run, "WORK_DIR", str(tmp_path / "work"))
    for k in ("SPARKDL_TEXT_BUCKETS", "SPARKDL_TEXT_MIN_BUCKET"):
        monkeypatch.setenv(k, os.environ.get(k, ""))  # restored afterwards
    args = argparse.Namespace(
        workload=cell, seed=2**31 + 21, seconds=0.2, trace=0, rehearse_cpu=True
    )
    assert run.run(args) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "cell, fault, failing",
    [
        ("bert-base-embed", None, None),
        ("bert-base-embed", _swap_two, "rows_mismatched"),
        ("bert-base-embed", _scale, "row_err_median"),
        ("bert-base-embed", _scale_a_quarter, "row_err_p90"),
        ("bert-base-embed", _drop_one, "rows_misplaced"),
    ],
)
def test_fault_in_the_timed_path_is_caught(
    monkeypatch, tmp_path, capsys, cell, fault, failing
):
    line = _run_with_fault(
        monkeypatch, tmp_path, capsys, cell, fault or (lambda outputs: outputs)
    )
    if fault is None:  # the same drive, nothing broken
        assert line["correct"] is True
        return
    assert line["correct"] is False
    assert line["compared"][failing]["ok"] is False
    if fault is _scale_a_quarter:
        assert line["compared"]["row_err_median"]["ok"] is True
    if failing == "rows_misplaced":
        assert line["failed"] > 0
