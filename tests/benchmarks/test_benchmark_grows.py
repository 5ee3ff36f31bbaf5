"""`BENCHMARK.json` as a later PR would leave it, through every assertion
under `tests/benchmarks/` that reads the file's shape: a fifth
configuration with a cell of its own, the cell listed under the metrics of
the code it shares with the accepted cells, and two per-layer entries
appended after today's last. A PR of another kind may edit no file that
is here, so an assertion that pins where an entry stands, how many cells
it lists or which cells a pair of metrics share is a PR refused later:
this test fails the day such a pin is written.

The copy is made in memory; the files it names and the tree lacks (the
configuration, its cell's limits, the two readers) are written into a
checkout of the benchmark's own files under `tmp_path`, which the
assertions take as their root."""

import copy
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_checkout import load_bench, make_checkout  # noqa: E402
from test_benchmark_json import (  # noqa: E402
    check_configs,
    check_kinds,
    check_limits,
    check_metrics,
    check_workloads,
)
from test_host_spans import check_span_readers  # noqa: E402
from test_program_scopes import CELLS, check_entry  # noqa: E402

CONFIG, CELL = "fifth-model", "fifth-model-embed-windows"
#: The metrics of the code an expert model with a kernel of its own would
#: share with the accepted cells: the grouped product and the routed path.
SHARED = ("moe_grouped_matmul_roofline", "moe.expert_ms_per_kslot", "moe.routed_ms_per_kslot")
APPENDED = [
    {"name": "windowed_attention_roofline", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "Kernel", "moves": "rows_per_s",
     "workloads": [CELL]},
    {"name": "attn.window_ms_per_ktoken", "unit": "ms/ktoken", "better": "lower",
     "source": "device_trace", "layer": "Program", "moves": "rows_per_s",
     "workloads": [CELL]},
]


def new_files(root: str) -> dict:
    """{path under benchmarks/: text} of the files the grown copy names and
    the tree lacks: the configuration (an accepted one's kinds under the
    new name), its cell's limits (an accepted cell's) and two readers."""
    def text(*parts):
        with open(os.path.join(root, "benchmarks", *parts)) as f:
            return f.read()

    config = dict(json.loads(text("configs", "jamba2-3b.json")), name=CONFIG)
    return {
        f"configs/{CONFIG}.json": json.dumps(config),
        f"limits/{CELL}.json": text("limits", "jamba2-3b-embed-windows.json"),
        **{f"layer_metrics/{m['name']}.py": "def read(ctx):\n    return None\n" for m in APPENDED},
    }


def grown(bench: dict) -> dict:
    """A copy of `bench` with the fifth configuration, its cell and the
    two entries; no entry that was there is moved, renamed or shortened."""
    bench = copy.deepcopy(bench)
    cells = {w["name"] for w in bench["workloads"]}
    bench["configs"].append(
        {"name": CONFIG, "source": "https://example.org/fifth-model/config.json",
         "file": f"benchmarks/configs/{CONFIG}.json",
         "reduced": ["num_hidden_layers"], "why": "a fifth configuration"}
    )
    bench["workloads"].append(
        {"name": CELL, "config": CONFIG, "traffic": "embed-windows", "chips": 1,
         "why": "the fifth configuration under a mix that is there"}
    )
    for m in bench["per_layer"]:
        if set(m["workloads"]) == cells or m["name"] in SHARED:
            m["workloads"].append(CELL)
    bench["per_layer"].extend(copy.deepcopy(APPENDED))
    return bench


@pytest.fixture(scope="module")
def later(tmp_path_factory):
    """(the grown copy, a checkout that holds the files it names)."""
    root = make_checkout(tmp_path_factory.mktemp("grown"))
    b = os.path.join(root, "benchmarks")
    for path, text in new_files(root).items():
        assert not os.path.exists(os.path.join(b, path))
        with open(os.path.join(b, path), "w") as f:
            f.write(text)
    return grown(load_bench()), root


def test_the_copy_grew_as_a_model_config_pr_would_grow_it(later):
    bench, _ = later
    before = load_bench()
    n = len(before["per_layer"])
    assert [m["name"] for m in bench["per_layer"][:n]] == [m["name"] for m in before["per_layer"]]
    assert [m["name"] for m in bench["per_layer"][n:]] == [m["name"] for m in APPENDED]
    # whatever entry is last today is no longer last, and the cell joined
    # the lists of the code it shares
    joined = {m["name"] for m in bench["per_layer"][:n] if CELL in m["workloads"]}
    assert {"program.unscoped_busy_pct", "mlp.ms_per_ktoken", "step_mfu", *SHARED} <= joined
    for old, new in zip(before["per_layer"], bench["per_layer"]):
        assert new["workloads"][: len(old["workloads"])] == old["workloads"]
        assert {k: v for k, v in new.items() if k != "workloads"} == {
            k: v for k, v in old.items() if k != "workloads"
        }


CHECKS = {
    "configs": check_configs,
    "workloads": check_workloads,
    "metrics": check_metrics,
    "kinds": check_kinds,
    "limits": check_limits,
    **{f"entry[{name}]": (lambda b, root, name=name: check_entry(b, name, root)) for name in CELLS},
    "span_readers": lambda b, root: check_span_readers(b),
}


@pytest.mark.parametrize("check", CHECKS)
def test_every_shape_assertion_takes_the_grown_copy(later, check):
    bench, root = later
    CHECKS[check](bench, root)


def test_an_accepted_cell_may_not_drop_out_and_pairs_stay_whole(later):
    """What the relaxed assertions still hold."""
    bench, root = later
    dropped = copy.deepcopy(bench)
    entry = next(m for m in dropped["per_layer"] if m["name"] == "mlp.ms_per_ktoken")
    entry["workloads"].remove("bert-base-embed")
    with pytest.raises(AssertionError):
        check_entry(dropped, "mlp.ms_per_ktoken", root)
    # a cell that joins the scope metric joins the kernel metric whose
    # divisor it shares
    lone = copy.deepcopy(bench)
    entry = next(m for m in lone["per_layer"] if m["name"] == "moe.expert_ms_per_kslot")
    entry["workloads"].remove(CELL)
    with pytest.raises(AssertionError):
        check_entry(lone, "moe.routed_ms_per_kslot", root)
    # the five keep PR 36's order among themselves
    swapped = copy.deepcopy(bench)
    names = [m["name"] for m in swapped["per_layer"]]
    i, j = names.index("mlp.ms_per_ktoken"), names.index("mamba.mixer_ms_per_ktoken")
    swapped["per_layer"][i], swapped["per_layer"][j] = swapped["per_layer"][j], swapped["per_layer"][i]
    with pytest.raises(AssertionError):
        check_entry(swapped, "mlp.ms_per_ktoken", root)
