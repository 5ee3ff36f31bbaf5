"""`BENCHMARK.json` against the rules it is refused by before any run, and
against the files it names. What reads the file's shape is a function of
`bench` and the checkout's root (`check_configs`, `check_workloads`,
`check_metrics`, `check_kinds`, `check_limits`), so that `test_benchmark_grows.py` can put a copy with a
fifth configuration through the same assertions."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_checkout import ROOT, load_bench  # noqa: E402

sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head).*(size|dim)|_dim$|_rank$")


@pytest.fixture(scope="module")
def bench():
    return load_bench()


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["command"]) <= 32 and all(_line(w) for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    # a full check with 24 cells fits the driver's day
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(bench):
    check_configs(bench)


def test_workloads(bench):
    check_workloads(bench)


def test_metrics(bench):
    check_metrics(bench)


def check_configs(bench, root=ROOT):
    assert 1 <= len(bench["configs"]) <= 24
    names = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        with open(os.path.join(root, c["file"])) as f:
            blob = json.load(f)
        assert blob["name"] == c["name"]
        assert all(k in blob for k in c["reduced"])


def check_workloads(bench, root=ROOT):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        for part in ("traffic", "limits"):
            name = w["traffic"] if part == "traffic" else w["name"]
            assert os.path.exists(
                os.path.join(root, "benchmarks", part, f"{name}.json")
            )
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def check_metrics(bench, root=ROOT):
    e2e, layers = bench["end_to_end"], bench["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in bench["workloads"]}
    e2e_names = {m["name"] for m in e2e}
    assert "setup_s" in e2e_names
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in layers:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }
        assert m["moves"] in e2e_names and _line(m["layer"])
        assert os.path.exists(
            os.path.join(root, "benchmarks", "layer_metrics", f"{m['name']}.py")
        )
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
    for m in e2e + layers:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    # a kernel's roofline stands beside the whole step's share of the peak
    for m in layers:
        if m["name"].endswith("_roofline"):
            assert any(
                "mfu" in re.split(r"[._]", o["name"])
                and o["moves"] == m["moves"]
                and set(m["workloads"]) <= set(o.get("workloads", cells))
                for o in layers
            )
    # every cell: set-up, one more end-to-end metric, one per-layer metric
    for cell in cells:
        mine = lambda ms: [m for m in ms if cell in m.get("workloads", cells)]  # noqa: E731
        assert len(mine(e2e)) >= 2 and len(mine(layers)) >= 1


def test_layers_are_named_in_perf_md(bench):
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert re.search(rf"^\| {re.escape(layer)} \|", perf, re.M), layer


def test_files_under_paths_are_named_from_names(bench):
    for p in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                assert re.fullmatch(r"[A-Za-z0-9_.\-]+", f), os.path.join(d, f)


def _json(root, *parts):
    with open(os.path.join(root, "benchmarks", *parts)) as f:
        return json.load(f)


def test_every_kind_a_cell_names_is_a_file_of_that_name(bench):
    check_kinds(bench)


def check_kinds(bench, root=ROOT):
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        with open(os.path.join(root, files[w["config"]])) as f:
            config = json.load(f)
        traffic = _json(root, "traffic", f"{w['traffic']}.json")
        for directory, name in (
            ("entries", config["entry"]["kind"]),
            ("reference", config["family"]),
            ("counts", config["family"]),
            ("drivers", traffic["driver"]),
            ("data", traffic["data"]["kind"]),
        ):
            assert os.path.exists(
                os.path.join(root, "benchmarks", directory, f"{name}.py")
            ), (w["name"], directory, name)
        assert _line(traffic["why"], limit=2000)
        # what a rehearsal overrides is there to override
        assert set(traffic["rehearsal"]) <= set(traffic)


def test_limits_lie_between_their_readings(bench):
    check_limits(bench)


def check_limits(bench, root=ROOT):
    """A limit with a tolerance was set from two readings, the program's
    largest and the control's least, at least three times apart, and has
    more room above the lower than a fifth of it; an exact one is 0."""
    for w in bench["workloads"]:
        blob = _json(root, "limits", f"{w['name']}.json")
        assert blob["limits"], w["name"]
        for name, limit in blob["limits"].items():
            set_from = blob["set_from"][name]
            if limit == 0:
                assert isinstance(set_from, str) and set_from.startswith("exact")
                continue
            lower, upper = set_from["lower"], set_from["upper"]
            assert upper >= 3 * lower, (name, set_from)
            assert 1.2 * lower <= limit < upper, (name, limit, set_from)
            assert set_from["program_seeds"] >= 12
            assert set_from["control_seeds"] >= 3
        # a number that is reported and not held says why
        for name, why in blob["set_from"].items():
            if name not in blob["limits"] and name != "where":
                assert isinstance(why, str) and "not held" in why
