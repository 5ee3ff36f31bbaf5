"""Every cell end to end on the CPU at its traffic file's rehearsal sizes,
behind `--rehearse-cpu`, which is never the default; and a cell dropped in
as new files only (a configuration with an entry kind and a model family
of its own, a traffic mix with a data kind of its own, limits and a
per-layer metric), found with no edit to a file that was there."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_checkout import ROOT, load_bench, make_checkout, run_cell  # noqa: E402

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("checkout"))


_bench = load_bench


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_without_a_tpu_nothing_is_printed(checkout, cell):
    rc, last, err = run_cell(
        checkout, "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"
    )
    assert rc == 2
    assert last is None
    assert "needs 1 TPU chip" in err and "Nothing was run" in err


@pytest.mark.parametrize(
    "cell, trace", [("bert-base-embed", 0), ("bert-base-embed", 1)]
)
def test_cell_rehearses_on_the_cpu(checkout, cell, trace):
    rc, last, err = run_cell(
        checkout,
        "--workload", cell, "--seed", str(2**31 + 11), "--seconds", "0.5",
        "--trace", str(trace), "--rehearse-cpu",
    )
    assert rc == 0, err[-3000:]
    assert KEYS <= set(last)
    assert list(last)[-1] == "compared"
    assert last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] >= 24
    assert last["device"]["platform"] == "cpu"
    assert "rehearsal" in last
    assert "breakdown" not in last
    assert "busy_s" not in last["device"]
    # no time, rate or share of a CPU run under a metric's name; what the
    # program counted may be said
    sources = {m["name"]: m["source"] for m in _bench()["per_layer"]}
    assert all(sources.get(m) == "program_counter" for m in last["metrics"])
    if trace:
        assert "feeder.pad_rows_pct" in last["metrics"]
        assert "text.pad_tokens_pct" in last["metrics"]
    for name, entry in last["compared"].items():
        if "limit" in entry:
            assert entry["ok"] is True, (name, entry)
    # the numbers compared are the last thing on standard error too
    assert json.loads(err.strip().splitlines()[-1])["compared"] == last["compared"]
    assert last["compiled_in_window"] == 0


#: A cell of another kind altogether, as the files a later PR would add:
#: rows of numbers, doubled by a column expression of the program's own
#: DataFrame. {path under benchmarks/: text}
DROPPED_IN = {
    "entries/Doubler.py": """
class _Doubler:
    def __init__(self, out_col, by):
        self.out_col, self.by = out_col, by

    def transform(self, df):
        by = self.by
        return df.withColumn(
            self.out_col,
            lambda row: None if row["in"] is None else [by * v for v in row["in"]],
        )


def build(cell, weights_path, out_col):
    import numpy as np

    return _Doubler(out_col, float(np.load(weights_path)["by"]))
""",
    "data/vectors.py": """
def rows(data, rng, nulls):
    for i in range(data["rows"]):
        yield None if i in nulls else rng.normal(size=data["width"])


def stored(row):
    return row.tolist()
""",
    "reference/doubling.py": """
import numpy as np

CONTROL_PRECISION = {"float64": "float32"}


def make_weights(config, seed):
    return {"by": np.float64(config["by"])}


def outputs(config, weights, inputs, precision="reference", block_rows=32):
    out = np.stack(inputs) * weights["by"]
    return out.astype(np.float32) if precision == "float32" else out
""",
    "counts/doubling.py": """
def forward_flops(config, work):
    return float(work["rows"] * config["width"])


def kernel_work(config, kernel, work):
    return None
""",
    "layer_metrics/feeder.rows.py": """
def read(ctx):
    return ctx["counters"].get("feeder.rows")
""",
    "layer_metrics/doubling.flops.py": """
def read(ctx):
    return ctx["counts"].forward_flops(ctx["cell"].config, ctx["work"])
""",
    "configs/doubler.json": json.dumps({
        "name": "doubler", "family": "doubling", "by": 2.0, "width": 5,
        "compute_dtype": "float64", "weights_seed": 0,
        "entry": {"kind": "Doubler"}, "env": {},
    }),
    "traffic/few-vectors.json": json.dumps({
        "driver": "offline_transform",
        "data": {"kind": "vectors", "rows": 40, "null_rows": 2, "width": 5},
        "partitions": 2, "batch_rows": 4, "check_rows": 16, "rehearsal": {},
    }),
    "limits/doubler-few.json": json.dumps(
        {"limits": {"rows_misplaced": 0, "rows_mismatched": 0, "row_err_max": 1e-6}}
    ),
}


def test_a_cell_of_new_kinds_is_found_by_name_with_no_edit(tmp_path):
    checkout = make_checkout(tmp_path / "checkout")
    b = os.path.join(checkout, "benchmarks")
    before = {
        os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
        for d, _, files in os.walk(b)
        for f in files
    }
    for path, text in DROPPED_IN.items():
        assert not os.path.exists(os.path.join(b, path))
        with open(os.path.join(b, path), "w") as f:
            f.write(text)
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "doubler", "source": "test", "reduced": [], "why": "test",
         "file": "benchmarks/configs/doubler.json"}
    )
    bench["workloads"].append(
        {"name": "doubler-few", "config": "doubler", "traffic": "few-vectors",
         "chips": 1, "why": "test"}
    )
    for name in ("feeder.rows", "doubling.flops"):
        bench["per_layer"].append(
            {"name": name, "unit": "n", "better": "higher",
             "source": "program_counter", "layer": "Batch engines",
             "moves": "rows_per_s", "workloads": ["doubler-few"]}
        )
    with open(os.path.join(checkout, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    rc, last, err = run_cell(
        checkout,
        "--workload", "doubler-few", "--seed", "5", "--seconds", "0.2",
        "--trace", "1", "--rehearse-cpu",
    )
    assert rc == 0, err[-3000:]
    assert last["correct"] is True and last["workload"] == "doubler-few"
    assert last["failed"] == 0 and last["rows"] == 40 * last["jobs"]
    assert last["compared"]["rows_compared"]["value"] >= 16 * last["jobs"]
    # the new readers ran, one of them silent (this entry feeds no batch
    # engine); the metrics of other cells were not asked for
    assert last["metrics"] == {
        "doubling.flops": {"value": 5.0 * last["rows"], "unit": "n"}
    }
    assert all(os.path.getmtime(p) == t for p, t in before.items())


SOME_LENGTHS = [[12, 3], [100, 5], [7, 1], [300, 1]]


@pytest.mark.parametrize(
    "mix, word_counts",
    [(w["traffic"], None) for w in _bench()["workloads"]] + [("embed", SOME_LENGTHS)],
)
def test_every_seed_gives_the_same_sizes_in_another_order(mix, word_counts):
    """The seed moves no work: the same lengths, and in every partition
    the same number of null rows, whatever the seed."""
    sys.path.insert(0, ROOT)
    from benchmarks import traffic_gen

    with open(os.path.join(ROOT, "benchmarks", "traffic", f"{mix}.json")) as f:
        traffic = json.load(f)
    data = dict(traffic["data"], rows=traffic["partitions"] * 64)
    if word_counts:
        data["word_counts"] = word_counts
    per = data["rows"] // traffic["partitions"]
    seen = []
    for seed in (1, 2**31 + 5, 77):
        rows = list(traffic_gen.make_rows(data, seed))
        assert len(rows) == data["rows"]
        nulls = [
            sum(r is None for r in rows[p * per : (p + 1) * per])
            for p in range(traffic["partitions"])
        ]
        sizes = [len(r.split()) for r in rows if r is not None]
        seen.append((nulls, sorted(sizes), [r is None for r in rows], sizes, rows))
    assert sum(seen[0][0]) == data["null_rows"]
    assert all(s[0] == seen[0][0] for s in seen)
    assert all(s[1] == seen[0][1] for s in seen)
    assert seen[0][2] != seen[1][2]  # at other places
    assert seen[0][4] != seen[1][4]  # with other words
    if word_counts:  # in another order, and in the histogram's shares
        assert seen[0][3] != seen[1][3]
        live = len(seen[0][1])
        for words, weight in word_counts:
            assert seen[0][1].count(words) == pytest.approx(live * weight / 10, abs=1)


def test_unknown_workload_is_refused(checkout):
    rc, last, err = run_cell(
        checkout, "--workload", "nope", "--seed", "1", "--seconds", "1",
        "--trace", "0", "--rehearse-cpu",
    )
    assert rc != 0 and last is None
    assert "no workload 'nope'" in err
