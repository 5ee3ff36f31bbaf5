"""The driver's account of what a window completed (`offline_transform.
work`): the rows' real lengths by dispatched edge, from the job's own
rows, held against the program's counters; the slots measured; and what
the readers do with it: counted where the two accounts square, silent,
with the reason in the result line, where they do not."""

import argparse
import json
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_checkout import ROOT  # noqa: E402

sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.counts import bert as bert_counts  # noqa: E402
from benchmarks.counts import deepseek_v32 as v32_counts  # noqa: E402
from benchmarks.counts import jamba as jamba_counts  # noqa: E402
from benchmarks.drivers import offline_transform as driver  # noqa: E402
from benchmarks.run import load_reader  # noqa: E402

#: a job of six rows, two of them null: words + [CLS] + [SEP]
ROWS = ["a b c", None, " ".join(["w"] * 10), "x", None, " ".join(["y"] * 30)]


def _state(rows=ROWS, cap=16, row_length="words"):
    if row_length == "words":
        row_length = lambda text: min(len(text.split()) + 2, cap)  # noqa: E731
    return SimpleNamespace(row_length=row_length, rows=rows)


def _window(jobs=3, rows=4):
    return SimpleNamespace(jobs=[object()] * jobs, rows=jobs * rows)


def _delta(jobs=3, **over):
    # as the program counts a window of `jobs` jobs at edges 8 and 16: the
    # row of 32 tokens is cut at the top edge
    delta = {
        "text.bucket_rows.8": 2 * jobs,
        "text.bucket_rows.16": 2 * jobs,
        "text.tokens": (5 + 3 + 12 + 16) * jobs,
        "text.pad_tokens": (3 + 5 + 4 + 0) * jobs,
        "feeder.rows": 4 * jobs,
    }
    delta.update(over)
    return delta


def test_real_lengths_by_edge_times_the_jobs_completed():
    work = driver.work(_state(), _delta(), _window())
    assert work["rows"] == 12
    assert work["rows_by_length"] == {"8": 6, "16": 6}
    assert work["lengths_by_edge"] == {"8": {5: 3, 3: 3}, "16": {12: 3, 16: 3}}
    assert "pairs_unknown" not in work and "slots_held" not in work
    assert work["lengths_check"] == {
        "ok": True, "jobs": 3, "tokens": [108, 108],
        "rows_by_edge": {"8": [6, 6], "16": [6, 6]},
    }
    # a family's pair terms read it: bert's pairs at 5, 3, 12 and 16
    config = {"hidden_size": 4, "intermediate_size": 8, "num_hidden_layers": 1}
    dense = (6 * 8 + 6 * 16) * (4 * 16 + 2 * 32)
    pairs = 3 * (5**2 + 3**2 + 12**2 + 16**2)
    assert bert_counts.forward_flops(config, work) == pytest.approx(2 * (dense + 2 * pairs * 4))


def test_the_measured_slots_go_with_the_work():
    work = driver.work(_state(), _delta(**{"moe.slots_held": 77}), _window())
    assert work["slots_held"] == 77
    assert "slots_held" not in driver.work(_state(), _delta(**{"moe.slots_held": 0}), _window())


def test_an_entry_that_cannot_tell_a_rows_length_gives_the_edges_alone():
    """A kind of entry without `row_length` (rows of numbers, images): as
    before this account existed, and nothing is said to be unknown."""
    work = driver.work(_state(row_length=None, rows=None), _delta(), _window())
    assert work == {"rows": 12, "rows_by_length": {"8": 6, "16": 6}}


def _raises(text):
    raise ValueError("no tokenizer here")


@pytest.mark.parametrize(
    "state, delta, said",
    [
        # one token more than the rows have: a counter, or a tokenizer, that moved
        (_state(), _delta(**{"text.tokens": 109}), "text.tokens [108, 109]"),
        # a row routed to another edge than its length says
        (
            _state(),
            _delta(**{"text.bucket_rows.8": 3, "text.bucket_rows.16": 9}),
            "text.bucket_rows {'8': [6, 3], '16': [6, 9]}",
        ),
        # a job more than the window saw
        (_state(), _delta(jobs=4), "text.tokens [108, 144]"),
        # a program that buckets nothing, or counts none of it
        (
            _state(),
            {k: v for k, v in _delta().items() if "bucket_rows" not in k},
            "counted no text.bucket_rows",
        ),
        (_state(row_length=_raises), _delta(), "row_length raised ValueError('no tokenizer here')"),
    ],
    ids=["tokens", "edges", "jobs", "no-buckets", "raises"],
)
def test_a_disagreement_leaves_no_real_length_and_says_why(state, delta, said):
    work = driver.work(state, delta, _window())
    assert "lengths_by_edge" not in work
    assert said in work["pairs_unknown"]
    check = work["lengths_check"]
    assert check["ok"] is False and check["why"] == work["pairs_unknown"]
    # no family counts a pair term from it
    config = {"hidden_size": 4, "intermediate_size": 8, "num_hidden_layers": 1,
              "param_dtype": "float32"}
    assert bert_counts.forward_flops(config, work) is None
    assert bert_counts.kernel_work(config, "flash_attention", work) is None


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        return json.load(f)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
KERNEL = ' = f32[8] custom-call(f32[8] %x), custom_call_target="tpu_custom_call"'


def _ctx(config, counts, work, ops, counters=None):
    trace = SimpleNamespace(window_s=50.0, op_s=ops)
    trace.kernel_s = lambda part: sum(s for n, s in ops.items() if part in n)
    return {
        "trace": trace,
        "cell": SimpleNamespace(config=config, traffic={"batch_rows": 1}),
        "work": work,
        "counts": counts,
        "peaks": PEAKS,
        "chips": 1,
        "counters": counters or {},
    }


PAIR_READERS = ["step_mfu", "mla_attention_roofline", "dsa_indexer_roofline"]


@pytest.mark.parametrize("name", PAIR_READERS)
def test_pair_readers_read_real_lengths_and_fall_silent_without_them(name):
    """The long-documents cell's family: a row of 2,690 tokens at the
    8,192 edge. With its real length a reader divides the pairs of 2,690
    by the kernel's seconds; with the accounts in disagreement, nothing."""
    config = _config("deepseek-v3.2-exp")
    ops = {"%flash_attention.5" + KERNEL: 2.0, "%dsa_index_scores.2" + KERNEL: 1.0}
    at_edge = {"rows": 1, "rows_by_length": {"8192": 1}, "slots_held": 0}
    real = dict(at_edge, lengths_by_edge={"8192": {2690: 1}})
    read = load_reader(name)
    got, edge = read(_ctx(config, v32_counts, real, ops)), read(_ctx(config, v32_counts, at_edge, ops))
    value = lambda v: v["value"] if isinstance(v, dict) else v  # noqa: E731
    assert 0 < value(got) < value(edge) < 100
    if name == "mla_attention_roofline":
        pairs = 2048 * 2049 // 2 + 642 * 2048
        assert edge["bound_by"] == "operations"
        # so short a row in so wide a bucket: its 8,192 tokens' bytes (and
        # a byte of selection a causal pair of the 2,690) outlast its pairs
        t_flops = 5 * pairs * 320 * 128 * 2 / 197e12
        t_bytes = 5 * (8192 * 128 * 640 * 2 + 2690 * 2691 // 2) / 819e9
        assert got["bound_by"] == "bytes" and t_bytes > t_flops
        assert got["value"] == pytest.approx(100 * t_bytes / 2.0)
    if name == "dsa_indexer_roofline":
        assert got["value"] == pytest.approx(
            100 * 5 * (2690 * 2691 // 2) * 64 * 128 * 2 / 197e12 / 1.0
        )
    if name == "step_mfu":
        assert got == pytest.approx(100 * v32_counts.forward_flops(config, real) / (50 * 197e12))
    unknown = dict(at_edge, pairs_unknown="text.tokens [1, 2]")
    assert read(_ctx(config, v32_counts, unknown, ops)) is None


def test_readers_without_a_pair_term_read_on():
    """The scan and the grouped product grow with tokens and slots."""
    config = _config("jamba2-3b")
    ops = {"%selective_scan.3" + KERNEL: 1.0}
    work = {"rows": 1, "rows_by_length": {"2048": 1}, "pairs_unknown": "text.tokens [1, 2]"}
    got = load_reader("selective_scan_roofline")(_ctx(config, jamba_counts, work, ops))
    # h, dt, z at 4 bytes, y at 2, B and C at 4: by its bytes
    assert got["bound_by"] == "bytes"
    assert got["value"] == pytest.approx(
        100 * 2048 * 26 * (5120 * 14 + 2 * 16 * 4) / 819e9 / 1.0
    )
    assert load_reader("step_mfu")(_ctx(config, jamba_counts, work, ops)) is None
    config = _config("deepseek-v3.2-exp")
    ops = {"%moe_grouped_matmul.1" + KERNEL: 1.0}
    work = {"rows": 1, "rows_by_length": {"8192": 1}, "pairs_unknown": "x"}
    counters = {"moe.slots_held": 5000, "feeder.rows": 1}
    got = load_reader("moe_grouped_matmul_roofline")(_ctx(config, v32_counts, work, ops, counters))
    assert got["value"] > 0


def test_step_mfu_counts_the_routed_experts_at_the_slots_the_driver_measured():
    config = _config("deepseek-v3.2-exp")
    expected = {"rows": 1, "rows_by_length": {"8192": 1}}
    read = load_reader("step_mfu")
    at_expectation = read(_ctx(config, v32_counts, expected, {}))
    # 8,192 tokens x 8 slots x 8 of 256 experts x 4 layers, if spread evenly
    assert v32_counts.slots_held(config, expected) == 8192 * 4 / 4
    measured = read(_ctx(config, v32_counts, dict(expected, slots_held=4096), {}))
    expert = 3 * 7168 * 2048
    assert (at_expectation - measured) * 50 * 197e12 / 100 == pytest.approx(
        4096 * 2 * expert, rel=1e-9
    )


# -- a whole run, the program's count of its tokens falsified --------------------


def _rehearse(monkeypatch, tmp_path, capsys, plant):
    monkeypatch.setattr(run, "WORK_DIR", str(tmp_path / "work"))
    for k in ("SPARKDL_TEXT_BUCKETS", "SPARKDL_TEXT_MIN_BUCKET"):
        monkeypatch.setenv(k, os.environ.get(k, ""))  # restored afterwards
    if plant:
        real, calls = driver.counters, []

        def counters(state):
            # one token more each time the registry is read: the window's
            # delta is one more than the rows have
            calls.append(1)
            got = real(state)
            got["text.tokens"] = got.get("text.tokens", 0) + len(calls)
            return got

        monkeypatch.setattr(driver, "counters", counters)
    args = argparse.Namespace(
        workload="bert-base-embed", seed=2**31 + 38, seconds=0.2, trace=1, rehearse_cpu=True
    )
    assert run.run(args) == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("plant", [False, True], ids=["as-it-is", "text.tokens-planted"])
def test_the_result_line_carries_the_cross_check(monkeypatch, tmp_path, capsys, plant):
    line, err = _rehearse(monkeypatch, tmp_path, capsys, plant)
    check = line["work_check"]
    assert list(line)[-2:] == ["work_check", "compared"]
    assert line["correct"] is True  # the answers are not what disagrees
    mine, counted = check["tokens"]
    assert check["jobs"] == line["jobs"] and mine == 22 * 102 * line["jobs"]
    assert check["rows_by_edge"] == {"128": [22 * line["jobs"]] * 2}
    # the numbers compared are still the last thing on standard error
    assert json.loads(err.strip().splitlines()[-1])["compared"] == line["compared"]
    if not plant:
        assert check["ok"] is True and counted == mine and "no pair term" not in err
        return
    assert check["ok"] is False and counted == mine + 1
    assert f"text.tokens [{mine}, {mine + 1}]" in check["why"]
    assert "benchmarks: no pair term is counted: " + check["why"] in err
