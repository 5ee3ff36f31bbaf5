"""`mla.query_blocks_run_pct`: the reader by hand, the counters it
divides as the program counts them for a job of each cell that runs the
latent kernel, and its entry in BENCHMARK.json (PR 38; the reader is PR
37's)."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.run import load_reader  # noqa: E402

NAME = "mla.query_blocks_run_pct"


def _read(counters):
    return load_reader(NAME)({"counters": counters, "trace": None})


@pytest.mark.parametrize(
    "blocks, run, share",
    [
        (600, 435, 72.5),  # the long-documents cell's job: 87 of 120 a layer
        (640, 555, 86.71875),  # the windows cell's: 111 of 128 a layer
        (120, 120, 100.0),  # an attention that takes no lengths runs them all
        (3, 1, 100.0 / 3),
    ],
)
def test_the_share_is_the_two_counters_quotient(blocks, run, share):
    got = _read({"mla.query_blocks": blocks, "mla.query_blocks_run": run, "text.tokens": 9})
    assert got == pytest.approx(share, rel=1e-12)


@pytest.mark.parametrize(
    "counters",
    [
        {},  # the parent of the PR that brought the counters; a family without the kernel
        {"mla.query_blocks": 600},
        {"mla.query_blocks_run": 435},
        {"mla.query_blocks": 0, "mla.query_blocks_run": 0},  # a window that dispatched nothing
        {"mla.query_blocks": 600, "mla.query_blocks_run": 0},
        {"mla.pairs_computed": 10**12, "mla.attention_tokens": 10**6},
    ],
    ids=["none", "no-run", "no-blocks", "zeros", "zero-run", "others"],
)
def test_the_reader_reports_nothing_without_both_counters(counters):
    assert _read(counters) is None


def _job(config, traffic):
    """One job's dispatches as ids: the live rows' token counts are the
    traffic file's quantiles (tokens = words + 2, as `benchmarks.data.
    texts` draws them), each in the first of the configuration's buckets
    that holds it, `batch_rows` rows a dispatch, the last of a bucket
    filled with rows of zeros."""
    from benchmarks.data import texts

    with open(os.path.join(ROOT, "benchmarks", "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs", config + ".json")) as f:
        edges = [int(e) for e in json.load(f)["env"]["SPARKDL_TEXT_BUCKETS"].split(",")]
    data, batch = mix["data"], mix["batch_rows"]
    tokens = texts.word_counts(data["rows"] - data["null_rows"], data["word_counts"]) + 2
    for low, edge in zip([0] + edges, edges):
        mine = [int(n) for n in tokens if low < n <= edge]
        mine += [0] * (-len(mine) % batch)
        for start in range(0, len(mine), batch):
            ids = np.zeros((batch, edge), np.int32)
            for row, n in enumerate(mine[start:start + batch]):
                ids[row, :n] = 5
            yield ids


@pytest.mark.parametrize(
    "config, traffic, blocks, run",
    [
        ("deepseek-v3.2-exp", "embed-long-docs", 120, 87),  # the claimed cell: 72.5
        # 12 of its 72 places are rows of zeros: 86.7
        ("deepseek-v2", "embed-windows", 128, 111),
    ],
)
def test_a_job_of_each_cell_counts_what_the_issue_counted(config, traffic, blocks, run):
    """The program's own counting (`attention_batch_counters`, 5 layers,
    blocks of 1,024) over a job's dispatches, through the reader."""
    from sparkdl_tpu.models.deepseek_v2 import attention_batch_counters
    from sparkdl_tpu.ops.flash_attention import make_latent_attention_fn

    kernel = make_latent_attention_fn(128, 0.1, block=1024, interpret=True)
    total = {}
    for ids in _job(config, traffic):
        for name, count in attention_batch_counters(kernel, 5, ids, ids != 0).items():
            total[name] = total.get(name, 0) + count
    assert total["mla.query_blocks"] == 5 * blocks
    assert total["mla.query_blocks_run"] == 5 * run
    assert _read(total) == pytest.approx(100.0 * run / blocks)


def test_the_entry_lists_the_cells_that_run_the_latent_kernel():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1
    entry = dict(entries[0])
    # a later cell that runs the kernel may join; these two stay
    assert set(entry.pop("workloads")) >= {
        "deepseek-v2-embed-windows", "deepseek-v3.2-exp-embed-long-docs",
    }
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "Kernel", "moves": "rows_per_s",
    }
