"""SQL optimizer smoke: prove the planner's vectorized arm end-to-end on
CPU, no chip or model zoo required (mirrors tools/feeder_smoke.py).

Floods one registered table with a mixed query workload — model-UDF
projection, metadata-only WHERE over a pruned scan, pushdown-then-UDF,
LIMIT — through the REAL engine (sql text -> planner -> Executor
partitions -> run_batched_shared -> DeviceFeeder), then checks from the
planner's own obs counters and a decode probe that the optimizer
actually engaged:

- ``sql.udf.batches`` < partition count: the UDF's rows crossed
  partition boundaries into shared coalesced device batches (8
  partitions funneling one feeder stream, not 8 private dispatch loops);
- the decode probe reads 0: a metadata WHERE over a pruned scan never
  touched the unreferenced element-lazy column;
- ``sql.pushdown.pruned_cols`` / ``sql.pushdown.skipped_rows`` moved;
- every query's rows are identical under ``SPARKDL_SQL_VECTORIZE=0``
  (the legacy row-path arm), Nones included;
- shutdown leaks no ``sparkdl-*`` thread (feeder owners, H2D pools,
  the default executor's worker pool).

With ``SPARKDL_LOCK_SANITIZER=1`` (how ``tools/preflight.sh`` runs this
smoke) the run also fails on any runtime-observed lock-order cycle or
on an observed held-before edge the static analyzer's graph does not
imply (``tools/lint/lockorder_check.py``).

Exit 0 and a one-line JSON verdict on success; exit 1 naming what failed.

Usage::

    JAX_PLATFORMS=cpu python tools/sql_smoke.py
"""

import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# One device, round-robin: batch geometry is platform-independent.
os.environ.setdefault("SPARKDL_INFERENCE_MODE", "roundrobin")
os.environ.setdefault("SPARKDL_INFERENCE_DEVICES", "1")
# Generous linger so partition streams coalesce even on a loaded 1-core
# CI box where partition threads start staggered.
os.environ.setdefault("SPARKDL_FEEDER_LINGER_MS", "200")
os.environ.setdefault("SPARKDL_SQL_VECTORIZE", "1")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


N_PARTITIONS = 8
ROWS_PER_PARTITION = 8
N_ROWS = N_PARTITIONS * ROWS_PER_PARTITION
#: bigger than one partition's rows: a full batch can only form by
#: packing rows across partitions, so the batch count proves coalescing
BATCH_SIZE = 32

UDF_NAME = "sql_smoke_sum"


class _ProbeCells(list):
    """Element reads counted — the stand-in for decoding one image."""

    reads = 0

    def __getitem__(self, i):
        if isinstance(i, int):
            _ProbeCells.reads += 1
        return list.__getitem__(self, i)


def _make_table():
    import numpy as np

    from sparkdl_tpu.dataframe import DataFrame

    rng = np.random.default_rng(7)
    parts = []
    k = 0
    for _ in range(N_PARTITIONS):
        parts.append(
            {
                "vec": [
                    rng.normal(size=(4,)).astype(np.float32)
                    if (k + i) % 11  # a few Nones ride through both arms
                    else None
                    for i in range(ROWS_PER_PARTITION)
                ],
                "label": [
                    "even" if (k + i) % 2 == 0 else "odd"
                    for i in range(ROWS_PER_PARTITION)
                ],
                "img": _ProbeCells(
                    f"payload-{k + i}" for i in range(ROWS_PER_PARTITION)
                ),
            }
        )
        k += ROWS_PER_PARTITION
    return DataFrame(parts, ["vec", "label", "img"])


#: the mixed flood: none reference img, so the probe must stay at 0
#: reads for the entire vectorized pass
QUERIES = (
    f"SELECT {UDF_NAME}(vec) AS s FROM t",
    "SELECT label FROM t WHERE label = 'even'",
    f"SELECT {UDF_NAME}(vec) AS s, label FROM t WHERE label = 'even'",
    "SELECT label FROM t WHERE label = 'odd' LIMIT 3",
)


def _engine_threads():
    """Live engine-owned threads by the house naming convention (see
    tools/feeder_smoke.py) — any survivor after shutdown is a leak."""
    return [
        t
        for t in threading.enumerate()
        if t.is_alive() and t.name.startswith("sparkdl-")
    ]


def _rows_as_data(rows):
    import numpy as np

    return [
        {
            k: (np.asarray(v).tolist() if isinstance(v, np.ndarray) else v)
            for k, v in r.items()
        }
        for r in rows
    ]


def _run_flood(ctx):
    """Run every query once; returns per-query row data."""
    return [_rows_as_data(ctx.sql(q).collect()) for q in QUERIES]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.parse_args(argv)

    from sparkdl_tpu import udf as udf_catalog
    from sparkdl_tpu.graph.ingest import ModelIngest
    from sparkdl_tpu.runtime.executor import (
        Executor,
        default_executor,
        set_default_executor,
    )
    from sparkdl_tpu.runtime.feeder import shutdown_feeders
    from sparkdl_tpu.sql import SQLContext
    from sparkdl_tpu.udf import registerModelUDF
    from sparkdl_tpu.utils.metrics import metrics

    # Concurrency is the point: coalescing only happens when >1
    # partition streams at once, and the default executor sizes its pool
    # to the (possibly 1-core CI) host — pin one wide enough for every
    # partition to feed simultaneously.
    set_default_executor(Executor(max_workers=N_PARTITIONS))

    mf = ModelIngest.from_callable(
        lambda x: x.reshape(x.shape[0], -1).sum(axis=1, keepdims=True),
        input_shape=(4,),
    )
    registerModelUDF(UDF_NAME, mf, batch_size=BATCH_SIZE)

    problems = []
    try:
        ctx = SQLContext()
        ctx.registerDataFrameAsTable(_make_table(), "t")

        counter_keys = (
            "sql.udf.batches",
            "sql.udf.batch_rows",
            "sql.pushdown.pruned_cols",
            "sql.pushdown.skipped_rows",
        )
        before = {k: metrics.counter(k) for k in counter_keys}
        _ProbeCells.reads = 0
        vec_out = _run_flood(ctx)
        deltas = {
            k: metrics.counter(k) - v for k, v in before.items()
        }
        probe_reads = _ProbeCells.reads

        # legacy arm: same queries, knob off — answers must match
        os.environ["SPARKDL_SQL_VECTORIZE"] = "0"
        try:
            legacy_out = _run_flood(ctx)
        finally:
            os.environ["SPARKDL_SQL_VECTORIZE"] = "1"

        if not deltas["sql.udf.batches"]:
            problems.append("vectorized UDF dispatch never engaged "
                            "(no sql.udf.batches)")
        elif deltas["sql.udf.batches"] >= 2 * N_PARTITIONS:
            # two UDF queries in the flood: each must have coalesced
            # across partitions, not dispatched one batch per partition
            problems.append(
                f"{deltas['sql.udf.batches']:.0f} device batches for 2 UDF "
                f"queries over {N_PARTITIONS} partitions — cross-partition "
                "coalescing not happening"
            )
        if probe_reads:
            problems.append(
                f"pruned scan decoded {probe_reads} probe cells (expected 0: "
                "no flood query references img)"
            )
        if not deltas["sql.pushdown.pruned_cols"]:
            problems.append("projection pushdown never pruned a column")
        # the two WHERE label='even' queries each pre-filter half the
        # table before anything expensive runs
        if deltas["sql.pushdown.skipped_rows"] < N_ROWS:
            problems.append(
                f"pushdown skipped {deltas['sql.pushdown.skipped_rows']:.0f} "
                f"rows < {N_ROWS} expected from the metadata WHEREs"
            )
        for q, a, b in zip(QUERIES, vec_out, legacy_out):
            if a != b:
                problems.append(f"arm parity mismatch for {q!r}")
                break
    finally:
        udf_catalog.unregister(UDF_NAME)
        shutdown_feeders()
        default_executor().close()

    leaked = _engine_threads()
    if leaked:
        time.sleep(0.5)  # close() joined already; allow OS-level teardown
        leaked = _engine_threads()
    if leaked:
        problems.append(
            "leaked engine threads after shutdown: "
            + ", ".join(t.name for t in leaked)
        )

    lock_problems, lock_stats = _common.lock_sanitizer_problems()
    problems += lock_problems

    verdict = {
        "sql_smoke": "FAIL" if problems else "OK",
        "udf_batches": int(deltas["sql.udf.batches"]),
        "udf_batch_rows": int(deltas["sql.udf.batch_rows"]),
        "pruned_cols": int(deltas["sql.pushdown.pruned_cols"]),
        "skipped_rows": int(deltas["sql.pushdown.skipped_rows"]),
        "probe_reads": int(probe_reads),
        **lock_stats,
    }
    if problems:
        verdict["problems"] = problems
        print(json.dumps(verdict), file=sys.stderr)
        return 1
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
