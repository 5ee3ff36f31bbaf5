"""Resident-engine smoke: prove the device-side input half end-to-end
on CPU, no chip or model zoo required (mirrors tools/feeder_smoke.py).

Runs the real image path — ImageModelTransformer partitions ->
run_batched_shared -> DeviceFeeder -> staged H2D -> jitted program —
and checks, from the engine's own obs counters, that the resident arms
actually engaged and agree:

- **staging overlap**: with ``SPARKDL_DEVICE_STAGE=1`` (the default)
  the ``transfer.stage_hits``/``stage_misses`` pair accounts for every
  coalesced batch, and at least one hit proves a copy was in flight
  BEFORE dispatch needed it (the overlap the arm exists to create);
- **all-arm parity**: staged vs legacy transfer
  (``SPARKDL_DEVICE_STAGE=0``) and device-preproc vs host-preproc
  (``SPARKDL_DEVICE_PREPROC``, at identity geometry where the arms are
  bit-identical) all produce row-identical outputs, Nones included;
- **compile-cache attribution**: rebuilding the identical pipeline
  records ≥1 ``compile.cache_hits`` (the ledger that says the
  persistent cache will serve this executable on the next cold start);
- **no leaked threads**: after ``shutdown_feeders()`` no feeder owner,
  drainer, or H2D copy-pool thread survives.

Exit 0 and a one-line JSON verdict on success; exit 1 naming what
failed.

Usage (a CPU drill; tools/preflight.sh runs it too)::

    JAX_PLATFORMS=cpu python tools/resident_smoke.py
"""

import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# One device, round-robin: dispatch size == batch_size exactly, so the
# batch accounting below is platform-independent.
os.environ.setdefault("SPARKDL_INFERENCE_MODE", "roundrobin")
os.environ.setdefault("SPARKDL_INFERENCE_DEVICES", "1")
os.environ.setdefault("SPARKDL_FEEDER_LINGER_MS", "200")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


N_PARTITIONS = 6
ROWS_PER_PARTITION = 40
BATCH_SIZE = 8
GEOM = 8  # source == model geometry: preproc arms are bit-identical


def _engine_threads():
    return [
        t
        for t in threading.enumerate()
        if t.is_alive()
        and t.name.startswith(("sparkdl-feeder", "sparkdl-h2d"))
    ]


def _structs(n, seed=0):
    import numpy as np

    from sparkdl_tpu.image import imageIO

    rng = np.random.default_rng(seed)
    out = [
        imageIO.imageArrayToStruct(
            rng.integers(0, 256, size=(GEOM, GEOM, 3), dtype=np.uint8)
        )
        for _ in range(n)
    ]
    out[3] = None  # null rows ride through on every arm
    return out


def _transformer():
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.transformers.image_model import ImageModelTransformer

    mf = ModelFunction(
        fn=lambda p, x: x.mean(axis=(1, 2)),
        params=None,
        input_shape=(GEOM, GEOM, 3),
        name="resident_smoke_meanpool",
    )
    return ImageModelTransformer(
        inputCol="image",
        outputCol="f",
        modelFunction=mf,
        targetHeight=GEOM,
        targetWidth=GEOM,
        preprocessing="tf",
        batchSize=BATCH_SIZE,
    )


def _run(device_stage: bool, device_preproc: bool = False):
    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.runtime.feeder import shutdown_feeders
    from sparkdl_tpu.utils.metrics import metrics

    os.environ["SPARKDL_DEVICE_STAGE"] = "1" if device_stage else "0"
    os.environ["SPARKDL_DEVICE_PREPROC"] = "1" if device_preproc else "0"
    keys = ("transfer.stage_hits", "transfer.stage_misses",
            "feeder.coalesced_batches")
    before = {k: metrics.counter(k) for k in keys}
    df = DataFrame.fromColumns(
        {
            "image": [
                s
                for p in range(N_PARTITIONS)
                for s in _structs(ROWS_PER_PARTITION, seed=p)
            ]
        },
        numPartitions=N_PARTITIONS,
    )
    rows = [r.f for r in _transformer().transform(df).collect()]
    counters = {k: metrics.counter(k) - v for k, v in before.items()}
    shutdown_feeders()
    return rows, counters


def _parity(label, a_rows, b_rows, problems):
    import numpy as np

    for i, (a, b) in enumerate(zip(a_rows, b_rows)):
        if (a is None) != (b is None) or (
            a is not None and not np.array_equal(a, b)
        ):
            problems.append(f"{label} mismatch at row {i}")
            return


def _compile_cache_hits() -> int:
    """Build the identical pipeline twice (fresh transformer objects, so
    nothing short-circuits in an object-level cache): whatever the first
    build was, the second must record a ledger hit under the compile
    cache directory in force (runtime/compile_cache.py)."""
    from sparkdl_tpu.utils.metrics import metrics

    before = metrics.counter("compile.cache_hits")
    for _ in range(2):
        xf = _transformer()
        xf._build_device_fn((BATCH_SIZE, GEOM, GEOM, 3))
    return int(metrics.counter("compile.cache_hits") - before)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.parse_args(argv)

    # A concurrent executor even on a 1-core CI box: with sequential
    # partitions the feeder (correctly) stands down and nothing here
    # would measure staging.
    from sparkdl_tpu.runtime.executor import Executor, set_default_executor

    set_default_executor(Executor(max_workers=N_PARTITIONS))

    staged_rows, staged = _run(device_stage=True)
    legacy_rows, legacy = _run(device_stage=False)
    preproc_rows, _ = _run(device_stage=True, device_preproc=True)
    os.environ["SPARKDL_DEVICE_PREPROC"] = "0"

    problems = []
    attributed = staged["transfer.stage_hits"] + staged["transfer.stage_misses"]
    if not staged["feeder.coalesced_batches"]:
        problems.append("feeder never engaged (no coalesced batches)")
    if not attributed:
        problems.append("staged arm recorded no stage hit/miss counters")
    elif attributed != staged["feeder.coalesced_batches"]:
        problems.append(
            f"stage hit+miss {attributed:.0f} != coalesced batches "
            f"{staged['feeder.coalesced_batches']:.0f}"
        )
    if not staged["transfer.stage_hits"]:
        problems.append(
            "no stage_hits: no H2D copy ever landed before dispatch "
            "needed it (staging overlap not happening)"
        )
    if legacy["transfer.stage_hits"] or legacy["transfer.stage_misses"]:
        problems.append("legacy arm moved the staging counters")
    _parity("staged/legacy output", staged_rows, legacy_rows, problems)
    _parity("device/host preproc output", preproc_rows, legacy_rows, problems)

    hits = _compile_cache_hits()
    if hits < 1:
        problems.append(
            f"compile cache recorded {hits} hits after an identical rebuild"
        )

    leaked = _engine_threads()
    if leaked:
        time.sleep(0.5)  # shutdown joined already; allow OS teardown
        leaked = _engine_threads()
    if leaked:
        problems.append(
            "leaked engine threads after shutdown: "
            + ", ".join(t.name for t in leaked)
        )

    verdict = {
        "resident_smoke": "FAIL" if problems else "OK",
        "coalesced_batches": int(staged["feeder.coalesced_batches"]),
        "stage_hits": int(staged["transfer.stage_hits"]),
        "stage_misses": int(staged["transfer.stage_misses"]),
        "compile_cache_hits": hits,
    }
    if problems:
        verdict["problems"] = problems
        print(json.dumps(verdict), file=sys.stderr)
        return 1
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
