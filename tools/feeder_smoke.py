"""Feeder smoke: prove cross-partition continuous batching end-to-end on
CPU, no chip or model zoo required (mirrors tools/obs_smoke.py).

Runs the acceptance workload — 16 partitions x 100 rows at batch_size=32
through the REAL engine (Executor partitions -> run_batched_shared ->
DeviceFeeder -> device dispatch) — then checks, from the feeder's own
obs counters, that the shared stream actually coalesced:

- dispatched batches <= ceil(1600/32) + 1  (one tail flush, not 16),
- total pad rows <= batch_size             (not 16 padded tails),
- outputs equal the plain numpy answer (``tanh(row).sum()``) in every
  cell, Nones included,
- the ASYNC readback arm (``SPARKDL_ASYNC_READBACK=1``, the default:
  dispatch-time ``copy_to_host_async`` + drainer thread) is
  row-identical to the synchronous arm (``=0``), its hit/miss overlap
  counters account for the dispatched batches, and shutdown leaks no
  ``sparkdl-*`` thread at all — feeder owner, drainer, H2D copy pools
  AND the executor worker pool (``Executor.close``).

With ``SPARKDL_LOCK_SANITIZER=1`` (how ``tools/preflight.sh`` runs this
smoke) the run also fails on any runtime-observed lock-order cycle or
on an observed held-before edge the static analyzer's graph does not
imply (``tools/lint/lockorder_check.py``).

Exit 0 and a one-line JSON verdict on success; exit 1 naming what failed.

Usage (a CPU drill; tools/preflight.sh runs it too)::

    JAX_PLATFORMS=cpu python tools/feeder_smoke.py
"""

import argparse
import json
import math
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# One device, round-robin: dispatch size == batch_size exactly, so the
# batch-count arithmetic below is platform-independent.
os.environ.setdefault("SPARKDL_INFERENCE_MODE", "roundrobin")
os.environ.setdefault("SPARKDL_INFERENCE_DEVICES", "1")
# Generous linger: the smoke asserts a single tail flush even on a
# loaded 1-core CI box where partition threads start staggered.
os.environ.setdefault("SPARKDL_FEEDER_LINGER_MS", "200")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


N_PARTITIONS = 16
ROWS_PER_PARTITION = 100
BATCH_SIZE = 32

_COUNTER_KEYS = (
    "coalesced_batches",
    "pad_rows",
    "rows",
    "readback_async_hits",
    "readback_async_misses",
)


def _engine_threads():
    """Live engine-owned threads, by the house naming convention: ALL
    'sparkdl-*' threads, not just the feeder/h2d families — the leak
    check used to miss the executor's persistent worker pool entirely
    (three Executors per run, never closed). Every component the smoke
    touches has a shutdown path (shutdown_feeders covers the feeder
    owners/drainers and H2D pools, Executor.close the worker pool), so
    any survivor is a lifecycle bug."""
    return [
        t
        for t in threading.enumerate()
        if t.is_alive() and t.name.startswith("sparkdl-")
    ]


def _run(async_readback: bool = True):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.runtime.executor import Executor
    from sparkdl_tpu.runtime.feeder import shutdown_feeders
    from sparkdl_tpu.transformers.execution import (
        arrays_to_batch,
        data_parallel_device_fn,
        run_batched_shared,
    )
    from sparkdl_tpu.utils.metrics import metrics

    os.environ["SPARKDL_ASYNC_READBACK"] = "1" if async_readback else "0"
    device_fn = data_parallel_device_fn(
        jax.jit(lambda b: jnp.tanh(b).sum(axis=1, keepdims=True)),
        devices=[jax.devices()[0]],
    )
    rng = np.random.default_rng(0)
    parts = [
        [rng.normal(size=(8,)).astype(np.float32) for _ in range(ROWS_PER_PARTITION)]
        for _ in range(N_PARTITIONS)
    ]
    for part in parts:
        part[3] = None  # null rows ride through
    before = {k: metrics.counter(f"feeder.{k}") for k in _COUNTER_KEYS}
    executor = Executor(max_workers=N_PARTITIONS)
    try:
        out = executor.map_partitions(
            lambda i, cells: run_batched_shared(
                cells, arrays_to_batch, device_fn, batch_size=BATCH_SIZE
            ),
            parts,
            count_rows=len,
        )
    finally:
        counters = {
            k: metrics.counter(f"feeder.{k}") - v
            for k, v in before.items()
        }
        shutdown_feeders()
        executor.close()  # the worker pool is a leak the all-sparkdl-*
        # thread check below now sees
    return parts, out, counters


def _numpy_expectation(parts):
    """The plain reference: the device fn's arithmetic in numpy, cell by
    cell, with no engine in between."""
    import numpy as np

    return [
        [
            None if c is None else np.tanh(c).sum(keepdims=True)
            for c in part
        ]
        for part in parts
    ]


def _parity_problems(label, a_out, b_out, problems, equal=None):
    import numpy as np

    equal = equal or np.array_equal
    for p, (a_part, b_part) in enumerate(zip(a_out, b_out)):
        for i, (a, b) in enumerate(zip(a_part, b_part)):
            if (a is None) != (b is None) or (
                a is not None and not equal(a, b)
            ):
                problems.append(
                    f"{label} mismatch at partition {p} row {i}"
                )
                return


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.parse_args(argv)

    parts, shared_out, counters = _run(async_readback=True)
    _, sync_out, _sync_counters = _run(async_readback=False)

    problems = []
    total_valid = N_PARTITIONS * (ROWS_PER_PARTITION - 1)
    max_batches = math.ceil(N_PARTITIONS * ROWS_PER_PARTITION / BATCH_SIZE) + 1
    if not counters["coalesced_batches"]:
        problems.append("feeder never engaged (no coalesced batches)")
    elif counters["coalesced_batches"] > max_batches:
        problems.append(
            f"dispatched {counters['coalesced_batches']:.0f} batches > "
            f"{max_batches} (cross-partition packing not happening)"
        )
    if counters["pad_rows"] > BATCH_SIZE:
        problems.append(
            f"pad_rows {counters['pad_rows']:.0f} > batch_size {BATCH_SIZE} "
            "(more than one padded tail)"
        )
    if counters["rows"] != total_valid:
        problems.append(
            f"feeder.rows {counters['rows']:.0f} != {total_valid} valid rows"
        )
    # Async-arm attribution: every drained batch is a hit (copy landed
    # before the drain started) or a miss (residual wait); jitted CPU
    # results always expose is_ready, so the two must account for every
    # coalesced batch — and there must BE some, or the arm never engaged.
    attributed = (
        counters["readback_async_hits"] + counters["readback_async_misses"]
    )
    if not attributed:
        problems.append("async arm recorded no readback hit/miss counters")
    elif attributed > counters["coalesced_batches"]:
        problems.append(
            f"readback hit+miss {attributed:.0f} > coalesced batches "
            f"{counters['coalesced_batches']:.0f}"
        )
    # f32 sums in XLA and numpy may differ in the last bits: a tolerance
    import numpy as np

    _parity_problems(
        "feeder/numpy output", shared_out, _numpy_expectation(parts),
        problems, equal=lambda a, b: np.allclose(a, b, rtol=1e-5, atol=1e-6),
    )
    _parity_problems("async/sync arm output", shared_out, sync_out, problems)
    # shutdown_feeders() closed every feeder, close() joins the owner,
    # drainer and worker pool — ANY surviving sparkdl-* thread is a leak.
    leaked = _engine_threads()
    if leaked:
        time.sleep(0.5)  # close() joined already; allow OS-level teardown
        leaked = _engine_threads()
    if leaked:
        problems.append(
            "leaked engine threads after shutdown: "
            + ", ".join(t.name for t in leaked)
        )

    # Lock sanitizer epilogue (preflight runs this smoke with
    # SPARKDL_LOCK_SANITIZER=1): no observed cycle, and every observed
    # held-before edge implied by the static analyzer's graph.
    lock_problems, lock_stats = _common.lock_sanitizer_problems()
    problems += lock_problems

    verdict = {
        "feeder_smoke": "FAIL" if problems else "OK",
        "coalesced_batches": int(counters["coalesced_batches"]),
        "pad_rows": int(counters["pad_rows"]),
        "rows": int(counters["rows"]),
        "readback_async_hits": int(counters["readback_async_hits"]),
        "readback_async_misses": int(counters["readback_async_misses"]),
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
