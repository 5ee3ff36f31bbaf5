"""Serving smoke: prove the online layer end-to-end on CPU, no chip or
model zoo required (mirrors tools/feeder_smoke.py).

Two phases over the REAL stack (ServingClient -> Router -> admission
queue -> feeder streams -> device dispatch):

1. **SLA + adaptive batching** (one model, no budget): a few sequential
   interactive singles prove the latency-mode short rung, then a burst
   of multi-row ``background`` requests with ``interactive`` singles
   arriving mid-drain proves class separation. Asserts:

   - interactive p95 < background p95 (``serve.latency.*`` timers) —
     strict priority + aging means the user-facing class never queues
     behind the backfill,
   - ``serve.batch_rows`` min == 1 (short batch at low depth) and
     max == full geometry (growth under load),
   - serving outputs row-identical to the OFFLINE path (the same rows
     through ``run_batched_shared`` with the same model).

2. **Residency** (two 2 MB models under a 3 MB
   ``SPARKDL_SERVE_HBM_BUDGET_MB``): serve A, then B, then A again.
   Asserts exactly 2 evictions (each load evicts the other, never while
   busy) and that the reloaded model's outputs still match the offline
   path bit-for-bit (the reload rebuilt identical params).

Exit 0 and a one-line JSON verdict on success; exit 1 naming what
failed.

Usage (also wired into tools/preflight.sh)::

    JAX_PLATFORMS=cpu python tools/serving_smoke.py
"""

import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# One device, round-robin: rung geometry == dispatched rows exactly, so
# the batch-size arithmetic below is platform-independent.
os.environ.setdefault("SPARKDL_INFERENCE_MODE", "roundrobin")
os.environ.setdefault("SPARKDL_INFERENCE_DEVICES", "1")
# The serving keepalive (satellite of the same PR): owner threads must
# not idle-exit between request bursts.
os.environ.setdefault("SPARKDL_FEEDER_IDLE_S", "0")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


ROW = 8
MAX_BATCH = 32
N_BACKGROUND = 128     # x BG_ROWS rows: the backlog the flood drains
BG_ROWS = 8
# Enough singles that the one compile-paying first sample falls OUTSIDE
# the p95 rank — the assertion compares steady-state queueing, not jit.
N_INTERACTIVE = 40


def _loader(name, mode):
    """Deterministic tiny models: 'small' for the latency phase, 2 MB
    'big_*' params for the residency phase (so a 3 MB budget fits one)."""
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.graph.function import ModelFunction

    width = 65536 if name.startswith("big") else 64
    rng = np.random.default_rng(abs(hash(name)) % 1000)
    w = jnp.asarray(
        rng.normal(size=(ROW, width)).astype(np.float32) / ROW
    )
    return ModelFunction(
        lambda p, x: jnp.tanh(x @ p), w, input_shape=(ROW,), name=name
    )


def _offline_outputs(name, rows_batch):
    """The batch pipeline's answer for the same rows: ``run_batched_shared``
    over the same ModelFunction — the parity oracle."""
    from sparkdl_tpu.transformers.execution import (
        arrays_to_batch,
        model_device_fn,
        run_batched_shared,
    )

    device_fn = model_device_fn(_loader(name, "features"))
    return run_batched_shared(
        list(rows_batch),
        arrays_to_batch,
        device_fn,
        batch_size=MAX_BATCH,
    )


def _p95_ms(cls):
    from sparkdl_tpu.utils.metrics import metrics

    stat = metrics.timing(f"serve.latency.{cls}")
    if stat is None or not stat.count:
        return None
    return stat.percentile(95) * 1e3


def _phase_sla(problems):
    import numpy as np

    from sparkdl_tpu.serving import Router, ServingClient
    from sparkdl_tpu.utils.metrics import metrics

    router = Router(loader=_loader, max_batch=MAX_BATCH)
    client = ServingClient(router)
    rng = np.random.default_rng(0)
    try:
        # -- latency mode: sequential singles at zero depth ----------------
        for i in range(3):
            x = rng.normal(size=(1, ROW)).astype(np.float32)
            client.predict("small", x, priority="interactive", timeout=120)

        # -- throughput mode: background flood + interactive mid-drain -----
        bg_inputs = [
            rng.normal(size=(BG_ROWS, ROW)).astype(np.float32)
            for _ in range(N_BACKGROUND)
        ]
        bg_reqs = [
            client.submit("small", x, priority="background")
            for x in bg_inputs
        ]
        int_reqs = []
        int_inputs = []
        for _ in range(N_INTERACTIVE):
            x = rng.normal(size=(1, ROW)).astype(np.float32)
            int_inputs.append(x)
            int_reqs.append(
                client.submit("small", x, priority="interactive")
            )
            time.sleep(0.002)  # spread arrivals across the drain window
        bg_out = [r.result(timeout=300) for r in bg_reqs]
        int_out = [r.result(timeout=300) for r in int_reqs]

        # class separation: the user-facing class must not queue behind
        # the backfill it shares the chip with
        p95_int, p95_bg = _p95_ms("interactive"), _p95_ms("background")
        if p95_int is None or p95_bg is None:
            problems.append("missing serve.latency.<class> timers")
        elif not p95_int < p95_bg:
            problems.append(
                f"interactive p95 {p95_int:.1f}ms not < background p95 "
                f"{p95_bg:.1f}ms (SLA classes not separating)"
            )

        # adaptive range: short rung at low depth, full geometry under load
        rows_stat = metrics.timing("serve.batch_rows")
        if rows_stat is None or not rows_stat.count:
            problems.append("no serve.batch_rows stats recorded")
        else:
            lo, hi = int(rows_stat.min_s), int(rows_stat.max_s)
            if lo != 1:
                problems.append(
                    f"adaptive batcher min rung {lo} != 1 (latency mode "
                    "never dispatched a short batch)"
                )
            if hi != MAX_BATCH:
                problems.append(
                    f"adaptive batcher max rung {hi} != {MAX_BATCH} "
                    "(throughput mode never reached full geometry)"
                )

        # parity vs the offline engine on the identical rows
        flat_inputs = [row for x in bg_inputs for row in x] + [
            x[0] for x in int_inputs
        ]
        served = [row for o in bg_out for row in o] + [
            o[0] for o in int_out
        ]
        expected = _offline_outputs("small", flat_inputs)
        for i, (got, want) in enumerate(zip(served, expected)):
            if not np.allclose(got, want, rtol=1e-5, atol=1e-5):
                problems.append(
                    f"serving/offline output mismatch at row {i}"
                )
                break
        return {
            "interactive_p95_ms": round(p95_int, 2) if p95_int else None,
            "background_p95_ms": round(p95_bg, 2) if p95_bg else None,
            "batch_rows_min": int(rows_stat.min_s) if rows_stat else None,
            "batch_rows_max": int(rows_stat.max_s) if rows_stat else None,
            "requests": int(metrics.counter("serve.admitted")),
        }
    finally:
        router.close()


def _phase_residency(problems):
    import numpy as np

    from sparkdl_tpu.serving import Router, ServingClient
    from sparkdl_tpu.utils.metrics import metrics

    # 2 MB models under a 3 MB budget: exactly one resident at a time.
    os.environ["SPARKDL_SERVE_HBM_BUDGET_MB"] = "3"
    router = Router(loader=_loader, max_batch=MAX_BATCH)
    client = ServingClient(router)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(4, ROW)).astype(np.float32)
    before = metrics.counter("serve.evictions")
    try:
        outs = {}
        for name in ("big_a", "big_b", "big_a"):
            outs[name] = client.predict(name, x, timeout=300)
        evictions = metrics.counter("serve.evictions") - before
        # A->B evicts idle A; B->A(reload) evicts idle B: exactly 2.
        if evictions != 2:
            problems.append(
                f"expected exactly 2 evictions under the 3 MB budget, "
                f"saw {evictions:.0f}"
            )
        # the reloaded model must still answer exactly like the offline
        # path (deterministic loader -> identical params after reload)
        for name in ("big_a", "big_b"):
            expected = np.stack(_offline_outputs(name, list(x)))
            if not np.allclose(
                outs[name], expected, rtol=1e-5, atol=1e-5
            ):
                problems.append(
                    f"post-eviction output mismatch for {name}"
                )
        return {"evictions": int(evictions)}
    finally:
        router.close()
        os.environ.pop("SPARKDL_SERVE_HBM_BUDGET_MB", None)


def _serving_threads():
    """ALL live 'sparkdl-*' threads — the serve/feeder-only prefix list
    used to miss the H2D staging pool the offline parity oracle spins
    up (the oracle's feeder stages batches too)."""
    return [
        t
        for t in threading.enumerate()
        if t.is_alive() and t.name.startswith("sparkdl-")
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.parse_args(argv)

    problems = []
    sla = _phase_sla(problems)
    residency = _phase_residency(problems)

    # router.close() joins the dispatcher, drains the completion pool,
    # and unloads every model (closing its feeders); shutdown_feeders
    # also stops the H2D pools the offline oracle used — survivors leak.
    from sparkdl_tpu.runtime.feeder import shutdown_feeders

    shutdown_feeders()
    leaked = _serving_threads()
    if leaked:
        time.sleep(0.5)
        leaked = _serving_threads()
    if leaked:
        problems.append(
            "leaked serving threads after close: "
            + ", ".join(t.name for t in leaked)
        )

    # Lock sanitizer epilogue (preflight runs this smoke with
    # SPARKDL_LOCK_SANITIZER=1): no observed cycle, and every observed
    # held-before edge implied by the static graph.
    lock_problems, lock_stats = _common.lock_sanitizer_problems()
    problems += lock_problems

    verdict = {
        "serving_smoke": "FAIL" if problems else "OK",
        **sla,
        **residency,
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
