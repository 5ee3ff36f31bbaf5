"""Mesh-serving smoke: prove data-parallel fan-out + precision rungs on
an emulated multi-chip CPU mesh, no hardware required (mirrors
tools/serving_smoke.py).

Forces ``--xla_force_host_platform_device_count=4`` so the REAL serving
stack (ServingClient -> Router -> admission -> mesh-sharded feeder
streams) runs 4-chip global-batch programs, then asserts the claims the
mesh/precision arms are allowed to make:

1. **Parity + exact accounting**: a 100-row request served at
   ``SPARKDL_SERVE_MESH_WIDTH=4`` is ROW-IDENTICAL to the width-1 arm
   (f32: same math, batch rows are independent), and the global-rung
   arithmetic is exact — per-chip rung 32, ONE 128-row global dispatch,
   28 pad rows, ``feeder.global_batches``/``serve.mesh.chip_rows``
   accounted to the row.
2. **Scaling**: under a mixed flood, aggregate throughput of the 4-chip
   arm is asserted > 1.5x the 1-chip arm — on this one-core host the
   win is the mesh shape itself (4x larger groups -> 4x fewer
   group-assembly/dispatch/drain passes per row), which is exactly the
   overhead a real pod amortizes, plus real parallel compute it adds on
   top.
3. **Precision rungs**: the same rows at ``bf16`` and ``int8-dynamic``
   match the f32 arm within tolerance (the output-parity gate every arm
   ships behind), per-arm ``serve.precision.<arm>.*`` metrics flow, and
   a per-class override (interactive=bf16, rest f32) loads TWO resident
   entries — precision is part of the residency key, not a global mode.

Plus the house epilogue: zero leaked ``sparkdl-*`` threads and (under
``SPARKDL_LOCK_SANITIZER=1``, as preflight runs it) a clean sanitizer
verdict.

Usage (also wired into tools/preflight.sh)::

    JAX_PLATFORMS=cpu python tools/mesh_smoke.py
"""

import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The emulated mesh: 4 CPU "chips". Must land before jax's backend
# initializes (same mechanism as tests/conftest.py).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()
# Serving keepalive + no batch-window nondeterminism in the accounting
# phase (the flood phase re-enables lingering via its own knob? no —
# the window only ever ADDS coalescing; accounting uses sequential
# requests where the queue is empty, so the window never engages).
os.environ.setdefault("SPARKDL_FEEDER_IDLE_S", "0")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


ROW = 8
MAX_BATCH = 32
WIDTH = 4
N_FLOOD = 384
FLOOD_ROWS = 8
SPEEDUP_FLOOR = 1.5


def _loader(name, mode):
    """Deterministic tiny MLP — per-dispatch overhead dominates compute,
    so the flood phase measures the serving machinery the mesh arm
    amortizes, not matmul wall time this one-core host can't parallelize."""
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.graph.function import ModelFunction

    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(ROW, 64)).astype(np.float32) / 8)
    return ModelFunction(
        lambda p, x: jnp.tanh(x @ p), w, input_shape=(ROW,), name=name
    )


def _counters(*names):
    from sparkdl_tpu.utils.metrics import metrics

    return {n: metrics.counter(n) for n in names}


def _deltas(before):
    after = _counters(*before)
    return {n: after[n] - before[n] for n in before}


def _with_router(width, fn, precision=None, per_class=None):
    """Run ``fn(client, router)`` under one router at ``width`` (and an
    optional precision arm), tearing the router down after — each arm
    is its own serving process in miniature."""
    from sparkdl_tpu.serving import Router, ServingClient

    os.environ["SPARKDL_SERVE_MESH_WIDTH"] = str(width)
    if precision is not None:
        os.environ["SPARKDL_SERVE_PRECISION"] = precision
    for cls, p in (per_class or {}).items():
        os.environ[f"SPARKDL_SERVE_PRECISION_{cls.upper()}"] = p
    router = Router(loader=_loader, max_batch=MAX_BATCH)
    client = ServingClient(router)
    try:
        return fn(client, router)
    finally:
        router.close()
        os.environ.pop("SPARKDL_SERVE_PRECISION", None)
        for cls in per_class or {}:
            os.environ.pop(f"SPARKDL_SERVE_PRECISION_{cls.upper()}", None)


def _phase_parity_accounting(problems):
    """Width-4 vs width-1 on the same 100 rows: identical answers,
    exact global-rung arithmetic."""
    import numpy as np

    rows = np.random.default_rng(0).normal(size=(100, ROW)).astype(
        np.float32
    )
    tracked = (
        "serve.dispatches",
        "serve.pad_rows",
        "serve.mesh.chip_rows",
        "feeder.global_batches",
        "transfer.stage_hits",
        "transfer.stage_misses",
    )

    def serve(client, router):
        client.predict("mesh_model", rows[:4], timeout=120)  # warm/compile
        before = _counters(*tracked)
        out = client.predict("mesh_model", rows, timeout=120)
        return out, _deltas(before), router.stats()

    out1, d1, _ = _with_router(1, serve)
    out4, d4, stats4 = _with_router(WIDTH, serve)

    if not np.array_equal(np.asarray(out1), np.asarray(out4)):
        problems.append(
            "width-4 f32 output not row-identical to the width-1 arm"
        )
    # 100 rows, cap 32/chip: width 1 -> rung 32, 4 batches, 28 pad;
    # width 4 -> per-chip 25 -> rung 32 -> ONE 128-row global batch,
    # same 28 pad. Exact or the rung math regressed.
    expect = {
        1: {"serve.dispatches": 4, "serve.pad_rows": 28,
            "serve.mesh.chip_rows": 0, "feeder.global_batches": 0},
        WIDTH: {"serve.dispatches": 1, "serve.pad_rows": 28,
                "serve.mesh.chip_rows": 32, "feeder.global_batches": 1},
    }
    for width, deltas in ((1, d1), (WIDTH, d4)):
        for name, want in expect[width].items():
            got = int(deltas[name])
            if got != want:
                problems.append(
                    f"width-{width} accounting: {name} delta {got} != "
                    f"{want}"
                )
    # The global batch's H2D must have gone through the staged
    # NamedSharding pre-place hook (stage_put), not an in-dispatch copy.
    staged4 = d4["transfer.stage_hits"] + d4["transfer.stage_misses"]
    if staged4 < 1:
        problems.append(
            "width-4 dispatch never used the staged NamedSharding "
            "pre-place hook (transfer.stage_* flat)"
        )
    mesh_stats = stats4.get("mesh") or {}
    if mesh_stats.get("width") != WIDTH:
        problems.append(
            f"router stats mesh width {mesh_stats.get('width')} != {WIDTH}"
        )
    return {
        "parity_rows": len(out1),
        "w4_dispatches": int(d4["serve.dispatches"]),
        "w4_pad_rows": int(d4["serve.pad_rows"]),
        "w4_chip_rows": int(d4["serve.mesh.chip_rows"]),
        "global_batches": int(d4["feeder.global_batches"]),
    }


def _flood_rows_per_sec(client, router):
    import numpy as np

    payloads = [
        np.random.default_rng(i).normal(size=(FLOOD_ROWS, ROW)).astype(
            np.float32
        )
        for i in range(N_FLOOD)
    ]
    # warm flood: every rung geometry + the feeder/completion pools pay
    # their first-use costs outside the clock
    warm = [
        client.submit("mesh_model", p, priority="background")
        for p in payloads[:64]
    ]
    for r in warm:
        r.result(timeout=120)
    t0 = time.perf_counter()
    reqs = [
        client.submit("mesh_model", p, priority="background")
        for p in payloads
    ]
    for r in reqs:
        r.result(timeout=300)
    wall = time.perf_counter() - t0
    return N_FLOOD * FLOOD_ROWS / wall


def _phase_scaling(problems):
    """Aggregate flood throughput: the 4-chip arm must clear 1.5x the
    1-chip arm. Best of two trials per arm — the claim is about the
    architecture, not one trial's scheduler jitter."""
    r1 = max(_with_router(1, _flood_rows_per_sec) for _ in range(2))
    r4 = max(_with_router(WIDTH, _flood_rows_per_sec) for _ in range(2))
    speedup = r4 / r1 if r1 else 0.0
    if speedup < SPEEDUP_FLOOR:
        problems.append(
            f"4-chip aggregate throughput only {speedup:.2f}x the "
            f"1-chip arm (< {SPEEDUP_FLOOR}x): {r4:.0f} vs {r1:.0f} "
            "rows/s"
        )
    return {
        "w1_rows_per_sec": round(r1),
        "w4_rows_per_sec": round(r4),
        "speedup": round(speedup, 2),
    }


def _phase_precision(problems):
    """bf16 / int8-dynamic rungs on the mesh: within tolerance of f32,
    per-arm metrics flowing, per-class override = two resident entries."""
    import numpy as np

    rows = np.random.default_rng(7).normal(size=(64, ROW)).astype(
        np.float32
    )

    def serve(client, router):
        return client.predict("mesh_model", rows, timeout=120)

    base = np.asarray(_with_router(WIDTH, serve))
    tol = {"bf16": 3e-2, "int8-dynamic": 5e-2}
    arm_counts = {}
    for precision in ("bf16", "int8-dynamic"):
        before = _counters(
            f"serve.precision.{precision}.requests",
            f"serve.precision.{precision}.rows",
        )
        got = np.asarray(
            _with_router(WIDTH, serve, precision=precision)
        )
        d = _deltas(before)
        arm_counts[precision] = int(
            d[f"serve.precision.{precision}.requests"]
        )
        if not np.allclose(
            got, base, rtol=tol[precision], atol=tol[precision]
        ):
            worst = float(np.max(np.abs(got - base)))
            problems.append(
                f"{precision} output outside tolerance of the f32 arm "
                f"(max abs delta {worst:.4f} > {tol[precision]})"
            )
        if d[f"serve.precision.{precision}.requests"] != 1:
            problems.append(
                f"serve.precision.{precision}.requests did not count "
                "the armed request"
            )
        if d[f"serve.precision.{precision}.rows"] != len(rows):
            problems.append(
                f"serve.precision.{precision}.rows miscounted the "
                "armed rows"
            )

    # per-class override: interactive rides bf16 while background stays
    # f32 — two residency entries (precision is part of the key)
    def mixed(client, router):
        client.predict(
            "mesh_model", rows[:8], priority="interactive", timeout=120
        )
        client.predict(
            "mesh_model", rows[:8], priority="background", timeout=120
        )
        return router.residency.models()

    entries = _with_router(
        WIDTH, mixed, per_class={"interactive": "bf16"}
    )
    precisions = sorted(m["precision"] for m in entries)
    if precisions != ["bf16", "f32"]:
        problems.append(
            "per-class precision override did not load distinct "
            f"residency entries (saw {precisions})"
        )
    return {"precision_requests": arm_counts,
            "mixed_entries": precisions}


def _leaked_threads():
    return [
        t
        for t in threading.enumerate()
        if t.is_alive() and t.name.startswith("sparkdl-")
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.parse_args(argv)

    import jax

    n_dev = len(jax.devices())
    if n_dev < WIDTH:
        print(
            json.dumps(
                {
                    "mesh_smoke": "FAIL",
                    "problems": [
                        f"only {n_dev} devices; the emulated mesh needs "
                        f">= {WIDTH} (XLA_FLAGS not applied?)"
                    ],
                }
            ),
            file=sys.stderr,
        )
        return 1

    problems = []
    accounting = _phase_parity_accounting(problems)
    scaling = _phase_scaling(problems)
    precision = _phase_precision(problems)

    from sparkdl_tpu.runtime.feeder import shutdown_feeders

    shutdown_feeders()
    leaked = _leaked_threads()
    if leaked:
        time.sleep(0.5)
        leaked = _leaked_threads()
    if leaked:
        problems.append(
            "leaked serving threads after close: "
            + ", ".join(t.name for t in leaked)
        )

    lock_problems, lock_stats = _common.lock_sanitizer_problems()
    problems += lock_problems

    verdict = {
        "mesh_smoke": "FAIL" if problems else "OK",
        "devices": n_dev,
        **accounting,
        **scaling,
        **precision,
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
