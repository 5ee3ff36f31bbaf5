"""Obs smoke: run a tiny transform under tracing on CPU and print the
per-stage report table.

Proves the flight recorder end-to-end without a chip or a model zoo
compile: a small tensor-cell workload goes through the REAL batched
engine (``run_batched_shared`` + executor partitions + explicit
device_put), and the resulting snapshot must contain a non-empty
breakdown with the four canonical stages — ingest, h2d, dispatch, and
the drain stage, whose name is readback-arm dependent (``drain_wait`` under the async default,
``device_wait`` when ``SPARKDL_ASYNC_READBACK=0``). Exit 0 and the
rendered table on success; exit 1 naming the missing stages otherwise.

Usage (a CPU drill; tools/preflight.sh runs it too)::

    JAX_PLATFORMS=cpu python tools/obs_smoke.py [--out-dir DIR]

``--out-dir`` additionally writes ``obs_smoke_snapshot.json`` and
``obs_smoke_trace.json`` (chrome://tracing / Perfetto) there.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Round-robin over one device: the explicit-device_put dispatch path, so
# the smoke exercises a real h2d span on CPU (shard_map's implicit
# transfer happens inside the sharded jit and records no span there).
os.environ.setdefault("SPARKDL_INFERENCE_MODE", "roundrobin")
os.environ.setdefault("SPARKDL_INFERENCE_DEVICES", "1")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


#: The drain stage records as drain_wait (async-readback arm, default)
#: or device_wait (legacy synchronous arm) — either satisfies the smoke.
REQUIRED_STAGES = ("ingest", "h2d", "dispatch", ("drain_wait", "device_wait"))


def run_smoke():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu import obs
    from sparkdl_tpu.runtime.executor import Executor
    from sparkdl_tpu.transformers.execution import (
        arrays_to_batch,
        data_parallel_device_fn,
        run_batched_shared,
    )

    obs.get_recorder().clear()
    device_fn = data_parallel_device_fn(
        jax.jit(lambda b: jnp.tanh(b).sum(axis=1)),
        devices=[jax.devices()[0]],
    )
    rng = np.random.default_rng(0)
    parts = [
        [rng.normal(size=(8,)).astype(np.float32) for _ in range(10)]
        for _ in range(3)
    ]
    Executor(max_workers=2).map_partitions(
        lambda i, cells: run_batched_shared(
            cells, arrays_to_batch, device_fn, batch_size=4
        ),
        parts,
        count_rows=len,
    )
    return obs.snapshot()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--out-dir", default=None,
        help="also write the snapshot + chrome trace here",
    )
    args = ap.parse_args(argv)

    from sparkdl_tpu import obs
    from sparkdl_tpu.obs.report import render_report, stage_summary

    snap = run_smoke()
    summary = stage_summary(snap)
    missing = [
        "|".join(alts)
        for alts in (
            (s,) if isinstance(s, str) else s for s in REQUIRED_STAGES
        )
        if not any(summary.get(a, {}).get("n") for a in alts)
    ]
    print(render_report(snap))
    if args.out_dir:
        os.makedirs(args.out_dir, exist_ok=True)
        sp = obs.write_snapshot(
            os.path.join(args.out_dir, "obs_smoke_snapshot.json"), snap
        )
        tp = obs.write_chrome_trace(
            os.path.join(args.out_dir, "obs_smoke_trace.json"), snap
        )
        print(f"\nsnapshot: {sp}\ntrace:    {tp}")
    if missing:
        print(
            json.dumps({"obs_smoke": "FAIL", "missing_stages": missing}),
            file=sys.stderr,
        )
        return 1
    print(json.dumps({"obs_smoke": "OK", "stages": sorted(summary)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
