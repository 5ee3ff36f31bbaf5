"""Host↔device transfer microbenchmark — characterizes the H2D/D2H path
that feeds every transformer.

Run through the chip tool, in the same command as whatever else shares
the machine (one process holds the chip at a time):

    python tools/bench_transfer.py

Prints one JSON line per (direction, size) with MB/s, plus a dispatch
round-trip latency estimate, so the regime (bandwidth-bound vs
latency-bound) is identifiable at a glance. The first line names the
device it ran on.
"""

import json
import time

import numpy as np

import _common  # noqa: F401  (puts the repo root on sys.path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def bench_h2d(nbytes: int, reps: int = 5) -> float:
    x = np.random.default_rng(0).integers(
        0, 255, size=(nbytes,), dtype=np.uint8
    )
    dev = jax.devices()[0]
    jax.device_put(x[:1024], dev).block_until_ready()  # path warmup
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.device_put(x, dev).block_until_ready()
        times.append(time.perf_counter() - t0)
    return nbytes / min(times) / 1e6


def bench_d2h(nbytes: int, reps: int = 5) -> float:
    # One fresh device array per rep: a jax Array keeps its host copy
    # after the first np.asarray, so re-reading one array times a memcpy.
    dev = jax.devices()[0]
    ys = [
        jax.device_put(jnp.full((nbytes,), i, dtype=jnp.uint8), dev)
        for i in range(reps + 1)
    ]
    jax.block_until_ready(ys)
    np.asarray(ys.pop())  # path warmup
    times = []
    for y in ys:
        t0 = time.perf_counter()
        np.asarray(y)
        times.append(time.perf_counter() - t0)
    return nbytes / min(times) / 1e6


def bench_dispatch_rtt(reps: int = 20) -> float:
    """Round-trip of a tiny program: dispatch+readback latency floor."""
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), dtype=jnp.float32)
    f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        f(x).block_until_ready()
    return (time.perf_counter() - t0) / reps * 1000


def main() -> None:
    dev = jax.devices()[0]
    print(
        json.dumps(
            {
                "platform": dev.platform,
                "device_kind": dev.device_kind,
                "count": len(jax.devices()),
            }
        )
    )
    # 1..64 MB brackets every batch size the feed ships (a 128x224x224x3
    # uint8 batch is 19 MB) and the 4 MB chunk size feed_plan defaults to
    for mb in (1, 4, 8, 12, 16, 19, 32, 64):
        n = mb << 20
        print(json.dumps({"dir": "h2d", "mb": mb, "mbps": round(bench_h2d(n), 1)}), flush=True)
    for mb in (1, 19):
        n = mb << 20
        print(json.dumps({"dir": "d2h", "mb": mb, "mbps": round(bench_d2h(n), 1)}), flush=True)
    print(json.dumps({"dispatch_rtt_ms": round(bench_dispatch_rtt(), 2)}))


if __name__ == "__main__":
    main()
