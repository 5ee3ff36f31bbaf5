"""Host->device transfer strategies for the feed path.

``chunked_device_put`` ships a flat host buffer as chunks of at most
``chunk_bytes`` and concatenates them on device. The strategies differ
in how many synchronous client calls a multi-chunk transfer costs:

- ``serial``   — one ``device_put`` per chunk, issued sequentially.
- ``onecall``  — ONE ``jax.device_put`` of the list of chunk views;
                 the backend sees a single transfer request batch.
- ``threads``  — concurrent puts from a small thread pool.

All three produce the identical device value (the concatenated 1-D
buffer). The mode is selected by ``SPARKDL_H2D_CHUNK_MODE`` (default
``serial``). Whether chunking, or any mode of it, pays on the attached
chip is not measured; ``tools/bench_transfer.py`` takes the H2D curve
that decides it.

Device-side input staging (the H2D half of the resident engine): with
``SPARKDL_DEVICE_STAGE`` on (the default), the feeder hands each packed
batch to :func:`stage_batch` the moment it is full — the device fn's
transfer half (``device_fn.stage_put``) runs on a dedicated copy pool,
so batch N+1's H2D copy is already in flight into its own device-side
staging slot while batch N computes, and the dispatch call itself never
waits on a transfer. ``transfer.stage_hits`` / ``.stage_misses`` count
whether the staged copy had already landed when dispatch claimed the
slot (the overlap the arm exists to create). ``0``/``off`` restores the
legacy transfer-inside-dispatch arm for A/B, matching the
``SPARKDL_ASYNC_READBACK`` house style. ``SPARKDL_DEVICE_STAGE_DEPTH``
(default 2) bounds how many staged copies ride ahead of dispatch.

Reference parity note: the upstream stack left transfer scheduling to
TensorFrames/libtensorflow (SURVEY.md section 3.1); this module is the
TPU-native replacement for that native feed path.
"""

from __future__ import annotations

import concurrent.futures as _futures
import threading
from typing import Any, Callable, Optional, Sequence

import numpy as np

from sparkdl_tpu.obs import span
from sparkdl_tpu.runtime import knobs, locksmith
from sparkdl_tpu.utils.metrics import metrics

_VALID_MODES = ("serial", "onecall", "threads")


def chunk_mode() -> str:
    mode = knobs.get_str("SPARKDL_H2D_CHUNK_MODE")
    if mode not in _VALID_MODES:
        raise ValueError(
            f"SPARKDL_H2D_CHUNK_MODE={mode!r}: expected one of {_VALID_MODES}"
        )
    return mode


_POOL: Optional[_futures.ThreadPoolExecutor] = None
_STAGE_POOL: Optional[_futures.ThreadPoolExecutor] = None
_POOL_LOCK = locksmith.lock("sparkdl_tpu/runtime/transfer.py::_POOL_LOCK")


def _pool() -> _futures.ThreadPoolExecutor:
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = _futures.ThreadPoolExecutor(
                max_workers=knobs.get_int("SPARKDL_H2D_THREADS"),
                thread_name_prefix="sparkdl-h2d",
            )
        return _POOL


def _stage_pool() -> _futures.ThreadPoolExecutor:
    """The staging copy pool is SEPARATE from the chunk-put pool: a
    staged transfer in 'threads' chunk mode fans its puts into _pool()
    and blocks on them — sharing one pool would let outer stage tasks
    occupy every worker while waiting on their own inner puts."""
    global _STAGE_POOL
    with _POOL_LOCK:
        if _STAGE_POOL is None:
            _STAGE_POOL = _futures.ThreadPoolExecutor(
                max_workers=knobs.get_int("SPARKDL_DEVICE_STAGE_THREADS"),
                thread_name_prefix="sparkdl-h2d-stage",
            )
        return _STAGE_POOL


def shutdown_transfer_pool() -> None:
    """Shut down the module-global H2D pools (chunk puts + staging).
    Idempotent; the pools are re-created lazily on next use, so callers
    mid-stream elsewhere just get a fresh pool for subsequent work
    (submissions race-safely retry on a fresh pool via ``_submit``).
    Called from ``feeder.shutdown_feeders()`` and ``Executor.close()``
    so process teardown (and the smokes' no-leaked-threads assertions)
    never strand a copy thread."""
    global _POOL, _STAGE_POOL
    with _POOL_LOCK:
        pools, _POOL, _STAGE_POOL = [_POOL, _STAGE_POOL], None, None
    for p in pools:
        if p is not None:
            p.shutdown(wait=True)


def _submit(pool_getter, fn, *args):
    """Submit to a module pool, tolerating a concurrent
    shutdown_transfer_pool: a pool that was shut down between the getter
    and the submit raises RuntimeError — drop it from the module slot
    and retry on the fresh pool the next getter call creates."""
    global _POOL, _STAGE_POOL
    for _ in range(2):
        pool = pool_getter()
        try:
            return pool.submit(fn, *args)
        except RuntimeError:
            with _POOL_LOCK:
                if _POOL is pool:
                    _POOL = None
                if _STAGE_POOL is pool:
                    _STAGE_POOL = None
    return pool_getter().submit(fn, *args)


# -- device-side input staging ------------------------------------------------


def device_stage_enabled() -> bool:
    """SPARKDL_DEVICE_STAGE gates double-buffered device-side input
    staging in the shared feeder (default ON; 0/off = the legacy
    transfer-inside-dispatch arm, for A/B)."""
    return knobs.get_flag("SPARKDL_DEVICE_STAGE")


def stage_depth() -> int:
    """How many staged H2D copies may ride ahead of dispatch (the size
    of the device-side staging slot ring). 2 = classic double
    buffering: one slot computing, one slot landing."""
    return max(1, knobs.get_int("SPARKDL_DEVICE_STAGE_DEPTH"))


class StagedBatch:
    """One device-side staging slot: the in-flight H2D copy of a packed
    batch, issued on the staging pool ahead of its dispatch.

    ``take()`` is called by the dispatcher when it actually needs the
    device value: a copy already complete counts ``transfer.stage_hits``
    (the overlap staging exists to create); one still in flight counts
    ``transfer.stage_misses`` and blocks only for the residual
    (``stage_wait`` span). ``settle()`` is the failure-path teardown —
    the host buffer behind the copy may not be reused until the pool
    task is done touching it."""

    __slots__ = ("_future", "rows")

    def __init__(self, future: "_futures.Future", rows: int = 0):
        self._future = future
        self.rows = rows

    def take(self):
        hit = self._future.done()
        metrics.inc(
            "transfer.stage_hits" if hit else "transfer.stage_misses"
        )
        with span("stage_wait", rows=self.rows, hit=hit):
            return self._future.result()

    def settle(self) -> None:
        """Cancel or wait out the staged copy without raising — after
        this returns, the pool no longer reads the host buffer."""
        if not self._future.cancel():
            try:
                self._future.result()
            except Exception:  # noqa: BLE001 — teardown must not raise
                pass


def stage_batch(
    stage_put: Callable[[np.ndarray], Any], batch: np.ndarray, rows: int = 0
) -> StagedBatch:
    """Issue ``stage_put(batch)`` (a device fn's transfer half) on the
    staging pool and return the slot. The caller keeps ownership of the
    host buffer until the slot's batch has drained — a device_put may
    alias it zero-copy."""
    return StagedBatch(_submit(_stage_pool, stage_put, batch), rows=rows)


def chunk_views(flat: np.ndarray, chunk_bytes: int) -> Sequence[np.ndarray]:
    """Split a 1-D host buffer into <=chunk_bytes contiguous views."""
    k = max(1, chunk_bytes // flat.itemsize)
    return [flat[i : i + k] for i in range(0, flat.size, k)]


def padded_chunk_views(flat: np.ndarray, chunk_bytes: int):
    """Split a 1-D buffer into EQUAL-length <=chunk_bytes views (the
    contract of ModelFunction.jitted_flat_parts: one compiled program
    per part count x part length), zero-padding only the tail view.
    Returns (views, part_elems); the consumer's program slices the
    concatenation back to the true element count."""
    total_bytes = flat.size * flat.itemsize
    n_parts = max(1, -(-total_bytes // chunk_bytes))
    k = -(-flat.size // n_parts)
    views = [flat[i * k : (i + 1) * k] for i in range(n_parts - 1)]
    tail = flat[(n_parts - 1) * k :]
    pad = n_parts * k - flat.size
    if pad:
        tail = np.concatenate([tail, np.zeros(pad, dtype=flat.dtype)])
    views.append(tail)
    return views, k


def chunked_device_put(
    flat: np.ndarray,
    device,
    chunk_bytes: int,
    mode: Optional[str] = None,
):
    """device_put a flat 1-D buffer as <=chunk_bytes chunks, concatenated
    on device. Returns a (possibly lazy) device array; the caller's
    compute dispatch provides the synchronization point."""
    import jax
    import jax.numpy as jnp

    if flat.ndim != 1:
        raise ValueError(
            f"chunked_device_put wants a flat 1-D buffer, got {flat.shape}"
        )
    mode = chunk_mode() if mode is None else mode
    views = chunk_views(flat, chunk_bytes)
    with span(
        "h2d",
        bytes=int(flat.nbytes),
        chunks=len(views),
        chunk_mode=mode if len(views) > 1 else "single",
    ):
        if len(views) == 1:
            return jax.device_put(flat, device)
        if mode == "serial":
            parts = [jax.device_put(v, device) for v in views]
        elif mode == "onecall":
            parts = jax.device_put(list(views), device)
        elif mode == "threads":
            futures = [
                _submit(_pool, jax.device_put, v, device) for v in views
            ]
            parts = [f.result() for f in futures]
        else:  # pragma: no cover - chunk_mode() validated already
            raise ValueError(mode)
        return jnp.concatenate(parts)


def put_pytree_chunked(
    params: Any, device, chunk_bytes: int, mode: Optional[str] = None
) -> Any:
    """Pre-place a parameter pytree on a device with every transfer at
    most ``chunk_bytes``.

    Closure-captured numpy params are otherwise transferred by XLA on the
    first call as whole leaves (ResNet50 has >8 MB leaves). Leaves up to
    ``chunk_bytes`` ship as-is (one put each); larger leaves ship as
    flat chunks and are reshaped on device.
    """
    import jax

    def _put_leaf(leaf):
        arr = np.asarray(leaf)
        if arr.nbytes <= chunk_bytes or arr.ndim == 0:
            return jax.device_put(arr, device)
        flat = np.ascontiguousarray(arr).reshape(-1)
        return chunked_device_put(flat, device, chunk_bytes, mode).reshape(
            arr.shape
        )

    def _leaf_bytes(a) -> int:
        # .nbytes is cheap on numpy AND jax arrays; only true scalars
        # fall back to materialization (np.asarray of a device array
        # here would D2H-copy the whole tree just to label the span)
        nb = getattr(a, "nbytes", None)
        return int(nb) if nb is not None else int(np.asarray(a).nbytes)

    leaves = jax.tree_util.tree_leaves(params)
    with span(
        "param_placement",
        leaves=len(leaves),
        bytes=sum(_leaf_bytes(a) for a in leaves),
    ):
        return jax.tree_util.tree_map(_put_leaf, params)
