"""Asynchronous D2H readback: overlap result copy-back with dispatch.

H2D has been pipelined since the chunked-feed work (PRs 2-3), but the
RETURN direction used to run synchronously: the dispatch loop blocked in
``np.asarray(y_dev)`` and nothing else moved while a result streamed
back over the link. What the overlap is worth on the attached chip is
not measured (PERF.md). The TensorFlow dataflow design and the CUDA-aware-MPI characterization work
(PAPERS.md) both make the same point — transfers must overlap compute
in *both* directions.

This module is where the dispatch paths (the offline engine's and the
serving router's ``runtime/feeder.DeviceFeeder`` streams) get that
overlap from:

- :func:`start_copy` — issue the device array's ``copy_to_host_async()``
  at DISPATCH time, so the D2H transfer rides under the device's compute
  of the *next* batches instead of starting only when the drain loop
  finally blocks. Gracefully a no-op where the runtime lacks the method
  (older jaxlib, fake arrays in tests, plain numpy from CPU paths).
- :func:`is_ready` — best-effort "has this result landed" probe
  (``None`` when the runtime can't say), used by the feeder's drainer to
  attribute hits (copy already complete at drain) vs misses (drain still
  had to wait) to ``feeder.readback_async_hits`` / ``.misses``.
- :func:`scatter_rows` — vectorized result scatter into a partition's
  output list: one C-level slice assignment when the destination indices
  are one contiguous run (the common no-nulls case), a native-int loop
  over pre-unpacked row views otherwise — replacing the per-row Python
  ``out[d] = rows[k]`` loop in the drain.

Env knob: ``SPARKDL_ASYNC_READBACK`` (default on; ``0``/``off`` restores
the fully synchronous legacy drain — the A/B arm and escape hatch, house
style, read per event so tests can flip it live).
"""

from __future__ import annotations

from sparkdl_tpu.runtime import knobs
from typing import List, Optional, Sequence

import numpy as np

__all__ = [
    "async_readback_enabled",
    "start_copy",
    "is_ready",
    "to_host",
    "scatter_rows",
]


def async_readback_enabled() -> bool:
    """SPARKDL_ASYNC_READBACK gates the async readback arm in BOTH
    dispatch paths (default ON; 0/off = the synchronous legacy drain)."""
    return knobs.get_flag("SPARKDL_ASYNC_READBACK")


def start_copy(y_dev) -> bool:
    """Kick off the device->host copy of a dispatched result NOW, without
    blocking. Returns True when an async copy was actually issued.

    jax arrays expose ``copy_to_host_async()``; anything without it
    (numpy results from CPU device fns, test doubles, older runtimes)
    is a silent no-op — the later ``np.asarray`` drain works either way,
    it just can't overlap.
    """
    fn = getattr(y_dev, "copy_to_host_async", None)
    if fn is None:
        return False
    try:
        fn()
        return True
    except Exception:  # noqa: BLE001 — an eager copy must never kill dispatch
        return False


def is_ready(y_dev) -> Optional[bool]:
    """Whether the result (and its D2H copy) has already completed —
    ``None`` when the runtime can't tell. Used only for the hit/miss
    attribution counters; never for control flow."""
    fn = getattr(y_dev, "is_ready", None)
    if fn is None:
        return None
    try:
        return bool(fn())
    except Exception:  # noqa: BLE001 — a probe must never raise
        return None


def to_host(y_dev) -> np.ndarray:
    """Materialize a (possibly still in-flight) device result on host.
    Blocks only for whatever transfer/compute remains."""
    return np.asarray(y_dev)


def scatter_rows(
    out: List[Optional[np.ndarray]],
    dest_idx: Sequence,
    rows: np.ndarray,
) -> None:
    """Scatter ``rows[k]`` into ``out[dest_idx[k]]`` without a per-row
    Python ``enumerate`` loop.

    ``list(rows[:n])`` unpacks the block into row views in one C-level
    pass; when the destinations are a single contiguous run (strictly
    increasing submission order makes the span check sufficient), the
    whole scatter is ONE list slice assignment. Gapped destinations
    (null cells interleaved) fall back to a zip over native ints —
    still far cheaper than indexing a list with numpy scalars one
    ``__setitem__`` at a time.
    """
    n = len(dest_idx)
    if n == 0:
        return
    views = list(rows[:n])
    first = int(dest_idx[0])
    last = int(dest_idx[-1])
    if last - first + 1 == n:
        out[first : last + 1] = views
    else:
        idx = (
            dest_idx.tolist()
            if isinstance(dest_idx, np.ndarray)
            else list(dest_idx)
        )
        for d, v in zip(idx, views):
            out[d] = v
