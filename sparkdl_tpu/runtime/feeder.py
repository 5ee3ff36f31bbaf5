"""The batch engine: one shared device feeder for every offline row.

Every offline row reaches the device through a :class:`DeviceFeeder`
(``transformers/execution.run_batched_shared`` -> :func:`run_shared`).
The TensorFlow paper's input pipelines decouple producers from a single
coalesced device stream, and Horovod's tensor fusion shows that batching
many small submissions into fewer large ones is where distributed
throughput lives; this module is that serving-shaped pattern for the
batched inference path. N concurrent ``Executor.map_partitions`` tasks
feed ONE dispatch loop — with 64 partitions of ~100 rows at batch 32, a
dispatch loop per partition would pad >20% of the dispatched device
rows — and a single partition, a direct call and a whole-mesh program
are one-producer streams of the same loop.

A :class:`DeviceFeeder` is shared per ``(device_fn, dispatch size, row
shape, dtype)``. Partition threads stay the *host* stage — they run
``to_batch`` (decode/tokenize) in parallel and submit only the VALID rows
of each chunk (null/undecodable cells never occupy device rows here).
One owner thread per feeder assembles those row-chunks into full batches
**across partition boundaries**, using a small ring of reusable
pre-allocated buffers (no per-batch ``np.zeros``/``np.concatenate``
churn), dispatches through the device fn's feed-plan/chunked-H2D path
with a ``prefetch``-deep in-flight window, and scatters results back to
each partition's output list via vectorized masked indexing. Only the
final flush batch — emitted after a short linger once every producer
has finished — is ever padded, so padding waste is one tail per quiet
period. A producer that knows it is alone (no ``TaskContext``, or a
sequential executor) says so when it opens its handle (``alone``), and
the owner pads and flushes its tail the moment its stream ends: nobody
could have joined it, so it waits out neither the poll nor the linger.

Buffer-reuse safety: a dispatched batch may alias its ring buffer (the
flat relayout is a view, and jax's CPU client can transfer numpy buffers
zero-copy), so a buffer only returns to the free ring after its batch's
result has been read back — never while the program might still be
consuming it. The ring holds ``prefetch + 2`` buffers: one being filled,
``prefetch`` in flight, one spare. A device fn that hands its input back
(an identity, a view) would leave the caller's answers aliasing that
buffer, so the drain copies such a result before it scatters it.

Asynchronous readback (the D2H half of the pipeline): the owner used to
block in ``np.asarray(y_dev)`` inside its own dispatch loop — no new
batch could pack or dispatch while a result streamed back over the
link. With ``SPARKDL_ASYNC_READBACK`` on (the default), the owner
instead issues ``copy_to_host_async()`` at dispatch time (via
``runtime/readback.py``; graceful no-op where the runtime lacks it) and
hands finished batches to a dedicated **drainer thread** over the
in-flight deque: the drainer waits out the residual copy (``drain_wait``
span), scatters results back with vectorized slice assignment, and
returns the buffer to the ring — while the owner keeps packing and
dispatching. ``feeder.readback_async_hits`` / ``.misses`` count whether
the copy had already completed when the drain started (the overlap the
arm exists to create). ``0``/``off`` restores the fully synchronous
owner-thread drain (the A/B arm); ``_fail_all``/``_abort`` reset both
threads to a clean state either way.

Device-side input staging (the H2D half, mirroring the readback half
above): with ``SPARKDL_DEVICE_STAGE`` on (the default) and a device fn
that exposes its transfer half (``stage_put``, built by
``execution.flat_device_fn`` and the data-parallel wrappers), the owner
no longer pays the H2D copy inside the dispatch call. Each packed batch
is handed to the copy pool (``runtime/transfer.py``) the moment it is
full, landing in its own device-side staging slot; dispatch claims the
OLDEST slot once ``SPARKDL_DEVICE_STAGE_DEPTH`` (default 2) batches are
staged ahead — so while batch N computes, batch N+1's copy is already
in flight, and ``transfer.stage_hits``/``.stage_misses`` count whether
dispatch ever had to wait (the residual shows as a ``stage_wait``
span). ``0``/``off`` restores the legacy transfer-inside-dispatch arm.

Host buffer ring: ring slots are allocated LAZILY up to
``prefetch + stage_depth + 2`` — a geometry that only ever sees one
producer's trickle (the serving layer's model x rung x geometry
populations are full of them) allocates one or two buffers, not the
whole ring.

Flow control: producers push through a bounded queue (backpressure keeps
host memory ~2x the in-flight window); the owner never blocks on
consumers, so an abandoned or crashed partition thread can never wedge
it — its handle is failed/ended and the stream keeps moving. When a
device call raises, every open handle receives the exception (each
waiting partition re-raises it, and the executor's per-partition retry
applies as usual) and the feeder resets for subsequent work.

Env knobs (all read per event, so tests can flip them live):

- ``SPARKDL_FEEDER_LINGER_MS`` (default 20): how long the owner waits
  with a partial batch after the last producer ends before padding and
  flushing it — the window in which a newly-arriving partition can still
  coalesce into the tail.
- ``SPARKDL_FEEDER_IDLE_S`` (default 30): idle owner threads exit after
  this long; they restart lazily on the next submission. ``0`` = never
  exit (the serving keepalive: request streams with gaps between bursts
  keep their owner warm instead of paying respawn latency per burst).
- ``SPARKDL_ASYNC_READBACK`` (default on): ``0``/``off`` disables the
  dispatch-time D2H copy and the drainer thread — the synchronous
  legacy drain, for A/B.
- ``SPARKDL_DEVICE_STAGE`` (default on): ``0``/``off`` disables the
  staged H2D arm — transfers run inside the dispatch call again.
- ``SPARKDL_DEVICE_STAGE_DEPTH`` (default 2): staged copies riding
  ahead of dispatch (read at feeder construction — it sizes the
  buffer ring).
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from collections import OrderedDict, deque
from typing import Callable, List, Optional, Sequence

import numpy as np

from sparkdl_tpu.obs import memory, span, utilization
from sparkdl_tpu.resilience.faults import maybe_fault
from sparkdl_tpu.resilience.policy import RetryPolicy
from sparkdl_tpu.runtime import knobs, locksmith, readback, transfer
from sparkdl_tpu.utils.metrics import metrics


def _max_feeders() -> int:
    """Feeders kept alive in the registry; least-recently-used *idle*
    feeders beyond this are closed (busy feeders are never evicted).
    The default suits the batch engine (one geometry per model); the
    serving layer multiplies the population by its batch-size rungs
    (model x rung x shape), so serving deployments raise
    SPARKDL_MAX_FEEDERS to avoid LRU churn re-spawning owner threads —
    the latency the SPARKDL_FEEDER_IDLE_S=0 keepalive exists to avoid."""
    return max(1, knobs.get_int("SPARKDL_MAX_FEEDERS"))


#: The handle-open race (LRU eviction closing a feeder between registry
#: lookup and first use) is local and fast-resolving: many cheap
#: attempts, near-zero backoff, only RuntimeError (the "closed" signal)
#: retries. Public: the serving router opens streams through the same
#: registry and shares the same race (and must stay tuned with it).
open_handle_policy = RetryPolicy(
    max_attempts=8,
    base_delay_s=0.001,
    max_delay_s=0.02,
    retryable=(RuntimeError,),
)


def prefetch_per_device() -> int:
    """In-flight device batches per device. The default (2) covers
    host/device overlap when dispatch is cheap; a deeper window keeps
    more transfers in flight — tune with SPARKDL_PREFETCH_PER_DEVICE.
    More in-flight batches hold more input+output buffers (HBM
    pressure), so the default stays 2."""
    return knobs.get_int("SPARKDL_PREFETCH_PER_DEVICE")


def default_prefetch(device_fn=None) -> int:
    """In-flight window: prefetch_per_device() per participating device."""
    return prefetch_per_device() * max(1, getattr(device_fn, "n_devices", 1))


def _linger_s() -> float:
    return max(0.0, knobs.get_float("SPARKDL_FEEDER_LINGER_MS")) / 1e3


def _idle_s() -> float:
    """Idle-exit window for owner threads. ``0`` (or negative) means
    NEVER exit — the serving keepalive: an online request stream pays
    owner-thread respawn latency on every burst otherwise. Values in
    (0, 0.1) clamp up to 0.1s so a typo can't busy-spin the lifecycle."""
    raw = knobs.get_float("SPARKDL_FEEDER_IDLE_S")
    if raw <= 0.0:
        return float("inf")
    return max(0.1, raw)


class _Handle:
    """One partition run's submission stream into a feeder.

    Completion is row-count driven: ``_pending`` rises as valid rows are
    submitted and falls as their results scatter back; the event fires
    when the producer has ended its stream and every submitted row is
    accounted for. ``fail`` is sticky — the first error wins and wakes
    the waiting partition immediately. ``alone`` is the producer's word
    that no other stream can join its batches (see ``run_shared``)."""

    __slots__ = (
        "feeder", "out", "partition", "alone", "_lock", "_event",
        "_pending", "_ended", "error", "segments",
    )

    def __init__(
        self, feeder: "DeviceFeeder", out: list, partition=None, alone=False
    ):
        self.feeder = feeder
        self.out = out
        self.partition = partition
        self.alone = alone
        self._lock = locksmith.lock(
            "sparkdl_tpu/runtime/feeder.py::_Handle._lock"
        )
        self._event = threading.Event()
        self._pending = 0
        self._ended = False
        self.error: Optional[BaseException] = None
        #: per-stream stage attribution for request tracing: the owner /
        #: drainer accumulate the stage_wait (residual H2D), dispatch
        #: (device call), and drain_wait (residual D2H) seconds each
        #: batch this stream contributed to cost. The serving router
        #: reads them after wait() to build per-request waterfalls —
        #: one handle per dispatch group, so the totals ARE the group's.
        self.segments: dict = {}

    def _note_seg(self, name: str, dt: float) -> None:
        with self._lock:
            self.segments[name] = self.segments.get(name, 0.0) + dt

    def segments_snapshot(self) -> dict:
        with self._lock:
            return dict(self.segments)

    @property
    def failed(self) -> bool:
        return self.error is not None

    def _add_pending(self, n: int) -> None:
        with self._lock:
            self._pending += n

    def _rows_drained(self, n: int) -> None:
        with self._lock:
            self._pending -= n
            if self._ended and self._pending <= 0:
                self._event.set()

    def _mark_ended(self) -> None:
        with self._lock:
            self._ended = True
            if self._pending <= 0:
                self._event.set()

    def fail(self, exc: BaseException) -> None:
        with self._lock:
            # A stream whose every row already landed is complete — a
            # later foreign failure (another partition's device error,
            # feeder close) must not poison its successful result.
            complete = self._ended and self._pending <= 0
            if self.error is None and not complete:
                self.error = exc
            self._event.set()

    def wait(self, timeout: Optional[float] = None) -> None:
        """Block until every submitted row's result has landed (or the
        stream failed). Re-raises producer/device errors. Guards against
        a dead owner thread so a bug there surfaces as an exception in
        the partition task, never as a hang."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self._event.wait(timeout=0.2):
            if not self.feeder._owner_alive():
                self.fail(
                    RuntimeError(
                        "DeviceFeeder owner thread exited with rows still "
                        "pending (feeder closed or crashed)"
                    )
                )
                break
            if deadline is not None and time.monotonic() >= deadline:
                raise TimeoutError(
                    f"DeviceFeeder result wait exceeded {timeout}s "
                    f"({self._pending} rows pending)"
                )
        if self.error is not None:
            raise self.error


class DeviceFeeder:
    """Shared continuous-batching service for one (device_fn, batch
    geometry). Producers submit valid-row chunks via :meth:`open_handle`
    / :meth:`submit_rows` / :meth:`finish`; the single owner thread packs
    them into full ``dispatch_rows``-row batches and dispatches with a
    bounded in-flight window."""

    def __init__(self, device_fn, dispatch_rows, row_shape, dtype, prefetch):
        self.device_fn = device_fn
        self.host_prepare = getattr(device_fn, "host_prepare", None)
        self.dispatch_rows = int(dispatch_rows)
        self.row_shape = tuple(int(d) for d in row_shape)
        self.dtype = np.dtype(dtype)
        self.prefetch = max(1, int(prefetch))
        self._q: "queue.Queue" = queue.Queue(maxsize=max(4, 2 * self.prefetch))
        self._lock = locksmith.lock(
            "sparkdl_tpu/runtime/feeder.py::DeviceFeeder._lock"
        )
        self._open = 0  # producers registered whose "end" is unprocessed
        self._handles: set = set()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        # Batch-assembly state (owner thread only): the buffer being
        # filled and its segment map. Ring slots allocate LAZILY in
        # _take_buffer up to _ring_cap — a stream that never has a
        # second batch in flight never pays for the whole ring.
        self._free: List[np.ndarray] = []
        self._allocated = 0
        # 1 filling + stage_depth staged + prefetch in flight + 1 spare.
        self._stage_lag = transfer.stage_depth()
        self._ring_cap = self.prefetch + self._stage_lag + 2
        self._cur: Optional[np.ndarray] = None
        self._fill = 0
        self._segs: list = []  # (handle, dest_idx, buffer offset)
        # Device-side staging slots awaiting dispatch (owner thread
        # only): (segs, fill, pad, StagedBatch, buffer).
        self._staged: deque = deque()
        # Drain-side state, shared between the owner and the (async-arm)
        # drainer thread, all guarded by _drain_cv: dispatched batches
        # waiting for readback, the free-buffer ring they return to, a
        # count of entries popped-but-not-finished, and the drainer's
        # first error (the owner resets its assembly state on seeing it).
        self._drain_cv = locksmith.condition(
            "sparkdl_tpu/runtime/feeder.py::DeviceFeeder._drain_cv"
        )
        self._inflight: deque = deque()
        self._draining = 0
        self._drainer: Optional[threading.Thread] = None
        self._drainer_stop = False
        self._drain_exc: Optional[BaseException] = None

    # -- producer side ------------------------------------------------------

    def open_handle(self, out: list, partition=None, alone=False) -> _Handle:
        h = _Handle(self, out, partition, alone)
        with self._lock:
            if self._closed:
                raise RuntimeError("DeviceFeeder is closed")
            self._open += 1
            self._handles.add(h)
            self._ensure_owner_locked()
            metrics.gauge("feeder.open_producers", self._open)
        return h

    def submit_rows(self, handle: _Handle, dest_idx: np.ndarray, rows: np.ndarray) -> None:
        """Hand a chunk of VALID rows to the owner. ``dest_idx[k]`` is the
        index in ``handle.out`` that ``rows[k]``'s result lands in."""
        handle._add_pending(len(dest_idx))
        self._put(("rows", handle, dest_idx, rows))

    def finish(self, handle: _Handle) -> None:
        """End a producer's stream (normal completion, producer error, or
        an abandoning consumer). Idempotent enough for the error path:
        the owner decrements its producer count exactly once per queued
        end marker."""
        handle._mark_ended()
        self._put(("end", handle))

    def _put(self, item) -> None:
        while True:
            with self._lock:
                if self._closed:
                    raise RuntimeError("DeviceFeeder is closed")
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                if not self._owner_alive():
                    raise RuntimeError(
                        "DeviceFeeder owner thread is not running and the "
                        "submission queue is full"
                    )

    # -- owner thread -------------------------------------------------------

    def _ensure_owner_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._owner_loop,
                name=f"sparkdl-feeder-{id(self) & 0xFFFFFF:x}",
                daemon=True,
            )
            self._thread.start()

    def _owner_alive(self) -> bool:
        with self._lock:
            t = self._thread
        return t is not None and t.is_alive()

    @staticmethod
    def _clear_gauges() -> None:
        """Rewrite the depth gauges from the TRUE aggregate state of all
        registered feeders on owner exit, so a post-run snapshot never
        shows a stale nonzero depth from the last burst (the burst stays
        visible via the gauges' max envelope and the time-series
        sampler's history). The gauges are process-global and shared by
        every feeder, so an exiting feeder must not write a blind zero —
        a sibling mid-burst keeps its open-producer count. A handle
        opened between this read and the write can still be overwritten
        for one event (gauge writes aren't globally serialized); the
        next submit/end rewrites the truth. Must be called without the
        feeder's own lock held (idle() takes it)."""
        with _feeders_lock:
            open_total, busy = 0, False
            for f in _feeders.values():
                if f._closed:
                    continue
                with f._lock:
                    open_total += f._open
                if not f.idle():
                    busy = True
            metrics.gauge("feeder.open_producers", open_total)
            if not busy:
                metrics.gauge("feeder.queue_depth", 0)

    def _owner_loop(self) -> None:
        idle_s = _idle_s()
        flush_at: Optional[float] = None
        last_work = time.monotonic()
        while True:
            self._check_drain_exc()
            try:
                item = self._q.get(timeout=0.05)
            except queue.Empty:
                now = time.monotonic()
                with self._lock:
                    open_producers = self._open
                    closed = self._closed
                if closed:
                    self._abort(RuntimeError("DeviceFeeder closed"))
                    self._clear_gauges()
                    return
                if open_producers == 0 and (
                    self._fill or self._staged or self._pending_results()
                ):
                    # Staged batches are COMPLETE — nothing more can
                    # coalesce into them; dispatch before any linger so
                    # a quiet stream never holds a packed batch back.
                    if self._staged:
                        try:
                            while self._staged:
                                self._dispatch_staged()
                        except BaseException as e:  # noqa: BLE001
                            self._fail_all(e)
                    # Quiet period with a partial batch: linger briefly so
                    # a late-starting partition can still coalesce into the
                    # tail, then pad and flush the ONE tail batch.
                    if flush_at is None:
                        flush_at = now + _linger_s()
                    if now >= flush_at:
                        self._flush_tail()
                        flush_at = None
                        last_work = time.monotonic()
                elif open_producers == 0:
                    exiting = False
                    with self._lock:
                        if (
                            time.monotonic() - last_work > idle_s
                            and self._open == 0
                            and self._q.empty()
                        ):
                            self._thread = None  # restarted lazily
                            exiting = True
                    if exiting:  # clear OUTSIDE our lock (idle() takes it)
                        self._stop_drainer()  # restarts with the owner
                        self._clear_gauges()
                        return
                else:
                    flush_at = None
                    # Producers are mid-assembly but the queue is empty:
                    # nothing new is arriving, so a held staging slot
                    # gains no overlap — keep the device fed instead.
                    if self._staged:
                        try:
                            while self._staged:
                                self._dispatch_staged()
                        except BaseException as e:  # noqa: BLE001
                            self._fail_all(e)
                    # Reclaim a finished batch so results (and ring
                    # buffers) keep flowing. With the async arm a live
                    # drainer already does this off-thread.
                    if self._pending_results() and not self._drainer_alive():
                        try:
                            self._drain_one()
                        except BaseException as e:  # noqa: BLE001
                            self._fail_all(e)
                continue
            flush_at = None
            last_work = time.monotonic()
            kind = item[0]
            if kind == "stop":
                self._abort(RuntimeError("DeviceFeeder closed"))
                self._clear_gauges()
                return
            if kind == "end":
                with self._lock:
                    self._open -= 1
                    quiet = self._open == 0
                    self._handles = {
                        h for h in self._handles if not h._event.is_set()
                    }
                    metrics.gauge("feeder.open_producers", self._open)
                if (
                    quiet
                    and item[1].alone
                    and all(h.alone for h, _, _ in self._segs)
                ):
                    # The last producer was alone and said so: no other
                    # stream can join its tail, so the poll and the
                    # linger would buy nothing but latency.
                    self._flush_tail()
                continue
            _, handle, dest_idx, rows = item
            if handle.failed:
                continue  # stream already dead; drop its rows
            try:
                self._append_rows(handle, dest_idx, rows)
            except BaseException as e:  # noqa: BLE001
                self._fail_all(e)

    def _flush_tail(self) -> None:
        """Quiet period: pad and flush the ONE part-filled tail batch,
        then see every dispatched batch's result home."""
        try:
            if self._fill:
                # Tail-flush accounting lives HERE, not in _flush, so a
                # tail that happens to be exactly full (pad == 0) still
                # counts — _flush's pad branch only owns pad_rows.
                metrics.inc("feeder.flushes")
                self._flush()
            self._settle_inflight()
        except BaseException as e:  # noqa: BLE001
            self._fail_all(e)

    def _append_rows(self, handle: _Handle, dest_idx: np.ndarray, rows: np.ndarray) -> None:
        if self._cur is None:  # a failed flush left no current buffer
            self._cur = self._take_buffer()
        if tuple(rows.shape[1:]) != self.row_shape or rows.dtype != self.dtype:
            handle.fail(
                ValueError(
                    f"DeviceFeeder expects rows of shape {self.row_shape} "
                    f"dtype {self.dtype}, got {tuple(rows.shape[1:])} "
                    f"{rows.dtype}"
                )
            )
            return
        off, n = 0, len(dest_idx)
        while off < n:
            take = min(n - off, self.dispatch_rows - self._fill)
            self._cur[self._fill : self._fill + take] = rows[off : off + take]
            self._segs.append((handle, dest_idx[off : off + take], self._fill))
            self._fill += take
            off += take
            if self._fill == self.dispatch_rows:
                self._flush()

    def _flush(self) -> None:
        fill, buf, segs = self._fill, self._cur, self._segs
        pad = self.dispatch_rows - fill
        if pad:
            buf[fill:] = 0  # the ring reuses buffers; stale rows pad as zeros
            metrics.inc("feeder.pad_rows", pad)
        batch = buf if self.host_prepare is None else self.host_prepare(buf)
        stage_fn = getattr(self.device_fn, "stage_put", None)
        if transfer.device_stage_enabled() and stage_fn is not None:
            # Double-buffered device staging: this batch's H2D copy
            # starts NOW on the copy pool; dispatch claims the oldest
            # slot once the ring is `stage_lag` batches ahead — while
            # batch N computes, batch N+1's copy is already in flight.
            slot = transfer.stage_batch(stage_fn, batch, rows=fill)
            staged_bytes = int(getattr(batch, "nbytes", 0) or 0)
            # device-memory ledger: the staged copy holds device bytes
            # until dispatch claims it (or a failure reset reclaims it)
            memory.note_staged(self.device_fn, staged_bytes)
            # buf is now owned by the staged entry: drop it from _cur
            # BEFORE anything below can raise, or _fail_all would hand
            # the same buffer out twice (once from _cur, once from the
            # entry) and corrupt a dispatched batch.
            self._staged.append((segs, fill, pad, slot, buf, staged_bytes))
            self._cur = None
            self._fill = 0
            self._segs = []
            # Hold a staged slot back only while MORE rows are arriving
            # (that's when the lag buys overlap: batch N+1's copy rides
            # under batch N's compute). An empty queue means a shallow
            # stream — serving's exact-rung groups — where holding the
            # slot would just add dispatch latency.
            while len(self._staged) >= self._stage_lag or (
                self._staged and self._q.empty()
            ):
                self._dispatch_staged()
        else:
            if self._staged:  # arm flipped off mid-stream: keep order
                while self._staged:
                    self._dispatch_staged()
            self._dispatch(segs, fill, pad, batch, buf)
            # buf now rides the in-flight entry (same aliasing hazard as
            # the staged branch above).
            self._cur = None
            self._fill = 0
            self._segs = []
        self._cur = self._take_buffer()

    def _dispatch_staged(self) -> None:
        """Dispatch the OLDEST staged slot: its H2D copy has been in
        flight under the later packs/stages, so claiming it pays at most
        the residual (hit/miss counted in StagedBatch.take). A failed
        claim or dispatch returns the buffer to the ring before the
        error reaches the owner's fail-all."""
        segs, fill, pad, slot, buf, staged_bytes = self._staged.popleft()
        try:
            t0 = time.perf_counter()
            batch = slot.take()
            dt = time.perf_counter() - t0
            for h in {s[0] for s in segs}:
                h._note_seg("stage_wait", dt)
            if dt > 0:
                # goodput ledger: the residual H2D wait is chip idle
                # time attributed to transfer (util.h2d_ms.<device>)
                utilization.note_transfer(self.device_fn, h2d_s=dt)
            self._dispatch(segs, fill, pad, batch, buf, staged=True)
        except BaseException:
            with self._drain_cv:
                self._free.append(buf)
                self._drain_cv.notify_all()
            raise
        finally:
            # consumed by dispatch (or reclaimed above): either way the
            # batch stops being a staged holding in the memory ledger
            memory.release_staged(self.device_fn, staged_bytes)

    def _dispatch(self, segs, fill, pad, batch, buf, staged=False) -> None:
        arm = readback.async_readback_enabled()
        if arm:
            self._ensure_drainer()
        self._throttle_inflight(arm)  # cap device residency at `prefetch`
        depth = self._q.qsize()
        metrics.gauge("feeder.queue_depth", depth)
        # Chaos hook (env-gated no-op): a raise= here exercises the
        # owner's fail-all/reset path — every open handle re-raises and
        # the executor's per-partition retry applies.
        maybe_fault("feeder.dispatch", rows=fill, depth=depth)
        t0 = time.perf_counter()
        with span(
            "dispatch",
            rows=fill,
            pad=pad,
            bytes=int(getattr(batch, "nbytes", 0)),
            feeder=True,
            queue_depth=depth,
            staged=staged,
        ):
            y_dev = self.device_fn(batch)
        dt = time.perf_counter() - t0
        for h in {s[0] for s in segs}:
            h._note_seg("dispatch", dt)
        # Goodput ledger roll-up: this program's wall time is chip BUSY
        # time on every device the fn engages; the gap to the next
        # dispatch accrues as idle (obs/utilization.py owns the
        # conservation arithmetic).
        utilization.note_busy(self.device_fn, dt)
        metrics.inc("feeder.coalesced_batches")
        # Mesh-aware accounting: a batch_multiplier > 1 device fn is a
        # GLOBAL batch — one dispatch whose rows shard over every chip
        # in the program's mesh (the staged H2D above already pre-placed
        # it with the program's own NamedSharding via stage_put).
        if getattr(self.device_fn, "batch_multiplier", 1) > 1:
            metrics.inc("feeder.global_batches")
        if arm:
            # Start the D2H copy NOW, while the next batches pack and
            # dispatch — the drainer's later asarray only pays the
            # residual (readback.start_copy no-ops where unsupported).
            readback.start_copy(y_dev)
        with self._drain_cv:
            self._inflight.append((segs, fill, y_dev, buf, arm))
            self._drain_cv.notify_all()

    # -- drain side (owner thread, or the drainer thread on the async arm) --

    def _pending_results(self) -> bool:
        with self._drain_cv:
            return bool(self._inflight or self._draining)

    def _check_drain_exc(self) -> None:
        """Owner-side: after a drainer-thread failure (which already
        failed every open handle and reclaimed the in-flight buffers),
        discard the partial batch under assembly — its segments belong
        to failed handles and must not dispatch as garbage."""
        with self._drain_cv:
            exc = self._drain_exc
            self._drain_exc = None
        if exc is not None:
            self._fill = 0
            self._segs = []
            self._reclaim_staged()

    def _throttle_inflight(self, arm: bool) -> None:
        """Block until fewer than ``prefetch`` batches are dispatched but
        undrained. Sync arm (or a dead drainer): drain the oldest batch
        ourselves, exactly the legacy behavior."""
        while True:
            with self._drain_cv:
                if len(self._inflight) + self._draining < self.prefetch:
                    return
                if self._closed:
                    raise RuntimeError("DeviceFeeder closed")
                wait_only = arm and self._drainer_alive()
                if wait_only:
                    self._drain_cv.wait(timeout=0.1)
                    continue
            if not self._drain_one():
                with self._drain_cv:
                    if (
                        len(self._inflight) + self._draining
                        >= self.prefetch
                    ):
                        self._drain_cv.wait(timeout=0.05)

    def _take_buffer(self) -> np.ndarray:
        """Pop a free ring buffer — allocating a fresh one while the ring
        is under its cap (lazy: a stream that never goes deep never pays
        for the full ring) — draining (or waiting for the drainer) when
        the ring is momentarily empty. Buffer conservation: every
        dispatched buffer returns via _drain_entry's finally or the
        failure paths, so free+inflight+draining can only all be empty
        on a leak — raise rather than hang."""
        while True:
            with self._drain_cv:
                if self._free:
                    return self._free.pop()
                if self._closed:
                    raise RuntimeError("DeviceFeeder closed")
                if self._allocated < self._ring_cap:
                    self._allocated += 1
                    return np.zeros(
                        (self.dispatch_rows, *self.row_shape), self.dtype
                    )
            if not self._drain_one():
                with self._drain_cv:
                    if self._free:
                        continue
                    if self._inflight or self._draining:
                        self._drain_cv.wait(timeout=0.1)
                    else:
                        raise RuntimeError(
                            "DeviceFeeder buffer ring exhausted with "
                            "nothing in flight (buffer leak)"
                        )

    def _settle_inflight(self) -> None:
        """Quiet-period tail: every dispatched batch's result has landed
        (drained by us or the drainer) before the stream is settled.
        Staged copies still awaiting dispatch go out first, in order."""
        while self._staged:
            self._dispatch_staged()
        while True:
            if self._drain_one():
                continue
            with self._drain_cv:
                if self._inflight:
                    continue
                if self._draining:
                    self._drain_cv.wait(timeout=0.1)
                    continue
                return

    def _drain_one(self) -> bool:
        """Pop and drain the oldest in-flight batch; False when there was
        nothing to pop. Safe from either thread — entries are claimed
        under the drain lock, so each drains exactly once."""
        with self._drain_cv:
            if not self._inflight:
                return False
            entry = self._inflight.popleft()
            self._draining += 1
        try:
            self._drain_entry(*entry)
        finally:
            with self._drain_cv:
                self._draining -= 1
                self._drain_cv.notify_all()
        return True

    def _drain_entry(self, segs, fill, y_dev, buf, arm) -> None:
        # device-memory ledger: the output buffer occupies device bytes
        # for the drain window (program tail + D2H); released in the
        # finally BEFORE the drain lock — ledger calls stay outside it
        readback_bytes = int(getattr(y_dev, "nbytes", 0) or 0)
        memory.note_readback(self.device_fn, readback_bytes)
        try:
            if arm:
                ready = readback.is_ready(y_dev)
                if ready is not None:
                    metrics.inc(
                        "feeder.readback_async_hits"
                        if ready
                        else "feeder.readback_async_misses"
                    )
            t0 = time.perf_counter()
            # drain_wait (async arm) is the RESIDUAL wait after the
            # dispatch-time copy; device_wait (sync arm) is the legacy
            # full block on program + D2H.
            with span(
                "drain_wait" if arm else "device_wait", rows=fill, feeder=True
            ):
                y = readback.to_host(y_dev)
                if np.may_share_memory(y, buf):
                    # the device fn handed its input back: the rows would
                    # alias a buffer that returns to the ring below
                    y = y.copy()
            dt = time.perf_counter() - t0
            metrics.record_time("transform.device_wait", dt)
            if dt > 0:
                # Goodput ledger: dispatch is async (the device_fn call
                # returns with the program in flight), so the drain
                # residual is the tail of the program + D2H still
                # running — BUSY wall, attributed to readback
                # (util.d2h_ms.<device>) so "busy, dominated by D2H"
                # stays readable next to pure compute.
                utilization.note_busy(self.device_fn, dt)
                utilization.note_transfer(self.device_fn, d2h_s=dt)
            # Trace attribution: the readback residual is the waterfall's
            # drain_wait segment on EITHER arm (the span name differs so
            # the stage tables stay arm-honest; the per-request ledger
            # wants one name for "waited on D2H").
            for handle in {s[0] for s in segs}:
                if not handle.failed:
                    handle._note_seg("drain_wait", dt)
            delivered = 0
            for handle, dest_idx, off in segs:
                if handle.failed:
                    continue  # failed streams deliver nothing — don't count
                readback.scatter_rows(
                    handle.out, dest_idx, y[off : off + len(dest_idx)]
                )
                delivered += len(dest_idx)
                handle._rows_drained(len(dest_idx))
            if delivered:
                metrics.inc("transform.rows", delivered)
                metrics.inc("feeder.rows", delivered)
        finally:
            memory.release_readback(self.device_fn, readback_bytes)
            with self._drain_cv:
                # a readback error must not shrink the ring
                self._free.append(buf)
                self._drain_cv.notify_all()

    # -- drainer thread lifecycle -------------------------------------------

    def _ensure_drainer(self) -> None:
        """Owner-thread only: (re)start the drainer lazily, mirroring the
        owner's own lazy lifecycle."""
        t = self._drainer
        if t is not None and t.is_alive():
            return
        with self._drain_cv:
            self._drainer_stop = False
        t = threading.Thread(
            target=self._drainer_loop,
            name=f"sparkdl-feeder-drain-{id(self) & 0xFFFFFF:x}",
            daemon=True,
        )
        self._drainer = t
        t.start()

    def _drainer_alive(self) -> bool:
        t = self._drainer
        return t is not None and t.is_alive()

    def _stop_drainer(self, timeout: float = 5.0) -> None:
        t = self._drainer
        with self._drain_cv:
            self._drainer_stop = True
            self._drain_cv.notify_all()
        if t is not None and t.is_alive():
            t.join(timeout=timeout)

    def _drainer_loop(self) -> None:
        """Async-arm drain stage: wait out each batch's residual D2H and
        scatter results while the owner keeps packing and dispatching.
        Errors fail every open handle (same contract as the owner's
        drain) and flag the owner to reset its assembly state."""
        while True:
            with self._drain_cv:
                while not self._inflight:
                    if self._closed or self._drainer_stop:
                        return
                    self._drain_cv.wait(timeout=0.25)
                entry = self._inflight.popleft()
                self._draining += 1
            try:
                self._drain_entry(*entry)
            except BaseException as e:  # noqa: BLE001
                self._drain_failure(e)
            finally:
                with self._drain_cv:
                    self._draining -= 1
                    self._drain_cv.notify_all()

    def _drain_failure(
        self, exc: BaseException, from_drainer: bool = True
    ) -> None:
        """Thread-safe half of the failure reset: fail every open stream,
        reclaim in-flight buffers, and (from the drainer) leave the error
        for the owner to discard its partial batch."""
        with self._lock:
            handles = list(self._handles)
            self._handles.clear()
        for h in handles:
            h.fail(exc)
        with self._drain_cv:
            while self._inflight:
                entry = self._inflight.popleft()
                self._free.append(entry[3])
            if from_drainer:
                self._drain_exc = exc
            self._drain_cv.notify_all()

    def _reclaim_staged(self) -> None:
        """Owner-side: return staged slots' buffers to the ring after a
        failure reset, waiting out any copy still reading them (a
        device_put may alias the host buffer zero-copy)."""
        while self._staged:
            _, _, _, slot, buf, staged_bytes = self._staged.popleft()
            slot.settle()
            memory.release_staged(self.device_fn, staged_bytes)
            with self._drain_cv:
                self._free.append(buf)
                self._drain_cv.notify_all()

    def _fail_all(self, exc: BaseException) -> None:
        """Device-path error: every open stream receives the exception
        (their partitions re-raise and the executor's retry applies) and
        the owner resets to a clean state for subsequent work."""
        self._drain_failure(exc, from_drainer=False)
        self._fill = 0
        self._segs = []
        self._reclaim_staged()
        if self._cur is None:
            with self._drain_cv:
                if self._free:
                    self._cur = self._free.pop()

    def _abort(self, exc: BaseException) -> None:
        self._fail_all(exc)
        self._stop_drainer()  # in-flight is clear, so it exits promptly
        while True:  # unblock any producer stuck on a full queue
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                return
            if item[0] == "end":
                with self._lock:
                    self._open -= 1
            elif item[0] == "rows":
                item[1].fail(exc)

    # -- lifecycle ----------------------------------------------------------

    def idle(self) -> bool:
        with self._lock:
            if self._open or self._fill or not self._q.empty():
                return False
        return not (self._staged or self._pending_results())

    def close(self, timeout: float = 5.0) -> None:
        with self._lock:
            self._closed = True
            t = self._thread
        with self._drain_cv:
            self._drain_cv.notify_all()  # wake buffer/slot/drainer waits
        try:
            self._q.put_nowait(("stop",))
        except queue.Full:
            pass  # owner sees _closed on its next queue timeout
        if t is not None and t.is_alive():
            t.join(timeout=timeout)
        # The owner's exit paths stop the drainer themselves; this covers
        # an owner that never started (or died) — close() must never
        # leak the drain thread.
        self._stop_drainer(timeout=timeout)
        self._fail_all(RuntimeError("DeviceFeeder closed"))
        self._clear_gauges()  # owner may never have started; don't rely on it


# -- registry ----------------------------------------------------------------

_feeders: "OrderedDict[tuple, DeviceFeeder]" = OrderedDict()
_feeders_lock = locksmith.lock("sparkdl_tpu/runtime/feeder.py::_feeders_lock")

#: extra teardown callables (guarded by _feeders_lock): subsystems that
#: own sparkdl-* threads outside the feeder registry — the generation
#: engine's decode streams — register here so shutdown_feeders() remains
#: THE one teardown call tests and smokes rely on for a thread-clean
#: process.
_shutdown_hooks: List = []


def register_shutdown_hook(fn):
    """Register ``fn`` to run (once per shutdown) at
    :func:`shutdown_feeders`; returns an unregister callable."""
    with _feeders_lock:
        _shutdown_hooks.append(fn)

    def _unregister():
        with _feeders_lock:
            try:
                _shutdown_hooks.remove(fn)
            except ValueError:
                pass

    return _unregister


def get_feeder(device_fn, dispatch_rows, row_shape, dtype, prefetch) -> DeviceFeeder:
    """The process-wide feeder for this (device_fn, batch geometry).
    Entries hold the device_fn itself so the id() in the key can never be
    recycled by a GC'd-and-reallocated callable; least-recently-used IDLE
    feeders beyond the cap are closed (busy ones never are)."""
    key = (
        id(device_fn),
        int(dispatch_rows),
        tuple(int(d) for d in row_shape),
        str(np.dtype(dtype)),
    )
    evicted: List[DeviceFeeder] = []
    with _feeders_lock:
        f = _feeders.get(key)
        if f is not None and f.device_fn is device_fn and not f._closed:
            _feeders.move_to_end(key)
            return f
        f = DeviceFeeder(device_fn, dispatch_rows, row_shape, dtype, prefetch)
        _feeders[key] = f
        cap = _max_feeders()
        if len(_feeders) > cap:
            for k in list(_feeders):
                if len(_feeders) <= cap:
                    break
                cand = _feeders[k]
                if cand is not f and cand.idle():
                    evicted.append(_feeders.pop(k))
    for ev in evicted:
        ev.close(timeout=1.0)
    return f


def shutdown_feeders() -> None:
    """Close every registered feeder AND the module-global H2D copy
    pools (tests / process teardown): a shut-down engine must leave no
    feeder, drainer, or transfer thread behind."""
    with _feeders_lock:
        feeders = list(_feeders.values())
        _feeders.clear()
        hooks = list(_shutdown_hooks)
    for f in feeders:
        f.close()
    for hook in hooks:
        # hooks unregister themselves when they run (engine close is
        # idempotent); never let one broken hook strand the rest
        try:
            hook()
        except Exception:  # noqa: BLE001 — teardown must finish
            pass
    transfer.shutdown_transfer_pool()


def close_feeders_for(device_fn) -> int:
    """Close and deregister every feeder stream of ONE device fn — the
    residency manager's eviction hook: a model leaving device memory must
    not keep compiled streams (and, via the registry's strong device_fn
    reference, its params) alive. Returns how many feeders closed."""
    with _feeders_lock:
        doomed = [
            k for k, f in _feeders.items() if f.device_fn is device_fn
        ]
        feeders = [_feeders.pop(k) for k in doomed]
    for f in feeders:
        f.close(timeout=1.0)
    return len(feeders)


# -- the partition-side entry point ------------------------------------------


@contextlib.contextmanager
def ingest_span(start: int, partition):
    """The partition thread's host stage of one chunk: the ``ingest`` span
    and the ``transform.host_batch`` timer around whatever turns cells
    into device rows. Yields the span for ``rows`` and ``bytes``."""
    t0 = time.perf_counter()
    with span(
        "ingest", batch_start=start, partition=partition, feeder=True
    ) as sp:
        yield sp
    metrics.record_time("transform.host_batch", time.perf_counter() - t0)


def _to_batch_chunks(cells, to_batch, dispatch_rows, partition):
    """``run_shared``'s own host stage: ``to_batch`` over one dispatched
    batch's cells at a time, each chunk compressed to its valid rows with
    vectorized masked indexing."""
    for start in range(0, len(cells), dispatch_rows):
        chunk = list(cells[start : start + dispatch_rows])
        with ingest_span(start, partition) as sp:
            batch, mask = to_batch(chunk)
            valid = np.flatnonzero(mask)
            sp.add(
                rows=int(len(valid)),
                bytes=int(getattr(batch, "nbytes", 0)),
            )
        if not len(valid):
            continue  # every cell null/undecodable: no device rows
        yield (
            start + valid,
            batch if len(valid) == len(chunk) else batch[valid],
        )


def run_shared(
    device_fn: Callable,
    cells: Sequence,
    to_batch: Callable,
    batch_size: int,
    prefetch: Optional[int] = None,
    partition=None,
    stream: Optional[Callable] = None,
    alone: bool = False,
) -> List[Optional[np.ndarray]]:
    """Map ``device_fn`` over ``cells`` in fixed-size batches: one output
    per cell, ndarray rows, None where masked out.

    Args:
        cells: partition column values (may contain None).
        to_batch: host stage: list of cells -> (batch array, bool mask).
        device_fn: jitted fn over one full batch (static shape).
        batch_size: device batch size (times the device fn's
            ``batch_multiplier``); only a tail batch is zero-padded to it.
        prefetch: max batches in flight on the device ahead of readback;
            defaults to 2 per participating device.
        partition: the caller's partition index, for its spans.
        alone: the caller knows no other producer runs beside it (a
            direct call, a sequential executor), so its tail is flushed
            when its stream ends, without the linger.

    The calling partition thread stays the host stage: it runs
    ``to_batch`` chunk by chunk (decode/tokenize overlapped across
    partitions by the executor's worker threads), compresses each chunk
    to its valid rows with vectorized masked indexing, and streams them
    into the feeder keyed by the observed row shape — so workloads whose
    row shape varies between chunks transparently use one feeder per
    shape.

    A host stage that is no ``to_batch`` — its rows leave a chunk in
    several shapes, or in chunks of its own size: the text engine's
    length buckets — passes ``stream`` in its place.
    ``stream(dispatch_rows)`` yields ``(dest_idx, rows)`` as the
    partition thread produces them, ``rows[k]``'s result landing in cell
    ``dest_idx[k]``. Every handle stays open until the stage is
    exhausted: the owners pad and flush a part-filled batch only once no
    producer is open, so handing rows over early costs no padding."""
    dispatch_rows = batch_size * getattr(device_fn, "batch_multiplier", 1)
    if prefetch is None:
        prefetch = default_prefetch(device_fn)
    n = len(cells)
    out: List[Optional[np.ndarray]] = [None] * n
    if n == 0:
        return out
    chunks = (
        _to_batch_chunks(cells, to_batch, dispatch_rows, partition)
        if stream is None
        else stream(dispatch_rows)
    )
    handles: dict = {}
    try:
        for dest_idx, rows in chunks:
            key = (tuple(rows.shape[1:]), str(rows.dtype))
            handle = handles.get(key)
            if handle is None:
                # LRU eviction can close the feeder between registry
                # lookup and first use; the registry re-creates it, so
                # the race is retryable — under the shared policy (tiny
                # backoff: the closer is another thread mid-close, not a
                # remote system) instead of the old hard-coded 8-loop.
                def _open():
                    feeder = get_feeder(
                        device_fn, dispatch_rows, rows.shape[1:],
                        rows.dtype, prefetch,
                    )
                    return feeder.open_handle(
                        out, partition=partition, alone=alone
                    )

                try:
                    handle = open_handle_policy.call(_open)
                except RuntimeError as e:
                    raise RuntimeError(
                        "could not open a DeviceFeeder handle (feeder "
                        "repeatedly closed under us)"
                    ) from e
                handles[key] = handle
            handle.feeder.submit_rows(handle, dest_idx, rows)
    except BaseException as e:
        for h in handles.values():
            h.fail(e)  # wake anything; owner drops our queued rows
        raise
    finally:
        for h in handles.values():
            try:
                h.feeder.finish(h)
            except RuntimeError:
                pass  # feeder closed underneath us; handles already failed
    # every row is submitted: what is left is the wait for the device
    # (and the other partitions sharing its batches) to hand them back
    with span("result_wait", partition=partition, feeder=True):
        for h in handles.values():
            h.wait()
    return out
