"""Nestable, thread-safe spans with attributes, feeding a ring buffer.

A span is one timed region of the batch path — ``ingest`` (host batch
assembly), ``h2d`` (host->device transfer), ``dispatch`` (handing a batch
to the device stream), ``device_wait`` (blocking on a device result),
``executor.partition`` (one partition task), ``worker.partition`` (one
gang-owned partition) — with free-form attributes (rows, bytes, chunk
mode, partition index). Spans nest per thread: each thread carries its
own stack, so the executor's partition threads and the feeder's owner
thread trace independently and a child span's ``parent_id`` names the
innermost open span *of its own thread*. Only a thread with no open span
takes a parent named explicitly (``span(name, parent_id=...)``): the
executor hands each partition task the id of its
``executor.map_partitions`` span, so the spans of one job form one tree
across the hand-off to the pool.

Recording costs one lock acquisition and two ``perf_counter`` reads per
span; the ring buffer bounds memory (``SPARKDL_OBS_RING`` spans, default
4096 — old spans fall off the back). ``SPARKDL_OBS=0`` turns span
recording into a shared no-op context manager for zero-overhead runs;
the cheap aggregate timers in :mod:`sparkdl_tpu.utils.metrics` keep
flowing either way because call sites record them directly.

One clock with the device: every span also opens a profiler annotation
``sparkdl:<name>`` (:func:`sparkdl_tpu.utils.profiler.annotate`, with
the scalar attributes known at open) on its own thread. While a
``jax.profiler`` trace runs, the span is an event on the ``/host:CPU``
plane, on the clock of the device's operations, so a device idle gap can
be laid against what the host was doing; while none runs, the annotation
is a check of one flag.

Wall-clock anchoring: durations come from ``perf_counter`` (monotonic);
start timestamps are anchored once per process to ``time.time`` so
exported traces from different processes of a gang line up on a shared
timeline to within clock skew.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from sparkdl_tpu.runtime import knobs
from sparkdl_tpu.utils.metrics import metrics
from sparkdl_tpu.utils.profiler import annotate

#: prefix of every span's event in a jax.profiler trace
ANNOTATION_PREFIX = "sparkdl:"
#: attribute types a profiler annotation carries as event stats
_SCALARS = (bool, int, float, str)

# Process-wide anchor: wall time of the perf_counter epoch, fixed at
# import so every span's start_unix is consistent within the process.
_ANCHOR_UNIX = time.time() - time.perf_counter()

_DEFAULT_RING = 4096


def obs_enabled() -> bool:
    return knobs.get_flag("SPARKDL_OBS")


def ring_capacity() -> int:
    return max(1, knobs.get_int("SPARKDL_OBS_RING"))


@dataclass
class SpanRecord:
    """One closed span, as it sits in the ring buffer."""

    name: str
    span_id: int
    parent_id: Optional[int]
    thread_id: int
    thread_name: str
    start_pc: float  # perf_counter at __enter__
    dur_s: float = 0.0
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def start_unix(self) -> float:
        return _ANCHOR_UNIX + self.start_pc

    def as_dict(self) -> dict:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "thread_id": self.thread_id,
            "thread_name": self.thread_name,
            "start_unix": self.start_unix,
            "dur_s": self.dur_s,
            "attrs": self.attrs,
        }


class SpanRecorder:
    """Bounded ring buffer of closed spans + registry of open ones.

    Thread-safe throughout: partition threads, the batch producer, the
    heartbeat thread, and the H2D thread pool all record concurrently.
    The open-span registry exists so liveness tooling (heartbeat beats)
    can report *what a thread is doing right now*, not just what it
    finished."""

    def __init__(self, capacity: Optional[int] = None):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity or ring_capacity())
        self._open: Dict[int, SpanRecord] = {}
        self._ids = itertools.count(1)
        self._local = threading.local()

    # -- span lifecycle (called by the ``span`` context manager) ------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def open(
        self,
        name: str,
        attrs: Dict[str, Any],
        parent_id: Optional[int] = None,
    ) -> SpanRecord:
        """``parent_id`` names a parent on ANOTHER thread (a task handed
        to a pool); an open span of this thread takes precedence."""
        t = threading.current_thread()
        stack = self._stack()
        rec = SpanRecord(
            name=name,
            span_id=next(self._ids),
            parent_id=stack[-1].span_id if stack else parent_id,
            thread_id=t.ident or 0,
            thread_name=t.name,
            start_pc=time.perf_counter(),
            attrs=attrs,
        )
        stack.append(rec)
        with self._lock:
            self._open[rec.span_id] = rec
        return rec

    def close(self, rec: SpanRecord) -> None:
        rec.dur_s = time.perf_counter() - rec.start_pc
        stack = self._stack()
        if stack and stack[-1] is rec:
            stack.pop()
        else:  # out-of-order exit (generator misuse): drop from wherever
            try:
                stack.remove(rec)
            except ValueError:
                pass
        with self._lock:
            self._open.pop(rec.span_id, None)
            self._ring.append(rec)
        # Aggregate view: spans double as registry timers so the cheap
        # always-on counters and the ring buffer can never disagree.
        metrics.record_time(f"span.{rec.name}", rec.dur_s)
        rows = rec.attrs.get("rows")
        if rows:
            metrics.inc(f"span.{rec.name}.rows", float(rows))
        nbytes = rec.attrs.get("bytes")
        if nbytes:
            metrics.inc(f"span.{rec.name}.bytes", float(nbytes))

    # -- reading ------------------------------------------------------------

    def spans(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._ring)

    def open_spans(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._open.values())

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._open.clear()


_recorder: Optional[SpanRecorder] = None
_recorder_lock = threading.Lock()


def get_recorder() -> SpanRecorder:
    """The process-global recorder (capacity read from the env on first
    use; tests swap it with :func:`set_recorder`)."""
    global _recorder
    with _recorder_lock:
        if _recorder is None:
            _recorder = SpanRecorder()
        return _recorder


def set_recorder(recorder: Optional[SpanRecorder]) -> None:
    global _recorder
    with _recorder_lock:
        _recorder = recorder


class _Span:
    """Context manager for one recorded span. ``attrs`` may be extended
    mid-span via :meth:`add` (e.g. row counts known only after batching)."""

    __slots__ = (
        "_name", "_attrs", "_parent_id", "_rec", "_recorder", "_note"
    )

    def __init__(
        self,
        name: str,
        attrs: Dict[str, Any],
        parent_id: Optional[int] = None,
    ):
        self._name = name
        self._attrs = attrs
        self._parent_id = parent_id
        self._rec: Optional[SpanRecord] = None
        self._recorder: Optional[SpanRecorder] = None
        self._note = None

    @property
    def span_id(self) -> Optional[int]:
        """This span's id once open — what a task handed to another
        thread passes as ``parent_id``."""
        return self._rec.span_id if self._rec is not None else None

    def add(self, **attrs) -> "_Span":
        if self._rec is not None:
            # Atomic dict swap, never in-place mutation: concurrent
            # readers (active_spans / dump_on_failure snapshotting open
            # spans) see either the old or the new attrs, and can never
            # hit "dictionary changed size during iteration".
            self._rec.attrs = {**self._rec.attrs, **attrs}
        else:
            self._attrs.update(attrs)
        return self

    def __enter__(self) -> "_Span":
        self._recorder = get_recorder()
        self._rec = self._recorder.open(
            self._name, self._attrs, self._parent_id
        )
        self._note = annotate(
            ANNOTATION_PREFIX + self._name,
            **{
                k: v
                for k, v in self._attrs.items()
                if isinstance(v, _SCALARS)
            },
        )
        self._note.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._rec is not None:
            self._note.__exit__(*exc)
            if exc and exc[0] is not None and "error" not in self._rec.attrs:
                # same atomic-swap discipline as add()
                self._rec.attrs = {
                    **self._rec.attrs,
                    "error": exc[0].__name__,
                }
            self._recorder.close(self._rec)


class _NoopSpan:
    """Shared do-nothing span for SPARKDL_OBS=0 paths."""

    __slots__ = ()
    span_id = None

    def add(self, **attrs) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NOOP = _NoopSpan()


def span(name: str, *, parent_id: Optional[int] = None, **attrs):
    """Open a span named ``name`` with initial attributes. ``parent_id``
    is the ``span_id`` of a span open on the thread that handed this one
    its work; it is used where this thread has no open span of its own.

    Usage::

        with span("ingest", partition=i) as sp:
            batch, mask = to_batch(chunk)
            sp.add(rows=int(mask.sum()), bytes=batch.nbytes)
    """
    if not obs_enabled():
        return _NOOP
    return _Span(name, attrs, parent_id)


def active_spans(recorder: Optional[SpanRecorder] = None) -> List[dict]:
    """The currently-open spans across all threads, oldest first —
    "what is this process doing right now"."""
    now = time.perf_counter()
    recorder = recorder or get_recorder()
    out = [
        {
            "name": rec.name,
            "age_s": round(now - rec.start_pc, 4),
            "thread": rec.thread_name,
            "attrs": dict(rec.attrs),
        }
        for rec in recorder.open_spans()
    ]
    out.sort(key=lambda d: -d["age_s"])
    return out


def compact_status(max_spans: int = 8, max_counters: int = 16) -> dict:
    """Small (<~1 KB) liveness payload for heartbeat beats: the open
    spans plus the top counters BY VALUE (row/byte totals dominate, and
    those are the "what was this rank chewing on" signal). Bounded so a
    beat file never balloons; the full picture lives in the ring-buffer
    snapshot."""
    snap = metrics.snapshot()
    counters = dict(
        sorted(snap["counters"].items(), key=lambda kv: -kv[1])[
            :max_counters
        ]
    )
    return {
        "active": active_spans()[:max_spans],
        "counters": counters,
    }
