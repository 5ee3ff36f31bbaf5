"""The device's idle time, split by what the host was doing.

The program's spans (`sparkdl_tpu/obs/spans.py`) are events named
`sparkdl:<span>` on the `/host:CPU` plane of the profiler's trace, on the
clock of the device's operations. `split_of(trace_dir)` takes the idle
intervals of the first device inside `bench:window` (the complement of
the merged operations, as `trace_reduce.reduce_window` takes its gaps)
and gives, for any set of span names, the seconds of idle time that the
union of those names' events covers, over all host threads.

The envelopes `executor.map_partitions` and `executor.partition` cover a
whole job and say nothing; they are left out. Idle time under no
remaining span is `unattributed`. Names overlap across threads, so the
seconds of two names may sum to more than the idle time; `unattributed`
plus the union of all names is the idle time exactly, and `split_of`
raises where it is not.

A trace with no `sparkdl:` event at all (a program that does not put its
spans on the profiler's clock) gives `None`: the readers then report
nothing.
"""

from __future__ import annotations

import functools
import os
from collections import defaultdict
from dataclasses import dataclass

from benchmarks import trace_reduce

WINDOW = "bench:window"
PREFIX = "sparkdl:"
ENVELOPES = frozenset({"executor.map_partitions", "executor.partition"})


def _seconds(intervals) -> float:
    return sum(t - s for s, t in intervals) / 1e9


def intersect(a, b) -> list:
    """The overlap of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if t > s:
            out.append((s, t))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(intervals, lo, hi) -> list:
    """What of [lo, hi] the sorted, disjoint `intervals` leave open."""
    out, at = [], lo
    for s, t in intervals:
        s, t = max(s, lo), min(t, hi)
        if t <= s:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, t)
    if hi > at:
        out.append((at, hi))
    return out


@dataclass
class IdleSplit:
    window: tuple
    #: the first device's idle intervals inside the window, nanoseconds
    idle: list
    #: span name -> merged intervals of its events, over all host threads
    spans: dict

    @property
    def idle_s(self) -> float:
        return _seconds(self.idle)

    def names(self) -> list:
        return sorted(n for n in self.spans if n not in ENVELOPES)

    def _union(self, names) -> list:
        return trace_reduce.merge(
            iv for n in names for iv in self.spans.get(n, ())
        )

    def under_s(self, *names) -> float:
        """Seconds of idle time under the union of these names' events."""
        return _seconds(intersect(self.idle, self._union(names)))

    @property
    def attributed_s(self) -> float:
        return self.under_s(*self.names())

    @property
    def unattributed_s(self) -> float:
        open_ = complement(self._union(self.names()), *self.window)
        return _seconds(intersect(self.idle, open_))

    def by_name(self) -> dict:
        return {n: self.under_s(n) for n in self.names()}


def split(events) -> IdleSplit | None:
    host = [e for e in events if e.plane == trace_reduce.HOST_PLANE]
    window = next((e for e in host if e.name == WINDOW), None)
    if window is None:
        raise ValueError(f"the trace has no host event named {WINDOW!r}")
    w0, w1 = window.start_ns, window.end_ns
    spans = defaultdict(list)
    for e in host:
        if e.name.startswith(PREFIX):
            spans[e.name[len(PREFIX):]].append((e.start_ns, e.end_ns))
    if not spans:
        return None
    planes = sorted(
        {
            e.plane
            for e in events
            if e.plane.startswith(trace_reduce.DEVICE_PLANE_PREFIX)
        }
    )
    if not planes:
        raise ValueError("the trace has no device plane")
    busy = trace_reduce.merge(
        (max(e.start_ns, w0), min(e.end_ns, w1))
        for e in events
        if e.plane == planes[0] and e.line == trace_reduce.OPS_LINE
    )
    out = IdleSplit(
        window=(w0, w1),
        idle=complement(busy, w0, w1),
        spans={n: trace_reduce.merge(iv) for n, iv in spans.items()},
    )
    if abs(out.unattributed_s + out.attributed_s - out.idle_s) > 1e-6:
        raise ValueError(
            f"idle {out.idle_s} s is not unattributed {out.unattributed_s} s "
            f"plus attributed {out.attributed_s} s"
        )
    return out


@functools.lru_cache(maxsize=1)
def split_of(trace_dir: str) -> IdleSplit | None:
    """The split of the trace under `trace_dir`, parsed once however many
    readers ask."""
    return split(trace_reduce.load_events(trace_dir))


def _split_for(ctx) -> IdleSplit | None:
    """`None` in a rehearsal and where the trace has no program span."""
    if ctx["trace"] is None:
        return None
    return split_of(os.path.join(ctx["cell"].work_dir, "trace"))


def _share(ctx, seconds: float, **beside) -> dict:
    return {
        "value": 100.0 * seconds / ctx["trace"].window_s,
        "seconds": seconds,
        **beside,
    }


def reading(ctx, *names):
    """A layer metric's value: the share of the traced window in which
    the device was idle under the union of these span names' events."""
    found = _split_for(ctx)
    return None if found is None else _share(ctx, found.under_s(*names))


def unattributed(ctx):
    """The share of the traced window in which the device was idle under
    no program span. Beside it, what holds the rest: the idle seconds
    under every span name the trace has (they overlap, so they do not add
    up), and `idle_seconds` = `seconds` + `attributed_seconds`."""
    found = _split_for(ctx)
    if found is None:
        return None
    return _share(
        ctx,
        found.unattributed_s,
        idle_seconds=found.idle_s,
        attributed_seconds=found.attributed_s,
        **{f"under.{n}": s for n, s in found.by_name().items()},
    )
