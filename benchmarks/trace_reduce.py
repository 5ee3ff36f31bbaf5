"""From the jax profiler's trace to numbers.

`load_events` flattens the `.xplane.pb` under a trace directory into
plain events (plane, line, name, start, duration; nanoseconds on the
trace's one clock). `reduce_window` takes those, the name of the host
annotation that spans the measured window and the prefix of the harness's
own annotations, and gives:

- busy time: per device the union of the intervals in which an operation
  ran (events of the device plane's operations line, clipped to the
  window), averaged over the devices;
- the time of each operation by name, and `kernel_s(part)` for the
  operations whose name holds `part`;
- the idle gaps of the first device, each named by the innermost span
  of the program (`span_prefix`, envelopes left out) that was open at
  its middle on any host thread; where none was, by the innermost
  harness annotation open there; else `unattributed`.

The reducer is tested on a hand-built trace
(`tests/benchmarks/test_trace_reduce.py`), so every PR reads the same
number in the same way.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE_PREFIX = "/device:TPU:"
#: The line of a device plane that holds one event per executed operation.
#: "XLA Modules" and "Steps" on the same plane are envelopes around them.
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(trace_dir: str) -> list:
    """Every event of the newest `.xplane.pb` under `trace_dir`."""
    import jax

    paths = sorted(
        glob.glob(
            os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
        ),
        key=os.path.getmtime,
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    return [
        Event(plane.name, line.name, ev.name, ev.start_ns, ev.duration_ns)
        for plane in data.planes
        for line in plane.lines
        for ev in line.events
    ]


def short_name(name: str) -> str:
    """`%fusion.16 = bf16[...] fusion(...)`, as the TPU's trace names an
    operation, cut to `fusion.16`; other names as they are."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def merge(intervals) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    devices: int
    #: seconds by operation name, averaged over the devices
    op_s: dict = field(default_factory=dict)
    #: (annotation name, seconds) of every idle gap of the first device
    gaps: list = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, part: str) -> float:
        return sum(s for name, s in self.op_s.items() if part in name)

    def breakdown(self, ops: int = 10, gaps: int = 5) -> dict:
        """The operations that took most time; the longest idle gaps, and
        after them the idle time summed by what the host was doing."""
        by_name = defaultdict(float)
        for name, s in self.gaps:
            by_name[f"all:{name}"] += s

        def top(pairs, n):
            return [[k, v] for k, v in sorted(pairs, key=lambda kv: -kv[1])[:n]]

        return {
            "device_ops": top(
                ((short_name(k), v) for k, v in self.op_s.items()), ops
            ),
            "idle_gaps": top(self.gaps, gaps) + top(by_name.items(), gaps),
        }


def _innermost(notes, at_ns: float, prefix: str):
    """The name, less `prefix`, of the note that started last among those
    open at `at_ns`; None where none is."""
    open_ = [a for a in notes if a.start_ns <= at_ns < a.end_ns]
    inner = max(open_, key=lambda a: a.start_ns, default=None)
    return inner.name[len(prefix):] if inner else None


def reduce_window(
    events,
    window_name: str,
    annotation_prefix: str,
    span_prefix: str | None = None,
    envelopes=frozenset(),
) -> Reduced:
    host = [e for e in events if e.plane == HOST_PLANE]
    spans = [e for e in host if e.name == window_name]
    if not spans:
        raise ValueError(f"the trace has no host event named {window_name!r}")
    w0, w1 = spans[0].start_ns, spans[0].end_ns
    planes = sorted(
        {e.plane for e in events if e.plane.startswith(DEVICE_PLANE_PREFIX)}
    )
    if not planes:
        raise ValueError(
            f"the trace has no plane named {DEVICE_PLANE_PREFIX}*: "
            "no device was traced"
        )
    busy_ns, op_ns, first_busy = 0.0, defaultdict(float), None
    for plane in planes:
        clipped = []
        for e in events:
            if e.plane != plane or e.line != OPS_LINE:
                continue
            s, t = max(e.start_ns, w0), min(e.end_ns, w1)
            if t > s:
                clipped.append((s, t))
                op_ns[e.name] += t - s
        merged = merge(clipped)
        busy_ns += sum(t - s for s, t in merged)
        if first_busy is None:
            first_busy = merged
    n = len(planes)
    notes = [e for e in host if e.name.startswith(annotation_prefix)]
    # the program's own spans say what the host was doing; the harness's
    # annotations only that a job was collecting
    spans = [
        e
        for e in host
        if span_prefix
        and e.name.startswith(span_prefix)
        and e.name[len(span_prefix):] not in envelopes
    ]
    gaps, at = [], w0
    for s, t in first_busy + [(w1, w1)]:
        if s > at:
            mid = (at + s) / 2
            name = (
                _innermost(spans, mid, span_prefix or "")
                or _innermost(notes, mid, annotation_prefix)
                or "unattributed"
            )
            gaps.append((name, (s - at) / 1e9))
        at = max(at, t)
    return Reduced(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy_ns / n / 1e9,
        devices=n,
        op_s={k: v / n / 1e9 for k, v in op_ns.items()},
        gaps=gaps,
    )
