"""The device's busy time, split by the program's own names.

The device's counterpart of `host_spans.py`. The text families put a
`jax.named_scope("sparkdl:<part>")` around every part of their programs
(`sparkdl_tpu/utils/profiler.py:scope`), so the `op_name` of a lowered
operation is a path such as
`jit(fn)/sparkdl:moe.routed/cond/branch_0_fun/sparkdl:moe.gather/gather`.
`by_scope_of(trace_dir)` gives, for the first device inside
`bench:window`, the SELF time of every operation under the innermost
`sparkdl:` part of its path (`unscoped` under none), and for every scope
the time of the operations that carry it at any level.

Where an operation's `op_name` comes from: a device event of the trace is
named by its instruction's text, which holds no metadata, and
`jax.profiler.ProfileData` hands out the event's own stats (its offset
and duration) and not those of its metadata. The profiler stores each
executable's optimized HLO in the same `.xplane.pb`, on the
`/host:metadata` plane under the program's id, and every device event
says its program's id: the events are joined to the instructions by
program and instruction name, through the file's own protocol buffers
(`tensorflow.tsl.profiler.protobuf.xplane_pb2`,
`tensorflow.compiler.xla.service.hlo_pb2`). That shows the inside of a
fusion too.

Self time: a conditional, and a loop, is an event around its branch's
and its body's events. An interval of the window belongs to the event
that started last among those open in it, so an event keeps its time
less what it contains, an overlap without nesting is counted once, under
the later event, and the scopes and `unscoped` add up to the device's
busy time: `ByScope.check` raises where they do not within 0.1%.

A fusion is counted under the scope its own `op_name` carries. Where the
instructions fused into it carry more than one scope (a product with the
next block's norm in it), its seconds are also summed as that scope's
`mixed` seconds: how soft the scope's edges are.

A trace whose programs carry no `sparkdl:` scope (a checkout from before
the scopes) gives `None`, and so does a rehearsal, which has no trace:
the readers then report nothing.
"""

from __future__ import annotations

import functools
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

from benchmarks import trace_reduce
from benchmarks.host_spans import PREFIX, WINDOW

UNSCOPED = "unscoped"
METADATA_PLANE = "/host:metadata"
#: the stat of a program's entry on the metadata plane that holds its HLO
HLO_STAT = "Hlo Proto"
#: scopes + unscoped against busy, as a share of busy
TOLERANCE = 1e-3


@dataclass(frozen=True)
class Op:
    """One event of a device's operations line."""

    program: int
    #: the instruction's name, `fusion.12`
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass(frozen=True)
class Instruction:
    op_name: str
    #: for a fusion, the `op_name` of every instruction fused into it
    fused: tuple = ()


def scopes_in(op_name: str) -> tuple:
    """The `sparkdl:` parts of an `op_name`, the outermost first."""
    return tuple(
        part[len(PREFIX):] for part in op_name.split("/") if part.startswith(PREFIX)
    )


def innermost(op_name: str) -> str:
    found = scopes_in(op_name)
    return found[-1] if found else UNSCOPED


def self_ns(intervals) -> list:
    """For (start, end) intervals in any order, how much of each no
    interval that started later covers; an interval that starts where
    another does is the later of the two if it ends first."""
    order = sorted(
        range(len(intervals)), key=lambda i: (intervals[i][0], -intervals[i][1])
    )
    out, open_, at = [0.0] * len(intervals), [], float("-inf")

    def run_to(to):
        nonlocal at
        while open_ and at < to:
            top = open_[-1]
            upto = min(intervals[top][1], to)
            if upto > at:
                out[top] += upto - at
                at = upto
            if intervals[top][1] <= at:
                open_.pop()
        at = to

    for i in order:
        run_to(intervals[i][0])
        open_.append(i)
    run_to(float("inf"))
    return out


@dataclass
class ByScope:
    busy_s: float
    #: seconds under each innermost scope, `unscoped` among them
    self_s: dict = field(default_factory=dict)
    #: seconds of the operations that carry a scope at any level
    any_s: dict = field(default_factory=dict)
    #: of `self_s`, the seconds of fusions that hold more than one scope
    mixed_s: dict = field(default_factory=dict)
    #: seconds of the operations under no scope, by the kind of their
    #: instruction: its name less the compiler's number (`copy-done`)
    unscoped_ops: dict = field(default_factory=dict)

    def seconds(self, *names) -> float:
        return sum(self.self_s.get(n, 0.0) for n in names)

    def mixed_seconds(self, *names) -> float:
        return sum(self.mixed_s.get(n, 0.0) for n in names)

    def names(self, prefix: str = "") -> list:
        return sorted(
            n for n in self.self_s if n != UNSCOPED and n.startswith(prefix)
        )

    def check(self) -> "ByScope":
        total = sum(self.self_s.values())
        if abs(total - self.busy_s) > TOLERANCE * self.busy_s:
            raise ValueError(
                f"the scopes and {UNSCOPED} sum to {total} s, "
                f"the device's busy time is {self.busy_s} s"
            )
        return self


def by_scope(ops, programs, window) -> ByScope | None:
    """`ops`: the `Op`s of one device; `programs`: {program id:
    {instruction name: Instruction}}; `window`: (start, end) in
    nanoseconds. None where no instruction of the programs that ran
    carries a scope."""
    w0, w1 = window
    clipped = [(op, max(op.start_ns, w0), min(op.end_ns, w1)) for op in ops]
    clipped = [(op, s, t) for op, s, t in clipped if t > s]
    ran = {op.program for op, _, _ in clipped}
    if not any(
        scopes_in(i.op_name)
        for p in ran
        for i in programs.get(p, {}).values()
    ):
        return None
    intervals = [(s, t) for _, s, t in clipped]

    @functools.lru_cache(maxsize=None)
    def read(program, name):
        """(innermost scope, every scope, whether a fusion of several) of
        an instruction; one the program's text does not hold has none."""
        found = programs.get(program, {}).get(name, Instruction(""))
        # what is fused in under no scope is a weight, named after the
        # program's argument, or the compiler's own
        inside = {scopes_in(n)[-1:] for n in found.fused} - {()}
        return innermost(found.op_name), set(scopes_in(found.op_name)), len(inside) > 1

    self_s, any_s, mixed_s, unscoped_ops = (defaultdict(float) for _ in range(4))
    for (op, _, _), ns in zip(clipped, self_ns(intervals)):
        scope, every, mixed = read(op.program, op.name)
        s = ns / 1e9
        self_s[scope] += s
        for name in every:
            any_s[name] += s
        if mixed:
            mixed_s[scope] += s
        if scope == UNSCOPED:
            unscoped_ops[op.name.split(".")[0]] += s
    return ByScope(
        busy_s=sum(t - s for s, t in trace_reduce.merge(intervals)) / 1e9,
        self_s=dict(self_s),
        any_s=dict(any_s),
        mixed_s=dict(mixed_s),
        unscoped_ops=dict(unscoped_ops),
    ).check()


def _newest_xplane(trace_dir: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stats(plane, metadata) -> dict:
    """{stat name: value} of an event's metadata."""
    return {
        plane.stat_metadata[s.metadata_id].name: getattr(s, s.WhichOneof("value"))
        for s in metadata.stats
    }


def _instructions(hlo_bytes: bytes) -> dict:
    from tensorflow.compiler.xla.service import hlo_pb2

    proto = hlo_pb2.HloProto()
    proto.ParseFromString(hlo_bytes)
    computations = {c.id: c for c in proto.hlo_module.computations}
    table = {}
    for computation in computations.values():
        for ins in computation.instructions:
            fused = ()
            if ins.opcode == "fusion":
                fused = tuple(
                    i.metadata.op_name
                    for i in computations[ins.called_computation_ids[0]].instructions
                )
            table[ins.name] = Instruction(ins.metadata.op_name, fused)
    return table


def load(trace_dir: str):
    """(ops of the first device, programs, window) of the newest
    `.xplane.pb` under `trace_dir`, as `by_scope` takes them. Only the
    programs that have an event on the device are parsed."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(_newest_xplane(trace_dir), "rb") as f:
        space.ParseFromString(f.read())
    planes = {p.name: p for p in space.planes}

    def events(plane, line_name=None):
        for line in plane.lines:
            if line_name is None or line.name == line_name:
                for ev in line.events:
                    start = line.timestamp_ns + ev.offset_ps / 1e3
                    yield plane.event_metadata[ev.metadata_id], start, ev.duration_ps / 1e3

    window = next(
        (
            (start, start + dur)
            for md, start, dur in events(planes[trace_reduce.HOST_PLANE])
            if md.name == WINDOW
        ),
        None,
    )
    if window is None:
        raise ValueError(f"the trace has no host event named {WINDOW!r}")
    devices = sorted(n for n in planes if n.startswith(trace_reduce.DEVICE_PLANE_PREFIX))
    if not devices:
        raise ValueError("the trace has no device plane")
    device, of_metadata, ops = planes[devices[0]], {}, []
    for md, start, dur in events(device, trace_reduce.OPS_LINE):
        if md.id not in of_metadata:
            of_metadata[md.id] = (
                int(_stats(device, md).get("program_id", 0)),
                md.display_name or trace_reduce.short_name(md.name),
            )
        ops.append(Op(*of_metadata[md.id], start, dur))
    ran, programs = {op.program for op in ops}, {}
    holder = planes.get(METADATA_PLANE)
    for key, md in (holder.event_metadata.items() if holder else ()):
        program = key % 2**64  # the map's keys are signed, a program's id is not
        if program in ran:
            hlo = _stats(holder, md).get(HLO_STAT)
            if hlo:
                programs[program] = _instructions(hlo)
    return ops, programs, window


@functools.lru_cache(maxsize=1)
def by_scope_of(trace_dir: str) -> ByScope | None:
    """The split of the trace under `trace_dir`, parsed once however many
    readers ask."""
    return by_scope(*load(trace_dir))


def reading(ctx) -> ByScope | None:
    """`None` in a rehearsal and where the programs carry no scope."""
    if ctx["trace"] is None:
        return None
    return by_scope_of(os.path.join(ctx["cell"].work_dir, "trace"))


def per_thousand(ctx, seconds: float, count, **beside):
    """A layer metric's value: milliseconds of the first device for every
    thousand of `count`, a sum of the program's counters over the window;
    `None` where the window counted none or the scopes took no time."""
    if count <= 0 or seconds <= 0:
        return None
    return {
        "value": 1e3 * seconds * ctx["chips"] / (count / 1e3),
        "seconds": seconds,
        **beside,
    }
