"""Structured runtime metrics.

Reference analogue: none in-tree — the reference exposed progress only
through the Spark UI's stage/task counters (SURVEY.md §6). Here metrics
are first-class: transformers and estimators record counters/timers into a
process-global registry, and the throughput numbers that bench.py
reports (images/sec/chip, step time) are computed from these.

Thread-safe: executor partition threads and the feeders' owner threads all
record concurrently.
"""

from __future__ import annotations

import random
import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional

#: Bounded per-timer sample reservoir: percentiles stay O(1) memory no
#: matter how many batches a long-running worker records. 512 samples
#: put the p99 estimate's error well under batch-to-batch noise.
RESERVOIR_SIZE = 512


def percentile_of_sorted(sorted_vals: List[float], q: float) -> float:
    """Linear-interpolation percentile (q in [0, 100]) over PRE-SORTED
    values — the one definition shared by timer reservoirs and the obs
    report, so the two views can only differ by reservoir error, never
    by interpolation method."""
    if not sorted_vals:
        return 0.0
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    pos = q / 100.0 * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac


@dataclass
class TimerStat:
    count: int = 0
    total_s: float = 0.0
    min_s: float = float("inf")
    max_s: float = 0.0
    samples: List[float] = field(default_factory=list, repr=False)
    _rng: Any = field(default=None, repr=False, compare=False)

    def record(self, dt: float) -> None:
        self.count += 1
        self.total_s += dt
        self.min_s = min(self.min_s, dt)
        self.max_s = max(self.max_s, dt)
        # Algorithm R reservoir: exact below RESERVOIR_SIZE, uniform
        # sample of the whole stream above it. Seeded per-stat so a
        # replayed run reproduces its percentiles bit-for-bit.
        if len(self.samples) < RESERVOIR_SIZE:
            self.samples.append(dt)
        else:
            if self._rng is None:
                self._rng = random.Random(0xC0FFEE)
            j = self._rng.randrange(self.count)
            if j < RESERVOIR_SIZE:
                self.samples[j] = dt

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0

    def percentile(self, q: float) -> float:
        """Percentile over the reservoir — exact when count <=
        RESERVOIR_SIZE, a uniform-sample estimate above."""
        return percentile_of_sorted(sorted(self.samples), q)

    def as_dict(self) -> dict:
        # Existing keys are a stable contract (bench.py stage_ms et al.);
        # percentiles are additive. One sort serves all three quantiles —
        # as_dict runs under the registry lock during snapshot().
        vals = sorted(self.samples)
        return {
            "count": self.count,
            "total_s": self.total_s,
            "mean_s": self.mean_s,
            "min_s": self.min_s if self.count else 0.0,
            "max_s": self.max_s,
            "p50_s": percentile_of_sorted(vals, 50),
            "p95_s": percentile_of_sorted(vals, 95),
            "p99_s": percentile_of_sorted(vals, 99),
            # The reservoir itself rides the snapshot (sorted, rounded to
            # 100 ns) so cross-rank tooling can MERGE timers with real
            # count-weighted resampling instead of averaging percentiles.
            "samples": [round(v, 7) for v in vals],
        }

    def merge(self, other: "TimerStat") -> "TimerStat":
        """Count-weighted combination of two stats into a NEW TimerStat.
        Thin wrapper over :func:`merge_timer_dicts` — one resampling
        implementation, whether the inputs are live objects or snapshot
        payloads. Neither input is mutated — safe on registry objects."""
        d = merge_timer_dicts([self.as_dict(), other.as_dict()])
        out = TimerStat()
        out.count = d["count"]
        out.total_s = d["total_s"]
        out.min_s = d["min_s"] if d["count"] else float("inf")
        out.max_s = d["max_s"]
        out.samples = list(d["samples"])
        return out


def merge_timer_dicts(dicts: Iterable[dict]) -> dict:
    """Count-weighted combination of ``TimerStat.as_dict()`` payloads —
    the cross-rank merge primitive for ``obs aggregate`` (each gang rank
    snapshots its registry independently; fleet percentiles need one
    combined view). Counts, totals, and min/max combine exactly. When
    payloads carry their reservoirs (``samples``, present since this
    schema), merged percentiles come from a count-weighted re-reservoir;
    payloads without samples fall back to a count-weighted mean of the
    per-payload percentiles (an approximation, flagged nowhere — old
    snapshots only)."""
    dicts = [d for d in dicts if d and d.get("count")]
    total_count = sum(int(d["count"]) for d in dicts)
    if not total_count:
        return {
            "count": 0, "total_s": 0.0, "mean_s": 0.0, "min_s": 0.0,
            "max_s": 0.0, "p50_s": 0.0, "p95_s": 0.0, "p99_s": 0.0,
            "samples": [],
        }
    total_s = sum(float(d.get("total_s", 0.0)) for d in dicts)
    out = {
        "count": total_count,
        "total_s": total_s,
        "mean_s": total_s / total_count,
        "min_s": min(float(d.get("min_s", 0.0)) for d in dicts),
        "max_s": max(float(d.get("max_s", 0.0)) for d in dicts),
    }
    if all(d.get("samples") for d in dicts):
        rng = random.Random(0xC0FFEE)
        merged: List[float] = []
        for d in dicts:
            samples = list(d["samples"])
            want = max(1, round(RESERVOIR_SIZE * d["count"] / total_count))
            if len(samples) <= want:
                merged.extend(samples)
            else:
                merged.extend(rng.sample(samples, want))
        if len(merged) > RESERVOIR_SIZE:
            merged = rng.sample(merged, RESERVOIR_SIZE)
        vals = sorted(merged)
        out["samples"] = vals
        for q, key in ((50, "p50_s"), (95, "p95_s"), (99, "p99_s")):
            out[key] = percentile_of_sorted(vals, q)
    else:
        out["samples"] = []
        for key in ("p50_s", "p95_s", "p99_s"):
            out[key] = (
                sum(float(d.get(key, 0.0)) * d["count"] for d in dicts)
                / total_count
            )
    return out


class WindowedCounter:
    """Time-bucketed event counter: the rolling-window half of SLO
    burn-rate math. Events land in coarse buckets (``bucket_s`` wide)
    and a read sums only the buckets inside the asked-for window, so
    one structure answers BOTH the fast (~1 min) and slow (~1 hr)
    windows of a multi-window burn-rate pair — the windows are just
    different read spans over the same ring.

    Deterministic by construction: every method takes an explicit
    ``now`` (``time.monotonic()`` when omitted), so a frozen-clock test
    replays bit-identically. NOT internally locked — callers (the SLO
    engine) serialize access under their own lock, the
    ``_recent_latency`` deque discipline."""

    def __init__(self, horizon_s: float, bucket_s: float):
        self.horizon_s = float(horizon_s)
        self.bucket_s = max(1e-6, float(bucket_s))
        self._buckets: Dict[int, float] = {}

    def _index(self, now: float) -> int:
        return int(now / self.bucket_s)

    def _prune(self, now: float) -> None:
        # drop whole buckets older than the horizon — the time-decay:
        # an event never fades gradually, its bucket expires wholesale
        floor = self._index(now - self.horizon_s)
        for idx in [i for i in self._buckets if i < floor]:
            del self._buckets[idx]

    def add(self, n: float = 1.0, now: Optional[float] = None) -> None:
        t = time.monotonic() if now is None else float(now)
        self._prune(t)
        idx = self._index(t)
        self._buckets[idx] = self._buckets.get(idx, 0.0) + float(n)

    def total(
        self, window_s: float, now: Optional[float] = None
    ) -> float:
        """Events in the trailing ``window_s`` (capped at the horizon).
        Bucket granularity: a bucket counts while ANY of it overlaps
        the window, so reads are conservative by up to one bucket."""
        t = time.monotonic() if now is None else float(now)
        self._prune(t)
        floor = self._index(t - min(float(window_s), self.horizon_s))
        return sum(v for i, v in self._buckets.items() if i >= floor)

    def clear(self) -> None:
        self._buckets.clear()


class WindowedReservoir:
    """Timestamped variant of the recent-latency window: per-bucket
    Algorithm R reservoirs under a shared time-bucket ring, so a
    windowed percentile (the SLO engine's live per-window p95) decays
    by TIME — a burst from twenty minutes ago ages out of a one-minute
    window entirely — instead of by observation count the way the
    ``_recent_latency`` deque does. Exact below ``cap_per_bucket``
    observations per bucket, a seeded uniform sample above (the
    :class:`TimerStat` discipline, so replays reproduce percentiles
    bit-for-bit). Same determinism/locking contract as
    :class:`WindowedCounter`: explicit ``now``, externally
    synchronized."""

    def __init__(
        self,
        horizon_s: float,
        bucket_s: float,
        cap_per_bucket: int = 128,
    ):
        self.horizon_s = float(horizon_s)
        self.bucket_s = max(1e-6, float(bucket_s))
        self.cap = max(1, int(cap_per_bucket))
        #: bucket index -> [count, samples list, rng]
        self._buckets: Dict[int, list] = {}

    def _index(self, now: float) -> int:
        return int(now / self.bucket_s)

    def _prune(self, now: float) -> None:
        floor = self._index(now - self.horizon_s)
        for idx in [i for i in self._buckets if i < floor]:
            del self._buckets[idx]

    def note(self, value: float, now: Optional[float] = None) -> None:
        t = time.monotonic() if now is None else float(now)
        self._prune(t)
        idx = self._index(t)
        b = self._buckets.get(idx)
        if b is None:
            b = self._buckets[idx] = [0, [], None]
        b[0] += 1
        if len(b[1]) < self.cap:
            b[1].append(float(value))
        else:
            if b[2] is None:
                b[2] = random.Random(0xC0FFEE ^ idx)
            j = b[2].randrange(b[0])
            if j < self.cap:
                b[1][j] = float(value)

    def _window_buckets(self, window_s: float, now: float) -> list:
        self._prune(now)
        floor = self._index(now - min(float(window_s), self.horizon_s))
        return [b for i, b in self._buckets.items() if i >= floor]

    def count(
        self, window_s: float, now: Optional[float] = None
    ) -> int:
        """TRUE observation count in the window (reservoir caps bound
        memory, not the count)."""
        t = time.monotonic() if now is None else float(now)
        return sum(b[0] for b in self._window_buckets(window_s, t))

    def values(
        self, window_s: float, now: Optional[float] = None
    ) -> List[float]:
        t = time.monotonic() if now is None else float(now)
        out: List[float] = []
        for b in self._window_buckets(window_s, t):
            out.extend(b[1])
        return out

    def percentile(
        self, q: float, window_s: float, now: Optional[float] = None
    ) -> Optional[float]:
        """Windowed percentile over the retained samples, or None when
        the window holds nothing. Count-weighting is implicit: each
        bucket retains up to ``cap`` samples of its own stream, so a
        busy bucket is represented by a denser sample, not a louder
        voice per observation."""
        vals = sorted(self.values(window_s, now))
        if not vals:
            return None
        return percentile_of_sorted(vals, q)

    def clear(self) -> None:
        self._buckets.clear()


class Timer:
    """Context manager recording wall time into a registry timer."""

    def __init__(self, registry: "MetricsRegistry", name: str):
        self._registry = registry
        self._name = name
        self._t0 = 0.0

    def __enter__(self) -> "Timer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._registry.record_time(
            self._name, time.perf_counter() - self._t0
        )


class MetricsRegistry:
    """Counters, gauges, and timers keyed by dotted names."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = defaultdict(float)
        self._gauges: Dict[str, float] = {}
        #: per-gauge [last, min, max] — a gauge write used to silently
        #: overwrite, so a burst (feeder.queue_depth spiking to 40) was
        #: invisible in any snapshot taken after it drained. The envelope
        #: keeps the burst observable; ``gauges`` itself stays last-write
        #: (stable snapshot contract).
        self._gauge_stats: Dict[str, List[float]] = {}
        self._timers: Dict[str, TimerStat] = defaultdict(TimerStat)

    # -- recording ----------------------------------------------------------

    def inc(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self._counters[name] += value

    def gauge(self, name: str, value: float) -> None:
        value = float(value)
        with self._lock:
            self._gauges[name] = value
            st = self._gauge_stats.get(name)
            if st is None:
                self._gauge_stats[name] = [value, value, value]
            else:
                st[0] = value
                if value < st[1]:
                    st[1] = value
                if value > st[2]:
                    st[2] = value

    def record_time(self, name: str, seconds: float) -> None:
        with self._lock:
            self._timers[name].record(seconds)

    def record_times(self, name: str, seconds_list) -> None:
        """Bulk form of :meth:`record_time`: one lock acquisition for a
        whole group's observations — the serving router records
        per-request queue/group waits group-at-a-time through this, so
        tracing adds O(groups) lock traffic, not O(requests)."""
        if not seconds_list:
            return
        with self._lock:
            stat = self._timers[name]
            for s in seconds_list:
                stat.record(s)

    def timer(self, name: str) -> Timer:
        return Timer(self, name)

    # -- reading ------------------------------------------------------------

    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0.0)

    def timing(self, name: str) -> Optional[TimerStat]:
        with self._lock:
            return self._timers.get(name)

    def rate(self, counter_name: str, timer_name: str) -> float:
        """counter / total timer seconds — e.g. images/sec from
        (images_processed, device_time)."""
        with self._lock:
            c = self._counters.get(counter_name, 0.0)
            t = self._timers.get(timer_name)
        total = t.total_s if t else 0.0
        return c / total if total > 0 else 0.0

    def gauge_stats(self, name: str) -> Optional[dict]:
        """``{"last", "min", "max"}`` envelope for one gauge, or None."""
        with self._lock:
            st = self._gauge_stats.get(name)
            return (
                {"last": st[0], "min": st[1], "max": st[2]} if st else None
            )

    def scalar_snapshot(self) -> dict:
        """Counters, gauges, and per-timer counts only — no reservoir
        sorting or sample materialization under the lock. The view for
        high-frequency readers (the 1 Hz time-series sampler) that only
        consume scalar values; ``snapshot()`` stays the full export."""
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "timer_counts": {
                    k: v.count for k, v in self._timers.items()
                },
            }

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "gauge_stats": {
                    k: {"last": v[0], "min": v[1], "max": v[2]}
                    for k, v in self._gauge_stats.items()
                },
                "timers": {k: v.as_dict() for k, v in self._timers.items()},
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._gauge_stats.clear()
            self._timers.clear()


#: Process-global registry used by transformers/estimators by default.
metrics = MetricsRegistry()
