"""The trace reducer on a hand-built trace with known busy intervals,
gaps and a named kernel; and the table of peaks."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(__file__))))

from benchmarks import peaks, trace_reduce  # noqa: E402
from benchmarks.trace_reduce import Event  # noqa: E402

MS = 1e6  # nanoseconds
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
HOST, OPS = trace_reduce.HOST_PLANE, trace_reduce.OPS_LINE


def _trace():
    """A 100 ms window from t=10 ms. Device 0 runs fusion.1 over 20..40,
    _flash_kernel over 35..50 (overlapping: the union is 20..50), and
    fusion.1 again over 105..130, of which only 105..110 is inside the
    window: busy 35 ms. Idle: 10..20 (in job), 50..105 (55 ms: its middle,
    77.5, is in collect inside job) and nothing after 110."""
    return [
        Event(HOST, "main", "bench:window", 10 * MS, 100 * MS),
        Event(HOST, "main", "bench:job", 12 * MS, 90 * MS),
        Event(HOST, "main", "bench:collect", 60 * MS, 30 * MS),
        Event(HOST, "main", "PjitFunction(f)", 12 * MS, 1 * MS),
        Event(DEV0, OPS, "fusion.1", 20 * MS, 20 * MS),
        Event(DEV0, OPS, "_flash_kernel.3", 35 * MS, 15 * MS),
        Event(DEV0, OPS, "fusion.1", 105 * MS, 25 * MS),
        # envelopes on other lines of the device plane are not operations
        Event(DEV0, "XLA Modules", "jit_f", 20 * MS, 110 * MS),
        Event(DEV0, "Steps", "0", 0, 200 * MS),
    ]


def test_busy_idle_kernel_and_gaps():
    r = trace_reduce.reduce_window(_trace(), "bench:window", "bench:")
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.100)
    assert r.busy_s == pytest.approx(0.035)
    assert 100 * r.idle_share == pytest.approx(65.0)
    assert r.kernel_s("_flash_kernel") == pytest.approx(0.015)
    assert r.op_s["fusion.1"] == pytest.approx(0.025)
    assert r.gaps == [
        ("job", pytest.approx(0.010)),
        ("collect", pytest.approx(0.055)),
    ]
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.025)]
    assert b["idle_gaps"][0] == ["collect", pytest.approx(0.055)]
    assert ["all:job", pytest.approx(0.010)] in b["idle_gaps"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_busy_is_averaged_over_devices_and_gap_outside_annotations():
    events = [e for e in _trace() if not e.name.startswith("bench:job")]
    events.append(Event(DEV1, OPS, "fusion.1", 10 * MS, 100 * MS))
    r = trace_reduce.reduce_window(events, "bench:window", "bench:job")
    assert r.devices == 2
    assert r.busy_s == pytest.approx((0.035 + 0.100) / 2)
    # device 0's gaps, with no annotation of that prefix around them
    assert {name for name, _ in r.gaps} == {"unattributed"}


def test_a_gap_is_named_by_the_programs_innermost_span_and_else_by_the_harness():
    """Three gaps of device 0 in a window 10..110: 10..20 (middle 15) under
    the program's `tokenize` on one thread inside `result_wait` on another
    and inside the envelope `executor.partition`; 50..80 (middle 65) under
    no span of the program but the envelope, inside the harness's
    `collect`; 90..110 (middle 100) under nothing but the window's own
    annotation."""
    span = lambda name, start, end, thread: Event(  # noqa: E731
        HOST, thread, "sparkdl:" + name, start * MS, (end - start) * MS
    )
    events = [
        Event(HOST, "main", "bench:window", 10 * MS, 100 * MS),
        Event(HOST, "main", "bench:job", 10 * MS, 75 * MS),
        Event(HOST, "main", "bench:collect", 12 * MS, 70 * MS),
        span("executor.partition", 11, 84, "exec_0"),
        span("result_wait", 12, 45, "exec_1"),
        span("tokenize", 13, 19, "exec_0"),
        span("ingest", 19.5, 20, "exec_0"),  # opens after the gap's middle
        span("drain_wait", 82, 84, "drainer"),  # closes before the last gap's
        Event(DEV0, OPS, "fusion.1", 20 * MS, 30 * MS),
        Event(DEV0, OPS, "fusion.2", 80 * MS, 10 * MS),
    ]
    named = trace_reduce.reduce_window(
        events, "bench:window", "bench:",
        span_prefix="sparkdl:",
        envelopes={"executor.map_partitions", "executor.partition"},
    )
    assert named.gaps == [
        ("tokenize", pytest.approx(0.010)),
        ("collect", pytest.approx(0.030)),
        ("window", pytest.approx(0.020)),
    ]
    b = named.breakdown()
    assert b["idle_gaps"][:3] == [
        ["collect", pytest.approx(0.030)],
        ["window", pytest.approx(0.020)],
        ["tokenize", pytest.approx(0.010)],
    ]
    assert ["all:tokenize", pytest.approx(0.010)] in b["idle_gaps"]
    # an envelope that is not left out covers every gap it spans, and
    # without a span prefix the harness's annotations name them, as before
    enveloped = trace_reduce.reduce_window(
        events, "bench:window", "bench:", span_prefix="sparkdl:"
    )
    assert [name for name, _ in enveloped.gaps] == ["tokenize", "executor.partition", "window"]
    plain = trace_reduce.reduce_window(events, "bench:window", "bench:")
    assert [name for name, _ in plain.gaps] == ["collect", "collect", "window"]
    assert [s for _, s in plain.gaps] == [s for _, s in named.gaps]


def test_merge_unions_overlaps_and_drops_empty():
    assert trace_reduce.merge([(5, 7), (1, 3), (2, 4), (9, 9)]) == [
        (1, 4),
        (5, 7),
    ]


@pytest.mark.parametrize(
    "events, message",
    [
        ([e for e in _trace() if e.name != "bench:window"], "no host event"),
        ([e for e in _trace() if e.plane == HOST], "no device"),
    ],
)
def test_trace_without_window_or_device_raises(events, message):
    with pytest.raises(ValueError, match=message):
        trace_reduce.reduce_window(events, "bench:window", "bench:")


def test_load_events_reads_a_profile_written_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = trace_reduce.load_events(str(tmp_path))
    assert any(
        e.plane == HOST and e.name == "bench:window" and e.dur_ns > 0
        for e in events
    )
    # a CPU trace has no device plane: the reducer says so, it does not
    # hand back an idle share
    with pytest.raises(ValueError, match="no device"):
        trace_reduce.reduce_window(events, "bench:window", "bench:")
    with pytest.raises(FileNotFoundError):
        trace_reduce.load_events(str(tmp_path / "empty"))


def test_peaks_known_and_unknown_device_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no peaks for device kind 'cpu'"):
        peaks.peaks_for("cpu")
