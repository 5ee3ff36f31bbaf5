"""The split of the device's idle time by the program's spans, on
hand-built events with known gaps; and the five readers over it."""

import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(__file__))))

from benchmarks import host_spans, trace_reduce  # noqa: E402
from benchmarks.run import load_reader  # noqa: E402
from benchmarks.trace_reduce import Event  # noqa: E402

MS = 1e6  # nanoseconds
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
HOST, OPS = trace_reduce.HOST_PLANE, trace_reduce.OPS_LINE

READERS = {
    "text.tokenize_idle_pct": ("tokenize",),
    "host.ingest_idle_pct": ("ingest",),
    "readback.drain_idle_pct": ("drain_wait", "device_wait"),
    "collect.box_idle_pct": ("collect.box",),
    "host.unattributed_idle_pct": (),
}


def _span(name, start_ms, end_ms, thread="exec_0"):
    return Event(
        HOST, thread, "sparkdl:" + name, start_ms * MS, (end_ms - start_ms) * MS
    )


def _trace(*spans):
    """A 100 ms window from t=10 ms. Device 0 runs over 20..40 and 60..100
    (and 105..130 on another line, which is no operation): idle 10..20,
    40..60 and 100..110, 40 ms in all. Device 1 is busy throughout and is
    not the device whose gaps are split."""
    return [
        Event(HOST, "main", "bench:window", 10 * MS, 100 * MS),
        Event(HOST, "main", "bench:collect", 12 * MS, 90 * MS),
        Event(DEV0, OPS, "fusion.1", 20 * MS, 20 * MS),
        Event(DEV0, OPS, "fusion.2", 60 * MS, 30 * MS),
        Event(DEV0, OPS, "fusion.3", 85 * MS, 15 * MS),
        Event(DEV0, "XLA Modules", "jit_f", 105 * MS, 25 * MS),
        Event(DEV1, OPS, "fusion.1", 0, 200 * MS),
        *spans,
    ]


CASES = {
    "a span over a whole gap": (
        [_span("tokenize", 38, 62)],
        {"tokenize": 20},
        20,
    ),
    "half a gap": ([_span("tokenize", 50, 60)], {"tokenize": 10}, 30),
    "two names on two threads over one gap": (
        [_span("tokenize", 40, 55), _span("ingest", 50, 60, "exec_1")],
        {"tokenize": 15, "ingest": 10},  # 25 between them, 20 of idle
        20,
    ),
    "one name on two threads is a union": (
        [_span("tokenize", 40, 50), _span("tokenize", 45, 60, "exec_1")],
        {"tokenize": 20},
        20,
    ),
    "an envelope alone": (
        [
            _span("executor.map_partitions", 0, 200, "main"),
            _span("executor.partition", 5, 150),
        ],
        {},
        40,
    ),
    "an envelope over a span": (
        [_span("executor.partition", 5, 150), _span("result_wait", 15, 45)],
        {"result_wait": 10},
        30,
    ),
    "a span outside the window": (
        [_span("collect.box", 0, 8, "main"), _span("collect.box", 112, 130, "main")],
        {"collect.box": 0},
        40,
    ),
    "a span across the window's end": (
        [_span("collect.box", 105, 130, "main")],
        {"collect.box": 5},
        35,
    ),
    "either wait is the readback": (
        [_span("drain_wait", 10, 15, "drainer"), _span("device_wait", 100, 104)],
        {"drain_wait": 5, "device_wait": 4},
        31,
    ),
}


@pytest.mark.parametrize("case", CASES)
def test_idle_seconds_by_span_and_conservation(case):
    spans, by_name, unattributed_ms = CASES[case]
    found = host_spans.split(_trace(*spans))
    assert found.idle_s == pytest.approx(0.040)
    assert found.by_name() == {
        name: pytest.approx(ms / 1e3, abs=1e-12) for name, ms in by_name.items()
    }
    assert found.unattributed_s == pytest.approx(unattributed_ms / 1e3)
    assert found.unattributed_s + found.attributed_s == pytest.approx(
        found.idle_s, abs=1e-9
    )
    # the same idle time as the accepted reducer's
    reduced = trace_reduce.reduce_window(_trace(*spans), "bench:window", "bench:")
    assert sum(s for _, s in reduced.gaps) == pytest.approx(found.idle_s)


def test_no_program_span_gives_nothing():
    assert host_spans.split(_trace()) is None


@pytest.mark.parametrize(
    "events, message",
    [
        ([e for e in _trace() if e.name != "bench:window"], "no host event"),
        (
            [e for e in _trace(_span("ingest", 1, 2)) if e.plane == HOST],
            "no device",
        ),
    ],
)
def test_trace_without_window_or_device_raises(events, message):
    with pytest.raises(ValueError, match=message):
        host_spans.split(events)


def _ctx(tmp_path, traced=True):
    return {
        "trace": SimpleNamespace(window_s=0.100) if traced else None,
        "cell": SimpleNamespace(work_dir=str(tmp_path)),
    }


@pytest.fixture
def loads(monkeypatch):
    """`load_events` stands in for a trace on disk; counts its calls."""
    calls = []

    def install(events):
        def load_events(trace_dir):
            calls.append(trace_dir)
            return events

        monkeypatch.setattr(trace_reduce, "load_events", load_events)
        host_spans.split_of.cache_clear()
        return calls

    yield install
    host_spans.split_of.cache_clear()


def test_five_readers_parse_one_trace_once(tmp_path, loads):
    calls = loads(
        _trace(
            _span("executor.partition", 5, 150),
            _span("tokenize", 40, 55),
            _span("ingest", 50, 58, "exec_1"),
            _span("device_wait", 100, 104, "drainer"),
            _span("collect.box", 106, 120, "main"),
        )
    )
    ctx = _ctx(tmp_path)
    got = {name: load_reader(name)(ctx) for name in READERS}
    assert calls == [os.path.join(str(tmp_path), "trace")]
    seconds = {name: v["seconds"] for name, v in got.items()}
    assert seconds == {
        "text.tokenize_idle_pct": pytest.approx(0.015),
        "host.ingest_idle_pct": pytest.approx(0.008),
        "readback.drain_idle_pct": pytest.approx(0.004),
        "collect.box_idle_pct": pytest.approx(0.004),
        "host.unattributed_idle_pct": pytest.approx(0.014),
    }
    for name, v in got.items():
        assert v["value"] == pytest.approx(100 * v["seconds"] / 0.100), name
    rest = got["host.unattributed_idle_pct"]
    assert rest["idle_seconds"] == pytest.approx(0.040)
    assert rest["seconds"] + rest["attributed_seconds"] == pytest.approx(0.040)
    assert rest["under.tokenize"] == pytest.approx(0.015)
    assert "under.executor.partition" not in rest


@pytest.mark.parametrize("name", READERS)
def test_reader_reports_nothing_without_program_spans(tmp_path, loads, name):
    """The parent of the PR that brought the spans: a trace, no `sparkdl:`
    event. The reader returns nothing and does not raise."""
    loads(_trace())
    assert load_reader(name)(_ctx(tmp_path)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_for_a_rehearsal(tmp_path, name):
    # no trace is reduced on the CPU, and none is looked for
    assert load_reader(name)(_ctx(tmp_path, traced=False)) is None


def test_every_reader_is_in_the_benchmark_for_the_text_cell():
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        check_span_readers(json.load(f))


def check_span_readers(bench):
    """A function of `bench`, so that `test_benchmark_grows.py` can put a
    copy with a fifth configuration through it."""
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert per_layer[name]["source"] == "program_span"
        assert per_layer[name]["unit"] == "%"
        assert per_layer[name]["moves"] == "rows_per_s"
