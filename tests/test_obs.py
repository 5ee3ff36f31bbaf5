"""Flight-recorder units: span nesting (including across threads), ring
bounds, snapshot/Chrome-trace export schema, dump-on-failure, heartbeat
obs payloads, the report CLI round-trip, the CPU end-to-end
acceptance path (ingest/h2d/dispatch/device_wait spans from the real
batched engine), and the spans as `sparkdl:` events of a jax.profiler
trace."""

import contextlib
import glob
import json
import os
import threading

import numpy as np
import pytest

from sparkdl_tpu import obs
from sparkdl_tpu.obs import export, report
from sparkdl_tpu.obs.spans import SpanRecorder, set_recorder, span
from sparkdl_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True)
def fresh_recorder():
    """Isolated ring per test (the global recorder is process-wide)."""
    rec = SpanRecorder(capacity=4096)
    set_recorder(rec)
    yield rec
    set_recorder(None)


# -- span model -------------------------------------------------------------


def test_span_nesting_and_attrs(fresh_recorder):
    with span("outer", partition=3):
        with span("inner") as sp:
            sp.add(rows=7, bytes=128)
    spans = fresh_recorder.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    inner, outer = spans
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert inner.attrs == {"rows": 7, "bytes": 128}
    assert outer.attrs == {"partition": 3}
    assert inner.dur_s <= outer.dur_s
    # spans double as registry timers + rows/bytes counters
    assert metrics.timing("span.inner").count >= 1
    assert metrics.counter("span.inner.rows") >= 7


def test_span_nesting_across_threads(fresh_recorder):
    """Each thread nests on its OWN stack: a child's parent is always the
    innermost open span of its own thread, never another thread's."""
    barrier = threading.Barrier(2)

    def work(tag):
        with span(f"outer.{tag}"):
            barrier.wait(timeout=10)  # both outers open simultaneously
            with span(f"inner.{tag}"):
                pass

    threads = [
        threading.Thread(target=work, args=(t,)) for t in ("a", "b")
    ]
    [t.start() for t in threads]
    [t.join() for t in threads]
    by_name = {s.name: s for s in fresh_recorder.spans()}
    assert len(by_name) == 4
    for tag in ("a", "b"):
        inner, outer = by_name[f"inner.{tag}"], by_name[f"outer.{tag}"]
        assert inner.parent_id == outer.span_id
        assert inner.thread_id == outer.thread_id
    assert by_name["outer.a"].thread_id != by_name["outer.b"].thread_id


def test_ring_buffer_is_bounded():
    rec = SpanRecorder(capacity=8)
    set_recorder(rec)
    for i in range(20):
        with span(f"s{i}"):
            pass
    spans = rec.spans()
    assert len(spans) == 8  # oldest 12 fell off the back
    assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]


def test_obs_disabled_records_nothing(fresh_recorder, monkeypatch):
    monkeypatch.setenv("SPARKDL_OBS", "0")
    with span("ghost") as sp:
        sp.add(rows=1)  # noop span accepts the same API
    assert fresh_recorder.spans() == []


def test_exception_exit_tags_span(fresh_recorder):
    with pytest.raises(ValueError):
        with span("doomed"):
            raise ValueError("boom")
    (rec,) = fresh_recorder.spans()
    assert rec.attrs["error"] == "ValueError"


def test_active_spans_visible_while_open(fresh_recorder):
    with span("long.task", partition=5):
        active = obs.active_spans()
        assert [a["name"] for a in active] == ["long.task"]
        assert active[0]["attrs"]["partition"] == 5
    assert obs.active_spans() == []


# -- exports ----------------------------------------------------------------


def test_snapshot_schema(fresh_recorder):
    with span("stage.x", rows=4):
        pass
    snap = export.snapshot()
    assert snap["schema"] == 1
    assert snap["pid"] == os.getpid()
    assert {"counters", "gauges", "timers"} <= set(snap["metrics"])
    (sp,) = snap["spans"]
    assert sp["name"] == "stage.x"
    assert sp["dur_s"] >= 0 and sp["start_unix"] > 0
    json.dumps(snap)  # fully JSON-serializable


def test_chrome_trace_schema(fresh_recorder, tmp_path):
    with span("outer"):
        with span("inner", bytes=64):
            pass
    path = export.write_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        trace = json.load(f)  # loads as valid JSON — the documented bar
    events = trace["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"outer", "inner"}
    for e in complete:
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert "span_id" in e["args"]
    # inner nests inside outer on the timeline
    by = {e["name"]: e for e in complete}
    assert by["outer"]["ts"] <= by["inner"]["ts"]
    assert trace["displayTimeUnit"] == "ms"
    # thread-name metadata present for Perfetto track labels
    assert any(e["ph"] == "M" for e in events)


def test_dump_on_failure_env_gated(fresh_recorder, tmp_path, monkeypatch):
    monkeypatch.delenv("SPARKDL_OBS_DUMP_DIR", raising=False)
    assert export.dump_on_failure("nope") is None  # unset => no dump
    monkeypatch.setenv("SPARKDL_OBS_DUMP_DIR", str(tmp_path))
    with span("before.crash"):
        pass
    path = export.dump_on_failure("unit_test")
    assert path and os.path.exists(path)
    with open(path) as f:
        snap = json.load(f)
    assert snap["reason"] == "unit_test"
    assert [s["name"] for s in snap["spans"]] == ["before.crash"]


# -- runtime integration ----------------------------------------------------


def test_executor_records_global_metrics_and_spans(fresh_recorder):
    from sparkdl_tpu.runtime.executor import Executor

    metrics.reset()
    out = Executor(max_workers=2).map_partitions(
        lambda i, part: [x * 2 for x in part],
        [[1, 2], [3, 4, 5], [6]],
        count_rows=len,
    )
    assert out == [[2, 4], [6, 8, 10], [12]]
    assert metrics.counter("executor.rows") == 6
    assert metrics.timing("executor.partition.time").count == 3
    names = [s.name for s in fresh_recorder.spans()]
    assert names.count("executor.partition") == 3
    assert "executor.map_partitions" in names
    part_spans = [
        s for s in fresh_recorder.spans() if s.name == "executor.partition"
    ]
    assert sorted(s.attrs["partition"] for s in part_spans) == [0, 1, 2]
    assert sum(s.attrs["rows"] for s in part_spans) == 6


def test_executor_failure_counts_and_dumps(
    fresh_recorder, tmp_path, monkeypatch
):
    from sparkdl_tpu.runtime.executor import Executor, PartitionTaskError

    monkeypatch.setenv("SPARKDL_OBS_DUMP_DIR", str(tmp_path))
    metrics.reset()

    def explode(i, part):
        raise RuntimeError("kaboom")

    with pytest.raises(PartitionTaskError):
        Executor(max_workers=1, max_failures=2).map_partitions(
            explode, [[1]]
        )
    assert metrics.counter("executor.partition.failures") == 2
    dumps = [p for p in os.listdir(tmp_path) if "partition_task_error" in p]
    assert len(dumps) == 1
    with open(tmp_path / dumps[0]) as f:
        snap = json.load(f)
    # the failed attempts' spans are in the flushed ring, error-tagged
    errs = [
        s for s in snap["spans"]
        if s["name"] == "executor.partition"
        and s["attrs"].get("error") == "RuntimeError"
    ]
    assert len(errs) == 2


def test_heartbeat_payload_carries_obs(fresh_recorder, tmp_path):
    from sparkdl_tpu.runtime.heartbeat import Heartbeat

    d = str(tmp_path / "hb")
    metrics.reset()
    metrics.inc("executor.rows", 42)
    hb = Heartbeat(d, rank=0, interval=60.0)
    with span("worker.partition", partition=7, rank=0):
        hb._write()
    with open(os.path.join(d, "hb.0")) as f:
        payload = json.load(f)
    status = payload["obs"]
    assert status["counters"]["executor.rows"] == 42
    (active,) = status["active"]
    assert active["name"] == "worker.partition"
    assert active["attrs"]["partition"] == 7
    assert active["age_s"] >= 0


def test_heartbeat_cli_obs_flag(fresh_recorder, tmp_path, capsys):
    from sparkdl_tpu.runtime.heartbeat import Heartbeat, main

    d = str(tmp_path / "hb")
    hb = Heartbeat(d, rank=0, interval=60.0)
    with span("worker.partition", partition=3, rank=0):
        hb._write()
    # stale-after 0: the fresh beat still counts as stale, and rank 1
    # never beat at all — the CLI reports both, with rank 0's last obs
    rc = main(
        ["--dir", d, "--num-ranks", "2", "--stale-after", "0", "--obs"]
    )
    assert rc == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["stale_ranks"] == [0, 1]
    assert out["obs"]["0"]["active"][0]["name"] == "worker.partition"
    assert out["obs"]["1"] is None  # never beat: nothing to show


def test_gang_rank_exception_dumps(fresh_recorder, tmp_path, monkeypatch):
    from sparkdl_tpu.runtime.heartbeat import Heartbeat

    monkeypatch.setenv("SPARKDL_OBS_DUMP_DIR", str(tmp_path / "dumps"))
    hb = Heartbeat(str(tmp_path / "hb"), rank=2, interval=60.0)
    hb.__enter__()
    hb.__exit__(RuntimeError, RuntimeError("collective hang"), None)
    dumps = os.listdir(tmp_path / "dumps")
    assert len(dumps) == 1
    assert "gang_rank2_RuntimeError" in dumps[0]


# -- report + CLI -----------------------------------------------------------


def _synthetic_snap(spans):
    return {"schema": 1, "pid": 1, "spans": spans, "metrics": {}}


def _sp(name, start, dur, **attrs):
    return {
        "name": name,
        "span_id": 0,
        "parent_id": None,
        "thread_id": 1,
        "thread_name": "t",
        "start_unix": start,
        "dur_s": dur,
        "attrs": attrs,
    }


def test_overlap_ratio_known_intervals():
    # host busy [0,2], device busy [1,3]: 1s of the 2s host time overlaps
    spans = [
        _sp("ingest", 0.0, 2.0),
        _sp("device_wait", 1.0, 2.0),
    ]
    assert report.overlap_ratio(spans) == pytest.approx(0.5)
    # no device spans at all -> undefined, not 0
    assert report.overlap_ratio([_sp("ingest", 0.0, 1.0)]) is None


def test_stage_rows_percentiles_and_throughput():
    spans = [
        _sp("h2d", float(i), 0.1 * (i + 1), bytes=1000) for i in range(10)
    ]
    (row,) = report.stage_rows(_synthetic_snap(spans))
    assert row["stage"] == "h2d" and row["count"] == 10
    assert row["p50_s"] == pytest.approx(0.55)
    assert row["p99_s"] <= 1.0 + 1e-9
    assert row["bytes"] == 10000
    assert row["bytes_per_s"] == pytest.approx(10000 / row["total_s"])


def test_cli_report_and_chrome_round_trip(
    fresh_recorder, tmp_path, capsys
):
    from sparkdl_tpu.obs.__main__ import main

    with span("ingest", rows=8, bytes=256):
        pass
    with span("device_wait", rows=8):
        pass
    snap_path = str(tmp_path / "snap.json")
    obs.write_snapshot(snap_path)

    assert main(["report", "--snapshot", snap_path]) == 0
    out = capsys.readouterr().out
    assert "ingest" in out and "device_wait" in out
    assert "p50_ms" in out and "p99_ms" in out

    trace_path = str(tmp_path / "trace.json")
    assert main(
        ["chrome", "--snapshot", snap_path, "--out", trace_path]
    ) == 0
    capsys.readouterr()
    with open(trace_path) as f:
        trace = json.load(f)
    assert {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"} == {
        "ingest",
        "device_wait",
    }


def test_cli_rejects_non_snapshot(tmp_path):
    from sparkdl_tpu.obs.__main__ import main

    bad = tmp_path / "not_a_snap.json"
    bad.write_text(json.dumps({"hello": 1}))
    with pytest.raises(SystemExit, match="not an obs snapshot"):
        main(["report", "--snapshot", str(bad)])


# -- CPU end-to-end (acceptance) --------------------------------------------


def test_batched_engine_end_to_end_snapshot(fresh_recorder, tmp_path):
    """A CPU transform through the real batched engine produces a
    snapshot with ingest, h2d, dispatch, and drain_wait spans (the
    async-readback default; device_wait is the legacy-arm name); the
    report renders a per-stage breakdown from it; the Chrome export
    loads as valid JSON."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.transformers.execution import (
        arrays_to_batch,
        data_parallel_device_fn,
        run_batched_shared,
    )

    device_fn = data_parallel_device_fn(
        jax.jit(lambda b: jnp.tanh(b).sum(axis=1)),
        devices=[jax.devices()[0]],
    )
    rng = np.random.default_rng(0)
    cells = [rng.normal(size=(16,)).astype(np.float32) for _ in range(10)]
    cells[3] = None  # null row rides through masked
    out = run_batched_shared(cells, arrays_to_batch, device_fn, batch_size=4)
    assert out[3] is None and sum(o is not None for o in out) == 9

    snap = export.snapshot()
    stages = {s["name"] for s in snap["spans"]}
    assert {"ingest", "h2d", "dispatch", "drain_wait"} <= stages
    summary = report.stage_summary(snap)
    for stage in ("ingest", "h2d", "dispatch", "drain_wait"):
        assert summary[stage]["n"] >= 1
        assert summary[stage]["p50_ms"] >= 0
    # ingest spans carry rows+bytes from the real batches
    ingest = [s for s in snap["spans"] if s["name"] == "ingest"]
    assert sum(s["attrs"]["rows"] for s in ingest) == 9
    assert all(s["attrs"]["bytes"] > 0 for s in ingest)
    # report renders; chrome export loads as valid JSON
    assert "ingest" in report.render_report(snap)
    path = export.write_chrome_trace(str(tmp_path / "e2e.json"), snap)
    with open(path) as f:
        assert json.load(f)["traceEvents"]


def test_batched_engine_legacy_arm_keeps_device_wait_span(
    fresh_recorder, monkeypatch
):
    """SPARKDL_ASYNC_READBACK=0 (the synchronous A/B arm) records the
    historical device_wait span name, and no drain_wait appears."""
    from sparkdl_tpu.transformers.execution import (
        arrays_to_batch,
        run_batched_shared,
    )

    monkeypatch.setenv("SPARKDL_ASYNC_READBACK", "0")
    cells = [np.ones(4, np.float32) * i for i in range(6)]
    run_batched_shared(cells, arrays_to_batch, lambda b: b * 2.0, batch_size=2)
    stages = {s["name"] for s in export.snapshot()["spans"]}
    assert "device_wait" in stages and "drain_wait" not in stages


def test_report_renders_async_readback_line(fresh_recorder):
    """feeder_summary picks up the readback hit/miss counters and the
    rendered report prints the overlap line; drain_wait counts as a
    device stage for the overlap ratio."""
    assert "drain_wait" in report.DEVICE_STAGES
    snap = {
        "spans": [],
        "metrics": {
            "counters": {
                "feeder.coalesced_batches": 4,
                "feeder.rows": 100,
                "feeder.pad_rows": 12,
                "feeder.flushes": 1,
                "feeder.readback_async_hits": 3,
                "feeder.readback_async_misses": 1,
            }
        },
    }
    summary = report.feeder_summary(snap)
    assert summary["readback_async_hits"] == 3
    assert summary["readback_async_misses"] == 1
    rendered = report.render_report(snap)
    assert "async readback: 3 copies complete at drain" in rendered
    assert "75.0% of drains fully overlapped" in rendered


# -- one clock: spans as events of the profiler's trace ----------------------


@contextlib.contextmanager
def _profiled(trace_dir):
    """Runs the block under a jax.profiler trace on the CPU; afterwards
    the dict it yielded holds, by name, (duration_ns, stats) of every
    `sparkdl:` event on the host plane."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    events = {}
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield events
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True
    )
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sparkdl:"):
                    events.setdefault(ev.name, []).append(
                        (ev.duration_ns, dict(ev.stats))
                    )


def _bucketed_job():
    """Two partitions at once through `run_bucketed` over a stub device
    fn: the shared-feeder path of the text engine."""
    from sparkdl_tpu.runtime.executor import Executor
    from sparkdl_tpu.runtime.feeder import shutdown_feeders
    from sparkdl_tpu.text.bucketing import run_bucketed

    def tokenize(text):
        return [1] + [5 + len(w) for w in text.split()] + [2]

    def device_fn(batch):
        return np.asarray(batch, np.float32).sum(axis=1, keepdims=True)

    # 45 and 40 cells: two chunks of the 32-row floor a partition
    parts = [["a bb ccc", None, "dd e"] * 15, ["ff g", "h i j k"] * 20]
    try:
        return Executor(max_workers=2).map_partitions(
            lambda i, cells: run_bucketed(
                cells, tokenize, device_fn, batch_size=4, max_length=16
            ),
            parts,
        )
    finally:
        shutdown_feeders()


@pytest.mark.parametrize("obs_on", [True, False], ids=["on", "SPARKDL_OBS=0"])
def test_text_path_spans_are_on_the_profilers_clock(
    fresh_recorder, tmp_path, monkeypatch, obs_on
):
    monkeypatch.setenv("SPARKDL_OBS", "1" if obs_on else "0")
    with _profiled(tmp_path) as events:
        out = _bucketed_job()
    assert out[0][1] is None and float(out[0][0][0]) == 1 + 6 + 7 + 8 + 2
    if not obs_on:
        assert events == {} and fresh_recorder.spans() == []
        return
    for name in ("tokenize", "ingest", "dispatch", "result_wait"):
        found = events.get("sparkdl:" + name)
        assert found, f"no sparkdl:{name} event; the trace has {sorted(events)}"
        assert all(dur > 0 for dur, _ in found), name
    # once per chunk of a partition, never per row; attributes known at
    # open are the event's stats, those added later only the ring's
    assert len(events["sparkdl:tokenize"]) == 4
    assert {st["partition"] for _, st in events["sparkdl:result_wait"]} == {0, 1}
    by_name = {}
    for rec in fresh_recorder.spans():
        by_name.setdefault(rec.name, []).append(rec)
    tokenized = by_name["tokenize"]
    assert len(tokenized) == 4 and len(by_name["executor.partition"]) == 2
    assert sum(r.attrs["rows"] for r in tokenized) == 30 + 40
    assert sum(r.attrs["tokens"] for r in tokenized) == 15 * 9 + 20 * 10
    # every chunk's span lies inside its partition's `executor.partition`
    partitions = {r.span_id: r for r in by_name["executor.partition"]}
    rows_of = {0: 0, 1: 0}
    for r in tokenized:
        part = partitions[r.parent_id]
        assert part.thread_id == r.thread_id
        assert part.start_pc <= r.start_pc
        assert r.start_pc + r.dur_s <= part.start_pc + part.dur_s
        rows_of[part.attrs["partition"]] += r.attrs["rows"]
    assert rows_of == {0: 30, 1: 40}
    # the annotation lies inside the span it belongs to
    assert max(d for d, _ in events["sparkdl:tokenize"]) <= 1e9 * max(
        r.dur_s for r in tokenized
    )


def test_collect_box_span_and_event(fresh_recorder, tmp_path):
    from sparkdl_tpu.dataframe import DataFrame

    df = DataFrame.fromColumns({"x": list(range(10))}, numPartitions=2)
    with _profiled(tmp_path) as events:
        rows = df.collect()
    assert [r["x"] for r in rows] == list(range(10))
    ((dur, _),) = events["sparkdl:collect.box"]
    assert dur > 0
    (rec,) = [r for r in fresh_recorder.spans() if r.name == "collect.box"]
    assert rec.attrs == {"rows": 10}


def test_annotation_carries_scalar_attrs_only(fresh_recorder, tmp_path):
    with _profiled(tmp_path) as events:
        with span("stage.y", partition=3, mode="flat", shape=(2, 2), gone=None):
            pass
    ((_, stats),) = events["sparkdl:stage.y"]
    assert stats == {"partition": 3, "mode": "flat"}
    (rec,) = fresh_recorder.spans()
    assert rec.attrs["shape"] == (2, 2)  # the ring keeps every attribute


def test_pool_thread_partition_span_hangs_under_map_partitions(
    fresh_recorder,
):
    from sparkdl_tpu.runtime.executor import Executor, current_task_context

    seen = {}

    def fn(i, part):
        seen[i] = (threading.get_ident(), current_task_context())
        with span("inner.work"):
            return part

    Executor(max_workers=3).map_partitions(fn, ["a", "b", "c"])
    spans = fresh_recorder.spans()
    (job,) = [s for s in spans if s.name == "executor.map_partitions"]
    parts = [s for s in spans if s.name == "executor.partition"]
    assert len(parts) == 3
    assert {tid for tid, _ in seen.values()}.isdisjoint({job.thread_id})
    for rec in parts:
        assert rec.parent_id == job.span_id
        assert rec.thread_id != job.thread_id
    assert {ctx.parent_span_id for _, ctx in seen.values()} == {job.span_id}
    # inside the task the thread's own stack still decides
    by_id = {s.span_id: s for s in spans}
    for rec in (s for s in spans if s.name == "inner.work"):
        assert by_id[rec.parent_id].name == "executor.partition"


def test_explicit_parent_yields_to_the_threads_own_stack(fresh_recorder):
    with span("outer") as outer:
        with span("child", parent_id=10**6):
            pass
    with span("handed", parent_id=outer.span_id):
        pass
    by_name = {s.name: s for s in fresh_recorder.spans()}
    assert by_name["child"].parent_id == by_name["outer"].span_id
    assert by_name["handed"].parent_id == by_name["outer"].span_id
    assert "parent_id" not in by_name["handed"].attrs
