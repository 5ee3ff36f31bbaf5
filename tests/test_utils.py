"""Units for metrics registry, profiler context, and the model fetcher."""

import hashlib
import threading

import numpy as np
import pytest

from sparkdl_tpu.models import fetcher
from sparkdl_tpu.utils import MetricsRegistry, profile_trace


# -- metrics ----------------------------------------------------------------


def test_counters_and_timers():
    m = MetricsRegistry()
    m.inc("rows", 5)
    m.inc("rows", 3)
    with m.timer("step"):
        pass
    m.record_time("step", 0.5)
    assert m.counter("rows") == 8
    t = m.timing("step")
    assert t.count == 2
    assert t.total_s >= 0.5


def test_rate():
    m = MetricsRegistry()
    m.inc("images", 100)
    m.record_time("device", 2.0)
    assert m.rate("images", "device") == pytest.approx(50.0)
    assert m.rate("images", "missing") == 0.0


def test_thread_safety():
    m = MetricsRegistry()

    def work():
        for _ in range(1000):
            m.inc("n")
            m.record_time("t", 0.001)

    threads = [threading.Thread(target=work) for _ in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert m.counter("n") == 8000
    assert m.timing("t").count == 8000


def test_snapshot_and_reset():
    m = MetricsRegistry()
    m.inc("a")
    m.gauge("g", 7.0)
    m.record_time("t", 0.1)
    snap = m.snapshot()
    assert snap["counters"]["a"] == 1
    assert snap["gauges"]["g"] == 7.0
    assert snap["timers"]["t"]["count"] == 1
    m.reset()
    assert m.counter("a") == 0


def test_execution_records_metrics():
    from sparkdl_tpu.transformers.execution import run_batched_shared
    from sparkdl_tpu.utils.metrics import metrics

    metrics.reset()
    cells = [np.ones(2, dtype=np.float32)] * 6

    def batcher(chunk):
        b = np.stack([c for c in chunk])
        return b, np.ones(len(chunk), dtype=bool)

    run_batched_shared(cells, batcher, lambda b: b, batch_size=3)
    assert metrics.counter("transform.rows") == 6
    assert metrics.timing("transform.host_batch").count == 2
    assert metrics.timing("transform.device_wait").count == 2


def test_timer_percentiles_exact_below_reservoir():
    from sparkdl_tpu.utils.metrics import TimerStat

    t = TimerStat()
    for ms in range(1, 101):  # 1..100 ms
        t.record(ms / 1e3)
    assert t.percentile(50) == pytest.approx(0.0505)
    assert t.percentile(95) == pytest.approx(0.09505)
    assert t.percentile(0) == pytest.approx(0.001)
    assert t.percentile(100) == pytest.approx(0.100)
    d = t.as_dict()
    # existing keys stay stable for bench.py consumers
    assert {"count", "total_s", "mean_s", "min_s", "max_s"} <= set(d)
    assert d["p50_s"] == pytest.approx(0.0505)
    assert d["p95_s"] == pytest.approx(0.09505)
    assert d["p99_s"] == pytest.approx(0.09901)


def test_timer_reservoir_is_bounded():
    from sparkdl_tpu.utils.metrics import RESERVOIR_SIZE, TimerStat

    t = TimerStat()
    for _ in range(5 * RESERVOIR_SIZE):
        t.record(0.25)
    assert len(t.samples) == RESERVOIR_SIZE  # memory stays bounded
    assert t.count == 5 * RESERVOIR_SIZE  # aggregate stats still exact
    assert t.percentile(50) == pytest.approx(0.25)
    assert t.as_dict()["p99_s"] == pytest.approx(0.25)


def test_registry_snapshot_includes_percentiles():
    m = MetricsRegistry()
    for v in (0.1, 0.2, 0.3):
        m.record_time("t", v)
    snap = m.snapshot()["timers"]["t"]
    assert snap["p50_s"] == pytest.approx(0.2)


def test_profile_trace_disabled_is_noop(tmp_path):
    with profile_trace(str(tmp_path), enabled=False):
        x = 1 + 1
    assert x == 2


def test_annotate_degrades_gracefully(monkeypatch):
    """annotate() must hand back a usable no-op (context manager AND
    decorator) when jax.profiler is unavailable, like profile_trace."""
    import sys

    from sparkdl_tpu.utils import profiler

    class _NoProfiler:
        def __getattr__(self, name):
            raise RuntimeError("profiler backend unavailable")

    monkeypatch.setattr(
        sys.modules["jax"], "profiler", _NoProfiler(), raising=False
    )
    with profiler.annotate("region"):
        x = 2 + 2
    assert x == 4

    @profiler.annotate("fn.region")
    def add(a, b):
        return a + b

    assert add(1, 2) == 3


# -- fetcher ----------------------------------------------------------------


def test_fetch_local_path(tmp_path):
    p = tmp_path / "w.npz"
    p.write_bytes(b"weights!")
    assert fetcher.fetch(str(p)) == str(p)


def test_fetch_file_uri_with_good_digest(tmp_path):
    p = tmp_path / "w.bin"
    data = b"\x00\x01\x02model"
    p.write_bytes(data)
    digest = hashlib.sha256(data).hexdigest()
    got = fetcher.fetch(f"file://{p}", sha256=digest)
    assert got == str(p)


def test_fetch_digest_mismatch_raises(tmp_path):
    p = tmp_path / "w.bin"
    p.write_bytes(b"corrupted")
    with pytest.raises(fetcher.IntegrityError, match="SHA-256 mismatch"):
        fetcher.fetch(str(p), sha256="00" * 32)


def test_fetch_missing_file_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        fetcher.fetch(str(tmp_path / "nope.bin"))


def test_fetch_unsupported_scheme():
    with pytest.raises(ValueError, match="Unsupported URI scheme"):
        fetcher.fetch("s3://bucket/key")


def test_fetch_http_offline_raises(tmp_path, monkeypatch):
    monkeypatch.setenv("SPARKDL_TPU_MODEL_CACHE", str(tmp_path))
    with pytest.raises(RuntimeError, match="offline|download"):
        fetcher.fetch(
            "http://192.0.2.1/model.npz"  # TEST-NET-1: guaranteed no route
        )
