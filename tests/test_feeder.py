"""Cross-partition continuous batching (runtime/feeder.py) + the
executor/engine changes that ride along with it.

The shared DeviceFeeder is the batch engine: one owner thread packs the
rows of every producer across partition boundaries, and a lone partition
or a direct call is a one-producer stream of it. These tests pin its
contract: right answers in the right cells (Nones included, ordered),
padding accounting (ONE tail flush per quiet period, not one padded tail
per partition), producer-exception propagation, and an owner thread that
can never be wedged by an abandoned consumer.

The async-readback arm (runtime/readback.py + the feeder's drainer
thread, SPARKDL_ASYNC_READBACK) rides the same contract: both arms must
produce identical outputs, the dispatch-time copy must actually be
issued, drain errors must propagate and reset cleanly, and close() must
never leak the drainer thread.
"""

import math
import threading

import numpy as np
import pytest

from sparkdl_tpu.runtime.executor import (
    Executor,
    TaskContext,
    current_task_context,
)
from sparkdl_tpu.runtime import feeder as feeder_mod
from sparkdl_tpu.runtime import readback
from sparkdl_tpu.runtime.feeder import run_shared, shutdown_feeders
from sparkdl_tpu.transformers.execution import (
    arrays_to_batch,
    run_batched_shared,
)
from sparkdl_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True)
def _clean_feeders():
    yield
    shutdown_feeders()


def _identity_batcher(chunk):
    batch = np.zeros((len(chunk), 2), dtype=np.float32)
    mask = np.zeros((len(chunk),), dtype=bool)
    for i, c in enumerate(chunk):
        if c is None:
            continue
        batch[i] = c
        mask[i] = True
    return batch, mask


def _feeder_counters():
    return {
        k: metrics.counter(f"feeder.{k}")
        for k in ("coalesced_batches", "pad_rows", "rows")
    }


def _counter_delta(before):
    return {k: metrics.counter(f"feeder.{k}") - v for k, v in before.items()}


def _make_parts(n_parts, rows_per_part, with_nones=True, seed=0):
    rng = np.random.default_rng(seed)
    parts = []
    for p in range(n_parts):
        cells = [
            rng.normal(size=(2,)).astype(np.float32)
            for _ in range(rows_per_part)
        ]
        if with_nones and rows_per_part > 3:
            cells[1] = None
            cells[-1] = None
        parts.append(cells)
    return parts


def _run_parts(parts, device_fn, batch_size, max_workers=None, prefetch=None):
    return Executor(max_workers=max_workers or len(parts)).map_partitions(
        lambda i, cells: run_batched_shared(
            cells, _identity_batcher, device_fn, batch_size,
            prefetch=prefetch,
        ),
        parts,
        count_rows=len,
    )


# -- the one entry, against a numpy expectation ------------------------------


def _assert_parts_equal(got, parts, expect):
    """Every cell of every partition: None stays None, a row is
    ``expect(row)``, in the cell it came from."""
    assert len(got) == len(parts)
    for gp, part in zip(got, parts):
        assert len(gp) == len(part)
        for a, cell in zip(gp, part):
            if cell is None:
                assert a is None
            else:
                np.testing.assert_array_equal(a, expect(cell))


def test_parity_many_partitions():
    """Many concurrent partitions: every row comes back in its own cell
    — Nones included, partition order kept."""
    parts = _make_parts(6, 23)
    out = _run_parts(parts, lambda b: b * 2.0, batch_size=4)
    _assert_parts_equal(out, parts, lambda c: c * 2.0)


def test_single_partition_goes_through_the_feeder():
    """One partition on a sequential executor is a one-producer stream
    of the same engine: right answers, and the feeder's counters move."""
    before = _feeder_counters()
    parts = _make_parts(1, 10)
    out = _run_parts(parts, lambda b: b + 1.0, batch_size=4)
    got = _counter_delta(before)
    assert got["coalesced_batches"] == 2 and got["rows"] == 8
    _assert_parts_equal(out, parts, lambda c: c + 1.0)


def test_direct_call_goes_through_the_feeder():
    """run_batched_shared called with no TaskContext (direct use) is a
    one-producer stream too: one tail flush, 3 pad rows."""
    assert current_task_context() is None
    before = _feeder_counters()
    flushes = metrics.counter("feeder.flushes")
    cells = [np.full(2, i, dtype=np.float32) for i in range(9)]
    out = run_batched_shared(cells, _identity_batcher, lambda b: b, 4)
    got = _counter_delta(before)
    assert got["coalesced_batches"] == 3 and got["pad_rows"] == 3
    assert metrics.counter("feeder.flushes") - flushes == 1
    _assert_parts_equal([out], [cells], lambda c: c)


def _bucket_by_width_stream(cells):
    """A ``stream`` host stage: rows leave in two shapes (width 2 and
    width 5), in chunks of its own size, as ``run_bucketed`` does."""

    def stream(dispatch_rows):
        for start in range(0, len(cells), 3):
            for width in (5, 2):
                idx = [
                    i
                    for i in range(start, min(start + 3, len(cells)))
                    if cells[i] is not None and len(cells[i]) == width
                ]
                if idx:
                    yield (
                        np.asarray(idx),
                        np.stack([cells[i] for i in idx]).astype(np.float32),
                    )

    return stream


@pytest.mark.parametrize("host_stage", ["to_batch", "stream"])
@pytest.mark.parametrize(
    "caller", ["no_context", "one_partition_sequential", "four_concurrent"]
)
def test_one_entry_matches_numpy(caller, host_stage):
    """The one entry serves a direct call, a lone partition and
    concurrent partitions, with either host stage, and every answer is
    the plain numpy one — nulls included."""
    rng = np.random.default_rng(7)
    n_parts = 4 if caller == "four_concurrent" else 1
    parts = []
    for p in range(n_parts):
        widths = [2] * 13 if host_stage == "to_batch" else [2, 5, 5, 2] * 4
        cells = [rng.normal(size=(w,)).astype(np.float32) for w in widths]
        cells[1] = None
        cells[-1] = None
        parts.append(cells)
    device_fn = lambda b: b * 3.0 - 1.0  # noqa: E731

    def run(_i, cells):
        if host_stage == "to_batch":
            return run_batched_shared(cells, arrays_to_batch, device_fn, 4)
        return run_batched_shared(
            cells, None, device_fn, 4, stream=_bucket_by_width_stream(cells)
        )

    before = _feeder_counters()
    if caller == "no_context":
        out = [run(None, parts[0])]
    else:
        workers = 1 if caller == "one_partition_sequential" else 4
        out = Executor(max_workers=workers).map_partitions(run, parts)
    got = _counter_delta(before)
    assert got["rows"] == sum(c is not None for p in parts for c in p)
    assert got["coalesced_batches"] > 0
    _assert_parts_equal(out, parts, lambda c: c * 3.0 - 1.0)


def test_single_stream_device_fn_goes_through_the_feeder():
    """A whole-mesh (``single_stream``) device fn is a device fn like any
    other to the engine: its rows pack across concurrent partitions."""
    import jax.numpy as jnp

    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.transformers.execution import model_device_fn

    mf = ModelFunction(
        lambda p, x: jnp.tanh(x) * 2.0, None, input_shape=(2,), name="mesh"
    )
    mf.single_stream = True
    device_fn = model_device_fn(mf)
    assert device_fn.single_stream and device_fn.n_devices == 1
    parts = _make_parts(3, 9)
    before = _feeder_counters()
    out = _run_parts(parts, device_fn, batch_size=4)
    got = _counter_delta(before)
    assert got["rows"] == 3 * 7 and got["coalesced_batches"] >= 6
    for gp, part in zip(out, parts):
        for a, cell in zip(gp, part):
            if cell is None:
                assert a is None
            else:
                np.testing.assert_allclose(a, np.tanh(cell) * 2.0, rtol=1e-6)


def test_lone_producer_does_not_wait_out_the_linger(monkeypatch):
    """A producer that knows it is alone (a direct call; a one-partition
    job) has its tail padded and flushed when its stream ends: a 5 s
    linger is never waited out. Two CONCURRENT partitions still share
    one tail batch inside the linger."""
    import time

    monkeypatch.setenv("SPARKDL_FEEDER_LINGER_MS", "5000")
    device_fn = lambda b: b * 2.0  # noqa: E731
    cells = [np.full(2, i, dtype=np.float32) for i in range(10)]

    t0 = time.monotonic()
    out = run_batched_shared(cells, _identity_batcher, device_fn, 4)
    direct_s = time.monotonic() - t0
    _assert_parts_equal([out], [cells], lambda c: c * 2.0)

    t0 = time.monotonic()
    out = _run_parts([cells], device_fn, batch_size=4)
    one_partition_s = time.monotonic() - t0
    _assert_parts_equal(out, [cells], lambda c: c * 2.0)
    assert direct_s < 1.0 and one_partition_s < 1.0, (
        direct_s, one_partition_s,
    )

    # 2 x 5 rows at batch 4 on a concurrent executor: the tails meet in
    # ONE padded batch (10 rows -> 3 batches, 2 pad rows), where a flush
    # per partition would pad 3 + 3
    monkeypatch.setenv("SPARKDL_FEEDER_LINGER_MS", "300")
    parts = [cells[:5], cells[5:]]
    before = _feeder_counters()
    out = _run_parts(parts, device_fn, batch_size=4, max_workers=2)
    got = _counter_delta(before)
    assert got == {"coalesced_batches": 3, "pad_rows": 2, "rows": 10}, got
    _assert_parts_equal(out, parts, lambda c: c * 2.0)


def test_identity_device_fn_gets_its_own_rows_back():
    """A device fn that returns its input (or a view of it) hands back
    rows that alias the feeder's ring buffer; the drain copies them, so
    later batches reusing the buffer cannot overwrite earlier answers."""
    cells = [np.full(2, i, dtype=np.float32) for i in range(41)]
    for device_fn in (lambda b: b, lambda b: b[::1]):
        # prefetch 1 -> a ring of 5 buffers; 11 batches go round it twice
        out = run_batched_shared(
            cells, _identity_batcher, device_fn, 4, prefetch=1
        )
        _assert_parts_equal([out], [cells], lambda c: c)


# -- the acceptance workload: padding accounting ------------------------------


def test_pad_rows_one_tail_flush_not_per_partition(monkeypatch):
    """16 partitions x 100 rows at batch_size=32: the shared feeder must
    dispatch <= ceil(1600/32)+1 batches with total pad rows <= 32 — vs
    a dispatch loop per partition's 16 padded tails."""
    n_parts, rows, batch = 16, 100, 32
    # generous linger so staggered thread starts on a loaded CI box can't
    # split the stream into multiple quiet periods
    monkeypatch.setenv("SPARKDL_FEEDER_LINGER_MS", "200")
    parts = _make_parts(n_parts, rows, with_nones=False)
    before = _feeder_counters()
    out = _run_parts(parts, lambda b: b * 2.0, batch_size=batch)
    got = _counter_delta(before)
    max_batches = math.ceil(n_parts * rows / batch) + 1
    assert 0 < got["coalesced_batches"] <= max_batches, got
    assert got["pad_rows"] <= batch, got
    assert got["rows"] == n_parts * rows, got
    for p, part in enumerate(parts):
        for i, cell in enumerate(part):
            np.testing.assert_array_equal(out[p][i], cell * 2.0)


def test_null_rows_never_occupy_device_rows():
    """Invalid cells come back as None AND are squeezed out of the device
    stream entirely (the feeder packs only valid rows)."""
    parts = [
        [np.ones(2, np.float32), None, np.full(2, 3.0, np.float32), None],
        [None, None, np.full(2, 5.0, np.float32), None],
    ]
    before = _feeder_counters()
    out = _run_parts(parts, lambda b: b + 1.0, batch_size=4)
    got = _counter_delta(before)
    assert got["rows"] == 3  # 3 valid cells total across both partitions
    assert out[0][1] is None and out[0][3] is None
    assert out[1][0] is None and out[1][1] is None and out[1][3] is None
    np.testing.assert_array_equal(out[0][2], [4.0, 4.0])
    np.testing.assert_array_equal(out[1][2], [6.0, 6.0])


def test_all_null_partitions_complete():
    parts = [[None, None, None], [None]]
    out = _run_parts(parts, lambda b: b, batch_size=2)
    assert out == [[None, None, None], [None]]


def test_shard_map_multiplier_packs_global_batches(monkeypatch):
    """A batch_multiplier device fn (shard_map mode) feeds global-size
    batches: dispatch size = batch_size x multiplier, always full except
    the tail flush — the mesh never sees an odd-sized (recompiling)
    batch."""
    monkeypatch.setenv("SPARKDL_FEEDER_LINGER_MS", "200")
    sizes = []

    def device_fn(b):
        sizes.append(len(b))
        return b * 2.0

    device_fn.batch_multiplier = 4
    parts = _make_parts(3, 10, with_nones=False)
    out = _run_parts(parts, device_fn, batch_size=2)
    assert set(sizes) == {8}  # every dispatch is the full global batch
    assert len(sizes) == math.ceil(30 / 8)
    np.testing.assert_array_equal(out[2][9], parts[2][9] * 2.0)


# -- failure paths ------------------------------------------------------------


def test_producer_exception_propagates_and_isolates():
    """A to_batch (host stage) error in one partition fails THAT
    partition's task; concurrently-coalescing partitions still complete
    with correct results, and the owner thread survives."""
    parts = _make_parts(4, 20, with_nones=False)

    def batcher(chunk):
        if any(
            isinstance(c, str) for c in chunk
        ):
            raise ValueError("decode exploded")
        return _identity_batcher(chunk)

    parts[2][7] = "poison"
    ex = Executor(max_workers=4, max_failures=1)
    with pytest.raises(Exception, match="decode exploded"):
        ex.map_partitions(
            lambda i, cells: run_batched_shared(
                cells, batcher, lambda b: b * 2.0, 8
            ),
            parts,
        )
    # the feeder is still healthy: a fresh run over clean data succeeds
    clean = _make_parts(2, 9, with_nones=False, seed=1)
    out = _run_parts(clean, lambda b: b * 2.0, batch_size=8)
    np.testing.assert_array_equal(out[1][8], clean[1][8] * 2.0)


def test_device_error_propagates_to_all_waiting_partitions():

    def bad_device(b):
        raise RuntimeError("device fell over")

    parts = _make_parts(3, 12, with_nones=False)
    ex = Executor(max_workers=3, max_failures=1)
    with pytest.raises(Exception, match="device fell over"):
        ex.map_partitions(
            lambda i, cells: run_batched_shared(
                cells, _identity_batcher, bad_device, 4
            ),
            parts,
        )
    # and the feeder recovers for the next (healthy) run
    out = _run_parts(
        _make_parts(2, 6, with_nones=False, seed=2),
        lambda b: b,
        batch_size=4,
    )
    assert all(o is not None for part in out for o in part)


def test_abandoned_consumer_does_not_wedge_owner(monkeypatch):
    """A consumer that submits rows and walks away (its thread dies
    without waiting) must not wedge the owner: later submissions to the
    same feeder complete normally."""
    monkeypatch.setenv("SPARKDL_FEEDER_LINGER_MS", "10")
    device_fn = lambda b: b * 2.0  # noqa: E731
    cells = [np.full(2, i, np.float32) for i in range(10)]

    def abandon():
        # simulate an abandoning consumer: open a stream, submit, end it,
        # but never wait for results
        f = feeder_mod.get_feeder(device_fn, 4, (2,), np.float32, 2)
        h = f.open_handle([None] * 10)
        batch, mask = _identity_batcher(cells)
        f.submit_rows(h, np.flatnonzero(mask), batch)
        f.finish(h)

    t = threading.Thread(target=abandon)
    t.start()
    t.join(timeout=5.0)
    assert not t.is_alive()
    # the owner drains the abandoned stream and serves the next consumer
    out = run_shared(device_fn, cells, _identity_batcher, 4, prefetch=2)
    for i, o in enumerate(out):
        np.testing.assert_array_equal(o, np.full(2, 2.0 * i))


def test_feeder_close_fails_pending_handles():
    device_fn = lambda b: b  # noqa: E731
    f = feeder_mod.DeviceFeeder(device_fn, 4, (2,), np.float32, prefetch=2)
    h = f.open_handle([None] * 8)
    f.submit_rows(h, np.arange(2), np.ones((2, 2), np.float32))
    f.close()
    with pytest.raises(RuntimeError, match="closed|exited"):
        h.wait(timeout=5.0)
    with pytest.raises(RuntimeError, match="closed"):
        f.open_handle([None] * 2)


def test_varying_row_shapes_route_to_separate_feeders():
    """Chunks whose row shape differs transparently stream into one
    feeder per shape — outputs land in the right cells either way."""

    def ragged_batcher(chunk):
        shapes = {np.asarray(c).shape for c in chunk if c is not None}
        assert len(shapes) == 1
        return arrays_to_batch(chunk)

    parts = [
        [np.ones(2, np.float32) * i for i in range(4)]
        + [np.ones(5, np.float32) * i for i in range(4)]
        for _ in range(2)
    ]
    out = Executor(max_workers=2).map_partitions(
        lambda i, cells: run_batched_shared(
            cells, ragged_batcher, lambda b: b * 2.0, 4
        ),
        parts,
    )
    for part_in, part_out in zip(parts, out):
        for a, b in zip(part_in, part_out):
            np.testing.assert_array_equal(b, np.asarray(a) * 2.0)


# -- async readback -----------------------------------------------------------


class _FakeDeviceArray:
    """Result double with the jax device-array readback surface: an
    async-copy hook, a readiness probe, and numpy materialization."""

    def __init__(self, value, ready=True):
        self._value = np.asarray(value)
        self._ready = ready
        self.copies = 0

    def copy_to_host_async(self):
        self.copies += 1

    def is_ready(self):
        return self._ready

    def __array__(self, dtype=None, copy=None):
        v = self._value
        return v.astype(dtype) if dtype is not None else v


def _readback_counters():
    return {
        k: metrics.counter(f"feeder.{k}")
        for k in ("readback_async_hits", "readback_async_misses")
    }


def test_async_vs_sync_arm_output_parity(monkeypatch):
    """The drainer-thread arm and the legacy synchronous drain produce
    identical outputs — Nones, ordering, values — across many
    concurrent partitions (the A/B acceptance criterion)."""
    parts = _make_parts(5, 27)
    device_fn = lambda b: b * 3.0 + 1.0  # noqa: E731

    monkeypatch.setenv("SPARKDL_ASYNC_READBACK", "1")
    async_out = _run_parts(parts, device_fn, batch_size=4)
    shutdown_feeders()
    monkeypatch.setenv("SPARKDL_ASYNC_READBACK", "0")
    sync_out = _run_parts(parts, device_fn, batch_size=4)

    assert len(async_out) == len(sync_out) == 5
    for ap, sp in zip(async_out, sync_out):
        for a, b in zip(ap, sp):
            if b is None:
                assert a is None
            else:
                assert a.tobytes() == b.tobytes()


def test_run_batched_async_vs_sync_arm_parity(monkeypatch):
    """A direct call honors the same A/B gate: both readback arms
    return identical cells."""
    cells = [
        None if i % 7 == 3 else np.full(2, i, dtype=np.float32)
        for i in range(25)
    ]
    monkeypatch.setenv("SPARKDL_ASYNC_READBACK", "1")
    a = run_batched_shared(cells, _identity_batcher, lambda b: b * 2.0, 4)
    monkeypatch.setenv("SPARKDL_ASYNC_READBACK", "0")
    b = run_batched_shared(cells, _identity_batcher, lambda b: b * 2.0, 4)
    for x, y in zip(a, b):
        if y is None:
            assert x is None
        else:
            assert x.tobytes() == y.tobytes()


def test_async_copy_issued_at_dispatch_and_hits_counted(monkeypatch):
    """With the async arm on, every dispatched batch gets its
    copy_to_host_async issued at dispatch time, and drains attribute
    hits (copy complete) to feeder.readback_async_hits."""
    monkeypatch.setenv("SPARKDL_ASYNC_READBACK", "1")
    results = []

    def device_fn(b):
        r = _FakeDeviceArray(b * 2.0, ready=True)
        results.append(r)
        return r

    cells = [np.full(2, i, np.float32) for i in range(12)]
    before = _readback_counters()
    out = run_shared(device_fn, cells, _identity_batcher, 4, prefetch=2)
    got = {k: metrics.counter(f"feeder.{k}") - v for k, v in before.items()}
    assert len(results) == 3
    assert all(r.copies == 1 for r in results)
    assert got["readback_async_hits"] == 3
    assert got["readback_async_misses"] == 0
    for i, o in enumerate(out):
        np.testing.assert_array_equal(o, np.full(2, 2.0 * i))


def test_sync_arm_never_issues_async_copy(monkeypatch):
    monkeypatch.setenv("SPARKDL_ASYNC_READBACK", "0")
    results = []

    def device_fn(b):
        r = _FakeDeviceArray(b + 1.0, ready=False)
        results.append(r)
        return r

    cells = [np.full(2, i, np.float32) for i in range(8)]
    before = _readback_counters()
    out = run_shared(device_fn, cells, _identity_batcher, 4, prefetch=2)
    got = {k: metrics.counter(f"feeder.{k}") - v for k, v in before.items()}
    assert all(r.copies == 0 for r in results)
    assert got["readback_async_hits"] == got["readback_async_misses"] == 0
    np.testing.assert_array_equal(out[7], [8.0, 8.0])


def test_drainer_thread_stops_on_close(monkeypatch):
    """close() joins BOTH feeder threads — the owner and the async-arm
    drainer — so repeated transform/close cycles never leak threads."""
    monkeypatch.setenv("SPARKDL_ASYNC_READBACK", "1")
    device_fn = lambda b: b * 2.0  # noqa: E731
    f = feeder_mod.DeviceFeeder(device_fn, 4, (2,), np.float32, prefetch=2)
    out = [None] * 8
    h = f.open_handle(out)
    batch = np.arange(16, dtype=np.float32).reshape(8, 2)
    f.submit_rows(h, np.arange(8), batch)
    f.finish(h)
    h.wait(timeout=10.0)
    assert f._drainer is not None  # the async arm really engaged
    f.close()
    assert f._thread is None or not f._thread.is_alive()
    assert not f._drainer.is_alive()
    np.testing.assert_array_equal(out[3], batch[3] * 2.0)


def test_drain_error_propagates_and_feeder_recovers(monkeypatch):
    """A readback failure on the DRAINER thread fails every waiting
    stream (same contract as a dispatch failure) and the feeder resets
    for the next healthy run."""
    monkeypatch.setenv("SPARKDL_ASYNC_READBACK", "1")

    class _ExplodingResult(_FakeDeviceArray):
        def __array__(self, dtype=None, copy=None):
            raise RuntimeError("readback fell over")

    def bad_device(b):
        return _ExplodingResult(b)

    cells = [np.full(2, i, np.float32) for i in range(8)]
    with pytest.raises(RuntimeError, match="readback fell over"):
        run_shared(bad_device, cells, _identity_batcher, 4, prefetch=2)
    # the same feeder geometry recovers for a healthy device fn
    out = run_shared(
        lambda b: b * 2.0, cells, _identity_batcher, 4, prefetch=2
    )
    for i, o in enumerate(out):
        np.testing.assert_array_equal(o, np.full(2, 2.0 * i))


def test_failed_handle_rows_excluded_from_row_counters():
    """feeder.rows / transform.rows count rows actually DELIVERED: a
    segment whose handle already failed contributes nothing (previously
    the full batch fill was counted regardless)."""
    device_fn = lambda b: b  # noqa: E731
    f = feeder_mod.DeviceFeeder(device_fn, 4, (2,), np.float32, prefetch=2)
    ok = feeder_mod._Handle(f, [None] * 4)
    dead = feeder_mod._Handle(f, [None] * 4)
    ok._add_pending(2)
    dead._add_pending(2)
    dead.fail(RuntimeError("gone"))
    segs = [(ok, np.array([0, 1]), 0), (dead, np.array([2, 3]), 2)]
    y = np.arange(8, dtype=np.float32).reshape(4, 2)
    before = {
        "feeder.rows": metrics.counter("feeder.rows"),
        "transform.rows": metrics.counter("transform.rows"),
    }
    f._drain_entry(segs, 4, y, np.zeros((4, 2), np.float32), False)
    assert metrics.counter("feeder.rows") - before["feeder.rows"] == 2
    assert (
        metrics.counter("transform.rows") - before["transform.rows"] == 2
    )
    np.testing.assert_array_equal(ok.out[1], y[1])
    assert dead.out == [None] * 4
    f.close()


def test_tail_flush_counted_at_call_site(monkeypatch):
    """feeder.flushes counts quiet-period tail flushes at the flush CALL
    SITE: a run whose rows fill every batch exactly records zero tail
    flushes, a partial tail records exactly one (pad_rows unchanged)."""
    monkeypatch.setenv("SPARKDL_FEEDER_LINGER_MS", "10")
    device_fn = lambda b: b * 2.0  # noqa: E731

    def flush_delta(n_rows):
        before = {
            k: metrics.counter(f"feeder.{k}") for k in ("flushes", "pad_rows")
        }
        cells = [np.full(2, i, np.float32) for i in range(n_rows)]
        run_shared(device_fn, cells, _identity_batcher, 4, prefetch=2)
        return {
            k: metrics.counter(f"feeder.{k}") - v for k, v in before.items()
        }

    assert flush_delta(8) == {"flushes": 0, "pad_rows": 0}  # exact fill
    assert flush_delta(5) == {"flushes": 1, "pad_rows": 3}  # one padded tail


# -- readback helpers ---------------------------------------------------------


def test_readback_enabled_gate(monkeypatch):
    monkeypatch.delenv("SPARKDL_ASYNC_READBACK", raising=False)
    assert readback.async_readback_enabled()
    for off in ("0", "off", ""):
        monkeypatch.setenv("SPARKDL_ASYNC_READBACK", off)
        assert not readback.async_readback_enabled()
    monkeypatch.setenv("SPARKDL_ASYNC_READBACK", "1")
    assert readback.async_readback_enabled()


def test_readback_helpers_degrade_on_plain_arrays():
    """numpy results (CPU device fns, tests) lack the async surface: the
    helpers no-op/None instead of raising."""
    y = np.ones((2, 2), np.float32)
    assert readback.start_copy(y) is False
    assert readback.is_ready(y) is None
    np.testing.assert_array_equal(readback.to_host(y), y)
    fake = _FakeDeviceArray(y, ready=False)
    assert readback.start_copy(fake) is True
    assert fake.copies == 1
    assert readback.is_ready(fake) is False


def test_readback_helpers_swallow_probe_errors():
    class _Broken:
        def copy_to_host_async(self):
            raise RuntimeError("no transfer manager")

        def is_ready(self):
            raise RuntimeError("no transfer manager")

    assert readback.start_copy(_Broken()) is False
    assert readback.is_ready(_Broken()) is None


def test_scatter_rows_contiguous_and_gapped():
    rows = np.arange(12, dtype=np.float32).reshape(6, 2)
    out = [None] * 8
    readback.scatter_rows(out, np.arange(2, 8), rows)  # contiguous run
    for k in range(6):
        np.testing.assert_array_equal(out[2 + k], rows[k])
    assert out[0] is None and out[1] is None
    out = [None] * 8
    readback.scatter_rows(out, np.array([0, 3, 4, 7]), rows[:4])  # gapped
    np.testing.assert_array_equal(out[3], rows[1])
    np.testing.assert_array_equal(out[7], rows[3])
    assert out[1] is None and out[2] is None and out[5] is None
    readback.scatter_rows(out, np.array([], dtype=np.int64), rows[:0])
    readback.scatter_rows(out, [5], rows[4:5])  # plain-list indices
    np.testing.assert_array_equal(out[5], rows[4])


# -- engine/executor satellites -----------------------------------------------


def test_task_context_published_per_partition():
    seen = {}

    def fn(i, part):
        seen[i] = current_task_context()
        return part

    Executor(max_workers=4).map_partitions(fn, ["a", "b", "c"])
    assert seen[1] == TaskContext(
        partition_index=1, num_partitions=3, concurrency=3
    )
    assert current_task_context() is None  # never leaks off-task
    # a sequential executor reports concurrency 1 (feeder gate: nothing
    # runs at once, so cross-partition coalescing cannot pay)
    Executor(max_workers=1).map_partitions(fn, ["a", "b"])
    assert seen[1].concurrency == 1 and seen[1].num_partitions == 2


def test_executor_reuses_worker_pool():
    ex = Executor(max_workers=4)

    def fn(i, part):
        return threading.current_thread().name

    names1 = set(ex.map_partitions(fn, list(range(6))))
    pool1 = ex._pool
    names2 = set(ex.map_partitions(fn, list(range(6))))
    assert pool1 is not None and ex._pool is pool1  # no per-call pool churn
    # every task ran on the persistent pool's named workers (which of the
    # <=4 workers picks up a task is scheduler-dependent)
    assert all(n.startswith("sparkdl-exec") for n in names1 | names2)
    assert len(names1 | names2) <= ex.max_workers
    ex.close()
    assert ex._pool is None
    # close() is not terminal: the pool re-creates lazily
    names3 = set(ex.map_partitions(fn, list(range(4))))
    assert names3
    ex.close()


def test_nested_map_partitions_does_not_deadlock():
    """A partition fn that itself runs map_partitions on the same
    executor must not starve behind the outer tasks occupying the shared
    pool (it gets a private pool)."""
    ex = Executor(max_workers=2)

    def inner(i, part):
        return part * 10

    def outer(i, part):
        return sum(ex.map_partitions(inner, [part, part + 1]))

    out = ex.map_partitions(outer, [1, 2, 3, 4])
    assert out == [30, 50, 70, 90]
    ex.close()


def test_feed_plan_rejects_malformed_chunk_env(monkeypatch):
    from sparkdl_tpu.transformers.execution import feed_plan

    monkeypatch.setenv("SPARKDL_INFERENCE_DEVICES", "1")
    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "4MB")
    with pytest.raises(ValueError, match="SPARKDL_H2D_CHUNK_MB"):
        feed_plan()
    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "-1")
    with pytest.raises(ValueError, match="megabytes"):
        feed_plan()
    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "0")
    assert feed_plan()["chunk_bytes"] is None


class _FakePoolDevice:
    """feed_plan only reads ``.platform`` off pool entries, so the TPU
    default can be pinned without a chip."""

    def __init__(self, platform):
        self.platform = platform


def test_feed_plan_chunk_default_engages_only_on_tpu_single_device(
    monkeypatch,
):
    """The 4 MB chunk default (the banked round-5 +42% win) applies on a
    single TPU device ONLY: multi-device pools carry the default but
    never engage it (the sharded global batch already splits), and CPU
    pools get no chunking at all."""
    from sparkdl_tpu.transformers.execution import feed_plan

    monkeypatch.delenv("SPARKDL_H2D_CHUNK_MB", raising=False)
    plan = feed_plan([_FakePoolDevice("tpu")])
    assert plan["chunk_bytes"] == 4 << 20
    assert plan["single_device"] and plan["chunk_engaged"]

    plan = feed_plan([_FakePoolDevice("tpu"), _FakePoolDevice("tpu")])
    assert plan["chunk_bytes"] == 4 << 20
    assert not plan["single_device"] and not plan["chunk_engaged"]

    plan = feed_plan([_FakePoolDevice("cpu")])
    assert plan["chunk_bytes"] is None and not plan["chunk_engaged"]


def test_feed_plan_chunk_env_overrides_default(monkeypatch):
    """SPARKDL_H2D_CHUNK_MB=0 disables chunking even on TPU; an explicit
    size both overrides the TPU default and engages on non-TPU pools."""
    from sparkdl_tpu.transformers.execution import feed_plan

    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "0")
    plan = feed_plan([_FakePoolDevice("tpu")])
    assert plan["chunk_bytes"] is None and not plan["chunk_engaged"]

    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "2")
    plan = feed_plan([_FakePoolDevice("tpu")])
    assert plan["chunk_bytes"] == 2 << 20 and plan["chunk_engaged"]
    plan = feed_plan([_FakePoolDevice("cpu")])
    assert plan["chunk_bytes"] == 2 << 20 and plan["chunk_engaged"]


def test_run_batched_drain_order_with_deque():
    """The in-flight window drains FIFO (deque.popleft) and scatters via
    flatnonzero — results stay ordered with a deep prefetch window and
    interleaved nulls."""
    cells = [
        None if i % 5 == 2 else np.full(2, i, dtype=np.float32)
        for i in range(23)
    ]
    out = run_batched_shared(
        cells, _identity_batcher, lambda b: b * 2.0, batch_size=3,
        prefetch=8,
    )
    for i, o in enumerate(out):
        if i % 5 == 2:
            assert o is None
        else:
            np.testing.assert_array_equal(o, np.full(2, 2.0 * i))


# -- end-to-end through a real transformer ------------------------------------


def test_transformer_parity_shared_vs_legacy():
    """ModelTransformer over a multi-partition DataFrame: the column is
    the plain numpy answer, null row included, through the feeder."""
    import jax.numpy as jnp

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.transformers import ModelTransformer

    mf = ModelFunction(
        lambda p, x: x * 2.0 + 1.0, None, input_shape=(3,), name="affine"
    )
    xf = ModelTransformer(
        inputCol="v", outputCol="o", modelFunction=mf, batchSize=4,
        flattenOutput=False,
    )
    cells = [
        None if i == 7 else np.ones(3, np.float32) * i for i in range(22)
    ]
    df = DataFrame.fromColumns({"v": cells}, numPartitions=3)

    # a concurrent default executor: on a 1-core box the default would be
    # sequential (concurrency 1), a one-producer stream
    from sparkdl_tpu.runtime.executor import (
        default_executor,
        set_default_executor,
    )

    prev = default_executor()
    set_default_executor(Executor(max_workers=3))
    try:
        before = _feeder_counters()
        rows = xf.transform(df).collect()
        engaged = _counter_delta(before)["coalesced_batches"]
    finally:
        set_default_executor(prev)

    assert engaged > 0
    assert len(rows) == len(cells)
    for row, cell in zip(rows, cells):
        if cell is None:
            assert row.o is None
        else:
            np.testing.assert_allclose(row.o, cell * 2.0 + 1.0, rtol=0, atol=0)


# -- device-side input staging ------------------------------------------------


def _staging_device_fn(staged_marker=None):
    """Device fn with an explicit transfer half, like the real builders:
    stage_put tags the batch so tests can assert dispatch consumed the
    STAGED value, not a fresh host transfer."""

    def stage_put(b):
        out = np.asarray(b) + 0.0  # a distinct "device-side" copy
        if staged_marker is not None:
            staged_marker.append(out)
        return out

    def fn(batch):
        return np.asarray(batch) * 2.0

    fn.stage_put = stage_put
    return fn


def _stage_counters():
    return {
        k: metrics.counter(f"transfer.{k}")
        for k in ("stage_hits", "stage_misses")
    }


def test_staged_on_off_parity_and_counters(monkeypatch):
    """SPARKDL_DEVICE_STAGE on vs off produce identical outputs across
    concurrent partitions; the staged arm's hit+miss pair accounts for
    every coalesced batch and the legacy arm never moves it."""
    parts = _make_parts(5, 21)

    monkeypatch.setenv("SPARKDL_DEVICE_STAGE", "1")
    before = {**_stage_counters(), **_feeder_counters()}
    staged_out = _run_parts(parts, _staging_device_fn(), batch_size=4)
    staged_delta = {
        k: metrics.counter(f"transfer.{k}") - before[k]
        for k in ("stage_hits", "stage_misses")
    }
    batches = metrics.counter("feeder.coalesced_batches") - before[
        "coalesced_batches"
    ]
    shutdown_feeders()

    monkeypatch.setenv("SPARKDL_DEVICE_STAGE", "0")
    before2 = _stage_counters()
    legacy_out = _run_parts(parts, _staging_device_fn(), batch_size=4)
    legacy_delta = {
        k: metrics.counter(f"transfer.{k}") - v for k, v in before2.items()
    }

    assert batches > 0
    assert staged_delta["stage_hits"] + staged_delta["stage_misses"] == batches
    assert legacy_delta["stage_hits"] == legacy_delta["stage_misses"] == 0
    for sp, lp in zip(staged_out, legacy_out):
        for a, b in zip(sp, lp):
            if b is None:
                assert a is None
            else:
                assert a.tobytes() == b.tobytes()


def test_staged_dispatch_consumes_staged_value(monkeypatch):
    """Dispatch receives the value stage_put produced (the staging slot),
    one per dispatched batch — proof the copy ran ahead of dispatch on
    the pool rather than inside the dispatch call."""
    monkeypatch.setenv("SPARKDL_DEVICE_STAGE", "1")
    staged = []
    seen = []

    def fn(batch):
        seen.append(batch)
        return np.asarray(batch) * 2.0

    def stage_put(b):
        out = np.asarray(b) + 0.0
        staged.append(out)
        return out

    fn.stage_put = stage_put
    cells = [np.full(2, i, np.float32) for i in range(12)]
    out = run_shared(fn, cells, _identity_batcher, 4, prefetch=2)
    assert len(staged) == 3
    assert all(any(s is b for s in staged) for b in seen)
    for i, o in enumerate(out):
        np.testing.assert_array_equal(o, np.full(2, 2.0 * i))


def test_plain_device_fn_never_stages(monkeypatch):
    """A device fn without a transfer half (no stage_put) runs the
    legacy inline-transfer arm even with the gate on."""
    monkeypatch.setenv("SPARKDL_DEVICE_STAGE", "1")
    before = _stage_counters()
    cells = [np.full(2, i, np.float32) for i in range(10)]
    out = run_shared(lambda b: b + 1.0, cells, _identity_batcher, 4)
    got = {
        k: metrics.counter(f"transfer.{k}") - v for k, v in before.items()
    }
    assert got["stage_hits"] == got["stage_misses"] == 0
    np.testing.assert_array_equal(out[0], [1.0, 1.0])


def test_stage_put_error_fails_handles_and_feeder_recovers(monkeypatch):
    """A transfer-half failure propagates to the waiting partitions
    (executor retry semantics apply) and the feeder — buffer ring
    included — recovers for subsequent work."""
    monkeypatch.setenv("SPARKDL_DEVICE_STAGE", "1")
    boom = [True]

    def stage_put(b):
        if boom[0]:
            raise OSError("transfer link down")
        return np.asarray(b)

    def fn(batch):
        return np.asarray(batch) * 2.0

    fn.stage_put = stage_put
    cells = [np.full(2, i, np.float32) for i in range(12)]
    with pytest.raises(OSError, match="transfer link down"):
        run_shared(fn, cells, _identity_batcher, 4, prefetch=2)
    boom[0] = False
    out = run_shared(fn, cells, _identity_batcher, 4, prefetch=2)
    for i, o in enumerate(out):
        np.testing.assert_array_equal(o, np.full(2, 2.0 * i))


def test_buffer_ring_allocates_lazily(monkeypatch):
    """Ring slots are allocated on demand: a single short stream never
    pays for the full prefetch+stage+spare ring (the memory win for
    serving's model x rung x geometry feeder populations)."""
    monkeypatch.setenv("SPARKDL_DEVICE_STAGE", "1")
    device_fn = _staging_device_fn()
    cells = [np.full(2, i, np.float32) for i in range(5)]
    out = run_shared(device_fn, cells, _identity_batcher, 4, prefetch=2)
    np.testing.assert_array_equal(out[4], [8.0, 8.0])
    feeders = list(feeder_mod._feeders.values())
    assert len(feeders) == 1
    f = feeders[0]
    assert f._ring_cap == f.prefetch + f._stage_lag + 2
    # 2 batches total: at most filling + one in flight + one staged were
    # ever live at once — far under the cap the eager ring would have
    # pre-allocated.
    assert f._allocated < f._ring_cap
    assert f._allocated <= 3


def test_shutdown_feeders_closes_transfer_pool(monkeypatch):
    """shutdown_feeders() shuts the module-global H2D pools too: no
    sparkdl-h2d* thread survives (the feeder_smoke leak assertion)."""
    import threading

    from sparkdl_tpu.runtime import transfer

    monkeypatch.setenv("SPARKDL_DEVICE_STAGE", "1")
    cells = [np.full(2, i, np.float32) for i in range(8)]
    run_shared(_staging_device_fn(), cells, _identity_batcher, 4)
    assert any(
        t.name.startswith("sparkdl-h2d") for t in threading.enumerate()
    )
    shutdown_feeders()
    alive = [
        t.name
        for t in threading.enumerate()
        if t.is_alive() and t.name.startswith("sparkdl-h2d")
    ]
    assert alive == []
    assert transfer._POOL is None and transfer._STAGE_POOL is None


def test_executor_close_shuts_transfer_pool():
    from sparkdl_tpu.runtime import transfer

    transfer._stage_pool().submit(lambda: None).result()
    ex = Executor(max_workers=2)
    ex.map_partitions(lambda i, p: p, [[1], [2]])
    ex.close()
    import threading

    assert not any(
        t.is_alive() and t.name.startswith("sparkdl-h2d")
        for t in threading.enumerate()
    )


def test_device_preproc_transformer_parity(monkeypatch):
    """SPARKDL_DEVICE_PREPROC at identity geometry (source == model
    input) is bit-identical to the host-preproc arm — uint8->float,
    channel flip, and normalization all happen on device either way —
    and a real device resize stays numerically close to the host one."""
    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.runtime.executor import (
        default_executor,
        set_default_executor,
    )
    from sparkdl_tpu.transformers.image_model import ImageModelTransformer

    rng = np.random.default_rng(0)

    def structs(h, w, n):
        out = [
            imageIO.imageArrayToStruct(
                rng.integers(0, 256, size=(h, w, 3), dtype=np.uint8)
            )
            for _ in range(n)
        ]
        out[2] = None
        return out

    mf = ModelFunction(
        fn=lambda p, x: x.mean(axis=(1, 2)),
        params=None,
        input_shape=(6, 6, 3),
        name="meanpool",
    )
    xf = ImageModelTransformer(
        inputCol="image", outputCol="f", modelFunction=mf,
        targetHeight=6, targetWidth=6, preprocessing="tf", batchSize=4,
    )
    df = DataFrame.fromColumns({"image": structs(6, 6, 18)}, numPartitions=3)
    prev = default_executor()
    set_default_executor(Executor(max_workers=3))
    try:
        monkeypatch.setenv("SPARKDL_DEVICE_PREPROC", "0")
        host = [r.f for r in xf.transform(df).collect()]
        monkeypatch.setenv("SPARKDL_DEVICE_PREPROC", "1")
        dev = [r.f for r in xf.transform(df).collect()]
        for a, b in zip(dev, host):
            if b is None:
                assert a is None
            else:
                np.testing.assert_array_equal(a, b)
        # real resize: 12x12 sources -> 6x6 model input on device
        df2 = DataFrame.fromColumns(
            {"image": structs(12, 12, 8)}, numPartitions=2
        )
        dev2 = [r.f for r in xf.transform(df2).collect()]
        monkeypatch.setenv("SPARKDL_DEVICE_PREPROC", "0")
        host2 = [r.f for r in xf.transform(df2).collect()]
        for a, b in zip(dev2, host2):
            if b is None:
                assert a is None
            else:
                np.testing.assert_allclose(a, b, atol=0.05)
    finally:
        set_default_executor(prev)


def test_run_batched_staged_vs_legacy_parity(monkeypatch):
    """A direct call honors the staging A/B gate: both arms return
    identical cells, and the staged arm's hit+miss pair accounts for
    every dispatched batch."""
    device_fn = _staging_device_fn()
    cells = [
        None if i % 7 == 3 else np.full(2, i, dtype=np.float32)
        for i in range(25)
    ]
    monkeypatch.setenv("SPARKDL_DEVICE_STAGE", "1")
    before = _stage_counters()
    a = run_batched_shared(cells, _identity_batcher, device_fn, 4)
    got = {
        k: metrics.counter(f"transfer.{k}") - v for k, v in before.items()
    }
    # 21 valid rows (3, 10, 17 and 24 are None) pack into ceil(21/4)
    assert got["stage_hits"] + got["stage_misses"] == 6
    monkeypatch.setenv("SPARKDL_DEVICE_STAGE", "0")
    b = run_batched_shared(cells, _identity_batcher, device_fn, 4)
    for x, y in zip(a, b):
        if y is None:
            assert x is None
        else:
            assert x.tobytes() == y.tobytes()
