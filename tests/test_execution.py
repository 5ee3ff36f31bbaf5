"""Units for the pipelined batched execution engine (execution.py).

The engine is the analogue of the reference's TensorFrames map_blocks hot
loop (SURVEY.md §4.1); these tests pin its semantics — fixed-size padded
batches, null-mask passthrough, ordering — independent of any model.
"""

import numpy as np
import pytest

from sparkdl_tpu.transformers.execution import (
    arrays_to_batch,
    run_batched_shared,
)


def _identity_batcher(chunk):
    batch = np.zeros((len(chunk), 2), dtype=np.float32)
    mask = np.zeros((len(chunk),), dtype=bool)
    for i, c in enumerate(chunk):
        if c is None:
            continue
        batch[i] = c
        mask[i] = True
    return batch, mask


def test_ordering_and_padding():
    cells = [np.full(2, i, dtype=np.float32) for i in range(10)]
    calls = []

    def device_fn(b):
        calls.append(b.shape)
        return b * 2.0

    out = run_batched_shared(cells, _identity_batcher, device_fn, batch_size=4)
    assert all(s == (4, 2) for s in calls)  # last batch padded to 4
    assert len(calls) == 3
    for i, o in enumerate(out):
        np.testing.assert_array_equal(o, np.full(2, 2.0 * i))


def test_null_rows_stay_null():
    cells = [np.ones(2, dtype=np.float32), None, np.full(2, 3.0), None]
    out = run_batched_shared(
        cells, _identity_batcher, lambda b: b + 1.0, batch_size=2
    )
    assert out[1] is None and out[3] is None
    np.testing.assert_array_equal(out[0], [2.0, 2.0])
    np.testing.assert_array_equal(out[2], [4.0, 4.0])


def test_all_null_batch_skips_device():
    cells = [None, None, None, None, np.ones(2, dtype=np.float32)]
    n_calls = []

    def device_fn(b):
        n_calls.append(1)
        return b

    out = run_batched_shared(cells, _identity_batcher, device_fn, batch_size=2)
    assert sum(n_calls) == 1  # the two all-null batches never dispatch
    assert out[:4] == [None, None, None, None]
    assert out[4] is not None


def test_empty_input():
    assert run_batched_shared([], _identity_batcher, lambda b: b, batch_size=4) == []


def test_prefetch_larger_than_batches():
    cells = [np.full(2, i, dtype=np.float32) for i in range(3)]
    out = run_batched_shared(
        cells, _identity_batcher, lambda b: b, batch_size=2, prefetch=16
    )
    assert len(out) == 3
    np.testing.assert_array_equal(out[2], [2.0, 2.0])


def test_host_stage_exception_propagates():
    def bad_batcher(chunk):
        raise ValueError("decode exploded")

    with pytest.raises(ValueError, match="decode exploded"):
        run_batched_shared([1, 2, 3], bad_batcher, lambda b: b, batch_size=2)


def test_arrays_to_batch_shape_mismatch():
    with pytest.raises(ValueError, match="inconsistent"):
        arrays_to_batch([np.ones(2), np.ones(3)])


def test_arrays_to_batch_all_none():
    batch, mask = arrays_to_batch([None, None])
    assert batch.shape == (2, 1)
    assert not mask.any()


# -- multi-device data-parallel inference -------------------------------------
# The reference's core distribution strategy is embarrassingly-parallel
# inference over partitions (SURVEY.md §3.2 row 1). Here batches round-robin
# across the 8 virtual devices; these tests prove N-device output is
# row-for-row identical to 1-device output.


def test_data_parallel_device_fn_round_robins_all_devices():
    import jax

    from sparkdl_tpu.transformers.execution import (
        data_parallel_device_fn,
        default_prefetch,
    )

    devs = jax.local_devices()
    assert len(devs) == 8, "conftest must force the 8-device CPU mesh"
    seen = []

    @jax.jit
    def f(b):
        return b * 2.0

    def spy(b):
        seen.append(b.devices())
        return f(b)

    dp_fn = data_parallel_device_fn(lambda b: spy(b), devices=devs)
    assert default_prefetch(dp_fn) == 16
    cells = [np.full(2, i, dtype=np.float32) for i in range(16)]
    out = run_batched_shared(cells, _identity_batcher, dp_fn, batch_size=2)
    used = set().union(*seen)
    assert used == set(devs)  # every device got work
    for i, o in enumerate(out):
        np.testing.assert_array_equal(o, np.full(2, 2.0 * i))


def test_multi_device_featurizer_matches_single_device(monkeypatch):
    """ImageModelTransformer on 8 devices == on 1 device, row for row."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.transformers import ImageModelTransformer

    rng = np.random.default_rng(0)
    structs = [
        imageIO.imageArrayToStruct(
            rng.integers(0, 256, size=(8, 8, 3), dtype=np.uint8)
        )
        for _ in range(21)
    ]
    structs[5] = None  # null row rides through on both paths
    df = DataFrame.fromColumns({"image": structs}, numPartitions=2)

    mf = ModelFunction(
        lambda p, x: jnp.mean(x, axis=(1, 2)),
        None,
        input_shape=(8, 8, 3),
        name="mean_pool",
    )

    def run(n_dev):
        monkeypatch.setenv("SPARKDL_INFERENCE_DEVICES", str(n_dev))
        xf = ImageModelTransformer(
            inputCol="image", outputCol="f", modelFunction=mf, batchSize=4
        )
        return xf.transform(df).collect()

    single = run(1)
    multi = run(8)
    assert single[5].f is None and multi[5].f is None
    for a, b in zip(single, multi):
        if a.f is None:
            assert b.f is None
            continue
        np.testing.assert_allclose(a.f, b.f, rtol=1e-6)


def test_nchw_flat_layout_matches_nhwc():
    """Channel-major flat packing (the TPU feed path) is numerically
    identical to the straight NHWC reshape."""
    import jax.numpy as jnp

    from sparkdl_tpu.graph.function import ModelFunction

    mf = ModelFunction(
        lambda p, x: jnp.mean(x.astype(jnp.float32), axis=(1, 2)),
        None,
        name="mean",
    )
    rng = np.random.default_rng(0)
    batch = rng.integers(0, 256, size=(4, 6, 5, 3), dtype=np.uint8)
    y_nhwc = mf.jitted_flat((4, 6, 5, 3))(
        np.ascontiguousarray(batch).reshape(-1)
    )
    y_nchw = mf.jitted_flat((4, 6, 5, 3), layout="nchw")(
        np.ascontiguousarray(batch.transpose(0, 3, 1, 2)).reshape(-1)
    )
    np.testing.assert_allclose(np.asarray(y_nhwc), np.asarray(y_nchw))


def test_flat_device_fn_uses_nchw_for_images():
    """flat_device_fn feeds image batches channel-major end-to-end; the
    identity oracle is permutation-SENSITIVE, so any mispacked transpose/
    reshape pair in the layout round-trip fails per-pixel."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.transformers.execution import flat_device_fn

    mf = ModelFunction(lambda p, x: x, None)
    rng = np.random.default_rng(1)
    batch = rng.integers(0, 256, size=(3, 4, 5, 3), dtype=np.uint8)
    fn = flat_device_fn(mf, (3, 4, 5, 3))
    assert hasattr(fn, "host_prepare")  # producer-thread relayout hook
    np.testing.assert_array_equal(np.asarray(fn(batch)), batch)
    # the prepared-flat path (what the feeder's owner dispatches) agrees
    np.testing.assert_array_equal(
        np.asarray(fn(fn.host_prepare(batch))), batch
    )


def test_shard_map_mode_matches_round_robin(monkeypatch):
    """shard_map inference mode (one mesh-sharded program) produces
    row-identical output to round-robin AND to single-device, nulls
    included — the mode is purely an execution-strategy choice."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.transformers import ImageModelTransformer

    rng = np.random.default_rng(1)
    structs = [
        imageIO.imageArrayToStruct(
            rng.integers(0, 256, size=(8, 8, 3), dtype=np.uint8)
        )
        for _ in range(19)
    ]
    structs[2] = None
    df = DataFrame.fromColumns({"image": structs}, numPartitions=2)

    mf = ModelFunction(
        lambda p, x: jnp.mean(x, axis=(1, 2)),
        None,
        input_shape=(8, 8, 3),
        name="mean_pool",
    )

    def run(mode, n_dev):
        monkeypatch.setenv("SPARKDL_INFERENCE_DEVICES", str(n_dev))
        monkeypatch.setenv("SPARKDL_INFERENCE_MODE", mode)
        xf = ImageModelTransformer(
            inputCol="image", outputCol="f", modelFunction=mf, batchSize=4
        )
        return xf.transform(df).collect()

    single = run("roundrobin", 1)
    rr = run("roundrobin", 8)
    sm = run("shard_map", 8)
    for a, b, c in zip(single, rr, sm):
        if a.f is None:
            assert b.f is None and c.f is None
            continue
        np.testing.assert_allclose(a.f, b.f, rtol=1e-6)
        np.testing.assert_allclose(a.f, c.f, rtol=1e-6)


def test_sharded_fn_engages_all_devices_in_one_dispatch():
    import jax

    from sparkdl_tpu.transformers.execution import (
        default_prefetch,
        sharded_data_parallel_fn,
    )

    devs = jax.local_devices()
    assert len(devs) == 8

    @jax.jit
    def f(b):
        return b * 3.0

    fn = sharded_data_parallel_fn(f, devices=devs)
    assert fn.batch_multiplier == 8
    assert default_prefetch(fn) == 2  # global-batch windows, not per-device
    x = np.arange(32, dtype=np.float32).reshape(32, 1)
    y = fn(x)
    assert set(y.devices()) == set(devs)  # one output spans the mesh
    np.testing.assert_allclose(np.asarray(y), x * 3.0)


def test_mode_toggle_mid_session_takes_effect(monkeypatch):
    """Toggling SPARKDL_INFERENCE_MODE between transforms of the SAME
    transformer must rebuild the device fn (cache keys include the
    dispatch env) — the documented A/B workflow."""
    import jax.numpy as jnp

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.transformers import ModelTransformer

    mf = ModelFunction(
        lambda p, x: x * 2.0, None, input_shape=(3,), name="x2"
    )
    xf = ModelTransformer(
        inputCol="v", outputCol="o", modelFunction=mf, batchSize=4,
        flattenOutput=False,
    )
    df = DataFrame.fromColumns(
        {"v": [np.ones(3, np.float32) * i for i in range(8)]}
    )

    monkeypatch.setenv("SPARKDL_INFERENCE_MODE", "roundrobin")
    xf.transform(df).count()
    fn_rr = xf._device_fn()
    monkeypatch.setenv("SPARKDL_INFERENCE_MODE", "shard_map")
    fn_sm = xf._device_fn()
    assert fn_rr is not fn_sm, "mode toggle silently reused cached fn"
    assert getattr(fn_sm, "batch_multiplier", 1) == 8
    out = xf.transform(df).collect()
    np.testing.assert_allclose(out[3].o, np.ones(3) * 6.0)


def test_prefetch_iter_order_exceptions_and_abandonment():
    import gc
    import time

    from sparkdl_tpu.transformers.execution import prefetch_iter

    # ordering preserved
    assert list(prefetch_iter(iter(range(20)), depth=3)) == list(range(20))

    # exceptions relay with traceback
    def boom():
        yield 1
        raise RuntimeError("producer failed")

    it = prefetch_iter(boom())
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="producer failed"):
        list(it)

    # abandonment stops the producer: yields stay bounded near depth
    produced = {"n": 0}

    def endless():
        while True:
            produced["n"] += 1
            yield produced["n"]

    it = prefetch_iter(endless(), depth=2)
    assert next(it) == 1
    it.close()  # consumer walks away
    gc.collect()
    mark = produced["n"]
    time.sleep(0.3)
    # producer observed stop: at most one in-flight item after the mark
    assert produced["n"] <= mark + 1, (mark, produced["n"])


def test_prefetch_env_knob(monkeypatch):
    """SPARKDL_PREFETCH_PER_DEVICE deepens the default in-flight window
    (the high-RTT-link tuning knob) and results stay identical at any
    depth."""
    from sparkdl_tpu.transformers.execution import default_prefetch

    cells = [np.full(2, i, dtype=np.float32) for i in range(7)]
    baseline = run_batched_shared(
        cells, _identity_batcher, lambda b: b, batch_size=2
    )
    monkeypatch.setenv("SPARKDL_PREFETCH_PER_DEVICE", "8")
    assert default_prefetch() == 8
    deep = run_batched_shared(cells, _identity_batcher, lambda b: b, batch_size=2)
    assert len(deep) == len(baseline) == 7
    for a, b in zip(deep, baseline):
        np.testing.assert_array_equal(a, b)


def test_h2d_chunking_equivalence(monkeypatch):
    """SPARKDL_H2D_CHUNK_MB splits the flat feed into several small
    device_puts + an on-device concat; outputs must match the one-shot
    path exactly (single-device only — with a pool the sharded global
    batch already splits)."""
    import jax.numpy as jnp

    from sparkdl_tpu.graph.function import piece
    from sparkdl_tpu.transformers.execution import flat_device_fn

    mf = piece(lambda x: x.astype(jnp.float32) * 2.0, name="double")
    shape = (8, 512, 512, 3)  # 6 MB uint8: big enough to really split
    rng = np.random.default_rng(0)
    batch = rng.integers(0, 255, size=shape).astype(np.uint8)

    monkeypatch.setenv("SPARKDL_INFERENCE_DEVICES", "1")
    fn_plain = flat_device_fn(mf, shape)
    ref = np.asarray(fn_plain(batch.copy()))

    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "32")  # > batch: no split
    fn_nosplit = flat_device_fn(mf, shape)
    np.testing.assert_array_equal(np.asarray(fn_nosplit(batch.copy())), ref)

    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "1")  # 6 splits
    fn_chunked = flat_device_fn(mf, shape)
    out = np.asarray(fn_chunked(batch.copy()))
    np.testing.assert_array_equal(out, ref)

    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "0")  # explicit opt-out
    fn_off = flat_device_fn(mf, shape)
    np.testing.assert_array_equal(np.asarray(fn_off(batch.copy())), ref)

    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "-3")
    with pytest.raises(ValueError, match="megabytes"):
        flat_device_fn(mf, shape)


def test_h2d_chunking_inert_on_device_pool(monkeypatch):
    """With a real device pool the sharded global batch already splits
    per device; the chunk knob must not disturb multi-device results."""
    import jax.numpy as jnp

    from sparkdl_tpu.graph.function import piece
    from sparkdl_tpu.transformers.execution import flat_device_fn

    mf = piece(lambda x: x.astype(jnp.float32) + 1.0, name="inc")
    shape = (2, 32, 32, 3)  # per-device batch; global = 2 * n_devices
    rng = np.random.default_rng(1)

    monkeypatch.delenv("SPARKDL_INFERENCE_DEVICES", raising=False)
    fn_plain = flat_device_fn(mf, shape)
    n_global = 2 * fn_plain.batch_multiplier
    batch = rng.integers(0, 255, size=(n_global, *shape[1:])).astype(np.uint8)
    ref = np.asarray(fn_plain(batch.copy()))

    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "1")
    fn_knob = flat_device_fn(mf, shape)
    np.testing.assert_array_equal(np.asarray(fn_knob(batch.copy())), ref)
