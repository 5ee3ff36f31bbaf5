"""Units for the H2D transfer strategies (runtime/transfer.py) and the
fused chunk-dispatch feed (ModelFunction.jitted_flat_parts +
SPARKDL_H2D_FUSE in execution.flat_device_fn).

The serial chunk loop (N puts + concat dispatch + model dispatch) makes
N+2 client calls per batch. The strategies below collapse that to 1-2
calls; every mode must be bit-identical to the plain path — only the
call pattern may differ. (Analogue of the reference's TensorFrames feed scheduling,
SURVEY.md §3.1, which delegated this to libtensorflow.)
"""

import numpy as np
import pytest

from sparkdl_tpu.runtime.transfer import (
    chunk_views,
    chunked_device_put,
    padded_chunk_views,
    put_pytree_chunked,
)


def _cpu_device():
    import jax

    return jax.devices()[0]


def test_chunk_views_cover_buffer_exactly():
    flat = np.arange(1000, dtype=np.float32)
    views = chunk_views(flat, 1024)  # 256 elems per chunk
    assert len(views) == 4
    np.testing.assert_array_equal(np.concatenate(views), flat)
    # single-chunk case
    assert len(chunk_views(flat, 1 << 20)) == 1


@pytest.mark.parametrize("mode", ["serial", "onecall", "threads"])
def test_chunked_device_put_modes_identical(mode):
    flat = np.random.default_rng(0).integers(
        0, 255, size=(10_000,), dtype=np.uint8
    )
    out = chunked_device_put(flat, _cpu_device(), 1024, mode=mode)
    np.testing.assert_array_equal(np.asarray(out), flat)


def test_chunked_device_put_rejects_nd_and_bad_mode(monkeypatch):
    with pytest.raises(ValueError, match="flat 1-D"):
        chunked_device_put(np.zeros((2, 2)), _cpu_device(), 1024)
    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MODE", "bogus")
    with pytest.raises(ValueError, match="SPARKDL_H2D_CHUNK_MODE"):
        chunked_device_put(np.zeros(8), _cpu_device(), 2)


def test_put_pytree_chunked_small_and_large_leaves():
    params = {
        "small": np.arange(10, dtype=np.float32),
        "big": np.random.default_rng(1).standard_normal((64, 33)).astype(
            np.float32
        ),
        "scalar": np.float32(3.0),
    }
    placed = put_pytree_chunked(params, _cpu_device(), 256)  # big splits
    np.testing.assert_array_equal(np.asarray(placed["small"]), params["small"])
    np.testing.assert_array_equal(np.asarray(placed["big"]), params["big"])
    assert placed["big"].shape == (64, 33)
    assert float(placed["scalar"]) == 3.0


def test_jitted_flat_parts_matches_jitted_flat():
    import jax.numpy as jnp

    from sparkdl_tpu.graph.function import piece

    mf = piece(lambda x: x.astype(jnp.float32) + 1.0, name="inc")
    shape = (4, 6, 5, 3)
    rng = np.random.default_rng(2)
    batch = rng.integers(0, 255, size=shape).astype(np.uint8)
    for layout, packed in (
        ("nhwc", np.ascontiguousarray(batch).reshape(-1)),
        ("nchw", np.ascontiguousarray(batch.transpose(0, 3, 1, 2)).reshape(-1)),
    ):
        ref = np.asarray(mf.jitted_flat(shape, layout=layout)(packed))
        # ~3 chunks with a padded tail (shared splitter: the same
        # arithmetic the fused feed uses)
        views, k = padded_chunk_views(packed, packed.size // 3 + 1)
        parts_fn = mf.jitted_flat_parts(shape, len(views), k, layout=layout)
        np.testing.assert_array_equal(np.asarray(parts_fn(*views)), ref)


def test_padded_chunk_views_contract():
    flat = np.arange(1000, dtype=np.uint8)
    views, k = padded_chunk_views(flat, 300)
    assert len(views) == 4 and all(v.size == k for v in views)
    np.testing.assert_array_equal(np.concatenate(views)[:1000], flat)
    assert np.all(np.concatenate(views)[1000:] == 0)
    # exact division: no padding, views alias the buffer
    views, k = padded_chunk_views(np.arange(1000, dtype=np.uint8), 500)
    assert len(views) == 2 and k == 500
    # one chunk
    views, k = padded_chunk_views(flat, 10_000)
    assert len(views) == 1


@pytest.mark.parametrize("fuse", ["implicit", "put"])
def test_fused_feed_equivalence(monkeypatch, fuse):
    """SPARKDL_H2D_FUSE folds the chunk concat into the model program;
    outputs must match the plain path exactly, including when the last
    chunk needs padding."""
    import jax.numpy as jnp

    from sparkdl_tpu.graph.function import piece
    from sparkdl_tpu.transformers.execution import flat_device_fn

    mf = piece(lambda x: x.astype(jnp.float32) * 2.0, name="double")
    # 8*511*511*3 = 6.0 MB uint8, NOT divisible by 1 MB chunks -> the
    # tail-pad path runs
    shape = (8, 511, 511, 3)
    rng = np.random.default_rng(3)
    batch = rng.integers(0, 255, size=shape).astype(np.uint8)

    monkeypatch.setenv("SPARKDL_INFERENCE_DEVICES", "1")
    ref = np.asarray(flat_device_fn(mf, shape)(batch.copy()))

    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "1")
    monkeypatch.setenv("SPARKDL_H2D_FUSE", fuse)
    out = np.asarray(flat_device_fn(mf, shape)(batch.copy()))
    np.testing.assert_array_equal(out, ref)


def test_fused_feed_rejects_bad_mode(monkeypatch):
    from sparkdl_tpu.graph.function import piece
    from sparkdl_tpu.transformers.execution import flat_device_fn

    monkeypatch.setenv("SPARKDL_INFERENCE_DEVICES", "1")
    monkeypatch.setenv("SPARKDL_H2D_FUSE", "sideways")
    with pytest.raises(ValueError, match="SPARKDL_H2D_FUSE"):
        flat_device_fn(piece(lambda x: x, name="id"), (2, 4, 4, 3))


def test_fuse_toggle_invalidates_transformer_cache(monkeypatch):
    """Toggling SPARKDL_H2D_FUSE mid-session must rebuild the
    transformer's cached device fn (dispatch_env_key contract): an A/B
    that flips the env between transforms must actually change feed
    strategy, not silently reuse the old executable while bench records
    the new arm."""
    from sparkdl_tpu.transformers.execution import dispatch_env_key

    monkeypatch.delenv("SPARKDL_H2D_FUSE", raising=False)
    base = dispatch_env_key()
    monkeypatch.setenv("SPARKDL_H2D_FUSE", "implicit")
    assert dispatch_env_key() != base
    monkeypatch.setenv("SPARKDL_H2D_FUSE", "put")
    keys = {base, dispatch_env_key()}
    monkeypatch.setenv("SPARKDL_PARAM_PLACEMENT", "chunked")
    assert dispatch_env_key() not in keys


def test_placement_toggle_invalidates_model_function_caches(monkeypatch):
    """ModelFunction's jit caches key on the param-capture env: flipping
    SPARKDL_PARAM_PLACEMENT or SPARKDL_H2D_CHUNK_MB mid-session must not
    reuse an executable built with the old capture."""
    from sparkdl_tpu.graph.function import piece

    mf = piece(lambda x: x * 1.0, name="id")
    monkeypatch.delenv("SPARKDL_PARAM_PLACEMENT", raising=False)
    f1 = mf.jitted_flat((4,))
    monkeypatch.setenv("SPARKDL_PARAM_PLACEMENT", "chunked")
    f2 = mf.jitted_flat((4,))
    assert f1 is not f2
    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "2")
    assert mf.jitted_flat((4,)) is not f2
    # same env -> cache hit
    assert mf.jitted_flat((4,)) is mf.jitted_flat((4,))
    g1 = mf.jitted()
    monkeypatch.setenv("SPARKDL_H2D_CHUNK_MB", "3")
    assert mf.jitted() is not g1


def test_param_placement_noop_off_tpu(monkeypatch):
    """SPARKDL_PARAM_PLACEMENT=chunked is a no-op unless exactly one
    local TPU device exists (the CPU test mesh has 8), so the flag is
    safe to set globally."""
    from sparkdl_tpu.graph.function import ModelFunction

    params = {"w": np.arange(6, dtype=np.float32)}
    mf = ModelFunction(fn=lambda p, x: x * p["w"][0], params=params)
    monkeypatch.setenv("SPARKDL_PARAM_PLACEMENT", "chunked")
    assert mf._capture_params() is params
    out = np.asarray(mf.jitted()(np.ones(3, dtype=np.float32)))
    np.testing.assert_array_equal(out, np.zeros(3))
