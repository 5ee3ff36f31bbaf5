import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.graph import (
    ModelFunction,
    ModelIngest,
    build_flattener,
    build_image_converter,
    image_structs_to_batch,
    piece,
)
from sparkdl_tpu.image import imageIO


def _linear_mf(din=4, dout=3, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(din, dout)), dtype=jnp.float32)
    b = jnp.asarray(rng.normal(size=(dout,)), dtype=jnp.float32)
    return ModelFunction(
        fn=lambda p, x: x @ p["w"] + p["b"],
        params={"w": w, "b": b},
        input_shape=(din,),
        input_dtype=jnp.float32,
        name="linear",
    )


def test_call_and_jit_agree():
    mf = _linear_mf()
    x = jnp.ones((2, 4))
    np.testing.assert_allclose(mf(x), mf.jitted()(x), rtol=1e-6)


def test_compose_and_then():
    mf = _linear_mf()
    combo = mf.and_then(lambda y: y * 2.0)
    x = jnp.ones((2, 4))
    np.testing.assert_allclose(np.asarray(combo(x)), np.asarray(mf(x)) * 2.0)


def test_compose_before_piece():
    mf = _linear_mf()
    pre = piece(lambda x: x + 1.0, name="inc")
    combo = mf.before(pre)
    x = jnp.zeros((2, 4))
    np.testing.assert_allclose(
        np.asarray(combo(x)), np.asarray(mf(jnp.ones((2, 4)))), rtol=1e-6
    )


def test_export_load_roundtrip(tmp_path):
    mf = _linear_mf()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 4)), jnp.float32)
    expected = np.asarray(mf(x))
    path = str(tmp_path / "exported")
    mf.export(path)  # symbolic batch dim
    loaded = ModelFunction.load(path)
    np.testing.assert_allclose(np.asarray(loaded(x)), expected, rtol=1e-5)
    # polymorphic batch: a different batch size must work too
    x8 = jnp.tile(x, (4, 1))
    assert np.asarray(loaded(x8)).shape == (8, 3)
    # params survive alongside the program for re-freezing
    assert "w" in loaded.raw_params


def test_image_converter_bgr_to_rgb_and_tf_mode():
    conv = build_image_converter(channel_order_in="BGR", preprocessing="tf")
    x = np.zeros((1, 2, 2, 3), dtype=np.uint8)
    x[..., 2] = 255  # red in BGR storage
    y = np.asarray(conv(jnp.asarray(x)))
    # After BGR->RGB: channel 0 is red=255 -> tf mode: 255/127.5-1 = 1.0
    np.testing.assert_allclose(y[..., 0], 1.0, atol=1e-6)
    np.testing.assert_allclose(y[..., 1], -1.0, atol=1e-6)


def test_normalize_modes_match_keras_conventions():
    from sparkdl_tpu.graph import normalize_fn

    x = jnp.full((1, 1, 1, 3), 255.0)
    np.testing.assert_allclose(np.asarray(normalize_fn("tf")(x)), 1.0, atol=1e-6)
    torch_out = np.asarray(normalize_fn("torch")(x))
    np.testing.assert_allclose(
        torch_out[0, 0, 0, 0], (1.0 - 0.485) / 0.229, rtol=1e-5
    )
    caffe_out = np.asarray(normalize_fn("caffe")(x))
    # caffe: RGB->BGR then mean-sub (BGR mean ordering)
    np.testing.assert_allclose(caffe_out[0, 0, 0, 0], 255.0 - 103.939, rtol=1e-5)


def test_flattener():
    f = build_flattener()
    y = np.asarray(f(jnp.ones((2, 3, 4))))
    assert y.shape == (2, 12) and y.dtype == np.float32


def test_image_structs_to_batch_nulls_and_resize():
    rng = np.random.default_rng(0)
    arrs = [
        rng.integers(0, 255, size=(10, 12, 3), dtype=np.uint8),
        rng.integers(0, 255, size=(8, 8, 3), dtype=np.uint8),
    ]
    structs = [imageIO.imageArrayToStruct(a) for a in arrs] + [None]
    batch, mask = image_structs_to_batch(structs, height=6, width=6)
    assert batch.shape == (3, 6, 6, 3)
    assert mask.tolist() == [True, True, False]
    assert batch[2].max() == 0


def test_image_structs_grayscale_broadcast():
    g = imageIO.imageArrayToStruct(np.full((5, 5), 7, dtype=np.uint8))
    batch, mask = image_structs_to_batch([g], height=5, width=5)
    assert mask[0] and batch.shape == (1, 5, 5, 3)
    assert (batch[0] == 7).all()


def test_ingest_from_flax():
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(2)(x)

    m = MLP()
    params = m.init(jax.random.PRNGKey(0), jnp.ones((1, 3)))
    mf = ModelIngest.from_flax(m, params, input_shape=(3,))
    y = mf(jnp.ones((4, 3)))
    assert y.shape == (4, 2)


def test_ingest_from_keras_matches_keras_predict():
    import keras

    keras.utils.set_random_seed(0)
    model = keras.Sequential(
        [
            keras.layers.Input((6,)),
            keras.layers.Dense(5, activation="relu"),
            keras.layers.Dense(3),
        ]
    )
    mf = ModelIngest.from_keras(model)
    x = np.random.default_rng(2).normal(size=(4, 6)).astype(np.float32)
    ours = np.asarray(mf(jnp.asarray(x)))
    theirs = model.predict(x, verbose=0)
    np.testing.assert_allclose(ours, theirs, rtol=1e-5, atol=1e-5)


def test_ingest_from_keras_file(tmp_path):
    import keras

    model = keras.Sequential(
        [keras.layers.Input((4,)), keras.layers.Dense(2)]
    )
    p = str(tmp_path / "m.keras")
    model.save(p)
    mf = ModelIngest.from_keras_file(p)
    x = np.ones((2, 4), dtype=np.float32)
    np.testing.assert_allclose(
        np.asarray(mf(jnp.asarray(x))), model.predict(x, verbose=0), rtol=1e-5
    )


class TestReferenceCompatAliases:
    """Upstream builder/tensorframes_udf symbols (SURVEY.md §3 #3/#7)."""

    def test_graph_function_is_model_function(self):
        import sparkdl_tpu
        from sparkdl_tpu.graph import GraphFunction, ModelFunction

        assert GraphFunction is ModelFunction
        assert sparkdl_tpu.GraphFunction is ModelFunction

    def test_isolated_session_names_the_migration(self):
        import sparkdl_tpu

        with pytest.raises(NotImplementedError, match="ModelIngest"):
            sparkdl_tpu.IsolatedSession()

    def test_make_graph_udf_registers_and_scores(self):
        import numpy as np

        import sparkdl_tpu
        from sparkdl_tpu import udf as udf_catalog
        from sparkdl_tpu.dataframe import DataFrame
        from sparkdl_tpu.graph import piece

        doubler = piece(lambda x: x * 2.0, name="doubler")
        sparkdl_tpu.makeGraphUDF(doubler, "compat_doubler")
        try:
            df = DataFrame.fromColumns(
                {"x": [np.ones(3, np.float32), None]}
            )
            rows = udf_catalog.apply_udf(
                "compat_doubler", df, "x", "y"
            ).collect()
            np.testing.assert_allclose(rows[0].y, [2.0, 2.0, 2.0])
            assert rows[1].y is None
            with pytest.raises(ValueError, match="blocked"):
                sparkdl_tpu.makeGraphUDF(doubler, "rowwise", blocked=False)
        finally:
            udf_catalog.unregister("compat_doubler")


# -- flat-input donation + persistent compile cache ---------------------------


@pytest.fixture()
def ledger_dir(tmp_path, monkeypatch):
    """The build ledger lives under the compile cache directory in force;
    the suite runs with the persistent cache off (conftest), so point the
    ledger at a directory of this test's own."""
    from sparkdl_tpu.runtime import compile_cache

    monkeypatch.setattr(compile_cache, "cache_dir", lambda: str(tmp_path))
    return tmp_path


def test_donation_gate_and_backend_support(monkeypatch):
    from sparkdl_tpu.graph import function as fmod

    monkeypatch.setenv("SPARKDL_DONATE_INPUT", "1")
    assert fmod.input_donation_enabled()
    monkeypatch.setenv("SPARKDL_DONATE_INPUT", "0")
    assert not fmod.input_donation_enabled()
    # CPU backend never engages (jax ignores donation there, and the
    # client may alias host numpy zero-copy): engagement is the arm
    # bench records, so it must reflect backend truth.
    monkeypatch.setenv("SPARKDL_DONATE_INPUT", "1")
    assert not fmod.input_donation_engaged()


def test_donation_on_off_parity(monkeypatch):
    """The donated build produces identical outputs to the plain build
    (forced engagement on CPU, where jax safely ignores the donation —
    the build path and cache keying are what's exercised)."""
    from sparkdl_tpu.graph import function as fmod

    monkeypatch.setattr(fmod, "_donation_supported", lambda: True)
    monkeypatch.setenv("SPARKDL_DONATE_INPUT", "1")
    mf_don = _linear_mf()
    assert fmod.input_donation_engaged()
    f_don = mf_don.jitted_flat((2, 4))
    monkeypatch.setenv("SPARKDL_DONATE_INPUT", "0")
    mf_plain = _linear_mf()
    f_plain = mf_plain.jitted_flat((2, 4))
    x = np.random.default_rng(3).normal(size=(8,)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(f_don(x.copy())), np.asarray(f_plain(x))
    )


def test_donation_uint8_fused_cast_parity(monkeypatch):
    """The image-shaped case the old comment called undonatable: a uint8
    flat input whose cast to float is FUSED into the program (converter
    first). The donated build must agree with the plain one."""
    from sparkdl_tpu.graph import function as fmod

    conv = build_image_converter(channel_order_in="BGR", preprocessing="tf")

    def pipeline():
        return conv.and_then(_linear_mf(din=3, dout=2)).and_then(
            build_flattener()
        )

    x = (
        np.random.default_rng(0)
        .integers(0, 256, size=(2 * 2 * 2 * 3,))
        .astype(np.uint8)
    )
    monkeypatch.setattr(fmod, "_donation_supported", lambda: True)
    monkeypatch.setenv("SPARKDL_DONATE_INPUT", "1")
    y_don = np.asarray(pipeline().jitted_flat((2, 2, 2, 3))(x.copy()))
    monkeypatch.setenv("SPARKDL_DONATE_INPUT", "0")
    y_plain = np.asarray(pipeline().jitted_flat((2, 2, 2, 3))(x))
    np.testing.assert_array_equal(y_don, y_plain)


def test_donation_arms_get_distinct_cache_entries(monkeypatch):
    """Flipping the donation arm mid-session must rebuild, never reuse
    the other arm's executable (same guarantee the placement key gives
    the param-capture knobs)."""
    from sparkdl_tpu.graph import function as fmod

    monkeypatch.setattr(fmod, "_donation_supported", lambda: True)
    mf = _linear_mf()
    monkeypatch.setenv("SPARKDL_DONATE_INPUT", "1")
    f_don = mf.jitted_flat((2, 4))
    monkeypatch.setenv("SPARKDL_DONATE_INPUT", "0")
    f_plain = mf.jitted_flat((2, 4))
    assert f_don is not f_plain
    # same arm again -> cached object, no rebuild
    assert mf.jitted_flat((2, 4)) is f_plain


def test_compile_cache_ledger_hits_and_misses(ledger_dir):
    """Second identical jitted_flat build (a FRESH ModelFunction, so no
    object-level cache short-circuits) records a compile-cache hit; the
    first records the miss. Different geometry is a different key."""
    from sparkdl_tpu.utils.metrics import metrics

    h0 = metrics.counter("compile.cache_hits")
    m0 = metrics.counter("compile.cache_misses")
    _linear_mf().jitted_flat((2, 4))
    assert metrics.counter("compile.cache_misses") - m0 == 1
    assert metrics.counter("compile.cache_hits") - h0 == 0
    _linear_mf().jitted_flat((2, 4))
    assert metrics.counter("compile.cache_hits") - h0 == 1
    _linear_mf().jitted_flat((4, 4))  # new geometry -> miss, not hit
    assert metrics.counter("compile.cache_misses") - m0 == 2
    assert len(list((ledger_dir / "ledger").glob("*.json"))) == 2


def test_compile_cache_off_records_nothing():
    """With jax's persistent cache switched off (as conftest does) no
    directory is in force and the ledger stays silent."""
    from sparkdl_tpu.runtime import compile_cache
    from sparkdl_tpu.utils.metrics import metrics

    assert compile_cache.cache_dir() is None
    h0 = metrics.counter("compile.cache_hits")
    m0 = metrics.counter("compile.cache_misses")
    _linear_mf().jitted_flat((2, 4))
    assert metrics.counter("compile.cache_hits") == h0
    assert metrics.counter("compile.cache_misses") == m0


def test_device_preproc_piece_identity_and_resize():
    from sparkdl_tpu.graph.pieces import build_device_preproc

    x = np.random.default_rng(0).integers(
        0, 256, size=(2, 4, 4, 3), dtype=np.uint8
    )
    ident = build_device_preproc((4, 4), (4, 4))
    y = np.asarray(ident(jnp.asarray(x)))
    np.testing.assert_array_equal(y, x.astype(np.float32))
    resized = build_device_preproc((4, 4), (2, 2))
    z = np.asarray(resized(jnp.asarray(x)))
    assert z.shape == (2, 2, 2, 3)
    assert np.isfinite(z).all()
