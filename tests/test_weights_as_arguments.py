"""Weights as program arguments (graph/function.py): a ModelFunction whose
builder marks it is jitted as `jit(fn)` and called with its parameter
tree, placed once a device, instead of closing over it."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models import get_model
from sparkdl_tpu.obs.spans import SpanRecorder, get_recorder, set_recorder
from sparkdl_tpu.transformers.execution import model_device_fn


@pytest.fixture(scope="module")
def tiny():
    return get_model("jamba-tiny").model_function(mode="embed", dtype=jnp.float32)


@pytest.fixture()
def recorder():
    old = get_recorder()
    fresh = SpanRecorder(10_000)
    set_recorder(fresh)
    yield fresh
    set_recorder(old)


def _ids(rows, length, seed=0):
    ids = np.random.default_rng(seed).integers(4, 512, (rows, length)).astype(np.int32)
    ids[0, length // 2 :] = 0  # one row padded on the right
    return ids


def _largest_constant(text: str) -> int:
    """Elements of the largest constant in a lowered module's text."""
    sizes = [1]
    for shape in re.findall(r"stablehlo\.constant dense<[^>]*> : tensor<([0-9x]+)x[a-z]", text):
        sizes.append(int(np.prod([int(d) for d in shape.split("x")])))
    return max(sizes)


def test_the_builder_marks_the_model_and_existing_models_keep_the_closure(tiny):
    assert tiny.weights_as_arguments is True
    call = tiny.jitted()
    assert hasattr(call, "place") and call is tiny.jitted()
    bert = get_model("bert-tiny").model_function(mode="embed")
    assert bert.weights_as_arguments is False
    assert not hasattr(bert.jitted(), "place")
    assert bert.jitted() is bert.jitted()


def test_one_executable_a_shape_and_no_weight_among_its_constants(tiny):
    call = tiny.jitted()
    before = call.program._cache_size()
    a, b = _ids(2, 32), _ids(2, 64)
    for _ in range(3):
        call(a), call(b)
    assert call.program._cache_size() - before == 2
    placed = call.place(jax.devices()[0])
    leaves = jax.tree_util.tree_leaves(tiny.params)
    matrices = [int(np.prod(x.shape)) for x in leaves if x.ndim == 2]
    as_arguments = call.program.lower(placed, a).as_text()
    assert _largest_constant(as_arguments) < min(matrices)
    # the closure form, for the contrast: the check can come out otherwise
    fn, params = tiny.fn, tiny.params
    closed = jax.jit(lambda x: fn(params, x)).lower(a).as_text()
    assert _largest_constant(closed) >= max(matrices)
    assert len(as_arguments) < len(closed) / 4


def test_same_answers_as_the_closure(tiny):
    fn, params = tiny.fn, tiny.params
    closed = jax.jit(lambda x: fn(params, x))
    for ids in (_ids(3, 32, 1), _ids(2, 128, 2)):
        # the same program but for what XLA folds of a constant: float32
        # roundings
        np.testing.assert_allclose(
            np.asarray(tiny.jitted()(ids)), np.asarray(closed(ids)),
            atol=1e-5, rtol=1e-5,
        )


def test_placed_once_a_device_before_the_first_batch_and_never_donated(recorder):
    mf = get_model("jamba-tiny").model_function(mode="embed", dtype=jnp.float32)
    fn = model_device_fn(mf)
    placed = [s for s in recorder.spans() if s.name == "param_place"]
    devices = {s.attrs["device"] for s in placed}
    assert len(placed) == len(devices) >= 1  # once each, before any batch
    want = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(mf.params))
    assert all(s.attrs["bytes"] == want and s.attrs["model"] == mf.name for s in placed)
    ids = _ids(4, 32, 3)
    first = np.asarray(fn((ids, (ids != 0).astype(np.int32))))
    again = np.asarray(fn((ids, (ids != 0).astype(np.int32))))
    np.testing.assert_array_equal(first, again)  # the tree is still there
    model_device_fn(mf)
    assert len([s for s in recorder.spans() if s.name == "param_place"]) == len(placed)
    # a batch committed to a device runs where it lies, on that device's tree
    dev = jax.devices()[-1]
    there = mf.jitted()(jax.device_put(ids, dev))
    assert there.devices() == {dev}
    np.testing.assert_allclose(np.asarray(there), first, atol=1e-6)
