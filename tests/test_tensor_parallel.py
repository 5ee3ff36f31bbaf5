"""Megatron-style tensor parallelism: dense-oracle parity on the
8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.parallel import make_mesh
from sparkdl_tpu.parallel.tensor_parallel import (
    shard_dense_params,
    tp_block_sharded,
)

D_IN, D_FF, D_OUT = 16, 64, 16


def _weights(rng, bias=False):
    w1 = jnp.asarray(rng.normal(size=(D_IN, D_FF)) * 0.2, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(D_FF, D_OUT)) * 0.2, jnp.float32)
    if not bias:
        return w1, w2, None, None
    b1 = jnp.asarray(rng.normal(size=(D_FF,)) * 0.1, jnp.float32)
    b2 = jnp.asarray(rng.normal(size=(D_OUT,)) * 0.1, jnp.float32)
    return w1, w2, b1, b2


def _oracle(x, w1, w2, b1, b2):
    h = x @ w1
    if b1 is not None:
        h = h + b1
    h = np.maximum(np.asarray(h), 0.0)
    y = h @ np.asarray(w2)
    if b2 is not None:
        y = y + np.asarray(b2)
    return np.asarray(y)


def test_tp_block_matches_dense():
    rng = np.random.default_rng(0)
    w1, w2, _, _ = _weights(rng)
    x = jnp.asarray(rng.normal(size=(4, D_IN)), jnp.float32)

    mesh = make_mesh({"tp": 8})
    out = tp_block_sharded(x, w1, w2, mesh)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(x, w1, w2, None, None),
        rtol=1e-5, atol=1e-6,
    )


def test_tp_block_with_biases():
    """Column-sharded b1 applies pre-psum; full b2 applies post-psum
    exactly once."""
    rng = np.random.default_rng(1)
    w1, w2, b1, b2 = _weights(rng, bias=True)
    x = jnp.asarray(rng.normal(size=(4, D_IN)), jnp.float32)

    mesh = make_mesh({"tp": 8})
    out = tp_block_sharded(x, w1, w2, mesh, b1=b1, b2=b2)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(x, w1, w2, b1, b2), rtol=1e-5, atol=1e-6
    )


def test_tp_composes_with_dp():
    rng = np.random.default_rng(2)
    w1, w2, _, _ = _weights(rng)
    x = jnp.asarray(rng.normal(size=(8, D_IN)), jnp.float32)

    mesh = make_mesh({"dp": 2, "tp": 4})
    out = tp_block_sharded(x, w1, w2, mesh, dp_axis="dp")
    np.testing.assert_allclose(
        np.asarray(out), _oracle(x, w1, w2, None, None),
        rtol=1e-5, atol=1e-6,
    )


def test_shard_dense_params_layouts():
    rng = np.random.default_rng(3)
    w1, w2, b1, b2 = _weights(rng, bias=True)
    mesh = make_mesh({"tp": 8})
    sw1, sw2, sb1, sb2 = shard_dense_params(w1, w2, mesh, b1=b1, b2=b2)
    assert sw1.sharding.spec == (None, "tp")
    assert sw2.sharding.spec == ("tp", None)
    assert sb1.sharding.spec == ("tp",)
    # pre-sharded arrays flow through the wrapper unchanged
    x = jnp.asarray(rng.normal(size=(4, D_IN)), jnp.float32)
    out = tp_block_sharded(x, sw1, sw2, mesh, b1=sb1, b2=sb2)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(x, w1, w2, b1, b2), rtol=1e-5, atol=1e-6
    )


def test_tp_rejects_indivisible_width():
    rng = np.random.default_rng(4)
    w1 = jnp.zeros((D_IN, 60), jnp.float32)  # 60 % 8 != 0
    w2 = jnp.zeros((60, D_OUT), jnp.float32)
    mesh = make_mesh({"tp": 8})
    with pytest.raises(ValueError, match="divide over tp"):
        tp_block_sharded(jnp.zeros((2, D_IN)), w1, w2, mesh)


def test_tp_grad_matches_dense():
    """Gradients flow through the psum — TP training works untouched."""
    rng = np.random.default_rng(5)
    w1, w2, _, _ = _weights(rng)
    x = jnp.asarray(rng.normal(size=(4, D_IN)), jnp.float32)
    mesh = make_mesh({"tp": 8})

    def loss_tp(w1_, w2_):
        return jnp.mean(tp_block_sharded(x, w1_, w2_, mesh) ** 2)

    def loss_dense(w1_, w2_):
        return jnp.mean((jax.nn.relu(x @ w1_) @ w2_) ** 2)

    g_tp = jax.grad(loss_tp, argnums=(0, 1))(w1, w2)
    g_dense = jax.grad(loss_dense, argnums=(0, 1))(w1, w2)
    for a, b in zip(g_tp, g_dense):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


def test_tp_validates_dp_batch_and_dff_mismatch():
    rng = np.random.default_rng(6)
    w1, w2, _, _ = _weights(rng)
    mesh = make_mesh({"dp": 2, "tp": 4})
    with pytest.raises(ValueError, match="dp_axis"):
        tp_block_sharded(
            jnp.zeros((5, D_IN), jnp.float32), w1, w2, mesh, dp_axis="dp"
        )
    w2_bad = jnp.zeros((32, D_OUT), jnp.float32)
    with pytest.raises(ValueError, match="disagree"):
        tp_block_sharded(jnp.zeros((4, D_IN), jnp.float32), w1, w2_bad, mesh)
