"""Sequence-parallel attention is DIFFERENTIABLE: ring and Ulysses
gradients match the dense oracle on the 8-device CPU mesh.

Long-context training is first-class (the reference had no long-context
support at all — SURVEY.md §6): these tests pin that jax.grad flows
through the ppermute ring schedule and the all-to-all head exchange,
not just the forward pass the parity tests cover."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.models.bert import dense_attention
from sparkdl_tpu.ops import (
    ring_attention_sharded,
    ulysses_attention_sharded,
)
from sparkdl_tpu.parallel import make_mesh


def _qkv(rng, B, H, L, D):
    return tuple(
        jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.float32)
        for _ in range(3)
    )


def _grads(fn, q, k, v):
    def loss(q, k, v):
        out = fn(q, k, v)
        # a non-uniform weighting so dq/dk/dv are all informative
        w = jnp.arange(out.size, dtype=jnp.float32).reshape(out.shape)
        return jnp.sum(out * w) / out.size

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("masked", [False, True])
def test_ring_attention_grads_match_dense(masked):
    rng = np.random.default_rng(0)
    B, H, L, D = 2, 4, 32, 8
    q, k, v = _qkv(rng, B, H, L, D)
    if masked:
        m = np.zeros((B, 1, 1, L), np.float32)
        m[:, :, :, L - 6:] = np.finfo(np.float32).min
        mask = jnp.asarray(m)
    else:
        mask = None
    mesh = make_mesh({"sp": 8})

    dense = _grads(
        lambda q, k, v: dense_attention(q, k, v, mask, jnp.float32),
        q, k, v,
    )
    ring = _grads(
        lambda q, k, v: ring_attention_sharded(
            q, k, v, mask, mesh, axis="sp"
        ),
        q, k, v,
    )
    for g_d, g_r, name in zip(dense, ring, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g_r), np.asarray(g_d), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch",
        )


def test_ulysses_attention_grads_match_dense():
    rng = np.random.default_rng(1)
    B, H, L, D = 2, 8, 32, 8
    q, k, v = _qkv(rng, B, H, L, D)
    mesh = make_mesh({"sp": 8})

    dense = _grads(
        lambda q, k, v: dense_attention(q, k, v, None, jnp.float32),
        q, k, v,
    )
    uly = _grads(
        lambda q, k, v: ulysses_attention_sharded(
            q, k, v, None, mesh, axis="sp"
        ),
        q, k, v,
    )
    for g_d, g_u, name in zip(dense, uly, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g_u), np.asarray(g_d), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch",
        )
