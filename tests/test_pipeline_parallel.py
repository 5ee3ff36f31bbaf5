"""GPipe-style pipeline parallelism: sequential-oracle parity on the
8-device CPU mesh (forward, backward, and dp×pp composition)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.parallel import make_mesh
from sparkdl_tpu.parallel.pipeline_parallel import (
    pipeline_apply,
    stack_stage_params,
)

D = 16


def _stage_fn(params, h):
    # One residual MLP block — signature-preserving, nonlinear.
    w, b = params["w"], params["b"]
    return h + jnp.tanh(h @ w + b)


def _stages(rng, n):
    return [
        {
            "w": jnp.asarray(rng.normal(size=(D, D)) * 0.3, jnp.float32),
            "b": jnp.asarray(rng.normal(size=(D,)) * 0.1, jnp.float32),
        }
        for _ in range(n)
    ]


def _sequential(stages, x):
    for p in stages:
        x = _stage_fn(p, x)
    return x


def test_pipeline_forward_matches_sequential():
    rng = np.random.default_rng(0)
    stages = _stages(rng, 8)
    x = jnp.asarray(rng.normal(size=(16, D)), jnp.float32)

    mesh = make_mesh({"pp": 8})
    out = pipeline_apply(
        _stage_fn, stack_stage_params(stages), x, mesh, axis="pp"
    )
    oracle = _sequential(stages, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(oracle), rtol=1e-5, atol=1e-6
    )


def test_pipeline_more_microbatches():
    rng = np.random.default_rng(1)
    stages = _stages(rng, 8)
    x = jnp.asarray(rng.normal(size=(32, D)), jnp.float32)

    mesh = make_mesh({"pp": 8})
    out = pipeline_apply(
        _stage_fn, stack_stage_params(stages), x, mesh,
        axis="pp", n_microbatches=16,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_sequential(stages, x)),
        rtol=1e-5, atol=1e-6,
    )


def test_pipeline_backward_matches_sequential():
    """jax.grad differentiates straight through the ppermute schedule —
    pipeline-parallel training without a hand-written backward pass."""
    rng = np.random.default_rng(2)
    stages = _stages(rng, 8)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(rng.normal(size=(16, D)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(16, D)), jnp.float32)
    mesh = make_mesh({"pp": 8})

    def loss_pp(p):
        out = pipeline_apply(_stage_fn, p, x, mesh, axis="pp")
        return jnp.mean((out - y) ** 2)

    def loss_seq(stages_list):
        return jnp.mean((_sequential(stages_list, x) - y) ** 2)

    g_pp = jax.grad(loss_pp)(stacked)
    g_seq = stack_stage_params(jax.grad(loss_seq)(stages))
    for a, b in zip(
        jax.tree_util.tree_leaves(g_pp), jax.tree_util.tree_leaves(g_seq)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


def test_pipeline_composes_with_dp():
    """2-D dp×pp mesh with dp_axis set: each dp shard pipelines its own
    slice of every microbatch, and the gathered output matches the
    sequential oracle."""
    rng = np.random.default_rng(3)
    stages = _stages(rng, 4)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(rng.normal(size=(8, D)), jnp.float32)

    mesh = make_mesh({"dp": 2, "pp": 4})
    out = pipeline_apply(
        _stage_fn, stacked, x, mesh, axis="pp", n_microbatches=4,
        dp_axis="dp",
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_sequential(stages, x)),
        rtol=1e-5, atol=1e-6,
    )


def test_pipeline_dp_geometry_validated():
    rng = np.random.default_rng(5)
    stages = _stages(rng, 4)
    mesh = make_mesh({"dp": 2, "pp": 4})
    # 4 microbatches of size 1 cannot shard over 2 dp shards
    with pytest.raises(ValueError, match="dp_axis"):
        pipeline_apply(
            _stage_fn, stack_stage_params(stages),
            jnp.zeros((4, D), jnp.float32), mesh, axis="pp",
            n_microbatches=4, dp_axis="dp",
        )


def test_pipeline_validates_geometry():
    rng = np.random.default_rng(4)
    stages = _stages(rng, 4)
    mesh = make_mesh({"pp": 8})
    x = jnp.zeros((8, D), jnp.float32)
    with pytest.raises(ValueError, match="stages"):
        pipeline_apply(_stage_fn, stack_stage_params(stages), x, mesh)
    stages8 = _stages(rng, 8)
    with pytest.raises(ValueError, match="divide"):
        pipeline_apply(
            _stage_fn, stack_stage_params(stages8),
            jnp.zeros((9, D), jnp.float32), mesh,
        )
