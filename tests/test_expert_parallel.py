"""Expert parallelism (MoE): dense-oracle parity on the 8-device CPU
mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.parallel import make_mesh
from sparkdl_tpu.parallel.expert_parallel import moe_apply, switch_route

D, E, T = 8, 8, 64


def _expert_fn(params, h):
    return jax.nn.relu(h @ params["w1"]) @ params["w2"]


def _params(rng):
    router_w = jnp.asarray(rng.normal(size=(D, E)) * 0.5, jnp.float32)
    expert_params = {
        "w1": jnp.asarray(rng.normal(size=(E, D, 2 * D)) * 0.3, jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(E, 2 * D, D)) * 0.3, jnp.float32),
    }
    return router_w, expert_params


def _oracle(router_w, expert_params, x):
    """Per-token: gate * expert_argmax(token) — valid when capacity is
    ample (no drops)."""
    probs = jax.nn.softmax(x @ router_w, axis=-1)
    chosen = np.argmax(np.asarray(probs), axis=-1)
    out = np.zeros_like(np.asarray(x))
    for t in range(x.shape[0]):
        e = int(chosen[t])
        p = {k: v[e] for k, v in expert_params.items()}
        out[t] = float(probs[t, e]) * np.asarray(
            _expert_fn(p, x[t][None, :])
        )[0]
    return out


def test_moe_matches_per_token_oracle():
    rng = np.random.default_rng(0)
    router_w, expert_params = _params(rng)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)

    mesh = make_mesh({"ep": 8})
    out = moe_apply(
        _expert_fn, router_w, expert_params, x, mesh, capacity=T,
    )
    np.testing.assert_allclose(
        np.asarray(out), _oracle(router_w, expert_params, x),
        rtol=1e-4, atol=1e-5,
    )


def test_moe_capacity_drops_to_zero():
    """All tokens routed to expert 0 with capacity 1: each shard keeps
    exactly one token, the rest output zeros. (A zero router gives every
    token identical logits, so argmax deterministically picks expert 0.)"""
    rng = np.random.default_rng(1)
    _, expert_params = _params(rng)
    router_w = jnp.zeros((D, E), jnp.float32)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)

    mesh = make_mesh({"ep": 8})
    out = np.asarray(
        moe_apply(_expert_fn, router_w, expert_params, x, mesh, capacity=1)
    )
    per_shard = T // 8
    kept = [t for t in range(T) if t % per_shard == 0]
    dropped = [t for t in range(T) if t % per_shard != 0]
    assert all(np.any(out[t] != 0) for t in kept)
    assert all(np.allclose(out[t], 0) for t in dropped)


def test_moe_gradients_flow():
    rng = np.random.default_rng(2)
    router_w, expert_params = _params(rng)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    mesh = make_mesh({"ep": 8})

    def loss(rw, ep):
        return jnp.mean(
            moe_apply(_expert_fn, rw, ep, x, mesh, capacity=T) ** 2
        )

    g_rw, g_ep = jax.grad(loss, argnums=(0, 1))(router_w, expert_params)
    assert np.isfinite(np.asarray(g_rw)).all()
    assert np.any(np.asarray(g_rw) != 0)  # router is differentiable
    for leaf in jax.tree_util.tree_leaves(g_ep):
        assert np.isfinite(np.asarray(leaf)).all()


def test_switch_route_shapes_and_slots():
    logits = jnp.asarray(
        [[5.0, 0.0], [5.0, 0.0], [5.0, 0.0], [0.0, 5.0]], jnp.float32
    )
    dispatch, combine = switch_route(logits, num_experts=2, capacity=2)
    assert dispatch.shape == (4, 2, 2)
    # tokens 0,1 fill expert 0's two slots; token 2 overflows (dropped)
    assert dispatch[0, 0, 0] == 1 and dispatch[1, 0, 1] == 1
    assert np.allclose(np.asarray(dispatch[2]), 0)
    assert dispatch[3, 1, 0] == 1
    # combine carries the gate prob on the same slots
    assert 0 < float(combine[0, 0, 0]) <= 1


def test_moe_validates_geometry():
    rng = np.random.default_rng(3)
    router_w, expert_params = _params(rng)
    mesh = make_mesh({"ep": 8})
    with pytest.raises(ValueError, match="Tokens"):
        moe_apply(
            _expert_fn, router_w, expert_params,
            jnp.zeros((7, D), jnp.float32), mesh,
        )
    with pytest.raises(ValueError, match="num_experts"):
        moe_apply(
            _expert_fn, jnp.zeros((D, 6), jnp.float32), expert_params,
            jnp.zeros((T, D), jnp.float32), mesh,
        )
