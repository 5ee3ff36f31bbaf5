"""GShard-style expert parallelism (MoE) over an 'ep' mesh axis.

The reference had no mixture-of-experts (SURVEY.md §3.2 lists EP as
absent); this completes the mesh-axis family (dp/tp/pp/sp/ep) with the
TPU-native formulation (Lepikhin et al., "GShard", 2006.16668; Fedus et
al., "Switch Transformer", 2101.03961): routing is expressed as dense
one-hot dispatch/combine einsums over a STATIC capacity axis — no
dynamic shapes, so XLA tiles everything onto the MXU — and experts are
sharded over the 'ep' axis with two ``all_to_all`` collectives moving
token slots to their expert's device and back.

Shapes (per 'ep' shard, n = axis size, E = total experts):

    x        [T, D]        local tokens
    dispatch [T, E, C]     one-hot: token t -> expert e, slot c
    staged   [E, C, D]     einsum(dispatch, x) — slots for every expert
    --all_to_all-->        [E/n, n*C, D]  local experts, slots from all
    expert MLP             (vmapped over the local expert axis)
    --all_to_all-->        [E, C, D] back to token owners
    out      [T, D]        einsum(combine, staged)

Top-1 (Switch) routing with capacity dropping: tokens beyond an
expert's capacity C contribute zero output (standard MoE semantics);
``capacity_factor`` sizes C = ceil(T/E · factor). The router is
differentiable through the combine weights, and the whole layer is
plain lax code — ``jax.grad`` works through both all_to_alls.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def switch_route(router_logits, num_experts: int, capacity: int):
    """Top-1 routing -> (dispatch [T,E,C] one-hot, combine [T,E,C]).

    Slot assignment is by arrival order within each expert (cumsum over
    the token axis); tokens past ``capacity`` are dropped (all-zero
    dispatch row -> zero output for that token).
    """
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                       # [T]
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.float32)
    # position of each token within its expert's arrival order
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot        # [T, E]
    slot = jnp.sum(pos, axis=-1).astype(jnp.int32)            # [T]
    keep = (slot < capacity).astype(jnp.float32)
    dispatch = (
        onehot[:, :, None]
        * jax.nn.one_hot(slot, capacity, dtype=jnp.float32)[:, None, :]
        * keep[:, None, None]
    )                                                          # [T, E, C]
    # dispatch already carries the keep mask, so the gate needn't.
    gate = jnp.sum(probs * onehot, axis=-1)                   # [T]
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def _local_moe(expert_fn, axis_name, num_experts, capacity):
    """Per-device MoE body for use inside shard_map over ``axis_name``.

    ``router_w`` [D, E]; ``expert_params`` pytree with leaves stacked on
    a leading local-expert axis [E/n, ...]; ``x`` [T, D] local tokens.
    """

    def run(router_w, expert_params, x):
        dispatch, combine = switch_route(
            x @ router_w, num_experts, capacity
        )
        staged = jnp.einsum(
            "tec,td->ecd", dispatch, x.astype(jnp.float32)
        )                                                      # [E, C, D]
        # all_to_all: split the expert axis across devices, gather the
        # slot axis -> [E/n, n*C, D]: this device's experts, every
        # device's slots.
        staged = jax.lax.all_to_all(
            staged, axis_name, split_axis=0, concat_axis=1, tiled=True
        )
        out = jax.vmap(expert_fn)(expert_params, staged)
        out = jax.lax.all_to_all(
            out, axis_name, split_axis=1, concat_axis=0, tiled=True
        )                                                      # [E, C, D]
        return jnp.einsum("tec,ecd->td", combine, out).astype(x.dtype)

    return run


def moe_apply(
    expert_fn: Callable[[Any, jax.Array], jax.Array],
    router_w: jax.Array,
    expert_params: Any,
    x: jax.Array,
    mesh,
    axis: str = "ep",
    capacity_factor: float = 2.0,
    capacity: Optional[int] = None,
):
    """Apply a top-1 MoE layer with experts sharded over ``axis``.

    ``expert_fn(params_e, h) -> h`` is one expert ([C', D] -> [C', D]);
    ``expert_params`` leaves are stacked [E, ...] and get sharded
    P(axis); ``router_w`` [D, E]; ``x`` [T, D] tokens, sharded over
    ``axis`` (each shard routes its own tokens — the dp-over-tokens ×
    ep-over-experts square layout standard for MoE).

    Returns [T, D]. Dropped tokens (capacity overflow) produce zeros.
    """
    E = router_w.shape[-1]
    n = mesh.shape[axis]
    if E % n:
        raise ValueError(
            f"num_experts {E} must divide over ep axis {axis!r} ({n})"
        )
    leaves = jax.tree_util.tree_leaves(expert_params)
    if not leaves:
        raise ValueError("expert_params is an empty pytree")
    bad = [l.shape[:1] for l in leaves if l.shape[:1] != (E,)]
    if bad:
        raise ValueError(
            f"every expert_params leaf must be stacked [num_experts={E}, "
            f"...]; got leading dims {bad[:3]}"
        )
    T = x.shape[0]
    if T % n:
        raise ValueError(
            f"Tokens {T} must divide over ep axis {axis!r} ({n})"
        )
    if capacity is None:
        capacity = max(1, math.ceil((T // n) / E * capacity_factor))

    fn = jax.shard_map(
        _local_moe(expert_fn, axis, E, capacity),
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    return fn(router_w, expert_params, x)
