"""Synchronous data-parallel training over a device mesh.

Reference analogue: HorovodEstimator's ring-all-reduce training loop
(SURVEY.md §4.4): per step, each worker computes gradients on its shard and
NCCL all-reduces them before the optimizer update. TPU-native design: ONE
jitted train step, ``shard_map``-ped over the 'dp' mesh axis — each device
computes loss/grads on its batch shard, ``jax.lax.psum`` averages grads
over ICI (XLA emits the all-reduce; there is no NCCL/MPI anywhere), and
the optimizer update runs replicated. Losses are psum-averaged too, so
every device returns the same scalar.

The step function is also the unit the multi-chip dryrun compiles: the same
code runs on 1 real TPU chip, an 8-device CPU-sim mesh, or a v5e-16 slice —
only the Mesh changes.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any


def create_train_state(params, optimizer: optax.GradientTransformation) -> TrainState:
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=optimizer.init(params),
    )


def _cast_for_compute(params, compute_dtype):
    """Cast float params to the forward/backward compute dtype (bf16 mixed
    precision); None = passthrough. Shared by both step builders."""
    if compute_dtype is None:
        return params
    return jax.tree_util.tree_map(
        lambda p: p.astype(compute_dtype)
        if hasattr(p, "dtype") and jnp.issubdtype(p.dtype, jnp.floating)
        else p,
        params,
    )


def _grads_to_f32(grads):
    return jax.tree_util.tree_map(
        lambda g: g.astype(jnp.float32)
        if hasattr(g, "dtype") and jnp.issubdtype(g.dtype, jnp.floating)
        else g,
        grads,
    )


def _accumulated_loss_and_grads(
    loss_fn, compute_params, batch, grad_accum_steps, microbatch_weight_fn
):
    """Per-device loss+f32 grads, with optional local microbatch
    accumulation via lax.scan (grads summed in f32, weighted by
    ``microbatch_weight_fn`` so padded microbatches contribute in
    proportion to their real rows). Shared by the plain and ZeRO-1 step
    builders — the semantics must not drift between them."""
    if grad_accum_steps <= 1:
        loss, grads = jax.value_and_grad(loss_fn)(compute_params, batch)
        return loss, _grads_to_f32(grads)

    micro = jax.tree_util.tree_map(
        lambda x: x.reshape(
            (grad_accum_steps, x.shape[0] // grad_accum_steps) + x.shape[1:]
        ),
        batch,
    )

    def accum(carry, mb):
        loss_sum, grad_sum, w_sum = carry
        loss, grads = jax.value_and_grad(loss_fn)(compute_params, mb)
        w = (
            jnp.asarray(microbatch_weight_fn(mb), jnp.float32)
            if microbatch_weight_fn is not None
            else jnp.asarray(1.0, jnp.float32)
        )
        return (
            loss_sum + loss * w,
            jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32) * w, grad_sum, grads
            ),
            w_sum + w,
        ), None

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), compute_params
    )
    (loss_sum, grad_sum, w_sum), _ = jax.lax.scan(
        accum,
        (jnp.zeros((), jnp.float32), zeros, jnp.zeros((), jnp.float32)),
        micro,
    )
    inv = 1.0 / jnp.maximum(w_sum, 1e-30)
    return loss_sum * inv, jax.tree_util.tree_map(
        lambda g: g * inv, grad_sum
    )


def make_data_parallel_step(
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = "dp",
    donate_state: bool = True,
    grad_accum_steps: int = 1,
    compute_dtype: Any = None,
    microbatch_weight_fn: Optional[Callable[[Any], jnp.ndarray]] = None,
):
    """Build the jitted SPMD train step.

    Args:
        loss_fn: ``loss_fn(params, batch) -> scalar loss`` on ONE shard
            (batch is the per-device slice; reductions inside should be
            means over the local shard).
        optimizer: optax transformation.
        mesh: device mesh containing ``axis``.
        axis: mesh axis to shard the batch over.
        grad_accum_steps: microbatch count. >1 splits each device's shard
            into that many microbatches consumed by a ``lax.scan``,
            accumulating gradients LOCALLY (f32) and all-reducing once at
            the end — the effective global batch grows by the factor with
            the same peak activation memory, and the ICI collective cost
            is unchanged. The batch's leading (per-shard) dim must be
            divisible by it.
        microbatch_weight_fn: optional ``fn(microbatch) -> scalar weight``
            (e.g. the valid-row count of a masked batch). Accumulation
            becomes a weighted mean, so partially-padded microbatches
            contribute in proportion to their real rows and the result
            matches ``grad_accum_steps=1`` exactly. Default: equal
            weights (exact only when every microbatch is fully valid).
        compute_dtype: when set (e.g. ``jnp.bfloat16``), the forward/
            backward pass sees params cast to this dtype (MXU-friendly)
            while the TrainState keeps float32 master params and the
            optimizer update runs in float32 — standard TPU mixed
            precision.

    Returns ``step_fn(state, batch) -> (state, metrics)`` where ``batch``
    is a pytree whose leaves are sharded along dim 0 (use
    mesh.shard_batch / jax.device_put with a dp sharding; plain host
    arrays also work — jit will shard them per the in_shardings).
    """
    replicated_spec = P()
    batch_spec = P(axis)

    def local_loss_and_grads(params, batch):
        return _accumulated_loss_and_grads(
            loss_fn,
            _cast_for_compute(params, compute_dtype),
            batch,
            grad_accum_steps,
            microbatch_weight_fn,
        )

    def per_device_step(state: TrainState, batch) -> Tuple[TrainState, dict]:
        loss, grads = local_loss_and_grads(state.params, batch)
        # The Horovod ring-all-reduce, as one XLA collective:
        grads = jax.lax.pmean(grads, axis_name=axis)
        loss = jax.lax.pmean(loss, axis_name=axis)
        updates, new_opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1, params=new_params, opt_state=new_opt_state
        )
        return new_state, {"loss": loss, "grad_norm": optax.global_norm(grads)}

    sharded = jax.shard_map(
        per_device_step,
        mesh=mesh,
        in_specs=(replicated_spec, batch_spec),
        out_specs=(replicated_spec, replicated_spec),
        check_vma=False,
    )

    state_sharding = NamedSharding(mesh, replicated_spec)
    batch_sharding = NamedSharding(mesh, batch_spec)

    return jax.jit(
        sharded,
        in_shardings=(state_sharding, batch_sharding),
        out_shardings=(state_sharding, state_sharding),
        donate_argnums=(0,) if donate_state else (),
    )


def _assert_elementwise_optimizer(
    optimizer: optax.GradientTransformation,
) -> None:
    """Build-time probe for the ZeRO-1 silent-divergence hazard: update a
    small vector once whole and once split into two shards (exactly what
    the sharded step does with 1/N slices) and require identical results.

    Non-elementwise transforms — ``clip_by_global_norm``, trust-ratio
    scaling (LARS/LAMB), anything whose update at index i depends on
    other indices — produce different per-shard updates and would train
    WRONG silently; this converts that into a loud build-time error.

    Probe design: the gradients have wildly asymmetric shard norms so
    norm-dependent transforms compute different factors whole vs
    sharded, and the probe runs THREE sequential updates at magnitudes
    spanning 1 to 1e6 (global norms ~1.2e3 to ~1.2e9). Multiple mixed-
    magnitude steps matter: a single Adam step from zero state is
    per-element scale-invariant (update -> sign(g)), which would hide
    any clipping scalar — but across steps the moment accumulators mix
    the scales, so a threshold anywhere below ~1e9 produces divergent
    final updates. Thresholds above 1e9 never fire on real gradients
    either."""
    probe_p = jnp.asarray(
        [0.5, -1.2, 2.0, -0.3, 0.01, 1.5, -2.2, 0.8], jnp.float32
    )
    # first half huge, second half tiny: per-shard norms differ by ~1e5;
    # the reversed middle step flips which shard is the big one
    base_g = np.asarray(
        [4e2, -7e2, 9e2, -2e2, 3e-3, -1e-3, 5e-3, 2e-3], np.float32
    )
    grad_seq = [base_g, base_g[::-1].copy() * 1e6, base_g * 0.5]

    def run_steps(p, grads):
        state = optimizer.init(p)
        update = None
        for g in grads:
            update, state = optimizer.update(jnp.asarray(g), state, p)
        return np.asarray(update)

    try:
        full = run_steps(probe_p, grad_seq)
        halves = [
            run_steps(probe_p[s], [g[s] for g in grad_seq])
            for s in (slice(0, 4), slice(4, 8))
        ]
    except Exception as e:
        # tree-structured transforms (optax.masked / multi_transform)
        # cannot run on the probe's bare array — surface the real
        # constraint instead of the transform's internal error
        raise ValueError(
            "shardOptimizerState=True (ZeRO-1) flattens params to one "
            "vector, so the optimizer must work elementwise on a bare "
            f"array; probing this one failed ({type(e).__name__}: {e})."
            " Use shardOptimizerState=False, or pass "
            "validate_elementwise=False / validateOptimizer=False if "
            "the optimizer is verified shard-consistent."
        ) from e
    if not np.allclose(
        full, np.concatenate(halves), rtol=1e-4, atol=1e-6,
    ):
        raise ValueError(
            "shardOptimizerState=True (ZeRO-1) requires an ELEMENTWISE "
            "optimizer: this one produces different updates when params "
            "are split into shards (clip_by_global_norm / trust-ratio / "
            "per-layer transforms do), so the sharded weight update "
            "would silently diverge from unsharded training. Drop the "
            "non-elementwise transform, or use the replicated-state "
            "step (shardOptimizerState=False / make_data_parallel_step)."
        )


def make_zero1_data_parallel_step(
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    params_template: Any,
    axis: str = "dp",
    donate_state: bool = True,
    compute_dtype: Any = None,
    grad_accum_steps: int = 1,
    microbatch_weight_fn: Optional[Callable[[Any], jnp.ndarray]] = None,
    validate_elementwise: bool = True,
):
    """Data-parallel step with WEIGHT-UPDATE (ZeRO-1) SHARDING: optimizer
    state lives sharded 1/N per device over the ``axis`` mesh axis.
    ``compute_dtype`` casts params for the forward/backward pass (bf16
    mixed precision) and ``grad_accum_steps``/``microbatch_weight_fn``
    accumulate microbatch gradients locally before the reduce-scatter,
    exactly as in :func:`make_data_parallel_step` (one shared
    implementation).

    Technique per Xu et al., "Automatic Cross-Replica Sharding of Weight
    Update Computation in Data-Parallel Training" (arXiv:2004.13336; see
    PAPERS.md) — the natural TPU extension of the reference's Horovod
    all-reduce (SURVEY.md §3.2): instead of every replica redundantly
    holding full optimizer state and applying the full update,

      1. gradients are ``psum_scatter``-ed (reduce-scatter rides ICI at
         half the all-reduce cost),
      2. each device updates only its 1/N param shard with its 1/N
         optimizer-state shard,
      3. updated shards are ``all_gather``-ed back to full params.

    For Adam on an M-param model this cuts per-device optimizer memory
    from 2M floats to 2M/N. Works with elementwise optax transforms
    (sgd/momentum/adam/adamw...); optimizers that need whole-tree
    structure (e.g. per-layer clipping) should use
    :func:`make_data_parallel_step`.

    The params pytree is flattened to one padded f32 vector for the
    scatter, so ``params_template`` (a pytree matching the params) is
    required to fix sizes at build time. Returns
    ``step_fn(state, batch) -> (state, metrics)`` where ``state`` is a
    :class:`TrainState` whose ``opt_state`` holds only this device
    group's shard (create it with the returned ``init_fn``):

        step_fn, init_fn = make_zero1_data_parallel_step(...)
        state = init_fn(params)

    ``validate_elementwise=False`` skips the build-time shard-consistency
    probe (see :func:`_assert_elementwise_optimizer`) for optimizers the
    caller has verified independently.
    """
    if validate_elementwise:
        _assert_elementwise_optimizer(optimizer)
    n_shards = int(mesh.shape[axis])
    leaves, treedef = jax.tree_util.tree_flatten(params_template)
    sizes = [int(np.prod(l.shape)) if hasattr(l, "shape") else 1 for l in leaves]
    shapes = [tuple(l.shape) for l in leaves]
    dtypes = [l.dtype for l in leaves]
    total = sum(sizes)
    padded = ((total + n_shards - 1) // n_shards) * n_shards
    shard_len = padded // n_shards

    def flatten(tree) -> jnp.ndarray:
        ls = jax.tree_util.tree_leaves(tree)
        flat = jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in ls]
        )
        return jnp.pad(flat, (0, padded - total))

    def unflatten(flat: jnp.ndarray):
        out = []
        off = 0
        for size, shape, dtype in zip(sizes, shapes, dtypes):
            out.append(flat[off : off + size].reshape(shape).astype(dtype))
            off += size
        return jax.tree_util.tree_unflatten(treedef, out)

    def per_device_step(state: TrainState, batch):
        loss, grads = _accumulated_loss_and_grads(
            loss_fn,
            _cast_for_compute(state.params, compute_dtype),
            batch,
            grad_accum_steps,
            microbatch_weight_fn,
        )
        loss = jax.lax.pmean(loss, axis_name=axis)
        gflat = flatten(grads)
        # reduce-scatter: each device ends with the MEAN of its slice
        gshard = jax.lax.psum_scatter(
            gflat.reshape(n_shards, shard_len),
            axis_name=axis,
            scatter_dimension=0,
            tiled=False,
        ) / n_shards
        pshard = jax.lax.dynamic_slice(
            flatten(state.params),
            (jax.lax.axis_index(axis) * shard_len,),
            (shard_len,),
        )
        # opt_state leaves carry the vmap-era leading shard axis; locally
        # it is size 1 — strip for the update, restore for the out spec.
        opt_local = jax.tree_util.tree_map(
            lambda x: x[0], state.opt_state
        )
        updates, new_opt_local = optimizer.update(
            gshard, opt_local, pshard
        )
        new_opt_state = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x)[None], new_opt_local
        )
        new_pshard = optax.apply_updates(pshard, updates)
        new_flat = jax.lax.all_gather(
            new_pshard, axis_name=axis, tiled=True
        )
        new_params = unflatten(new_flat)
        grad_norm = jnp.sqrt(
            jax.lax.psum(jnp.sum(gshard * gshard), axis_name=axis)
        )
        return (
            TrainState(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt_state,
            ),
            {"loss": loss, "grad_norm": grad_norm},
        )

    state_specs = TrainState(step=P(), params=P(), opt_state=P(axis))
    sharded = jax.shard_map(
        per_device_step,
        mesh=mesh,
        in_specs=(state_specs, P(axis)),
        out_specs=(state_specs, P()),
        check_vma=False,
    )

    def to_sharding(spec_tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            spec_tree,
            is_leaf=lambda s: isinstance(s, P),
        )

    step_fn = jax.jit(
        sharded,
        in_shardings=(to_sharding(state_specs), NamedSharding(mesh, P(axis))),
        out_shardings=(to_sharding(state_specs), NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate_state else (),
    )

    def init_fn(params) -> TrainState:
        """TrainState with the optimizer state initialized SHARDED: each
        device's opt_state covers its shard_len slice. Works in a
        multi-process gang: every rank computes the same full host state
        and contributes its addressable shards."""
        flat = flatten(params)

        def init_shard(shard):
            return optimizer.init(shard)

        shards = flat.reshape(n_shards, shard_len)
        opt_states = jax.vmap(init_shard)(shards)

        if jax.process_count() == 1:
            # all devices addressable: reshard on-device, no host round-trip
            opt_state = jax.device_put(
                opt_states,
                to_sharding(
                    jax.tree_util.tree_map(lambda _: P(axis), opt_states)
                ),
            )
        else:
            # device_put cannot target non-addressable devices; build
            # global arrays from the (identical-on-every-rank) host values
            def globalize(a):
                host = np.asarray(a)
                return jax.make_array_from_callback(
                    host.shape,
                    NamedSharding(mesh, P(axis)),
                    lambda idx, _h=host: _h[idx],
                )

            opt_state = jax.tree_util.tree_map(globalize, opt_states)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
        )

    return step_fn, init_fn


def make_eval_step(
    metric_fn: Callable[[Any, Any], Any], mesh: Mesh, axis: str = "dp"
):
    """Jitted SPMD eval step: per-shard metrics psum-averaged over the mesh."""
    def per_device(params, batch):
        m = metric_fn(params, batch)
        return jax.tree_util.tree_map(
            lambda v: jax.lax.pmean(v, axis_name=axis), m
        )

    sharded = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(
        sharded,
        in_shardings=(NamedSharding(mesh, P()), NamedSharding(mesh, P(axis))),
        out_shardings=NamedSharding(mesh, P()),
    )
