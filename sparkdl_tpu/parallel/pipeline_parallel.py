"""GPipe-style pipeline parallelism over a 'pp' mesh axis.

The reference had no pipeline parallelism (SURVEY.md §3.2 — Spark's
distribution was partition-parallel only); this is a TPU-native bonus
strategy for models whose layer stack does not fit one chip's HBM: the
stack is split into ``n`` stages, one per device along the 'pp' axis, and
a batch is fed through as microbatches on a software-pipelined schedule
(Huang et al., "GPipe", 1811.06965; PAPERS.md). Activations hop
stage-to-stage with ``jax.lax.ppermute`` — neighbor-to-neighbor ICI
traffic — inside one SPMD program, so XLA overlaps the collective with
the next microbatch's compute. Wrap repeated calls (a training step) in
``jax.jit`` so the traced schedule is compiled once and cached, like the
step factories in parallel/data_parallel.py.

Design constraints (the classic SPMD-pipeline trade):

- Every stage must share one activation signature (same shape/dtype in
  and out), e.g. a run of identical transformer blocks or any
  hidden-state-preserving layer stack.
- Stage parameters are STACKED on a leading axis (one slice per stage)
  and sharded ``P('pp')``, so each device holds exactly its stage's
  weights — the pipeline analogue of ZeRO's weight sharding.

Training composes for free: the schedule is ordinary traceable lax code
(scan + ppermute), so ``jax.grad`` differentiates straight through it,
yielding pipeline-parallel backward without a hand-written schedule, and
the 'pp' axis composes with 'dp' on a 2-D mesh.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def stack_stage_params(param_trees) -> Any:
    """Stack per-stage parameter pytrees (one per pipeline stage) on a new
    leading axis, producing the stacked layout pipeline_apply expects."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *param_trees
    )


def _local_pipeline(stage_fn, axis_name):
    """The per-device schedule, to run inside shard_map over ``axis_name``.

    ``stacked`` arrives sharded P(axis) on the leading (stage) axis — the
    local slice is [1, ...] = this device's stage params. ``x`` is the
    full [n_micro, B_m, ...] microbatched input, replicated; outputs are
    replicated back via a masked psum so every device returns the result.
    """

    def run(stacked, x):
        idx = jax.lax.axis_index(axis_name)
        n = jax.lax.axis_size(axis_name)
        my_params = jax.tree_util.tree_map(lambda a: a[0], stacked)
        n_micro = x.shape[0]
        ticks = n_micro + n - 1
        perm = [(i, i + 1) for i in range(n - 1)]  # stage i -> i+1

        zeros_mb = jnp.zeros_like(x[0])
        out_buf = jnp.zeros_like(x)

        def tick(carry, t):
            incoming, outs = carry
            # Stage 0 injects microbatch t (zeros once the batch is
            # drained — harmless: their products are never collected);
            # later stages consume what the previous stage just sent.
            feed = jnp.where(
                t < n_micro, x[jnp.minimum(t, n_micro - 1)], zeros_mb
            )
            state = jnp.where(idx == 0, feed, incoming)
            y = stage_fn(my_params, state)
            # The last stage emits microbatch (t - (n-1)) at tick t.
            # (select, not cond: the predicate varies per device)
            emit_t = t - (n - 1)
            updated = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.maximum(emit_t, 0), axis=0
            )
            take = jnp.logical_and(idx == n - 1, emit_t >= 0)
            outs = jnp.where(take, updated, outs)
            outgoing = jax.lax.ppermute(y, axis_name, perm)
            return (outgoing, outs), None

        (_, out_buf), _ = jax.lax.scan(
            tick, (zeros_mb, out_buf), jnp.arange(ticks)
        )
        # Only the last stage holds real outputs; broadcast to all
        # devices so the caller sees a replicated result.
        mask = (idx == n - 1).astype(out_buf.dtype)
        return jax.lax.psum(out_buf * mask, axis_name)

    return run


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    mesh,
    axis: str = "pp",
    n_microbatches: Optional[int] = None,
    dp_axis: Optional[str] = None,
):
    """Run ``x`` [B, ...] through ``n`` pipeline stages of ``stage_fn``.

    ``stage_fn(params_i, h) -> h`` must preserve the activation
    signature. ``stacked_params``: per-stage params stacked on axis 0
    (see stack_stage_params), length = mesh.shape[axis]. ``x`` is split
    into ``n_microbatches`` (default: the stage count) along batch dim 0.
    Returns [B, ...] outputs, replicated over ``axis``.

    ``dp_axis``: a second mesh axis to data-parallelize over — each of
    its shards pipelines a 1/dp slice of every microbatch (stage params
    stay replicated across it). Without it, on a multi-axis mesh the
    batch is simply replicated over the other axes.

    Differentiable: take ``jax.grad`` of a loss over this call for
    pipeline-parallel training.
    """
    n = mesh.shape[axis]
    n_micro = n if n_microbatches is None else n_microbatches
    B = x.shape[0]
    if n_micro < 1 or B % n_micro:
        raise ValueError(
            f"Batch {B} must divide into n_microbatches={n_micro}"
        )
    if dp_axis is not None and (B // n_micro) % mesh.shape[dp_axis]:
        raise ValueError(
            f"Microbatch size {B // n_micro} must divide over "
            f"dp_axis {dp_axis!r} ({mesh.shape[dp_axis]} shards)"
        )
    stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if stages != n:
        raise ValueError(
            f"stacked_params has {stages} stages but mesh axis "
            f"{axis!r} has {n} devices"
        )
    xm = x.reshape(n_micro, B // n_micro, *x.shape[1:])

    spec_x = P(None, dp_axis) if dp_axis is not None else P()
    fn = jax.shard_map(
        _local_pipeline(stage_fn, axis),
        mesh=mesh,
        in_specs=(P(axis), spec_x),
        out_specs=spec_x,
        check_vma=False,
    )
    out = fn(stacked_params, xm)
    return out.reshape(B, *out.shape[2:])
