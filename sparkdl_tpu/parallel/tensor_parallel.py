"""Megatron-style tensor parallelism over a 'tp' mesh axis.

The reference had no tensor parallelism (SURVEY.md §3.2); this is the
TPU-native strategy for layers too wide for one chip: weights are split
across the 'tp' axis — the first dense of a block column-wise, the second
row-wise — so the block needs exactly ONE ``psum`` at its output (Shoeybi
et al., "Megatron-LM", 1909.08053; the scaling-book recipe). XLA routes
the psum over ICI; activations between the two matmuls stay sharded, so
peak per-chip activation and weight memory both drop by the axis size.

All helpers are plain functions for use INSIDE ``shard_map`` (the same
convention as ops/ring_attention.py); ``shard_dense_params`` prepares the
per-device weight shards, and ``tp_block_sharded`` is the one-call
wrapper mirroring ``*_attention_sharded``.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def column_parallel(x, w, b=None):
    """First dense of a TP block: ``w`` is the LOCAL column shard
    [d_in, d_ff/n]; output stays sharded on its last dim (no
    communication). Bias, if any, is the matching column shard."""
    y = x @ w
    return y if b is None else y + b


def row_parallel(x, w, axis_name: str = "tp", b=None):
    """Second dense of a TP block: ``w`` is the LOCAL row shard
    [d_ff/n, d_out]; the partial products are summed with ONE psum over
    ``axis_name``. Bias, if any, is full-size and added AFTER the psum
    (adding it per-shard would count it n times)."""
    y = jax.lax.psum(x @ w, axis_name)
    return y if b is None else y + b


def tp_mlp(x, w1, w2, axis_name: str = "tp",
           activation: Callable = jax.nn.relu, b1=None, b2=None):
    """The canonical 2-dense TP block: column-parallel w1, activation,
    row-parallel w2, one psum. For use inside shard_map."""
    h = activation(column_parallel(x, w1, b1))
    return row_parallel(h, w2, axis_name, b2)


def shard_dense_params(w1, w2, mesh, axis: str = "tp",
                       b1=None, b2=None):
    """Device-put full [d_in, d_ff] / [d_ff, d_out] weights as the
    sharded arrays tp_block_sharded expects (w1 column-split, w2
    row-split, b1 column-split, b2 replicated)."""
    from jax.sharding import NamedSharding

    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    out = [put(w1, P(None, axis)), put(w2, P(axis, None))]
    out.append(put(b1, P(axis)) if b1 is not None else None)
    out.append(put(b2, P()) if b2 is not None else None)
    return tuple(out)


def tp_block_sharded(
    x, w1, w2, mesh, axis: str = "tp",
    activation: Callable = jax.nn.relu,
    b1=None, b2=None,
    dp_axis: Optional[str] = None,
):
    """Convenience wrapper: full (or pre-sharded) weights in, TP-executed
    MLP block out. ``dp_axis`` additionally shards the batch over a
    second mesh axis (2-D dp×tp). For repeated calls (a training loop),
    wrap the surrounding step in ``jax.jit`` so the traced program is
    compiled once and cached."""
    n = mesh.shape[axis]
    if w1.shape[1] != w2.shape[0]:
        raise ValueError(
            f"w1 [.., {w1.shape[1]}] and w2 [{w2.shape[0]}, ..] disagree "
            "on d_ff"
        )
    if w1.shape[1] % n:
        raise ValueError(
            f"d_ff {w1.shape[1]} must divide over tp axis {axis!r} ({n})"
        )
    if dp_axis is not None and x.shape[0] % mesh.shape[dp_axis]:
        raise ValueError(
            f"Batch {x.shape[0]} must divide over dp_axis {dp_axis!r} "
            f"({mesh.shape[dp_axis]} shards)"
        )

    spec_x = P(dp_axis) if dp_axis is not None else P()
    in_specs = [spec_x, P(None, axis), P(axis, None)]
    args = [x, w1, w2]
    if b1 is not None:
        in_specs.append(P(axis))
        args.append(b1)
    if b2 is not None:
        in_specs.append(P())
        args.append(b2)

    def local(x_, w1_, w2_, *biases):
        bs = iter(biases)
        b1_ = next(bs) if b1 is not None else None
        b2_ = next(bs) if b2 is not None else None
        return tp_mlp(x_, w1_, w2_, axis, activation, b1_, b2_)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=spec_x,
        check_vma=False,
    )
    return fn(*args)
