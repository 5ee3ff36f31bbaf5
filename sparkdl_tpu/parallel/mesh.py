"""Device mesh construction and sharding helpers.

The reference's distribution substrate was Spark partitions (inference) and
Horovod's NCCL ring (training) — SURVEY.md §3.1/§3.2. The TPU-native
substrate is a ``jax.sharding.Mesh`` over the chip topology: data
parallelism ('dp'), tensor/model parallelism ('tp'), and sequence/context
parallelism ('sp') are mesh axes; XLA inserts the collectives (psum /
all-gather / reduce-scatter / ppermute) and routes them over ICI within a
slice and DCN across slices. Nothing here names a transport — the mesh IS
the communication backend.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    axes: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a named mesh.

    ``axes`` maps axis name -> size, in major-to-minor order; sizes must
    multiply to the device count. ``-1`` for at most one axis means "all
    remaining devices". Default: every device on a single 'dp' axis.

    Axis-order convention (matters for collective locality): put the axis
    with the heaviest communication innermost (last), so it lands on
    adjacent ICI neighbors — e.g. {'dp': n_hosts, 'tp': chips_per_host}.
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if axes is None:
        axes = {"dp": n}
    names = list(axes.keys())
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise ValueError("At most one axis may be -1")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1])) or 1
        if n % known:
            raise ValueError(
                f"Cannot infer -1 axis: {n} devices not divisible by {known}"
            )
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(
            f"Mesh axes {dict(zip(names, sizes))} need {total} devices, "
            f"have {n}"
        )
    if devices is None and n > 1 and devs[0].platform == "tpu":
        # Topology-aware device assignment: on real TPU slices the flat
        # jax.devices() order does not put ICI neighbors adjacent under a
        # plain reshape; mesh_utils permutes devices so the innermost
        # (heaviest-communication) axes land on physical neighbors. A
        # topology it cannot lay out is an error, not a silent reshape.
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(tuple(sizes), devices=devs)
    else:
        # CPU meshes have no topology to respect, and explicit device
        # lists keep the caller's order
        dev_array = np.asarray(devs).reshape(sizes)
    return Mesh(dev_array, axis_names=tuple(names))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Shard dim 0 (batch) across ``axis``, replicate the rest."""
    return NamedSharding(mesh, P(axis))


def shard_batch(batch, mesh: Mesh, axis: str = "dp"):
    """Place a host batch onto the mesh, sharded along dim 0."""
    sharding = batch_sharding(mesh, axis)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), batch
    )


def local_device_count() -> int:
    return jax.local_device_count()


def pad_batch_to_multiple(
    arrays: Tuple[np.ndarray, ...], multiple: int
) -> Tuple[Tuple[np.ndarray, ...], np.ndarray]:
    """Pad each array's dim 0 to a multiple of ``multiple`` (device count),
    returning (padded_arrays, valid_mask). Keeps shapes static and divisible
    for even sharding across 'dp'."""
    n = arrays[0].shape[0]
    target = ((n + multiple - 1) // multiple) * multiple
    pad = target - n
    mask = np.concatenate([np.ones(n, bool), np.zeros(pad, bool)])
    if pad == 0:
        return arrays, mask
    padded = tuple(
        np.concatenate(
            [a, np.zeros((pad, *a.shape[1:]), dtype=a.dtype)], axis=0
        )
        for a in arrays
    )
    return padded, mask
