"""Grouped matrix product: the routed experts' three products.

``lhs`` [M, K] holds rows sorted by group (the (token, k) slots of an
expert layer sorted by expert), ``rhs`` [G, K, N] one matrix a group
(the experts this chip holds) and ``group_sizes`` [G] how many rows each
group has, known only on the device. Row ``r`` of group ``g`` gives
``lhs[r] @ rhs[g]``; rows past the last group (slots whose expert lives
on another chip) are **not computed and not written**: what the result
holds there is unspecified, and the caller masks it.

The Pallas kernel (TPU, or interpreted) walks the row tiles that groups
cover, a dynamic number of grid steps taken from ``group_sizes``: a tile
that two groups share is visited once by each, and each stores only its
own rows. The contraction is whole where a [K, tn] block of a group's
matrix fits the budget (the shapes of an expert layer do): consecutive
row tiles of one group then name the same weight block, which is fetched
once, so a call reads every group's matrix once for each pass over the
columns. The tiling walk follows ``jax.experimental.pallas.ops.tpu
.megablox.gmm`` (no group sharding, no transposed operand, no
accumulation into an existing result). Off the TPU the plain form is
``lax.ragged_dot``. :func:`make_grouped_matmul_fn` chooses at build time,
as the other kernels' factories do, and says which (``.kind``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: bytes of one [tk, tn] weight block (it is double-buffered)
_RHS_BLOCK_BYTES = 8 << 20
#: what the kernel may hold in VMEM: two buffers of each block; the
#: chip's default scoped limit (16 MiB on a v5e) is under two 8 MiB blocks
_VMEM_LIMIT_BYTES = 64 << 20


def _divisor_tile(n: int, most: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``most``; ``n`` itself where there is none (a block as wide as the
    array is always legal)."""
    best = 0
    for t in range(128, min(n, most) + 1, 128):
        if n % t == 0:
            best = t
    return best or n


def choose_tiling(m: int, k: int, n: int, itemsize: int = 2) -> Tuple[int, int, int]:
    """(tm, tk, tn) for [m, k] x [g, k, n]: 256 rows a tile (a group of
    600 rows then computes 870, and the MXU still streams 256 rows a
    weight tile), the whole contraction where [k, 128] fits the block
    budget, and the widest column tile the budget then leaves."""
    tm = 256 if m >= 256 else -(-m // 16) * 16
    tk = k if k * 128 * itemsize <= _RHS_BLOCK_BYTES else _divisor_tile(k, 2048)
    tn = _divisor_tile(n, max(128, _RHS_BLOCK_BYTES // (tk * itemsize)))
    return tm, tk, tn


def group_metadata(group_sizes, m: int, tm: int):
    """(offsets [G + 1], group_ids [tiles + G - 1], tile_ids [same],
    steps): grid step ``s`` < ``steps`` works on rows of group
    ``group_ids[s]`` in row tile ``tile_ids[s]``. A tile is visited once
    by every group with rows in it, consecutively; an empty group visits
    nothing, and neither do the rows past the last group."""
    groups = group_sizes.shape[0]
    tiles = m // tm
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first_tile = starts // tm
    last_tile = (ends + tm - 1) // tm  # one past
    visits = jnp.where(group_sizes > 0, last_tile - first_tile, 0)
    total = tiles + groups - 1
    group_ids = jnp.repeat(
        jnp.arange(groups, dtype=jnp.int32), visits, total_repeat_length=total
    )
    # step s of group g is that group's (s - steps before g)-th tile
    before = jnp.cumsum(visits) - visits
    step = jnp.arange(total, dtype=jnp.int32)
    tile_ids = first_tile[group_ids] + step - before[group_ids]
    tile_ids = jnp.clip(tile_ids, 0, tiles - 1).astype(jnp.int32)
    return offsets, group_ids, tile_ids, jnp.sum(visits)


def _kernel(tm, tn, tiles_k, offsets, group_ids, tile_ids, lhs, rhs, out, *acc):
    from jax.experimental import pallas as pl

    step, k_i = pl.program_id(1), pl.program_id(2)
    part = jax.lax.dot_general(
        lhs[...], rhs[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    def store(total):
        group = group_ids[step]
        row = tile_ids[step] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        mine = (row >= offsets[group]) & (row < offsets[group + 1])
        # another group's rows of this tile stay as that group wrote them
        out[...] = jnp.where(mine, total, out[...].astype(jnp.float32)).astype(out.dtype)

    if tiles_k == 1:
        store(part)
        return
    (acc,) = acc

    @pl.when(k_i == 0)
    def _first():
        acc[...] = part

    @pl.when(k_i > 0)
    def _next():
        acc[...] += part

    @pl.when(k_i == tiles_k - 1)
    def _last():
        store(acc[...])


@functools.partial(
    jax.jit, static_argnames=("tiling", "out_dtype", "interpret")
)
def grouped_matmul(
    lhs,
    rhs,
    group_sizes,
    *,
    tiling: Optional[Tuple[int, int, int]] = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    """``lhs`` [M, K] rows sorted by group x ``rhs`` [G, K, N] with
    ``group_sizes`` [G] int32 -> [M, N] ``out_dtype``, accumulated in
    float32. Rows past ``sum(group_sizes)`` are unspecified. ``tiling``
    (tm, tk, tn) defaults to :func:`choose_tiling`; M is padded to a
    whole tile, tk and tn must divide K and N."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    groups, k_rhs, n = rhs.shape
    if k_rhs != k or group_sizes.shape != (groups,):
        raise ValueError(
            f"grouped_matmul: lhs {lhs.shape}, rhs {rhs.shape}, "
            f"group_sizes {group_sizes.shape}"
        )
    tm, tk, tn = tiling or choose_tiling(m, k, n, lhs.dtype.itemsize)
    if k % tk or n % tn:
        raise ValueError(f"tiles ({tk}, {tn}) do not divide K={k}, N={n}")
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tiles_k = k // tk
    offsets, group_ids, tile_ids, steps = group_metadata(group_sizes, m + pad, tm)
    out = pl.pallas_call(
        functools.partial(_kernel, tm, tn, tiles_k),
        out_shape=jax.ShapeDtypeStruct((m + pad, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, s, k_i, o, g, t: (t[s], k_i)),
                pl.BlockSpec(
                    (None, tk, tn), lambda n_i, s, k_i, o, g, t: (g[s], k_i, n_i)
                ),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, s, k_i, o, g, t: (t[s], n_i)),
            grid=(n // tn, steps, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * (tiles_k > 1),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        # a stable name for the kernel's events in a profiler trace
        name="moe_grouped_matmul",
    )(offsets, group_ids, tile_ids, lhs, rhs)
    return out[:m] if pad else out


def ragged_matmul(lhs, rhs, group_sizes, *, out_dtype=jnp.float32):
    """What :func:`grouped_matmul` computes, by ``lax.ragged_dot``: the
    build-time fallback off the TPU (rows past the last group are zero)."""
    out = jax.lax.ragged_dot(
        lhs, rhs, group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32,
    )
    return out.astype(out_dtype)


ragged_matmul.kind = "ragged_dot"


def make_grouped_matmul_fn(interpret: bool = False):
    """The grouped product a model is BUILT with: the Pallas kernel on
    the TPU (or interpreted when asked), ``lax.ragged_dot`` elsewhere.
    ``.kind`` ('pallas' | 'ragged_dot') says which."""
    if not interpret and jax.default_backend() != "tpu":
        return ragged_matmul

    def experts(lhs, rhs, group_sizes, *, out_dtype=jnp.float32):
        return grouped_matmul(
            lhs, rhs, group_sizes, out_dtype=out_dtype, interpret=interpret
        )

    experts.kind = "pallas"
    return experts
