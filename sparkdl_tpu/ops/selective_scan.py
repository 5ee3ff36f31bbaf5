"""Pallas TPU selective scan: the Mamba-1 recurrence of the hybrid text
models (models/jamba.py).

    S_t = exp(dt_t[:, None] * A) * S_{t-1} + (dt_t * h_t)[:, None] * B_t[None, :]
    y_t = S_t C_t + D * h_t;        out_t = y_t * silu(z_t)

per row, with a state ``S`` of ``[d_inner, d_state]`` float32. Written
out over time the state is ``d_inner * d_state * 4`` bytes a token (328
KB at 5,120 x 16); stepped from the host it is one tiny program a token.
The kernel walks the sequence in chunks: grid = (row, block of
``d_inner``, chunk of the sequence), the last axis sequential, the state
in VMEM scratch from chunk to chunk and in vector registers from token to
token.

The tiling of the token loop. ``d_inner`` lies on BOTH the sublanes and
the lanes, ``d_state`` counts registers: a *tile* is 8 lane groups of 128
channels, so what a token brings for 1,024 channels (``dt_t``, ``u_t =
dt_t * h_t``, ``y_t``) is one full ``[8, 128]`` register each, the state
of a tile is ``d_state`` registers ``S_n`` and ``A``'s column ``n`` one
register ``A_n``. Nothing in the recurrence mixes channels, so with the
channels filling the register every step of it is a whole-register
multiply or add, and the sum over ``d_state`` is a sum of registers: no
sublane reduction, no select of one row into a tile, no broadcast of a
row. ``B_t[n]`` and ``C_t[n]`` do not depend on the channel: they are
scalars, read from SMEM (a chunk's ``B`` and ``C``, 8 tokens a row, 8 KB
each at 128 x 16) and splatted. A width that does not fill its last tile
(fewer than 8 lane groups) runs the same code with dead sublanes (``dt``
and ``A`` zero there, so the state stays zero).

The blocks arrive as ``[chunk, bd]`` with the tokens on the sublanes. In
VMEM that block already lies as ``[chunk / 8, 8 groups, 8 tokens, 128]``,
tile after tile; a token's register is the rows ``j, j + 8, ...`` of one
such ``[64, 128]`` piece, one sublane-strided load. So ``dt`` and ``dt *
h`` are copied a chunk at a time, tile by tile as they are, into scratch
of that shape (a view of the block itself is not Mosaic's to give), ``y``
comes back by the matching strided store, and ``D * h`` and the gate
``silu(z)`` are applied to the whole block at the end. Scratch: the state
``d_state * 4`` KB a tile, and three ``[chunk, bd]`` float32 pieces (1.5
MB at 128 x 1,024); the 2 MB that held ``B_t`` and ``C_t`` broadcast over
the lanes are gone.

``dt``, ``A``, ``B``, ``C``, the state and every step of the recurrence
are float32 whatever the dtype of ``h`` and ``z``; one exponential a state
element a token, taken as ``2 ** (dt * (A * log2(e)))`` with ``A`` scaled
once outside the kernel, which is the multiply ``exp`` would do inside for
every element. ``d_state`` is unrolled: at 16 the state and ``A`` of a
tile are 32 of the 64 vector registers; at ``MAX_D_STATE`` = 32 the
state alone is half of them and ``A`` is read again from VMEM; above it
(at 64 the state would be the whole file) the call is refused.

:func:`selective_scan` always runs the kernel — compiled, or under
``interpret=True`` (the CPU tests). :func:`make_selective_scan_fn` picks
the scan a model is BUILT with, once, from the process's default backend
(as ``make_flash_attention_fn`` does): the kernel on TPU,
:func:`chunked_scan` in plain ``jax.numpy`` elsewhere, recorded as
``.kind``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LANES = 128
SUBLANES = 8
MAX_D_STATE = 32


def _sum_of(parts):
    """The sum of a list of registers as a tree: four additions deep at
    16, where a chain would be fifteen."""
    while len(parts) > 1:
        odd = parts[len(parts) - len(parts) % 2 :]
        parts = [a + b for a, b in zip(parts[::2], parts[1::2])] + odd
    return parts[0]


def _scan_kernel(
    h_ref,
    dt_ref,
    b_ref,
    c_ref,
    z_ref,
    a_ref,
    d_ref,
    o_ref,
    s_ref,
    dt_t_ref,
    u_t_ref,
    y_t_ref,
):
    """One (row, d_inner block, chunk) step. Blocks: h, dt, z, o
    ``[1, chunk, bd]`` in VMEM; B, C ``[1, chunk / 8, 8 * d_state]`` in
    SMEM (token ``8 i + j``'s ``B[n]`` is ``[0, i, j * d_state + n]``: the
    dynamic index picks a row, the place in it is static); A, scaled by
    ``log2(e)``, ``[d_state, 8 * tiles, 128]`` (a tile's ``A_n`` is one
    ``[8, 128]`` piece); D ``[1, bd]``. Scratch: the state
    ``[tiles, d_state, 8, 128]``; ``dt``, ``dt * h`` and ``y`` as
    ``[tiles, chunk / 8, 64, 128]``, row ``8 g + j`` of piece ``i`` being
    lane group ``g`` of token ``8 i + j``."""
    from jax.experimental import pallas as pl

    tiles, n = s_ref.shape[:2]
    _, chunk, bd = h_ref.shape
    groups = bd // LANES
    pieces = chunk // SUBLANES
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_ref[:] = jnp.zeros_like(s_ref)
        live = groups % SUBLANES * SUBLANES
        if live:  # a part-filled last tile: its dead sublanes read zeros
            dead = jnp.zeros((pieces, SUBLANES * SUBLANES - live, LANES), f32)
            dt_t_ref[tiles - 1, :, live:, :] = dead
            u_t_ref[tiles - 1, :, live:, :] = dead

    def place(g):
        """Lane group g: its tile, its rows in a piece, its lanes."""
        tile, at = divmod(g, SUBLANES)
        return tile, pl.ds(at * SUBLANES, SUBLANES), pl.ds(g * LANES, LANES)

    for g in range(groups):
        tile, rows, lanes = place(g)
        dt = dt_ref[0, :, lanes]
        u = dt * h_ref[0, :, lanes].astype(f32)
        dt_t_ref[tile, :, rows, :] = dt.reshape(pieces, SUBLANES, LANES)
        u_t_ref[tile, :, rows, :] = u.reshape(pieces, SUBLANES, LANES)

    for tile in range(tiles):
        a = [a_ref[m, pl.ds(tile * SUBLANES, SUBLANES), :] for m in range(n)]

        def eight_tokens(i, states, tile=tile, a=a):
            states = list(states)
            for j in range(SUBLANES):
                # the rows j, j + 8, ... of piece i: token 8 i + j's eight
                # lane groups, one register
                token = pl.ds(j, SUBLANES, stride=SUBLANES)
                dt_t = dt_t_ref[tile, i, token, :]
                u_t = u_t_ref[tile, i, token, :]
                read = []
                for m in range(n):
                    at = j * n + m
                    states[m] = (
                        jnp.exp2(dt_t * a[m]) * states[m] + u_t * b_ref[0, i, at]
                    )
                    read.append(states[m] * c_ref[0, i, at])
                y_t_ref[tile, i, token, :] = _sum_of(read)
            return tuple(states)

        states = jax.lax.fori_loop(
            0, pieces, eight_tokens, tuple(s_ref[tile, m] for m in range(n))
        )
        for m in range(n):
            s_ref[tile, m] = states[m]

    for g in range(groups):
        tile, rows, lanes = place(g)
        y = y_t_ref[tile, :, rows, :].reshape(chunk, LANES)
        h = h_ref[0, :, lanes].astype(f32)
        z = z_ref[0, :, lanes].astype(f32)
        o_ref[0, :, lanes] = (
            (y + d_ref[:, lanes] * h) * (z * jax.nn.sigmoid(z))
        ).astype(o_ref.dtype)


def _block_d(d_inner: int, want: int) -> int:
    """The largest multiple of 128 that divides ``d_inner`` and is at
    most ``want``."""
    best = LANES
    for bd in range(LANES, min(d_inner, want) + 1, LANES):
        if d_inner % bd == 0:
            best = bd
    return best


# jitted, so that a program of 26 such layers traces and lowers the unrolled
# kernel body once a shape and not once a layer (seconds of set-up a bucket)
@functools.partial(
    jax.jit, static_argnames=("chunk", "block_d", "interpret", "out_dtype")
)
def selective_scan(
    h,
    dt,
    b,
    c,
    z,
    a,
    d,
    *,
    chunk: int = 128,
    block_d: int = 1024,
    interpret: bool = False,
    out_dtype=None,
):
    """The recurrence above over whole sequences.

    Args:
        h, z: ``[B, L, d_inner]``, any float dtype; the output is
            ``out_dtype``, h's by default.
        dt: ``[B, L, d_inner]`` float32, after its softplus.
        b, c: ``[B, L, d_state]`` float32.
        a: ``[d_inner, d_state]`` float32 (negative); d: ``[d_inner]``.
            ``d_state`` is at most ``MAX_D_STATE``: the state of a tile
            is ``d_state`` vector registers of the chip's 64.
        chunk: tokens a grid step, a multiple of 128. A sequence is
            padded up to it with ``dt = 0``, which leaves the state as it
            is.
        block_d: the widest block of ``d_inner`` a grid step takes; a
            block is cut into tiles of 1,024 channels, the last one
            part-filled where the block is not a whole number of them.

    Returns ``y * silu(z)``, ``[B, L, d_inner]``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, length, d_inner = h.shape
    n = a.shape[1]
    if chunk % LANES or d_inner % LANES:
        raise ValueError(
            f"selective_scan: chunk {chunk} and d_inner {d_inner} must be "
            f"multiples of {LANES}"
        )
    if n > MAX_D_STATE:
        raise ValueError(
            f"selective_scan: d_state {n} is above {MAX_D_STATE}: the state "
            f"of a tile is d_state vector registers"
        )
    pad = -length % chunk
    if pad:
        pad3 = ((0, 0), (0, pad), (0, 0))
        h, dt, b, c, z = (jnp.pad(t, pad3) for t in (h, dt, b, c, z))
    padded = length + pad
    bd = _block_d(d_inner, block_d)
    groups = bd // LANES
    tiles = -(-groups // SUBLANES)
    f32 = jnp.float32
    # A^T a block as whole tiles: [d_state, blocks * 8 * tiles, 128], the
    # groups a part-filled tile lacks zero
    a_t = jnp.swapaxes(a.astype(f32) * math.log2(math.e), 0, 1)
    a_t = a_t.reshape(n, d_inner // bd, groups, LANES)
    a_t = jnp.pad(a_t, ((0, 0), (0, 0), (0, tiles * SUBLANES - groups), (0, 0)))
    a_t = a_t.reshape(n, -1, LANES)

    def eight_tokens_a_row(t):
        return t.astype(f32).reshape(rows, padded // SUBLANES, SUBLANES * n)

    wide = pl.BlockSpec((1, chunk, bd), lambda r, j, k: (r, k, j))
    scalars = pl.BlockSpec(
        (1, chunk // SUBLANES, SUBLANES * n),
        lambda r, j, k: (r, k, 0),
        memory_space=pltpu.SMEM,
    )
    turned = pltpu.VMEM(
        (tiles, chunk // SUBLANES, SUBLANES * SUBLANES, LANES), f32
    )
    out = pl.pallas_call(
        _scan_kernel,
        grid=(rows, d_inner // bd, padded // chunk),
        in_specs=[
            wide,
            wide,
            scalars,
            scalars,
            wide,
            pl.BlockSpec((n, tiles * SUBLANES, LANES), lambda r, j, k: (0, j, 0)),
            pl.BlockSpec((1, bd), lambda r, j, k: (0, j)),
        ],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(
            (rows, padded, d_inner), out_dtype or h.dtype
        ),
        scratch_shapes=[
            pltpu.VMEM((tiles, n, SUBLANES, LANES), f32),  # the state
            turned,  # dt, a token a register
            turned,  # dt * h
            turned,  # y
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        # a stable name for the kernel's events in a profiler trace
        name="selective_scan",
    )(
        h,
        dt.astype(f32),
        eight_tokens_a_row(b),
        eight_tokens_a_row(c),
        z,
        a_t,
        d.astype(f32)[None, :],
    )
    return out[:, :length]


def chunked_scan(h, dt, b, c, z, a, d, *, chunk: int = 64, out_dtype=None):
    """The same recurrence in plain ``jax.numpy``: chunks of the sequence
    one after another (``lax.scan`` carrying the state), each chunk's
    tokens by an associative scan, so that the state written out over
    time is a chunk's and never the sequence's."""
    f32 = jnp.float32
    rows, length, d_inner = h.shape
    pad = -length % chunk
    h32, z32 = h.astype(f32), z.astype(f32)
    u = dt.astype(f32) * h32
    parts = [dt.astype(f32), u, b.astype(f32), c.astype(f32)]
    if pad:
        parts = [jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in parts]
    # [chunks, B, chunk, ...]
    parts = [
        jnp.swapaxes(t.reshape(rows, -1, chunk, t.shape[-1]), 0, 1)
        for t in parts
    ]
    a = a.astype(f32)

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    def one_chunk(state, part):
        dt_c, u_c, b_c, c_c = part
        decay = jnp.exp(dt_c[..., None] * a)  # [B, chunk, d_inner, d_state]
        fed = u_c[..., None] * b_c[:, :, None, :]
        through, added = jax.lax.associative_scan(combine, (decay, fed), axis=1)
        states = through * state[:, None] + added
        y = jnp.sum(states * c_c[:, :, None, :], -1)
        return states[:, -1], y

    state = jnp.zeros((rows, d_inner, a.shape[1]), f32)
    _, y = jax.lax.scan(one_chunk, state, tuple(parts))
    y = jnp.swapaxes(y, 0, 1).reshape(rows, -1, d_inner)[:, :length]
    return ((y + d.astype(f32) * h32) * (z32 * jax.nn.sigmoid(z32))).astype(
        out_dtype or h.dtype
    )


def make_selective_scan_fn(
    chunk: int = 128,
    block_d: int = 1024,
    interpret: bool = False,
    out_dtype=None,
):
    """Returns ``scan(h, dt, b, c, z, a, d)``. The choice is made HERE, at
    build time, from the process's default backend: the Pallas kernel on
    TPU (or interpreted when asked, never derived from the backend),
    :func:`chunked_scan` elsewhere. The returned function's ``.kind``
    ('pallas' | 'jnp') says which, and nothing downstream re-decides: a
    kernel that fails to compile raises."""
    if not interpret and jax.default_backend() != "tpu":
        fn = functools.partial(chunked_scan, out_dtype=out_dtype)
        fn.kind = "jnp"
    else:
        fn = functools.partial(
            selective_scan,
            chunk=chunk,
            block_d=block_d,
            interpret=interpret,
            out_dtype=out_dtype,
        )
        fn.kind = "pallas"
    return fn
