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
token. ``d_inner`` lies on the lanes and ``d_state`` on the sublanes, so a
``[16, 128]`` piece of the state is two full vector registers; with
``d_state`` minor it would fill an eighth of each.

Inside a chunk: ``dt * h`` is formed for the whole block at once; the
columns ``B_t`` and ``C_t`` (``d_state`` values a token, needed along the
sublanes) are cut from the ``[d_state, chunk]`` blocks by a one-hot
select and a lane reduction and kept broadcast over 128 lanes in VMEM,
once a chunk; then, a few lane groups at a time, a loop over the tokens
carries those groups' state in registers and writes one row of ``y`` a
token; the gate ``silu(z)`` and ``D * h`` are applied to the whole block
at the end. ``dt``, ``A``, the state and every step of the recurrence are
float32 whatever the dtype of ``h`` and ``z``.

:func:`selective_scan` always runs the kernel — compiled, or under
``interpret=True`` (the CPU tests). :func:`make_selective_scan_fn` picks
the scan a model is BUILT with, once, from the process's default backend
(as ``make_flash_attention_fn`` does): the kernel on TPU,
:func:`chunked_scan` in plain ``jax.numpy`` elsewhere, recorded as
``.kind``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LANES = 128
SUBLANES = 8


def _scan_kernel(
    chunk: int,
    groups_per_loop: int,
    h_ref,
    dt_ref,
    bt_ref,
    ct_ref,
    z_ref,
    at_ref,
    d_ref,
    o_ref,
    s_ref,
    bx_ref,
    cx_ref,
    u_ref,
    y_ref,
):
    """One (row, d_inner block, chunk) step. Blocks: h, dt, z, o
    ``[1, chunk, bd]``; B^T, C^T ``[1, d_state, chunk]``; A^T
    ``[d_state, bd]``; D ``[1, bd]``. Scratch: the state ``[d_state, bd]``,
    the chunk's B and C columns broadcast over the lanes
    ``[chunk, d_state, 128]``, ``dt * h`` and ``y`` ``[chunk, bd]``."""
    from jax.experimental import pallas as pl

    n, bd = s_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_ref[:] = jnp.zeros_like(s_ref)

    u_ref[:] = dt_ref[0] * h_ref[0].astype(jnp.float32)

    bt, ct = bt_ref[0], ct_ref[0]  # [d_state, chunk]
    token = jax.lax.broadcasted_iota(jnp.int32, (n, chunk), 1)

    def columns(t, carry):
        here = token == t
        for src, dst in ((bt, bx_ref), (ct, cx_ref)):
            col = jnp.sum(jnp.where(here, src, 0.0), axis=1, keepdims=True)
            dst[t] = jnp.broadcast_to(col, (n, LANES))
        return carry

    jax.lax.fori_loop(0, chunk, columns, 0)

    sublane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    width = groups_per_loop * LANES
    for start in range(0, bd, width):
        lanes = [
            pl.ds(start + g * LANES, LANES) for g in range(groups_per_loop)
        ]
        a = [at_ref[:, at] for at in lanes]

        def eight_tokens(i, states, lanes=lanes, a=a):
            # a dynamic load or store of one row is not Mosaic's to give:
            # eight rows at an aligned offset are, and a row of those is
            # a static slice
            rows = pl.ds(pl.multiple_of(i * SUBLANES, SUBLANES), SUBLANES)
            dt8 = [dt_ref[0, rows, at] for at in lanes]
            u8 = [u_ref[rows, at] for at in lanes]
            y8 = [jnp.zeros((SUBLANES, LANES), jnp.float32) for _ in lanes]
            states = list(states)
            for j in range(SUBLANES):
                t = i * SUBLANES + j
                bx, cx = bx_ref[t], cx_ref[t]
                for g, a_g in enumerate(a):
                    states[g] = (
                        jnp.exp(dt8[g][j : j + 1] * a_g) * states[g]
                        + u8[g][j : j + 1] * bx
                    )
                    y = jnp.sum(states[g] * cx, axis=0, keepdims=True)
                    y8[g] = jnp.where(sublane == j, y, y8[g])
            for at, y in zip(lanes, y8):
                y_ref[rows, at] = y
            return tuple(states)

        states = jax.lax.fori_loop(
            0,
            chunk // SUBLANES,
            eight_tokens,
            tuple(s_ref[:, at] for at in lanes),
        )
        for at, state in zip(lanes, states):
            s_ref[:, at] = state

    h = h_ref[0].astype(jnp.float32)
    z = z_ref[0].astype(jnp.float32)
    o_ref[0] = ((y_ref[:] + d_ref[:] * h) * (z * jax.nn.sigmoid(z))).astype(
        o_ref.dtype
    )


def _block_d(d_inner: int, want: int) -> int:
    """The largest multiple of 128 that divides ``d_inner`` and is at
    most ``want``."""
    best = LANES
    for bd in range(LANES, min(d_inner, want) + 1, LANES):
        if d_inner % bd == 0:
            best = bd
    return best


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
    block_d: int = 1280,
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
        chunk: tokens a grid step, a multiple of 128 (B^T and C^T put the
            tokens on the lanes). A sequence is padded up to it with
            ``dt = 0``, which leaves the state as it is.
        block_d: the widest block of ``d_inner`` a grid step takes.

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
    pad = -length % chunk
    if pad:
        pad3 = ((0, 0), (0, pad), (0, 0))
        h, dt, b, c, z = (jnp.pad(t, pad3) for t in (h, dt, b, c, z))
    padded = length + pad
    bd = _block_d(d_inner, block_d)
    groups = bd // LANES
    groups_per_loop = max(g for g in (1, 2, 3, 4, 5) if groups % g == 0)
    f32 = jnp.float32
    kernel = functools.partial(_scan_kernel, chunk, groups_per_loop)
    wide = pl.BlockSpec((1, chunk, bd), lambda r, j, k: (r, k, j))
    cols = pl.BlockSpec((1, n, chunk), lambda r, j, k: (r, 0, k))
    out = pl.pallas_call(
        kernel,
        grid=(rows, d_inner // bd, padded // chunk),
        in_specs=[
            wide,
            wide,
            cols,
            cols,
            wide,
            pl.BlockSpec((n, bd), lambda r, j, k: (0, j)),
            pl.BlockSpec((1, bd), lambda r, j, k: (0, j)),
        ],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(
            (rows, padded, d_inner), out_dtype or h.dtype
        ),
        scratch_shapes=[
            pltpu.VMEM((n, bd), f32),  # the state, from chunk to chunk
            pltpu.VMEM((chunk, n, LANES), f32),  # B_t over the lanes
            pltpu.VMEM((chunk, n, LANES), f32),  # C_t over the lanes
            pltpu.VMEM((chunk, bd), f32),  # dt * h
            pltpu.VMEM((chunk, bd), f32),  # y
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
        jnp.swapaxes(b.astype(f32), 1, 2),
        jnp.swapaxes(c.astype(f32), 1, 2),
        z,
        jnp.swapaxes(a.astype(f32), 0, 1),
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
    block_d: int = 1280,
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
