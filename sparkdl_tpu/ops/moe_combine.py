"""The routed experts' combine: each token's held slot rows, weighted and
summed back in token order.

``y`` [rows, hidden] float32 holds the experts' results one row a slot
(the slot buffer, rows sorted by expert), ``slot`` [tokens, k] int32 the
row of each of a token's k slots, -1 where the slot is not held (a pad
token, or an expert that lives on another chip), ``weights`` [tokens, k]
float32 the routing weights. The result is ``out[t] = sum_j w[t, j] *
y[slot[t, j]]`` over the held slots, added in float32 in the order j =
0 ... k-1; a token with no held slot gets zeros.

The Pallas kernel (TPU, or interpreted) leaves ``y`` in HBM where the
last product wrote it and copies each held row by DMA into VMEM: a row
that is not held is never read. HBM holds a float32 [rows, hidden] array
in tiles of 8 rows by 128 lanes, one row a sublane, and a DMA cannot cut
one sublane out of a tile; but the same bytes are [rows / 8, hidden /
128, 8, 1, 128] laid out plainly (a bitcast, nothing copied), where a
row is one index of the third axis: one strided DMA of hidden / 128
pieces of 512 bytes. It lands token t of a group of eight in sublane t
of a [hidden / 128, 8, 128] scratch, one for each of the k slots, so
that the eight tokens' sums are whole registers, added in the slots'
order under the held mask and stored as they are into the result's
tiles. The next eight tokens' copies are started before the current
ones are summed (two scratch slots), across the token tiles too. Its
loops are ``lax.fori_loop``s and only the k slots are unrolled, so the
lowered module is the same size at every token count, and
:func:`moe_combine` builds one ``pallas_call`` a shape: the expert
layers of a program share one lowering. Off the TPU the plain form is k
gathers of the whole buffer and a mask (:func:`gather_combine`).
:func:`make_moe_combine_fn` chooses at build time and says which
(``.kind``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: tokens a step of the kernel's loop: one a sublane of a float32 register
_GROUP = 8
#: lanes of a register: a row is read in pieces of this many
_LANES = 128
#: bytes of the double-buffered output block, which sets the token tile
_OUT_BLOCK_BYTES = 8 << 20
#: the most tokens a tile
_MOST_TILE = 512
#: gathered parts summed a pass where ``y`` holds a row for every slot
_COMBINE_AT_ONCE = 3


def _stride(top_k: int) -> int:
    """Places a token in the kernel's flat slot and weight tables: k
    rounded up to a power of two (8 for the cells' 6 and 8)."""
    return 1 << (top_k - 1).bit_length()


def token_tile(hidden: int) -> int:
    """Tokens a grid step: the largest power of two, from 8 up to 512,
    whose double-buffered float32 output block [tile, hidden] is within
    8 MiB. At hidden 2,048 that is 512, at 5,120 and 7,168 it is 128: the
    block, the [2, k, hidden / 128, 8, 128] scratch (3.7 MB at 7,168 and
    k = 8) and the weights' blocks lie under the 16 MiB of VMEM a v5e
    scopes by default."""
    tile = _MOST_TILE
    while tile > _GROUP and 2 * tile * hidden * 4 > _OUT_BLOCK_BYTES:
        tile //= 2
    return tile


def _kernel(top_k, stride, tile, slots, weights, tile_slots, y, out, rows, sems):
    """One token tile. ``slots`` is every token's k rows in SMEM (the
    scalar-prefetch operand, flat, ``stride`` places a token), ``weights``
    and ``tile_slots`` the tile's [tile, stride] in VMEM, ``y`` [rows / 8,
    pieces, 8, 1, lanes] in HBM and ``rows`` the [2, k, pieces, 8, lanes]
    scratch of fetched rows, a token a sublane."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pieces, lanes = y.shape[1], y.shape[-1]
    groups = tile // _GROUP
    first = pl.program_id(0) * groups
    last = pl.num_programs(0) * groups

    def copy(group, t, j, buf):
        """The copy of slot j of token t of a group of eight tokens into
        scratch slot ``buf``, and whether that slot is held. A held row is
        never negative: its tile and sublane are a shift and a mask."""
        row = slots[(group * _GROUP + t) * stride + j]
        fetch = pltpu.make_async_copy(
            y.at[row >> 3, :, row & 7], rows.at[buf, j, :, pl.ds(t, 1)], sems.at[buf]
        )
        return row >= 0, fetch

    @pl.when(first == 0)
    def _():
        def token(t, carry):
            for j in range(top_k):
                held, fetch = copy(0, t, j, 0)
                pl.when(held)(fetch.start)
            return carry

        jax.lax.fori_loop(0, _GROUP, token, 0)

    def group(g, carry):
        at = first + g
        buf = at % 2
        ahead, after = at + 1 < last, jnp.minimum(at + 1, last - 1)
        landed = pltpu.make_async_copy(y.at[0, :, 0], rows.at[buf, 0, :, pl.ds(0, 1)], sems.at[buf])

        def token(t, carry):
            for j in range(top_k):
                held, fetch = copy(after, t, j, 1 - buf)
                pl.when(ahead & held)(fetch.start)
            for j in range(top_k):
                # a wait needs the copy's size and semaphore alone
                pl.when(slots[(at * _GROUP + t) * stride + j] >= 0)(landed.wait)
            return carry

        # the next group's copies start; this group's all signal one
        # semaphore, and a wait returns once a row's bytes have come,
        # whichever row's: every one has landed before any is read
        jax.lax.fori_loop(0, _GROUP, token, 0)

        # each slot's weight and held mask, a token a sublane
        lo = pl.multiple_of(g * _GROUP, _GROUP)
        w8, s8 = weights[pl.ds(lo, _GROUP), :], tile_slots[pl.ds(lo, _GROUP), :]
        weight = [jnp.broadcast_to(w8[:, j : j + 1], (_GROUP, lanes)) for j in range(top_k)]
        held = [jnp.broadcast_to(s8[:, j : j + 1] >= 0, (_GROUP, lanes)) for j in range(top_k)]

        def piece(c, carry):
            # the gather loop's arithmetic in its order; a slot not held
            # adds nothing, where the loop adds zero: the same bits
            acc = jnp.zeros((_GROUP, lanes), jnp.float32)
            for j in range(top_k):
                acc = jnp.where(held[j], acc + rows[buf, j, c] * weight[j], acc)
            out[pl.ds(lo, _GROUP), pl.ds(pl.multiple_of(c * lanes, lanes), lanes)] = acc
            return carry

        jax.lax.fori_loop(0, pieces, piece, 0)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)


@functools.lru_cache(maxsize=None)
def _build(tokens: int, hidden: int, top_k: int, interpret: bool):
    """The ``pallas_call`` of one shape (``tokens`` a whole number of
    tiles): call sites of that shape share the object and with it one
    lowering for each size of slot buffer they hand it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, stride = token_tile(hidden), _stride(top_k)
    lanes = min(hidden, _LANES)
    return pl.pallas_call(
        functools.partial(_kernel, top_k, stride, tile),
        out_shape=jax.ShapeDtypeStruct((tokens, hidden), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((tile, stride), lambda i, s: (i, 0)),
                pl.BlockSpec((tile, stride), lambda i, s: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tile, hidden), lambda i, s: (i, 0)),
            grid=(tokens // tile,),
            scratch_shapes=[
                pltpu.VMEM((2, top_k, hidden // lanes, _GROUP, lanes), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            # the copies run ahead across tiles: one step after another
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        # a stable name for the kernel's events in a profiler trace
        name="moe_combine",
    )


def moe_combine(y, slot, weights, *, interpret: bool = False):
    """``y`` [rows, hidden] float32, ``slot`` [tokens, k] int32 (-1 where
    not held), ``weights`` [tokens, k] float32 -> [tokens, hidden]
    float32: each token's held rows, weighted and summed in the order of
    its slots. On the TPU ``hidden`` is a whole number of 128."""
    tokens, top_k = slot.shape
    rows, hidden = y.shape
    if weights.shape != slot.shape or y.dtype != jnp.float32:
        raise ValueError(
            f"moe_combine: y {y.shape} {y.dtype}, slot {slot.shape}, "
            f"weights {weights.shape}"
        )
    lanes = min(hidden, _LANES)
    pad = -tokens % token_tile(hidden)
    # a token's k places padded to `_stride(k)`, a power of two: a place
    # in the flat table in SMEM is then a shift and an add
    places = ((0, pad), (0, _stride(top_k) - top_k))
    slot = jnp.pad(slot.astype(jnp.int32), places, constant_values=-1)
    weights = jnp.pad(weights.astype(jnp.float32), places)
    # the buffer's 8 x 128 tiles as they lie (a bitcast on the TPU): row r
    # is [r // 8, :, r % 8, 0, :]; the routed path's buffers are whole
    # tiles of 256 rows, and the pad is there for other callers
    y = jnp.pad(y, ((0, -rows % _GROUP), (0, 0)))
    pieces = hidden // lanes
    y = y.reshape(-1, _GROUP, pieces, lanes).transpose(0, 2, 1, 3)[:, :, :, None]
    call = _build(tokens + pad, hidden, top_k, interpret)
    out = call(slot.reshape(-1), weights, slot, y)
    return out[:tokens] if pad else out


def gather_combine(y, slot, weights):
    """What :func:`moe_combine` computes, by k gathers of the whole buffer
    and a mask: the build-time fallback off the TPU and the kernel's
    oracle. Where ``y`` holds a row for every slot (the worst-case
    buffer), all k gathered parts at once would be 2 GB beside it at
    ``deepseek-v2``'s sizes, so they are summed three a pass, the passes
    ordered by a barrier (4.40 s a job against 4.19 for one a pass on a
    v5e); a buffer sized to the held share leaves room for all k."""
    tokens, top_k = slot.shape
    at_once = _COMBINE_AT_ONCE if y.shape[0] >= slot.size else top_k
    held = slot >= 0
    at = jnp.maximum(slot, 0)
    out = jnp.zeros((tokens, y.shape[1]), jnp.float32)
    for j in range(top_k):
        part = y[at[:, j]] * weights[:, j, None]
        out = out + jnp.where(held[:, j, None], part, 0.0)
        if (j + 1) % at_once == 0 and j + 1 < top_k:
            y, out = jax.lax.optimization_barrier((y, out))
    return out


gather_combine.kind = "gather"


def make_moe_combine_fn(interpret: bool = False):
    """The combine a model is BUILT with: the Pallas kernel on the TPU
    (or interpreted when asked), :func:`gather_combine` elsewhere.
    ``.kind`` ('pallas' | 'gather') says which."""
    if not interpret and jax.default_backend() != "tpu":
        return gather_combine

    def combine(y, slot, weights):
        return moe_combine(y, slot, weights, interpret=interpret)

    combine.kind = "pallas"
    return combine
