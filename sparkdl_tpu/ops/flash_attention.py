"""Pallas TPU flash attention — the hot-op kernel for the text path.

The reference executed BERT through opaque TF graphs (BASELINE config[3]
names a BERT-base text-embedding UDF; SURVEY.md §3 #11); its attention was
whatever stock TF emitted. Here the local attention is an in-tree Pallas
kernel written for the TPU memory hierarchy: Q/K/V stream through VMEM in
(block_q × block_k) tiles, scores hit the MXU via ``dot_general`` with
float32 accumulation, and the softmax runs online (running max/sum in VMEM
scratch) so the [L, L] score matrix never materializes in HBM — O(L)
memory instead of O(L²).

Three tilings of that one algorithm, chosen by the heads' shape
(the third, :func:`flash_attention_latent`, is the blocked causal kernel
over a latent-attention model's own projections: a head's [nope | rope]
query, its keys and values as two column blocks of the one up-projected
array, and a rotary key that all heads share; given a selection, a byte
a (query, key) pair, it attends to the selected keys only).
:func:`flash_attention` takes [B, H, L, Dh] and gives one (row, head)
pair to a grid step: right where a head fills the 128 lanes (Jamba's
128), and what causal and shared-key/value attention run. A head
narrower than the lanes (BERT's 64) would be padded to 128 in HBM and
transposed in and out of that layout, so :func:`flash_attention_packed`
reads [B, L, H*Dh], the array each projection wrote, and a grid step
handles every head of a block of rows (:func:`packs` is the condition).

Composes with ring attention (ops/ring_attention.py): the ring rotates K/V
shards over the mesh's 'sp' axis while this kernel computes each local
block product. Both functions always run the kernel — compiled, or under
``interpret=True`` (the CPU tests) — and a Mosaic compile error is an
error. :func:`make_flash_attention_fn` picks the attention a model is
BUILT with, once, from the process's default backend and the heads'
shape, and the choice is recorded on the returned function (``.kind``,
``.layout``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30  # finite -inf stand-in: keeps exp()/max() NaN-free
LANES = 128  # the lane width: the last dim of a VMEM tile


def _flash_kernel(
    nk: int,
    scale: float,
    q_ref,
    k_ref,
    v_ref,
    mask_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    causal: bool = False,
    live=None,
):
    """Grid = (B*H, num_q_blocks, num_k_blocks); the k dimension is
    sequential ('arbitrary'), so VMEM scratch carries the online softmax
    state across k-steps for each (bh, qi) tile. ``causal`` (square
    blocks): a key block above the diagonal is skipped, not masked after
    the product, and the diagonal block is masked inside. ``live``: see
    :func:`_when`."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qi = pl.program_id(1) if causal else None

    @_when(ki == 0, live)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _block():
        q = q_ref[0].astype(jnp.float32)  # [bq, dh]
        k = k_ref[0].astype(jnp.float32)  # [bk, dh]
        v = v_ref[0].astype(jnp.float32)  # [bk, dh]

        s = (
            jax.lax.dot_general(
                q,
                k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [bq, bk]
        s = s + mask_ref[0]  # [1, bk] broadcasts over the q rows
        if causal:
            bq, bk = s.shape
            row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col <= row, s, NEG_INF)

        _softmax_step(s, v, m_ref, l_ref, acc_ref)

    if causal:
        _when(ki <= qi, live)(_block)
    else:
        _block()

    @_when(ki == nk - 1, live)
    def _finalize():
        _write_result(o_ref, l_ref, acc_ref)


def _when(step, live):
    """``pl.when(step)`` (``step`` None: always), and in a call that knows
    its rows' lengths (``live``: whether the step's query block is live,
    a traced bool) in a live query block alone. Without lengths a step
    that always runs is called as it is, outside any branch."""
    from jax.experimental import pallas as pl

    if live is None:
        return (lambda f: f()) if step is None else pl.when(step)
    return pl.when(live if step is None else jnp.logical_and(step, live))


def _by_length(kernel, heads: int, last_step: int, live_ref, *refs):
    """``kernel`` (the causal or the window kernel, over the refs q, k,
    v, mask, o and its scratch) for a call that knows its rows' lengths:
    ``live_ref`` is the prefetched [B] int32 of how many leading query
    blocks of each row are *live*, hold a real token (a grid step's row
    is its first index over ``heads``). A live query block runs the
    kernel's steps as a call without lengths runs them, step for step
    (the kernel ANDs ``live`` into each of its branches, :func:`_when`:
    one branch around the whole kernel would not do, since the Pallas
    interpreter reads no ``program_id`` inside a branch). A *dead* block,
    padding alone, runs nothing at any key step and, at its last
    (``last_step``), writes zeros itself."""
    from jax.experimental import pallas as pl

    live = pl.program_id(1) < live_ref[jax.lax.div(pl.program_id(0), heads)]
    kernel(*refs, live=live)

    @pl.when(jnp.logical_and(pl.program_id(2) == last_step, jnp.logical_not(live)))
    def _dead():
        o_ref = refs[4]
        o_ref[...] = jnp.zeros_like(o_ref)


def _resident(block, qi, live):
    """The block that a step of query block ``qi`` names, where its row
    has ``live`` live query blocks: ``block``, the one it names without
    lengths, in a live query block; in a dead one the row's last live
    query block (block 0 for a row of padding), which the last step of
    that block named for its queries and its keys alike (the diagonal),
    so that nothing is fetched for a dead block's steps."""
    last = jnp.maximum(live, 1) - 1
    return jnp.where(qi > last, last, block)


def _named(heads: int, block, bh, qi, *live):
    """The block an index map names at step (``bh``, ``qi``): ``block``;
    in a call with lengths (``live``, the maps' prefetched last argument,
    each row's count of live query blocks) a dead query block's names the
    row's last live one (:func:`_resident`)."""
    return _resident(block, qi, live[0][bh // heads]) if live else block


def _window_key_block(qi, ki, steps: int):
    """The key block that step ``ki`` of query block ``qi`` names in the
    window kernel's band of ``steps`` blocks, the diagonal last; a step
    below block 0 names block 0, which the band's first live step reads,
    so that nothing is fetched for it."""
    return jnp.maximum(qi - (steps - 1) + ki, 0)


def _window_kernel(
    steps: int, scale: float, window: int, q_ref, k_ref, v_ref, mask_ref,
    o_ref, m_ref, l_ref, acc_ref, live=None,
):
    """Grid = (B*H, num_q_blocks, steps): step ``ki`` of query block
    ``qi`` reads key block ``qi - (steps - 1) + ki``, the band of blocks
    that a query block's window reaches, the diagonal last. A step below
    block 0 runs nothing. The diagonal block is masked by ``j <= i``, the
    band's lowest by ``i - j < window`` where it can hold a key out of
    the window, and the blocks between run unmasked. ``live``: see
    :func:`_when`."""
    from jax.experimental import pallas as pl

    qi, ki = pl.program_id(1), pl.program_id(2)
    last = steps - 1
    key_block = qi - last + ki
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @_when(ki == 0, live)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _block(diagonal: bool, band: bool):
        q = q_ref[0].astype(jnp.float32)  # [bq, dh]
        k = k_ref[0].astype(jnp.float32)  # [bk, dh]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # [bq, bk]
        s = s + mask_ref[0]  # [1, bk] broadcasts over the q rows
        if diagonal or band:
            row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = key_block * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = (col <= row) if diagonal else (row - col < window)
            if diagonal and band:
                seen = seen & (row - col < window)
            s = jnp.where(seen, s, NEG_INF)
        _softmax_step(s, v_ref[0].astype(jnp.float32), m_ref, l_ref, acc_ref)

    # whether the band's lowest block can hold a key out of the window
    clipped = steps * bk > window
    if steps == 1:
        _when(None, live)(lambda: _block(True, clipped))
    else:
        started = key_block >= 0
        _when(jnp.logical_and(ki == 0, started), live)(lambda: _block(False, clipped))
        if steps > 2:
            between = jnp.logical_and(ki > 0, ki < last)
            _when(jnp.logical_and(between, started), live)(lambda: _block(False, False))
        _when(ki == last, live)(lambda: _block(True, False))

    @_when(ki == last, live)
    def _finalize():
        _write_result(o_ref, l_ref, acc_ref)


def _softmax_step(s, v, m_ref, l_ref, acc_ref, rows=slice(None)):
    """One key block of the online softmax: scores s [bq, bk] and values
    v [bk, dv] folded into the running max, sum and accumulator, of the
    query rows ``rows`` of the block (all of them, as ``[:]`` reads)."""
    # lanes of m_ref/l_ref all hold the same per-row value; max() reads it
    # back without a sub-128 lane slice.
    m_prev = jnp.max(m_ref[rows], axis=-1, keepdims=True)  # [bq, 1]
    l_prev = jnp.max(l_ref[rows], axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)  # [bq, bk]
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_ref[rows] = acc_ref[rows] * alpha + pv
    m_ref[rows] = jnp.broadcast_to(m_new, s.shape[:1] + m_ref.shape[1:])
    l_ref[rows] = jnp.broadcast_to(l_new, s.shape[:1] + l_ref.shape[1:])


def _write_result(o_ref, l_ref, acc_ref):
    l_final = jnp.max(l_ref[:], axis=-1, keepdims=True)
    o_ref[0] = (acc_ref[:] / jnp.maximum(l_final, 1e-30)).astype(o_ref.dtype)


def _pad_len(n: int, block: int) -> int:
    return (block - n % block) % block


def _key_mask(mask, batch: int, keys: int):
    """The additive key mask as float32 [B, Lk]; zeros for none."""
    if mask is None:
        return jnp.zeros((batch, keys), jnp.float32)
    return mask.reshape(batch, keys).astype(jnp.float32)


def flash_attention(
    q,
    k,
    v,
    mask: Optional[jax.Array] = None,
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    causal: bool = False,
    window: Optional[int] = None,
    lengths: Optional[jax.Array] = None,
):
    """Blockwise-online softmax attention.

    Args:
        q: [B, H, L, Dh]. k, v: [B, Hkv, Lk, Dh], Hkv a divisor of H:
            consecutive groups of H // Hkv query heads share one
            key/value head, found by the blocks' index maps and never
            repeated in memory.
        mask: additive key mask, [B, L] or [B, 1, 1, L] float (0 for keep,
            large-negative for drop). Applied to keys, as in BERT padding.
        block_q/block_k: VMEM tile sizes (128 matches the lane width).
        interpret: run the Pallas interpreter (CPU tests).
        causal: a query sees the keys at or before its own position
            (Lk == L, square blocks). Key blocks above the diagonal are
            skipped, and their index clamps to the diagonal's, so they
            are not fetched either.
        window: with ``causal``, a sliding window: query i sees key j
            iff 0 <= i - j < window (a multiple of the block). The grid's
            key axis then walks only the band of ``min(nk, window /
            block_k + 1)`` blocks that a query block's window reaches,
            the diagonal last (:func:`_window_kernel`); a step below
            block 0 is neither run nor fetched. The call is named
            ``flash_attention_window``. None: the causal or plain kernel,
            as it was before the option existed.
        lengths: with ``causal``, None or [B] int32: how many leading
            positions of each row hold its real tokens, the rest right
            padding (0 for a row of padding). A real query's answer needs
            no length: right padding needs no mask under a causal one.
            What a length saves is the padding's own queries: each row's
            count of live query blocks (those that start before its
            length) is a scalar-prefetch operand (same grid, blocks,
            scratch and name). A live block, the one that holds the row's
            last real token too, runs the steps it runs without lengths,
            in their order, so a real query's answer is the same to the
            bit; a dead block runs no step, every step of it names the
            blocks the row's last live one left resident (block 0 for a
            row of padding), so nothing is fetched for it, and its
            positions of the result are zeros. None: every block runs,
            through the call without the operand, as before the option
            existed.

    Returns [B, H, L, Dh] in q's dtype.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, L, Dh = q.shape
    Hkv, Lk = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(f"{H} query heads over {Hkv} key/value heads")
    if causal and (Lk != L or block_q != block_k):
        raise ValueError(
            "causal attention wants Lk == L and square blocks, got "
            f"L={L}, Lk={Lk}, blocks {block_q}x{block_k}"
        )
    if window is not None and (
        not causal or window < block_k or window % block_k
    ):
        raise ValueError(
            f"a window of {window} wants causal attention and a multiple "
            f"of the key block {block_k}"
        )
    if lengths is not None and (
        not causal or lengths.shape != (B,) or lengths.dtype != jnp.int32
    ):
        raise ValueError(
            f"lengths for q {q.shape} want causal attention and [B] int32, "
            f"got {lengths.shape} {lengths.dtype}"
        )
    mask2d = _key_mask(mask, B, Lk)

    # Head dims below the 128-lane tile (BERT-base: Dh=64) are zero-padded
    # up to the lane width: zero q/k columns leave the scores unchanged
    # (scale uses the TRUE Dh), zero v columns emit zero output columns
    # that are sliced off at the end.
    scale = 1.0 / np.sqrt(Dh)
    dh_pad = _pad_len(Dh, 128)
    if dh_pad:
        pad4 = ((0, 0), (0, 0), (0, 0), (0, dh_pad))
        q = jnp.pad(q, pad4)
        k = jnp.pad(k, pad4)
        v = jnp.pad(v, pad4)
    Dh_p = Dh + dh_pad

    # pad sequence lengths up to block multiples; padded keys get NEG_INF
    pq, pk = _pad_len(L, block_q), _pad_len(Lk, block_k)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
        mask2d = jnp.pad(mask2d, ((0, 0), (0, pk)), constant_values=NEG_INF)
    Lq_p, Lk_p = L + pq, Lk + pk

    qf = q.reshape(B * H, Lq_p, Dh_p)
    kf = k.reshape(B * Hkv, Lk_p, Dh_p)
    vf = v.reshape(B * Hkv, Lk_p, Dh_p)
    # [B, 1, Lk]: the mask block's last two dims are then (1, block_k),
    # and a second-minor block dim of 1 is legal only where it equals
    # the array's own — blocking a [B, Lk] mask as (1, block_k) breaks
    # the (8, 128) tiling rule for every B > 1.
    mask3d = mask2d[:, None, :]

    nq = Lq_p // block_q
    nk = Lk_p // block_k
    live = None if lengths is None else (lengths + (block_q - 1)) // block_q
    named = functools.partial(_named, H)
    if window is not None:
        out = _flash_window(
            qf, kf, vf, mask3d, H, Hkv, min(nk, window // block_k + 1), scale,
            window, block_q, interpret, live,
        )
        return out.reshape(B, H, Lq_p, Dh_p)[:, :, :L, :Dh]

    kernel = functools.partial(_flash_kernel, nk, scale)
    q_block = lambda bh, qi, ki, *live: (bh, named(qi, bh, qi, *live), 0)  # noqa: E731
    kv_block = lambda bh, qi, ki: (bh, ki, 0)  # noqa: E731
    mask_block = lambda bh, qi, ki, H=H: (bh // H, 0, ki)  # noqa: E731
    if causal or Hkv != H:
        # with neither, the lowered kernel is to the byte what it was
        # before either existed
        kernel = functools.partial(kernel, causal=causal)
        group = H // Hkv

        def kv_head(bh):
            return (bh // H) * Hkv + (bh % H) // group

        def key_block(bh, qi, ki, *live):
            return named(jnp.minimum(ki, qi) if causal else ki, bh, qi, *live)

        kv_block = lambda bh, qi, ki, *live: (  # noqa: E731
            kv_head(bh), key_block(bh, qi, ki, *live), 0
        )
        mask_block = lambda bh, qi, ki, *live: (  # noqa: E731
            bh // H, 0, key_block(bh, qi, ki, *live)
        )
    grid = dict(
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh_p), q_block),
            pl.BlockSpec((1, block_k, Dh_p), kv_block),
            pl.BlockSpec((1, block_k, Dh_p), kv_block),
            pl.BlockSpec((1, 1, block_k), mask_block),
        ],
        # the result's map alone keeps a dead block's own index: its zeros
        out_specs=pl.BlockSpec(
            (1, block_q, Dh_p), lambda bh, qi, ki, *live: (bh, qi, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
            pltpu.VMEM((block_q, Dh_p), jnp.float32),  # output accumulator
        ],
    )
    out = _blocked_call(
        kernel, grid, [qf, kf, vf, mask3d], (B * H, Lq_p, Dh_p), q.dtype,
        # a stable name for the kernel's events in a profiler trace
        "flash_attention", interpret, live, H, nk - 1,
    )

    out = out.reshape(B, H, Lq_p, Dh_p)
    return out[:, :, :L, :Dh]


def _flash_window(
    qf, kf, vf, mask3d, H, Hkv, steps, scale, window, block, interpret, live,
):
    """The window kernel's call over :func:`flash_attention`'s flattened,
    padded operands: q [B*H, L, Dh], k and v [B*Hkv, L, Dh], the key
    mask [B, 1, L]; grid (B*H, L / block, ``steps``). ``live``: None, or
    each row's count of live query blocks, prefetched (:func:`_named`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, L, Dh = qf.shape
    group = H // Hkv
    named = functools.partial(_named, H)

    def key_block(bh, qi, ki, *live):
        return named(_window_key_block(qi, ki, steps), bh, qi, *live)

    def kv_block(bh, qi, ki, *live):
        head = (bh // H) * Hkv + (bh % H) // group
        return head, key_block(bh, qi, ki, *live), 0

    kernel = functools.partial(_window_kernel, steps, scale, window)
    grid = dict(
        grid=(BH, L // block, steps),
        in_specs=[
            pl.BlockSpec(
                (1, block, Dh),
                lambda bh, qi, ki, *live: (bh, named(qi, bh, qi, *live), 0),
            ),
            pl.BlockSpec((1, block, Dh), kv_block),
            pl.BlockSpec((1, block, Dh), kv_block),
            pl.BlockSpec(
                (1, 1, block),
                lambda bh, qi, ki, *live: (bh // H, 0, key_block(bh, qi, ki, *live)),
            ),
        ],
        # the result's map alone keeps a dead block's own index: its zeros
        out_specs=pl.BlockSpec((1, block, Dh), lambda bh, qi, ki, *live: (bh, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block, 128), jnp.float32),  # running max
            pltpu.VMEM((block, 128), jnp.float32),  # running sum
            pltpu.VMEM((block, Dh), jnp.float32),  # output accumulator
        ],
    )
    return _blocked_call(
        kernel, grid, [qf, kf, vf, mask3d], (BH, L, Dh), qf.dtype,
        # its own name: a trace tells the window layers from the full ones
        "flash_attention_window", interpret, live, H, steps - 1,
    )


def _blocked_call(
    kernel, grid, operands, shape, dtype, name, interpret, live, heads, last_step
):
    """The one ``pallas_call`` of the blocked kernel or its window mode:
    ``grid`` its grid, blocks and scratch, the last axis sequential. With
    ``live`` (each row's count of live query blocks) that count is the
    first operand, scalar-prefetched, and :func:`_by_length` runs the
    kernel; without, the call is the one made before lengths existed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if live is not None:
        kernel = functools.partial(_by_length, kernel, heads, last_step)
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, **grid))
        operands = [live, *operands]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=name,
        **grid,
    )(*operands)


def packs(num_heads: Optional[int], head_dim: Optional[int]) -> bool:
    """Whether heads of this shape lie whole in lane tiles of the
    projections' [B, L, H*Dh] output: a head size under the lane width
    that divides it, and whole tiles. The packed kernel's condition."""
    return bool(
        num_heads
        and head_dim
        and head_dim < LANES
        and LANES % head_dim == 0
        and (num_heads * head_dim) % LANES == 0
    )


def _packed_kernel(nk: int, scale: float, group: int, *refs):
    """Grid = (B // r, num_q_blocks, num_k_blocks): one step is every
    head of ``r`` rows. The refs' last dim is H*Dh, heads side by side
    as the projections wrote them, ``group`` = 128 // Dh of them to a
    lane tile. A tile's heads are separated without a lane shuffle: the
    tile's q is stacked ``group`` times on the rows, copy j keeping head
    j's lanes and zeros elsewhere (exact: the zeros add nothing to the
    contraction over the tile's 128 lanes), so one product with the
    tile's k gives every head's scores, [group*bq, bk], and the softmax
    is the blocked kernel's, row by row. ``p·v`` against the whole tile
    then holds head j's answer in rows j*bq.. at head j's lanes, which
    the last key step selects.

    With more than one key step the scratch carries the online softmax
    for each (row, tile): running max and sum (lane broadcast) and the
    stacked accumulator. With one (``nk == 1``, a length within a
    block) there is no scratch: the softmax starts from the same
    NEG_INF floor and its answer goes straight to the output block."""
    from jax.experimental import pallas as pl

    q_ref, k_ref, v_ref, mask_ref, o_ref, *scratch = refs
    rows, bq, width = q_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, LANES), 1)
    # head j's lanes of a tile, [bq, 128]
    of_head = [lane // (LANES // group) == j for j in range(group)]

    def tiles():
        for t in range(width // LANES):
            yield t, slice(t * LANES, (t + 1) * LANES)

    def write(i, lanes, acc, l_sum):
        out = acc / jnp.maximum(l_sum, 1e-30)  # [group*bq, 128]
        merged = out[:bq]
        for j in range(1, group):
            merged = jnp.where(of_head[j], out[j * bq : (j + 1) * bq], merged)
        o_ref[i, :, lanes] = merged.astype(o_ref.dtype)

    def block(i, carry):
        mask = mask_ref[i]  # [1, bk] broadcasts over the stacked q rows
        for t, lanes in tiles():
            q = q_ref[i, :, lanes].astype(jnp.float32)  # [bq, 128]
            k = k_ref[i, :, lanes].astype(jnp.float32)  # [bk, 128]
            v = v_ref[i, :, lanes].astype(jnp.float32)  # [bk, 128]
            q = jnp.concatenate(
                [jnp.where(lanes_j, q, 0.0) for lanes_j in of_head]
            )  # [group*bq, 128]
            s = (
                jax.lax.dot_general(
                    q,
                    k,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [group*bq, bk]
            s = s + mask
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            if scratch:
                # lanes of m_ref/l_ref all hold the same per-row value
                m_prev = jnp.max(m_ref[i, t], axis=-1, keepdims=True)
                l_prev = jnp.max(l_ref[i, t], axis=-1, keepdims=True)
                m_new = jnp.maximum(m_prev, m_cur)
                alpha = jnp.exp(m_prev - m_new)
            else:
                m_new = jnp.maximum(m_cur, NEG_INF)
            p = jnp.exp(s - m_new)
            l_cur = jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p,
                v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [group*bq, 128]
            if scratch:
                acc_ref[i, t] = acc_ref[i, t] * alpha + pv
                m_ref[i, t] = jnp.broadcast_to(m_new, m_ref.shape[2:])
                l_ref[i, t] = jnp.broadcast_to(
                    l_prev * alpha + l_cur, l_ref.shape[2:]
                )
            else:
                write(i, lanes, pv, l_cur)
        return carry

    if scratch:
        m_ref, l_ref, acc_ref = scratch
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    jax.lax.fori_loop(0, rows, block, None)

    if scratch:

        @pl.when(ki == nk - 1)
        def _finalize():
            def row(i, carry):
                for t, lanes in tiles():
                    write(
                        i,
                        lanes,
                        acc_ref[i, t],
                        jnp.max(l_ref[i, t], axis=-1, keepdims=True),
                    )
                return carry

            jax.lax.fori_loop(0, rows, row, None)


def _packed_rows(batch: int, nk: int) -> int:
    """Rows of the batch a grid step of the packed kernel takes, as the
    chip chose (PERF.md, PR 29: at [2048, 128, 768] float32 two rows a
    step beat one by 1.7% and four gain 0.7% more but overflow the
    default scoped VMEM once there is scratch; with the scratch of two
    or four key blocks one row beat two by 3-7%): two where there is one
    key block and the batch is even, else one."""
    return 2 if nk == 1 and batch % 2 == 0 else 1


def flash_attention_packed(
    q,
    k,
    v,
    mask: Optional[jax.Array] = None,
    *,
    num_heads: int,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """:func:`flash_attention` for heads narrower than the lane width,
    read where the projections wrote them.

    Args:
        q: [B, L, H*Dh]. k, v: [B, Lk, H*Dh], the same H heads of Dh
            side by side on the last axis, with ``packs(H, Dh)``.
        mask: additive key mask, as :func:`flash_attention` takes it.

    Returns [B, L, H*Dh] in q's dtype. The same products at the same
    precision as the blocked kernel; nothing is padded or transposed in
    HBM (a length off the block size is padded as there)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, L, D = q.shape
    Lk = k.shape[1]
    head_dim = D // num_heads
    if D % num_heads or not packs(num_heads, head_dim):
        raise ValueError(
            f"{num_heads} heads over a width of {D} do not pack into "
            f"{LANES}-lane tiles: use flash_attention"
        )
    if k.shape != (B, Lk, D) or v.shape != k.shape:
        raise ValueError(
            f"packed q {q.shape} wants k and v [B, Lk, {D}], got "
            f"{k.shape} and {v.shape}"
        )
    mask2d = _key_mask(mask, B, Lk)
    pq, pk = _pad_len(L, block_q), _pad_len(Lk, block_k)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
        mask2d = jnp.pad(mask2d, ((0, 0), (0, pk)), constant_values=NEG_INF)
    nq, nk = (L + pq) // block_q, (Lk + pk) // block_k
    rows = _packed_rows(B, nk)
    group = LANES // head_dim
    stacked = (rows, D // LANES, group * block_q, LANES)
    out = pl.pallas_call(
        functools.partial(
            _packed_kernel, nk, 1.0 / np.sqrt(head_dim), group
        ),
        grid=(B // rows, nq, nk),
        in_specs=[
            pl.BlockSpec((rows, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((rows, block_k, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((rows, block_k, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((rows, 1, block_k), lambda b, qi, ki: (b, 0, ki)),
        ],
        out_specs=pl.BlockSpec(
            (rows, block_q, D), lambda b, qi, ki: (b, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, L + pq, D), q.dtype),
        # running max, running sum, output accumulator
        scratch_shapes=[pltpu.VMEM(stacked, jnp.float32)] * 3 * (nk > 1),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        # the blocked kernel's name: one kernel to the trace's readers
        name="flash_attention",
    )(q, k, v, mask2d[:, None, :])
    return out[:, :L]


#: Key columns of one sub-tile of the latent kernel's step (a block of
#: ``block`` keys is walked ``block // LATENT_KEY_TILE`` sub-tiles; a block
#: that is no multiple of it is one sub-tile). Chosen on the chip from
#: {128, 256, 512} at the four shapes the cells run: PERF.md, PR 35.
LATENT_KEY_TILE = 256


def _key_tile(block: int) -> int:
    return LATENT_KEY_TILE if block % LATENT_KEY_TILE == 0 else block


def latent_pairs_computed(length: int, block: int, tile: int) -> int:
    """(query, key) pairs the latent kernel runs for one head of one row
    of ``length`` tokens in blocks of ``block`` walked in key sub-tiles of
    ``tile``: every block under the diagonal whole, and of a diagonal
    block sub-tile j against the query rows from j * tile on."""
    n = -(-length // block)
    sub = block // tile
    return n * (n - 1) // 2 * block * block + n * tile * tile * sub * (sub + 1) // 2


def _latent_kernel(nk, scale, nope, selected, heads, *refs):
    """The blocked causal kernel for latent attention, one (row, head) a
    grid step: the query block is [bq, nope + rope], the head's own keys
    [bk, nope] and the token's shared rotary key [bk, rope]; a score is
    the sum of the two products. Key blocks above the diagonal are
    skipped; right padding needs no mask under a causal one. With
    ``selected`` a fifth operand is the block [bq, bk] of a selection
    (int8, nonzero where the query attends to the key, zero above the
    diagonal): it takes the causal mask's place, before the running
    maximum.

    ``heads``: None, or the head count of a call that knows its rows'
    lengths: the first ref is then the prefetched [B] int32 of how many
    leading query blocks of each row are *live*, hold a real token (a
    grid step's row is its first index over ``heads``). The others are
    *dead*: padding alone, which no real token reads. A dead block runs
    nothing at any key step and, at its last, writes zeros itself. A live
    block, the one that holds the row's last real token too, runs whole,
    as without lengths.

    A fetched block is walked in key sub-tiles of ``_key_tile(bk)``
    columns. Under the diagonal every sub-tile meets every query row, and
    without a selection nothing is masked there. On the diagonal sub-tile
    j meets the query rows from j * tile on (those above see none of its
    keys), and the causal compare falls on the tile x tile square the
    diagonal crosses alone; a selection's bytes are applied wherever a
    sub-tile runs."""
    from jax.experimental import pallas as pl

    if heads:
        live_ref, *refs = refs
    q_ref, k_ref, v_ref, kr_ref, *refs = refs
    if selected:
        sel_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    qi, ki = pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[1]
    tile = _key_tile(bq)
    live = qi < live_ref[jax.lax.div(pl.program_id(0), heads)] if heads else None

    def when(step):
        return pl.when(step if live is None else jnp.logical_and(step, live))

    @when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _sub_tile(j, rows, crossed):
        """Key sub-tile j against the query rows ``rows``; ``crossed``:
        the first ``tile`` of them are the square the diagonal crosses."""
        cols = slice(j * tile, (j + 1) * tile)
        contract = (((1,), (1,)), ((), ()))
        q = q_ref[0, rows].astype(jnp.float32)  # [rows, nope + rope]
        s = jax.lax.dot_general(
            q[:, :nope], k_ref[0, cols].astype(jnp.float32), contract,
            preferred_element_type=jnp.float32,
        ) + jax.lax.dot_general(
            q[:, nope:], kr_ref[0, cols].astype(jnp.float32), contract,
            preferred_element_type=jnp.float32,
        )
        s = s * scale
        if selected:
            s = jnp.where(sel_ref[0, rows, cols].astype(jnp.int32) != 0, s, NEG_INF)
        elif crossed:
            row = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
            square = jnp.where(col <= row, s[:tile], NEG_INF)
            s = jnp.concatenate([square, s[tile:]]) if s.shape[0] > tile else square
        _softmax_step(
            s, v_ref[0, cols].astype(jnp.float32), m_ref, l_ref, acc_ref, rows
        )

    @when(ki < qi)
    def _under_the_diagonal():
        for j in range(bq // tile):
            _sub_tile(j, slice(None), False)

    @when(ki == qi)
    def _diagonal():
        for j in range(bq // tile):
            _sub_tile(j, slice(j * tile, bq), True)

    @when(ki == nk - 1)
    def _finalize():
        _write_result(o_ref, l_ref, acc_ref)

    if heads:

        @pl.when(jnp.logical_and(ki == nk - 1, jnp.logical_not(live)))
        def _dead():
            o_ref[...] = jnp.zeros_like(o_ref)


def flash_attention_latent(
    q,
    kv,
    k_rope,
    selection=None,
    lengths=None,
    *,
    num_heads: int,
    scale: float,
    block: int = 128,
    interpret: bool = False,
):
    """Causal latent attention (MLA) over arrays as the projections wrote
    them: nothing is transposed, concatenated, repeated or padded in HBM
    but a length off the block size.

    Args:
        q: [B, L, H * (Dn + Dr)], a head's query [nope Dn | rope Dr].
        kv: [B, L, H * (Dn + Dv)], a head's [key nope Dn | value Dv] side
            by side, as the up-projection of the key/value latent writes
            them; Dn == Dv, so that a head's keys and values are column
            blocks 2h and 2h + 1 of the one array, each read in place.
        k_rope: [B, L, Dr], a token's rotary key, shared by all heads:
            every head's step reads the same block.
        selection: None, or [B, L, L] int8, nonzero where query t
            attends to key s and zero for every s > t (sparse attention
            by a learned selection, ``ops/dsa_indexer.py``): one
            selection for all heads, a byte a pair, read a block a step
            in the causal mask's place. Every query selects a key.
        lengths: None, or [B] int32: how many leading positions of each
            row hold its real tokens, the rest right padding (0 for a
            row of padding). A real query's answer needs no length: right
            padding needs no mask under a causal one. What a length saves
            is the padding's own queries: a query block that starts at or
            past its row's length is neither fetched nor run, and its
            positions of the result are zeros. None: every block runs,
            through the call without the operand (no prefetch, index maps
            that read nothing): what a caller whose rows are all whole
            wants, since the maps' reads of the live count cost a
            full-length call 2-3% at the cells' shapes and 12% at
            8 x 1,024 (PERF.md, PR 37). Both DeepSeek models always hand
            lengths; None is the yardstick the kernel with lengths is
            held to bit for bit, in the tests and timed alone.
        On the TPU Dn, Dr and Dv are multiples of the 128 lanes (a
        block's last dim); the interpreter takes any.

    A score is (q_nope . k_nope + q_rope . k_rope) * ``scale``. Returns
    [B, L, H * Dv] in q's dtype. The blocked kernel's algorithm and
    name; grid (B * H, L / block, L / block), the last axis sequential.
    A step fetches one [block, block] pair of a query and a key block
    (none above the diagonal) and walks it in key sub-tiles of
    ``LATENT_KEY_TILE`` columns (``block`` a multiple of it; any other
    ``block`` is one sub-tile). A block under the diagonal runs every
    sub-tile against every query row, unmasked but for a selection's
    bytes. A diagonal block runs sub-tile j against the query rows from
    j * tile on, (n + 1) / 2n of its pairs with n sub-tiles, and masks
    the tile x tile squares the diagonal crosses alone (with a selection,
    its bytes wherever a sub-tile runs): a pair left out is one whose
    probability is zero. :func:`latent_pairs_computed` counts the pairs.

    With ``lengths`` each row's count of live query blocks (those that
    start before its length) is a scalar-prefetch operand, which the
    index maps read too: every step of a dead query block names the
    blocks the row's last live one left resident (block 0 for a row of
    padding), so nothing is copied for it, and it costs what a step above
    the diagonal costs. A live block, the one that holds the row's last
    real token too, runs the steps it runs without lengths, in their
    order: a real query's answer is the same to the bit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, L, wide = q.shape
    H, rope = num_heads, k_rope.shape[2]
    nope = wide // H - rope
    dv = kv.shape[2] // H - nope
    if (
        wide % H or kv.shape[2] % H or nope != dv
        or kv.shape[:2] != (B, L) or k_rope.shape[:2] != (B, L)
    ):
        raise ValueError(
            f"latent attention over {H} heads wants q [B, L, H*(Dn+Dr)], "
            f"kv [B, L, H*(Dn+Dv)] with Dn == Dv and k_rope [B, L, Dr]; "
            f"got {q.shape}, {kv.shape}, {k_rope.shape}"
        )
    if selection is not None and selection.shape != (B, L, L):
        raise ValueError(
            f"a selection for q {q.shape} is [B, L, L], got {selection.shape}"
        )
    if lengths is not None and (lengths.shape != (B,) or lengths.dtype != jnp.int32):
        raise ValueError(
            f"lengths for q {q.shape} is [B] int32, got {lengths.shape} {lengths.dtype}"
        )
    pad = _pad_len(L, block)
    if pad:
        q, kv, k_rope = (
            jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (q, kv, k_rope)
        )
    n = (L + pad) // block

    def last_live(bh, live):
        """The last live query block of the step's row; 0 for a row of
        padding. ``live``: each row's count of live query blocks, the
        index maps' last argument where there are lengths."""
        return jnp.maximum(live[bh // H], 1) - 1

    def queries(bh, qi, *live):  # a dead block is not fetched
        return jnp.minimum(qi, last_live(bh, *live)) if live else qi

    def keys(bh, qi, ki, *live):
        diagonal = jnp.minimum(ki, qi)  # a block above it is not fetched
        if not live:
            return diagonal
        last = last_live(bh, *live)
        return jnp.where(qi > last, last, diagonal)

    in_specs = [
        pl.BlockSpec(
            (1, block, nope + rope),
            lambda bh, qi, ki, *live: (bh // H, queries(bh, qi, *live), bh % H),
        ),
        pl.BlockSpec(
            (1, block, nope),
            lambda bh, *step: (bh // H, keys(bh, *step), 2 * (bh % H)),
        ),
        pl.BlockSpec(
            (1, block, dv),
            lambda bh, *step: (bh // H, keys(bh, *step), 2 * (bh % H) + 1),
        ),
        pl.BlockSpec((1, block, rope), lambda bh, *step: (bh // H, keys(bh, *step), 0)),
    ]
    operands = [q, kv, kv, k_rope]
    if selection is not None:
        if pad:
            # a padded query attends to key 0, so that its row has a maximum
            selection = jnp.pad(selection, ((0, 0), (0, pad), (0, pad)))
            selection = selection.at[:, L:, 0].set(1)
        in_specs.append(
            pl.BlockSpec(
                (1, block, block),
                lambda bh, qi, ki, *live: (
                    bh // H, queries(bh, qi, *live), keys(bh, qi, ki, *live)
                ),
            )
        )
        operands.append(selection)

    grid = dict(
        grid=(B * H, n, n),
        in_specs=in_specs,
        # the result's map alone keeps a dead block's own index: its zeros
        out_specs=pl.BlockSpec(
            (1, block, dv), lambda bh, qi, ki, *live: (bh // H, qi, bh % H)
        ),
        scratch_shapes=[
            pltpu.VMEM((block, 128), jnp.float32),  # running max
            pltpu.VMEM((block, 128), jnp.float32),  # running sum
            pltpu.VMEM((block, dv), jnp.float32),  # output accumulator
        ],
    )
    if lengths is not None:
        grid = dict(
            grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, **grid)
        )
        operands.insert(0, (lengths + (block - 1)) // block)
    out = pl.pallas_call(
        functools.partial(
            _latent_kernel, n, scale, nope, selection is not None,
            H if lengths is not None else None,
        ),
        out_shape=jax.ShapeDtypeStruct((B, L + pad, H * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        # the blocked kernel's name: one kernel to the trace's readers
        name="flash_attention",
        **grid,
    )(*operands)
    return out[:, :L]


def dense_latent_attention(
    q, kv, k_rope, dtype, selection=None, *, num_heads: int, scale: float
):
    """What :func:`flash_attention_latent` computes, as dense einsums over
    the same arrays: float32 scores and softmax. The build-time fallback
    off the TPU and the kernel's test oracle."""
    B, L, _ = q.shape
    rope = k_rope.shape[2]
    q = q.reshape(B, L, num_heads, -1)
    nope = q.shape[3] - rope
    kv = kv.reshape(B, L, num_heads, -1)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope],
        preferred_element_type=jnp.float32,
    ) + jnp.einsum(
        "bqhd,bkd->bhqk", q[..., nope:], k_rope, preferred_element_type=jnp.float32
    )
    seen = jnp.tril(jnp.ones((L, L), bool))
    if selection is not None:
        seen = (selection != 0)[:, None]
    s = jnp.where(seen, s * scale, NEG_INF)
    p = jax.nn.softmax(s, -1).astype(dtype)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", p, kv[..., nope:], preferred_element_type=jnp.float32
    )
    return o.reshape(B, L, -1).astype(dtype)


def make_latent_attention_fn(
    num_heads: int, scale: float, block: int = 128, interpret: bool = False
):
    """The latent attention a model is BUILT with, ``fn(q, kv, k_rope,
    dtype, selection=None)`` over the projections' own arrays: the Pallas
    kernel on TPU (or interpreted when asked),
    :func:`dense_latent_attention` elsewhere. ``.kind`` ('flash' |
    'dense') says which, and ``.pairs_computed(length)`` how many (query,
    key) pairs it runs for a head of one row of that length: the square
    for the dense one, :func:`latent_pairs_computed` for the kernel.

    The kernel's also takes ``lengths=None`` ([B] int32, each row's
    leading real positions) and runs no query block of padding alone:
    its ``.takes_lengths`` is True, and ``.query_blocks(length)`` is how
    many query blocks a row of that length has. The dense one has neither
    attribute: a caller hands lengths only to an attention that says it
    takes them."""
    if not interpret and jax.default_backend() != "tpu":
        dense = functools.partial(
            dense_latent_attention, num_heads=num_heads, scale=scale
        )
        dense.kind = "dense"
        dense.pairs_computed = lambda length: length * length
        return dense

    def attention(q, kv, k_rope, dtype, selection=None, lengths=None):
        out = flash_attention_latent(
            q, kv, k_rope, selection, lengths, num_heads=num_heads, scale=scale,
            block=block, interpret=interpret,
        )
        return out.astype(dtype)

    attention.kind = "flash"
    attention.takes_lengths = True
    attention.query_blocks = lambda length: -(-length // block)
    attention.pairs_computed = lambda length: latent_pairs_computed(
        length, block, _key_tile(block)
    )
    return attention


def dense_causal_attention(q, k, v, mask, dtype, window=None):
    """What ``flash_attention(causal=True, window=window)`` computes, as
    dense einsums: q [B, H, L, Dh] over k, v [B, Hkv, L, Dh], float32
    scores and softmax, an optional additive key mask; with ``window``,
    query i sees key j iff 0 <= i - j < window. The build-time fallback
    off the TPU and the kernel's test oracle."""
    B, H, L, Dh = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(B, Hkv, H // Hkv, L, Dh)
    s = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) / np.sqrt(Dh)
    if mask is not None:
        s = s + mask.reshape(B, 1, 1, 1, L).astype(jnp.float32)
    seen = jnp.tril(jnp.ones((L, L), bool))
    if window is not None:
        seen = seen & ~jnp.tril(seen, -window)
    s = jnp.where(seen, s, NEG_INF)
    p = jax.nn.softmax(s, -1).astype(dtype)
    o = jnp.einsum(
        "bhgqk,bhkd->bhgqd", p, v, preferred_element_type=jnp.float32
    )
    return o.reshape(B, H, L, Dh).astype(dtype)


dense_causal_attention.kind = "dense"


def make_flash_attention_fn(
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    causal: bool = False,
    num_heads: Optional[int] = None,
    head_dim: Optional[int] = None,
    window: Optional[int] = None,
):
    """Returns an attention fn with the ``dense_attention`` signature
    (q, k, v, mask, dtype) — drop-in for BertEncoder(attention_fn=...).

    The choice is made HERE, at build time, from the process's default
    backend: the Pallas kernel on TPU (or interpreted when asked — never
    derived from the backend), ``dense_attention`` itself elsewhere
    (``dense_causal_attention`` for ``causal``) so CPU meshes keep
    working. Either way the returned function's ``.kind`` ('flash' |
    'dense') says which, and nothing downstream re-decides: a kernel
    that fails to compile raises.

    Which kernel is chosen here too, from the shape a builder knows:
    where ``packs(num_heads, head_dim)`` and not ``causal``, the
    function takes q, k, v as the projections wrote them, [B, L, H*Dh],
    and the head count (``num_heads=``), runs
    :func:`flash_attention_packed` and returns [B, L, H*Dh]; its
    ``.layout`` is 'packed'. Every other shape, and a caller that names
    none, gets the blocked kernel over [B, H, L, Dh] ('heads', what a
    function without ``.layout`` takes). ``window`` (with ``causal``):
    the sliding-window kernel, ``flash_attention_window``, and off the TPU
    ``dense_causal_attention`` with that window.

    The blocked kernel's causal and window functions also take
    ``lengths=None`` ([B] int32, each row's leading real positions) and
    run no query block of padding alone: their ``.takes_lengths`` is
    True, and ``.query_blocks(length)`` is how many query blocks a row of
    that length has. The dense fallbacks have neither attribute: a caller
    hands lengths only to an attention that says it takes them."""
    if not interpret and jax.default_backend() != "tpu":
        if window is not None:
            windowed = functools.partial(dense_causal_attention, window=window)
            windowed.kind = "dense"
            return windowed
        if causal:
            return dense_causal_attention
        from sparkdl_tpu.models.bert import dense_attention

        return dense_attention

    if not causal and packs(num_heads, head_dim):

        def packed_attention(q, k, v, mask, dtype, *, num_heads):
            out = flash_attention_packed(
                q,
                k,
                v,
                mask,
                num_heads=num_heads,
                block_q=block_q,
                block_k=block_k,
                interpret=interpret,
            )
            return out.astype(dtype)

        packed_attention.kind = "flash"
        packed_attention.layout = "packed"
        return packed_attention

    def attention(q, k, v, mask, dtype, lengths=None):
        out = flash_attention(
            q,
            k,
            v,
            mask,
            block_q=block_q,
            block_k=block_k,
            interpret=interpret,
            causal=causal,
            window=window,
            lengths=lengths,
        )
        return out.astype(dtype)

    attention.kind = "flash"
    if causal:
        attention.takes_lengths = True
        attention.query_blocks = lambda length: -(-length // block_q)
    return attention
