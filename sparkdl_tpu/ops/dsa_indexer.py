"""The lightning indexer of DeepSeek sparse attention (DSA): which keys
each query attends to.

    I[t, s] = sum_j w[t, j] * ReLU(q[t, j] . k[s])        s <= t
    S_t     = the min(top_k, t + 1) keys s <= t of largest I[t, s],
              of equal scores the lower s first

``q`` [B, L, H * D] holds a token's H index queries side by side, as
their projection wrote them, ``k`` [B, L, D] its ONE index key (every
index head reads the same), ``w`` [B, L, H] float32 the heads' weights.
The products take the operands as they are given (bfloat16 in a
bfloat16 model) and accumulate in float32; ReLU, the weights and the sum
over heads are float32.

Two steps, each a Pallas kernel on the TPU and plain ``jax.numpy``
elsewhere, chosen at build time by :func:`make_indexer_fn` (``.kind``):

- :func:`dsa_index_scores` (plain: :func:`index_scores`) writes I
  [B, L, L] float32 and nothing else: the H per-head score tiles live in
  VMEM only (H x L x L float32 would be 68.7 GB a row of 16,384). Grid
  (B, L / bq, L / bk); key blocks above the diagonal are neither fetched
  nor computed, and what I holds above the diagonal is unspecified.
- :func:`dsa_select` (plain: :func:`select_keys`) turns I into the
  selection [B, L, L] int8 (1 where s is in S_t; 0 above the diagonal),
  exactly: a score is mapped to the integer that orders as it does, a
  32-step search finds each query's ``top_k``-th largest, bit by bit
  from the top (the count of keys at or above a candidate says whether
  the bit stays), and of the keys equal to it the lowest positions are
  taken by a second search over the position, made only where a tie is.
  No sort, no approximate ``top_k``. The kernel keeps a block of
  queries' scores in VMEM for all of its searches; grid (B, L / bq), and
  a query block looks at the keys up to its own end. On a v5e at one row
  of 16,384 with ``top_k`` 2,048 (PERF.md, PR 34): the scores 13.1 ms,
  the kernel's selection 7.2 ms, the plain form compiled by XLA 13.9,
  and ``lax.top_k`` over the masked rows 239.

Given each row's length (``lengths`` [B] int32, its last real position
+ 1; the kernels' indexer says ``.takes_lengths``), both kernels run no
query block of padding alone: each row's count of live query blocks,
those that start before its length, is a scalar-prefetch operand, and a
dead block's steps name blocks already resident. A live block, the one
that holds the row's last real token too, runs as without lengths, so
every real query's scores and selection are the same to the bit. Past
the live blocks I is unspecified and never read, and each query of a
dead selection block selects itself alone: no row of the selection is
ever empty, whatever attention reads it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_INT_MIN = np.int32(-(2**31))
#: the index-scores kernel's query block: the unit of ``.query_blocks``
SCORES_BLOCK_Q = 256
#: what the select kernel may hold in VMEM: two buffers of a [bq, L]
#: float32 block of scores, its int8 result, and the ordered integers
_SELECT_VMEM_LIMIT_BYTES = 96 << 20


def index_scores(q, k, w, *, num_heads: int):
    """I [B, L, L] float32 by dense einsums, the per-head scores written
    out: the build-time fallback off the TPU and the kernel's oracle."""
    B, L, _ = q.shape
    qh = q.reshape(B, L, num_heads, -1)
    s = jnp.einsum("bqhd,bkd->bhqk", qh, k, preferred_element_type=jnp.float32)
    return jnp.einsum("bhqk,bqh->bqk", jnp.maximum(s, 0.0), w.astype(jnp.float32))


def _scores_kernel(heads, dim, q_ref, k_ref, w_ref, o_ref, live=None):
    """One step: the key block's scores for the query block, summed over
    the heads; a block above the diagonal computes nothing, nor, in a
    call with lengths (``live``: whether the query block is live, a
    traced bool), does a dead query block."""
    from jax.experimental import pallas as pl

    qi, ki = pl.program_id(1), pl.program_id(2)
    bq, bk = o_ref.shape[1:]
    needed = ki * bk < (qi + 1) * bq

    @pl.when(needed if live is None else jnp.logical_and(needed, live))
    def _block():
        k = k_ref[0]  # [bk, D]
        w = w_ref[0]  # [bq, H] float32
        acc = jnp.zeros((bq, bk), jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(
                q_ref[0, :, j * dim : (j + 1) * dim], k,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )
            acc = acc + jnp.maximum(s, 0.0) * w[:, j : j + 1]
        o_ref[0] = acc


def _scores_by_length(heads, dim, live_ref, *refs):
    """:func:`_scores_kernel` in a call that knows its rows' lengths:
    ``live_ref`` the prefetched [B] int32 of each row's count of live
    query blocks."""
    from jax.experimental import pallas as pl

    live = pl.program_id(1) < live_ref[pl.program_id(0)]
    _scores_kernel(heads, dim, *refs, live=live)


def _live_blocks(lengths, block: int):
    """[B] int32 lengths -> each row's count of *live* query blocks of
    ``block`` queries: those that start before its length."""
    return (lengths + (block - 1)) // block


def _last_live(live, b):
    """Row ``b``'s last live query block (block 0 for a row of padding),
    ``live`` the index maps' prefetched counts: a dead block's steps name
    the blocks it left resident, so that nothing is fetched for them."""
    return jnp.maximum(live[b], 1) - 1


def _check_lengths(lengths, rows: int):
    if lengths is not None and (lengths.shape != (rows,) or lengths.dtype != jnp.int32):
        raise ValueError(
            f"lengths for {rows} rows are [{rows}] int32, got "
            f"{lengths.shape} {lengths.dtype}"
        )


def dsa_index_scores(
    q, k, w, lengths=None, *, num_heads: int, block_q: int = SCORES_BLOCK_Q,
    block_k: int = 512, interpret: bool = False,
):
    """I [B, L, L] float32, the per-head scores never in HBM. ``block_k``
    a multiple of ``block_q`` or the reverse; L is padded to both.

    ``lengths`` [B] int32 (a row's last real position + 1, 0 for a row
    of padding): each row's count of live query blocks is a
    scalar-prefetch operand, a dead query block computes nothing and
    fetches nothing, and what I holds in its rows is unspecified."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, L, wide = q.shape
    dim = wide // num_heads
    if wide % num_heads or k.shape != (B, L, dim) or w.shape != (B, L, num_heads):
        raise ValueError(
            f"index scores over {num_heads} heads want q [B, L, H*D], k "
            f"[B, L, D] and w [B, L, H]; got {q.shape}, {k.shape}, {w.shape}"
        )
    _check_lengths(lengths, B)
    pad = -L % max(block_q, block_k)
    if pad:
        q, k, w = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (q, k, w))
    n = L + pad
    if n % block_q or n % block_k:
        raise ValueError(f"blocks {block_q} x {block_k} do not tile {n}")

    def last_needed(qi):  # the key block that holds the query block's end
        return ((qi + 1) * block_q - 1) // block_k

    def step(b, qi, ki, *live):
        """The query block and key step a grid step names: in a dead
        query block the last step of the row's last live one."""
        if not live:
            return qi, ki
        last = _last_live(live[0], b)
        dead = qi > last
        return jnp.where(dead, last, qi), jnp.where(dead, n // block_k - 1, ki)

    def key_block(b, qi, ki, *live):
        qi, ki = step(b, qi, ki, *live)
        return jnp.minimum(ki, last_needed(qi))

    def query_block(b, qi, ki, *live):
        return step(b, qi, ki, *live)[0]

    grid = dict(
        grid=(B, n // block_q, n // block_k),
        in_specs=[
            pl.BlockSpec(
                (1, block_q, wide), lambda b, *at: (b, query_block(b, *at), 0)
            ),
            pl.BlockSpec((1, block_k, dim), lambda b, *at: (b, key_block(b, *at), 0)),
            pl.BlockSpec(
                (1, block_q, num_heads), lambda b, *at: (b, query_block(b, *at), 0)
            ),
        ],
        # a skipped step names the block before it: nothing is written twice
        out_specs=pl.BlockSpec(
            (1, block_q, block_k),
            lambda b, *at: (b, query_block(b, *at), key_block(b, *at)),
        ),
    )
    kernel = functools.partial(_scores_kernel, num_heads, dim)
    operands = [q, k, w]
    if lengths is not None:
        kernel = functools.partial(_scores_by_length, num_heads, dim)
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, **grid))
        operands.insert(0, _live_blocks(lengths, block_q))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, n, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        # a stable name for the kernel's events in a profiler trace
        name="dsa_index_scores",
        **grid,
    )(*operands)
    return out[:, :L, :L]


# -- the selection ------------------------------------------------------------


def _ordered(scores):
    """float32 -> int32 that orders as the scores do (negative values'
    magnitudes flipped; -0.0 is 0.0, as it compares)."""
    scores = jnp.where(scores == 0.0, 0.0, scores)
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)


def _search_bits(count_at_least, wanted, bits: int, floor):
    """The largest int32 ``t >= floor`` with ``count_at_least(t) >=
    wanted``, found bit by bit from the top (``floor`` has the low
    ``bits`` bits clear); elementwise over ``wanted``."""

    def step(i, t):
        cand = t + jnp.left_shift(np.int32(1), np.int32(bits - 1) - i)
        return jnp.where(count_at_least(cand) >= wanted, cand, t)

    return jax.lax.fori_loop(0, bits, step, floor)


def select_rows(scores, first_query: int, top_k: int):
    """The selection of queries ``first_query ..`` over keys ``0 ..``:
    scores [B, Q, K] float32 -> [B, Q, K] bool. Plain ``jax.numpy``: the
    fallback, the oracle, and a body XLA compiles well enough to time
    against the kernel."""
    B, Q, K = scores.shape
    t = first_query + jnp.arange(Q, dtype=jnp.int32)[:, None]
    s = jnp.arange(K, dtype=jnp.int32)[None, :]
    # a key above the diagonal orders below every score (-inf is above it)
    key = jnp.where(s <= t, _ordered(scores), _INT_MIN)
    wanted = jnp.minimum(top_k, t + 1)  # [Q, 1]

    def count(cond):
        return jnp.sum(cond, -1, keepdims=True, dtype=jnp.int32)

    # the sign bit first: are there `wanted` keys at or above zero
    floor = jnp.where(count(key >= 0) >= wanted, np.int32(0), _INT_MIN)
    kth = _search_bits(lambda c: count(key >= c), wanted, 31, floor)
    above = key > kth
    equal = key == kth
    short = wanted - count(above)  # how many of the equal ones, >= 1

    def lowest():
        # the largest position with fewer than `short` equal keys before it
        bits = max(1, int(K - 1).bit_length())
        return _search_bits(
            lambda c: short - count(equal & (s < c)), np.int32(1), bits,
            jnp.zeros_like(short),
        )

    # ties at a query's last place are rare: the second search runs only
    # where some query has more equal keys than places for them
    last = jax.lax.cond(
        jnp.any(count(equal) > short), lowest, lambda: jnp.full_like(short, K)
    )
    return above | (equal & (s <= last))


def select_keys(scores, *, top_k: int, block_q: int = 2048):
    """I [B, L, L] float32 -> the selection [B, L, L] int8 by
    :func:`select_rows`, a block of queries at a time over the keys up to
    the block's end; queries that see no more than ``top_k`` keys select
    their whole causal row and are searched for nothing."""
    B, L, _ = scores.shape
    block_q = min(block_q, L)
    parts = []
    for lo in range(0, L, block_q):
        hi = min(lo + block_q, L)
        if hi <= top_k:
            t = jnp.arange(lo, hi)[:, None]
            part = jnp.broadcast_to(jnp.arange(hi)[None, :] <= t, (B, hi - lo, hi))
        else:
            part = select_rows(scores[:, lo:hi, :hi], lo, top_k)
        parts.append(jnp.pad(part.astype(jnp.int8), ((0, 0), (0, 0), (0, L - hi))))
    return jnp.concatenate(parts, 1)


def _select_kernel(top_k, chunk, s_ref, o_ref, key_ref):
    """One block of queries, the grid's second index:
    :func:`_select_block`."""
    from jax.experimental import pallas as pl

    _select_block(top_k, chunk, pl.program_id(1), s_ref, o_ref, key_ref)


def _select_by_length(top_k, chunk, live_ref, s_ref, o_ref, key_ref):
    """:func:`_select_kernel` in a call that knows its rows' lengths
    (``live_ref``: each row's count of live query blocks, prefetched). A
    live block runs :func:`_select_block`; a dead one, padding alone,
    reads no score, runs no search and writes for each of its queries
    the query itself alone, so that no row of the selection is empty."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    live = qi < live_ref[pl.program_id(0)]
    pl.when(live)(lambda: _select_block(top_k, chunk, qi, s_ref, o_ref, key_ref))

    @pl.when(jnp.logical_not(live))
    def _dead():
        bq, length = key_ref.shape
        t = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, chunk), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (bq, chunk), 1)

        def write(c, carry):
            at = pl.multiple_of(c * chunk, chunk)
            itself = jnp.where(at + lane == t, np.int32(1), np.int32(0))
            o_ref[0, :, pl.ds(at, chunk)] = itself.astype(jnp.int8)
            return carry

        jax.lax.fori_loop(0, length // chunk, write, None)


def _select_block(top_k, chunk, qi, s_ref, o_ref, key_ref):
    """Query block ``qi``: its scores [bq, L] become ordered integers in
    VMEM once; every search step counts over the key chunks up to the
    block's end."""
    from jax.experimental import pallas as pl

    bq, length = key_ref.shape
    # key chunks that hold a key any query of the block may see
    chunks = ((qi + 1) * bq + chunk - 1) // chunk
    t = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, chunk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, chunk), 1)
    wanted = jnp.minimum(top_k, t[:, :1] + 1)  # [bq, 1]

    def fill(c, carry):
        at = pl.multiple_of(c * chunk, chunk)
        key = _ordered(s_ref[0, :, pl.ds(at, chunk)])
        key_ref[:, pl.ds(at, chunk)] = jnp.where(at + lane <= t, key, _INT_MIN)
        return carry

    jax.lax.fori_loop(0, chunks, fill, None)

    def count(cond):
        """cond(key chunk, first position of the chunk) -> bool [bq,
        chunk]; the count over the block's keys, [bq, 1]."""

        def one(c, acc):
            at = pl.multiple_of(c * chunk, chunk)
            hit = cond(key_ref[:, pl.ds(at, chunk)], at)
            return acc + jnp.where(hit, 1.0, 0.0)

        # at most L to a query: exact in float32, which the lanes sum in
        acc = jax.lax.fori_loop(0, chunks, one, jnp.zeros((bq, chunk), jnp.float32))
        return jnp.sum(acc, -1, keepdims=True).astype(jnp.int32)

    floor = jnp.where(
        count(lambda key, at: key >= 0) >= wanted, np.int32(0), _INT_MIN
    )
    kth = _search_bits(
        lambda c: count(lambda key, at: key >= c), wanted, 31, floor
    )
    short = wanted - count(lambda key, at: key > kth)
    bits = max(1, int(length - 1).bit_length())
    # the second search costs half of the first again; a block without a
    # tie at any query's last place skips it
    tied = jnp.max(count(lambda key, at: key == kth) - short) > 0
    last = jax.lax.cond(
        tied,
        lambda: _search_bits(
            lambda c: short - count(lambda key, at: (key == kth) & (at + lane < c)),
            np.int32(1), bits, jnp.zeros_like(short),
        ),
        lambda: jnp.full_like(short, length),
    )

    def write(c, carry):
        at = pl.multiple_of(c * chunk, chunk)
        key = key_ref[:, pl.ds(at, chunk)]
        chosen = (key > kth) | ((key == kth) & (at + lane <= last))
        chosen = jnp.where(chosen, np.int32(1), np.int32(0))
        o_ref[0, :, pl.ds(at, chunk)] = jnp.where(c < chunks, chosen, 0).astype(jnp.int8)
        return carry

    jax.lax.fori_loop(0, length // chunk, write, None)


def dsa_select(
    scores, lengths=None, *, top_k: int, block_q: int = 64, chunk: int = 512,
    interpret: bool = False,
):
    """:func:`select_keys` as a kernel, grid (B, L / bq): the block's
    scores are read from HBM once and searched in VMEM.

    ``lengths`` [B] int32 (as :func:`dsa_index_scores` takes them): each
    row's count of live query blocks is a scalar-prefetch operand; a live
    block selects as without lengths, a dead one reads no score (its map
    names the row's last live block, resident) and selects for each
    query the query itself alone (:func:`_select_by_length`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, L, _ = scores.shape
    _check_lengths(lengths, B)
    pad = -L % max(block_q, chunk)
    if pad:
        scores = jnp.pad(scores, ((0, 0), (0, pad), (0, pad)))
    n = L + pad
    if n % block_q or n % chunk:
        raise ValueError(f"a block of {block_q} and chunks of {chunk} do not tile {n}")

    def queries(b, qi, *live):
        return jnp.minimum(qi, _last_live(live[0], b)) if live else qi

    grid = dict(
        grid=(B, n // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, n), lambda b, qi, *live: (b, queries(b, qi, *live), 0))
        ],
        out_specs=pl.BlockSpec((1, block_q, n), lambda b, qi, *live: (b, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, n), jnp.int32)],
    )
    kernel = _select_kernel
    operands = [scores]
    if lengths is not None:
        kernel = _select_by_length
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, **grid))
        operands.insert(0, _live_blocks(lengths, block_q))
    out = pl.pallas_call(
        functools.partial(kernel, top_k, chunk),
        out_shape=jax.ShapeDtypeStruct((B, n, n), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_SELECT_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="dsa_select",
        **grid,
    )(*operands)
    return out[:, :L, :L]


def make_indexer_fn(num_heads: int, top_k: int, interpret: bool = False):
    """The indexer a model is BUILT with, ``fn(q, k, w) -> selection
    [B, L, L] int8``: the two Pallas kernels on the TPU (or interpreted
    when asked), ``jax.numpy`` elsewhere. ``.kind`` ('pallas' | 'jnp')
    says which.

    The kernels' also takes ``lengths=None`` ([B] int32, each row's
    last real position + 1) and runs no query block of padding alone:
    its ``.takes_lengths`` is True, and ``.query_blocks(length)`` is how
    many of the scores kernel's query blocks a row of that length has.
    The plain one has neither attribute."""
    if not interpret and jax.default_backend() != "tpu":

        def plain(q, k, w):
            return select_keys(index_scores(q, k, w, num_heads=num_heads), top_k=top_k)

        plain.kind = "jnp"
        return plain

    def indexer(q, k, w, lengths=None):
        scores = dsa_index_scores(
            q, k, w, lengths, num_heads=num_heads, interpret=interpret
        )
        return dsa_select(scores, lengths, top_k=top_k, interpret=interpret)

    indexer.kind = "pallas"
    indexer.takes_lengths = True
    indexer.query_blocks = lambda length: -(-length // SCORES_BLOCK_Q)
    return indexer
