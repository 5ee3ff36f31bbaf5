"""Manifold-constrained hyper-connections (mHC): a residual stream of ``n``
copies of the hidden state, read by a sublayer through a dynamic pre-mix
and written back through a dynamic post-mix and a residual mix projected
onto the doubly stochastic matrices by Sinkhorn-Knopp (Hyper-Connections,
Zhu et al. 2024, arXiv:2409.19606; mHC, DeepSeek-AI, arXiv:2512.24880).

Per token, ``X`` [n, C] float32 lies as one row of ``x`` [tokens, n * C],
stream i at lanes [i C, (i + 1) C). A sublayer's own ``phi`` [n C, 2n +
n^2], ``bias`` [2n + n^2] and gains ``alpha`` (pre, post, res) give

    x^        = vec(X) / sqrt(mean(vec(X)^2) + rms_eps)      no weight
    [p|q|r]   = x^ phi                                       float32, highest
    H_pre     = sigmoid(alpha_pre p + b_pre)                 [n]
    H_post    = 2 sigmoid(alpha_post q + b_post)             [n]
    M^0       = exp(clamp(alpha_res mat(r) + b_res, lo, hi)) [n, n], mat row-major
    M^t       = cols(rows(M^{t-1})), t = 1..iters            each / (sum + hc_eps)
    H_res     = M^iters
    u         = sum_i H_pre[i] X[i]                          -> the sublayer
    X'[j]     = sum_i H_res[j, i] X[i] + H_post[j] F(u)

:func:`hc_pre` is the first half (``u`` in the compute dtype, ``H_post``
[tokens, n] and ``H_res`` [tokens, n^2] float32), :func:`hc_post` the
second. On the TPU each is one Pallas kernel, named so on the trace
(``hc_pre``, ``hc_post``), over a tile of tokens whose stream is read
once. ``hc_pre`` lays the coefficients with the tokens on lanes: the
product is ``phi^T x^T`` [2n + n^2, tile], every Sinkhorn row or column
sum an add of whole vectors, and the tile's coefficients are turned to a
token a sublane once, for the pre-mix and the two results. Both walk the
hidden size in pieces of 128 lanes under ``lax.fori_loop``, so the
lowered module does not grow with the width. Off the TPU the same
equations in plain ``jax.numpy`` (:func:`hc_pre_xla`, :func:`hc_post_xla`)
are the build-time fallback and the kernels' oracle;
:func:`make_hyper_connection_fn` chooses and says which (``.kind``:
``pallas`` | ``xla``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
#: lanes of a register: the hidden size is walked in pieces of this many
_LANES = 128
#: columns of ``phi^T`` and of the stream a step of ``hc_pre``'s product
_CONTRACT = 512
#: tokens a tile of ``hc_pre``: one lane register of coefficients
_PRE_TILE = 128
#: the most bytes of double-buffered blocks a kernel's tile may hold
_BLOCK_BYTES = 32 << 20
#: ``NT``: contract the last axis of both operands
_NT = (((1,), (1,)), ((), ()))


def coefficients(n: int) -> int:
    """Columns of ``phi``: n pre, n post and n^2 residual logits."""
    return 2 * n + n * n


def _round8(k: int) -> int:
    return -(-k // 8) * 8


def _sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))


def _gains(alpha, n: int):
    """The three gains (pre, post, res) laid over the coefficients."""
    counts = np.array([n, n, n * n])
    return jnp.repeat(alpha.astype(jnp.float32), counts, total_repeat_length=coefficients(n))


def _sinkhorn(rows, n: int, iters: int, hc_eps: float):
    """``rows``: the n^2 entries of M, row-major, each an array of any
    shape (a token's or a tile's lanes) -> the same after ``iters`` steps
    of row- then column-normalisation, each a divide by (sum + hc_eps)."""

    def step(_, rows):
        rows = list(rows)
        for j in range(n):
            s = rows[n * j]
            for i in range(1, n):
                s = s + rows[n * j + i]
            for i in range(n):
                rows[n * j + i] = rows[n * j + i] / (s + hc_eps)
        for i in range(n):
            s = rows[i]
            for j in range(1, n):
                s = s + rows[n * j + i]
            for j in range(n):
                rows[n * j + i] = rows[n * j + i] / (s + hc_eps)
        return tuple(rows)

    return jax.lax.fori_loop(0, iters, step, tuple(rows))


@dataclass(frozen=True)
class Constants:
    """The configuration's numbers a hyper-connection reads."""

    n: int
    iters: int
    hc_eps: float
    clamp: Tuple[float, float]
    rms_eps: float


# -- plain jax.numpy ------------------------------------------------------------


def hc_pre_xla(k: Constants, x, phi, bias, alpha, dtype):
    """x [T, n C] float32 -> (u [T, C] ``dtype``, H_post [T, n], H_res
    [T, n^2] float32), the module's equations as written."""
    n = k.n
    width = x.shape[1]
    r = jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) / width + k.rms_eps)
    logits = jnp.einsum("tk,km->tm", x, phi, precision=_HIGHEST) * r
    logits = logits * _gains(alpha, n) + bias
    pre = _sigmoid(logits[:, :n])
    post = 2.0 * _sigmoid(logits[:, n : 2 * n])
    m0 = jnp.exp(jnp.clip(logits[:, 2 * n :], *k.clamp))
    res = jnp.stack(_sinkhorn([m0[:, e] for e in range(n * n)], n, k.iters, k.hc_eps), -1)
    streams = x.reshape(x.shape[0], n, -1)
    u = pre[:, 0, None] * streams[:, 0]
    for i in range(1, n):
        u = u + pre[:, i, None] * streams[:, i]
    return u.astype(dtype), post, res


def hc_post_xla(k: Constants, x, f, h_post, h_res):
    """x [T, n C], f [T, C], H_post [T, n], H_res [T, n^2], all float32 ->
    X' [T, n C] float32."""
    n = k.n
    streams = x.reshape(x.shape[0], n, -1)
    out = []
    for j in range(n):
        acc = h_res[:, n * j, None] * streams[:, 0]
        for i in range(1, n):
            acc = acc + h_res[:, n * j + i, None] * streams[:, i]
        out.append(acc + h_post[:, j, None] * f)
    return jnp.concatenate(out, -1)


# -- the Pallas kernels ----------------------------------------------------------


def _pre_kernel(k, group, x_ref, phi_ref, gain_ref, bias_ref, u_ref, post_ref, res_ref,
                logit_ref, h_ref, pre_t_ref):
    """One tile of tokens. ``x_ref`` [tile, n C], ``phi_ref`` [m, n C] (phi
    turned), ``gain_ref`` and ``bias_ref`` [m, 1]; ``logit_ref`` [m, tile]
    and ``h_ref`` [3 P, tile] hold coefficients a token a lane (P: n
    rounded up to 8 rows; pre at row 0, post at P, the residual mix at 2 P
    on), ``pre_t_ref`` [tile, P] the pre-mix a token a sublane."""
    from jax.experimental import pallas as pl

    n = k.n
    tile, width = x_ref.shape
    hidden = width // n
    m = phi_ref.shape[0]
    lanes = min(hidden, _LANES)
    step = min(width, _CONTRACT)
    P = _round8(n)

    def contract(c, carry):
        acc, sq = carry
        at = pl.multiple_of(c * step, step)
        xs = x_ref[:, pl.ds(at, step)]
        acc = acc + jax.lax.dot_general(
            phi_ref[:, pl.ds(at, step)], xs, _NT,
            precision=_HIGHEST, preferred_element_type=jnp.float32,
        )
        for p in range(step // lanes):
            piece = xs[:, p * lanes : (p + 1) * lanes]
            sq = sq + piece * piece
        return acc, sq

    zeros = (jnp.zeros((m, tile), jnp.float32), jnp.zeros((tile, lanes), jnp.float32))
    acc, sq = jax.lax.fori_loop(0, width // step, contract, zeros)
    # each token's sum of squares, a token a lane: the lane sums by a
    # product with ones (exact in the product's float32 passes)
    total = jax.lax.dot_general(
        jnp.ones((8, lanes), jnp.float32), sq, _NT,
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )[0:1]
    r = jax.lax.rsqrt(total / width + k.rms_eps)  # [1, tile]
    logit_ref[...] = acc * r * gain_ref[...] + bias_ref[...]

    row = lambda e: logit_ref[e : e + 1, :]  # noqa: E731
    h_ref[...] = jnp.zeros(h_ref.shape, jnp.float32)
    for i in range(n):
        h_ref[i : i + 1, :] = _sigmoid(row(i))
        h_ref[P + i : P + i + 1, :] = 2.0 * _sigmoid(row(n + i))
    m0 = [jnp.exp(jnp.clip(row(2 * n + e), *k.clamp)) for e in range(n * n)]
    for e, v in enumerate(_sinkhorn(m0, n, k.iters, k.hc_eps)):
        h_ref[2 * P + e : 2 * P + e + 1, :] = v

    # a token a sublane from here on
    pre_t_ref[...] = h_ref[0:P, :].T
    post_ref[...] = h_ref[P : 2 * P, :].T[:, :n]
    res_ref[...] = h_ref[2 * P :, :].T[:, : n * n]

    def one_group(g, carry):
        lo = pl.multiple_of(g * group, group)
        h = pre_t_ref[pl.ds(lo, group), :]
        weight = [jnp.broadcast_to(h[:, i : i + 1], (group, lanes)) for i in range(n)]

        def piece(c, carry):
            at = pl.multiple_of(c * lanes, lanes)
            acc = weight[0] * x_ref[pl.ds(lo, group), pl.ds(at, lanes)]
            for i in range(1, n):
                at_i = pl.multiple_of(i * hidden + c * lanes, lanes)
                acc = acc + weight[i] * x_ref[pl.ds(lo, group), pl.ds(at_i, lanes)]
            u_ref[pl.ds(lo, group), pl.ds(at, lanes)] = acc.astype(u_ref.dtype)
            return carry

        return jax.lax.fori_loop(0, hidden // lanes, piece, carry)

    jax.lax.fori_loop(0, tile // group, one_group, 0)


def _post_kernel(k, x_ref, f_ref, post_ref, res_ref, out_ref):
    """One tile of tokens, a group of eight a sublane register: each
    token's n + n^2 coefficients broadcast along the lanes once, then the
    hidden size walked in pieces of 128 lanes, the n streams and F read
    once a piece and the n new streams written."""
    from jax.experimental import pallas as pl

    n, group = k.n, 8
    tile, width = x_ref.shape
    hidden = width // n
    lanes = min(hidden, _LANES)

    def one_group(g, carry):
        lo = pl.multiple_of(g * group, group)
        hp, hr = post_ref[pl.ds(lo, group), :], res_ref[pl.ds(lo, group), :]
        wide = lambda h, e: jnp.broadcast_to(h[:, e : e + 1], (group, lanes))  # noqa: E731
        post = [wide(hp, j) for j in range(n)]
        res = [wide(hr, e) for e in range(n * n)]

        def piece(c, carry):
            at = [pl.multiple_of(i * hidden + c * lanes, lanes) for i in range(n)]
            xs = [x_ref[pl.ds(lo, group), pl.ds(at[i], lanes)] for i in range(n)]
            f = f_ref[pl.ds(lo, group), pl.ds(at[0], lanes)]
            for j in range(n):
                acc = res[n * j] * xs[0]
                for i in range(1, n):
                    acc = acc + res[n * j + i] * xs[i]
                out_ref[pl.ds(lo, group), pl.ds(at[j], lanes)] = acc + post[j] * f
            return carry

        return jax.lax.fori_loop(0, hidden // lanes, piece, carry)

    jax.lax.fori_loop(0, tile // group, one_group, 0)


def _vmem_limit(block_bytes: int) -> int:
    """Double-buffered blocks with room above, within the v5e's 128 MiB."""
    return min(2 * block_bytes + (8 << 20), 100 << 20)


def _post_tile(width: int) -> int:
    """Tokens a tile of ``hc_post``: the largest power of two from 8 to
    256 whose double-buffered blocks (the stream in and out, F) lie
    within 32 MiB: 64 at n C = 14,336."""
    tile = 256
    while tile > 8 and 2 * tile * (2 * width + width // 4) * 4 > _BLOCK_BYTES:
        tile //= 2
    return tile


@functools.lru_cache(maxsize=None)
def _build_pre(k: Constants, tokens: int, width: int, dtype, interpret: bool):
    """The ``pallas_call`` of one shape: call sites of that shape share
    one lowering."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, tile = k.n, _PRE_TILE
    m, hidden, P = coefficients(n), width // n, _round8(k.n)
    size = jnp.dtype(dtype).itemsize
    group = 8 * 4 // size  # a packed register of ``dtype``'s rows
    blocks = tile * width * 4 + m * width * 4 + tile * hidden * size + tile * (n + n * n) * 4
    return pl.pallas_call(
        functools.partial(_pre_kernel, k, group),
        out_shape=(
            jax.ShapeDtypeStruct((tokens, hidden), dtype),
            jax.ShapeDtypeStruct((tokens, n), jnp.float32),
            jax.ShapeDtypeStruct((tokens, n * n), jnp.float32),
        ),
        grid=(tokens // tile,),
        in_specs=[
            pl.BlockSpec((tile, width), lambda t: (t, 0)),
            pl.BlockSpec((m, width), lambda t: (0, 0)),
            pl.BlockSpec((m, 1), lambda t: (0, 0)),
            pl.BlockSpec((m, 1), lambda t: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((tile, hidden), lambda t: (t, 0)),
            pl.BlockSpec((tile, n), lambda t: (t, 0)),
            pl.BlockSpec((tile, n * n), lambda t: (t, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((m, tile), jnp.float32),
            pltpu.VMEM((2 * P + _round8(n * n), tile), jnp.float32),
            pltpu.VMEM((tile, P), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_vmem_limit(blocks)
        ),
        interpret=interpret,
        # a stable name for the kernel's events in a profiler trace
        name="hc_pre",
    )


@functools.lru_cache(maxsize=None)
def _build_post(k: Constants, tokens: int, width: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, tile, hidden = k.n, _post_tile(width), width // k.n
    blocks = tile * (2 * width + hidden + n + n * n) * 4
    return pl.pallas_call(
        functools.partial(_post_kernel, k),
        out_shape=jax.ShapeDtypeStruct((tokens, width), jnp.float32),
        grid=(tokens // tile,),
        in_specs=[
            pl.BlockSpec((tile, width), lambda t: (t, 0)),
            pl.BlockSpec((tile, hidden), lambda t: (t, 0)),
            pl.BlockSpec((tile, n), lambda t: (t, 0)),
            pl.BlockSpec((tile, n * n), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((tile, width), lambda t: (t, 0)),
        # the new stream takes the old one's place, tile by tile
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_vmem_limit(blocks)
        ),
        interpret=interpret,
        name="hc_post",
    )


def _padded(a, tokens: int):
    return jnp.pad(a, ((0, tokens - a.shape[0]), (0, 0))) if tokens != a.shape[0] else a


def hc_pre(k: Constants, x, phi, bias, alpha, dtype, *, interpret: bool = False):
    """:func:`hc_pre_xla` as one kernel: the tokens in tiles of 128 (the
    tail padded), ``phi`` turned to [m, n C] for the product."""
    tokens, width = x.shape
    padded = -(-tokens // _PRE_TILE) * _PRE_TILE
    call = _build_pre(k, padded, width, jnp.dtype(dtype), interpret)
    u, post, res = call(
        _padded(x, padded), phi.astype(jnp.float32).T, _gains(alpha, k.n)[:, None],
        bias.astype(jnp.float32)[:, None],
    )
    return u[:tokens], post[:tokens], res[:tokens]


def hc_post(k: Constants, x, f, h_post, h_res, *, interpret: bool = False):
    """:func:`hc_post_xla` as one kernel; its result takes ``x``'s buffer."""
    tokens, width = x.shape
    tile = _post_tile(width)
    padded = -(-tokens // tile) * tile
    call = _build_post(k, padded, width, interpret)
    out = call(*(_padded(a, padded) for a in (x, f, h_post, h_res)))
    return out[:tokens] if padded != tokens else out


@dataclass(frozen=True)
class HyperConnection:
    """A model's pair of halves, built once: ``pre(x, phi, bias, alpha,
    dtype)`` and ``post(x, f, h_post, h_res)`` over x [T, n C]."""

    constants: Constants
    kind: str
    interpret: bool = False

    def pre(self, x, phi, bias, alpha, dtype):
        if self.kind == "xla":
            return hc_pre_xla(self.constants, x, phi, bias, alpha, dtype)
        return hc_pre(self.constants, x, phi, bias, alpha, dtype, interpret=self.interpret)

    def post(self, x, f, h_post, h_res):
        if self.kind == "xla":
            return hc_post_xla(self.constants, x, f, h_post, h_res)
        return hc_post(self.constants, x, f, h_post, h_res, interpret=self.interpret)


def make_hyper_connection_fn(constants: Constants, interpret: bool = False) -> HyperConnection:
    """The two kernels on the TPU (or interpreted when asked), the plain
    ``jax.numpy`` equations elsewhere. ``.kind`` ('pallas' | 'xla') says
    which."""
    if not interpret and jax.default_backend() != "tpu":
        return HyperConnection(constants, "xla")
    return HyperConnection(constants, "pallas", interpret)
