"""The selective-scan kernel (ops/selective_scan.py) against the Mamba-1
recurrence stepped one token at a time.

The kernel runs under the Pallas interpreter on the CPU: the real kernel
body, tiny shapes. Tolerances: the kernel and the oracle do the same
float32 arithmetic in another order (the kernel adds ``d_state`` registers
as a tree, and takes ``exp(dt A)`` as ``2 ** (dt (A log2 e))``), so they
agree to a few float32 roundings of values of order 1: 2e-5 absolute.
With bfloat16 ``h`` and ``z`` both sides read the same rounded inputs, and
the kernel's output is rounded to bfloat16 once more: 2^-8 relative."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops.selective_scan import (
    chunked_scan,
    make_selective_scan_fn,
    selective_scan,
)

F32_ATOL = 2e-5


def token_scan(h, dt, b, c, z, a, d, state=None):
    """The recurrence as written down: one token a step. Returns the
    gated output and the state after the last token."""
    h32, z32 = h.astype(jnp.float32), z.astype(jnp.float32)

    def step(s, at):
        dt_t, h_t, b_t, c_t = at
        s = (
            jnp.exp(dt_t[:, :, None] * a) * s
            + (dt_t * h_t)[:, :, None] * b_t[:, None, :]
        )
        return s, jnp.sum(s * c_t[:, None, :], -1)

    if state is None:
        state = jnp.zeros((h.shape[0], h.shape[2], a.shape[1]), jnp.float32)
    first = lambda t: jnp.swapaxes(t, 0, 1)  # noqa: E731
    state, y = jax.lax.scan(step, state, tuple(map(first, (dt, h32, b, c))))
    y = first(y) + d * h32
    return y * (z32 * jax.nn.sigmoid(z32)), state


def inputs(rows, length, d_inner, n=16, seed=0, dtype=jnp.float32):
    """Steps log-uniform in [0.001, 0.1] and A = -(1..n), as Mamba
    initialises them: the state's memory spans from 5 to 1,000 tokens."""
    r = np.random.default_rng(seed)
    wide = (rows, length, d_inner)
    h = jnp.asarray(r.normal(size=wide), dtype)
    z = jnp.asarray(r.normal(size=wide), dtype)
    dt = jnp.asarray(
        np.exp(r.uniform(np.log(1e-3), np.log(1e-1), size=wide)), jnp.float32
    )
    b = jnp.asarray(r.normal(size=(rows, length, n)), jnp.float32)
    c = jnp.asarray(r.normal(size=(rows, length, n)), jnp.float32)
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (d_inner, n))
    d = jnp.asarray(r.normal(size=(d_inner,)), jnp.float32)
    return h, dt, b, c, z, a, d


@pytest.mark.parametrize(
    "length, d_inner, block_d",
    [
        # part-filled tiles: fewer than 8 lane groups on the sublanes
        (256, 256, 128),  # two whole chunks, two blocks of one lane group
        (200, 256, 256),  # the last chunk part empty; two lane groups
        (130, 384, 384),  # two tokens into the second chunk; three groups
        (128, 640, 1280),  # one chunk; five groups, block_d above d_inner
        # whole tiles of 1,024 channels
        (136, 1024, 1024),  # one block of one tile
        (128, 2048, 1024),  # two blocks
        (128, 2048, 2048),  # one block of two tiles
        (128, 1280, 1280),  # a whole tile and a part-filled one in a block
    ],
)
def test_kernel_matches_the_token_by_token_scan(length, d_inner, block_d):
    args = inputs(2, length, d_inner, seed=length)
    want, _ = token_scan(*args)
    got = selective_scan(*args, chunk=128, block_d=block_d, interpret=True)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=F32_ATOL)


def test_b_and_c_are_read_at_their_row_state_and_token():
    """``B_t[n]`` and ``C_t[n]`` reach the kernel as scalars, found by
    (row, chunk, token in the chunk, state index). Every one of them is a
    value of its own here, over two rows, two chunks and two blocks of
    ``d_inner``; and the same inputs with ``B`` or ``C`` moved by one place
    along any of the three axes give another answer by far more than the
    tolerance, so a scalar read one place off could not pass."""
    h, dt, _, _, z, a, d = inputs(2, 256, 256, seed=13)
    r, t, m = np.meshgrid(np.arange(2), np.arange(256), np.arange(16), indexing="ij")
    b = jnp.asarray(np.sin(0.37 * t + 1.3 * m + 2.1 * r), jnp.float32)
    c = jnp.asarray(np.cos(0.23 * t + 0.7 * m + 1.7 * r), jnp.float32)
    want, _ = token_scan(h, dt, b, c, z, a, d)
    got = selective_scan(h, dt, b, c, z, a, d, chunk=128, block_d=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=F32_ATOL)
    for axis in range(3):
        for moved in ((jnp.roll(b, 1, axis), c), (b, jnp.roll(c, 1, axis))):
            other, _ = token_scan(h, dt, *moved, z, a, d)
            off = np.abs(np.asarray(other) - np.asarray(want)).max()
            assert off > 1e3 * F32_ATOL, (axis, off)


def test_state_is_carried_across_the_chunk_edge():
    """What the tokens after a chunk's edge read is the state the chunk
    before left: the same kernel started at the edge with no state gives
    another answer there, by far more than the tolerance."""
    chunk = 128
    args = inputs(1, 2 * chunk, 128, seed=7)
    h, dt, b, c, z, a, d = args
    got = selective_scan(*args, chunk=chunk, block_d=128, interpret=True)
    want, _ = token_scan(*args)
    after = slice(chunk, chunk + 8)
    np.testing.assert_allclose(
        np.asarray(got[:, after]), np.asarray(want[:, after]), atol=F32_ATOL
    )
    # the oracle's state at the edge, carried on by the oracle, is what
    # the kernel's second chunk computed from
    tail = tuple(t[:, chunk:] for t in (h, dt, b, c, z))
    _, edge = token_scan(*(t[:, :chunk] for t in (h, dt, b, c, z)), a, d)
    carried, _ = token_scan(*tail, a, d, state=edge)
    np.testing.assert_allclose(
        np.asarray(got[:, chunk:]), np.asarray(carried), atol=F32_ATOL
    )
    dropped = selective_scan(*tail, a, d, chunk=chunk, block_d=128, interpret=True)
    lost = np.abs(np.asarray(dropped[:, :8]) - np.asarray(want[:, after])).max()
    assert lost > 1e3 * F32_ATOL, lost


def test_rows_do_not_share_a_state():
    """The state is zeroed at a row's first chunk: a row's answer does
    not depend on the row the grid visited before it."""
    args = inputs(3, 128, 128, seed=11)
    got = selective_scan(*args, chunk=128, block_d=128, interpret=True)
    alone = selective_scan(
        *(t[2:] for t in args[:5]), *args[5:], chunk=128, block_d=128,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got[2:]), np.asarray(alone))


def test_bfloat16_in_and_out_float32_inside():
    args = inputs(2, 200, 256, seed=3, dtype=jnp.bfloat16)
    want, _ = token_scan(*args)
    got = selective_scan(*args, chunk=128, block_d=256, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=2**-8, atol=1e-3
    )
    wide = selective_scan(
        *args, chunk=128, block_d=256, interpret=True, out_dtype=jnp.float32
    )
    assert wide.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(wide), np.asarray(want), atol=F32_ATOL)


@pytest.mark.parametrize("length, chunk", [(192, 64), (100, 64), (50, 64)])
def test_plain_chunked_scan_matches_too(length, chunk):
    args = inputs(2, length, 128, seed=length)
    want, _ = token_scan(*args)
    got = chunked_scan(*args, chunk=chunk)
    # an associative scan multiplies decays together before it applies
    # them: a few more roundings than the kernel's token order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


def test_sizes_the_lanes_cannot_hold_are_refused():
    args = inputs(1, 128, 192)
    with pytest.raises(ValueError, match="multiples of 128"):
        selective_scan(*args, interpret=True)
    with pytest.raises(ValueError, match="multiples of 128"):
        selective_scan(*inputs(1, 128, 128), chunk=64, interpret=True)


def test_a_state_the_registers_cannot_hold_is_refused():
    """``d_state`` counts the vector registers a tile's state takes: 32 is
    the most, and runs; 64 would be the whole register file."""
    args = inputs(1, 128, 128, n=32, seed=2)
    want, _ = token_scan(*args)
    got = selective_scan(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=F32_ATOL)
    with pytest.raises(ValueError, match="d_state 64"):
        selective_scan(*inputs(1, 128, 128, n=64), interpret=True)


def test_scan_choice_is_made_at_build_and_recorded():
    """Off the TPU a model is built over the plain scan; the kernel only
    where it was asked for by name (`interpret`), never found out later."""
    assert make_selective_scan_fn().kind == "jnp"
    fn = make_selective_scan_fn(interpret=True, block_d=128)
    assert fn.kind == "pallas"
    args = inputs(1, 128, 128, seed=5)
    np.testing.assert_allclose(
        np.asarray(fn(*args)),
        np.asarray(make_selective_scan_fn()(*args)),
        atol=5e-5,
    )
