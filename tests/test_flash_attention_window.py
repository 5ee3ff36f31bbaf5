"""The sliding-window mode of the blocked flash kernel
(`ops/flash_attention.py:flash_attention(causal=True, window=W)`), through
the Pallas interpreter, against dense windowed attention written out
here: query i sees key j iff 0 <= i - j < W.

Tolerance: kernel and oracle both keep scores and softmax in float32; the
kernel's online softmax rescales its running sum once a key block: 2e-5,
as `test_flash_attention_causal.py` holds the causal kernel to."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops.flash_attention import (
    NEG_INF,
    _resident,
    _window_key_block,
    dense_causal_attention,
    flash_attention,
    make_flash_attention_fn,
)

TOL = dict(atol=2e-5, rtol=2e-5)
W, BLOCK = 32, 16  # a band of W / BLOCK + 1 = 3 key blocks


def qkv(seed, B, H, Hkv, L, Dh=32):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(B, H, L, Dh)), jnp.float32)
    k = jnp.asarray(r.normal(size=(B, Hkv, L, Dh)), jnp.float32)
    v = jnp.asarray(r.normal(size=(B, Hkv, L, Dh)), jnp.float32)
    return q, k, v


def windowed(q, k, v, window, mask=None):
    """Every query head against its own copy of its key/value head, the
    keys j with 0 <= i - j < window, in float32 at highest precision."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    L = q.shape[2]
    i, j = np.arange(L)[:, None], np.arange(L)[None, :]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") / np.sqrt(q.shape[-1])
    if mask is not None:
        s = s + mask[:, None, None, :]
    s = jnp.where((i - j >= 0) & (i - j < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v, precision="highest")


def run(q, k, v, window=W, block=BLOCK, mask=None, lengths=None):
    return flash_attention(
        q, k, v, mask, block_q=block, block_k=block, interpret=True,
        causal=True, window=window, lengths=lengths,
    )


@pytest.mark.parametrize("H, Hkv", [(2, 2), (8, 2), (8, 1)], ids=["mha", "gqa4", "gqa8"])
@pytest.mark.parametrize(
    "L",
    [
        W,  # the window is the row: the band is every block, no key is out of it
        2 * W + 8,  # two windows and a part-block, padded up to the block
        4 * W,  # four windows: every query block past the first bands
    ],
)
def test_window_matches_dense(H, Hkv, L):
    q, k, v = qkv(L + H + Hkv, 2, H, Hkv, L)
    np.testing.assert_allclose(
        np.asarray(run(q, k, v)), np.asarray(windowed(q, k, v, W)), **TOL
    )


@pytest.mark.parametrize(
    "window, block, L",
    [
        (16, 16, 80),  # a window of one block: a band of two, nothing between
        (48, 16, 100),  # a band of four, two blocks between
        (64, 32, 64),  # the window is the row: one band of two blocks
        (256, 16, 96),  # a window longer than the row: every block, causal alone
    ],
)
def test_other_windows_and_blocks(window, block, L):
    q, k, v = qkv(window + L, 1, 4, 2, L)
    np.testing.assert_allclose(
        np.asarray(run(q, k, v, window, block)),
        np.asarray(windowed(q, k, v, window)),
        **TOL,
    )


def test_padded_keys_are_never_seen():
    """An additive key mask (the padding's NEG_INF) inside the band."""
    L = 3 * W
    q, k, v = qkv(5, 2, 4, 1, L)
    mask = np.zeros((2, L), np.float32)
    mask[0, 70:] = NEG_INF
    mask[1, 40:50] = NEG_INF
    mask = jnp.asarray(mask)
    np.testing.assert_allclose(
        np.asarray(run(q, k, v, mask=mask)),
        np.asarray(windowed(q, k, v, W, mask)),
        **TOL,
    )


def _grid(L, window, block):
    q = jnp.zeros((1, 4, L, 128), jnp.bfloat16)
    k = jnp.zeros((1, 1, L, 128), jnp.bfloat16)
    text = str(
        jax.make_jaxpr(
            lambda q, k: flash_attention(
                q, k, k, block_q=block, block_k=block, causal=True, window=window
            )
        )(q, k)
    )
    assert "name=flash_attention_window" in text
    grid = text.split("GridMapping(grid=(", 1)[1].split(")", 1)[0]
    return tuple(int(n) for n in grid.split(","))


@pytest.mark.parametrize(
    "L, window, block, steps",
    [
        (16384, 2048, 512, 5),  # the cell's row: 5 key steps, not 32
        (8192, 2048, 512, 5),
        (2048, 2048, 512, 4),  # the window covers the row: every block
        (1024, 2048, 512, 2),
        (160, 32, 16, 3),
    ],
)
def test_the_grids_key_axis_is_the_band(L, window, block, steps):
    assert steps == min(L // block, window // block + 1)
    assert _grid(L, window, block) == (4, L // block, steps)


def test_a_step_below_block_zero_fetches_nothing():
    """Walking a query block's steps in order, the key block changes only
    to a block that a live step runs: a step below block 0 names block 0,
    which the band's first live step reads next, so nothing is copied for
    it that is not used."""
    for steps in (2, 3, 5):
        for qi in range(8):
            named = [int(_window_key_block(qi, ki, steps)) for ki in range(steps)]
            live = [qi - (steps - 1) + ki for ki in range(steps)]
            live = [b for b in live if b >= 0]
            fetched = [b for n, b in enumerate(named) if n == 0 or b != named[n - 1]]
            assert fetched == live, (steps, qi, named)
            assert named[-1] == qi  # the diagonal last


def test_window_none_is_the_causal_kernel():
    """Without a window the call is the causal kernel's, by name too."""
    q = jnp.zeros((1, 4, 64, 128), jnp.bfloat16)
    k = jnp.zeros((1, 1, 64, 128), jnp.bfloat16)

    def text(**kw):
        return str(jax.make_jaxpr(
            lambda q, k: flash_attention(q, k, k, block_q=16, block_k=16, causal=True, **kw)
        )(q, k))

    assert text() == text(window=None)
    assert re.search(r"name=flash_attention\b", text())
    assert "flash_attention_window" not in text()


@pytest.mark.parametrize(
    "kw, said",
    [
        (dict(causal=False, window=32), "wants causal"),
        (dict(causal=True, window=24), "multiple of the key block 16"),
        (dict(causal=True, window=8), "multiple of the key block 16"),
    ],
)
def test_what_the_window_cannot_do_is_refused(kw, said):
    q, k, v = qkv(0, 1, 2, 2, 64)
    with pytest.raises(ValueError, match=said):
        flash_attention(q, k, v, block_q=16, block_k=16, interpret=True, **kw)


def test_the_built_function_and_its_fallback():
    """Off the TPU the factory gives dense windowed attention, tagged; the
    interpreted kernel it gives when asked agrees with it."""
    dense = make_flash_attention_fn(block_q=16, block_k=16, causal=True, window=W)
    assert dense.kind == "dense"
    kernel = make_flash_attention_fn(
        block_q=16, block_k=16, causal=True, window=W, interpret=True
    )
    assert kernel.kind == "flash"
    q, k, v = qkv(3, 2, 8, 2, 96)
    want = windowed(q, k, v, W)
    np.testing.assert_allclose(np.asarray(dense(q, k, v, None, jnp.float32)), np.asarray(want), **TOL)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v, None, jnp.float32)), np.asarray(want), **TOL)
    # the causal fallback is the same function, without a window
    assert make_flash_attention_fn(causal=True) is dense_causal_attention


# -- the window kernel given its rows' lengths ----------------------------------


@pytest.mark.parametrize("H, Hkv", [(2, 2), (8, 2)], ids=["mha", "gqa4"])
@pytest.mark.parametrize(
    "window, L, lengths",
    [
        # in blocks of 16: a length inside a block, on a block's edge, the
        # row's whole edge, and 0 (a row that only fills the batch)
        (W, 96, (37, 48, 96, 0)),
        (16, 96, (1, 16, 96, 0)),  # a band of two
        (256, 96, (17, 80, 96, 0)),  # a window longer than the row
        (W, 90, (90, 64, 33, 0)),  # a row off the block size, padded to 96
    ],
)
def test_window_with_lengths_matches_dense_at_every_real_position(H, Hkv, window, L, lengths):
    """Every real position is `dense_causal_attention`'s; every position
    of a live query block is the call without lengths' to the bit (the
    same steps in the same order); every position of a dead block is
    zero."""
    q, k, v = qkv(L + H + window, len(lengths), H, Hkv, L)
    got = np.asarray(run(q, k, v, window, lengths=jnp.asarray(lengths, jnp.int32)))
    whole = np.asarray(run(q, k, v, window))
    want = np.asarray(dense_causal_attention(q, k, v, None, jnp.float32, window=window))
    assert got.shape == whole.shape and not np.isnan(got).any()
    for row, n in enumerate(lengths):
        live = min(-(-n // BLOCK) * BLOCK, L)
        np.testing.assert_allclose(got[row, :, :n], want[row, :, :n], **TOL)
        np.testing.assert_array_equal(got[row, :, :live], whole[row, :, :live])
        assert not got[row, :, live:].any(), (row, n)
    assert np.abs(got[0]).max() > 0.01  # the comparison is of something


def _walk(steps, nq, live, key_block):
    """(query block, the query and key blocks its step names) of every step
    of one row's head in the grid's order, where the row has ``live``
    live query blocks: the index maps' rule (:func:`_resident`)."""
    return [
        (qi, int(_resident(qi, qi, live)), int(_resident(key_block(qi, ki), qi, live)))
        for qi in range(nq)
        for ki in range(steps)
    ]


@pytest.mark.parametrize("live", [0, 1, 3, 7, 8])
@pytest.mark.parametrize("steps", [2, 3, 5])
def test_a_dead_query_blocks_steps_fetch_nothing(steps, live):
    """A live query block names what it names without lengths. Every step
    of a dead one names the query and key blocks the step before it
    named, the last live block's diagonal: nothing is copied for it. A
    row of padding names block 0 throughout, fetched once a head."""
    nq = 8
    walked = _walk(steps, nq, live, lambda qi, ki: _window_key_block(qi, ki, steps))
    for n, (qi, q_block, k_block) in enumerate(walked):
        if qi < live:
            assert (q_block, k_block) == (qi, int(_window_key_block(qi, n % steps, steps)))
        elif n:
            assert (q_block, k_block) == walked[n - 1][1:], (steps, live, n)
    assert walked[-1][1:] == (max(live, 1) - 1,) * 2


@pytest.mark.parametrize(
    "kw, lengths, said",
    [
        (dict(causal=False), np.zeros((2,), np.int32), "want causal attention"),
        (dict(causal=True, window=32), np.zeros((3,), np.int32), r"got \(3,\) int32"),
        (dict(causal=True, window=32), np.zeros((2,), np.float32), r"got \(2,\) float32"),
        (dict(causal=True), np.zeros((2, 1), np.int32), r"got \(2, 1\) int32"),
    ],
)
def test_lengths_of_another_shape_or_type_or_without_causal_are_refused(kw, lengths, said):
    q, k, v = qkv(0, 2, 2, 2, 64)
    with pytest.raises(ValueError, match=said):
        flash_attention(
            q, k, v, block_q=16, block_k=16, interpret=True, lengths=jnp.asarray(lengths), **kw
        )


def test_the_built_kernels_say_they_take_lengths_and_the_fallbacks_do_not():
    """The blocked kernel's causal and window functions take lengths and
    count a row's query blocks; the dense fallbacks and the bidirectional
    kernel have neither attribute, so a caller hands them none."""
    for window in (None, W):
        dense = make_flash_attention_fn(block_q=16, block_k=16, causal=True, window=window)
        assert dense.kind == "dense"  # the tests run on the CPU
        assert not hasattr(dense, "takes_lengths") and not hasattr(dense, "query_blocks")
        kernel = make_flash_attention_fn(
            block_q=16, block_k=16, causal=True, window=window, interpret=True
        )
        assert kernel.takes_lengths is True
        assert [kernel.query_blocks(n) for n in (0, 1, 16, 17, 96)] == [0, 1, 1, 2, 6]
        q, k, v = qkv(31, 2, 4, 2, 64)
        whole = np.asarray(kernel(q, k, v, None, jnp.float32))
        got = np.asarray(kernel(q, k, v, None, jnp.float32, lengths=jnp.asarray([20, 0], jnp.int32)))
        np.testing.assert_array_equal(got[0, :, :32], whole[0, :, :32])
        assert not got[0, :, 32:].any() and not got[1].any()
    plain = make_flash_attention_fn(block_q=16, block_k=16, interpret=True)
    assert not hasattr(plain, "takes_lengths") and not hasattr(plain, "query_blocks")
