"""The routed experts' combine (ops/moe_combine.py): the Pallas kernel,
interpreted on the CPU, against the gather loop it replaces on the TPU.

Tolerance. Where every product and partial sum is exact in float32
(small whole numbers in ``y``, weights that are powers of two) the order
of the additions cannot show, and the kernel agrees with the loop to the
bit: a row fetched for the wrong slot, a slot summed twice or a held one
left out would. With any other values it agrees within 1e-6 of the row's
size: the CPU compiles the interpreted body with one product fused into
the add that follows it (one rounding fewer, an ulp or so apart), where
the loop rounds each product first."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops import moe_combine as mc


def _case(k, hidden, tokens=40, exact=False, seed=0):
    """A slot table with every shape the kernel meets: a token with every
    slot held, one with none, pad tokens at the end, and the held slots
    on every row of the buffer once, its last and its first among them."""
    rng = np.random.default_rng(seed)
    held = rng.random((tokens, k)) < 0.5
    held[0], held[1], held[-3:] = True, False, False
    rows = int(held.sum())
    slot = np.full((tokens, k), -1, np.int32)
    slot[held] = rng.permutation(rows)
    if exact:
        y = rng.integers(-8, 9, (rows, hidden)).astype(np.float32)
        w = (2.0 ** rng.integers(-3, 2, (tokens, k))).astype(np.float32)
    else:
        y = rng.standard_normal((rows, hidden)).astype(np.float32)
        w = rng.random((tokens, k)).astype(np.float32)
    return jnp.asarray(y), jnp.asarray(slot), jnp.asarray(w)


def _kernel(y, slot, w):
    return np.asarray(mc.moe_combine(y, slot, w, interpret=True))


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 16 tokens: the 40 tokens of a case span three, so the
    copies started across a tile's end are read in the next."""
    monkeypatch.setattr(mc, "_MOST_TILE", 16)
    mc._build.cache_clear()
    yield
    mc._build.cache_clear()


@pytest.mark.parametrize("hidden", [64, 128, 256])
@pytest.mark.parametrize("k", [6, 8])
def test_the_kernel_is_the_loop_to_the_bit(small_tiles, k, hidden):
    """At 256 a row is two pieces of 128 lanes, fetched and summed apart;
    the cases' buffers are not whole tiles of 8 rows."""
    y, slot, w = _case(k, hidden, exact=True)
    assert mc.token_tile(hidden) == 16
    got, want = _kernel(y, slot, w), np.asarray(mc.gather_combine(y, slot, w))
    np.testing.assert_array_equal(got, want)
    assert np.abs(want[0]).sum() > 0
    # no held slot, and the pad tokens: zeros
    assert not got[1].any() and not got[-3:].any()


@pytest.mark.parametrize("hidden", [64, 128])
@pytest.mark.parametrize("k", [6, 8])
def test_the_kernel_is_the_loop_within_a_rounding(k, hidden):
    y, slot, w = _case(k, hidden, seed=k + hidden)
    got, want = _kernel(y, slot, w), np.asarray(mc.gather_combine(y, slot, w))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert not got[1].any() and not got[-3:].any()


def test_the_token_tile_follows_the_width():
    """The double-buffered float32 output block within 8 MiB: 512 tokens
    at 2,048 (Trinity), 128 at 5,120 and 7,168 (both DeepSeek cells)."""
    assert [mc.token_tile(h) for h in (64, 2048, 5120, 7168)] == [512, 512, 128, 128]
    for hidden in (2048, 5120, 7168):
        assert 2 * mc.token_tile(hidden) * hidden * 4 <= 8 << 20


def test_the_build_time_choice():
    """The gather loop off the TPU, the kernel when interpreted."""
    plain = mc.make_moe_combine_fn()
    assert plain is mc.gather_combine and plain.kind == "gather"
    kernel = mc.make_moe_combine_fn(interpret=True)
    assert kernel.kind == "pallas"
    y, slot, w = _case(8, 64, tokens=16, exact=True)
    np.testing.assert_array_equal(
        np.asarray(kernel(y, slot, w)), np.asarray(plain(y, slot, w))
    )


@pytest.mark.parametrize("rows, barriers", [(96, 1), (48, 0)])
def test_the_gather_loop_passes_three_parts_where_the_buffer_holds_every_slot(rows, barriers):
    """k = 6: a buffer of tokens x k rows (the worst-case arm) sums its
    parts three a pass behind one barrier; a smaller one in one pass."""
    S = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(mc.gather_combine)(
        S((rows, 64), jnp.float32), S((16, 6), jnp.int32), S((16, 6), jnp.float32)
    ))
    assert text.count("optimization_barrier") == barriers
