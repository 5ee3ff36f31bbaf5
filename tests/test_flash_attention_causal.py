"""Causal masking and shared key/value heads in the flash kernel
(ops/flash_attention.py), through the Pallas interpreter, against dense
attention written out here; and the bidirectional, equal-heads kernel
left as it was.

Tolerance: kernel and oracle both keep scores and softmax in float32; the
kernel's online softmax rescales its running sum once a key block, a few
float32 roundings of values of order 1: 2e-5, as test_flash_attention.py
holds the bidirectional kernel to."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models.bert import dense_attention
from sparkdl_tpu.ops.flash_attention import (
    NEG_INF,
    dense_causal_attention,
    flash_attention,
    make_flash_attention_fn,
)

TOL = dict(atol=2e-5, rtol=2e-5)


def qkv(seed, B, H, Hkv, L, Dh, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(B, H, L, Dh)), dtype)
    k = jnp.asarray(r.normal(size=(B, Hkv, L, Dh)), dtype)
    v = jnp.asarray(r.normal(size=(B, Hkv, L, Dh)), dtype)
    return q, k, v


def dense(q, k, v, causal, mask=None):
    """Every query head against its own copy of its key/value head."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    L = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")
    s = s / np.sqrt(q.shape[-1])
    if mask is not None:
        s = s + mask[:, None, None, :]
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v, precision="highest"
    )


@pytest.mark.parametrize(
    "H, Hkv, L, block",
    [
        (4, 1, 256, 128),  # one shared head, two blocks: one skipped
        (4, 1, 200, 128),  # the sequence padded up to the block
        (4, 2, 192, 64),  # two groups of two; three blocks, three skipped
        (2, 2, 128, 32),  # causal alone, heads equal
    ],
)
def test_causal_and_shared_head_match_dense(H, Hkv, L, block):
    q, k, v = qkv(L + H, 2, H, Hkv, L, 32)
    got = flash_attention(
        q, k, v, block_q=block, block_k=block, interpret=True, causal=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense(q, k, v, True)), **TOL
    )


def test_shared_head_without_causal():
    q, k, v = qkv(1, 2, 6, 2, 96, 16)
    mask = np.zeros((2, 96), np.float32)
    mask[0, 70:] = NEG_INF
    mask = jnp.asarray(mask)
    got = flash_attention(q, k, v, mask, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense(q, k, v, False, mask)), **TOL
    )


def test_causal_with_a_key_mask():
    q, k, v = qkv(2, 2, 4, 1, 128, 32)
    mask = np.zeros((2, 128), np.float32)
    mask[1, 90:] = NEG_INF
    mask = jnp.asarray(mask)
    got = flash_attention(
        q, k, v, mask, block_q=64, block_k=64, interpret=True, causal=True
    )
    want = dense(q, k, v, True, mask)
    # a query past the mask still sees itself in the oracle's -inf form
    # and not in the kernel's large-negative one: compare the real ones
    np.testing.assert_allclose(
        np.asarray(got[1, :, :90]), np.asarray(want[1, :, :90]), **TOL
    )
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), **TOL)


def test_a_real_token_never_sees_a_later_pad():
    """Right padding needs no mask in a causal stack: what the real
    positions get does not depend on what lies after them."""
    q, k, v = qkv(3, 1, 4, 1, 128, 32)
    cut = 77
    k2 = jnp.concatenate([k[:, :, :cut], 50.0 + 0 * k[:, :, cut:]], axis=2)
    v2 = jnp.concatenate([v[:, :, :cut], 3 * v[:, :, cut:]], axis=2)
    kw = dict(block_q=64, block_k=64, interpret=True, causal=True)
    one = np.asarray(flash_attention(q, k, v, **kw))
    two = np.asarray(flash_attention(q, k2, v2, **kw))
    np.testing.assert_array_equal(one[:, :, :cut], two[:, :, :cut])
    assert np.abs(one[:, :, cut:] - two[:, :, cut:]).max() > 0.1


def test_dense_causal_fallback_is_the_same_function():
    q, k, v = qkv(4, 2, 4, 1, 64, 16)
    got = dense_causal_attention(q, k, v, None, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense(q, k, v, True)), **TOL
    )
    assert dense_causal_attention.kind == "dense"
    assert make_flash_attention_fn(causal=True) is dense_causal_attention
    fn = make_flash_attention_fn(block_q=32, block_k=32, interpret=True, causal=True)
    assert fn.kind == "flash"
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v, None, jnp.float32)), np.asarray(got), **TOL
    )


def test_what_the_causal_kernel_cannot_do_is_refused():
    q, k, v = qkv(5, 1, 4, 3, 64, 16)
    with pytest.raises(ValueError, match="4 query heads over 3"):
        flash_attention(q, k, v, interpret=True)
    q, k, v = qkv(5, 1, 2, 2, 64, 16)
    with pytest.raises(ValueError, match="square blocks"):
        flash_attention(q, k, v, block_q=32, block_k=64, interpret=True, causal=True)


def _kernel_jaxpr(**kw):
    q, k, v = qkv(6, 2, 4, kw.pop("Hkv", 4), 128, 32)
    mask = jnp.zeros((2, 128), jnp.float32)
    return str(
        jax.make_jaxpr(
            lambda q, k, v, m: flash_attention(q, k, v, m, block_q=64, block_k=64, **kw)
        )(q, k, v, mask)
    )


def test_bidirectional_equal_heads_kernel_is_untouched():
    """With `causal=False` and equal head counts the kernel traces to the
    program it was before either existed: no position arithmetic, no
    branch around the block, the plain index maps; and it still agrees
    with dense attention under a key mask. (That this jaxpr is to the
    letter the parent commit's was checked when the option was added:
    PERF.md, Findings, PR 28.)"""
    plain = _kernel_jaxpr()
    assert plain == _kernel_jaxpr(causal=False)
    assert "iota" not in plain and "min" not in plain.replace("reduce_min", "")
    # one branch each for the first and the last key block, none around
    # the block itself
    assert plain.count("cond[") == 2
    causal = _kernel_jaxpr(causal=True)
    assert "iota" in causal and causal.count("cond[") == 3
    assert causal != plain
    shared = _kernel_jaxpr(Hkv=1)
    assert shared != plain and "iota" not in shared

    q, k, v = qkv(7, 2, 4, 4, 96, 32)
    mask = np.zeros((2, 96), np.float32)
    mask[1, 60:] = NEG_INF
    mask = jnp.asarray(mask)
    got = flash_attention(q, k, v, mask, block_q=32, block_k=32, interpret=True)
    want = dense_attention(q, k, v, mask[:, None, None, :], jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


#: sha256 of the traced kernel call at the shapes that ran before the
#: online-softmax step was shared with the latent kernel, taken from the
#: parent commit (PR 32): the
#: hybrid family's causal 20 x 128 over one key/value head in blocks of
#: 512, bert's 12 x 64 padded to the lanes, and a ring shard's 4 x 32.
PINNED = {
    (2, 20, 1, 1024, 128, True, 512): "0a60b61336085f04",
    (2, 12, 12, 256, 64, False, 128): "c91f841b90097964",
    (1, 4, 4, 96, 32, False, 32): "11f3602d0556a1d0",
}


@pytest.mark.parametrize("shape", sorted(PINNED, key=str))
def test_existing_shapes_lower_to_what_they_were(shape):
    import hashlib

    B, H, Hkv, L, Dh, causal, block = shape
    q = jnp.zeros((B, H, L, Dh), jnp.bfloat16)
    k = jnp.zeros((B, Hkv, L, Dh), jnp.bfloat16)
    mask = jnp.zeros((B, L), jnp.float32)
    text = str(
        jax.make_jaxpr(
            lambda q, k, v, m: flash_attention(
                q, k, v, m, block_q=block, block_k=block, causal=causal
            )
        )(q, k, k, mask)
    )
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PINNED[shape]


# -- the causal kernel given its rows' lengths ----------------------------------


@pytest.mark.parametrize(
    "H, Hkv, L, block, lengths",
    [
        # a length inside a block, on a block's edge, the row's whole edge,
        # and 0 (a row that only fills the batch)
        (4, 1, 96, 16, (21, 32, 96, 0)),  # one shared head
        (4, 2, 96, 32, (1, 64, 96, 0)),  # two groups of two
        (2, 2, 128, 32, (100, 96, 128, 0)),  # heads equal
        (4, 2, 90, 16, (90, 48, 17, 0)),  # a row off the block size, padded to 96
    ],
)
def test_causal_with_lengths_matches_dense_at_every_real_position(H, Hkv, L, block, lengths):
    """Every real position is `dense_causal_attention`'s; every position
    of a live query block is the call without lengths' to the bit (the
    same steps in the same order); every position of a dead block is
    zero."""
    q, k, v = qkv(L + H + block, len(lengths), H, Hkv, L, 32)
    kw = dict(block_q=block, block_k=block, interpret=True, causal=True)
    got = np.asarray(flash_attention(q, k, v, lengths=jnp.asarray(lengths, jnp.int32), **kw))
    whole = np.asarray(flash_attention(q, k, v, **kw))
    want = np.asarray(dense_causal_attention(q, k, v, None, jnp.float32))
    assert got.shape == whole.shape and not np.isnan(got).any()
    for row, n in enumerate(lengths):
        live = min(-(-n // block) * block, L)
        np.testing.assert_allclose(got[row, :, :n], want[row, :, :n], **TOL)
        np.testing.assert_array_equal(got[row, :, :live], whole[row, :, :live])
        assert not got[row, :, live:].any(), (row, n)
    assert np.abs(got[0]).max() > 0.01  # the comparison is of something


@pytest.mark.parametrize("live", [0, 1, 3, 6])
def test_a_dead_causal_query_blocks_steps_fetch_nothing(live):
    """The causal kernel's index maps over one row's head of 6 query
    blocks, walked in the grid's order: a live block names its own query
    block and the key blocks up to its diagonal (clamped there above
    it); every step of a dead one names what the step before it named,
    the last live block's diagonal, so nothing is copied for it."""
    from sparkdl_tpu.ops.flash_attention import _resident

    nq = 6
    walked = [
        (qi, ki, int(_resident(qi, qi, live)), int(_resident(min(ki, qi), qi, live)))
        for qi in range(nq)
        for ki in range(nq)
    ]
    for n, (qi, ki, q_block, k_block) in enumerate(walked):
        if qi < live:
            assert (q_block, k_block) == (qi, min(ki, qi))
        elif n:
            assert (q_block, k_block) == walked[n - 1][2:], (live, n)
    assert walked[-1][2:] == (max(live, 1) - 1,) * 2


@pytest.mark.parametrize("window", [None, 64])
def test_the_kernel_with_lengths_is_one_call_of_the_same_name(window):
    """With lengths: the same one call and name, the live counts a
    prefetched operand; the branches are the call without lengths' (three
    in the causal kernel, as `test_bidirectional_equal_heads_kernel_is_untouched`
    counts them, five in the window kernel), each taken in a live query
    block alone (one `and` more a branch), and one more writes a dead
    block's zeros."""
    q, k, v = qkv(8, 2, 4, 2, 128, 32)

    def call(*lengths):
        return jax.make_jaxpr(
            lambda q, k, v, *n: flash_attention(
                q, k, v, block_q=32, block_k=32, causal=True, window=window,
                lengths=n[0] if n else None,
            )
        )(q, k, v, *lengths)

    def calls(jaxpr):
        return [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]

    (whole,) = calls(call())
    (by_length,) = calls(call(jnp.zeros((2,), jnp.int32)))
    assert by_length.params["name"] == whole.params["name"]
    assert whole.params["name"] == ("flash_attention_window" if window else "flash_attention")
    assert by_length.params["grid_mapping"].num_index_operands == 1
    assert whole.params["grid_mapping"].num_index_operands == 0
    def count(e, primitive):
        return sum(x.primitive.name == primitive for x in e.params["jaxpr"].eqns)

    assert count(by_length, "cond") == count(whole, "cond") + 1
    assert count(by_length, "and") == count(whole, "and") + count(whole, "cond") + 1


# -- latent attention over the projections' own arrays -------------------------


def latent_arrays(seed, B, L, H, nope, rope, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(B, L, H * (nope + rope))), dtype)
    kv = jnp.asarray(r.normal(size=(B, L, H * 2 * nope)), dtype)
    k_rope = jnp.asarray(r.normal(size=(B, L, rope)), dtype)
    return q, kv, k_rope


def latent_by_heads(q, kv, k_rope, H, scale):
    """The same attention written out head by head: heads split out, the
    shared rotary key repeated for every head, float32 throughout."""
    B, L, _ = q.shape
    rope = k_rope.shape[2]
    q = q.reshape(B, L, H, -1).transpose(0, 2, 1, 3)
    kv = kv.reshape(B, L, H, -1).transpose(0, 2, 1, 3)
    nope = q.shape[3] - rope
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[:, None], (B, H, L, rope))], -1
    )
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), kv[..., nope:])
    return o.transpose(0, 2, 1, 3).reshape(B, L, -1)


def _selection(kind, B, L, seed):
    """None; every key at or before the query; or the eight keys a query
    scores highest at random among those (all of them for its first
    eight): what `ops/dsa_indexer.py` hands over, a byte a pair."""
    if kind is None:
        return None
    seen = np.tril(np.ones((L, L), bool))
    if kind == "top-k":
        scores = np.where(seen, np.random.default_rng(seed).normal(size=(B, L, L)), -np.inf)
        kth = np.sort(scores, -1)[..., ::-1][..., min(7, L - 1)]
        seen = seen & (scores >= kth[..., None])
    return jnp.asarray(np.broadcast_to(seen, (B, L, L)).astype(np.int8))


@pytest.mark.parametrize("selection", [None, "causal", "top-k"])
@pytest.mark.parametrize(
    "H, L, nope, rope, block, tile",
    [
        (3, 200, 128, 128, 64, None),  # the sizes the chip runs; a length off the block
        (4, 200, 128, 64, 128, None),  # the published 128 + 64 over values of 128
        (128, 64, 16, 16, 32, None),  # the published head count
        (4, 333, 32, 16, 64, None),  # six blocks, the last part-filled
        (4, 192, 128, 64, 64, None),  # whole blocks, the diagonal's alone masked
        (1, 130, 128, 64, 128, None),  # one head, two blocks, the second nearly empty
        # a block walked in 2, 4 and 8 key sub-tiles: one block exactly, one
        # part-filled (its padded queries attend to key 0, in sub-tile 0),
        # and several with the last part-filled
        (2, 128, 32, 16, 128, 64),
        (2, 100, 32, 16, 128, 64),
        (2, 300, 32, 16, 128, 64),
        (2, 128, 32, 16, 128, 32),
        (2, 77, 32, 16, 128, 32),
        (2, 333, 32, 16, 128, 32),
        (2, 128, 32, 16, 128, 16),
        (2, 50, 32, 16, 128, 16),
        (2, 400, 32, 16, 128, 16),
    ],
)
def test_latent_kernel_matches_dense_over_split_heads(
    monkeypatch, H, L, nope, rope, block, tile, selection
):
    from sparkdl_tpu.ops import flash_attention as ops

    if tile is None:  # the module's own: these blocks are one sub-tile each
        assert ops._key_tile(block) == block
    else:
        monkeypatch.setattr(ops, "LATENT_KEY_TILE", tile)
        assert ops._key_tile(block) == tile
    B = 2 if H < 8 else 1
    q, kv, k_rope = latent_arrays(H + L, B, L, H, nope, rope)
    chosen = _selection(selection, B, L, H + L)
    scale = 0.07
    got = ops.flash_attention_latent(
        q, kv, k_rope, chosen, num_heads=H, scale=scale, block=block, interpret=True
    )
    assert got.shape == (B, L, H * nope)
    with jax.default_matmul_precision("highest"):
        plain = ops.dense_latent_attention(
            q, kv, k_rope, jnp.float32, chosen, num_heads=H, scale=scale
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain), **TOL)
        if selection != "top-k":  # a causal-everything selection is causal attention
            want = latent_by_heads(q, kv, k_rope, H, scale)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def _latent_branches(selected, block=128):
    """The traced kernel's two branches that do work, as text: a block
    under the diagonal and one on it, from a trace of two blocks a side."""
    from sparkdl_tpu.ops import flash_attention as ops

    q, kv, k_rope = latent_arrays(3, 1, 2 * block, 2, 32, 16)
    args = [q, kv, k_rope]
    if selected:
        args.append(jnp.ones((1, 2 * block, 2 * block), jnp.int8))
    jaxpr = jax.make_jaxpr(
        lambda *a: ops.flash_attention_latent(*a, num_heads=2, scale=0.1, block=block)
    )(*args)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    conds = [e for e in call.params["jaxpr"].eqns if e.primitive.name == "cond"]
    # the first key block, under the diagonal, on it, the last key block
    assert len(conds) == 4
    # a `cond` on a boolean holds (not taken, taken)
    return [str(e.params["branches"][1]) for e in conds[1:3]]


def test_latent_kernel_runs_and_masks_only_what_the_diagonal_leaves(monkeypatch):
    """The traced kernel's text at four sub-tiles a block. Under the
    diagonal every sub-tile meets every query row and, without a
    selection, nothing is compared: no `iota`. On the diagonal there are
    as many products as sub-tiles, over falling row counts, and the
    compare falls on [tile, tile] squares alone. A selection's bytes
    are read wherever a sub-tile runs."""
    import re

    from sparkdl_tpu.ops import flash_attention as ops

    monkeypatch.setattr(ops, "LATENT_KEY_TILE", 32)

    def rows_of_products(text):
        return [int(r) for r in re.findall(r"f32\[(\d+),32\] = dot_general", text)]

    under, diagonal = _latent_branches(selected=False)
    assert "iota" not in under and "select_n" not in under
    # nope 32 = dv = tile here: a sub-tile's three products (the score's
    # two and p . v) each write [rows, 32]
    assert rows_of_products(under) == [128] * 12
    assert rows_of_products(diagonal) == [128] * 3 + [96] * 3 + [64] * 3 + [32] * 3
    assert diagonal.count("iota") == 8  # a row and a column index a crossed square
    assert set(re.findall(r"bool\[(\d+,\d+)\] = le", diagonal)) == {"32,32"}

    under, diagonal = _latent_branches(selected=True)
    assert "iota" not in under and "iota" not in diagonal
    assert rows_of_products(under) == [128] * 12
    assert rows_of_products(diagonal) == [128] * 3 + [96] * 3 + [64] * 3 + [32] * 3
    assert re.findall(r"bool\[(\d+),32\] = ne", under) == ["128"] * 4
    assert re.findall(r"bool\[(\d+),32\] = ne", diagonal) == ["128", "96", "64", "32"]


@pytest.mark.parametrize(
    "length, block, tile, blocks",
    [
        (2048, 1024, 256, 2.25),  # one block whole, two diagonals at 5/8
        (1024, 1024, 256, 0.625),
        (16384, 1024, 256, 130),  # 120 under the diagonal, 16 on it
        (8192, 1024, 256, 33),
        (2000, 1024, 256, 2.25),  # a length off the block runs its padding too
        (1024, 1024, 1024, 1),  # one sub-tile a block: the square, as before
        (2048, 1024, 128, 2.125),
        (300, 128, 32, 3 + 3 * 0.625),
        # a row's length inside a block: the block that holds its last
        # real token runs whole, those past it not at all
        (0, 1024, 256, 0),
        (1, 1024, 256, 0.625),
        (1025, 1024, 256, 2.25),
        (2690, 1024, 256, 3 + 3 * 0.625),  # the claimed cell's shortest row
        (8408, 1024, 256, 36 + 9 * 0.625),  # and the 16,384 bucket's shortest
        (12494, 1024, 256, 78 + 13 * 0.625),
        (129, 128, 32, 1 + 2 * 0.625),
    ],
)
def test_pairs_the_latent_kernel_computes_by_hand(length, block, tile, blocks):
    from sparkdl_tpu.ops.flash_attention import latent_pairs_computed

    assert latent_pairs_computed(length, block, tile) == blocks * block * block


def test_latent_kernel_moves_nothing_in_hbm_and_says_what_it_cannot_do():
    from sparkdl_tpu.ops.flash_attention import (
        flash_attention_latent,
        make_latent_attention_fn,
    )

    q, kv, k_rope = latent_arrays(11, 1, 128, 2, 128, 128)
    text = str(
        jax.make_jaxpr(
            lambda *a: flash_attention_latent(*a, num_heads=2, scale=0.1, block=64)
        )(q, kv, k_rope)
    )
    # the call and nothing beside it: no pad, transpose, concatenate, broadcast
    for moved in ("pad", "transpose", "concatenate", "broadcast_in_dim[\n"):
        assert moved not in text.split("pallas_call")[0], moved
    with pytest.raises(ValueError, match="Dn == Dv"):
        flash_attention_latent(
            q, kv[..., :384], k_rope, num_heads=2, scale=0.1, interpret=True
        )
    fn = make_latent_attention_fn(2, 0.1)
    assert fn.kind == "dense"  # the tests run on the CPU
    kernel = make_latent_attention_fn(2, 0.1, block=64, interpret=True)
    assert kernel.kind == "flash"
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            np.asarray(kernel(q, kv, k_rope, jnp.float32)),
            np.asarray(fn(q, kv, k_rope, jnp.float32)),
            **TOL,
        )


# -- the latent kernel given its rows' lengths ----------------------------------

#: lengths 0, 1, a block's edge, a block's edge + 1 and the whole row, in
#: blocks of 128 over 384 positions: 0, 1, 1, 2 and 3 live query blocks
LENGTHS = (0, 1, 128, 129, 384)


@pytest.mark.parametrize("tile", [None, 32, 64])
@pytest.mark.parametrize("selection", [None, "causal", "top-k"])
def test_latent_kernel_with_lengths_runs_no_block_of_padding(monkeypatch, selection, tile):
    """The live positions (every position of a query block that holds a
    real token) are `lengths=None`'s bit for bit, every position of a
    dead block is exactly zero, and nothing is NaN."""
    from sparkdl_tpu.ops import flash_attention as ops

    if tile is not None:  # blocks of 128 walked in 4 and 2 key sub-tiles
        monkeypatch.setattr(ops, "LATENT_KEY_TILE", tile)
    B, L, H, block = len(LENGTHS), LENGTHS[-1], 2, 128
    q, kv, k_rope = latent_arrays(17, B, L, H, 32, 16)
    chosen = _selection(selection, B, L, 17)
    run = functools.partial(
        ops.flash_attention_latent, q, kv, k_rope, chosen, num_heads=H, scale=0.07,
        block=block, interpret=True,
    )
    whole = np.asarray(run())
    got = np.asarray(run(jnp.asarray(LENGTHS, jnp.int32)))
    assert got.shape == whole.shape and not np.isnan(got).any()
    for row, length in enumerate(LENGTHS):
        live = -(-length // block) * block
        np.testing.assert_array_equal(got[row, :live], whole[row, :live])
        assert not got[row, live:].any(), (row, length)
    assert np.abs(got[-1]).max() > 0.01  # the comparison is of something


def test_latent_kernel_with_lengths_off_the_block_size():
    """A row length off the block size (the arrays are padded to 256) and a
    length that ends in the padded block: still the live blocks alone."""
    from sparkdl_tpu.ops.flash_attention import flash_attention_latent

    q, kv, k_rope = latent_arrays(19, 2, 200, 2, 32, 16)
    chosen = _selection("top-k", 2, 200, 19)
    kw = dict(num_heads=2, scale=0.07, block=128, interpret=True)
    whole = np.asarray(flash_attention_latent(q, kv, k_rope, chosen, **kw))
    got = np.asarray(
        flash_attention_latent(q, kv, k_rope, chosen, jnp.asarray([100, 200], jnp.int32), **kw)
    )
    assert got.shape == (2, 200, 64)
    np.testing.assert_array_equal(got[0, :128], whole[0, :128])
    assert not got[0, 128:].any()
    np.testing.assert_array_equal(got[1], whole[1])


#: sha256 of the traced call without lengths, taken from the parent commit
#: (PR 36) at (B, L, H, nope, rope, block, selected): two of the cells'
#: blocks of 1,024 in sub-tiles of 256, and two part-filled blocks of 128
PINNED_LATENT = {
    (1, 2048, 4, 128, 128, 1024, True): "a3934789b54e1124",
    (2, 2048, 4, 128, 128, 1024, False): "bed3b94fafb2bd1e",
    (1, 300, 2, 32, 16, 128, False): "70ecc565c7c748fc",
    (1, 300, 2, 32, 16, 128, True): "afcba3c4abca6fca",
}


def _latent_jaxpr(shape, lengths=False):
    from sparkdl_tpu.ops.flash_attention import flash_attention_latent

    B, L, H, nope, rope, block, selected = shape
    bf16 = jnp.bfloat16
    args = [
        jnp.zeros((B, L, H * (nope + rope)), bf16),
        jnp.zeros((B, L, H * 2 * nope), bf16),
        jnp.zeros((B, L, rope), bf16),
        jnp.zeros((B, L, L), jnp.int8) if selected else None,
    ]
    if lengths:
        args.append(jnp.zeros((B,), jnp.int32))
    return str(
        jax.make_jaxpr(
            lambda *a: flash_attention_latent(*a, num_heads=H, scale=0.1, block=block)
        )(*args)
    )


@pytest.mark.parametrize("shape", sorted(PINNED_LATENT, key=str))
def test_latent_kernel_without_lengths_lowers_to_what_it_did(shape):
    """`lengths=None` is the parent's call to the letter: no prefetched
    operand, no live test in the kernel. With lengths the text differs
    and holds them. (The Mosaic module compiled for a described v5e at
    the four shapes the cells run is the parent's too, debug locations
    aside: PERF.md, Findings, PR 37.)"""
    import hashlib

    text = _latent_jaxpr(shape)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PINNED_LATENT[shape]
    prefetched = f"Ref<smem>{{i32[{shape[0]}]}}"  # a row's live query blocks
    assert prefetched not in text
    assert prefetched in _latent_jaxpr(shape, lengths=True)


@pytest.mark.parametrize(
    "lengths, said",
    [
        (np.zeros((3,), np.int32), r"lengths .* is \[B\] int32, got \(3,\) int32"),
        (np.zeros((2, 1), np.int32), r"lengths .* got \(2, 1\) int32"),
        (np.zeros((2,), np.float32), r"lengths .* got \(2,\) float32"),
        (np.zeros((2,), np.int8), r"lengths .* is \[B\] int32, got \(2,\) int8"),
    ],
)
def test_latent_kernel_refuses_lengths_of_another_shape_or_type(lengths, said):
    from sparkdl_tpu.ops.flash_attention import flash_attention_latent

    q, kv, k_rope = latent_arrays(23, 2, 128, 2, 32, 16)
    with pytest.raises(ValueError, match=said):
        flash_attention_latent(
            q, kv, k_rope, None, jnp.asarray(lengths), num_heads=2, scale=0.1,
            block=64, interpret=True,
        )


def test_the_built_kernel_says_it_takes_lengths_and_the_dense_one_does_not():
    from sparkdl_tpu.ops.flash_attention import make_latent_attention_fn

    dense = make_latent_attention_fn(2, 0.1)
    assert dense.kind == "dense"  # the tests run on the CPU
    assert not hasattr(dense, "takes_lengths") and not hasattr(dense, "query_blocks")
    kernel = make_latent_attention_fn(2, 0.1, block=64, interpret=True)
    assert kernel.takes_lengths is True
    assert [kernel.query_blocks(n) for n in (0, 1, 64, 65, 200)] == [0, 1, 1, 2, 4]
    assert kernel.pairs_computed(65) == kernel.pairs_computed(128)
    q, kv, k_rope = latent_arrays(29, 2, 128, 2, 32, 16)
    whole = np.asarray(kernel(q, kv, k_rope, jnp.float32))
    got = np.asarray(
        kernel(q, kv, k_rope, jnp.float32, lengths=jnp.asarray([64, 0], jnp.int32))
    )
    np.testing.assert_array_equal(got[0, :64], whole[0, :64])
    assert not got[0, 64:].any() and not got[1].any()
