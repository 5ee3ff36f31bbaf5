"""The lightning indexer's two steps (ops/dsa_indexer.py) and the
selecting tiling of latent attention (ops/flash_attention.py), the
interpreted kernels against `jax.numpy` and both against an oracle
written with numpy's sort: lengths off the block size, blocks wider than
tall and the reverse, and a selection with ties at a query's last place."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops import dsa_indexer
from sparkdl_tpu.ops.flash_attention import (
    dense_latent_attention,
    flash_attention_latent,
    make_latent_attention_fn,
)


def _operands(rows, length, heads, dim, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((rows, length, heads * dim)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((rows, length, dim)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((rows, length, heads)), jnp.float32)
    return q, k, w


def _scores_by_hand(q, k, w, heads):
    q, k, w = (np.asarray(t, np.float64) for t in (q, k, w))
    rows, length, _ = q.shape
    q = q.reshape(rows, length, heads, -1)
    out = np.zeros((rows, length, length))
    for j in range(heads):
        out += np.maximum(np.einsum("bqd,bkd->bqk", q[:, :, j], k), 0.0) * w[:, :, j, None]
    return out


def _selection_by_hand(scores, top_k):
    """The stated rule: of a query's causal keys the `top_k` of largest
    score, of equal scores the lower position first."""
    scores = np.asarray(scores)
    out = np.zeros(scores.shape, np.int8)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            row = scores[b, t, : t + 1] + 0.0  # -0.0 equals 0.0
            order = np.lexsort((np.arange(t + 1), -row))[:top_k]
            out[b, t, order] = 1
    return out


@pytest.mark.parametrize(
    "length, block_q, block_k",
    [(64, 32, 32), (200, 32, 64), (200, 64, 32), (100, 128, 128), (130, 16, 64)],
)
def test_index_scores_kernel_is_the_sum_over_heads(length, block_q, block_k):
    heads, dim = 4, 16
    q, k, w = _operands(2, length, heads, dim)
    want = _scores_by_hand(q, k, w, heads)
    plain = dsa_indexer.index_scores(q, k, w, num_heads=heads)
    got = dsa_indexer.dsa_index_scores(
        q, k, w, num_heads=heads, block_q=block_q, block_k=block_k, interpret=True
    )
    assert got.shape == (2, length, length) and got.dtype == jnp.float32
    causal = np.tril(np.ones((length, length), bool))
    np.testing.assert_allclose(np.asarray(plain), want, atol=2e-4)
    # above the diagonal the kernel's result is unspecified
    np.testing.assert_allclose(np.where(causal, np.asarray(got), 0), np.where(causal, want, 0), atol=2e-4)


def test_index_scores_takes_its_operands_as_they_come():
    """bfloat16 operands, float32 accumulation and weights."""
    heads, dim = 4, 16
    q, k, w = _operands(1, 96, heads, dim, seed=1)
    q, k = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16)
    want = _scores_by_hand(q.astype(jnp.float32), k.astype(jnp.float32), w, heads)
    got = dsa_indexer.dsa_index_scores(
        q, k, w, num_heads=heads, block_q=32, block_k=32, interpret=True
    )
    causal = np.tril(np.ones((96, 96), bool))
    np.testing.assert_allclose(np.where(causal, np.asarray(got), 0), np.where(causal, want, 0), atol=1e-3)
    with pytest.raises(ValueError, match="index scores over 4 heads"):
        dsa_indexer.dsa_index_scores(q, k[:, :, :8], w, num_heads=heads, interpret=True)


def _tied_scores(rows, length, seed):
    """Scores on a grid of halves, so that many are equal, some of them
    -0.0, and one key column all zeros."""
    rng = np.random.default_rng(seed)
    scores = np.round(rng.standard_normal((rows, length, length)) * 2) / 2
    scores[:, :, 5] = -0.0
    return scores.astype(np.float32)


@pytest.mark.parametrize("how", ["jnp", "pallas"])
@pytest.mark.parametrize(
    "length, top_k, block_q, scores",
    [
        (200, 24, 64, "random"),
        (200, 24, 64, "tied"),
        (96, 16, 32, "tied"),
        (130, 130, 64, "random"),  # nothing to select: the causal triangle
        (64, 1, 32, "tied"),
        (160, 40, 32, "constant"),  # every score equal: the first top_k keys
    ],
)
def test_selection_is_the_stated_rule(how, length, top_k, block_q, scores):
    if scores == "random":
        values = np.random.default_rng(0).standard_normal((2, length, length)).astype(np.float32)
    elif scores == "tied":
        values = _tied_scores(2, length, 1)
    else:
        values = np.full((2, length, length), 0.25, np.float32)
    want = _selection_by_hand(values, top_k)
    if how == "jnp":
        got = dsa_indexer.select_keys(jnp.asarray(values), top_k=top_k, block_q=block_q)
    else:
        got = dsa_indexer.dsa_select(
            jnp.asarray(values), top_k=top_k, block_q=block_q, chunk=128, interpret=True
        )
    assert got.dtype == jnp.int8 and got.shape == values.shape
    assert (np.asarray(got) == want).all()
    # every query has min(top_k, t + 1) keys, none above the diagonal
    count = np.asarray(got).sum(-1)
    assert (count == np.minimum(top_k, np.arange(length) + 1)).all()
    if scores == "constant":
        assert (np.asarray(got)[0, -1, :top_k] == 1).all()


def test_lax_top_k_has_the_stated_tie_rule():
    """What the plain reference selects with."""
    values = _tied_scores(1, 120, 2)
    values = np.where(values == 0.0, 0.0, values)  # as the reference does
    causal = np.tril(np.ones((120, 120), bool))
    _, best = jax.lax.top_k(jnp.where(causal, values, -jnp.inf), 20)
    got = np.zeros(values.shape, np.int8)
    np.put_along_axis(got, np.asarray(best), 1, -1)
    assert ((got & causal) == _selection_by_hand(values, 20)).all()


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "pallas"])
def test_the_built_indexer_is_scores_then_selection(interpret):
    heads, dim, top_k = 4, 16, 12
    q, k, w = _operands(2, 80, heads, dim, seed=3)
    fn = dsa_indexer.make_indexer_fn(heads, top_k, interpret=interpret)
    assert fn.kind == ("pallas" if interpret else "jnp")
    want = _selection_by_hand(_scores_by_hand(q, k, w, heads).astype(np.float32), top_k)
    got = np.asarray(fn(q, k, w))
    # float32 sums in another order may swap two nearly equal scores
    assert (got != want).sum() <= 4
    assert (got.sum(-1) == np.minimum(top_k, np.arange(80) + 1)).all()


# -- the selecting attention ---------------------------------------------------


def _attention_operands(rows, length, heads, nope, rope, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((rows, length, heads * (nope + rope))), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((rows, length, heads * 2 * nope)), jnp.float32)
    k_rope = jnp.asarray(rng.standard_normal((rows, length, rope)), jnp.float32)
    return q, kv, k_rope


def _attention_by_hand(q, kv, k_rope, selection, heads, scale):
    q, kv, k_rope = (np.asarray(t, np.float64) for t in (q, kv, k_rope))
    rows, length, _ = q.shape
    rope = k_rope.shape[2]
    q = q.reshape(rows, length, heads, -1)
    nope = q.shape[3] - rope
    kv = kv.reshape(rows, length, heads, -1)
    out = np.zeros((rows, length, heads, nope))
    for b in range(rows):
        for h in range(heads):
            s = q[b, :, h, :nope] @ kv[b, :, h, :nope].T + q[b, :, h, nope:] @ k_rope[b].T
            s = np.where(np.asarray(selection[b]) != 0, s * scale, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b, :, h] = (p / p.sum(-1, keepdims=True)) @ kv[b, :, h, nope:]
    return out.reshape(rows, length, -1)


@pytest.mark.parametrize(
    "length, block, top_k", [(128, 64, 16), (200, 64, 24), (100, 128, 100), (72, 32, 5)]
)
def test_selecting_attention_reads_the_selected_keys_only(length, block, top_k):
    heads, nope, rope = 4, 16, 8
    q, kv, k_rope = _attention_operands(2, length, heads, nope, rope)
    scores = np.random.default_rng(5).standard_normal((2, length, length)).astype(np.float32)
    selection = jnp.asarray(_selection_by_hand(scores, top_k))
    want = _attention_by_hand(q, kv, k_rope, selection, heads, 0.2)
    dense = dense_latent_attention(
        q, kv, k_rope, jnp.float32, selection, num_heads=heads, scale=0.2
    )
    flash = flash_attention_latent(
        q, kv, k_rope, selection, num_heads=heads, scale=0.2, block=block, interpret=True
    )
    np.testing.assert_allclose(np.asarray(dense), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(flash), want, atol=2e-5)
    with pytest.raises(ValueError, match="a selection for q"):
        flash_attention_latent(
            q, kv, k_rope, selection[:, :-1], num_heads=heads, scale=0.2, interpret=True
        )


@pytest.mark.parametrize("interpret", [False, True], ids=["dense", "flash"])
def test_a_selection_of_every_causal_key_is_causal_attention(interpret):
    """The built function with a selection that binds nowhere gives what
    it gives without one. The dense function's arithmetic is the same,
    the causal mask alone taken from another operand: to the bit. The
    kernel without a selection leaves a block under the diagonal
    unmasked and masks a diagonal one on the crossed squares alone,
    where a selection's bytes are read throughout: the same values from
    another program, so to the last bit or two."""
    heads, nope, rope, length = 4, 16, 8, 128
    q, kv, k_rope = _attention_operands(1, length, heads, nope, rope, seed=2)
    fn = make_latent_attention_fn(heads, 0.2, block=64, interpret=interpret)
    everything = jnp.tril(jnp.ones((1, length, length), jnp.int8))
    with_selection = fn(q, kv, k_rope, jnp.float32, everything)
    without = fn(q, kv, k_rope, jnp.float32)
    if interpret:
        np.testing.assert_allclose(
            np.asarray(with_selection), np.asarray(without), rtol=0, atol=5e-7
        )
    else:
        assert (np.asarray(with_selection) == np.asarray(without)).all()


# -- the rows' lengths ---------------------------------------------------------

#: a bucket of 512 at the kernels' own blocks: 256 x 512 for the scores,
#: 64 queries over chunks of 512 for the selection
BUCKET, TOP_K = 512, 24


@pytest.fixture(scope="module")
def whole_bucket():
    """Two rows' operands and the selection the kernels give without
    lengths: what the parent commit gave."""
    heads, dim = 4, 16
    q, k, w = _operands(2, BUCKET, heads, dim, seed=7)
    scores = dsa_indexer.dsa_index_scores(q, k, w, num_heads=heads, interpret=True)
    blind = dsa_indexer.dsa_select(scores, top_k=TOP_K, interpret=True)
    return (q, k, w), np.asarray(blind)


@pytest.mark.parametrize(
    "length",
    [0, 1, 100, 128, 256, BUCKET],
    ids=["padding", "one", "inside-a-block", "64-edge", "256-edge", "whole-bucket"],
)
def test_with_lengths_every_real_query_selects_as_without(whole_bucket, length):
    """The built indexer handed the rows' lengths: a row of `length` real
    tokens beside one of the whole bucket. Every query of a live 64-query
    block (those before the length among them) selects what it selects
    without lengths, to the bit; every query of a block past the length
    selects itself alone, so that no row of the selection is empty; each
    row runs its own count of blocks."""
    (q, k, w), blind = whole_bucket
    fn = dsa_indexer.make_indexer_fn(4, TOP_K, interpret=True)
    assert fn.takes_lengths
    assert fn.query_blocks(length) == -(-length // dsa_indexer.SCORES_BLOCK_Q)
    lengths = jnp.asarray([length, BUCKET], jnp.int32)
    got = np.asarray(fn(q, k, w, lengths=lengths))
    assert got.dtype == np.int8 and got.shape == blind.shape
    np.testing.assert_array_equal(got[1], blind[1])
    live = -(-length // 64) * 64
    np.testing.assert_array_equal(got[0, :live], blind[0, :live])
    np.testing.assert_array_equal(got[0, live:], np.eye(BUCKET, dtype=np.int8)[live:])
    assert (got.sum(-1) >= 1).all()
    assert (np.triu(got, 1) == 0).all()


def test_the_scores_of_a_live_block_are_the_parents_and_a_dead_one_is_not_read():
    """The scores kernel handed lengths gives a live query block's causal
    scores as without them, to the bit; the selection reads nothing of a
    dead block's rows (NaN put there changes nothing), and a row of
    padding alone selects the diagonal whatever its scores."""
    heads, dim = 4, 16
    q, k, w = _operands(3, BUCKET, heads, dim, seed=8)
    lengths = jnp.asarray([0, 200, BUCKET], jnp.int32)
    blind = np.asarray(dsa_indexer.dsa_index_scores(q, k, w, num_heads=heads, interpret=True))
    got = np.asarray(
        dsa_indexer.dsa_index_scores(q, k, w, lengths, num_heads=heads, interpret=True)
    )
    causal = np.tril(np.ones((BUCKET, BUCKET), bool))
    for row, live in ((1, 256), (2, BUCKET)):
        np.testing.assert_array_equal(got[row, :live][causal[:live]], blind[row, :live][causal[:live]])
    poisoned = blind.copy()
    poisoned[0] = np.nan
    poisoned[1, 256:] = np.nan
    want = dsa_indexer.dsa_select(jnp.asarray(blind), lengths, top_k=TOP_K, interpret=True)
    got = dsa_indexer.dsa_select(jnp.asarray(poisoned), lengths, top_k=TOP_K, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got)[0], np.eye(BUCKET, dtype=np.int8))
    with pytest.raises(ValueError, match=r"lengths for 1 rows are \[1\] int32"):
        dsa_indexer.dsa_select(jnp.asarray(blind[:1]), lengths[:2], top_k=TOP_K)
