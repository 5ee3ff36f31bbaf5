"""Pallas flash attention vs dense attention — numerics parity.

Runs the real kernel through the Pallas interpreter on the CPU test mesh
(SURVEY.md §5 testing model: real code, tiny shapes)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models.bert import dense_attention
from sparkdl_tpu.ops.flash_attention import (
    NEG_INF,
    flash_attention,
    flash_attention_packed,
    make_flash_attention_fn,
    packs,
)


def _qkv(rng, B=2, H=4, L=64, Dh=32):
    def t(seed):
        return jnp.asarray(
            rng.normal(size=(B, H, L, Dh)), dtype=jnp.float32
        )

    return t(0), t(1), t(2)


def test_matches_dense_no_mask(rng):
    q, k, v = _qkv(rng)
    ours = flash_attention(
        q, k, v, block_q=32, block_k=32, interpret=True
    )
    ref = dense_attention(q, k, v, None, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ours), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_matches_dense_with_padding_mask(rng):
    q, k, v = _qkv(rng, B=2, L=48)
    lengths = [31, 48]
    mask = np.zeros((2, 48), np.float32)
    for b, n in enumerate(lengths):
        mask[b, n:] = NEG_INF
    mask_j = jnp.asarray(mask)
    ours = flash_attention(
        q, k, v, mask_j, block_q=16, block_k=16, interpret=True
    )
    ref = dense_attention(
        q, k, v, mask_j[:, None, None, :], jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(ours), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_non_multiple_lengths_padded(rng):
    # L=40 with 32-blocks forces internal padding on q and k
    q, k, v = _qkv(rng, B=1, H=2, L=40, Dh=16)
    ours = flash_attention(
        q, k, v, block_q=32, block_k=32, interpret=True
    )
    ref = dense_attention(q, k, v, None, jnp.float32)
    assert ours.shape == ref.shape
    np.testing.assert_allclose(
        np.asarray(ours), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_bfloat16_io(rng):
    q, k, v = _qkv(rng, L=32, Dh=16)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = flash_attention(
        q, k, v, block_q=16, block_k=16, interpret=True
    )
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v, None, jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(ref, dtype=np.float32),
        atol=3e-2,
        rtol=3e-2,
    )


def test_attention_fn_plugs_into_bert(rng):
    from sparkdl_tpu.models.bert import BertConfig, BertEncoder

    cfg = BertConfig(
        vocab_size=64,
        hidden_size=32,
        num_layers=1,
        num_heads=2,
        intermediate_size=64,
        max_position_embeddings=32,
    )
    ids = jnp.asarray(rng.integers(0, 64, size=(2, 16)), dtype=jnp.int32)
    enc_dense = BertEncoder(config=cfg)
    params = enc_dense.init(jax.random.PRNGKey(0), ids)
    out_dense = enc_dense.apply(params, ids)
    enc_flash = BertEncoder(
        config=cfg,
        attention_fn=make_flash_attention_fn(
            block_q=8, block_k=8, interpret=True
        ),
    )
    out_flash = enc_flash.apply(params, ids)
    np.testing.assert_allclose(
        np.asarray(out_dense), np.asarray(out_flash), atol=1e-4, rtol=1e-4
    )


def test_batched_mask_at_production_blocks(rng):
    """B > 1 with a different mask per row, at the (128, 128) blocks and
    Dh=64 lane padding the compiled kernel uses, masked the way
    BertEncoder masks (finfo.min): the mask's [B, 1, Lk] blocking must
    hand each (batch, head) program its own batch row's keys."""
    B, H, L, Dh = 3, 2, 256, 64
    q, k, v = _qkv(rng, B=B, H=H, L=L, Dh=Dh)
    mask = np.zeros((B, L), np.float32)
    for b, n in enumerate([256, 130, 7]):
        mask[b, n:] = np.finfo(np.float32).min
    mask_j = jnp.asarray(mask)[:, None, None, :]
    ours = flash_attention(q, k, v, mask_j, interpret=True)
    ref = dense_attention(q, k, v, mask_j, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ours), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_attention_choice_is_made_at_build_and_recorded():
    """Off-TPU the default builder hands back dense_attention itself
    (kind 'dense'); interpret mode is only ever asked for, and a text
    model function says which attention it was built with."""
    from sparkdl_tpu.models.bert import bert_model_function

    assert make_flash_attention_fn() is dense_attention
    assert dense_attention.kind == "dense"
    assert make_flash_attention_fn(interpret=True).kind == "flash"
    assert bert_model_function(size="tiny", max_length=16).attention == "dense"
    forced = bert_model_function(
        size="tiny",
        max_length=16,
        attention_fn=make_flash_attention_fn(interpret=True),
    )
    assert forced.attention == "flash"


def _split(t, heads):  # [B, L, H*Dh] -> [B, H, L, Dh]
    B, L, D = t.shape
    return t.reshape(B, L, heads, D // heads).transpose(0, 2, 1, 3)


# B, H, Dh, L, blocks (q, k), live keys a row (None: all), the mask's fill
PACKED_CASES = {
    "bert_base_heads_padded_keys": (2, 12, 64, 128, (128, 128), [101, 128], NEG_INF),
    "four_heads_to_a_tile": (2, 4, 32, 64, (32, 32), None, NEG_INF),
    "two_key_blocks": (2, 2, 64, 256, (128, 128), [256, 130], NEG_INF),
    "four_key_blocks": (1, 2, 64, 512, (128, 128), [300], NEG_INF),
    "rows_the_step_does_not_divide": (3, 2, 64, 32, (32, 32), [32, 7, 20], NEG_INF),
    "length_off_the_block": (2, 4, 32, 40, (32, 32), [40, 33], NEG_INF),
    "a_fully_masked_row": (2, 2, 64, 32, (32, 32), [0, 32], NEG_INF),
    "masked_as_bert_masks": (2, 2, 64, 256, (128, 128), [130, 7], float(np.finfo(np.float32).min)),
}


@pytest.mark.parametrize("case", PACKED_CASES)
def test_packed_matches_dense(rng, case):
    """The packed kernel (interpreted) on [B, L, H*Dh], the projections'
    own layout, against dense attention on the heads split out."""
    B, H, Dh, L, (bq, bk), live, fill = PACKED_CASES[case]
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, L, H * Dh)), jnp.float32) for _ in range(3)
    )
    mask = np.zeros((B, L), np.float32)
    for b, n in enumerate(live or []):
        mask[b, n:] = fill
    mask = jnp.asarray(mask)[:, None, None, :]
    ours = flash_attention_packed(
        q, k, v, mask, num_heads=H, block_q=bq, block_k=bk, interpret=True
    )
    ref = dense_attention(
        _split(q, H), _split(k, H), _split(v, H), mask, jnp.float32
    )
    assert ours.shape == (B, L, H * Dh)
    np.testing.assert_allclose(
        np.asarray(_split(ours, H)), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_packed_row_with_no_key_is_the_blocked_kernels():
    """Every key at finfo.min: both kernels start the softmax from the
    same floor (NEG_INF), so both answer zeros, with one key block
    (no scratch) and with two."""
    B, H, Dh, L = 1, 2, 64, 64
    t = jnp.ones((B, L, H * Dh), jnp.float32)
    mask = jnp.full((B, L), np.finfo(np.float32).min, jnp.float32)
    blocked = flash_attention(
        _split(t, H), _split(t, H), _split(t, H), mask,
        block_q=32, block_k=32, interpret=True,
    )
    for bk in (64, 32):
        ours = flash_attention_packed(
            t, t, t, mask, num_heads=H, block_q=32, block_k=bk, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(ours), 0.0)
        np.testing.assert_array_equal(
            np.asarray(_split(ours, H)), np.asarray(blocked)
        )


def test_packed_bfloat16_io(rng):
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 32, 128)), jnp.bfloat16) for _ in range(3)
    )
    out = flash_attention_packed(
        q, k, v, num_heads=2, block_q=16, block_k=16, interpret=True
    )
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(_split(q, 2), _split(k, 2), _split(v, 2), None, jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(_split(out, 2), dtype=np.float32),
        np.asarray(ref, dtype=np.float32),
        atol=3e-2,
        rtol=3e-2,
    )


@pytest.mark.parametrize(
    "heads,head_dim,packed",
    [
        (12, 64, True),  # bert-base: two heads a tile, six tiles
        (4, 32, True),  # bert-long: four heads in one tile
        (2, 64, True),  # bert-tiny
        (20, 128, False),  # Jamba: a head is a whole tile
        (2, 16, False),  # 32 lanes in all: no whole tile
        (3, 64, False),  # a tile and a half
        (4, 48, False),  # 48 does not divide 128
        (None, None, False),  # a caller that names no shape
    ],
)
def test_kernel_is_chosen_from_the_heads_shape_at_build(heads, head_dim, packed):
    assert packs(heads, head_dim) is packed
    fn = make_flash_attention_fn(interpret=True, num_heads=heads, head_dim=head_dim)
    assert fn.kind == "flash"
    assert getattr(fn, "layout", "heads") == ("packed" if packed else "heads")
    # causal attention keeps the blocked kernel whatever the shape
    causal = make_flash_attention_fn(
        interpret=True, causal=True, num_heads=heads, head_dim=head_dim
    )
    assert not hasattr(causal, "layout")


def test_what_the_packed_kernel_cannot_do_is_refused():
    t = jnp.zeros((1, 16, 96), jnp.float32)
    with pytest.raises(ValueError, match="do not pack"):
        flash_attention_packed(t, t, t, num_heads=3, interpret=True)
    q, kv = jnp.zeros((1, 16, 128), jnp.float32), jnp.zeros((1, 16, 64), jnp.float32)
    with pytest.raises(ValueError, match="wants k and v"):
        flash_attention_packed(q, kv, kv, num_heads=2, interpret=True)


def test_packed_encoder_is_the_dense_encoder_without_transposes(rng):
    """BertEncoder built with the packed function gives the dense
    encoder's output, and hands the projections' outputs to the kernel
    as they are: no transpose anywhere in its jaxpr (the heads-layout
    flash encoder has four a layer)."""
    from sparkdl_tpu.models.bert import (
        BertConfig,
        BertEncoder,
        encoder_model_function,
    )

    cfg = BertConfig(
        vocab_size=64,
        hidden_size=128,
        num_layers=2,
        num_heads=2,
        intermediate_size=64,
        max_position_embeddings=32,
    )
    ids = jnp.asarray(rng.integers(1, 64, size=(3, 16)), dtype=jnp.int32)
    mask = jnp.asarray(np.arange(16)[None, :] < np.array([16, 9, 3])[:, None])
    dense = BertEncoder(config=cfg)
    params = dense.init(jax.random.PRNGKey(0), ids)
    kw = dict(block_q=8, block_k=8, interpret=True)
    packed = BertEncoder(
        config=cfg,
        attention_fn=make_flash_attention_fn(num_heads=2, head_dim=64, **kw),
    )
    heads = BertEncoder(config=cfg, attention_fn=make_flash_attention_fn(**kw))
    np.testing.assert_allclose(
        np.asarray(dense.apply(params, ids, mask)),
        np.asarray(packed.apply(params, ids, mask)),
        atol=1e-4,
        rtol=1e-4,
    )
    jaxpr = lambda m: str(jax.make_jaxpr(m.apply)(params, ids, mask))  # noqa: E731
    # `transpose[` is the operation; a loop's `_split_transpose=` is not
    assert "pallas_call" in jaxpr(packed) and "transpose[" not in jaxpr(packed)
    assert jaxpr(heads).count("transpose[") == 4 * cfg.num_layers
    built = [
        encoder_model_function(m, None, None, "x") for m in (dense, heads, packed)
    ]
    assert [(mf.attention, mf.attention_layout) for mf in built] == [
        ("dense", "heads"), ("flash", "heads"), ("flash", "packed"),
    ]
