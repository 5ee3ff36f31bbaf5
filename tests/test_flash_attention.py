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
    make_flash_attention_fn,
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
