"""Ulysses all-to-all sequence parallelism: dense-oracle parity on the
8-device CPU mesh (same oracle pattern as the ring-attention tests)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.models.bert import BertConfig, BertEncoder, dense_attention
from sparkdl_tpu.ops import (
    make_ulysses_attention,
    ulysses_attention_sharded,
)
from sparkdl_tpu.parallel import make_mesh

def _qkv(rng, B, H, L, D):
    return tuple(
        jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.float32)
        for _ in range(3)
    )


def test_ulysses_matches_dense_one_head_per_device():
    rng = np.random.default_rng(0)
    B, H, L, D = 2, 8, 32, 8
    q, k, v = _qkv(rng, B, H, L, D)
    mask = np.zeros((B, 1, 1, L), np.float32)
    mask[:, :, :, L - 5:] = np.finfo(np.float32).min  # pad the tail
    mask = jnp.asarray(mask)

    dense = dense_attention(q, k, v, mask, jnp.float32)
    mesh = make_mesh({"sp": 8})
    out = ulysses_attention_sharded(q, k, v, mask, mesh, axis="sp")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_ulysses_matches_dense_multiple_heads_per_device():
    rng = np.random.default_rng(1)
    B, H, L, D = 2, 16, 64, 4
    q, k, v = _qkv(rng, B, H, L, D)

    dense = dense_attention(q, k, v, None, jnp.float32)
    mesh = make_mesh({"sp": 8})
    out = ulysses_attention_sharded(q, k, v, None, mesh, axis="sp")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_ulysses_matches_ring():
    from sparkdl_tpu.ops import ring_attention_sharded

    rng = np.random.default_rng(2)
    B, H, L, D = 1, 8, 48, 8
    q, k, v = _qkv(rng, B, H, L, D)
    mask = np.zeros((B, 1, 1, L), np.float32)
    mask[:, :, :, L - 7:] = np.finfo(np.float32).min
    mask = jnp.asarray(mask)

    mesh = make_mesh({"sp": 8})
    ring = ring_attention_sharded(q, k, v, mask, mesh, axis="sp")
    uly = ulysses_attention_sharded(q, k, v, mask, mesh, axis="sp")
    np.testing.assert_allclose(
        np.asarray(uly), np.asarray(ring), rtol=1e-5, atol=1e-5
    )


def test_ulysses_rejects_indivisible_heads():
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, 1, 6, 16, 4)  # 6 heads over 8 devices
    mesh = make_mesh({"sp": 8})
    with pytest.raises(ValueError, match="heads % axis_size"):
        ulysses_attention_sharded(q, k, v, None, mesh, axis="sp")


def test_bert_ulysses_sequence_parallel_matches_dense():
    """Full tiny-BERT (8 heads) with the sequence sharded over 'sp' and
    attention computed via all_to_all head swaps == dense oracle."""
    from jax.sharding import PartitionSpec as P

    cfg = BertConfig(
        vocab_size=1000,
        hidden_size=128,
        num_layers=2,
        num_heads=8,
        intermediate_size=256,
        max_position_embeddings=128,
    )
    m_dense = BertEncoder(cfg)
    ids = jnp.asarray(
        np.random.default_rng(4).integers(4, 1000, (2, 32)), jnp.int32
    )
    params = m_dense.init(jax.random.PRNGKey(0), ids)
    oracle = np.asarray(m_dense.apply(params, ids))

    mesh = make_mesh({"sp": 8})
    m_uly = BertEncoder(cfg, attention_fn=make_ulysses_attention("sp"))
    L_local = ids.shape[1] // 8

    def local_run(p, ids_shard):
        offset = jax.lax.axis_index("sp") * L_local
        return m_uly.apply(p, ids_shard, position_offset=offset)

    fn = jax.shard_map(
        local_run,
        mesh=mesh,
        in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp", None),
        check_vma=False,
    )
    out = np.asarray(fn(params, ids))
    np.testing.assert_allclose(out, oracle, rtol=2e-4, atol=2e-4)
