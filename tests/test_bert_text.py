import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.dataframe import DataFrame
from sparkdl_tpu.models.bert import (
    BertConfig,
    BertEncoder,
    bert_model_function,
    bert_tiny,
    dense_attention,
    load_hf_bert_params,
)
from sparkdl_tpu.ops import make_ring_attention, ring_attention_sharded
from sparkdl_tpu.parallel import make_mesh
from sparkdl_tpu.transformers.text import (
    HashingTokenizer,
    TextEmbedder,
    pad_or_truncate,
)


def test_bert_tiny_shapes():
    m = bert_tiny()
    ids = jnp.ones((2, 16), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), ids)
    hidden = m.apply(params, ids)
    assert hidden.shape == (2, 16, 128)
    pooled = m.apply(params, ids, pooled=True)
    assert pooled.shape == (2, 128)


def test_bert_mask_respected():
    m = bert_tiny()
    ids = jnp.asarray(np.random.default_rng(0).integers(4, 1000, (1, 16)), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), ids)
    mask_full = jnp.ones((1, 16), jnp.int32)
    mask_half = mask_full.at[:, 8:].set(0)
    # changing PADDED content must not change pooled output under the mask
    ids2 = ids.at[:, 8:].set(999)
    p1 = m.apply(params, ids, mask_half, pooled=True)
    p2 = m.apply(params, ids2, mask_half, pooled=True)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=1e-5)
    # but changes under the full mask do
    p3 = m.apply(params, ids2, mask_full, pooled=True)
    assert np.abs(np.asarray(p3) - np.asarray(p1)).max() > 1e-4


def test_bert_parity_vs_hf_flax():
    """Oracle: transformers FlaxBertModel with the SAME weights must produce
    the same last_hidden_state (SURVEY.md §5 oracle pattern, text path)."""
    transformers = pytest.importorskip("transformers")
    from transformers import BertConfig as HFConfig, FlaxBertModel

    hf_cfg = HFConfig(
        vocab_size=1000,
        hidden_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        intermediate_size=256,
        max_position_embeddings=128,
        type_vocab_size=2,
    )
    hf = FlaxBertModel(hf_cfg, seed=0)
    ours_cfg = BertConfig(
        vocab_size=1000,
        hidden_size=128,
        num_layers=4,
        num_heads=4,
        intermediate_size=256,
        max_position_embeddings=128,
    )
    ours = BertEncoder(ours_cfg)
    params = load_hf_bert_params(hf.params, ours_cfg)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 1000, size=(2, 24)).astype(np.int32)
    mask = np.ones_like(ids)
    mask[:, 20:] = 0

    theirs = np.asarray(
        hf(input_ids=ids, attention_mask=mask).last_hidden_state
    )
    mine = np.asarray(ours.apply(params, jnp.asarray(ids), jnp.asarray(mask)))
    np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-4)


def test_ring_attention_matches_dense():
    rng = np.random.default_rng(0)
    B, H, L, D = 2, 2, 32, 8
    q = jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.float32)
    mask = np.zeros((B, 1, 1, L), np.float32)
    mask[:, :, :, L - 5 :] = np.finfo(np.float32).min  # pad the tail
    mask = jnp.asarray(mask)

    dense = dense_attention(q, k, v, mask, jnp.float32)
    mesh = make_mesh({"sp": 8})
    ring = ring_attention_sharded(q, k, v, mask, mesh, axis="sp")
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_bert_sequence_parallel_matches_dense():
    """Full tiny-BERT with sequence sharded over 'sp' (ring attention +
    global position offsets) == single-device dense run."""
    from jax.sharding import PartitionSpec as P

    m_dense = bert_tiny()
    ids = jnp.asarray(
        np.random.default_rng(1).integers(4, 1000, (2, 32)), jnp.int32
    )
    params = m_dense.init(jax.random.PRNGKey(0), ids)
    oracle = np.asarray(m_dense.apply(params, ids))

    mesh = make_mesh({"sp": 8})
    m_ring = BertEncoder(
        m_dense.config, attention_fn=make_ring_attention("sp")
    )
    L_local = ids.shape[1] // 8

    def local_run(p, ids_shard):
        offset = jax.lax.axis_index("sp") * L_local
        return m_ring.apply(p, ids_shard, position_offset=offset)

    fn = jax.shard_map(
        local_run,
        mesh=mesh,
        in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp", None),
        check_vma=False,
    )
    out = np.asarray(fn(params, ids))
    np.testing.assert_allclose(out, oracle, rtol=2e-4, atol=2e-4)


def test_hashing_tokenizer_stable():
    tok = HashingTokenizer(vocab_size=1000)
    a = tok("Hello, TPU world")
    b = tok("Hello, TPU world")
    assert a == b and a[0] == 1 and a[-1] == 2
    assert all(0 <= t < 1000 for t in a)
    assert pad_or_truncate(a, 8).shape == (8,)
    assert pad_or_truncate([1], 4).tolist() == [1, 0, 0, 0]


def test_text_embedder_end_to_end():
    mf = bert_model_function(size="tiny", max_length=32)
    t = TextEmbedder(
        inputCol="text", outputCol="emb", modelFunction=mf,
        maxLength=32, batchSize=4,
    )
    df = DataFrame.fromColumns(
        {
            "text": [
                "the quick brown fox",
                "jumps over the lazy dog",
                None,
                "pack my box with five dozen jugs",
            ]
        },
        numPartitions=2,
    )
    rows = t.transform(df).collect()
    assert rows[2].emb is None
    ok = [r.emb for r in rows if r.emb is not None]
    assert all(e.shape == (128,) for e in ok)
    # different texts embed differently
    assert np.abs(ok[0] - ok[1]).max() > 1e-5


def _sp_vs_dense_embedder(strategy, mesh):
    """Shared oracle: TextEmbedder over the sequence-parallel model fn
    must equal the dense TextEmbedder row-for-row with the SAME params."""
    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.models.bert import (
        bert_model_function,
        bert_model_function_sequence_parallel,
    )
    from sparkdl_tpu.transformers.text import TextEmbedder

    max_len = 32
    mf_dense = bert_model_function(size="tiny", max_length=max_len)
    mf_sp = bert_model_function_sequence_parallel(
        size="tiny", mesh=mesh, strategy=strategy, max_length=max_len,
        params=mf_dense.params,
    )
    assert mf_sp.single_stream
    # every text builder records its attention through the same helper
    assert mf_sp.attention == strategy and mf_dense.attention == "dense"

    texts = [
        "sequence parallelism makes long context first class",
        "short",
        None,
        "the quick brown fox jumps over the lazy dog " * 3,
    ]
    df = DataFrame.fromColumns({"text": texts}, numPartitions=2)

    def embed(mf):
        emb = TextEmbedder(
            inputCol="text", outputCol="e", modelFunction=mf,
            maxLength=max_len, batchSize=2,
        )
        return [r.e for r in emb.transform(df).collect()]

    dense, sp = embed(mf_dense), embed(mf_sp)
    assert sp[2] is None and dense[2] is None  # null rides through
    for a, b in zip(dense, sp):
        if a is not None:
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_text_embedder_ring_sequence_parallel():
    _sp_vs_dense_embedder("ring", make_mesh({"sp": 8}))


def test_text_embedder_ulysses_sequence_parallel():
    # tiny-BERT has 4 heads; ulysses shards heads, so use a 4-wide axis
    import jax

    _sp_vs_dense_embedder(
        "ulysses", make_mesh({"sp": 4}, devices=jax.devices()[:4])
    )


def test_sequence_parallel_validations():
    from sparkdl_tpu.models.bert import bert_model_function_sequence_parallel

    with pytest.raises(ValueError, match="divisible"):
        bert_model_function_sequence_parallel(
            size="tiny", mesh=make_mesh({"sp": 8}), max_length=30
        )
    with pytest.raises(ValueError, match="heads"):
        bert_model_function_sequence_parallel(
            size="tiny", mesh=make_mesh({"sp": 8}), strategy="ulysses",
            max_length=32,
        )
    with pytest.raises(ValueError, match="strategy"):
        bert_model_function_sequence_parallel(
            size="tiny", mesh=make_mesh({"sp": 8}), strategy="nope",
            max_length=32,
        )
