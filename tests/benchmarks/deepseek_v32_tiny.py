"""The DeepSeek-V3.2 family at the size of the CPU tests: the published
configuration's file with the sizes of the program's
`deepseek-v3.2-exp-tiny` preset put in, for the plain reference; its
weights written as the benchmark writes them; and the faults that the
cell's tests plant in the program, each a function of a `setattr`
(`monkeypatch.setattr`, or `benchmarks.prove_released --plant`, which
reads one at the cell's size)."""

import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def published_config() -> dict:
    path = os.path.join(ROOT, "benchmarks", "configs", "deepseek-v3.2-exp.json")
    with open(path) as f:
        return json.load(f)


def tiny_config(max_length: int = 256, held=(0, 4)) -> dict:
    """`models/deepseek_v32.py:deepseek_v32_tiny` in the configuration
    file's keys: a dense layer and two expert layers, 16 experts in 4
    groups of which 2, top-3, 1 shared, 4 heads of 16 + 8 / 16, and 4
    index heads of 16 that pick 16 keys; `held` is the share (`None`: the
    uncut layer, all 16 experts)."""
    config = published_config()
    first, end = held or (0, 16)
    config.update(
        name="deepseek-v3.2-exp-tiny", vocab_size=512, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=end - first, num_experts_per_tok=3,
        n_group=4, topk_group=2, experts_held=[first, end],
        index_n_heads=4, index_head_dim=16, index_topk=16,
        max_length=max_length,
    )
    config["published"] = dict(config["published"], n_routed_experts=16)
    return config


def write_weights(path, config, seed=0) -> dict:
    """The reference's weights for `config`, saved as the driver saves
    them; returns them."""
    from benchmarks.reference import deepseek_v32

    weights = deepseek_v32.make_weights(config, seed)
    np.savez(path, **weights)
    return weights


def _with_indexer(setattr, change):
    """Builds the family's programs with `change(indexer_fn)` in the
    indexer's place."""
    from sparkdl_tpu.ops import dsa_indexer

    make = dsa_indexer.make_indexer_fn

    def changed(num_heads, top_k, interpret=False):
        fn = change(make, num_heads, top_k, interpret)
        fn.kind = make(num_heads, top_k, interpret).kind
        return fn

    setattr(dsa_indexer, "make_indexer_fn", changed)


def selection_ignored(setattr):
    """Attention reads every causal key: dense MLA, the indexer's
    selection thrown away."""
    import jax.numpy as jnp

    def dense(make, num_heads, top_k, interpret):
        def fn(q, k, w):
            length = q.shape[1]
            causal = jnp.tril(jnp.ones((length, length), jnp.int8))
            return jnp.broadcast_to(causal, (q.shape[0], length, length))

        return fn

    _with_indexer(setattr, dense)


def relu_dropped(setattr):
    """The index scores sum the heads' products as they are, negative
    ones too."""
    import jax.numpy as jnp

    from sparkdl_tpu.ops import dsa_indexer

    def linear(make, num_heads, top_k, interpret):
        def fn(q, k, w):
            rows, length, _ = q.shape
            s = jnp.einsum(
                "bqhd,bkd->bhqk", q.reshape(rows, length, num_heads, -1), k,
                preferred_element_type=jnp.float32,
            )
            scores = jnp.einsum("bhqk,bqh->bqk", s, w.astype(jnp.float32))
            return dsa_indexer.select_keys(scores, top_k=top_k)

        return fn

    _with_indexer(setattr, linear)


def top_k_halved(setattr):
    """Each query attends to the best half of its `index_topk` keys."""

    def half(make, num_heads, top_k, interpret):
        return make(num_heads, top_k // 2, interpret)

    _with_indexer(setattr, half)
