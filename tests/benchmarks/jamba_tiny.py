"""The Jamba family at the size of the CPU tests: the published
configuration's file with the sizes of the program's `jamba-tiny` preset
put in, for the plain reference; and its weights written as the
benchmark writes them."""

import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def published_config() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs", "jamba2-3b.json")) as f:
        return json.load(f)


def tiny_config(max_length: int = 256) -> dict:
    """`models/jamba.py:jamba_tiny` in the configuration file's keys: five
    layers, attention in the middle, the published `d_state` 16 and
    `d_conv` 4, one key/value head."""
    config = published_config()
    config.update(
        name="jamba-tiny", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_hidden_layers=5, attn_layer_period=5,
        attn_layer_offset=2, num_attention_heads=4, num_key_value_heads=1,
        head_dim=16, mamba_dt_rank=8, max_length=max_length,
    )
    return config


def write_weights(path, config, seed=0) -> dict:
    """The reference's weights for `config`, saved as the driver saves
    them; returns them."""
    from benchmarks.reference import jamba

    weights = jamba.make_weights(config, seed)
    np.savez(path, **weights)
    return weights
