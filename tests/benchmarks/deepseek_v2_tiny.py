"""The DeepSeek-V2 family at the size of the CPU tests: the published
configuration's file with the sizes of the program's `deepseek-v2-tiny`
preset put in, for the plain reference; its weights written as the
benchmark writes them; and the faults that the cell's tests plant in the
program, each a function of a `setattr` (`monkeypatch.setattr`, or
`benchmarks.prove_released --plant`, which reads one at the cell's size)."""

import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def published_config() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs", "deepseek-v2.json")) as f:
        return json.load(f)


def tiny_config(max_length: int = 256, held=(0, 4)) -> dict:
    """`models/deepseek_v2.py:deepseek_v2_tiny` in the configuration
    file's keys: a dense layer and two expert layers, 16 experts in 4
    groups of which 2, top-3, 2 shared, 4 heads of 16 + 8 / 16; `held`
    is the share (`None`: the uncut layer, all 16 experts)."""
    config = published_config()
    first, end = held or (0, 16)
    config.update(
        name="deepseek-v2-tiny", vocab_size=512, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=end - first, num_experts_per_tok=3,
        n_group=4, topk_group=2, experts_held=[first, end],
        max_length=max_length,
    )
    config["published"] = dict(config["published"], n_routed_experts=16)
    return config


def write_weights(path, config, seed=0) -> dict:
    """The reference's weights for `config`, saved as the driver saves
    them; returns them."""
    from benchmarks.reference import deepseek_v2

    weights = deepseek_v2.make_weights(config, seed)
    np.savez(path, **weights)
    return weights


def an_expert_slot_dropped(setattr):
    """A token's last chosen expert is never computed."""
    from sparkdl_tpu.models import deepseek_v2

    route = deepseek_v2.route

    def one_short(config, u, router):
        experts, weights = route(config, u, router)
        return experts, weights.at[:, -1].set(0.0)

    setattr(deepseek_v2, "route", one_short)


def weights_renormalised(setattr):
    """The routing weights made to sum to 1, as `norm_topk_prob` would."""
    from sparkdl_tpu.models import deepseek_v2

    route = deepseek_v2.route

    def normalised(config, u, router):
        experts, weights = route(config, u, router)
        return experts, weights / weights.sum(-1, keepdims=True)

    setattr(deepseek_v2, "route", normalised)


def rotary_key_not_rotated(setattr):
    """`k_pe` goes into the scores as the projection left it."""
    from sparkdl_tpu.models import deepseek_v2

    setattr(deepseek_v2, "_rotate", lambda x, cos, sin: x)
