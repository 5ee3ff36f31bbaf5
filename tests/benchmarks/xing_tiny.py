"""The Xing4.0 family at the size of the CPU tests: the published
configuration's file with the sizes of the program's `xing4.0-tiny` preset
put in, for the plain reference; its weights written as the benchmark
writes them; and the faults that the tests plant in the program, each a
function of a `setattr` (`monkeypatch.setattr`, or `benchmarks.prove_released
--plant`, which reads one at the cell's size)."""

import dataclasses
import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
#: the faults that the cell's limits must catch, by name
FAULTS = ("plain_residual",)


def published_config() -> dict:
    path = os.path.join(ROOT, "benchmarks", "configs", "xing4.0-29b-a4b.json")
    with open(path) as f:
        return json.load(f)


def tiny_config(max_length: int = 256) -> dict:
    """`models/xing4_0.py:xing4_0_tiny` in the configuration file's keys:
    a dense layer and two expert layers, 4 heads, 16 experts of 32 of which
    4 a token, all held; the hyper-connections' keys as published."""
    config = published_config()
    config.update(
        name="xing4.0-tiny", vocab_size=512, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16, experts_held=[0, 16],
        max_length=max_length,
    )
    return config


def write_weights(path, config, seed=0) -> dict:
    """The reference's weights for `config`, saved as the driver saves
    them; returns them."""
    from benchmarks.reference import xing4_0

    weights = xing4_0.make_weights(config, seed)
    np.savez(path, **weights)
    return weights


class _PlainResidual:
    """A residual of one stream in the four's place: every sublayer reads
    the first stream and adds its output to every stream (H_pre the first
    unit vector, H_post ones, H_res the identity), so that the four stay
    equal and their sum is four times a plain pre-norm residual's."""

    kind = "xla"

    def __init__(self, n):
        self.n = n

    def pre(self, x, phi, bias, alpha, dtype):
        import jax.numpy as jnp

        tokens, hidden = x.shape[0], x.shape[1] // self.n
        post = jnp.ones((tokens, self.n), jnp.float32)
        res = jnp.broadcast_to(jnp.eye(self.n, dtype=jnp.float32).reshape(-1), (tokens, self.n**2))
        return x[:, :hidden].astype(dtype), post, res

    def post(self, x, f, h_post, h_res):
        import jax.numpy as jnp

        return x + jnp.tile(f, (1, self.n))


def plain_residual(setattr):
    """The hyper-connections replaced by a plain residual (n = 1)."""
    from sparkdl_tpu.ops import hyper_connection

    setattr(hyper_connection, "make_hyper_connection_fn", lambda k, **_: _PlainResidual(k.n))


def sinkhorn_one_step(setattr):
    """The residual mix after one Sinkhorn step of the configuration's 20:
    rows normalised once, then columns, and no more."""
    from sparkdl_tpu.ops import hyper_connection

    make = hyper_connection.make_hyper_connection_fn
    setattr(
        hyper_connection, "make_hyper_connection_fn",
        lambda k, **kw: make(dataclasses.replace(k, iters=1), **kw),
    )
