"""The AFMoE family at the size of the CPU tests: the published
configuration's file with the sizes of the program's `trinity-mini-tiny`
preset put in, for the plain reference; its weights written as the
benchmark writes them; and the faults that the tests plant in the
program, each a function of a `setattr` (`monkeypatch.setattr`, or
`benchmarks.prove_released --plant`, which reads one at the cell's size)."""

import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
#: the faults below, by name
FAULTS = ("window_ignored", "gate_ignored", "rope_on_full", "qk_norm_dropped", "shared_dropped")


def published_config() -> dict:
    path = os.path.join(ROOT, "benchmarks", "configs", "trinity-mini.json")
    with open(path) as f:
        return json.load(f)


def tiny_config(max_length: int = 256) -> dict:
    """`models/afmoe.py:trinity_mini_tiny` in the configuration file's
    keys: five layers (a dense sliding one, then sliding, full, sliding,
    sliding), 4 query heads over 2 key/value heads of 16, a window of 16,
    16 experts of 32 of which 4 a token, 1 shared, all held."""
    config = published_config()
    config.update(
        name="trinity-mini-tiny", vocab_size=512, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, sliding_window=16, num_experts=16,
        num_experts_per_tok=4, experts_held=[0, 16], max_length=max_length,
    )
    return config


def write_weights(path, config, seed=0) -> dict:
    """The reference's weights for `config`, saved as the driver saves
    them; returns them."""
    from benchmarks.reference import afmoe

    weights = afmoe.make_weights(config, seed)
    np.savez(path, **weights)
    return weights


def window_ignored(setattr):
    """The sliding layers attend to every causal key: full attention in
    the window's place."""
    from sparkdl_tpu.ops import flash_attention

    make = flash_attention.make_flash_attention_fn

    def without_window(*args, window=None, **kwargs):
        return make(*args, **kwargs)

    setattr(flash_attention, "make_flash_attention_fn", without_window)


def gate_ignored(setattr):
    """The attention's output goes to W_o without its sigmoid gate."""
    from sparkdl_tpu.models import afmoe

    setattr(afmoe, "_output_gate", lambda o, g: o)


def rope_on_full(setattr):
    """The full layers' queries and keys turned by the rotary as the
    sliding layers' are."""
    from sparkdl_tpu.models import afmoe

    attention = afmoe._attention

    def turned(config, p, u, tables, attention_fn):
        if tables is None:
            tables = afmoe.rope_tables(config, u.shape[1])
        return attention(config, p, u, tables, attention_fn)

    setattr(afmoe, "_attention", turned)


def qk_norm_dropped(setattr):
    """Queries and keys go to the kernel without their RMSNorm: every norm
    over a head's lanes (the only norms of that width) passes its input."""
    import jax.numpy as jnp

    from sparkdl_tpu.models import afmoe

    rms = afmoe._rms

    def some(x, w, eps):
        if x.ndim == 4 and w.shape == x.shape[-1:]:
            return x.astype(jnp.float32)
        return rms(x, w, eps)

    setattr(afmoe, "_rms", some)


def shared_dropped(setattr):
    """The expert layers leave out their shared expert: a SwiGLU narrower
    than the hidden size (the shared expert's; the dense layer's is
    wider, in both presets) gives zeros."""
    import jax.numpy as jnp

    from sparkdl_tpu.models import deepseek_v2

    swiglu = deepseek_v2._swiglu

    def some(p, u):
        out = swiglu(p, u)
        hidden, width = p["gate"].shape
        return jnp.zeros_like(out) if width < hidden else out

    setattr(deepseek_v2, "_swiglu", some)
