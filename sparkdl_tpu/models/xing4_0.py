"""Xing4.0 (``model_type: xing4_0``; XingChen-AGI, Xing4.0-29B-A4B):
DeepSeek-V3's layers, latent attention (MLA) and a feed-forward that is a
dense SwiGLU in the first ``first_k_dense`` layers and one shared plus 64
routed experts after them, whose residual is not ``x + F(norm(x))`` but a
stream of ``hc_mult`` copies of the hidden state mixed by
manifold-constrained hyper-connections (``ops/hyper_connection.py``).

    X [B, L, n, C] float32;  X[i] = E[ids] for every i < n
    each sublayer F of a layer (MLA, then the feed-forward), with its own
    phi [n C, 2n + n^2], bias [2n + n^2] and gains (pre, post, res):
      u, H_post, H_res = hc_pre(X)                u in the compute dtype
      X = hc_post(X, F(u), H_post, H_res)         X'[j] = sum_i H_res[j, i] X[i] + H_post[j] F(u)
    F = MLA(rms(u; w_in)) or FFN(rms(u; w_ff)), as ``deepseek_v2.forward``
      computes them from its input; FFN_i>=first_k_dense = Shared + Routed,
      the router ``moe.route``'s sigmoid gate with a correction
      bias (``noaux_tc``, one group: none dropped), top-k renormalised and
      scaled by ``routed_scaling_factor``
    h = sum_i X[i];  embed = the mean over a row's real tokens of rms(h; w_final)

**Shared with DeepSeek, adapted and not copied**: the sublayers are
``deepseek_v2``'s ``mla`` (over ``flash_attention_latent``, handed each
row's length) and SwiGLU, and ``models/moe.py``'s ``route`` and
``routed`` (every expert held: one slot buffer and no conditional, over
``ops/grouped_matmul.py`` and ``ops/moe_combine.py``), with
``deepseek_v2.rope_tables`` and ``models/layers.py``'s norms, lengths
and pooling; :class:`Xing4Config` is ``DeepseekV2Config`` with this
family's values and its hyper-connection keys. Only the loop around
them is this module's.

Precision: matrices and the activations that feed them are ``dtype``,
every product accumulates in float32; the n streams, every mHC coefficient
and mix (``phi``'s product in float32 at the highest precision), the norms,
softmax, rotary and the router are float32. The mixes are
``hc_pre``/``hc_post``: the Pallas kernels on TPU, plain ``jax.numpy``
elsewhere, chosen at build time and reported as ``mf.residual``
(``pallas`` | ``xla``), beside ``mf.attention``, ``mf.experts`` and
``mf.combine``. Layers are unrolled into one program with every layer's
weights an argument of its own (``weights_as_arguments``).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.models import deepseek_v2 as v2
from sparkdl_tpu.models import layers, moe
from sparkdl_tpu.models.deepseek_v2 import DeepseekV2Config
from sparkdl_tpu.models.layers import (
    attention_batch_counters,
    load_flat,
    mean_real_state,
    preset,
    rms,
    row_lengths,
    unflatten,
)
from sparkdl_tpu.ops import hyper_connection
from sparkdl_tpu.ops.moe_combine import gather_combine
from sparkdl_tpu.utils.profiler import scope

#: a layer's two hyper-connections, by the sublayer each wraps
SUBLAYERS = ("hc_attn", "hc_ffn")


@dataclass(frozen=True)
class Xing4Config(DeepseekV2Config):
    """The published ``config.json``'s values, under ``DeepseekV2Config``'s
    names (``num_layers`` is ``num_hidden_layers``, ``first_k_dense`` is
    ``first_k_dense_replace``, ``rope_*`` the ``rope_scaling`` group)."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_layers: int = 40
    first_k_dense: int = 2
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    scoring_func: str = "sigmoid"
    rope_factor: float = 64.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    experts_held: Tuple[int, int] = (0, 64)
    worst_case_chunk_rows: Optional[int] = None
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0

    @property
    def hyper_constants(self) -> hyper_connection.Constants:
        return hyper_connection.Constants(
            n=self.hc_mult,
            iters=self.hc_sinkhorn_iters,
            hc_eps=self.hc_eps,
            clamp=(self.mhc_h_res_clamp_min, self.mhc_h_res_clamp_max),
            rms_eps=self.rms_norm_eps,
        )


def xing4_0_29b_a4b() -> Xing4Config:
    """Xing4.0-29B-A4B as ``benchmarks/configs/xing4.0-29b-a4b.json`` cuts
    it: every published width, every expert and the whole vocabulary; one
    leading dense layer and four expert layers of the 40."""
    return Xing4Config(num_layers=5, first_k_dense=1)


def xing4_0_tiny() -> Xing4Config:
    """The same family at a size the CPU tests hold: a dense layer and two
    expert layers, 4 heads, 16 experts of which 4 a token, all held; the
    hyper-connections at their published keys (4 streams, 20 steps)."""
    return Xing4Config(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        moe_intermediate_size=32,
        num_layers=3,
        first_k_dense=1,
        num_heads=4,
        q_lora_rank=24,
        kv_lora_rank=16,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        n_routed_experts=16,
        experts_held=(0, 16),
    )


_SIZES = {"xing4.0-29b-a4b": xing4_0_29b_a4b, "xing4.0-tiny": xing4_0_tiny}


def layer_shapes(config: Xing4Config, i: int) -> dict:
    """``deepseek_v2.layer_shapes`` with the gate's correction bias and the
    two hyper-connections' ``phi``, ``bias`` and ``alpha`` (pre, post, res)."""
    shapes = v2.layer_shapes(config, i)
    if i >= config.first_k_dense:
        shapes["moe/router_bias"] = (config.n_routed_experts,)
    n = config.hc_mult
    m = hyper_connection.coefficients(n)
    for part in SUBLAYERS:
        shapes.update({
            f"{part}/phi": (n * config.hidden_size, m),
            f"{part}/bias": (m,),
            f"{part}/alpha": (3,),
        })
    return shapes


def param_shapes(config: Xing4Config) -> dict:
    return v2.param_shapes(config, layer_shapes)


def _leaf_dtype(path: str, shape: tuple, dtype):
    """``layers.leaf_dtype``, and every hyper-connection leaf float32."""
    if "/hc_" in path:
        return jnp.float32
    return layers.leaf_dtype(path, shape, dtype)


def init_params(config: Xing4Config, seed: int, dtype) -> dict:
    """Random weights as ``deepseek_v2.init_params`` makes them, the gate's
    bias uniform in +-0.05, ``phi`` at variance 1 / (n C), the mixes'
    biases at unit scale and the gains about one, so that the mixes are
    neither the identity nor uniform."""
    rng = np.random.default_rng([int(seed), 0x1A64C])
    flat = {}
    for path, shape in param_shapes(config).items():
        kind = path.rsplit("/", 1)[-1]
        if "/hc_" in path:
            v = {
                "phi": lambda: rng.standard_normal(shape) / math.sqrt(shape[0]),
                "bias": lambda: rng.standard_normal(shape),
                "alpha": lambda: rng.uniform(0.5, 1.5, shape),
            }[kind]()
        elif kind == "router_bias":
            v = rng.uniform(-0.05, 0.05, shape)
        elif "norm" in kind:
            v = np.ones(shape)
        elif kind == "embed":
            v = rng.standard_normal(shape)
        else:
            v = rng.standard_normal(shape) / math.sqrt(shape[-2])
            if kind == "router":
                v *= 2.0
        flat[path] = jnp.asarray(np.asarray(v, np.float32), _leaf_dtype(path, shape, dtype))
    return unflatten(flat)


def forward(
    config: Xing4Config, params, ids, *, dtype, attention_fn, experts_fn, hyper,
    combine_fn=gather_combine,
):
    """ids [B, L] int32, zero-padded on the right -> (embeddings
    [B, hidden] float32, slots that fell on held experts [B] int32, how
    many expert layers worked on the sized slot buffer, an int32 scalar).
    ``hyper``: ``ops/hyper_connection.py:make_hyper_connection_fn``'s."""
    eps, n, hidden = config.rms_norm_eps, config.hc_mult, config.hidden_size
    rows, length = ids.shape
    tokens = rows * length
    with scope("embed"):
        real = ids != 0
        tables = v2.rope_tables(config, length)
        x = params["embed"][ids].astype(jnp.float32).reshape(tokens, hidden)
        stream = jnp.tile(x, (1, n))  # [T, n C]: every stream the embedding
        by_length = row_lengths(attention_fn, real)
    slots_held = jnp.zeros((rows,), jnp.int32)
    sized = jnp.zeros((), jnp.int32)

    def pre(stream, p):
        with scope("mhc.pre"):
            u, h_post, h_res = hyper.pre(stream, p["phi"], p["bias"], p["alpha"], dtype)
            return u.reshape(rows, length, hidden), h_post, h_res

    def post(stream, f, h_post, h_res):
        # the residual sum's place
        with scope("mhc.post"):
            return hyper.post(stream, f.reshape(tokens, hidden), h_post, h_res)

    for i in range(config.num_layers):
        p = params["layers"][str(i)]
        u, h_post, h_res = pre(stream, p["hc_attn"])
        # a norm is in the scope of the first part it feeds
        with scope("mla.q"):
            u = rms(u, p["norm_in"], eps).astype(dtype)
        attended = v2.mla(config, p["attn"], u, tables, attention_fn, by_length)
        stream = post(stream, attended, h_post, h_res)

        u, h_post, h_res = pre(stream, p["hc_ffn"])
        with scope("mlp"):
            u = rms(u, p["norm_ff"], eps)
            # ``deepseek_v2._swiglu`` itself: a planted fault patches it there
            if i < config.first_k_dense:
                f = v2._swiglu(p["mlp"], u.astype(dtype))
        if i >= config.first_k_dense:
            routed, count, fits = moe.routed(
                config, p["moe"], u, real, experts_fn, gate=moe.route, combine_fn=combine_fn
            )
            with scope("mlp"):
                f = v2._swiglu(p["moe"]["shared"], u.astype(dtype))
            with scope("moe.routed"):
                f = f + routed
            slots_held, sized = slots_held + count, sized + fits
        stream = post(stream, f, h_post, h_res)
    with scope("pool"):
        streams = stream.reshape(rows, length, n, hidden)
        h = streams[:, :, 0]
        for i in range(1, n):
            h = h + streams[:, :, i]
        out = mean_real_state(rms(h, params["final_norm"], eps), real)
    return out, slots_held, sized


def xing4_0_model_function(
    size: str = "xing4.0-tiny",
    dtype=jnp.float32,
    seed: int = 0,
    weights_file: Optional[str] = None,
    attention_fn=None,
    experts_fn=None,
    combine_fn=None,
    hyper=None,
    name: Optional[str] = None,
):
    """The ``embed`` ModelFunction (``moe.routed_model_function``: the
    embedding and ``moe.ROW_COUNTERS`` a row). ``attention_fn`` and
    ``hyper`` default to the build-time choice of
    ``make_latent_attention_fn(heads, scale)`` in blocks of 1,024 as
    DeepSeek-V2's and ``make_hyper_connection_fn(...)``: the Pallas kernels
    on TPU.

    Per dispatched token it counts ``mla.attention_tokens`` (once a layer)
    and ``mhc.tokens`` (twice a layer: the mixes' tokens);
    ``mf.batch_counters`` counts attention's pairs and query blocks
    (``layers.attention_batch_counters``)."""
    from sparkdl_tpu.ops import flash_attention

    config = preset(_SIZES, size, "Xing4.0")
    if attention_fn is None:
        attention_fn = flash_attention.make_latent_attention_fn(
            config.num_heads, config.softmax_scale, block=1024
        )
    if hyper is None:
        hyper = hyper_connection.make_hyper_connection_fn(config.hyper_constants)
    if weights_file:
        params = load_flat(param_shapes(config), weights_file, dtype, _leaf_dtype)
    else:
        params = init_params(config, seed, dtype)
    kernels = {
        "attention_fn": attention_fn, "experts_fn": experts_fn,
        "combine_fn": combine_fn, "hyper": hyper,
    }
    return moe.routed_model_function(
        config, params, forward, kernels, dtype=dtype, name=name or f"{size}[embed]",
        dispatched_token_counters={
            "mla.attention_tokens": config.num_layers,
            "mhc.tokens": len(SUBLAYERS) * config.num_layers,
        },
        batch_counters=functools.partial(
            attention_batch_counters, attention_fn, config.num_layers
        ),
    )
