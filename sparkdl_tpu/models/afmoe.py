"""AFMoE, Arcee's Trinity family (``model_type: afmoe``): attention
layers of two kinds in one stack, a sliding window and full causal
attention by ``layer_types``, grouped-query attention with QK-norm and a
sigmoid output gate, sandwich norms, and a feed-forward that is a dense
SwiGLU MLP in the first ``num_dense_layers`` layers and one shared plus
routed experts after them.

    x0 = E[ids] * sqrt(hidden)                                 (muP)
    h  = rms(x; w_in)
    q, k, v, g = h W_q, h W_k, h W_v, h W_g      heads of head_dim; g [L, H * head_dim]
    q, k = rms(q; w_q), rms(k; w_k)              over each head's lanes
    q, k = rope(q, t), rope(k, t)                on sliding layers only (rotate halves,
                                                 positions from 0); full layers have none
    o = softmax(q k^T / sqrt(head_dim) + M) v    H / Hkv query heads a key/value head
      M: full layers j <= i; sliding layers 0 <= i - j < sliding_window
    x = x + rms(W_o (o * sigmoid(g)); w_post_attn)
    x = x + rms(F(rms(x; w_pre_mlp)); w_post_mlp)
    F = SwiGLU(intermediate_size) for i < num_dense_layers, else
        Shared(u) + sum over the top-k of s + b of  w_e SwiGLU_e(u),
        s = sigmoid(W_r u) over all experts, w = route_scale * s_chosen / sum

**Shared with DeepSeek, adapted and not copied**: the router is
``deepseek_v2.route``'s second gate (``n_group`` 1: no group is dropped)
and the routed experts ``deepseek_v2._routed`` over
``ops/grouped_matmul.py``; each reads this family's keys through the
properties of :class:`AfmoeConfig` that carry DeepSeek's names. A chip
here holds every expert (``experts_held`` (0, 128)), so ``slot_capacity``
is every slot and ``_routed`` builds the one body and no conditional.
The norms, the dense products and the pooling are ``models/jamba.py``'s
and ``models/deepseek_v2.py``'s.

``embed`` is the mean, over a row's real tokens, of the final RMSNorm of
their states, as both DeepSeek families have it. Precision: matrices and
the activations that feed them are ``dtype``, every product accumulates
in float32; the residual stream, every norm, the rotary angles, softmax,
the output gate's sigmoid, the router (operands too, ``highest``), the
routing weights and the combine are float32. Attention is
``ops/flash_attention.py:flash_attention``, the blocked causal kernel on
the full layers and its window mode (``flash_attention_window``) on the
sliding ones, both handed each row's length so that no query block of
padding alone runs; the Pallas kernels on TPU, plain ``jax.numpy``
elsewhere, chosen at build time and reported as ``mf.attention`` and
``mf.window_attention``. Layers are unrolled into one program with every
layer's weights an argument of its own (``weights_as_arguments``).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.models import deepseek_v2 as v2
from sparkdl_tpu.models.jamba import _dense, _rms, _unflatten, load_flat
from sparkdl_tpu.ops.moe_combine import gather_combine
from sparkdl_tpu.utils.profiler import scope

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class AfmoeConfig:
    """The published ``config.json``'s keys, Trinity-Mini's values."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    #: the published pattern: three sliding layers, then a full one
    layer_types: Tuple[str, ...] = ((SLIDING,) * 3 + (FULL,)) * 8
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    score_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    #: [first, end) of the routed experts whose weights this chip holds
    experts_held: Tuple[int, int] = (0, 128)
    #: ``deepseek_v2._routed``'s worst-case arm in one buffer
    worst_case_chunk_rows: Optional[int] = None

    # -- the names ``deepseek_v2.route`` and ``_routed`` read ---------------

    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

    @property
    def scoring_func(self) -> str:
        return self.score_func

    # -- the stack ---------------------------------------------------------

    def is_sliding(self, i: int) -> bool:
        return self.layer_types[i] == SLIDING

    @property
    def sliding_layers(self) -> int:
        return sum(map(self.is_sliding, range(self.num_hidden_layers)))

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers


def trinity_mini() -> AfmoeConfig:
    """One chip's share of Trinity-Mini as ``benchmarks/configs/
    trinity-mini.json`` cuts it: every published width, every expert and
    the whole vocabulary; published layers 1-5 (the second dense layer,
    then sliding, full, sliding, sliding)."""
    published = AfmoeConfig()
    return AfmoeConfig(
        num_hidden_layers=5, num_dense_layers=1, layer_types=published.layer_types[1:6]
    )


def trinity_mini_tiny() -> AfmoeConfig:
    """The same family at a size the CPU tests hold: the cut's five layers
    (a dense sliding layer, then sliding, full, sliding, sliding), 4 query
    heads over 2 key/value heads of 16, a window of 16 so that a row of 64
    tokens already bands, 16 experts of 32 of which 4 a token, 1 shared."""
    return AfmoeConfig(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        moe_intermediate_size=32,
        num_hidden_layers=5,
        num_dense_layers=1,
        layer_types=(SLIDING, SLIDING, FULL, SLIDING, SLIDING),
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        sliding_window=16,
        num_experts=16,
        num_experts_per_tok=4,
        experts_held=(0, 16),
    )


_SIZES = {"trinity-mini": trinity_mini, "trinity-mini-tiny": trinity_mini_tiny}


def layer_shapes(config: AfmoeConfig, i: int) -> dict:
    """{path under ``layers/<i>/``: shape}; matrices are [in, out], a
    layer's experts stacked [expert, in, out]."""
    h, d = config.hidden_size, config.head_dim
    q, kv = config.num_attention_heads * d, config.num_key_value_heads * d
    shapes = {
        "norm_in": (h,),
        "norm_post_attn": (h,),
        "norm_pre_mlp": (h,),
        "norm_post_mlp": (h,),
        "attn/q": (h, q),
        "attn/k": (h, kv),
        "attn/v": (h, kv),
        "attn/gate": (h, q),
        "attn/q_norm": (d,),
        "attn/k_norm": (d,),
        "attn/o": (q, h),
    }
    if i < config.num_dense_layers:
        f = config.intermediate_size
        shapes.update({"mlp/gate": (h, f), "mlp/up": (h, f), "mlp/down": (f, h)})
        return shapes
    f = config.moe_intermediate_size
    shared = config.num_shared_experts * f
    held = config.experts_held[1] - config.experts_held[0]
    shapes.update({
        "moe/router": (h, config.num_experts),
        "moe/router_bias": (config.num_experts,),
        "moe/shared/gate": (h, shared),
        "moe/shared/up": (h, shared),
        "moe/shared/down": (shared, h),
        "moe/experts/gate": (held, h, f),
        "moe/experts/up": (held, h, f),
        "moe/experts/down": (held, f, h),
    })
    return shapes


def param_shapes(config: AfmoeConfig) -> dict:
    """{flat path: shape} of every leaf, as a weights file names them."""
    h = config.hidden_size
    shapes = {"embed": (config.vocab_size, h), "final_norm": (h,)}
    for i in range(config.num_hidden_layers):
        for name, shape in layer_shapes(config, i).items():
            shapes[f"layers/{i}/{name}"] = shape
    return shapes


def init_params(config: AfmoeConfig, seed: int, dtype) -> dict:
    """Random weights scaled by fan-in, the embedding by the hidden size
    (so that muP's sqrt(hidden) leaves unit variance), the router's twice
    as wide, the expert bias uniform in +-0.05, norm weights one."""
    rng = np.random.default_rng([int(seed), 0xAF30E])
    flat = {}
    for path, shape in param_shapes(config).items():
        kind = path.rsplit("/", 1)[-1]
        if kind == "router_bias":
            v = rng.uniform(-0.05, 0.05, shape).astype(np.float32)
        elif "norm" in kind:
            v = np.ones(shape, np.float32)
        else:
            fan_in = shape[-1] if kind == "embed" else shape[-2]
            v = rng.standard_normal(shape, dtype=np.float32) / math.sqrt(fan_in)
            if kind == "router":
                v *= 2.0
        flat[path] = jnp.asarray(v, v2._leaf_dtype(path, shape, dtype))
    return _unflatten(flat)


def rope_tables(config: AfmoeConfig, length: int):
    """(cos, sin), each [L, head_dim] float32: pair i of a head's halves
    turns by t * theta^(-2i / head_dim), both halves carrying the angle."""
    d = config.head_dim
    inv_freq = 1.0 / config.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(length, dtype=np.float64)[:, None] * inv_freq
    angle = np.concatenate([angle, angle], -1)
    return jnp.asarray(np.cos(angle), jnp.float32), jnp.asarray(np.sin(angle), jnp.float32)


def _rotate(t, tables):
    """t [B, L, heads, d] float32 turned by the rotary ``tables``, lane i
    paired with lane i + d / 2."""
    cos, sin = tables
    return t * cos[:, None] + v2._turn(t) * sin[:, None]


def _output_gate(o, g):
    """o [B, L, H * d] scaled lane by lane by sigmoid(g), float32."""
    return o * jax.nn.sigmoid(g)


def _with_lengths(attention_fn, real):
    """``attention_fn`` handed its rows' lengths besides its operands
    (``deepseek_v2.row_lengths`` over real [B, L] bool) where it takes
    them, so that the kernel runs no query block of padding alone; as it
    is where it does not (the dense fallback, a stand-in in a test)."""
    by_length = v2.row_lengths(attention_fn, real)
    return functools.partial(attention_fn, **by_length) if by_length else attention_fn


def _attention(config: AfmoeConfig, p, u, tables, attention_fn):
    """u [B, L, hidden] in the compute dtype -> o W_o [B, L, hidden]
    float32, before the post-attention norm. ``tables``: the rotary
    (cos, sin) of a sliding layer, None for a full one. ``attention_fn``
    carries the rows' lengths where it takes them (:func:`_with_lengths`)."""
    dtype, eps = u.dtype, config.rms_norm_eps
    rows, length, _ = u.shape
    d = config.head_dim

    def heads(w, norm=None):  # [B, L, heads * d] -> [B, heads, L, d]
        t = _dense(u, w).reshape(rows, length, -1, d)
        if norm is not None:
            t = _rms(t, norm, eps)
            if tables is not None:
                t = _rotate(t, tables)
        return t.astype(dtype).transpose(0, 2, 1, 3)

    with scope("attn.qkv"):
        q, k = heads(p["q"], p["q_norm"]), heads(p["k"], p["k_norm"])
        v = heads(p["v"])
        g = _dense(u, p["gate"])
    with scope("attn.window" if tables is not None else "attn.full"):
        o = attention_fn(q, k, v, None, dtype)
    with scope("attn.out"):
        o = o.transpose(0, 2, 1, 3).reshape(rows, length, -1)
        return _dense(_output_gate(o, g).astype(dtype), p["o"])


def forward(
    config: AfmoeConfig, params, ids, *, dtype, attention_fn, window_attention_fn,
    experts_fn, combine_fn=gather_combine,
):
    """ids [B, L] int32, zero-padded on the right -> (embeddings
    [B, hidden] float32, slots that fell on held experts [B] int32, how
    many expert layers worked on the sized slot buffer, an int32 scalar)."""
    eps = config.rms_norm_eps
    with scope("embed"):
        real = ids != 0
        x = params["embed"][ids].astype(jnp.float32)
        if config.mup_enabled:
            x = x * math.sqrt(config.hidden_size)
        tables = rope_tables(config, ids.shape[1])
        attention_fn = _with_lengths(attention_fn, real)
        window_attention_fn = _with_lengths(window_attention_fn, real)
    slots_held = jnp.zeros((ids.shape[0],), jnp.int32)
    sized = jnp.zeros((), jnp.int32)
    for i in range(config.num_hidden_layers):
        p = params["layers"][str(i)]
        sliding = config.is_sliding(i)
        # a norm is in the scope of the first part it feeds, a residual
        # sum in that of the part it closes
        with scope("attn.qkv"):
            u = _rms(x, p["norm_in"], eps).astype(dtype)
        attended = _attention(
            config, p["attn"], u, tables if sliding else None,
            window_attention_fn if sliding else attention_fn,
        )
        with scope("attn.out"):
            x = x + _rms(attended, p["norm_post_attn"], eps)
        with scope("mlp"):
            u = _rms(x, p["norm_pre_mlp"], eps)
            if i < config.num_dense_layers:
                x = x + _rms(v2._swiglu(p["mlp"], u.astype(dtype)), p["norm_post_mlp"], eps)
                continue
        routed, count, fits = v2._routed(
            config, p["moe"], u, real, experts_fn, combine_fn=combine_fn
        )
        with scope("mlp"):
            shared = v2._swiglu(p["moe"]["shared"], u.astype(dtype))
            x = x + _rms(shared + routed, p["norm_post_mlp"], eps)
        slots_held, sized = slots_held + count, sized + fits
    with scope("pool"):
        out = v2._mean_real_state(_rms(x, params["final_norm"], eps), real)
    return out, slots_held, sized


def afmoe_model_function(
    size: str = "trinity-mini-tiny",
    dtype=jnp.float32,
    seed: int = 0,
    weights_file: Optional[str] = None,
    attention_fn=None,
    window_attention_fn=None,
    experts_fn=None,
    combine_fn=None,
    name: Optional[str] = None,
):
    """The ``embed`` ModelFunction over ids batches (or ``(ids, mask)``
    tuples, as TextEmbedder feeds them). ``attention_fn`` (the full
    layers), ``window_attention_fn`` (the sliding ones), ``experts_fn``
    and ``combine_fn`` default to the build-time choice of
    ``make_flash_attention_fn(causal=True)``, the same with
    ``window=sliding_window``, both in blocks of 512 as Jamba's,
    ``make_grouped_matmul_fn()`` and ``make_moe_combine_fn()``: the
    Pallas kernels on TPU.

    The program's result is [B, hidden + 3]: the embedding and DeepSeek's
    three row counters (``moe.slots_held``, ``moe.buffer_sized``,
    ``moe.buffer_full``), which ``TextEmbedder`` strips. Per dispatched
    token it counts ``attn.window_tokens`` (once a sliding layer) and
    ``attn.full_tokens`` (once a full one), per real token
    ``moe.slots_routed``; ``mf.batch_counters`` counts the attention
    kernels' query blocks, ``attn.query_blocks`` and
    ``attn.query_blocks_run``, over the five layers
    (``deepseek_v2.query_block_counters``, each attention at its own
    layers: nothing for one that says no block count, the dense
    fallbacks)."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.ops.flash_attention import make_flash_attention_fn
    from sparkdl_tpu.ops.grouped_matmul import make_grouped_matmul_fn
    from sparkdl_tpu.ops.moe_combine import make_moe_combine_fn

    if size not in _SIZES:
        raise ValueError(f"Unknown AFMoE size {size!r}; supported: {sorted(_SIZES)}")
    config = _SIZES[size]()
    if attention_fn is None:
        attention_fn = make_flash_attention_fn(block_q=512, block_k=512, causal=True)
    if window_attention_fn is None:
        window_attention_fn = make_flash_attention_fn(
            block_q=512, block_k=512, causal=True, window=config.sliding_window
        )
    if experts_fn is None:
        experts_fn = make_grouped_matmul_fn()
    if combine_fn is None:
        combine_fn = make_moe_combine_fn()
    if weights_file:
        params = load_flat(param_shapes(config), weights_file, dtype, v2._leaf_dtype)
    else:
        params = init_params(config, seed, dtype)

    def fn(p, x):
        ids = x[0] if isinstance(x, (tuple, list)) else x
        out, slots_held, sized = forward(
            config, p, ids, dtype=dtype, attention_fn=attention_fn,
            window_attention_fn=window_attention_fn, experts_fn=experts_fn,
            combine_fn=combine_fn,
        )
        with scope("pool"):
            sized = jnp.broadcast_to(sized, slots_held.shape)
            counts = jnp.stack([slots_held, sized, config.expert_layers - sized], 1)
            return jnp.concatenate([out, counts.astype(jnp.float32)], 1)

    mf = ModelFunction(fn, params, input_dtype=jnp.int32, name=name or f"{size}[embed]")
    mf.weights_as_arguments = True
    mf.vocab_size = config.vocab_size
    mf.attention = getattr(attention_fn, "kind", "custom")
    mf.window_attention = getattr(window_attention_fn, "kind", "custom")
    mf.experts = getattr(experts_fn, "kind", "custom")
    mf.combine = getattr(combine_fn, "kind", "custom")
    mf.row_counters = ("moe.slots_held", "moe.buffer_sized", "moe.buffer_full")
    sliding = config.sliding_layers
    full = config.num_hidden_layers - sliding
    mf.dispatched_token_counters = {"attn.window_tokens": sliding, "attn.full_tokens": full}

    def batch_counters(ids, real) -> dict:
        counted = {}
        for fn, layers in ((window_attention_fn, sliding), (attention_fn, full)):
            for name, count in v2.query_block_counters(fn, layers, ids, real, "attn").items():
                counted[name] = counted.get(name, 0) + count
        return counted

    mf.batch_counters = batch_counters
    mf.real_token_counters = {
        "moe.slots_routed": config.num_experts_per_tok * config.expert_layers
    }
    return mf
