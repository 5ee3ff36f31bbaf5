"""DeepSeek-V2 (``model_type: deepseek_v2``; DeepSeek-AI 2024,
arXiv:2405.04434): latent attention (MLA) and a feed-forward of shared
plus routed experts, the first family here with either.

    h = h + MLA(rms(h; w_in));  h = h + FFN_i(rms(h; w_ff))
    FFN_i = SwiGLU(intermediate_size) for i < first_k_dense, else Shared(u) + Routed(u)

    MLA(u), heads of [nope | rope] queries and keys, causal, positions from 0:
      c_q = rms(W_qa u; w_qn);        q = W_qb c_q -> per head [q_nope | q_pe]
      [c_kv | k_pe] = W_kva u;        kv = W_kvb rms(c_kv; w_kvn) -> per head [k_nope | v]
      q_pe, k_pe = rope(q_pe, t), rope(k_pe, t)     k_pe one vector a token, for all heads
      score(t,s) = (q_nope_t.k_nope_s + q_pe_t.k_pe_s) * (nope + rope)^-0.5 * m^2
      out = W_o concat_heads(softmax_{s<=t}(score) v)
    Routed(u): s = softmax(W_g u) over ALL the model's experts, float32;
      the ``topk_group`` groups with the largest best score are kept, top-k
      of s over them -> (e_k, s_k); w_k = routed_scaling_factor * s_k, not
      renormalised; sum over the k whose expert this chip holds.

**The chip's share.** ``experts_held = (first, end)`` are the experts
whose weights are here; the router keeps the model's width and its
experts a token. The routed sum over the held ones, with no slot dropped
at any load, is ``models/moe.py:routed``, handed this module's ``route``.

The rotary part follows YaRN with frequencies fixed at build time. The
source de-interleaves a rotary vector (x0,x1,x2,..) -> (x0,x2,..|x1,x3,..)
and then rotates halves; here the de-interleave is a permutation of the
projections' output columns (of ``q_b`` and ``kv_a``), applied to the
weights inside the program, so the rotation reads contiguous halves and
every score is the source's (``mla_inputs`` says how the rotation is shared
between the two sides of a score).

``embed`` is the mean, over a row's real tokens, of the final RMSNorm of
their states: every token's routing then moves the answer by its share
and no single discrete choice decides a row (``layers.mean_real_state``).
Precision: matrices and the activations that feed them are ``dtype``,
every product accumulates in float32; the residual stream, norms,
softmax, rotary angles, the router (operands too, ``highest``), the
routing weights and the combine are float32. Attention is
``ops/flash_attention.py:flash_attention_latent`` (causal, over the
projections' own arrays; handed each row's length where it takes them,
``layers.row_lengths``, so that query blocks of padding alone are not run) and
the routed experts ``ops/grouped_matmul.py`` and their combine
``ops/moe_combine.py``: the Pallas kernels on TPU, plain ``jax.numpy``
elsewhere, chosen at build time and reported as ``mf.attention``,
``mf.experts`` and ``mf.combine``. Layers are unrolled
into one program with every layer's weights an argument of its own
(``weights_as_arguments``), as ``models/jamba.py`` does.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.models import moe
from sparkdl_tpu.models.layers import (
    attention_batch_counters,
    dense,
    leaf_dtype,
    load_flat,
    mean_real_state,
    preset,
    rms,
    row_lengths,
    silu,
    turn,
    unflatten,
)
from sparkdl_tpu.models.moe import route
from sparkdl_tpu.ops.moe_combine import gather_combine
from sparkdl_tpu.utils.profiler import scope


@dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_layers: int = 60
    first_k_dense: int = 1
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 16.0
    #: which gate ``moe.route`` computes: ``softmax`` (this family) or
    #: ``sigmoid`` (``models/deepseek_v32.py``)
    scoring_func: str = "softmax"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    #: [first, end) of the routed experts whose weights this chip holds
    experts_held: Tuple[int, int] = (0, 160)
    #: slot rows a pass of the routed path's worst-case arm (``moe.routed``),
    #: for a configuration whose one buffer of every slot does not fit
    #: beside its weights; None: one buffer
    worst_case_chunk_rows: Optional[int] = None

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def softmax_scale(self) -> float:
        m = _yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


def deepseek_v2() -> DeepseekV2Config:
    """One chip's share of DeepSeek-V2 as ``benchmarks/configs/
    deepseek-v2.json`` cuts it: every published width, the leading dense
    layer and four expert layers of the 60, experts 0-39 of the 160
    (routing groups 0 and 1 of 8), a quarter of the vocabulary."""
    return DeepseekV2Config(vocab_size=25600, num_layers=5, experts_held=(0, 40))


def deepseek_v2_tiny() -> DeepseekV2Config:
    """The same family at a size the CPU tests hold: a dense layer and
    two expert layers, 16 experts in 4 groups of which 2, top-3, this
    chip's share the first group."""
    return DeepseekV2Config(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        moe_intermediate_size=32,
        num_layers=3,
        num_heads=4,
        q_lora_rank=24,
        kv_lora_rank=16,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        n_routed_experts=16,
        num_experts_per_tok=3,
        n_group=4,
        topk_group=2,
        experts_held=(0, 4),
    )


_SIZES = {"deepseek-v2": deepseek_v2, "deepseek-v2-tiny": deepseek_v2_tiny}


def layer_shapes(config: DeepseekV2Config, i: int) -> dict:
    """{path under ``layers/<i>/``: shape}; matrices are [in, out], a
    layer's held experts stacked [expert, in, out]."""
    h, heads = config.hidden_size, config.num_heads
    nope, rope, dv = config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim
    rq, rkv = config.q_lora_rank, config.kv_lora_rank
    shapes = {
        "norm_in": (h,),
        "norm_ff": (h,),
        "attn/q_a": (h, rq),
        "attn/q_norm": (rq,),
        "attn/q_b": (rq, heads * (nope + rope)),
        "attn/kv_a": (h, rkv + rope),
        "attn/kv_norm": (rkv,),
        "attn/kv_b": (rkv, heads * (nope + dv)),
        "attn/o": (heads * dv, h),
    }
    if i < config.first_k_dense:
        f = config.intermediate_size
        shapes.update({"mlp/gate": (h, f), "mlp/up": (h, f), "mlp/down": (f, h)})
        return shapes
    f = config.moe_intermediate_size
    shared = config.n_shared_experts * f
    held = config.experts_held[1] - config.experts_held[0]
    shapes.update({
        "moe/router": (h, config.n_routed_experts),
        "moe/shared/gate": (h, shared),
        "moe/shared/up": (h, shared),
        "moe/shared/down": (shared, h),
        "moe/experts/gate": (held, h, f),
        "moe/experts/up": (held, h, f),
        "moe/experts/down": (held, f, h),
    })
    return shapes


def param_shapes(config: DeepseekV2Config, layer_shapes=layer_shapes) -> dict:
    """{flat path: shape} of every leaf, as a weights file names them;
    ``layer_shapes`` is the family's (this module's by default)."""
    h = config.hidden_size
    shapes = {"embed": (config.vocab_size, h), "final_norm": (h,)}
    for i in range(config.num_layers):
        for name, shape in layer_shapes(config, i).items():
            shapes[f"layers/{i}/{name}"] = shape
    return shapes


def init_params(config: DeepseekV2Config, seed: int, dtype) -> dict:
    """Random weights scaled by fan-in; the router's twice as wide, so
    that its scores are spread and not flat."""
    rng = np.random.default_rng([int(seed), 0xD5E2])
    flat = {}
    for path, shape in param_shapes(config).items():
        kind = path.rsplit("/", 1)[-1]
        if "norm" in kind:
            v = np.ones(shape, np.float32)
        elif kind == "embed":
            v = rng.standard_normal(shape, dtype=np.float32)
        else:
            fan_in = shape[-2]
            v = rng.standard_normal(shape, dtype=np.float32) / math.sqrt(fan_in)
            if kind == "router":
                v *= 2.0
        flat[path] = jnp.asarray(v, leaf_dtype(path, shape, dtype))
    return unflatten(flat)


# -- rotary positions (YaRN) --------------------------------------------------


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_range(config: DeepseekV2Config) -> Tuple[int, int]:
    """(low, high): the frequency pairs between which YaRN blends."""
    dim = config.qk_rope_head_dim

    def correction(rotations):
        return (
            dim
            * math.log(config.rope_original_max_position / (rotations * 2 * math.pi))
            / (2 * math.log(config.rope_theta))
        )

    low = math.floor(correction(config.rope_beta_fast))
    high = math.ceil(correction(config.rope_beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(config: DeepseekV2Config) -> np.ndarray:
    dim = config.qk_rope_head_dim
    f = 1.0 / config.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_range(config)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (f * (1 - ramp) + f / config.rope_factor * ramp).astype(np.float32)


def rope_tables(config: DeepseekV2Config, length: int):
    """(cos, sin), each [L, rope] float32: both halves carry the pair's
    angle, scaled by mscale(factor, mscale) / mscale(factor, all_dim)."""
    angle = np.arange(length, dtype=np.float32)[:, None] * yarn_inv_freq(config)
    angle = np.concatenate([angle, angle], -1)
    factor = _yarn_mscale(config.rope_factor, config.rope_mscale) / _yarn_mscale(
        config.rope_factor, config.rope_mscale_all_dim
    )
    return (
        jnp.asarray(np.cos(angle) * factor, jnp.float32),
        jnp.asarray(np.sin(angle) * factor, jnp.float32),
    )


def _deinterleave(width: int) -> np.ndarray:
    """Column order (0, 2, 4, .. | 1, 3, 5, ..): the source's
    de-interleave, as a permutation of a projection's output columns."""
    return np.concatenate([np.arange(0, width, 2), np.arange(1, width, 2)])


def _rotate(x, cos, sin):
    """x [..., L, rope] float32, de-interleaved: halves rotated by the
    tables' angles."""
    return x * cos + turn(x) * sin


# -- the layers ---------------------------------------------------------------


def _query_weights(config: DeepseekV2Config, w):
    """``q_b`` [rank, H * (nope + rope)] -> [rank, H * (nope + 2 * rope)]:
    a head's columns [nope | r | turn(r)], r its rotary columns
    de-interleaved. ``x @ turn(w) = turn(x @ w)``, so the projection
    itself writes both terms of ``rope(x) = x * cos + turn(x) * sin``
    side by side and no lane moves afterwards."""
    heads, nope = config.num_heads, config.qk_nope_head_dim
    w = w.reshape(w.shape[0], heads, -1)
    r = w[..., nope:][..., _deinterleave(config.qk_rope_head_dim)]
    return jnp.concatenate([w[..., :nope], r, turn(r)], -1).reshape(w.shape[0], -1)


def mla_inputs(config: DeepseekV2Config, p, u, tables):
    """u [B, L, hidden] in the compute dtype -> (the query latent c_q
    [B, L, q_lora_rank], and the attention kernel's three operands q, kv,
    k_rope), all in the compute dtype.

    Every array the kernel reads is what a projection wrote, heads side
    by side on the last axis: q [B, L, H * (nope + 2 rope)], the
    up-projected latent kv [B, L, H * (nope + v)] whole, and the one
    rotary key a token [B, L, 2 rope]. The rotation is split between the
    two sides of the score so that it costs no lane shuffle on q: a
    head's rotary query is left as [x * cos | turn(x) * sin] and the
    rotated key is written twice, [k | k]; their product over the 2 rope
    lanes is rope(x) . k, each term a bfloat16 operand accumulated in
    float32 with the rest of the score."""
    dtype, eps = u.dtype, config.rms_norm_eps
    rows, length, _ = u.shape
    heads, nope, rope = config.num_heads, config.qk_nope_head_dim, config.qk_rope_head_dim
    rkv = config.kv_lora_rank
    cos, sin = tables

    with scope("mla.q"):
        c_q = rms(dense(u, p["q_a"]), p["q_norm"], eps).astype(dtype)
        q = dense(c_q, _query_weights(config, p["q_b"]))
        by_lane = jnp.concatenate([jnp.ones((length, nope), jnp.float32), cos, sin], -1)
        by_lane = jnp.broadcast_to(by_lane[:, None], (length, heads, nope + 2 * rope))
        q = (q * by_lane.reshape(length, -1)).astype(dtype)

    with scope("mla.kv"):
        kv_a = dense(u, p["kv_a"])  # [B, L, rkv + rope] float32
        c_kv = rms(kv_a[..., :rkv], p["kv_norm"], eps).astype(dtype)
        kv = dense(c_kv, p["kv_b"]).astype(dtype)  # a head's [k_nope | v]
        k_pe = _rotate(kv_a[..., rkv:][..., _deinterleave(rope)], cos, sin).astype(dtype)
        return c_q, q, kv, jnp.concatenate([k_pe, k_pe], -1)


def mla(config: DeepseekV2Config, p, u, tables, attention_fn, by_length):
    """u [B, L, hidden] in the compute dtype -> [B, L, hidden] float32:
    dense causal latent attention (``by_length``: ``layers.row_lengths``).
    A family that selects each query's keys (``models/deepseek_v32.py``)
    takes :func:`mla_inputs` and hands its selection to the kernel
    itself."""
    _, q, kv, k_pe = mla_inputs(config, p, u, tables)
    with scope("mla.core"):
        o = attention_fn(q, kv, k_pe, u.dtype, **by_length)
    with scope("mla.out"):
        return dense(o, p["o"])


def _swiglu(p, u):
    gate = silu(dense(u, p["gate"]))
    return dense((gate * dense(u, p["up"])).astype(u.dtype), p["down"])


def forward(
    config: DeepseekV2Config, params, ids, *, dtype, attention_fn, experts_fn,
    combine_fn=gather_combine,
):
    """ids [B, L] int32, zero-padded on the right -> (embeddings
    [B, hidden] float32, slots that fell on held experts [B] int32, how
    many expert layers worked on the sized slot buffer, an int32 scalar)."""
    eps = config.rms_norm_eps
    with scope("embed"):
        real = ids != 0
        tables = rope_tables(config, ids.shape[1])
        x = params["embed"][ids].astype(jnp.float32)
        by_length = row_lengths(attention_fn, real)
    slots_held = jnp.zeros((ids.shape[0],), jnp.int32)
    sized = jnp.zeros((), jnp.int32)
    for i in range(config.num_layers):
        p = params["layers"][str(i)]
        # a norm is in the scope of the first part it feeds, a residual
        # sum in that of the part it closes
        with scope("mla.q"):
            u = rms(x, p["norm_in"], eps).astype(dtype)
        attended = mla(config, p["attn"], u, tables, attention_fn, by_length)
        with scope("mla.out"):
            x = x + attended
        with scope("mlp"):
            u = rms(x, p["norm_ff"], eps)
            if i < config.first_k_dense:
                x = x + _swiglu(p["mlp"], u.astype(dtype))
                continue
        # the gate is this module's global: a planted fault replaces it here
        routed, count, fits = moe.routed(
            config, p["moe"], u, real, experts_fn, gate=route, combine_fn=combine_fn
        )
        with scope("mlp"):
            x = x + _swiglu(p["moe"]["shared"], u.astype(dtype))
        with scope("moe.routed"):
            x = x + routed
        slots_held, sized = slots_held + count, sized + fits
    with scope("pool"):
        out = mean_real_state(rms(x, params["final_norm"], eps), real)
    return out, slots_held, sized


def deepseek_v2_model_function(
    size: str = "deepseek-v2-tiny",
    dtype=jnp.float32,
    seed: int = 0,
    weights_file: Optional[str] = None,
    attention_fn=None,
    experts_fn=None,
    combine_fn=None,
    name: Optional[str] = None,
):
    """The ``embed`` ModelFunction (``moe.routed_model_function``: the
    embedding and ``moe.ROW_COUNTERS`` a row). ``attention_fn`` defaults
    to the build-time choice of ``make_latent_attention_fn(heads, scale)``,
    the Pallas kernel on TPU. Per dispatched token it counts
    ``mla.attention_tokens`` (once a layer); ``mf.batch_counters``
    counts ``mla.pairs_computed``, ``mla.query_blocks`` and
    ``mla.query_blocks_run`` (``layers.attention_batch_counters``)."""
    from sparkdl_tpu.ops import flash_attention

    config = preset(_SIZES, size, "DeepSeek-V2")
    if attention_fn is None:
        # blocks of 1,024 tokens: at 8 x 128 heads x 2,048 the blocked
        # kernel read 23.4 ms a call against 24.9 at 512 and 42.0 at 256
        # (7.6, 8.3, 13.5 at 1,024 tokens; PERF.md, PR 32)
        attention_fn = flash_attention.make_latent_attention_fn(
            config.num_heads, config.softmax_scale, block=1024
        )
    if weights_file:
        params = load_flat(param_shapes(config), weights_file, dtype, leaf_dtype)
    else:
        params = init_params(config, seed, dtype)
    return moe.routed_model_function(
        config, params, forward,
        {"attention_fn": attention_fn, "experts_fn": experts_fn, "combine_fn": combine_fn},
        dtype=dtype, name=name or f"{size}[embed]",
        dispatched_token_counters={"mla.attention_tokens": config.num_layers},
        batch_counters=functools.partial(
            attention_batch_counters, attention_fn, config.num_layers
        ),
    )
