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
whose weights are here. The router keeps the model's width and its
experts a token; a (token, k) slot whose expert is absent is computed by
nobody here and nothing stands in for it: the partial sum goes on. Pad
tokens (id 0) are not routed: a causal stack never lets a real token see
them. No slot is dropped at any load: the slots are sorted by expert,
absent ones last, and ``ops/grouped_matmul.py`` is given the held
experts' group sizes. The buffer they are sorted into has the rows the
share implies (``slot_capacity``: held / routed experts of the tokens x k
slots, with room above), and the worst-case buffer of tokens x k rows is
the other arm of a ``lax.cond`` on the measured load (``_routed``), so a
load over the share costs time and never a slot; a chip that holds every
expert has the one worst-case body and no conditional. Which arm each
expert layer took rides back with the rows (``mf.row_counters``).

The rotary part follows YaRN with frequencies fixed at build time. The
source de-interleaves a rotary vector (x0,x1,x2,..) -> (x0,x2,..|x1,x3,..)
and then rotates halves; here the de-interleave is a permutation of the
projections' output columns (of ``q_b`` and ``kv_a``), applied to the
weights inside the program, so the rotation reads contiguous halves and
every score is the source's (``_mla`` says how the rotation is shared
between the two sides of a score).

``embed`` is the mean, over a row's real tokens, of the final RMSNorm of
their states: every token's routing then moves the answer by its share
and no single discrete choice decides a row (``_mean_real_state``).
Precision: matrices and the activations that feed them are ``dtype``,
every product accumulates in float32; the residual stream, norms,
softmax, rotary angles, the router (operands too, ``highest``), the
routing weights and the combine are float32. Attention is
``ops/flash_attention.py:flash_attention_latent`` (causal, over the
projections' own arrays; handed each row's length where it takes them,
``row_lengths``, so that query blocks of padding alone are not run) and
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
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.models.jamba import (
    _dense,
    _rms,
    _silu,
    _unflatten,
    load_flat,
)
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
    #: which gate ``route`` computes: ``softmax`` (this family) or
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
    #: slot rows a pass of the routed path's worst-case arm (``_routed``),
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


def _leaf_dtype(path: str, shape: tuple, dtype) -> Any:
    """Matrices (and the embedding) are ``dtype``; norm weights and the
    router feed float32 arithmetic and stay float32."""
    small = len(shape) == 1 or path.endswith("/router")
    return jnp.float32 if small else dtype


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
        flat[path] = jnp.asarray(v, _leaf_dtype(path, shape, dtype))
    return _unflatten(flat)


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


def _turn(x):
    """Rotate halves: (a | b) -> (-b | a) on the last axis."""
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def _rotate(x, cos, sin):
    """x [..., L, rope] float32, de-interleaved: halves rotated by the
    tables' angles."""
    return x * cos + _turn(x) * sin


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
    return jnp.concatenate([w[..., :nope], r, _turn(r)], -1).reshape(w.shape[0], -1)


def _mla_inputs(config: DeepseekV2Config, p, u, tables):
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
        c_q = _rms(_dense(u, p["q_a"]), p["q_norm"], eps).astype(dtype)
        q = _dense(c_q, _query_weights(config, p["q_b"]))
        by_lane = jnp.concatenate([jnp.ones((length, nope), jnp.float32), cos, sin], -1)
        by_lane = jnp.broadcast_to(by_lane[:, None], (length, heads, nope + 2 * rope))
        q = (q * by_lane.reshape(length, -1)).astype(dtype)

    with scope("mla.kv"):
        kv_a = _dense(u, p["kv_a"])  # [B, L, rkv + rope] float32
        c_kv = _rms(kv_a[..., :rkv], p["kv_norm"], eps).astype(dtype)
        kv = _dense(c_kv, p["kv_b"]).astype(dtype)  # a head's [k_nope | v]
        k_pe = _rotate(kv_a[..., rkv:][..., _deinterleave(rope)], cos, sin).astype(dtype)
        return c_q, q, kv, jnp.concatenate([k_pe, k_pe], -1)


def _last_real_position(real, xp):
    """real [B, L] bool -> [B] int32, a row's last real position + 1 (0
    for a row of padding): its length. Not the count of its real tokens,
    so that an id 0 inside a text could never cut a row short. ``xp``:
    ``jax.numpy`` in the program, ``numpy`` where the host counts."""
    position = xp.arange(1, real.shape[1] + 1, dtype=xp.int32)
    return xp.max(xp.where(real, position, 0), 1)


def row_lengths(attention_fn, real):
    """What an attention that takes its rows' lengths is handed besides
    its operands, as keywords: real [B, L] bool -> ``{"lengths": [B]
    int32}`` (:func:`_last_real_position`). Nothing for any other
    attention: what stands in for one in a test or a planted fault keeps
    its arguments."""
    if not getattr(attention_fn, "takes_lengths", False):
        return {}
    return {"lengths": _last_real_position(real, jnp)}


def _mla(config: DeepseekV2Config, p, u, tables, attention_fn, by_length):
    """u [B, L, hidden] in the compute dtype -> [B, L, hidden] float32:
    dense causal latent attention (``by_length``: :func:`row_lengths`).
    A family that selects each query's keys (``models/deepseek_v32.py``)
    takes :func:`_mla_inputs` and hands its selection to the kernel
    itself."""
    _, q, kv, k_pe = _mla_inputs(config, p, u, tables)
    with scope("mla.core"):
        o = attention_fn(q, kv, k_pe, u.dtype, **by_length)
    with scope("mla.out"):
        return _dense(o, p["o"])


def _swiglu(p, u):
    gate = _silu(_dense(u, p["gate"]))
    return _dense((gate * _dense(u, p["up"])).astype(u.dtype), p["down"])


def route(config: DeepseekV2Config, u, router, bias=None):
    """u [T, hidden], router [hidden, experts], both float32 ->
    (experts [T, k] int32, weights [T, k] float32) over all the model's
    experts. The configuration's own keys say which gate:

    - DeepSeek-V2 (``scoring_func`` softmax, ``group_limited_greedy``):
      softmax scores, a group ranked by
      its best score, top-k of the scores over the kept groups, and the
      weights EITHER renormalised (``norm_topk_prob``) OR scaled by
      ``routed_scaling_factor``.
    - DeepSeek-V3 and V3.2 (``scoring_func`` sigmoid, ``noaux_tc``):
      sigmoid scores s; the CHOICE is made on s + ``bias`` (a learned
      correction, [experts] float32), a group ranked by the sum of its
      two best, top-k over the kept groups; the weights are the chosen
      experts' s without the bias, renormalised (``norm_topk_prob``) and
      THEN scaled."""
    n, groups = config.n_routed_experts, config.n_group
    logits = jnp.einsum(
        "ti,io->to", u.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if config.scoring_func == "sigmoid":
        return _route_by_corrected_sigmoid(config, logits, bias)
    scores = jax.nn.softmax(logits, -1)
    if groups > 1:
        best = scores.reshape(-1, groups, n // groups).max(-1)
        _, kept = jax.lax.top_k(best, config.topk_group)
        keep = jnp.any(kept[..., None] == jnp.arange(groups), -2)  # [T, groups]
        scores = jnp.where(jnp.repeat(keep, n // groups, -1), scores, 0.0)
    weights, experts = jax.lax.top_k(scores, config.num_experts_per_tok)
    if config.norm_topk_prob and config.num_experts_per_tok > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    else:
        weights = weights * config.routed_scaling_factor
    return experts, weights


def _route_by_corrected_sigmoid(config, logits, bias):
    """``route``'s second gate (``noaux_tc``), from the router's logits."""
    n, groups, top_k = config.n_routed_experts, config.n_group, config.num_experts_per_tok
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias.astype(jnp.float32)
    if groups > 1:
        two_best, _ = jax.lax.top_k(choice.reshape(-1, groups, n // groups), 2)
        _, kept = jax.lax.top_k(two_best.sum(-1), config.topk_group)
        keep = jnp.any(kept[..., None] == jnp.arange(groups), -2)  # [T, groups]
        choice = jnp.where(jnp.repeat(keep, n // groups, -1), choice, -jnp.inf)
    _, experts = jax.lax.top_k(choice, top_k)
    weights = jnp.take_along_axis(scores, experts, -1)
    if config.norm_topk_prob and top_k > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts, weights * config.routed_scaling_factor


#: the grouped kernel's row tile at an expert layer's sizes
#: (``ops/grouped_matmul.py:choose_tiling``)
_ROW_TILE = 256
#: room above the held share's expectation in the sized slot buffer. The
#: load of a dispatch of 16,384 tokens spreads under 1% and its fullest
#: reading is 24.60% of the routed slots for a held quarter (PERF.md,
#: PR 32); a load over the buffer costs the worst-case arm's time and no
#: slot, so the low end of what covers every reading is the one to take
_CAPACITY_MARGIN = 1.25


def slot_capacity(config: DeepseekV2Config, tokens: int) -> int:
    """Rows of the routed path's slot buffer for a dispatch of ``tokens``:
    the chip's share of the ``tokens x k`` slots with ``_CAPACITY_MARGIN``
    of room, in whole row tiles; all the slots where that is no fewer (a
    chip that holds every expert, shapes too small to round)."""
    slots = tokens * config.num_experts_per_tok
    first, end = config.experts_held
    room = _CAPACITY_MARGIN * (end - first) / config.n_routed_experts * slots
    return min(slots, -(-math.ceil(room) // _ROW_TILE) * _ROW_TILE)


def _experts_and_combine(
    rows, experts_fn, experts, flat, order, sizes, slot, weights, held, *,
    combine_fn=gather_combine,
):
    """The held slots' three products over a buffer of ``rows`` slot rows
    (static; the sorted slots' first ``rows``, which must hold every held
    one) and their weighted sum back in token order [tokens, hidden]
    float32 by ``combine_fn`` (``ops/moe_combine.py``)."""
    top_k = weights.shape[1]
    with scope("moe.gather"):
        x = flat[order[:rows] // top_k]  # [rows, hidden]
    with scope("moe.experts"):
        gate = experts_fn(x, experts["gate"], sizes)
        up = experts_fn(x, experts["up"], sizes)
        y = experts_fn((_silu(gate) * up).astype(x.dtype), experts["down"], sizes)
    with scope("moe.combine"):
        # back to (token, k) order; what the kernel left unwritten is not read
        return combine_fn(y, jnp.where(held, slot, -1), weights)


def _experts_in_chunks(
    rows, experts_fn, experts, flat, order, sizes, slot, weights, held
):
    """:func:`_experts_and_combine` over every slot, ``rows`` sorted slot
    rows a pass (static, a divisor of the slots): each pass gives the
    kernel the part of every group that lies in its rows and adds its
    weighted rows to their tokens. A pass past the last held slot does
    nothing, so the arm costs what the load costs. No slot is dropped."""
    tokens, top_k = weights.shape
    ends = jnp.cumsum(sizes)
    starts, total = ends - sizes, ends[-1]
    by_slot = weights.reshape(-1)

    def one_pass(out, lo):
        def compute(out):
            with scope("moe.gather"):
                at = jax.lax.dynamic_slice(order, (lo,), (rows,))
                part = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
                x = flat[at // top_k]
            with scope("moe.experts"):
                gate = experts_fn(x, experts["gate"], part)
                up = experts_fn(x, experts["up"], part)
                y = experts_fn((_silu(gate) * up).astype(x.dtype), experts["down"], part)
            with scope("moe.combine"):
                # what the kernel left unwritten is not read
                mine = (lo + jnp.arange(rows) < total)[:, None]
                y = jnp.where(mine, y * by_slot[at][:, None], 0.0)
                return out.at[at // top_k].add(y)

        return jax.lax.cond(lo < total, compute, lambda out: out, out), None

    out = jnp.zeros((tokens, flat.shape[1]), jnp.float32)
    out, _ = jax.lax.scan(one_pass, out, jnp.arange(0, tokens * top_k, rows))
    return out


def _routed(
    config: DeepseekV2Config, p, u, real, experts_fn, *, combine_fn=gather_combine
):
    """u [B, L, hidden] float32 (the norm's output), real [B, L] bool ->
    (the held experts' part of the routed sum [B, L, hidden] float32,
    how many of each row's slots fell on held experts [B] int32, whether
    the sized slot buffer held the load, a bool scalar). ``combine_fn``
    sums the rows back in token order (``ops/moe_combine.py``; the
    worst-case arm in passes, ``worst_case_chunk_rows``, adds its own).

    The slot buffer is ``slot_capacity`` rows, and the worst-case buffer
    of every slot is the other arm of a ``lax.cond`` on the measured load:
    no slot is dropped in either. The slots are sorted held-first, so the
    first ``sum(sizes)`` rows are the same rows in both arms, the kernel
    visits the same tiles and the sized arm does the full one's arithmetic
    in its order: the same bits wherever the compiler fuses the two alike
    (PERF.md, PR 33). Where the two row counts are one there is one body."""
    rows, length, hidden = u.shape
    tokens, top_k = rows * length, config.num_experts_per_tok
    first, end = config.experts_held
    flat = u.reshape(tokens, hidden)
    # the first gate is called as it always was: what stands in for
    # ``route`` in a test or a planted fault takes three arguments
    with scope("moe.route"):
        if "router_bias" in p:
            experts, weights = route(config, flat, p["router"], p["router_bias"])
        else:
            experts, weights = route(config, flat, p["router"])
    with scope("moe.routed"):
        held = (experts >= first) & (experts < end) & real.reshape(tokens, 1)
        # slots sorted by expert, those of absent experts (and of pad tokens) last
        key = jnp.where(held, experts - first, end - first).reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(
            key[:, None] == jnp.arange(end - first, dtype=key.dtype), 0, dtype=jnp.int32
        )
        slot = jnp.argsort(order).reshape(tokens, top_k)
        operands = (
            p["experts"], flat.astype(p["experts"]["gate"].dtype), order, sizes,
            slot, weights, held,
        )
        slots, capacity = tokens * top_k, slot_capacity(config, tokens)

        full = functools.partial(
            _experts_and_combine, slots, experts_fn, combine_fn=combine_fn
        )
        chunk = config.worst_case_chunk_rows
        if chunk and slots > chunk:
            full = functools.partial(_experts_in_chunks, chunk, experts_fn)
        if capacity == slots:
            fits, out = jnp.zeros((), bool), full(*operands)
        else:
            sized = functools.partial(
                _experts_and_combine, capacity, experts_fn, combine_fn=combine_fn
            )

            def worst_case(*operands):
                with scope("moe.worst_case"):
                    return full(*operands)

            fits = jnp.sum(sizes) <= capacity
            out = jax.lax.cond(fits, sized, worst_case, *operands)
        count = jnp.sum(held.reshape(rows, -1), 1, dtype=jnp.int32)
        return out.reshape(rows, length, hidden), count, fits


def _mean_real_state(x, real):
    """x [B, L, hidden] float32 averaged over each row's tokens that are
    not padding; a row of padding alone gives zeros."""
    count = jnp.maximum(jnp.sum(real, 1, dtype=jnp.float32), 1.0)
    return jnp.sum(jnp.where(real[..., None], x, 0.0), 1) / count[:, None]


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
            u = _rms(x, p["norm_in"], eps).astype(dtype)
        attended = _mla(config, p["attn"], u, tables, attention_fn, by_length)
        with scope("mla.out"):
            x = x + attended
        with scope("mlp"):
            u = _rms(x, p["norm_ff"], eps)
            if i < config.first_k_dense:
                x = x + _swiglu(p["mlp"], u.astype(dtype))
                continue
        routed, count, fits = _routed(
            config, p["moe"], u, real, experts_fn, combine_fn=combine_fn
        )
        with scope("mlp"):
            x = x + _swiglu(p["moe"]["shared"], u.astype(dtype))
        with scope("moe.routed"):
            x = x + routed
        slots_held, sized = slots_held + count, sized + fits
    with scope("pool"):
        out = _mean_real_state(_rms(x, params["final_norm"], eps), real)
    return out, slots_held, sized


def _counted_lengths(attention_fn, ids, real) -> list:
    """Each row's length as the attention runs it: its last real position
    + 1 (as ``row_lengths`` hands it over) where the attention takes
    lengths, the bucket's edge where it takes none (every block runs)."""
    rows, edge = ids.shape
    if getattr(attention_fn, "takes_lengths", False):
        return _last_real_position(real, np).tolist()
    return [edge] * rows


def query_block_counters(attention_fn, layers: int, ids, real, prefix: str = "mla") -> dict:
    """The query blocks of a dispatched batch ``ids`` [B, L] whose real
    tokens are ``real``, in ``layers`` layers of an attention that says
    how many a row has (``.query_blocks``; nothing for one that does not):

    - ``<prefix>.query_blocks``: rows x layers x the bucket's query blocks;
    - ``<prefix>.query_blocks_run``: those of them that hold a real token,
      every one where the attention takes no lengths."""
    blocks = getattr(attention_fn, "query_blocks", None)
    if blocks is None:
        return {}
    rows, edge = ids.shape
    lengths = _counted_lengths(attention_fn, ids, real)
    return {
        f"{prefix}.query_blocks": rows * layers * blocks(edge),
        f"{prefix}.query_blocks_run": layers * sum(blocks(n) for n in lengths),
    }


def attention_batch_counters(attention_fn, layers: int, ids, real) -> dict:
    """What ``mf.batch_counters`` counts of attention for a dispatched
    batch ``ids`` [B, L] whose real tokens are ``real``, where the
    attention it was built with says what it runs
    (``.pairs_computed``, ``.query_blocks``, ``.takes_lengths``, as
    ``make_latent_attention_fn``'s do):

    - ``mla.pairs_computed``: layers x the (query, key) pairs a head's
      attention runs, summed over the rows: how much of the square was
      run, beside ``mla.attention_tokens``;
    - ``mla.query_blocks`` and ``mla.query_blocks_run``
      (:func:`query_block_counters`).

    A row counts at its length where the attention takes lengths, and at
    the bucket's edge where it takes none (:func:`_counted_lengths`)."""
    pairs = getattr(attention_fn, "pairs_computed", None)
    if pairs is None:
        return {}
    lengths = _counted_lengths(attention_fn, ids, real)
    counters = {"mla.pairs_computed": layers * sum(pairs(n) for n in lengths)}
    counters.update(query_block_counters(attention_fn, layers, ids, real))
    return counters


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
    """The ``embed`` ModelFunction over ids batches (or ``(ids, mask)``
    tuples, as TextEmbedder feeds them). ``attention_fn``, ``experts_fn``
    and ``combine_fn`` default to the build-time choice of
    ``make_latent_attention_fn(heads, scale)``, ``make_grouped_matmul_fn()``
    and ``make_moe_combine_fn()``: the Pallas kernels on TPU.

    The program's result is [B, hidden + 3]: the embedding and, named by
    ``mf.row_counters``, three more columns counted on the device and
    read back with the row: how many of the row's routed slots fell on
    held experts, and how many of its dispatch's expert layers worked on
    the sized slot buffer and on the worst-case one (``_routed``; the
    two sum to the expert layers, so over a job to live rows x expert
    layers). ``TextEmbedder`` strips the columns and adds them to
    counters ``moe.slots_held``, ``moe.buffer_sized`` and
    ``moe.buffer_full``; any other caller slices them off. On the host,
    ``mf.batch_counters(ids, real)`` counts ``mla.pairs_computed``,
    ``mla.query_blocks`` and ``mla.query_blocks_run`` for every
    dispatched batch (:func:`attention_batch_counters`)."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.ops.flash_attention import make_latent_attention_fn
    from sparkdl_tpu.ops.grouped_matmul import make_grouped_matmul_fn
    from sparkdl_tpu.ops.moe_combine import make_moe_combine_fn

    if size not in _SIZES:
        raise ValueError(
            f"Unknown DeepSeek-V2 size {size!r}; supported: {sorted(_SIZES)}"
        )
    config = _SIZES[size]()
    if attention_fn is None:
        # blocks of 1,024 tokens: at 8 x 128 heads x 2,048 the blocked
        # kernel read 23.4 ms a call against 24.9 at 512 and 42.0 at 256
        # (7.6, 8.3, 13.5 at 1,024 tokens; PERF.md, PR 32)
        attention_fn = make_latent_attention_fn(
            config.num_heads, config.softmax_scale, block=1024
        )
    if experts_fn is None:
        experts_fn = make_grouped_matmul_fn()
    if combine_fn is None:
        combine_fn = make_moe_combine_fn()
    if weights_file:
        params = load_flat(param_shapes(config), weights_file, dtype, _leaf_dtype)
    else:
        params = init_params(config, seed, dtype)

    def fn(p, x):
        ids = x[0] if isinstance(x, (tuple, list)) else x
        out, slots_held, sized = forward(
            config, p, ids, dtype=dtype, attention_fn=attention_fn,
            experts_fn=experts_fn, combine_fn=combine_fn,
        )
        with scope("pool"):
            sized = jnp.broadcast_to(sized, slots_held.shape)
            counts = jnp.stack([slots_held, sized, config.expert_layers - sized], 1)
            # at most tokens x k x layers a row: exact in float32
            return jnp.concatenate([out, counts.astype(jnp.float32)], 1)

    mf = ModelFunction(
        fn, params, input_dtype=jnp.int32, name=name or f"{size}[embed]"
    )
    mf.weights_as_arguments = True
    mf.vocab_size = config.vocab_size
    mf.attention = getattr(attention_fn, "kind", "custom")
    mf.experts = getattr(experts_fn, "kind", "custom")
    mf.combine = getattr(combine_fn, "kind", "custom")
    mf.row_counters = ("moe.slots_held", "moe.buffer_sized", "moe.buffer_full")
    # per dispatched token (pad rows and pad tokens too), and per real one
    mf.dispatched_token_counters = {"mla.attention_tokens": config.num_layers}
    mf.real_token_counters = {
        "moe.slots_routed": config.num_experts_per_tok * config.expert_layers
    }
    mf.batch_counters = functools.partial(
        attention_batch_counters, attention_fn, config.num_layers
    )
    return mf
