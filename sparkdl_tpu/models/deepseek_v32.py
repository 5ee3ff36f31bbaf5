"""DeepSeek-V3.2-Exp (``model_type: deepseek_v32``; DeepSeek-AI 2025):
DeepSeek-V3's stack, latent attention (MLA) and shared plus routed
experts, with DeepSeek sparse attention (DSA): in every layer a
*lightning indexer* of its own projections scores every (query, key)
pair and attention sees only the ``index_topk`` best keys of each query.

    h = h + Attn(rms(h; w_in));  h = h + FFN_i(rms(h; w_ff))
    FFN_i = SwiGLU(intermediate_size) for i < first_k_dense, else Shared(u) + Routed(u)

    MLA's projections, rotary split and score are ``models/deepseek_v2.py``'s.
    Indexer (u the normed input, c_q MLA's own query latent):
      qI[t,j] = (W_iq c_q[t])_j, j < index_n_heads: its first ``rope`` lanes rotary at t
      kI[s]   = LayerNorm(W_ik u[s]; g, b): its first ``rope`` lanes rotary at s;
                ONE key a token for all index heads
      w[t,j]  = (W_iw u[t])_j * index_n_heads^-0.5 * index_head_dim^-0.5
      I[t,s]  = sum_j w[t,j] * ReLU(qI[t,j] . kI[s])                    s <= t
      S_t     = the min(index_topk, t + 1) keys s <= t of largest I[t,s],
                of equal scores the lower s first
      Attn: softmax over s in S_t only, every head of token t the same S_t
    Routed(u): ``models/deepseek_v2.py:route``'s second gate (sigmoid
      scores, the choice on score + a learned correction bias, a group
      ranked by the sum of its two best, weights renormalised and then
      scaled), the sum over the k whose expert this chip holds.

**Shared with DeepSeek-V2, adapted and not copied**: ``_mla_inputs`` (the
five projections, YaRN's tables and the rotary split), the latent flash
kernel (given the selection), ``_routed`` with ``slot_capacity``, the
``lax.cond`` on the load and ``_experts_and_combine``, ``_swiglu``,
``_mean_real_state``, weights as arguments and the row counters: each
takes this family's sizes through its configuration. What is this
family's own: the indexer, the gate's second branch, the worst-case
arm of the routed path in passes (``worst_case_chunk_rows``: its one
buffer of tokens x k slot rows is 5.6 GB at 16,384 tokens of 7,168),
and the indexer's rotary, which pairs lane i with i + rope / 2 (halves)
where MLA's pairs 2i with 2i + 1.

A row no longer than ``index_topk`` selects every causal key: a program
for such a length has no indexer and is dense causal MLA. The selection
is exact (``ops/dsa_indexer.py``): selecting fewer keys, or
approximately, would be another model.

Precision: as ``models/deepseek_v2.py``; the indexer's two projections'
and its score product's operands are ``dtype``, accumulated in float32
(the release quantises qI and kI to FP8 after turning both by one
Hadamard matrix, which leaves qI . kI as it is: left out; a v5e has no
FP8 product); LayerNorm, rotary, ReLU, the heads' weights and the sum
over heads are float32. Multi-token prediction's extra block is used in
training and for drafting and is not built; no output head.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.models import deepseek_v2 as v2
from sparkdl_tpu.models.deepseek_v2 import DeepseekV2Config
from sparkdl_tpu.models.jamba import _dense, _rms, _unflatten, load_flat
from sparkdl_tpu.ops.moe_combine import gather_combine
from sparkdl_tpu.utils.profiler import scope


@dataclass(frozen=True)
class DeepseekV32Config(DeepseekV2Config):
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_layers: int = 61
    first_k_dense: int = 3
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    experts_held: Tuple[int, int] = (0, 256)
    #: ``route``'s second gate (``noaux_tc``)
    scoring_func: str = "sigmoid"
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    layer_norm_eps: float = 1e-6
    #: one buffer of tokens x k slot rows is 5.6 GB at 16,384 tokens
    worst_case_chunk_rows: Optional[int] = 16384


def deepseek_v32() -> DeepseekV32Config:
    """One chip's share of DeepSeek-V3.2-Exp as ``benchmarks/configs/
    deepseek-v3.2-exp.json`` cuts it: every published width, one of the
    leading dense layers and four expert layers of the 61, experts 0-7 of
    the 256 (a quarter of routing group 0; 32 chips share a layer), an
    eighth of the vocabulary."""
    return DeepseekV32Config(
        vocab_size=16160, num_layers=5, first_k_dense=1, experts_held=(0, 8)
    )


def deepseek_v32_tiny() -> DeepseekV32Config:
    """The same family at a size the CPU tests hold: a dense layer and
    two expert layers, 16 experts in 4 groups of which 2, top-3, this
    chip's share the first group, and 4 index heads of 16 that pick 16
    keys, so that a row of 64 tokens already selects."""
    return DeepseekV32Config(
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
        num_experts_per_tok=3,
        n_group=4,
        topk_group=2,
        experts_held=(0, 4),
        index_n_heads=4,
        index_head_dim=16,
        index_topk=16,
        worst_case_chunk_rows=None,
    )


_SIZES = {"deepseek-v3.2-exp": deepseek_v32, "deepseek-v3.2-exp-tiny": deepseek_v32_tiny}


def layer_shapes(config: DeepseekV32Config, i: int) -> dict:
    """``deepseek_v2.layer_shapes`` with the indexer's four leaves and the
    gate's correction bias."""
    shapes = v2.layer_shapes(config, i)
    h, heads, dim = config.hidden_size, config.index_n_heads, config.index_head_dim
    shapes.update({
        "attn/indexer/q_b": (config.q_lora_rank, heads * dim),
        "attn/indexer/k": (h, dim),
        "attn/indexer/k_norm": (dim,),
        "attn/indexer/k_norm_bias": (dim,),
        "attn/indexer/weights": (h, heads),
    })
    if i >= config.first_k_dense:
        shapes["moe/router_bias"] = (config.n_routed_experts,)
    return shapes


def param_shapes(config: DeepseekV32Config) -> dict:
    """{flat path: shape} of every leaf, as a weights file names them."""
    return v2.param_shapes(config, layer_shapes)


def init_params(config: DeepseekV32Config, seed: int, dtype) -> dict:
    """Random weights scaled by fan-in, the router's twice as wide; the
    gate's correction bias uniform in +-0.05, LayerNorm's bias zero."""
    rng = np.random.default_rng([int(seed), 0xD5E32])
    flat = {}
    for path, shape in param_shapes(config).items():
        kind = path.rsplit("/", 1)[-1]
        if kind == "router_bias":
            v = rng.uniform(-0.05, 0.05, shape).astype(np.float32)
        elif kind == "k_norm_bias":
            v = np.zeros(shape, np.float32)
        elif "norm" in kind:
            v = np.ones(shape, np.float32)
        elif kind == "embed":
            v = rng.standard_normal(shape, dtype=np.float32)
        else:
            v = rng.standard_normal(shape, dtype=np.float32) / math.sqrt(shape[-2])
            if kind == "router":
                v *= 2.0
        flat[path] = jnp.asarray(v, v2._leaf_dtype(path, shape, dtype))
    return _unflatten(flat)


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _rotate_head(x, cos, sin):
    """x [..., L, (heads,) dim] float32: the first ``rope`` lanes rotated
    by the tables' angles, lane i paired with i + rope / 2; the tables
    carry a pair's angle in both halves."""
    rope = cos.shape[-1]
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    r = x[..., :rope]
    return jnp.concatenate([r * cos + v2._turn(r) * sin, x[..., rope:]], -1)


def index_inputs(config: DeepseekV32Config, p, c_q, u, tables):
    """The indexer's three operands from MLA's query latent c_q and the
    normed input u, both in the compute dtype: qI [B, L, heads * dim] and
    kI [B, L, dim] in the compute dtype, w [B, L, heads] float32."""
    dtype = u.dtype
    rows, length, _ = u.shape
    heads, dim = config.index_n_heads, config.index_head_dim
    cos, sin = tables
    q = _dense(c_q, p["q_b"]).reshape(rows, length, heads, dim)
    q = _rotate_head(q, cos, sin).reshape(rows, length, heads * dim).astype(dtype)
    k = _layer_norm(_dense(u, p["k"]), p["k_norm"], p["k_norm_bias"], config.layer_norm_eps)
    k = _rotate_head(k, cos, sin).astype(dtype)
    w = _dense(u, p["weights"]) * (heads**-0.5 * dim**-0.5)
    return q, k, w


def causal_pairs(real):
    """real [B, L] bool, padded on the right -> how many (query, key)
    pairs dense causal attention reads for each row's real queries,
    [B] int32."""
    n = jnp.sum(real, 1, dtype=jnp.int32)
    return n * (n + 1) // 2


def forward(
    config: DeepseekV32Config, params, ids, *, dtype, attention_fn, experts_fn,
    indexer_fn, combine_fn=gather_combine,
):
    """ids [B, L] int32, zero-padded on the right -> (embeddings
    [B, hidden] float32, slots that fell on held experts [B] int32, how
    many expert layers worked on the sized slot buffer, an int32 scalar,
    the (query, key) pairs the layers' attention read for the row's real
    queries [B] int32). A length within ``index_topk`` runs no indexer.
    The attention and the indexer are each handed the rows' lengths where
    they say they take them (``deepseek_v2.row_lengths``)."""
    eps = config.rms_norm_eps
    selects = ids.shape[1] > config.index_topk
    with scope("embed"):
        real = ids != 0
        tables = v2.rope_tables(config, ids.shape[1])
        x = params["embed"][ids].astype(jnp.float32)
        by_length = v2.row_lengths(attention_fn, real)
        index_by_length = v2.row_lengths(indexer_fn, real) if selects else {}
    slots_held = jnp.zeros((ids.shape[0],), jnp.int32)
    pairs = jnp.zeros((ids.shape[0],), jnp.int32)
    sized = jnp.zeros((), jnp.int32)
    for i in range(config.num_layers):
        p = params["layers"][str(i)]
        # the scopes are ``deepseek_v2.forward``'s, and the indexer's own:
        # its operands, its two kernels (one call here), and the count of
        # what it selected
        with scope("mla.q"):
            u = _rms(x, p["norm_in"], eps).astype(dtype)
        c_q, q, kv, k_pe = v2._mla_inputs(config, p["attn"], u, tables)
        if selects:
            with scope("dsa.index_inputs"):
                operands = index_inputs(config, p["attn"]["indexer"], c_q, u, tables)
            with scope("dsa.select"):
                selection = indexer_fn(*operands, **index_by_length)
            with scope("mla.core"):
                o = attention_fn(q, kv, k_pe, dtype, selection, **by_length)
            with scope("dsa.count"):
                pairs = pairs + jnp.sum(
                    jnp.where(real[:, :, None], selection, 0), (1, 2), dtype=jnp.int32
                )
        else:
            with scope("mla.core"):
                o = attention_fn(q, kv, k_pe, dtype, **by_length)
            with scope("dsa.count"):
                pairs = pairs + causal_pairs(real)
        with scope("mla.out"):
            x = x + _dense(o, p["attn"]["o"])
        with scope("mlp"):
            u = _rms(x, p["norm_ff"], eps)
            if i < config.first_k_dense:
                x = x + v2._swiglu(p["mlp"], u.astype(dtype))
                continue
        routed, count, fits = v2._routed(
            config, p["moe"], u, real, experts_fn, combine_fn=combine_fn
        )
        with scope("mlp"):
            x = x + v2._swiglu(p["moe"]["shared"], u.astype(dtype))
        with scope("moe.routed"):
            x = x + routed
        slots_held, sized = slots_held + count, sized + fits
    with scope("pool"):
        out = v2._mean_real_state(_rms(x, params["final_norm"], eps), real)
    return out, slots_held, sized, pairs


#: ``dsa.pairs_selected`` rides back in two float32 columns, the count's
#: multiple of this and the rest: each is exact in float32, and so are
#: their sums over a partition's rows (a row's count reaches 1.7e8)
_PAIRS_SPLIT = 4096


def deepseek_v32_model_function(
    size: str = "deepseek-v3.2-exp-tiny",
    dtype=jnp.float32,
    seed: int = 0,
    weights_file: Optional[str] = None,
    attention_fn=None,
    experts_fn=None,
    indexer_fn=None,
    combine_fn=None,
    name: Optional[str] = None,
):
    """The ``embed`` ModelFunction over ids batches (or ``(ids, mask)``
    tuples), as ``deepseek_v2_model_function`` builds it. ``indexer_fn``
    defaults to the build-time choice of ``make_indexer_fn(heads, top_k)``:
    the Pallas kernels on TPU, reported as ``mf.indexer``.

    The program's result is [B, hidden + 5]: the embedding, DeepSeek-V2's
    three row counters and, in two columns, ``dsa.pairs_selected``: the
    (query, key) pairs the layers' attention read for the row's real
    queries, counted on the device from the selection itself. On the
    host, ``mf.batch_counters(ids, real)`` counts for every dispatched
    batch ``dsa.index_tokens`` (rows x bucket edge x layers, where the
    bucket runs the indexer), ``dsa.pairs_causal`` (what dense causal
    attention would have read, from the real lengths) and
    ``mla.pairs_computed``, ``mla.query_blocks`` and
    ``mla.query_blocks_run`` (what the attention it was built with runs,
    at each row's length where it takes lengths:
    ``deepseek_v2.attention_batch_counters``), and where the bucket runs
    the indexer ``dsa.query_blocks`` and ``dsa.query_blocks_run`` (the
    index-scores kernel's query blocks, every one and those holding a
    real token, where the indexer says how many a row has:
    ``deepseek_v2.query_block_counters``)."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.ops.dsa_indexer import make_indexer_fn
    from sparkdl_tpu.ops.flash_attention import make_latent_attention_fn
    from sparkdl_tpu.ops.grouped_matmul import make_grouped_matmul_fn
    from sparkdl_tpu.ops.moe_combine import make_moe_combine_fn

    if size not in _SIZES:
        raise ValueError(
            f"Unknown DeepSeek-V3.2 size {size!r}; supported: {sorted(_SIZES)}"
        )
    config = _SIZES[size]()
    if attention_fn is None:
        attention_fn = make_latent_attention_fn(
            config.num_heads, config.softmax_scale, block=1024
        )
    if experts_fn is None:
        experts_fn = make_grouped_matmul_fn()
    if indexer_fn is None:
        indexer_fn = make_indexer_fn(config.index_n_heads, config.index_topk)
    if combine_fn is None:
        combine_fn = make_moe_combine_fn()
    if weights_file:
        params = load_flat(param_shapes(config), weights_file, dtype, v2._leaf_dtype)
    else:
        params = init_params(config, seed, dtype)

    def fn(p, x):
        ids = x[0] if isinstance(x, (tuple, list)) else x
        out, slots_held, sized, pairs = forward(
            config, p, ids, dtype=dtype, attention_fn=attention_fn,
            experts_fn=experts_fn, indexer_fn=indexer_fn, combine_fn=combine_fn,
        )
        with scope("pool"):
            sized = jnp.broadcast_to(sized, slots_held.shape)
            counts = jnp.stack(
                [
                    slots_held, sized, config.expert_layers - sized,
                    pairs // _PAIRS_SPLIT * _PAIRS_SPLIT, pairs % _PAIRS_SPLIT,
                ],
                1,
            )
            return jnp.concatenate([out, counts.astype(jnp.float32)], 1)

    layers, top_k = config.num_layers, config.index_topk

    def batch_counters(ids, real) -> dict:
        n = real.sum(1).astype(np.int64)
        selects = ids.shape[1] > top_k
        return {
            "dsa.index_tokens": int(ids.size) * layers * selects,
            "dsa.pairs_causal": int((n * (n + 1) // 2).sum()) * layers,
            **v2.attention_batch_counters(attention_fn, layers, ids, real),
            **(v2.query_block_counters(indexer_fn, layers, ids, real, "dsa") if selects else {}),
        }

    mf = ModelFunction(
        fn, params, input_dtype=jnp.int32, name=name or f"{size}[embed]"
    )
    mf.weights_as_arguments = True
    mf.vocab_size = config.vocab_size
    mf.attention = getattr(attention_fn, "kind", "custom")
    mf.experts = getattr(experts_fn, "kind", "custom")
    mf.combine = getattr(combine_fn, "kind", "custom")
    mf.indexer = getattr(indexer_fn, "kind", "custom")
    mf.row_counters = (
        "moe.slots_held", "moe.buffer_sized", "moe.buffer_full",
        "dsa.pairs_selected", "dsa.pairs_selected",
    )
    mf.dispatched_token_counters = {"mla.attention_tokens": config.num_layers}
    mf.real_token_counters = {
        "moe.slots_routed": config.num_experts_per_tok * config.expert_layers
    }
    mf.batch_counters = batch_counters
    return mf
