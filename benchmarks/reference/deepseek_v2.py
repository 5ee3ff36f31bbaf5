"""Plain reference for DeepSeek-V2 (`model_type: deepseek_v2`; DeepSeek-AI
2024, arXiv:2405.04434, section 2.1 MLA and 2.2 DeepSeekMoE, computed as the
source's `modeling_deepseek.py` computes it) as the text embedder runs one
chip's share of it: a hashing tokenizer, a word embedding, pre-norm
residual layers of latent attention and a feed-forward that is a dense
SwiGLU MLP in the first `first_k_dense_replace` layers and shared plus
routed experts after them, and the final RMSNorm of every real token's
state, averaged over the row. The untied output head is not computed.

    h = h + MLA(rms(h; w_in));  h = h + FFN_i(rms(h; w_ff))
    MLA(u), heads of [nope | rope] queries and keys, positions t = 0..L-1:
      c_q = rms(W_qa u; w_qn);        q = W_qb c_q -> per head [q_nope | q_pe]
      [c_kv | k_pe] = W_kva u;        kv = W_kvb rms(c_kv; w_kvn) -> per head [k_nope | v]
      q_pe, k_pe = rope(q_pe, t), rope(k_pe, t)      k_pe one vector a token, for all heads
      score(t,s) = (q_nope_t.k_nope_s + q_pe_t.k_pe_s) * (nope + rope)^-0.5 * m^2
      out = W_o concat_heads(softmax_{s<=t}(score) v)
    rope: YaRN frequencies fixed from the configuration; the source
      de-interleaves (x0,x1,x2,..) -> (x0,x2,..|x1,x3,..) and rotates halves
    Routed(u): s = softmax(W_g u) over all n_routed_experts (float32);
      the topk_group groups of largest best score are kept, top-k of s
      over them -> (e_k, s_k); weight w_k = routed_scaling_factor * s_k,
      not renormalised; the sum runs over the k whose expert is held here
    FFN_i>0(u) = SwiGLU_shared(u) + Routed(u)

The chip's share (`experts_held = [first, end)`): the router scores all
`published.n_routed_experts` experts, the experts held are computed and
nothing stands in for the others; the partial sum goes on to the next
layer. The vocabulary is the slice the configuration's `vocab_size` gives.

float32 throughout, dense attention a few heads at a time, the experts a
masked loop (every token through every held expert, weighted by zero
where it was not chosen; no sort, no kernel), one layer's weights on the
device at a time and of an expert layer one expert's. Precisions:

- `highest`: every product in float32;
- `reference`: what the configuration states: both operands of every
  matrix product rounded to bfloat16 and accumulated in float32; the
  router's product in float32 (`highest`), norms, softmax, rotary angles,
  routing weights and the combine in float32;
- `float8`: the control, the nearest precision below: both operands of
  every matrix product rounded to float8 (e4m3); the router as stated;
- `router_bfloat16`: the second control: `reference`, with both operands
  of the router's product rounded to bfloat16.

Departures from the source, each where it happens: pad tokens are routed
here (nothing reads them: the stack is causal and only real tokens are
pooled), the program leaves them out; positions count from 0 in
each row; no output head.

Weights are random, bfloat16-exact and travel as `uint16` bit patterns,
each leaf made when first read (`reference/jamba.py:Leaf`, whose
tokenizer, products and norms this module shares).
"""

from __future__ import annotations

import functools
import math
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.jamba import (
    Leaf,
    _product,
    _rms,
    _scalars,
    _silu,
    from_bits,
    to_bits,
    tokenize,
)

CONTROL_PRECISION = {"bfloat16": "float8"}
#: the second control, read beside the first where a cell's limits are set
SECOND_CONTROL = "router_bfloat16"

#: heads whose [L, L] scores are alive at once
HEAD_CHUNK = 16


def experts_held(config) -> tuple:
    first, end = config.get("experts_held", (0, config["n_routed_experts"]))
    return int(first), int(end)


def experts_routed(config) -> int:
    """The router's width: every expert of the model, held here or not."""
    return config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"]
    )


def is_dense(config, i: int) -> bool:
    return i < config["first_k_dense_replace"]


def layer_shapes(config, i: int) -> dict:
    """{name under `layers/<i>/`: shape}. Matrices are [in, out]; a
    layer's held experts are stacked, [expert, in, out]."""
    h = config["hidden_size"]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
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
    if is_dense(config, i):
        f = config["intermediate_size"]
        shapes.update({"mlp/gate": (h, f), "mlp/up": (h, f), "mlp/down": (f, h)})
        return shapes
    f = config["moe_intermediate_size"]
    shared = config["n_shared_experts"] * f
    first, end = experts_held(config)
    shapes.update({
        "moe/router": (h, experts_routed(config)),
        "moe/shared/gate": (h, shared),
        "moe/shared/up": (h, shared),
        "moe/shared/down": (shared, h),
        "moe/experts/gate": (end - first, h, f),
        "moe/experts/up": (end - first, h, f),
        "moe/experts/down": (end - first, f, h),
    })
    return shapes


def weight_shapes(config) -> dict:
    """{flat name: shape} of every leaf of the weights file."""
    h = config["hidden_size"]
    shapes = {"embed": (config["vocab_size"], h), "final_norm": (h,)}
    for i in range(config["num_hidden_layers"]):
        for name, shape in layer_shapes(config, i).items():
            shapes[f"layers/{i}/{name}"] = shape
    return shapes


#: the router's logits have this standard deviation (fan-in scaling gives
#: 1): scores over 160 experts are then spread over two orders, not flat,
#: and fewer tokens sit on a tie between the sixth and the seventh
ROUTER_SPREAD = 2.0


class _Leaf(Leaf):
    """`reference/jamba.py:Leaf` with this family's kinds: stacked experts
    scale by their own fan-in (the middle axis), the router is wider."""

    def _make(self) -> np.ndarray:
        kind = self.name.rsplit("/", 1)[-1]
        if kind == "router":
            return to_bits(
                self._uniform(ROUTER_SPREAD * math.sqrt(3.0 / self.shape[0]))
            )
        if len(self.shape) == 3:
            return to_bits(self._uniform(math.sqrt(3.0 / self.shape[1])))
        return super()._make()


def make_weights(config, seed) -> dict:
    return {
        name: _Leaf(name, shape, seed)
        for name, shape in weight_shapes(config).items()
    }


# -- rotary positions ---------------------------------------------------------


def _yarn_correction_dim(rotations, dim, base, original):
    return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))


def yarn_range(config) -> tuple:
    """(low, high): the pairs between which YaRN blends the two frequencies."""
    s = config["rope_scaling"]
    dim, base = config["qk_rope_head_dim"], config["rope_theta"]
    original = s["original_max_position_embeddings"]
    low = math.floor(_yarn_correction_dim(s["beta_fast"], dim, base, original))
    high = math.ceil(_yarn_correction_dim(s["beta_slow"], dim, base, original))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(config) -> np.ndarray:
    """[rope / 2] float32: the source's `DeepseekV2YarnRotaryEmbedding`."""
    s = config["rope_scaling"]
    dim, base = config["qk_rope_head_dim"], config["rope_theta"]
    f = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_range(config)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (f * (1 - ramp) + f / s["factor"] * ramp).astype(np.float32)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(config) -> float:
    """(nope + rope)^-0.5 * m^2, m = mscale(factor, mscale_all_dim)."""
    s = config["rope_scaling"]
    m = yarn_mscale(s["factor"], s["mscale_all_dim"])
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return width**-0.5 * m * m


def _rope(config, x, length):
    """x [..., L, rope] -> the same, rotated as the source rotates it:
    de-interleave, then rotate halves by t * inv_freq. cos and sin carry
    mscale(factor, mscale) / mscale(factor, mscale_all_dim), 1 here."""
    s = config["rope_scaling"]
    attn_factor = yarn_mscale(s["factor"], s["mscale"]) / yarn_mscale(
        s["factor"], s["mscale_all_dim"]
    )
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * yarn_inv_freq(config)
    angle = jnp.concatenate([angle, angle], -1)  # [L, rope]
    cos, sin = jnp.cos(angle) * attn_factor, jnp.sin(angle) * attn_factor
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


# -- the forward pass ---------------------------------------------------------


def _mla(config, w, u, precision):
    product = _product(precision)
    eps = config["rms_norm_eps"]
    rows, length, _ = u.shape
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rkv = config["v_head_dim"], config["kv_lora_rank"]

    c_q = _rms(product("bli,io->blo", u, w["attn/q_a"]), w["attn/q_norm"], eps)
    q = product("bli,io->blo", c_q, w["attn/q_b"]).reshape(rows, length, heads, nope + rope)
    q = jnp.swapaxes(q, 1, 2)  # [B, H, L, nope + rope]
    q_nope, q_pe = q[..., :nope], _rope(config, q[..., nope:], length)
    kv_a = product("bli,io->blo", u, w["attn/kv_a"])
    k_pe = _rope(config, kv_a[..., rkv:], length)  # [B, L, rope]
    c_kv = _rms(kv_a[..., :rkv], w["attn/kv_norm"], eps)
    kv = product("bli,io->blo", c_kv, w["attn/kv_b"]).reshape(rows, length, heads, nope + dv)
    kv = jnp.swapaxes(kv, 1, 2)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    causal = jnp.tril(jnp.ones((length, length), bool))
    scale = softmax_scale(config)

    def some_heads(part):
        qn, qp, kn, vv = part  # [B, chunk, L, .]
        s = product("bhqd,bhkd->bhqk", qn, kn) + product("bhqd,bkd->bhqk", qp, k_pe)
        p = jax.nn.softmax(jnp.where(causal, s * scale, -jnp.inf), -1)
        return product("bhqk,bhkd->bhqd", p, vv)

    chunk = math.gcd(heads, HEAD_CHUNK)

    def chunks(t):  # [B, H, L, d] -> [H / chunk, B, chunk, L, d]
        return jnp.moveaxis(t.reshape(rows, heads // chunk, chunk, length, -1), 1, 0)

    o = jax.lax.map(some_heads, tuple(map(chunks, (q_nope, q_pe, k_nope, v))))
    o = jnp.moveaxis(o, 0, 1).reshape(rows, heads, length, dv)
    o = jnp.swapaxes(o, 1, 2).reshape(rows, length, heads * dv)
    return product("bli,io->blo", o, w["attn/o"])


def route(config, u, router, precision="reference"):
    """(experts [.., k] int32, weights [.., k] float32) of every token of
    u [.., hidden], over all the model's experts: the source's
    `MoEGate.forward` for `group_limited_greedy`."""
    n, groups = experts_routed(config), config["n_group"]
    top_k = config["num_experts_per_tok"]
    u, router = u.astype(jnp.float32), router.astype(jnp.float32)
    if precision == "router_bfloat16":
        # not `astype` there and back: the TPU's compiler drops that pair
        u = jax.lax.reduce_precision(u, exponent_bits=8, mantissa_bits=7)
        router = jax.lax.reduce_precision(router, exponent_bits=8, mantissa_bits=7)
    logits = jnp.einsum("...i,io->...o", u, router, precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, -1)
    if config["topk_method"] == "group_limited_greedy":
        best = scores.reshape(*scores.shape[:-1], groups, n // groups).max(-1)
        _, kept = jax.lax.top_k(best, config["topk_group"])
        keep = jnp.any(kept[..., None] == jnp.arange(groups), -2)  # [.., groups]
        scores = jnp.where(jnp.repeat(keep, n // groups, -1), scores, 0.0)
    weights, experts = jax.lax.top_k(scores, top_k)
    if top_k > 1 and config["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    else:
        weights = weights * config["routed_scaling_factor"]
    return experts, weights


@functools.partial(jax.jit, static_argnums=(0, 3))
def _attend(config_items, w, x, precision):
    config = dict(config_items)
    config["rope_scaling"] = dict(config["rope_scaling"])
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = x + _mla(config, w, _rms(x, w["norm_in"], config["rms_norm_eps"]), precision)
    return x, _rms(x, w["norm_ff"], config["rms_norm_eps"])


def _swiglu(precision, w, u):
    product = _product(precision)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    act = _silu(product("bli,io->blo", u, w["gate"])) * product("bli,io->blo", u, w["up"])
    return product("bli,io->blo", act, w["down"])


_feed_forward = jax.jit(_swiglu, static_argnums=(0,))


@functools.partial(jax.jit, static_argnums=(0,))
def _expert(precision, expert, w, u, experts, weights):
    """What held expert number `expert` (as the router counts) adds:
    every token through it, weighted by zero where it was not chosen."""
    weight = jnp.sum(jnp.where(experts == expert, weights, 0.0), -1)
    return weight[..., None] * _swiglu(precision, w, u)


def _layer_names(config, i: int) -> dict:
    """{part: [leaf names]}: what `outputs` sends to the device together."""
    parts: dict = {}
    for name in layer_shapes(config, i):
        head = name.split("/")[0]
        part = "attend" if head in ("attn", "norm_in", "norm_ff") else name.rsplit("/", 1)[0]
        parts.setdefault(part, []).append(name)
    return parts


def outputs(config, weights, inputs, precision="reference", block_rows=4):
    """Embeddings of `inputs` (text strings), float32 [N, hidden]. Rows run
    in blocks, longest first, each block padded on the right to its own
    longest row rounded up to 64. The layers are the outer loop and, in an
    expert layer, the held experts the next: one part's weights are made,
    sent and dropped before the next."""
    if precision == "reference":
        precision = {"bfloat16": "reference"}[config["compute_dtype"]]
    max_len, hidden = config["max_length"], config["hidden_size"]
    rows = [tokenize(t, config["vocab_size"], max_len) for t in inputs]
    order = sorted(range(len(rows)), key=lambda i: -len(rows[i]))
    embed = from_bits(weights["embed"])
    blocks = []
    for i in range(0, len(order), block_rows):
        idx = order[i : i + block_rows]
        length = min(max_len, -(-len(rows[idx[0]]) // 64) * 64)
        ids = np.zeros((len(idx), length), np.int32)
        for j, r in enumerate(idx):
            ids[j, : len(rows[r])] = rows[r]
        blocks.append((idx, jnp.asarray(embed[ids], jnp.float32)))
    del embed
    items = _scalars_with_scaling(config)
    first, end = experts_held(config)
    # the products' precision; the router alone follows `router_bfloat16`
    products = "reference" if precision == "router_bfloat16" else precision

    def send(pool, i, names, strip=""):
        made = pool.map(lambda n: from_bits(weights[f"layers/{i}/{n}"]), names)
        return {n[len(strip):]: jnp.asarray(leaf) for n, leaf in zip(names, made)}

    with ThreadPoolExecutor(8) as pool:  # a part's leaves are made side by side
        for i in range(config["num_hidden_layers"]):
            parts = _layer_names(config, i)
            w = send(pool, i, parts["attend"])
            attended = [(idx, *_attend(items, w, x, products)) for idx, x in blocks]
            if is_dense(config, i):
                w = send(pool, i, parts["mlp"], "mlp/")
                blocks = [
                    (idx, x + _feed_forward(products, w, u)) for idx, x, u in attended
                ]
                continue
            w = send(pool, i, parts["moe/shared"], "moe/shared/")
            router = jnp.asarray(from_bits(weights[f"layers/{i}/moe/router"]))
            routed = [route(config, u, router, precision) for _, _, u in attended]
            sums = [x + _feed_forward(products, w, u) for _, x, u in attended]
            stacked = {
                n.rsplit("/", 1)[1]: from_bits(weights[f"layers/{i}/{n}"])
                for n in parts["moe/experts"]
            }
            for e in range(first, end):
                w = {k: jnp.asarray(v[e - first]) for k, v in stacked.items()}
                sums = [
                    s + _expert(products, e, w, u, *chosen)
                    for s, (_, _, u), chosen in zip(sums, attended, routed)
                ]
            blocks = [(idx, s) for (idx, _, _), s in zip(attended, sums)]
    final = jnp.asarray(from_bits(weights["final_norm"]), jnp.float32)
    out = np.zeros((len(rows), hidden), np.float32)
    for idx, x in blocks:
        normed = np.asarray(_rms(x, final, config["rms_norm_eps"]), np.float64)
        for j, r in enumerate(idx):
            out[r] = normed[j, : len(rows[r])].mean(0)
    return out


def _scalars_with_scaling(config) -> tuple:
    """The configuration's scalars and its `rope_scaling` group, hashable:
    what the jitted parts are compiled for."""
    scaling = tuple(sorted(config["rope_scaling"].items()))
    return _scalars(config) + (("rope_scaling", scaling),)
