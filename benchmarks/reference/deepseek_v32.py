"""Plain reference for DeepSeek-V3.2-Exp (`model_type: deepseek_v32`;
DeepSeek-AI 2025, the release's `inference/model.py` as recalled: MLA and
DeepSeekMoE as DeepSeek-V3 has them, arXiv:2412.19437 section 2.1, with
DeepSeek sparse attention) as the text embedder runs one chip's share of
it: a hashing tokenizer, a word embedding, pre-norm residual layers of
sparse latent attention and a feed-forward that is a dense SwiGLU MLP in
the first `first_k_dense_replace` layers and one shared plus routed
experts after them, and the final RMSNorm of every real token's state,
averaged over the row. No output head, no multi-token-prediction block.

    h = h + Attn(rms(h; w_in));  h = h + FFN_i(rms(h; w_ff))
    MLA as `reference/deepseek_v2.py` computes it (c_q, q, c_kv, k_pe, kv,
      YaRN rotary, score scale (nope + rope)^-0.5 * m^2), and
    Indexer (u the normed input, the same c_q):
      qI[t,j] = (W_iq c_q[t])_j, j < index_n_heads; its first `rope` lanes rotary at t
      kI[s]   = LayerNorm(W_ik u[s]; g, b); its first `rope` lanes rotary at s
      w[t,j]  = (W_iw u[t])_j * index_n_heads^-0.5 * index_head_dim^-0.5
      I[t,s]  = sum_j w[t,j] * ReLU(qI[t,j] . kI[s])                   s <= t
      S_t     = the min(index_topk, t + 1) keys s <= t of largest I[t,s],
                of equal scores the lower s first  (`lax.top_k` over the
                masked row; -0.0 made 0.0 first, since the two are equal)
      Attn: softmax over s in S_t only; every head of token t the same S_t
    the indexer's rotary pairs lane i with i + rope / 2 (halves, not
      interleaved as MLA's), with MLA's YaRN frequencies
    Routed(u): s = sigmoid(W_g u) over all n_routed_experts (float32);
      c = s + b_corr chooses: a group's score is the sum of its two
      largest c, the topk_group best groups are kept, top-k of c over
      them -> e_k; weight w_k = routed_scaling_factor * s[e_k] / (sum_k'
      s[e_k'] + 1e-20), the sum over all k, held here or not; the routed
      sum runs over the k whose expert is held here

float32 throughout, attention dense over a block of queries at a time and
a few heads at a time (so that a row of 16,384 tokens fits), the
selection applied as a mask, the experts a masked loop, one layer's
weights on the device at a time, the rows in groups that fit. Precisions:

- `highest`: every product in float32;
- `reference`: what the configuration states: both operands of every
  matrix product (the indexer's too) rounded to bfloat16 and accumulated
  in float32; the router's product in float32 (`highest`); norms, softmax,
  rotary angles, ReLU, the index heads' weights and their sum, routing
  weights and the combine in float32;
- `float8`: the control: both operands of every matrix product rounded to
  float8 (e4m3); the router as stated;
- `indexer_float8`: the second control: `reference`, with the operands of
  the indexer's three products alone rounded to float8.

Weights as `reference/deepseek_v2.py` makes them; the gate's correction
bias is uniform in +-0.05 and LayerNorm's bias in +-0.1.
"""

from __future__ import annotations

import functools
import math
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import deepseek_v2 as v2
from benchmarks.reference.deepseek_v2 import (
    experts_held,
    experts_routed,
    is_dense,
    softmax_scale,
)
from benchmarks.reference.jamba import (
    _product,
    _rms,
    from_bits,
    to_bits,
    tokenize,
)

CONTROL_PRECISION = {"bfloat16": "float8"}
#: the second control, read beside the first where a cell's limits are set
SECOND_CONTROL = "indexer_float8"

#: heads whose [block, L] scores are alive at once, and queries a block
HEAD_CHUNK = 16
QUERY_BLOCK = 256
#: tokens whose float32 states (before and after attention) stay on the
#: device between layers: 8 rows of 16,384 left attention no room
TOKENS_A_GROUP = 4 * 16384
LAYER_NORM_EPS = 1e-6
CORRECTION_BIAS = 0.05


def layer_shapes(config, i: int) -> dict:
    """`reference/deepseek_v2.py`'s, with the indexer's leaves and the
    gate's correction bias."""
    shapes = v2.layer_shapes(config, i)
    h = config["hidden_size"]
    heads, dim = config["index_n_heads"], config["index_head_dim"]
    shapes.update({
        "attn/indexer/q_b": (config["q_lora_rank"], heads * dim),
        "attn/indexer/k": (h, dim),
        "attn/indexer/k_norm": (dim,),
        "attn/indexer/k_norm_bias": (dim,),
        "attn/indexer/weights": (h, heads),
    })
    if not is_dense(config, i):
        shapes["moe/router_bias"] = (experts_routed(config),)
    return shapes


def weight_shapes(config) -> dict:
    """{flat name: shape} of every leaf of the weights file."""
    h = config["hidden_size"]
    shapes = {"embed": (config["vocab_size"], h), "final_norm": (h,)}
    for i in range(config["num_hidden_layers"]):
        for name, shape in layer_shapes(config, i).items():
            shapes[f"layers/{i}/{name}"] = shape
    return shapes


class _Leaf(v2._Leaf):
    def _make(self) -> np.ndarray:
        kind = self.name.rsplit("/", 1)[-1]
        if kind == "router_bias":
            return to_bits(self._uniform(CORRECTION_BIAS))
        if kind == "k_norm_bias":
            return to_bits(self._uniform(0.1))
        return super()._make()


def make_weights(config, seed) -> dict:
    return {
        name: _Leaf(name, shape, seed)
        for name, shape in weight_shapes(config).items()
    }


# -- the indexer ---------------------------------------------------------------


def _rope_halves(config, x, length):
    """x [..., L, (heads,) dim]: the first `rope` lanes rotated by
    t * inv_freq, lane i paired with i + rope / 2; the rest as they are."""
    rope, scaling = config["qk_rope_head_dim"], config["rope_scaling"]
    factor = v2.yarn_mscale(scaling["factor"], scaling["mscale"]) / v2.yarn_mscale(
        scaling["factor"], scaling["mscale_all_dim"]
    )  # 1 here, as in MLA's own rotary
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * v2.yarn_inv_freq(config)
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor  # [L, rope / 2]
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., : rope // 2], x[..., rope // 2 : rope]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rope:]], -1)


def _layer_norm(x, g, b):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LAYER_NORM_EPS) * g + b


def index_operands(config, w, c_q, u, precision):
    """(qI [B, L, heads, dim], kI [B, L, dim], weights [B, L, heads])."""
    product = _product(precision)
    rows, length, _ = u.shape
    heads, dim = config["index_n_heads"], config["index_head_dim"]
    q = product("bli,io->blo", c_q, w["attn/indexer/q_b"])
    q = _rope_halves(config, q.reshape(rows, length, heads, dim), length)
    k = _layer_norm(
        product("bli,io->blo", u, w["attn/indexer/k"]),
        w["attn/indexer/k_norm"], w["attn/indexer/k_norm_bias"],
    )
    k = _rope_halves(config, k, length)
    weights = product("bli,io->blo", u, w["attn/indexer/weights"])
    return q, k, weights * (heads**-0.5 * dim**-0.5)


def index_scores(q, k, weights, precision):
    """I [B, Q, L] float32 of a block of queries q [B, Q, heads, dim]
    against every key; what lies above the diagonal is not masked here."""
    s = _product(precision)("bqhd,bkd->bhqk", q, k)
    return jnp.einsum(
        "bhqk,bqh->bqk", jnp.maximum(s, 0.0), weights,
        precision=jax.lax.Precision.HIGHEST,
    )


def selected(scores, first_query, top_k: int):
    """scores [B, Q, L] of queries first_query.. -> [B, Q, L] bool: each
    query's min(top_k, t + 1) best keys s <= t, of equal scores the lower
    s first."""
    B, Q, L = scores.shape
    t = first_query + jnp.arange(Q)[:, None]
    causal = jnp.arange(L)[None, :] <= t
    scores = jnp.where(scores == 0.0, 0.0, scores)  # -0.0 equals 0.0
    _, best = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(top_k, L))
    chosen = jnp.zeros((B, Q, L), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(Q)[None, :, None], best
    ].set(True)
    return chosen & causal


# -- the forward pass ----------------------------------------------------------


def _attention(config, w, u, precision):
    indexer = "float8" if precision == "indexer_float8" else precision
    product = _product("reference" if precision == "indexer_float8" else precision)
    eps = config["rms_norm_eps"]
    rows, length, _ = u.shape
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rkv = config["v_head_dim"], config["kv_lora_rank"]
    top_k = config["index_topk"]

    c_q = _rms(product("bli,io->blo", u, w["attn/q_a"]), w["attn/q_norm"], eps)
    q = product("bli,io->blo", c_q, w["attn/q_b"]).reshape(rows, length, heads, nope + rope)
    q = jnp.swapaxes(q, 1, 2)  # [B, H, L, nope + rope]
    q_nope, q_pe = q[..., :nope], v2._rope(config, q[..., nope:], length)
    kv_a = product("bli,io->blo", u, w["attn/kv_a"])
    k_pe = v2._rope(config, kv_a[..., rkv:], length)  # [B, L, rope]
    c_kv = _rms(kv_a[..., :rkv], w["attn/kv_norm"], eps)
    kv = product("bli,io->blo", c_kv, w["attn/kv_b"]).reshape(rows, length, heads, nope + dv)
    kv = jnp.swapaxes(kv, 1, 2)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_i, k_i, w_i = index_operands(config, w, c_q, u, indexer)
    scale = softmax_scale(config)
    block = math.gcd(length, QUERY_BLOCK)
    chunk = math.gcd(heads, HEAD_CHUNK)

    def by_chunk(t):  # [B, H, Q, d] -> [H / chunk, B, chunk, Q, d]
        return jnp.moveaxis(t.reshape(rows, heads // chunk, chunk, *t.shape[2:]), 1, 0)

    keys = (by_chunk(k_nope), by_chunk(v))

    def some_queries(first):
        cut = lambda t, axis: jax.lax.dynamic_slice_in_dim(t, first, block, axis)  # noqa: E731
        seen = jnp.arange(length)[None, :] <= first + jnp.arange(block)[:, None]
        if length > top_k:
            scores = index_scores(cut(q_i, 1), k_i, cut(w_i, 1), indexer)
            seen = selected(scores, first, top_k)[:, None]  # [B, 1, Q, L]

        def some_heads(part):
            qn, qp, kn, vv = part  # [B, chunk, Q | L, .]
            s = product("bhqd,bhkd->bhqk", qn, kn) + product("bhqd,bkd->bhqk", qp, k_pe)
            p = jax.nn.softmax(jnp.where(seen, s * scale, -jnp.inf), -1)
            return product("bhqk,bhkd->bhqd", p, vv)

        o = jax.lax.map(
            some_heads, (by_chunk(cut(q_nope, 2)), by_chunk(cut(q_pe, 2)), *keys)
        )
        return jnp.moveaxis(o, 0, 1).reshape(rows, heads, block, dv)

    o = jax.lax.map(some_queries, jnp.arange(0, length, block))  # [n, B, H, Q, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(rows, heads, length, dv)
    o = jnp.swapaxes(o, 1, 2).reshape(rows, length, heads * dv)
    return product("bli,io->blo", o, w["attn/o"])


def route(config, u, router, bias):
    """(experts [.., k] int32, weights [.., k] float32) of every token of
    u [.., hidden], over all the model's experts: the release's `Gate`
    for `noaux_tc` with sigmoid scores."""
    n, groups = experts_routed(config), config["n_group"]
    top_k = config["num_experts_per_tok"]
    logits = jnp.einsum(
        "...i,io->...o", u.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias.astype(jnp.float32)
    if groups > 1:
        by_group = choice.reshape(*choice.shape[:-1], groups, n // groups)
        two_best, _ = jax.lax.top_k(by_group, 2)
        _, kept = jax.lax.top_k(two_best.sum(-1), config["topk_group"])
        keep = jnp.any(kept[..., None] == jnp.arange(groups), -2)  # [.., groups]
        choice = jnp.where(jnp.repeat(keep, n // groups, -1), choice, -jnp.inf)
    _, experts = jax.lax.top_k(choice, top_k)
    weights = jnp.take_along_axis(scores, experts, -1)
    if top_k > 1 and config["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts, weights * config["routed_scaling_factor"]


@functools.partial(jax.jit, static_argnums=(0, 3))
def _attend(config_items, w, x, precision):
    config = dict(config_items)
    config["rope_scaling"] = dict(config["rope_scaling"])
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = config["rms_norm_eps"]
    x = x + _attention(config, w, _rms(x, w["norm_in"], eps), precision)
    return x, _rms(x, w["norm_ff"], eps)


def _groups(order, lengths, most: int):
    """`order` cut into runs whose padded tokens stay within `most`."""
    out, run, held = [], [], 0
    for r in order:
        if run and held + lengths[r] > most:
            out.append(run)
            run, held = [], 0
        run.append(r)
        held += lengths[r]
    return out + [run] if run else out


def outputs(config, weights, inputs, precision="reference", block_rows=1):
    """Embeddings of `inputs` (text strings), float32 [N, hidden]. Rows run
    in blocks of `block_rows`, longest first, each padded on the right to
    its longest row rounded up to 64; a group of blocks whose states fit
    the device goes through all the layers before the next group starts.
    The layers are the outer loop of a group and, in an expert layer, the
    held experts the next: one part's weights are sent and dropped before
    the next."""
    if precision == "reference":
        precision = {"bfloat16": "reference"}[config["compute_dtype"]]
    max_len, hidden = config["max_length"], config["hidden_size"]
    rows = [tokenize(t, config["vocab_size"], max_len) for t in inputs]
    padded = [min(max_len, -(-len(r) // 64) * 64) for r in rows]
    order = sorted(range(len(rows)), key=lambda i: -len(rows[i]))
    out = np.zeros((len(rows), hidden), np.float32)
    for group in _groups(order, padded, TOKENS_A_GROUP):
        _group_outputs(config, weights, rows, group, precision, block_rows, out)
    return out


def _group_outputs(config, weights, rows, order, precision, block_rows, out):
    embed = from_bits(weights["embed"])
    max_len = config["max_length"]
    blocks = []
    for i in range(0, len(order), block_rows):
        idx = order[i : i + block_rows]
        length = min(max_len, -(-len(rows[idx[0]]) // 64) * 64)
        ids = np.zeros((len(idx), length), np.int32)
        for j, r in enumerate(idx):
            ids[j, : len(rows[r])] = rows[r]
        blocks.append((idx, jnp.asarray(embed[ids], jnp.float32)))
    del embed
    items = v2._scalars_with_scaling(config)
    first, end = experts_held(config)
    # the feed-forward's products; the indexer alone follows `indexer_float8`
    products = "reference" if precision == "indexer_float8" else precision

    def send(pool, i, names, strip=""):
        made = pool.map(lambda n: from_bits(weights[f"layers/{i}/{n}"]), names)
        return {n[len(strip):]: jnp.asarray(leaf) for n, leaf in zip(names, made)}

    with ThreadPoolExecutor(8) as pool:  # a part's leaves are made side by side
        for i in range(config["num_hidden_layers"]):
            parts = v2._layer_names(config, i)
            names = [n for n in layer_shapes(config, i) if n.startswith(("attn/", "norm_"))]
            w = send(pool, i, names)
            attended = [(idx, *_attend(items, w, x, precision)) for idx, x in blocks]
            if is_dense(config, i):
                w = send(pool, i, parts["mlp"], "mlp/")
                blocks = [
                    (idx, x + v2._feed_forward(products, w, u)) for idx, x, u in attended
                ]
                continue
            w = send(pool, i, parts["moe/shared"], "moe/shared/")
            router = jnp.asarray(from_bits(weights[f"layers/{i}/moe/router"]))
            bias = jnp.asarray(from_bits(weights[f"layers/{i}/moe/router_bias"]))
            routed = [route(config, u, router, bias) for _, _, u in attended]
            sums = [x + v2._feed_forward(products, w, u) for _, x, u in attended]
            stacked = {
                n.rsplit("/", 1)[1]: from_bits(weights[f"layers/{i}/{n}"])
                for n in parts["moe/experts"]
            }
            for e in range(first, end):
                w = {k: jnp.asarray(v[e - first]) for k, v in stacked.items()}
                sums = [
                    s + v2._expert(products, e, w, u, *chosen)
                    for s, (_, _, u), chosen in zip(sums, attended, routed)
                ]
            blocks = [(idx, s) for (idx, _, _), s in zip(attended, sums)]
    final = jnp.asarray(from_bits(weights["final_norm"]), jnp.float32)
    for idx, x in blocks:
        normed = np.asarray(_rms(x, final, config["rms_norm_eps"]), np.float64)
        for j, r in enumerate(idx):
            out[r] = normed[j, : len(rows[r])].mean(0)
