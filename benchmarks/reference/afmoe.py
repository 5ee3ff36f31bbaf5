"""Plain reference for AFMoE (`model_type: afmoe`; Arcee's Trinity family,
the release's `config.json` and `modeling_afmoe.py` as recalled) as the text
embedder runs one chip's share of it: a hashing tokenizer, a word
embedding scaled by sqrt(hidden) (`mup_enabled`), layers of gated
grouped-query attention with QK-norm, a sliding window or full causal
attention by `layer_types`, sandwich norms, a feed-forward that is a dense
SwiGLU MLP in the first `num_dense_layers` layers and one shared plus
routed experts after them, and the final RMSNorm of every real token's
state, averaged over the row. No output head.

    x = E[ids] * sqrt(hidden)
    h = rms(x; w_in)
    q = rms(h W_q; w_q) per head, k = rms(h W_k; w_k) per head, v = h W_v, g = h W_g
    sliding layers: q, k = rope(q, t), rope(k, t), theta `rope_theta`,
      lane i paired with lane i + head_dim / 2, positions from 0 in each row;
      full layers: no positional encoding
    o = softmax(q k^T / sqrt(head_dim) + M) v, `num_attention_heads /
      num_key_value_heads` query heads a key/value head;
      M: full j <= i, sliding 0 <= i - j < sliding_window
    x = x + rms((o * sigmoid(g)) W_o; w_post_attn)
    u = rms(x; w_pre_mlp)
    x = x + rms(F(u); w_post_mlp)
    F(u) = SwiGLU(u) in a dense layer, else SwiGLU_shared(u) + sum_k w_k SwiGLU_{e_k}(u):
      s = sigmoid(W_r u) over all `num_experts` (float32); e = the top-k of
      s + b (b the expert bias); w_k = route_scale * s[e_k] / (sum_k' s[e_k'] + 1e-20)

Attention dense over a block of queries at a time against every key (the
window is a mask), grouped heads as a grouped product and not repeated;
the routed experts through each token's own k and not every expert
densely: expert by expert, the real tokens that chose it are gathered,
put through it, weighted, and added back (`_routed`). Pad tokens are not
routed (the stack is causal and only real tokens are pooled). A block of
rows is padded up to a power of two, one layer's weights are on the
device at a time, the rows in groups that fit. Every expert is held: a
chip's share is the whole layer. Precisions:

- `highest`: every product in float32;
- `reference`: what the configuration states: both operands of every
  matrix product rounded to bfloat16 and accumulated in float32; the
  router's product in float32 (`highest`); norms, rotary, softmax, the
  gate's sigmoid, the routing weights and the combine in float32;
- `float8`: the control: both operands of every matrix product rounded to
  float8 (e4m3); the router as stated.

Departures from the release, each as recalled and listed under `assumed`
in the configuration file: the output gate's weight has no key in the
config; the full layers carry no rotary; the embedding's sqrt(hidden).
Weights are random, bfloat16-exact, `uint16` bit patterns made leaf by
leaf (`reference/deepseek_v2.py:_Leaf`), the embedding at variance
1 / hidden and the expert bias uniform in +-0.05.
"""

from __future__ import annotations

import functools
import math
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import deepseek_v2 as v2
from benchmarks.reference.jamba import _product, _rms, _scalars, _silu, from_bits, to_bits, tokenize

CONTROL_PRECISION = {"bfloat16": "float8"}

#: queries a block of the dense attention
QUERY_BLOCK = 256
#: tokens whose float32 states stay on the device between layers
TOKENS_A_GROUP = 8 * 16384
#: an expert's gathered tokens take a multiple of this many rows, so that
#: few shapes are compiled
EXPERT_ROWS = 1024
EXPERT_BIAS = 0.05


def is_dense(config, i: int) -> bool:
    return i < config["num_dense_layers"]


def is_sliding(config, i: int) -> bool:
    return config["layer_types"][i] == "sliding_attention"


def layer_shapes(config, i: int) -> dict:
    """{name under `layers/<i>/`: shape}. Matrices are [in, out]; a
    layer's experts are stacked, [expert, in, out]."""
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
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
    if is_dense(config, i):
        f = config["intermediate_size"]
        shapes.update({"mlp/gate": (h, f), "mlp/up": (h, f), "mlp/down": (f, h)})
        return shapes
    f, n = config["moe_intermediate_size"], config["num_experts"]
    shared = config["num_shared_experts"] * f
    shapes.update({
        "moe/router": (h, n),
        "moe/router_bias": (n,),
        "moe/shared/gate": (h, shared),
        "moe/shared/up": (h, shared),
        "moe/shared/down": (shared, h),
        "moe/experts/gate": (n, h, f),
        "moe/experts/up": (n, h, f),
        "moe/experts/down": (n, f, h),
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


class _Leaf(v2._Leaf):
    """`reference/deepseek_v2.py`'s leaves, with the embedding at variance
    1 / hidden (sqrt(hidden) then gives the stream unit variance) and the
    expert bias."""

    def _make(self) -> np.ndarray:
        kind = self.name.rsplit("/", 1)[-1]
        if kind == "embed":
            return to_bits(self._uniform(math.sqrt(3.0 / self.shape[1])))
        if kind == "router_bias":
            return to_bits(self._uniform(EXPERT_BIAS))
        return super()._make()


def make_weights(config, seed) -> dict:
    return {
        name: _Leaf(name, shape, seed)
        for name, shape in weight_shapes(config).items()
    }


# -- the forward pass ----------------------------------------------------------


def _rope(config, x, length):
    """x [B, L, heads, d]: pair i of a head's halves turned by
    t * rope_theta^(-2i / d)."""
    d = config["head_dim"]
    inv_freq = 1.0 / config["rope_theta"] ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq  # [L, d / 2]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(config, w, h, sliding, precision):
    """h [B, L, hidden] -> (o * sigmoid(g)) W_o [B, L, hidden]."""
    product = _product(precision)
    eps = config["rms_norm_eps"]
    rows, length, _ = h.shape
    heads, groups, d = (
        config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    )
    q = _rms(product("bli,io->blo", h, w["attn/q"]).reshape(rows, length, heads, d), w["attn/q_norm"], eps)
    k = _rms(product("bli,io->blo", h, w["attn/k"]).reshape(rows, length, groups, d), w["attn/k_norm"], eps)
    v = product("bli,io->blo", h, w["attn/v"]).reshape(rows, length, groups, d)
    if sliding:
        q, k = _rope(config, q, length), _rope(config, k, length)
    q = q.reshape(rows, length, groups, heads // groups, d)
    block = math.gcd(length, QUERY_BLOCK)
    window = config["sliding_window"]

    def some_queries(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, 1)  # [B, Q, G, g, d]
        s = product("bqhgd,bkhd->bhgqk", qb, k) / math.sqrt(d)
        back = first + jnp.arange(block)[:, None] - jnp.arange(length)[None, :]
        seen = (back >= 0) & (back < window) if sliding else back >= 0
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return product("bhgqk,bkhd->bqhgd", p, v)

    o = jax.lax.map(some_queries, jnp.arange(0, length, block))  # [n, B, Q, G, g, d]
    o = jnp.moveaxis(o, 0, 1).reshape(rows, length, heads * d)
    o = o * jax.nn.sigmoid(product("bli,io->blo", h, w["attn/gate"]))
    return product("bli,io->blo", o, w["attn/o"])


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _attend(config_items, sliding, w, x, precision):
    """(x after the attention block, the feed-forward's input u)."""
    config = dict(config_items)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = config["rms_norm_eps"]
    attended = _attention(config, w, _rms(x, w["norm_in"], eps), sliding, precision)
    x = x + _rms(attended, w["norm_post_attn"], eps)
    return x, _rms(x, w["norm_pre_mlp"], eps)


def _swiglu(precision, w, u):
    product = _product(precision)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    act = _silu(product("...i,io->...o", u, w["gate"])) * product("...i,io->...o", u, w["up"])
    return product("...i,io->...o", act, w["down"])


_feed_forward = jax.jit(_swiglu, static_argnums=(0,))


@functools.partial(jax.jit, static_argnums=(0,))
def _post_norm(eps, x, f, w):
    return x + _rms(f, w.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def route(u, router, bias, top_k, scale, norm):
    """(experts [T, k] int32, weights [T, k] float32) of tokens u
    [T, hidden]: sigmoid scores over every expert, chosen on score +
    bias, weighted by the score renormalised over the k (where `norm`)
    and scaled."""
    logits = jnp.einsum(
        "ti,io->to", u.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weights = jnp.take_along_axis(scores, experts, -1)
    if norm:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts, weights * scale


def _capacity(chosen, real, experts: int) -> int:
    """Rows that the fullest expert's gathered tokens take, rounded up to
    `EXPERT_ROWS` so that few shapes are compiled."""
    load = np.bincount(np.asarray(chosen)[np.asarray(real)].ravel(), minlength=experts)
    return max(EXPERT_ROWS, -(-int(load.max()) // EXPERT_ROWS) * EXPERT_ROWS)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _routed(precision, capacity, experts_w, u, real, chosen, weights):
    """The routed sum [T, hidden] of the real tokens of u [T, hidden],
    each through its own k experts (`chosen`, `weights` [T, k]): expert by
    expert, the tokens that chose it are gathered (at most `capacity`;
    the rest of the gather is an empty row past the last token), put
    through it, weighted and added back."""
    tokens, hidden = u.shape
    rows = jnp.concatenate([u, jnp.zeros((1, hidden), u.dtype)])

    def one(out, expert):
        e, w = expert
        hit = (chosen == e) & real[:, None]
        weight = jnp.append(jnp.where(hit, weights, 0.0).sum(-1), 0.0)
        (at,) = jnp.nonzero(hit.any(-1), size=capacity, fill_value=tokens)
        y = _swiglu(precision, w, rows[at]) * weight[at][:, None]
        return out.at[at].add(y), None

    experts = jnp.arange(experts_w["gate"].shape[0])
    out, _ = jax.lax.scan(one, jnp.zeros_like(rows), (experts, experts_w))
    return out[:tokens]


def _routed_sum(config, precision, experts_w, u, real, router, bias):
    """[B, L, hidden]: `route` and then `_routed` over a block's tokens."""
    shape = u.shape
    flat, real = u.reshape(-1, shape[-1]), jnp.asarray(real.reshape(-1))
    chosen, weights = route(
        flat, router, bias, config["num_experts_per_tok"], config["route_scale"],
        config["route_norm"],
    )
    capacity = _capacity(chosen, real, config["num_experts"])
    return _routed(precision, capacity, experts_w, flat, real, chosen, weights).reshape(shape)


def _padded(length: int, max_len: int) -> int:
    """A block's length: its longest row's, up to a power of two (64 at
    least, `max_len` at most), so that few shapes are compiled; the stack
    is causal and what lies past a row's end is never read."""
    return min(max_len, max(64, 1 << (length - 1).bit_length()))


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
    the device goes through all the layers before the next group starts,
    one layer's weights on the device at a time."""
    if precision == "reference":
        precision = {"bfloat16": "reference"}[config["compute_dtype"]]
    max_len = config["max_length"]
    rows = [tokenize(t, config["vocab_size"], max_len) for t in inputs]
    padded = [_padded(len(r), max_len) for r in rows]
    order = sorted(range(len(rows)), key=lambda i: -len(rows[i]))
    out = np.zeros((len(rows), config["hidden_size"]), np.float32)
    for group in _groups(order, padded, TOKENS_A_GROUP):
        _group_outputs(config, weights, rows, group, precision, block_rows, out)
    return out


def _group_outputs(config, weights, rows, order, precision, block_rows, out):
    embed = from_bits(weights["embed"])
    max_len, eps = config["max_length"], config["rms_norm_eps"]
    scale = math.sqrt(config["hidden_size"]) if config["mup_enabled"] else 1.0
    blocks = []
    for i in range(0, len(order), block_rows):
        idx = order[i : i + block_rows]
        length = _padded(len(rows[idx[0]]), max_len)
        ids = np.zeros((len(idx), length), np.int32)
        for j, r in enumerate(idx):
            ids[j, : len(rows[r])] = rows[r]
        x = jnp.asarray(embed[ids], jnp.float32) * scale
        blocks.append((idx, ids != 0, x))
    del embed
    items = _scalars(config)

    def send(pool, i, names, strip=""):
        made = pool.map(lambda n: from_bits(weights[f"layers/{i}/{n}"]), names)
        return {n[len(strip):]: jnp.asarray(leaf) for n, leaf in zip(names, made)}

    with ThreadPoolExecutor(8) as pool:  # a part's leaves are made side by side
        for i in range(config["num_hidden_layers"]):
            shapes = layer_shapes(config, i)
            part = lambda prefix: [n for n in shapes if n.startswith(prefix)]  # noqa: E731
            w = send(pool, i, part("attn/") + part("norm_"))
            post = w["norm_post_mlp"]
            attended = [
                (idx, real, *_attend(items, is_sliding(config, i), w, x, precision))
                for idx, real, x in blocks
            ]
            if is_dense(config, i):
                w = send(pool, i, part("mlp/"), "mlp/")
                blocks = [
                    (idx, real, _post_norm(eps, x, _feed_forward(precision, w, u), post))
                    for idx, real, x, u in attended
                ]
                continue
            shared = send(pool, i, part("moe/shared/"), "moe/shared/")
            experts = send(pool, i, part("moe/experts/"), "moe/experts/")
            router, bias = (
                jnp.asarray(from_bits(weights[f"layers/{i}/moe/{n}"]))
                for n in ("router", "router_bias")
            )
            blocks = [
                (idx, real, _post_norm(
                    eps, x,
                    _feed_forward(precision, shared, u)
                    + _routed_sum(config, precision, experts, u, real, router, bias),
                    post,
                ))
                for idx, real, x, u in attended
            ]
            del experts
    final = jnp.asarray(from_bits(weights["final_norm"]), jnp.float32)
    for idx, _, x in blocks:
        normed = np.asarray(_rms(x, final, eps), np.float64)
        for j, r in enumerate(idx):
            out[r] = normed[j, : len(rows[r])].mean(0)
