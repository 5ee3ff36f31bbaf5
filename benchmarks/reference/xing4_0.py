"""Plain reference for Xing4.0 (`model_type: xing4_0`; XingChen-AGI,
Xing4.0-29B-A4B, the release's `config.json`) as the text embedder runs it:
a hashing tokenizer, a word embedding copied into `hc_mult` residual
streams, layers of latent attention and a feed-forward that is a dense
SwiGLU MLP in the first `first_k_dense_replace` layers and one shared plus
routed experts after them, each sublayer read and written through a
manifold-constrained hyper-connection (Hyper-Connections, Zhu et al. 2024,
arXiv:2409.19606; mHC, DeepSeek-AI, arXiv:2512.24880, as recalled), and the
final RMSNorm of the streams' sum at every real token, averaged over the
row. No output head, no multi-token-prediction block.

Per token X [n, C] float32, n = `hc_mult`; each sublayer F has its own
phi [n C, 2n + n^2], b [2n + n^2] and gains a = (pre, post, res):

    x^       = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)      no weight
    [p|q|r]  = x^ phi                                            highest precision
    H_pre    = sigmoid(a_pre p + b_pre);  H_post = 2 sigmoid(a_post q + b_post)
    M^0      = exp(clamp(a_res mat(r) + b_res, clamp_min, clamp_max)),  mat row-major
    M^t      = M^{t-1} / (its row sums + hc_eps), then / (its column sums + hc_eps),
               t = 1..hc_sinkhorn_iters, written out step by step;  H_res = M^iters
    u        = sum_i H_pre[i] X[i]
    X'[j]    = sum_i H_res[j, i] X[i] + H_post[j] F(u)
    F(u)     = MLA(rms(u; w_in)) (`reference/deepseek_v2.py:_mla`), or the
               feed-forward of rms(u; w_ff): SwiGLU, or SwiGLU_shared + sum_k w_k
               SwiGLU_{e_k} with `reference/deepseek_v32.py:route` (sigmoid scores,
               the choice on score + bias, one group, renormalised, scaled)
    X^0[i]   = E[id] for every i;  h = sum_i X^L[i];  embed = mean_real rms(h; w_final)

Every expert is held: the experts are a masked loop over all of them.
Attention dense a few heads at a time; the rows in groups whose streams fit
the device, one layer's weights there at a time. Precisions:

- `highest`: every product in float32;
- `reference`: what the configuration states: both operands of every
  matrix product of the sublayers rounded to bfloat16 and accumulated in
  float32; the router's product, phi's product and the mixes in float32
  at the highest precision; the streams, norms, softmax, rotary and the
  routing weights in float32;
- `float8`: the control: both operands of every sublayer product rounded
  to float8 (e4m3); the mixes and the router as stated;
- `stream_bfloat16`: the second control: `reference`, with the four
  streams rounded to bfloat16 wherever they are written.

Weights are random, bfloat16-exact, `uint16` bit patterns made leaf by
leaf (`reference/deepseek_v32.py:_Leaf`), with phi at variance 1 / (n C),
the mixes' biases at unit variance and the gains uniform in [0.5, 1.5]:
H_res then lies far from the identity and from the uniform 1 / n, and
H_pre and H_post vary from token to token.
"""

from __future__ import annotations

import functools
import math
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import deepseek_v2 as v2
from benchmarks.reference import deepseek_v32 as v32
from benchmarks.reference.deepseek_v2 import experts_held, is_dense
from benchmarks.reference.jamba import _product, _rms, from_bits, to_bits, tokenize

CONTROL_PRECISION = {"bfloat16": "float8"}
#: the second control, read beside the first where a cell's limits are set
SECOND_CONTROL = "stream_bfloat16"

#: tokens whose float32 streams stay on the device between layers: 57 KB
#: a token at the cell's widths, 1.9 GB
TOKENS_A_GROUP = 16 * 2048
#: a hyper-connection's leaves under `layers/<i>/`, by the sublayer it wraps
SUBLAYERS = ("hc_attn", "hc_ffn")
GAIN = (0.5, 1.5)


def coefficients(config) -> int:
    n = config["hc_mult"]
    return 2 * n + n * n


def layer_shapes(config, i: int) -> dict:
    """`reference/deepseek_v2.py`'s, with the gate's correction bias and
    the two hyper-connections."""
    shapes = v2.layer_shapes(config, i)
    if not is_dense(config, i):
        shapes["moe/router_bias"] = (config["n_routed_experts"],)
    width, m = config["hc_mult"] * config["hidden_size"], coefficients(config)
    for part in SUBLAYERS:
        shapes.update({f"{part}/phi": (width, m), f"{part}/bias": (m,), f"{part}/alpha": (3,)})
    return shapes


def weight_shapes(config) -> dict:
    """{flat name: shape} of every leaf of the weights file."""
    h = config["hidden_size"]
    shapes = {"embed": (config["vocab_size"], h), "final_norm": (h,)}
    for i in range(config["num_hidden_layers"]):
        for name, shape in layer_shapes(config, i).items():
            shapes[f"layers/{i}/{name}"] = shape
    return shapes


class _Leaf(v32._Leaf):
    """DeepSeek-V3.2's leaves (phi, as a matrix, uniform at variance
    1 / fan-in = 1 / (n C)), the mixes' biases at unit variance and the
    gains in [0.5, 1.5]."""

    def _make(self) -> np.ndarray:
        if "/hc_" in self.name:
            kind = self.name.rsplit("/", 1)[-1]
            if kind == "bias":
                return to_bits(self._uniform(math.sqrt(3.0)))
            if kind == "alpha":
                lo, hi = GAIN
                return to_bits((lo + hi) / 2 + self._uniform((hi - lo) / 2))
        return super()._make()


def make_weights(config, seed) -> dict:
    return {
        name: _Leaf(name, shape, seed)
        for name, shape in weight_shapes(config).items()
    }


# -- the hyper-connections -------------------------------------------------------


def hc_pre(config, w, X, iters=None):
    """X [B, L, n, C] float32, w the sublayer's `phi`, `bias`, `alpha` ->
    (u [B, L, C], H_post [B, L, n], H_res [B, L, n, n]), all float32."""
    n = config["hc_mult"]
    iters = config["hc_sinkhorn_iters"] if iters is None else iters
    eps = config["hc_eps"]
    rows, length, _, hidden = X.shape
    flat = X.reshape(rows, length, n * hidden)
    normed = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + config["rms_norm_eps"])
    logits = jnp.einsum("blk,km->blm", normed, w["phi"], precision=jax.lax.Precision.HIGHEST)
    a, b = w["alpha"], w["bias"]
    pre = jax.nn.sigmoid(a[0] * logits[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * logits[..., n : 2 * n] + b[n : 2 * n])
    res = a[2] * logits[..., 2 * n :] + b[2 * n :]
    lo, hi = config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]
    M = jnp.exp(jnp.clip(res, lo, hi)).reshape(rows, length, n, n)
    for _ in range(iters):
        M = M / (M.sum(-1, keepdims=True) + eps)
        M = M / (M.sum(-2, keepdims=True) + eps)
    u = jnp.einsum("bli,blic->blc", pre, X, precision=jax.lax.Precision.HIGHEST)
    return u, post, M


def hc_post(X, f, post, res):
    """X' [B, L, n, C] = H_res X + H_post F(u), float32."""
    mixed = jnp.einsum("blji,blic->bljc", res, X, precision=jax.lax.Precision.HIGHEST)
    return mixed + post[..., None] * f[:, :, None]


def _stream(x, precision):
    """The streams as written: float32, or rounded to bfloat16 for the
    second control (not `astype` there and back: the TPU's compiler drops
    that pair)."""
    if precision == "stream_bfloat16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _products(precision):
    """The sublayers' precision: the second control's are the stated ones."""
    return "reference" if precision == "stream_bfloat16" else precision


# -- the forward pass ------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(0, 3))
def _attend(config_items, w, X, precision):
    """X after the attention sublayer."""
    config = dict(config_items)
    config["rope_scaling"] = dict(config["rope_scaling"])
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    hc = {k.split("/", 1)[1]: v for k, v in w.items() if k.startswith("hc_attn/")}
    u, post, res = hc_pre(config, hc, X)
    f = v2._mla(config, w, _rms(u, w["norm_in"], config["rms_norm_eps"]), _products(precision))
    return _stream(hc_post(X, f, post, res), precision)


@functools.partial(jax.jit, static_argnums=(0,))
def _ffn_input(config_items, w, X):
    """(the feed-forward's input rms(u; w_ff), H_post, H_res)."""
    config = dict(config_items)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    hc = {k.split("/", 1)[1]: v for k, v in w.items() if k.startswith("hc_ffn/")}
    u, post, res = hc_pre(config, hc, X)
    return _rms(u, w["norm_ff"], config["rms_norm_eps"]), post, res


@functools.partial(jax.jit, static_argnums=(4,))
def _written(X, f, post, res, precision):
    return _stream(hc_post(X, f, post, res), precision)


def outputs(config, weights, inputs, precision="reference", block_rows=4):
    """Embeddings of `inputs` (text strings), float32 [N, hidden]. Rows run
    in blocks of `block_rows`, longest first, each padded on the right to
    its longest row rounded up to 64; a group of blocks whose streams fit
    the device goes through all the layers before the next group starts,
    one part's weights on the device at a time."""
    if precision == "reference":
        precision = {"bfloat16": "reference"}[config["compute_dtype"]]
    max_len, hidden = config["max_length"], config["hidden_size"]
    rows = [tokenize(t, config["vocab_size"], max_len) for t in inputs]
    padded = [min(max_len, -(-len(r) // 64) * 64) for r in rows]
    order = sorted(range(len(rows)), key=lambda i: -len(rows[i]))
    out = np.zeros((len(rows), hidden), np.float32)
    for group in v32._groups(order, padded, TOKENS_A_GROUP):
        _group_outputs(config, weights, rows, group, precision, block_rows, out)
    return out


def _group_outputs(config, weights, rows, order, precision, block_rows, out):
    embed = from_bits(weights["embed"])
    max_len, n = config["max_length"], config["hc_mult"]
    blocks = []
    for i in range(0, len(order), block_rows):
        idx = order[i : i + block_rows]
        length = min(max_len, -(-len(rows[idx[0]]) // 64) * 64)
        ids = np.zeros((len(idx), length), np.int32)
        for j, r in enumerate(idx):
            ids[j, : len(rows[r])] = rows[r]
        x = jnp.asarray(embed[ids], jnp.float32)
        blocks.append((idx, _stream(jnp.repeat(x[:, :, None], n, 2), precision)))
    del embed
    items = v2._scalars_with_scaling(config)
    first, end = experts_held(config)
    products = _products(precision)

    def send(pool, i, names, strip=""):
        made = pool.map(lambda n: from_bits(weights[f"layers/{i}/{n}"]), names)
        return {n[len(strip):]: jnp.asarray(leaf) for n, leaf in zip(names, made)}

    with ThreadPoolExecutor(8) as pool:  # a part's leaves are made side by side
        for i in range(config["num_hidden_layers"]):
            shapes = layer_shapes(config, i)
            named = lambda *heads: [n for n in shapes if n.startswith(heads)]  # noqa: E731
            w = send(pool, i, named("attn/", "norm_in", "hc_attn/"))
            blocks = [(idx, _attend(items, w, X, precision)) for idx, X in blocks]
            w = send(pool, i, named("norm_ff", "hc_ffn/"))
            inputs = [_ffn_input(items, w, X) for _, X in blocks]
            if is_dense(config, i):
                w = send(pool, i, named("mlp/"), "mlp/")
                fs = [v2._feed_forward(products, w, u) for u, _, _ in inputs]
            else:
                w = send(pool, i, named("moe/shared/"), "moe/shared/")
                router, bias = (
                    jnp.asarray(from_bits(weights[f"layers/{i}/moe/{n}"]))
                    for n in ("router", "router_bias")
                )
                routed = [v32.route(config, u, router, bias) for u, _, _ in inputs]
                fs = [v2._feed_forward(products, w, u) for u, _, _ in inputs]
                stacked = {
                    n.rsplit("/", 1)[1]: from_bits(weights[f"layers/{i}/{n}"])
                    for n in named("moe/experts/")
                }
                for e in range(first, end):
                    w = {k: jnp.asarray(v[e - first]) for k, v in stacked.items()}
                    fs = [
                        f + v2._expert(products, e, w, u, *chosen)
                        for f, (u, _, _), chosen in zip(fs, inputs, routed)
                    ]
                del stacked
            blocks = [
                (idx, _written(X, f, post, res, precision))
                for (idx, X), f, (_, post, res) in zip(blocks, fs, inputs)
            ]
    final = jnp.asarray(from_bits(weights["final_norm"]), jnp.float32)
    for idx, X in blocks:
        h = X.sum(2)
        normed = np.asarray(_rms(h, final, config["rms_norm_eps"]), np.float64)
        for j, r in enumerate(idx):
            out[r] = normed[j, : len(rows[r])].mean(0)
