"""Plain reference for the BERT encoder (Devlin et al. 2018,
arXiv:1810.04805 section 3) as the text embedder runs it: a hashing
tokenizer (lower-cased words hashed by FNV-1a into the vocabulary, [CLS]
and [SEP] around them, truncated to the maximum length), word, position
and segment-0 embeddings, layer norm, post-norm transformer layers with
exact GELU, and the mean of the last hidden states over the real tokens.

float32 throughout, dense softmax attention, one row block at a time.
Three precisions:

- `highest`: every product in float32 (six bf16 passes on the MXU);
- `mxu_default`: what the configuration states, float32 parameters and
  activations with the MXU's default products: both operands of every
  matrix product rounded to bfloat16, accumulated in float32. Everything
  else (sums, norms, softmax, GELU) stays float32;
- `bfloat16`: the control. Weights, activations and products in bfloat16,
  the nearest precision under the one the configuration states.

`reference` means the configuration's own `matmul_precision`. Against
`highest` the stated precision and the control read within a factor of 1.6
of each other (both round every product's operands to bfloat16), so that
comparison cannot tell them apart; against `mxu_default` it can (PERF.md).
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

CONTROL_PRECISION = {"float32": "bfloat16"}

PAD, CLS, SEP, N_RESERVED = 0, 1, 2, 4
_WORD = re.compile(r"[\w']+")


def tokenize(text, vocab_size, max_length):
    ids = [CLS]
    for word in _WORD.findall(text.lower()):
        h = 0xCBF29CE484222325
        for b in word.encode("utf-8"):
            h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
        ids.append(N_RESERVED + h % (vocab_size - N_RESERVED))
    ids.append(SEP)
    return ids[:max_length]


def _dense_names(config):
    h, f = config["hidden_size"], config["intermediate_size"]
    for i in range(config["num_hidden_layers"]):
        for proj in ("query", "key", "value", "output"):
            yield f"layer_{i}/attention/{proj}", (h, h)
        yield f"layer_{i}/intermediate", (h, f)
        yield f"layer_{i}/mlp_output", (f, h)


def _norm_names(config):
    yield "embeddings/layer_norm"
    for i in range(config["num_hidden_layers"]):
        yield f"layer_{i}/attention_norm"
        yield f"layer_{i}/output_norm"


def weight_shapes(config) -> dict:
    """{flat name: shape} of every leaf of the weights file."""
    h = config["hidden_size"]
    shapes = {
        "params/embeddings/word_embeddings/embedding": (config["vocab_size"], h),
        "params/embeddings/position_embeddings/embedding": (
            config["max_position_embeddings"], h),
        "params/embeddings/token_type_embeddings/embedding": (
            config["type_vocab_size"], h),
    }
    for name, shape in _dense_names(config):
        shapes[f"params/{name}/kernel"] = shape
        shapes[f"params/{name}/bias"] = (shape[1],)
    for name in _norm_names(config):
        shapes[f"params/{name}/scale"] = shapes[f"params/{name}/bias"] = (h,)
    return shapes


def make_weights(config, seed):
    """Normal(0, 0.02) embeddings as published; dense kernels at
    1/sqrt(fan_in) so that twelve layers of random weights keep every
    token's state alive; small biases and norm offsets so every leaf tells."""
    rng = np.random.default_rng([int(seed), 0xBE27])
    h = config["hidden_size"]
    w = {}
    for name, rows in (
        ("word_embeddings", config["vocab_size"]),
        ("position_embeddings", config["max_position_embeddings"]),
        ("token_type_embeddings", config["type_vocab_size"]),
    ):
        w[f"params/embeddings/{name}/embedding"] = rng.standard_normal(
            (rows, h), dtype=np.float32
        ) * np.float32(0.02)
    for name, shape in _dense_names(config):
        w[f"params/{name}/kernel"] = rng.standard_normal(
            shape, dtype=np.float32
        ) * np.float32(1.0 / np.sqrt(shape[0]))
        w[f"params/{name}/bias"] = (
            0.02 * rng.standard_normal(shape[1])
        ).astype(np.float32)
    for name in _norm_names(config):
        w[f"params/{name}/scale"] = (
            1.0 + 0.1 * rng.standard_normal(h)
        ).astype(np.float32)
        w[f"params/{name}/bias"] = (0.05 * rng.standard_normal(h)).astype(
            np.float32
        )
    return w


def _forward(config, w, ids, precision):
    if precision == "reference":
        precision = config["matmul_precision"]
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    heads = config["num_attention_heads"]
    eps = config["layer_norm_eps"]
    p = lambda name: w[f"params/{name}"].astype(dt)  # noqa: E731

    def product(spec, a, b):
        if precision == "highest":
            return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
        return jnp.einsum(
            spec,
            a.astype(jnp.bfloat16),
            b.astype(jnp.bfloat16),
            preferred_element_type=dt,
        )

    def dense(x, name):
        return product("...i,io->...o", x, p(f"{name}/kernel")) + p(f"{name}/bias")

    def norm(x, name):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype))
        return y * p(f"{name}/scale") + p(f"{name}/bias")

    B, L = ids.shape
    keep = ids != PAD
    x = (
        p("embeddings/word_embeddings/embedding")[ids]
        + p("embeddings/position_embeddings/embedding")[None, :L]
        + p("embeddings/token_type_embeddings/embedding")[0]
    )
    x = norm(x, "embeddings/layer_norm")
    split = lambda t: t.reshape(B, L, heads, -1).transpose(0, 2, 1, 3)  # noqa: E731
    for i in range(config["num_hidden_layers"]):
        a = f"layer_{i}/attention"
        q, k, v = (split(dense(x, f"{a}/{n}")) for n in ("query", "key", "value"))
        s = product("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
            jnp.asarray(q.shape[-1], dt)
        )
        s = jnp.where(keep[:, None, None, :], s, -jnp.inf)
        o = product("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        o = o.transpose(0, 2, 1, 3).reshape(B, L, -1)
        x = norm(x + dense(o, f"{a}/output"), f"layer_{i}/attention_norm")
        m = jax.nn.gelu(dense(x, f"layer_{i}/intermediate"), approximate=False)
        x = norm(x + dense(m, f"layer_{i}/mlp_output"), f"layer_{i}/output_norm")
    x = x.astype(jnp.float32)
    m = keep[..., None].astype(jnp.float32)
    return jnp.sum(x * m, 1) / jnp.maximum(jnp.sum(m, 1), 1.0)


def outputs(config, weights, inputs, precision="reference", block_rows=32):
    """Embeddings of `inputs` (text strings). Rows run in blocks, longest
    first, each block padded to its own longest row rounded up to 64."""
    max_len = config["max_length"]
    rows = [tokenize(t, config["vocab_size"], max_len) for t in inputs]
    order = sorted(range(len(rows)), key=lambda i: -len(rows[i]))
    w = {k: jnp.asarray(v) for k, v in weights.items()}
    fwd = jax.jit(functools.partial(_forward, config), static_argnums=(2,))
    out = np.zeros((len(rows), config["hidden_size"]), np.float32)
    for i in range(0, len(order), block_rows):
        idx = order[i : i + block_rows]
        length = min(max_len, -(-len(rows[idx[0]]) // 64) * 64)
        ids = np.zeros((block_rows, length), np.int32)
        for j, r in enumerate(idx):
            ids[j, : len(rows[r])] = rows[r]
        ids[len(idx) :] = ids[0]  # one compiled shape per length
        out[idx] = np.asarray(fwd(w, ids, precision))[: len(idx)]
    return out
