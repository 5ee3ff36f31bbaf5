"""Plain reference for the Jamba hybrid (`model_type: jamba`, as
AI21-Jamba2-3B's `config.json` builds it) as the text embedder runs it: a
hashing tokenizer (lower-cased words hashed by FNV-1a into the vocabulary,
[CLS] and [SEP] around them, truncated to the maximum length), a word
embedding, pre-norm residual layers whose mixer is attention where
`i % attn_layer_period == attn_layer_offset` and a Mamba-1 selective
state-space layer elsewhere, each followed by a dense SwiGLU MLP, and the
final RMSNorm of the state at a row's last real token. The tied output
head is not computed.

    x = x + mixer(rms(x; w_in));  x = x + W_down(silu(W_gate u) * (W_up u)), u = rms(x; w_ff)
    Mamba mixer, t = 1..L:
      [h, z]  = W_inproj x
      h_t     = silu(b_c + sum_{k<4} w_c[k] * h_{t-3+k})      zeros before t = 1
      [r,B,C] = W_x h_t
      dt_t    = softplus(W_dt rms(r) + b_dt);  B_t = rms(B);  C_t = rms(C)
      S_t     = exp(dt_t[:,None] * A) * S_{t-1} + (dt_t * h_t)[:,None] * B_t[None,:]
      y_t     = S_t C_t + D * h_t;   out_t = W_out (y_t * silu(z_t))
    Attention mixer: causal softmax(q k^T / sqrt(head)) v over one shared
      key/value head, no bias, no positional encoding

float32 throughout, dense attention, the recurrence one token at a time
(`lax.scan`), one layer's weights on the device at a time. Precisions:

- `highest`: every product in float32;
- `reference`: what the configuration states: both operands of every
  matrix product rounded to bfloat16 and accumulated in float32; `dt`,
  `A`, the state and the recurrence, norms, softmax and gates in float32;
- `float8`: the control, the nearest precision below: both operands of
  every matrix product (weights and activations) rounded to float8
  (e4m3), accumulated in float32;
- `state_bfloat16`: the second control: `reference`, with the recurrent
  state rounded to bfloat16 after every token.

Weights are random and bfloat16-exact, and travel in a 2-byte form: a
leaf is the `uint16` bit pattern of its bfloat16 values, which the
program's loader and `outputs` both read. `make_weights` draws nothing:
each leaf is made when it is first read (`np.asarray`), from its own
stream, and kept in its 2-byte form (6 GB in all), so neither a run nor
the weights file ever holds 12 GB of float32, a run that finds its
weights file written draws nothing before its window, and a second
reading of the reference (a control, another seed) draws nothing again.
"""

from __future__ import annotations

import functools
import math
import re
import zlib
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

CONTROL_PRECISION = {"bfloat16": "float8"}
#: the second control, read beside the first where a cell's limits are set
SECOND_CONTROL = "state_bfloat16"

PAD, CLS, SEP, N_RESERVED = 0, 1, 2, 4
HEAD_DIM = 128
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


def is_attention(config, i: int) -> bool:
    return i % config["attn_layer_period"] == config["attn_layer_offset"]


def d_inner(config) -> int:
    return config["mamba_expand"] * config["hidden_size"]


def head_dim(config) -> int:
    return config.get("head_dim", HEAD_DIM)


def layer_shapes(config, i: int) -> dict:
    """{name under `layers/<i>/`: shape}. Matrices are [in, out]; the
    convolution's taps are [tap, channel], the oldest first."""
    h, f = config["hidden_size"], config["intermediate_size"]
    shapes = {"norm_in": (h,), "norm_ff": (h,)}
    if is_attention(config, i):
        q = config["num_attention_heads"] * head_dim(config)
        kv = config["num_key_value_heads"] * head_dim(config)
        shapes.update(
            {"attn/q": (h, q), "attn/k": (h, kv), "attn/v": (h, kv), "attn/o": (q, h)}
        )
    else:
        di, n = d_inner(config), config["mamba_d_state"]
        r = config["mamba_dt_rank"]
        shapes.update({
            "mamba/in_proj": (h, 2 * di),
            "mamba/conv_w": (config["mamba_d_conv"], di),
            "mamba/conv_b": (di,),
            "mamba/x_proj": (di, r + 2 * n),
            "mamba/dt_norm": (r,),
            "mamba/b_norm": (n,),
            "mamba/c_norm": (n,),
            "mamba/dt_proj": (r, di),
            "mamba/dt_bias": (di,),
            "mamba/A_log": (di, n),
            "mamba/D": (di,),
            "mamba/out_proj": (di, h),
        })
    shapes.update({"mlp/gate": (h, f), "mlp/up": (h, f), "mlp/down": (f, h)})
    return shapes


def weight_shapes(config) -> dict:
    """{flat name: shape} of every leaf of the weights file."""
    h = config["hidden_size"]
    shapes = {"embed": (config["vocab_size"], h), "final_norm": (h,)}
    for i in range(config["num_hidden_layers"]):
        for name, shape in layer_shapes(config, i).items():
            shapes[f"layers/{i}/{name}"] = shape
    return shapes


def to_bits(values: np.ndarray) -> np.ndarray:
    """float32 -> the uint16 bit patterns of the nearest bfloat16."""
    return np.asarray(values, np.float32).astype(jnp.bfloat16).view(np.uint16)


def from_bits(leaf) -> np.ndarray:
    """A leaf of the weights, as made here or as read back from the
    weights file, as a bfloat16 array."""
    a = np.asarray(leaf)
    return a.view(jnp.bfloat16) if a.dtype == np.uint16 else a


class Leaf:
    """One tensor of the weights, made when `np.asarray` first asks.

    Matrices are uniform in +-sqrt(3 / fan_in), so that every layer's
    output has about the variance of its input and 28 of them neither
    blow up nor die; norm weights lie in [0.8, 1.2]. The recurrence is
    given a memory, as Mamba initialises it: `A_log = log(1..d_state)`,
    `b_dt` the inverse softplus of a step log-uniform in [0.001, 0.1],
    `D = 1`: `exp(dt * A)` then spans 0.2 to 0.999, and a state dropped
    at a chunk's edge changes hundreds of later tokens."""

    def __init__(self, name: str, shape: tuple, seed: int):
        self.name, self.shape, self.seed = name, tuple(shape), int(seed)
        self.dtype = np.dtype(np.uint16)
        self._bits = None  # kept once made: 2 bytes a parameter

    def _uniform(self, scale: float = 1.0) -> np.ndarray:
        """Uniform in (-scale, scale) on a grid of 65,536 values, from
        this leaf's own stream: 16-bit draws cost a sixteenth of float
        draws, and 3 billion of them are set-up time."""
        rng = np.random.default_rng(
            [self.seed, 0x1A3BA, zlib.crc32(self.name.encode())]
        )
        u = rng.integers(0, 65536, size=self.shape, dtype=np.uint16).astype(np.float32)
        u -= np.float32(32767.5)
        u *= np.float32(scale / 32768.0)
        return u

    def __array__(self, dtype=None, copy=None):
        if self._bits is None:
            self._bits = self._make()
        return self._bits if dtype is None else self._bits.astype(dtype)

    def _make(self) -> np.ndarray:
        kind = self.name.rsplit("/", 1)[-1]
        if kind == "A_log":
            n = self.shape[1]
            v = np.broadcast_to(np.log(np.arange(1, n + 1, dtype=np.float32)), self.shape)
        elif kind == "D":
            v = np.ones(self.shape, np.float32)
        elif kind == "dt_bias":
            lo, hi = math.log(1e-3), math.log(1e-1)
            dt = np.exp((self._uniform() + 1.0) * (0.5 * (hi - lo)) + lo)
            v = dt + np.log(-np.expm1(-dt))  # softplus(v) == dt
        elif kind.startswith("norm") or kind.endswith("_norm"):
            v = 1.0 + self._uniform(0.2)
        elif kind == "conv_b":
            v = self._uniform(0.1)
        elif kind == "embed":
            v = self._uniform(math.sqrt(3.0))  # unit variance
        else:
            # dt_proj is half as wide, as Mamba's own initialisation
            # keeps the step near its bias
            scale = 0.5 if kind == "dt_proj" else 1.0
            v = self._uniform(scale * math.sqrt(3.0 / self.shape[0]))
        return to_bits(v)


def make_weights(config, seed) -> dict:
    return {
        name: Leaf(name, shape, seed)
        for name, shape in weight_shapes(config).items()
    }


# -- the forward pass ---------------------------------------------------------


def _product(precision):
    """The matrix product of a precision: einsum(spec, a, b) -> float32."""
    if precision == "highest":
        return functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    low = jnp.float8_e4m3fn if precision == "float8" else jnp.bfloat16

    def product(spec, a, b):
        # float8 values are bfloat16 values: once rounded, the product of
        # two of them is exact in the float32 accumulator either way
        a = a.astype(low).astype(jnp.bfloat16)
        b = b.astype(low).astype(jnp.bfloat16)
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)

    return product


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mamba(config, w, u, precision):
    product = _product(precision)
    eps, n, r = config["rms_norm_eps"], config["mamba_d_state"], config["mamba_dt_rank"]
    length = u.shape[1]
    h, z = jnp.split(product("bli,io->blo", u, w["mamba/in_proj"]), 2, -1)
    taps = w["mamba/conv_w"]
    past = jnp.pad(h, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    h = _silu(
        w["mamba/conv_b"]
        + sum(taps[k] * past[:, k : k + length] for k in range(taps.shape[0]))
    )
    rbc = product("bli,io->blo", h, w["mamba/x_proj"])
    dt = jax.nn.softplus(
        product("bli,io->blo", _rms(rbc[..., :r], w["mamba/dt_norm"], eps), w["mamba/dt_proj"])
        + w["mamba/dt_bias"]
    )
    b = _rms(rbc[..., r : r + n], w["mamba/b_norm"], eps)
    c = _rms(rbc[..., r + n :], w["mamba/c_norm"], eps)
    a = -jnp.exp(w["mamba/A_log"])

    def step(state, at):
        dt_t, h_t, b_t, c_t = at
        state = (
            jnp.exp(dt_t[:, :, None] * a) * state
            + (dt_t * h_t)[:, :, None] * b_t[:, None, :]
        )
        if precision == "state_bfloat16":
            # not `astype` there and back: the TPU's compiler is allowed
            # excess precision and drops that pair (it read 0.0 on the chip)
            state = jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return state, jnp.sum(state * c_t[:, None, :], -1)

    time_first = lambda t: jnp.swapaxes(t, 0, 1)  # noqa: E731
    state = jnp.zeros((u.shape[0], h.shape[-1], n), jnp.float32)
    _, y = jax.lax.scan(step, state, tuple(map(time_first, (dt, h, b, c))))
    y = time_first(y) + w["mamba/D"] * h
    return product("bli,io->blo", y * _silu(z), w["mamba/out_proj"])


def _attention(config, w, u, precision):
    product = _product(precision)
    rows, length, _ = u.shape
    dh = head_dim(config)
    q = product("bli,io->blo", u, w["attn/q"]).reshape(rows, length, -1, dh)
    k = product("bli,io->blo", u, w["attn/k"]).reshape(rows, length, -1, dh)
    v = product("bli,io->blo", u, w["attn/v"]).reshape(rows, length, -1, dh)
    group = q.shape[2] // k.shape[2]  # query heads that share a key/value head
    q = q.reshape(rows, length, k.shape[2], group, dh)
    s = product("bqhgd,bkhd->bhgqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((length, length), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = product("bhgqk,bkhd->bqhgd", p, v).reshape(rows, length, -1)
    return product("bli,io->blo", o, w["attn/o"])


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer(config_items, attention, w, x, precision):
    config = dict(config_items)
    product = _product(precision)
    eps = config["rms_norm_eps"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    mixer = _attention if attention else _mamba
    x = x + mixer(config, w, _rms(x, w["norm_in"], eps), precision)
    u = _rms(x, w["norm_ff"], eps)
    gate = _silu(product("bli,io->blo", u, w["mlp/gate"]))
    up = product("bli,io->blo", u, w["mlp/up"])
    return x + product("bli,io->blo", gate * up, w["mlp/down"])


def _scalars(config) -> tuple:
    return tuple(sorted(
        (k, v) for k, v in config.items() if isinstance(v, (int, float, str, bool))
    ))


def outputs(config, weights, inputs, precision="reference", block_rows=4):
    """Embeddings of `inputs` (text strings), float32 [N, hidden]. Rows run
    in blocks, longest first, each block padded on the right to its own
    longest row rounded up to 64: a causal stack never lets a real token
    see a later pad. The layers are the outer loop, so one layer's
    weights are made, sent and dropped before the next."""
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
    items = _scalars(config)
    with ThreadPoolExecutor(8) as pool:  # a layer's leaves are made side by side
        for i in range(config["num_hidden_layers"]):
            names = list(layer_shapes(config, i))
            made = pool.map(
                lambda name, i=i: from_bits(weights[f"layers/{i}/{name}"]), names
            )
            w = {name: jnp.asarray(leaf) for name, leaf in zip(names, made)}
            blocks = [
                (idx, _layer(items, is_attention(config, i), w, x, precision))
                for idx, x in blocks
            ]
    final = jnp.asarray(from_bits(weights["final_norm"]), jnp.float32)
    out = np.zeros((len(rows), hidden), np.float32)
    for idx, x in blocks:
        last = np.array([len(rows[r]) - 1 for r in idx])
        state = x[np.arange(len(idx)), last]
        out[idx] = np.asarray(_rms(state, final, config["rms_norm_eps"]))
    return out
