"""Jamba: a hybrid of Mamba-1 state-space layers and attention layers
(``model_type: jamba``), the first causal, non-BERT text family here.

Layer ``i`` mixes with attention where ``i % attn_layer_period ==
attn_layer_offset`` and with a Mamba-1 selective state-space mixer
elsewhere; every layer ends in a dense SwiGLU MLP; residuals are
pre-norm (RMSNorm); there is no positional encoding (the recurrence and
the causal mask carry order); the attention layers share one key/value
head among all query heads.

    x = x + mixer(rms(x; w_in));  x = x + W_down(silu(W_gate u) * (W_up u)),  u = rms(x; w_ff)
    Mamba mixer, t = 1..L:
      [h, z]  = W_inproj x
      h_t     = silu(b_c + sum_k w_c[k] * h_{t-(K-1)+k})     zeros before t = 1
      [r,B,C] = W_x h_t
      dt_t    = softplus(W_dt rms(r) + b_dt);  B_t = rms(B);  C_t = rms(C)
      S_t     = exp(dt_t[:,None] * A) * S_{t-1} + (dt_t * h_t)[:,None] * B_t[None,:],  A = -exp(A_log)
      y_t     = S_t C_t + D * h_t;   out_t = W_out (y_t * silu(z_t))

``embed`` is the final RMSNorm of the state at a row's last real token,
float32: what a causal embedder pools. Rows are padded on the right, and
a causal stack never lets a real token see a later pad, so nothing
inside the stack is masked: the mask only says where a row ends.

Precision: matrices and the activations that feed them are ``dtype``
(bfloat16 where the benchmark runs it), every matrix product accumulates
in float32, and the residual stream, the norms, the recurrence with its
inputs (``h``, ``z``, ``dt``, ``B``, ``C``, ``A``) and its state ``S``
are float32 whatever ``dtype`` is. The
recurrence is ``ops/selective_scan.py`` and the attention
``ops/flash_attention.py`` (causal, shared key/value head): the Pallas
kernels on TPU, plain ``jax.numpy`` elsewhere, chosen once at build time
and reported as ``mf.scan`` and ``mf.attention``.

The 28 layers are unrolled into one program with every layer's weights
an argument of its own (``weights_as_arguments``): a scan over stacked
layers would compile faster and copy each layer's 0.2 GB out of the
stack as it goes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.models.layers import dense, load_flat, preset, rms, silu, unflatten
from sparkdl_tpu.utils.profiler import scope


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    rms_norm_eps: float = 1e-6

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    def is_attention(self, i: int) -> bool:
        return i % self.attn_layer_period == self.attn_layer_offset

    @property
    def scan_layers(self) -> int:
        return sum(not self.is_attention(i) for i in range(self.num_layers))


def jamba2_3b() -> JambaConfig:
    """AI21-Jamba2-3B as its ``config.json`` states it: 26 Mamba layers
    and two attention layers (7 and 21), 3.03 B parameters."""
    return JambaConfig()


def jamba_tiny() -> JambaConfig:
    """The same family at a size the CPU tests hold: both mixer kinds,
    two Mamba layers either side of the one attention layer, the
    published ``d_state`` 16 and ``d_conv`` 4, one key/value head."""
    return JambaConfig(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=5,
        attn_layer_period=5,
        attn_layer_offset=2,
        num_heads=4,
        num_kv_heads=1,
        head_dim=16,
        dt_rank=8,
    )


_SIZES = {"jamba2-3b": jamba2_3b, "jamba-tiny": jamba_tiny}


def layer_shapes(config: JambaConfig, i: int) -> dict:
    """{path under ``layers/<i>/``: shape}; matrices are [in, out], the
    convolution's taps [tap, channel], the oldest first."""
    h, f = config.hidden_size, config.intermediate_size
    shapes = {"norm_in": (h,), "norm_ff": (h,)}
    if config.is_attention(i):
        q = config.num_heads * config.head_dim
        kv = config.num_kv_heads * config.head_dim
        shapes.update({
            "attn/q": (h, q), "attn/k": (h, kv), "attn/v": (h, kv),
            "attn/o": (q, h),
        })
    else:
        di, n, r = config.d_inner, config.d_state, config.dt_rank
        shapes.update({
            "mamba/in_proj": (h, 2 * di),
            "mamba/conv_w": (config.d_conv, di),
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


def param_shapes(config: JambaConfig) -> dict:
    """{flat path: shape} of every leaf, as a weights file names them."""
    h = config.hidden_size
    shapes = {"embed": (config.vocab_size, h), "final_norm": (h,)}
    for i in range(config.num_layers):
        for name, shape in layer_shapes(config, i).items():
            shapes[f"layers/{i}/{name}"] = shape
    return shapes


def _leaf_dtype(path: str, shape: tuple, dtype) -> Any:
    """Matrices (and the embedding) are ``dtype``; vectors, the
    convolution and ``A_log`` feed float32 arithmetic and stay float32."""
    kind = path.rsplit("/", 1)[-1]
    small = len(shape) == 1 or kind in ("conv_w", "A_log")
    return jnp.float32 if small else dtype


def init_params(config: JambaConfig, seed: int, dtype) -> dict:
    """Random weights that leave the recurrence a memory, as Mamba
    initialises it: ``A_log = log(1..d_state)``, ``b_dt`` the inverse
    softplus of a step log-uniform in [0.001, 0.1], ``D = 1``; matrices
    scaled by fan-in so that the layers neither blow up nor die."""
    rng = np.random.default_rng([int(seed), 0x7A3BA])
    flat = {}
    for path, shape in param_shapes(config).items():
        kind = path.rsplit("/", 1)[-1]
        if kind == "A_log":
            v = np.broadcast_to(
                np.log(np.arange(1, shape[1] + 1, dtype=np.float32)), shape
            )
        elif kind == "D" or "norm" in kind:
            v = np.ones(shape, np.float32)
        elif kind == "dt_bias":
            dt = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), shape))
            v = dt + np.log(-np.expm1(-dt))
        elif kind == "conv_b":
            v = np.zeros(shape, np.float32)
        elif kind == "embed":
            v = rng.standard_normal(shape, dtype=np.float32)
        else:
            v = rng.standard_normal(shape, dtype=np.float32) / math.sqrt(shape[0])
        flat[path] = jnp.asarray(v, _leaf_dtype(path, shape, dtype))
    return unflatten(flat)


def load_params(config: JambaConfig, weights_file: str, dtype) -> dict:
    """A flat ``.npz`` of ``param_shapes``' paths."""
    return load_flat(param_shapes(config), weights_file, dtype, _leaf_dtype)


def _causal_conv(h, taps, bias):
    """Depthwise over the sequence: token t reads tokens t-(K-1)..t, zeros
    before the first. ``taps`` [K, channel], the oldest first."""
    k, length = taps.shape[0], h.shape[1]
    past = jnp.pad(h, ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(taps[j] * past[:, j : j + length] for j in range(k))


def _last_real_state(x, ids):
    """x [B, L, hidden] at each row's last token that is not padding."""
    last = jnp.maximum(jnp.sum(ids != 0, axis=1) - 1, 0)
    return x[jnp.arange(ids.shape[0]), last]


def _mamba(config: JambaConfig, p, u, scan_fn):
    """``h`` and ``z`` are inputs of the float32 recurrence and stay
    float32 from the projection that makes them to the kernel that reads
    them; what feeds a matrix product is rounded to ``u``'s dtype."""
    dtype, eps = u.dtype, config.rms_norm_eps
    n, r = config.d_state, config.dt_rank
    with scope("mamba.in_proj"):
        h, z = jnp.split(dense(u, p["in_proj"]), 2, -1)
    with scope("mamba.conv"):
        h = silu(_causal_conv(h, p["conv_w"], p["conv_b"]))
    with scope("mamba.ssm_inputs"):
        rbc = dense(h.astype(dtype), p["x_proj"])
        dt = jax.nn.softplus(
            dense(rms(rbc[..., :r], p["dt_norm"], eps).astype(dtype), p["dt_proj"])
            + p["dt_bias"]
        )
        b = rms(rbc[..., r : r + n], p["b_norm"], eps)
        c = rms(rbc[..., r + n :], p["c_norm"], eps)
        a = -jnp.exp(p["A_log"])
    with scope("mamba.scan"):
        gated = scan_fn(h, dt, b, c, z, a, p["D"])
    with scope("mamba.out_proj"):
        return dense(gated.astype(dtype), p["out_proj"])


def _attention(config: JambaConfig, p, u, attention_fn):
    dtype = u.dtype
    rows, length, _ = u.shape

    def heads(w):  # [B, L, heads * Dh] -> [B, heads, L, Dh]
        t = dense(u, w).astype(dtype)
        return t.reshape(rows, length, -1, config.head_dim).transpose(0, 2, 1, 3)

    with scope("attn.qkv"):
        q, k, v = heads(p["q"]), heads(p["k"]), heads(p["v"])
    with scope("attn.core"):
        o = attention_fn(q, k, v, None, dtype)
    with scope("attn.out"):
        return dense(o.transpose(0, 2, 1, 3).reshape(rows, length, -1), p["o"])


def forward(config: JambaConfig, params, ids, *, dtype, attention_fn, scan_fn):
    """ids [B, L] int32, zero-padded on the right -> [B, hidden] float32."""
    eps = config.rms_norm_eps
    with scope("embed"):
        x = params["embed"][ids].astype(jnp.float32)
    for i in range(config.num_layers):
        p = params["layers"][str(i)]
        attends = config.is_attention(i)
        # a norm is in the scope of the part it feeds, a residual sum in
        # that of the part it closes
        with scope("attn.qkv" if attends else "mamba.in_proj"):
            u = rms(x, p["norm_in"], eps).astype(dtype)
        if attends:
            mixed = _attention(config, p["attn"], u, attention_fn)
        else:
            mixed = _mamba(config, p["mamba"], u, scan_fn)
        with scope("attn.out" if attends else "mamba.out_proj"):
            x = x + mixed
        with scope("mlp"):
            u = rms(x, p["norm_ff"], eps).astype(dtype)
            mlp = p["mlp"]
            gate = silu(dense(u, mlp["gate"]))
            x = x + dense((gate * dense(u, mlp["up"])).astype(dtype), mlp["down"])
    with scope("pool"):
        return rms(_last_real_state(x, ids), params["final_norm"], eps)


def jamba_model_function(
    size: str = "jamba-tiny",
    dtype=jnp.float32,
    seed: int = 0,
    weights_file: Optional[str] = None,
    attention_fn=None,
    scan_fn=None,
    name: Optional[str] = None,
):
    """The ``embed`` ModelFunction over ids batches (or ``(ids, mask)``
    tuples, as TextEmbedder feeds them; the mask is not needed).
    ``attention_fn`` and ``scan_fn`` default to the build-time choice of
    ``make_flash_attention_fn(causal=True)`` and
    ``make_selective_scan_fn()``: the Pallas kernels on TPU."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.ops.flash_attention import make_flash_attention_fn
    from sparkdl_tpu.ops.selective_scan import make_selective_scan_fn

    config = preset(_SIZES, size, "Jamba")
    if attention_fn is None:
        # 512-token blocks: a 2,048-token row is 10 live blocks a head,
        # not 136 of 128
        attention_fn = make_flash_attention_fn(
            block_q=512, block_k=512, causal=True
        )
    if scan_fn is None:
        scan_fn = make_selective_scan_fn(out_dtype=dtype)
    if weights_file:
        params = load_params(config, weights_file, dtype)
    else:
        params = init_params(config, seed, dtype)

    def fn(p, x):
        ids = x[0] if isinstance(x, (tuple, list)) else x
        return forward(
            config, p, ids, dtype=dtype, attention_fn=attention_fn,
            scan_fn=scan_fn,
        )

    mf = ModelFunction(
        fn, params, input_dtype=jnp.int32, name=name or f"{size}[embed]"
    )
    mf.weights_as_arguments = True
    mf.vocab_size = config.vocab_size
    mf.attention = getattr(attention_fn, "kind", "custom")
    mf.scan = getattr(scan_fn, "kind", "custom")
    mf.scan_layers = config.scan_layers
    return mf
