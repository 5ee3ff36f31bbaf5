"""Flax-native BERT encoder (bert-base geometry).

Reference analogue: the "KerasTransformer BERT-base text-embedding UDF"
capability (BASELINE config[3]; SURVEY.md §3.2 — sequence models appear as
fixed-length inference). Original flax implementation, TPU-first:

- bf16-capable compute dtype, float32 params/layernorm accumulation;
- attention is pluggable: dense softmax attention for single-device, or
  **ring attention** (sparkdl_tpu.ops.ring_attention) when the sequence
  axis is sharded over a mesh 'sp' axis — long-context inference/training
  beyond one chip's HBM, which the reference had no analogue for;
- pure-function apply (no mutable state), so the whole encoder jits into
  one XLA program and shards with pjit/shard_map.

Weights: random init offline (see registry docstring), or load a
HuggingFace Flax BERT checkpoint pytree via ``load_hf_bert_params`` —
parity with transformers' FlaxBertModel is tested by mapping its weights
into this module and comparing outputs.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.utils.profiler import scope


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.float32


def bert_base(dtype=jnp.float32) -> "BertEncoder":
    return BertEncoder(BertConfig(dtype=dtype))


def bert_tiny(dtype=jnp.float32) -> "BertEncoder":
    """4-layer/128-hidden geometry for tests."""
    return BertEncoder(
        BertConfig(
            vocab_size=1000,
            hidden_size=128,
            num_layers=4,
            num_heads=4,
            intermediate_size=256,
            max_position_embeddings=128,
            dtype=dtype,
        )
    )


def bert_long(dtype=jnp.float32, max_positions: int = 2048) -> "BertEncoder":
    """Long-context encoder: tiny-ish compute geometry with a position
    table stretched to ``max_positions`` (default 2048). The config the
    flash/ring kernels exist for — dense attention materializes the
    [L, L] score matrix (a 2048² float32 block per head), the Pallas
    flash kernel streams it through VMEM in O(L) memory — registered as
    the serving path's seq>=2048 workload (models/registry.py)."""
    return BertEncoder(
        BertConfig(
            vocab_size=8192,
            hidden_size=128,
            num_layers=2,
            num_heads=4,
            intermediate_size=256,
            max_position_embeddings=max_positions,
            dtype=dtype,
        )
    )


class BertEmbeddings(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, position_offset=0):
        c = self.config
        # position_offset: sequence-parallel runs pass axis_index * L_local
        # so each shard embeds its GLOBAL positions.
        pos_ids = (jnp.arange(input_ids.shape[1]) + position_offset)[None, :]
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        e = (
            nn.Embed(c.vocab_size, c.hidden_size, name="word_embeddings")(
                input_ids
            )
            + nn.Embed(
                c.max_position_embeddings,
                c.hidden_size,
                name="position_embeddings",
            )(pos_ids)
            + nn.Embed(
                c.type_vocab_size, c.hidden_size, name="token_type_embeddings"
            )(token_type_ids)
        )
        e = nn.LayerNorm(epsilon=c.layer_norm_eps, name="layer_norm")(e)
        return e.astype(c.dtype)


def dense_attention(q, k, v, mask, dtype):
    """Standard softmax attention. q,k,v: [B, H, L, Dh]; mask: [B, 1, 1, L]
    additive (-inf on pads). Softmax accumulates in float32."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


dense_attention.kind = "dense"


def attention_layout(attention_fn) -> str:
    """The layout an attention function takes and returns, said beside
    ``.kind``: 'heads' ([B, H, L, Dh], also what a function that says
    nothing takes) or 'packed' ([B, L, H*Dh], the projections' own
    output, with the head count as ``num_heads=``)."""
    return getattr(attention_fn or dense_attention, "layout", "heads")


class BertSelfAttention(nn.Module):
    config: BertConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, mask):
        c = self.config
        h, dh = c.num_heads, c.hidden_size // c.num_heads

        def proj(name):
            return nn.Dense(c.hidden_size, dtype=c.dtype, name=name)(x)

        attn = self.attention_fn or dense_attention
        packed = attention_layout(attn) == "packed"

        def split(t):  # [B, L, D] -> [B, H, L, Dh]
            return t.reshape(*t.shape[:2], h, dh).transpose(0, 2, 1, 3)

        with scope("attn.qkv"):
            q, k, v = proj("query"), proj("key"), proj("value")
            if not packed:
                q, k, v = split(q), split(k), split(v)
        with scope("attn.core"):
            if packed:
                out = attn(q, k, v, mask, c.dtype, num_heads=h)
            else:
                out = attn(q, k, v, mask, c.dtype)
        with scope("attn.out"):
            if not packed:
                out = out.transpose(0, 2, 1, 3).reshape(
                    *x.shape[:2], c.hidden_size
                )
            out = nn.Dense(c.hidden_size, dtype=c.dtype, name="output")(out)
        return out


class BertLayer(nn.Module):
    config: BertConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, mask):
        c = self.config
        attn_out = BertSelfAttention(
            c, attention_fn=self.attention_fn, name="attention"
        )(x, mask)
        # post-norm: a norm closes the block before it, so its scope is
        # that block's last
        with scope("attn.out"):
            x = nn.LayerNorm(epsilon=c.layer_norm_eps, name="attention_norm")(
                (x + attn_out).astype(jnp.float32)
            ).astype(c.dtype)
        with scope("mlp"):
            mlp = nn.Dense(
                c.intermediate_size, dtype=c.dtype, name="intermediate"
            )(x)
            mlp = nn.gelu(mlp, approximate=False)
            mlp = nn.Dense(c.hidden_size, dtype=c.dtype, name="mlp_output")(mlp)
            x = nn.LayerNorm(epsilon=c.layer_norm_eps, name="output_norm")(
                (x + mlp).astype(jnp.float32)
            ).astype(c.dtype)
        return x


class BertEncoder(nn.Module):
    """Returns last_hidden_state [B, L, D]; ``pooled`` gives mean-pooled
    masked embeddings [B, D] (the text-embedding UDF output)."""

    config: BertConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        pooled: bool = False,
        position_offset=0,
    ):
        c = self.config
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        with scope("embed"):
            additive = (
                1.0 - attention_mask[:, None, None, :].astype(jnp.float32)
            )
            additive = additive * jnp.finfo(jnp.float32).min
            x = BertEmbeddings(c, name="embeddings")(
                input_ids, token_type_ids, position_offset=position_offset
            )
        for i in range(c.num_layers):
            x = BertLayer(
                c, attention_fn=self.attention_fn, name=f"layer_{i}"
            )(x, additive)
        with scope("pool"):
            x = x.astype(jnp.float32)
            if pooled:
                m = attention_mask[..., None].astype(jnp.float32)
                return jnp.sum(x * m, axis=1) / jnp.maximum(
                    jnp.sum(m, axis=1), 1.0
                )
            return x

    def embed(self, input_ids, attention_mask=None, token_type_ids=None):
        return self(
            input_ids, attention_mask, token_type_ids, pooled=True
        )


_SIZES = {"base": bert_base, "tiny": bert_tiny, "long": bert_long}


def flash_attention_for(config: BertConfig):
    """``make_flash_attention_fn`` told the heads' shape, which decides
    the kernel and the layout it reads (see there)."""
    from sparkdl_tpu.ops.flash_attention import make_flash_attention_fn

    return make_flash_attention_fn(
        num_heads=config.num_heads,
        head_dim=config.hidden_size // config.num_heads,
    )


def encoder_model_function(module: "BertEncoder", fn, params, name: str):
    """The embed ModelFunction over ``fn`` (a closure on ``module.apply``),
    carrying what the module knows — the one place every text builder
    gets these from. ``vocab_size`` lets tokenizers bound their id space
    (out-of-vocab ids would be out-of-bounds embedding gathers);
    ``attention`` is the ``kind`` of the attention the module was built
    with ('flash' | 'dense' | 'ring' | 'ulysses'; 'custom' for a
    caller's own function), which ``/v1/models`` and ``chip_smoke.py``
    report, with ``attention_layout`` beside it ('packed' where the
    function reads the projections' own output, else 'heads')."""
    from sparkdl_tpu.graph.function import ModelFunction

    mf = ModelFunction(fn, params, input_dtype=jnp.int32, name=name)
    mf.vocab_size = module.config.vocab_size
    mf.attention = getattr(
        module.attention_fn or dense_attention, "kind", "custom"
    )
    mf.attention_layout = attention_layout(module.attention_fn)
    return mf


def bert_model_function(
    size: str = "base",
    dtype=jnp.float32,
    seed: int = 0,
    params=None,
    attention_fn=None,
    max_length: int = 128,
    config: "Optional[BertConfig]" = None,
):
    """Build a ModelFunction over (ids, mask) -> pooled embeddings [B, D]
    for the TextEmbedder / text-embedding UDF path. ``config`` overrides
    the size ladder with an explicit :class:`BertConfig` (its dtype is
    replaced by ``dtype``) — the long-context registry entries and the
    smokes' scaled-down geometries build through this."""
    if config is not None:
        from dataclasses import replace

        module = BertEncoder(replace(config, dtype=dtype))
    elif size in _SIZES:
        module = _SIZES[size](dtype=dtype)
    else:
        raise ValueError(
            f"Unknown BERT size {size!r}; supported: {sorted(_SIZES)}"
        )
    if max_length > module.config.max_position_embeddings:
        # JAX clamps out-of-bounds gathers, so an oversized sequence
        # would silently reuse the last position embedding — refuse
        # (same guard as the sequence-parallel builder).
        raise ValueError(
            f"max_length {max_length} exceeds the model's learned "
            f"position table ({module.config.max_position_embeddings})"
        )
    if attention_fn is None:
        # The Pallas flash kernel on TPU, the dense einsum elsewhere —
        # chosen once, here (see make_flash_attention_fn). Pass
        # attention_fn=dense_attention to force the einsum path.
        attention_fn = flash_attention_for(module.config)
    module = BertEncoder(module.config, attention_fn=attention_fn)
    if params is None:
        ids0 = jnp.zeros((1, min(max_length, 16)), jnp.int32)
        params = module.init(jax.random.PRNGKey(seed), ids0)

    def fn(p, x):
        ids, mask = x if isinstance(x, (tuple, list)) else (x, None)
        return module.apply(p, ids, mask, pooled=True)

    return encoder_model_function(module, fn, params, f"bert_{size}[embed]")


def bert_model_function_sequence_parallel(
    size: str = "base",
    mesh=None,
    axis: str = "sp",
    strategy: str = "ring",
    dtype=jnp.float32,
    seed: int = 0,
    params=None,
    max_length: int = 128,
):
    """Sequence-parallel BERT embedder: the SAME (ids, mask) ->
    pooled-embedding contract as :func:`bert_model_function`, but with
    the sequence dimension sharded over the mesh ``axis`` — the
    long-context path, usable anywhere a ModelFunction is (TextEmbedder,
    UDF registry, ...).

    ``strategy``: 'ring' (ppermute K/V rotation; any head count) or
    'ulysses' (all_to_all head swap; heads % axis size == 0). Masked
    mean pooling is computed with one psum pair over the axis, so every
    shard returns the identical [B, D] embeddings. ``max_length`` must
    be divisible by the axis size and fit the model's learned position
    table (``max_position_embeddings``).

    The returned ModelFunction carries ``single_stream=True``: it uses
    the WHOLE mesh per batch, so batch-level device round-robin must not
    apply (transformers/execution honors the flag).
    """
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        from sparkdl_tpu.parallel import make_mesh

        mesh = make_mesh({axis: len(jax.devices())})
    n = mesh.shape[axis]
    if max_length % n:
        raise ValueError(
            f"max_length {max_length} must be divisible by the {axis!r} "
            f"axis size ({n})"
        )
    if strategy == "ring":
        from sparkdl_tpu.ops.ring_attention import make_ring_attention

        attention_fn = make_ring_attention(axis)
    elif strategy == "ulysses":
        from sparkdl_tpu.ops.ulysses import make_ulysses_attention

        attention_fn = make_ulysses_attention(axis)
    else:
        raise ValueError(
            f"Unknown strategy {strategy!r}; expected 'ring' or 'ulysses'"
        )

    if size not in ("base", "tiny"):
        raise ValueError(f"Unknown BERT size {size!r}; supported: base, tiny")
    base_module = (bert_base if size == "base" else bert_tiny)(dtype=dtype)
    if max_length > base_module.config.max_position_embeddings:
        # JAX clamps out-of-bounds gathers, so an oversized sequence
        # would silently reuse the last position embedding — refuse.
        raise ValueError(
            f"max_length {max_length} exceeds the model's learned "
            f"position table "
            f"({base_module.config.max_position_embeddings}); sequence "
            "parallelism shards compute, not the position vocabulary"
        )
    if strategy == "ulysses" and base_module.config.num_heads % n:
        raise ValueError(
            f"ulysses needs heads ({base_module.config.num_heads}) "
            f"divisible by the {axis!r} axis ({n}); use strategy='ring'"
        )
    module = BertEncoder(base_module.config, attention_fn=attention_fn)
    if params is None:
        ids0 = jnp.zeros((1, min(max_length, 16)), jnp.int32)
        # init via the dense base_module: the attention fn carries no
        # parameters, so dense-trained params load directly.
        params = base_module.init(jax.random.PRNGKey(seed), ids0)

    L_local = max_length // n

    def local(p, ids_sh, mask_sh):
        offset = jax.lax.axis_index(axis) * L_local
        hidden = module.apply(
            p, ids_sh, mask_sh, position_offset=offset
        )  # [B, L/n, D]
        m = mask_sh[..., None].astype(jnp.float32)
        total = jax.lax.psum(jnp.sum(hidden * m, axis=1), axis)
        count = jax.lax.psum(jnp.sum(m, axis=1), axis)
        return total / jnp.maximum(count, 1.0)

    sharded = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(None, axis), P(None, axis)),
        out_specs=P(),
        check_vma=False,
    )

    def fn(p, x):
        ids, mask = x if isinstance(x, (tuple, list)) else (x, None)
        if mask is None:
            mask = jnp.ones_like(ids)
        if ids.shape[1] != max_length:
            raise ValueError(
                f"sequence length {ids.shape[1]} != max_length "
                f"{max_length} the mesh sharding was built for"
            )
        return sharded(p, ids, jnp.asarray(mask, jnp.int32))

    mf = encoder_model_function(
        module, fn, params, f"bert_{size}[embed,{strategy}/{axis}x{n}]"
    )
    mf.single_stream = True  # whole-mesh per batch; no device round-robin
    return mf


# -- autoregressive generation ------------------------------------------------
#
# The serving generate path (serving/generation.py) needs the encoder's
# per-layer K/V exposed as explicit cache state: a prefill program that
# runs the prompt once under a causal mask and returns the keys/values
# every later step will attend, and a single-token decode program that
# advances MANY sequences one position each call against a static
# [slots, max_length] cache (static shapes keep the jit cache at one
# program per geometry — the full-compilation story, applied to the step
# loop). flax's module.apply hides the K/V tensors, so the generator
# re-implements the layer math as pure jnp over the SAME param tree the
# embed path initializes — one set of weights, two program families.


def _ln_apply(p, x, eps):
    """flax LayerNorm equivalent over a {scale, bias} subtree, float32."""
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _dense_apply(p, x):
    return x @ p["kernel"] + p["bias"]


def _embed_apply(cfg: BertConfig, p, ids, positions):
    """Token + position + (type-0) embeddings -> layer-normed hidden."""
    emb = p["embeddings"]
    x = (
        emb["word_embeddings"]["embedding"][ids]
        + emb["position_embeddings"]["embedding"][positions]
        + emb["token_type_embeddings"]["embedding"][jnp.zeros_like(ids)]
    )
    return _ln_apply(emb["layer_norm"], x, cfg.layer_norm_eps)


def _layer_tail(cfg: BertConfig, lp, x, attn_out):
    """Post-attention residual + MLP half of one encoder layer."""
    x = _ln_apply(lp["attention_norm"], x + attn_out, cfg.layer_norm_eps)
    mlp = _dense_apply(lp["intermediate"], x)
    mlp = jax.nn.gelu(mlp, approximate=False)
    mlp = _dense_apply(lp["mlp_output"], mlp)
    return _ln_apply(lp["output_norm"], x + mlp, cfg.layer_norm_eps)


def _causal_forward(cfg: BertConfig, p, ids):
    """Causal full-sequence forward: hidden [B, L, D] plus the per-layer
    keys/values [n_layers, B, H, L, Dh] the decode cache is seeded from.
    Pad positions AFTER a row's real length compute garbage — harmless,
    because every later read is masked to keys <= the row's position."""
    B, L = ids.shape
    h, dh = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    pos = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
    x = _embed_apply(cfg, p, ids, pos)
    causal = jnp.tril(jnp.ones((L, L), jnp.float32))
    additive = (1.0 - causal)[None, None, :, :] * jnp.finfo(jnp.float32).min
    ks, vs = [], []
    for i in range(cfg.num_layers):
        lp = p[f"layer_{i}"]
        att = lp["attention"]

        def split(t):
            return t.reshape(B, L, h, dh).transpose(0, 2, 1, 3)

        q = split(_dense_apply(att["query"], x))
        k = split(_dense_apply(att["key"], x))
        v = split(_dense_apply(att["value"], x))
        ks.append(k)
        vs.append(v)
        out = dense_attention(q, k, v, additive, jnp.float32)
        out = out.transpose(0, 2, 1, 3).reshape(B, L, cfg.hidden_size)
        x = _layer_tail(cfg, lp, x, _dense_apply(att["output"], out))
    return x, jnp.stack(ks), jnp.stack(vs)


class BertGenerator:
    """Prefill + single-token decode over a BertEncoder param tree.

    - :meth:`prefill` runs one prompt [1, Lb] (seq-bucketed by the
      caller) under a causal mask: returns the per-layer K/V block and
      the next-token logits at the prompt's last real position.
    - :meth:`decode_step` advances ``slots`` sequences one token each:
      writes each row's new K/V at its own position via a one-hot
      scatter (per-row positions differ — that is continuous batching),
      attends keys <= position, returns updated caches + logits.

    Both programs jit against STATIC shapes: prefill per prompt bucket,
    decode once per (slots, max_length) — the warm-cache property the
    tentpole names. Cache layout: [n_layers, slots, H, max_length, Dh]
    float32; :meth:`kv_bytes_per_token` is the per-token ledger charge
    the admission-time KV budget uses.
    """

    def __init__(self, config: BertConfig, params, max_length: int):
        self.config = config
        self.max_length = int(max_length)
        if self.max_length > config.max_position_embeddings:
            raise ValueError(
                f"max_length {self.max_length} exceeds the model's "
                f"learned position table ({config.max_position_embeddings})"
            )
        self.vocab_size = int(config.vocab_size)
        # the same pytree module.init produced; accept either the
        # {"params": ...} envelope or the bare tree
        tree = params.get("params", params) if isinstance(params, dict) else params
        self._p = tree
        cfg = config

        def prefill_fn(p, ids, lengths):
            x, k, v = _causal_forward(cfg, p, ids)
            last = x[jnp.arange(ids.shape[0]), lengths - 1]
            logits = last @ p["embeddings"]["word_embeddings"]["embedding"].T
            return k, v, logits

        max_len = self.max_length
        h, dh = cfg.num_heads, cfg.hidden_size // cfg.num_heads

        def decode_fn(p, k_cache, v_cache, tokens, positions):
            S = tokens.shape[0]
            x = _embed_apply(cfg, p, tokens, positions)  # [S, D]
            oh = jax.nn.one_hot(positions, max_len, dtype=jnp.float32)
            keep = (1.0 - oh)[:, None, :, None]
            put = oh[:, None, :, None]
            live = jnp.arange(max_len)[None, :] <= positions[:, None]
            additive = (
                (1.0 - live.astype(jnp.float32))
                * jnp.finfo(jnp.float32).min
            )  # [S, M]
            scale = 1.0 / np.sqrt(dh)
            new_k, new_v = [], []
            for i in range(cfg.num_layers):
                lp = p[f"layer_{i}"]
                att = lp["attention"]
                q = _dense_apply(att["query"], x).reshape(S, h, dh)
                kn = _dense_apply(att["key"], x).reshape(S, h, dh)
                vn = _dense_apply(att["value"], x).reshape(S, h, dh)
                kc = k_cache[i] * keep + put * kn[:, :, None, :]
                vc = v_cache[i] * keep + put * vn[:, :, None, :]
                new_k.append(kc)
                new_v.append(vc)
                scores = (
                    jnp.einsum("shd,shmd->shm", q, kc).astype(jnp.float32)
                    * scale
                    + additive[:, None, :]
                )
                probs = jax.nn.softmax(scores, axis=-1)
                out = jnp.einsum("shm,shmd->shd", probs, vc).reshape(
                    S, cfg.hidden_size
                )
                x = _layer_tail(cfg, lp, x, _dense_apply(att["output"], out))
            logits = x @ p["embeddings"]["word_embeddings"]["embedding"].T
            return jnp.stack(new_k), jnp.stack(new_v), logits

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn)

    @property
    def kv_bytes_per_token(self) -> int:
        """Per-token K/V footprint: 2 (K and V) x layers x hidden x 4B
        (float32 cache) — the ledger/budget charge per cache position."""
        c = self.config
        return 2 * c.num_layers * c.hidden_size * 4

    @property
    def param_bytes(self) -> int:
        """Bytes of the generator's param pytree — the residency
        manager's budget charge for a resident ``generate`` entry."""
        return sum(
            int(getattr(leaf, "nbytes", 0))
            for leaf in jax.tree_util.tree_leaves(self._p)
        )

    def new_cache(self, slots: int):
        """Zeroed (k_cache, v_cache) for ``slots`` decode slots."""
        c = self.config
        shape = (
            c.num_layers,
            int(slots),
            c.num_heads,
            self.max_length,
            c.hidden_size // c.num_heads,
        )
        return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)

    def prefill(self, ids, length: int):
        """Run one prompt: ``ids`` [1, Lb] int32 (zero-padded past
        ``length``). Returns (k [Ln,1,H,Lb,Dh], v, logits [1, vocab])."""
        ids = jnp.asarray(ids, jnp.int32)
        lengths = jnp.asarray([int(length)], jnp.int32)
        return self._prefill(self._p, ids, lengths)

    def write_prefill(self, k_cache, v_cache, slot: int, k, v):
        """Install one prefilled sequence's K/V block into ``slot``.
        Stale positions past the block are never attended (the decode
        key mask stops at each row's own position)."""
        width = k.shape[3]
        k_cache = k_cache.at[:, slot, :, :width, :].set(k[:, 0])
        v_cache = v_cache.at[:, slot, :, :width, :].set(v[:, 0])
        return k_cache, v_cache

    def decode_step(self, k_cache, v_cache, tokens, positions):
        """One token for every slot: ``tokens``/``positions`` [slots]
        int32 (free slots pass token 0 at position 0 — their garbage
        write lands where the next prefill overwrites). Returns
        (k_cache, v_cache, logits [slots, vocab])."""
        return self._decode(
            self._p,
            k_cache,
            v_cache,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32),
        )

    def oracle_next_token(self, prompt_ids) -> int:
        """Cacheless greedy reference: recompute the full causal forward
        over ``prompt_ids`` and argmax the last position's logits — the
        independent path the smoke/tests compare streamed tokens
        against."""
        n = len(prompt_ids)
        # pad to a power-of-two edge (capped at the position table) so
        # the oracle compiles O(log max_length) programs, not one per
        # observed length; zero pads past ``n`` contribute exactly 0
        # under the causal mask, so the logits are length-exact
        width = 1
        while width < n:
            width *= 2
        width = min(max(width, n), self.max_length)
        ids = np.zeros((1, width), np.int32)
        ids[0, :n] = np.asarray(prompt_ids, np.int32)
        _, _, logits = self._prefill(
            self._p, jnp.asarray(ids), jnp.asarray([n], jnp.int32)
        )
        return int(jnp.argmax(logits[0]))

    def greedy_oracle(self, prompt_ids, max_new_tokens: int,
                      eos_id: Optional[int] = None) -> list:
        """Sequential greedy decode by full recompute (no cache): the
        row-identical oracle for the continuous-batching engine."""
        ids = [int(t) for t in prompt_ids]
        out = []
        for _ in range(int(max_new_tokens)):
            if len(ids) >= self.max_length:
                break
            tok = self.oracle_next_token(ids)
            out.append(tok)
            ids.append(tok)
            if eos_id is not None and tok == int(eos_id):
                break
        return out


# -- HuggingFace weight mapping ----------------------------------------------


def load_hf_bert_params(hf_params: dict, config: BertConfig) -> dict:
    """Map a transformers FlaxBertModel params pytree into this module's
    layout (embeddings + encoder layers; the HF pooler head is unused —
    our pooled output is masked mean pooling)."""

    def t(x):
        return jnp.asarray(x)

    emb = hf_params["embeddings"]
    out = {
        "embeddings": {
            "word_embeddings": {
                "embedding": t(emb["word_embeddings"]["embedding"])
            },
            "position_embeddings": {
                "embedding": t(emb["position_embeddings"]["embedding"])
            },
            "token_type_embeddings": {
                "embedding": t(emb["token_type_embeddings"]["embedding"])
            },
            "layer_norm": {
                "scale": t(emb["LayerNorm"]["scale"]),
                "bias": t(emb["LayerNorm"]["bias"]),
            },
        }
    }
    layers = hf_params["encoder"]["layer"]
    for i in range(config.num_layers):
        l = layers[str(i)]
        att = l["attention"]
        out[f"layer_{i}"] = {
            "attention": {
                "query": {
                    "kernel": t(att["self"]["query"]["kernel"]),
                    "bias": t(att["self"]["query"]["bias"]),
                },
                "key": {
                    "kernel": t(att["self"]["key"]["kernel"]),
                    "bias": t(att["self"]["key"]["bias"]),
                },
                "value": {
                    "kernel": t(att["self"]["value"]["kernel"]),
                    "bias": t(att["self"]["value"]["bias"]),
                },
                "output": {
                    "kernel": t(att["output"]["dense"]["kernel"]),
                    "bias": t(att["output"]["dense"]["bias"]),
                },
            },
            "attention_norm": {
                "scale": t(att["output"]["LayerNorm"]["scale"]),
                "bias": t(att["output"]["LayerNorm"]["bias"]),
            },
            "intermediate": {
                "kernel": t(l["intermediate"]["dense"]["kernel"]),
                "bias": t(l["intermediate"]["dense"]["bias"]),
            },
            "mlp_output": {
                "kernel": t(l["output"]["dense"]["kernel"]),
                "bias": t(l["output"]["dense"]["bias"]),
            },
            "output_norm": {
                "scale": t(l["output"]["LayerNorm"]["scale"]),
                "bias": t(l["output"]["LayerNorm"]["bias"]),
            },
        }
    return {"params": out}
