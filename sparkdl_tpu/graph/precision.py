"""Precision rungs: compute dtype as a serving latency/cost dial.

The batch-size rung (``serving/router.py``) quantizes the WIDTH of a
dispatch; this module adds the DEPTH axis — how many bits each weight
and activation carries through the program. TPU-native stacks drive
precision through the XLA program rather than the model definition
(bf16 on the MXU is the canonical example), which makes dtype a
per-deployment knob instead of a model rewrite. Three rungs:

- ``f32`` — the baseline arm: the loaded ModelFunction untouched.
- ``bf16`` — floating params cast to bfloat16 (half the HBM; the
  residency budget sees the real loaded bytes, so capacity doubles)
  and floating inputs cast at the program edge, so matmuls run in
  bf16 where the backend's units support it; outputs cast back to
  float32 so the serving API's answer dtype never changes with the
  rung.
- ``int8-dynamic`` — weight-only dynamic quantization: large floating
  param leaves are stored as int8 with one symmetric per-tensor scale
  (4x smaller than f32) and dequantized INSIDE the jitted program at
  use; activations stay floating (the "dynamic" in the name — no
  calibration pass, no activation quantization error). Small leaves
  (biases, norms) stay f32: quantizing a 64-float bias saves nothing
  and costs accuracy.

Selection is per SLA class, house A/B style:
``SPARKDL_SERVE_PRECISION`` sets every class,
``SPARKDL_SERVE_PRECISION_<CLASS>`` overrides one, default ``f32``.
The rung rides the residency key, the router's grouping key, and the
wrapped ModelFunction's name (``resnet50[features]@bf16``) — so the
jit caches, the compile-cache ledger, and ``dispatch_env_key`` all see
a precision flip as a new program, never a silent reuse.

Donation interplay: the bf16 input cast is FUSED into the jitted
program (the cast is the wrapper fn's first op), so a donated flat
input buffer still frees at its last use in-program — same contract
as the uint8->f32 converter cast ``graph/function.py`` documents.

Parity contract: every non-f32 rung must pass an output-tolerance
gate against the f32 arm before it serves traffic
(``tools/mesh_smoke.py`` asserts it on every preflight), exactly like
every prior A/B arm.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.runtime import knobs

#: Supported rungs, baseline first.
PRECISIONS = ("f32", "bf16", "int8-dynamic")

#: Floating param leaves below this many elements stay f32 under
#: int8-dynamic: the storage win is negligible and the quant error is
#: pure loss (biases, layer norms, tiny heads).
_QUANT_MIN_ELEMS = 256


def serve_precision(priority: Optional[str] = None) -> str:
    """The effective precision rung for one SLA class (or the global
    default when ``priority`` is None): per-class override first, then
    the global knob, then ``f32``. Unknown values raise, naming the
    knob — a typo'd rung must not silently serve f32."""
    raw = None
    name = "SPARKDL_SERVE_PRECISION"
    if priority:
        per_cls = f"SPARKDL_SERVE_PRECISION_{priority.upper()}"
        raw = knobs.get_str(per_cls)
        if raw:
            name = per_cls
    if not raw:
        raw = knobs.get_str("SPARKDL_SERVE_PRECISION") or "f32"
    if raw not in PRECISIONS:
        raise ValueError(
            f"{name}={raw!r}: expected one of {PRECISIONS}"
        )
    return raw


def precision_active() -> bool:
    """Whether any precision knob is explicitly set — the gate for the
    per-arm ``serve.precision.<arm>.*`` metrics, so a deployment that
    never touched the dial doesn't grow a redundant f32-only metric
    family next to the per-class latencies it already has."""
    if knobs.get_raw("SPARKDL_SERVE_PRECISION") is not None:
        return True
    return any(
        knobs.get_raw(f"SPARKDL_SERVE_PRECISION_{cls}") is not None
        for cls in ("INTERACTIVE", "BATCH", "BACKGROUND")
    )


def _is_float_leaf(leaf: Any) -> bool:
    dtype = getattr(leaf, "dtype", None)
    return dtype is not None and jnp.issubdtype(dtype, jnp.floating)


def _cast_floating(tree: Any, dtype) -> Any:
    """Cast floating leaves of a pytree to ``dtype``; integer leaves
    (token-id inputs, embedding indices) pass through untouched."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.astype(dtype) if _is_float_leaf(leaf) else leaf,
        tree,
    )


def _quantize_params(params: Any):
    """Weight-only symmetric int8: each large floating leaf becomes
    ``{"q": int8, "s": scale}`` (one per-tensor scale; zero-point-free,
    so dequant is a single multiply); everything else rides as
    ``{"raw": leaf}``. The packed list-of-dicts is itself a valid
    pytree, so it closes over the jit like any params tree."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    packed = []
    for leaf in leaves:
        if _is_float_leaf(leaf) and int(np.prod(leaf.shape)) >= _QUANT_MIN_ELEMS:
            arr = np.asarray(leaf, dtype=np.float32)
            scale = float(np.max(np.abs(arr)) / 127.0) or 1.0
            q = np.clip(np.round(arr / scale), -127, 127).astype(np.int8)
            packed.append({"q": jnp.asarray(q), "s": jnp.float32(scale)})
        else:
            packed.append({"raw": leaf})
    return packed, treedef


def _dequantize(packed, treedef):
    """Trace-time inverse of :func:`_quantize_params` — runs INSIDE the
    jitted program, so the int8 tensors are what the device holds and
    the f32 view exists only transiently at use."""
    leaves = [
        d["q"].astype(jnp.float32) * d["s"] if "q" in d else d["raw"]
        for d in packed
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def apply_precision(mf, precision: str):
    """The ``precision`` rung of a ModelFunction: a NEW ModelFunction
    whose params carry the rung's storage dtype and whose fn casts at
    the program edges (floating inputs down, outputs back to f32).
    ``f32`` returns ``mf`` unchanged. The wrapped name carries the rung
    (``<name>@<precision>``) so every jit/compile-ledger key downstream
    is a distinct first-class arm."""
    from sparkdl_tpu.graph.function import ModelFunction

    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision rung {precision!r}; expected one of "
            f"{PRECISIONS}"
        )
    if precision == "f32" or getattr(mf, "precision", None) == precision:
        return mf
    inner = mf.fn
    if precision == "bf16":
        params = _cast_floating(mf.params, jnp.bfloat16)

        def fn(p, x):
            y = inner(p, _cast_floating(x, jnp.bfloat16))
            return _cast_floating(y, jnp.float32)

    else:  # int8-dynamic
        packed, treedef = _quantize_params(mf.params)
        params = packed

        def fn(p, x):
            y = inner(_dequantize(p, treedef), x)
            return _cast_floating(y, jnp.float32)

    wrapped = ModelFunction(
        fn,
        params,
        input_shape=mf.input_shape,
        input_dtype=mf.input_dtype,
        name=f"{mf.name}@{precision}",
    )
    # Dynamic attributes the serving path reads off loader-built MFs
    # must survive the wrap (single_stream keeps whole-mesh programs
    # off the per-batch rotation; params_sharded drives the residency
    # manager's per-chip sizing; vocab_size and the attention a text
    # model was built with ride text entries).
    for attr in (
        "single_stream", "params_sharded", "vocab_size", "mesh", "attention"
    ):
        if hasattr(mf, attr):
            setattr(wrapped, attr, getattr(mf, attr))
    wrapped.precision = precision
    return wrapped


__all__ = [
    "PRECISIONS",
    "apply_precision",
    "precision_active",
    "serve_precision",
]
