"""Ulysses-style all-to-all sequence parallelism.

The second long-context strategy next to ops/ring_attention.py (the
reference had neither — SURVEY.md §6 "Long-context / sequence
parallelism: Absent"): instead of rotating K/V blocks around a ring, two
``all_to_all`` collectives re-shard the activations between
sequence-sharded and head-sharded layouts (Jacobs et al.,
"DeepSpeed Ulysses", 2309.14509; PAPERS.md):

    [B, H, L/n, Dh] --all_to_all--> [B, H/n, L, Dh]
        (attention with FULL sequence on 1/n of the heads)
    [B, H/n, L, Dh] --all_to_all--> [B, H, L/n, Dh]

Every layer outside attention stays sequence-sharded; inside attention
each device sees the whole sequence for its head shard, so ANY inner
attention implementation works unchanged — including the Pallas flash
kernel (ops/flash_attention.py), which composes with the ring variant
less directly. Communication is two all-to-alls of the activations
(O(B·L·D/n) per device, riding ICI) versus the ring's n K/V rotations;
the trade is head-count divisibility (H % n == 0) for collective
simplicity.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp


def make_ulysses_attention(
    axis_name: str = "sp", inner: Optional[Callable] = None
):
    """Returns an attention fn with the ``dense_attention`` signature
    (q, k, v, mask, dtype) for use INSIDE shard_map, where q/k/v are the
    local sequence shards [B, H, L/n, Dh] and mask is the local additive
    mask [B, 1, 1, L/n] (or None). ``inner`` is the attention executed on
    the head-sharded layout (default: dense softmax attention; pass
    ``make_flash_attention_fn()`` for the Pallas kernel on TPU)."""

    def ulysses_attention(q, k, v, mask, dtype):
        n = jax.lax.axis_size(axis_name)
        nheads = q.shape[1]
        if nheads % n != 0:
            raise ValueError(
                f"Ulysses attention needs heads % axis_size == 0; got "
                f"{nheads} heads over {n} devices (use ring attention for "
                "head counts that don't divide)"
            )
        inner_fn = inner
        if inner_fn is None:
            from sparkdl_tpu.models.bert import dense_attention

            inner_fn = dense_attention

        def seq_to_heads(x):
            # [B, H, L/n, Dh] -> [B, H/n, L, Dh]
            return jax.lax.all_to_all(
                x, axis_name, split_axis=1, concat_axis=2, tiled=True
            )

        qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
        full_mask = (
            jax.lax.all_gather(mask, axis_name, axis=3, tiled=True)
            if mask is not None
            else None
        )
        out = inner_fn(qh, kh, vh, full_mask, dtype)
        # [B, H/n, L, Dh] -> [B, H, L/n, Dh]
        return jax.lax.all_to_all(
            out, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    ulysses_attention.kind = "ulysses"
    return ulysses_attention


def ulysses_attention_sharded(
    q, k, v, mask, mesh, axis: str = "sp", dtype=jnp.float32,
    inner: Optional[Callable] = None,
):
    """Convenience wrapper: exact attention with L sharded over ``axis``
    and heads swapped via all_to_all inside. Mirrors
    ring_attention_sharded."""
    from sparkdl_tpu.ops.ring_attention import sharded_attention

    return sharded_attention(
        make_ulysses_attention(axis, inner=inner),
        q, k, v, mask, mesh, axis, dtype,
    )
