"""Ring attention: sequence-parallel exact attention over a mesh axis.

Long-context support is first-class in this framework (the reference had
none — SURVEY.md §6 "Long-context / sequence parallelism: Absent"): when a
sequence is too long for one chip's HBM, shard it over the mesh 'sp' axis
and compute exact attention with a ring schedule (Liu et al., Ring
Attention; the public scaling-book recipe): each device holds its local
Q/K/V chunk, iterates over the ring rotating K/V blocks with
``jax.lax.ppermute`` (neighbor-to-neighbor ICI traffic, overlappable with
compute), and accumulates the softmax **online** (flash-style running max/
sum), so no device ever materializes the full [L, L] score matrix or the
full K/V.

Numerics: scores and the online accumulator run in float32 regardless of
the compute dtype; the result is cast back to ``dtype``.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np


def _online_block_update(q, k_blk, v_blk, mask_blk, m, l, o, scale):
    """One flash-attention accumulation step against a K/V block.

    q: [B,H,Lq,Dh]; k_blk/v_blk: [B,H,Lk,Dh]; mask_blk: [B,1,1,Lk] additive
    (float32) or None; m,l: [B,H,Lq]; o: [B,H,Lq,Dh] (all float32).
    """
    s = (
        jnp.einsum("bhqd,bhkd->bhqk", q, k_blk).astype(jnp.float32) * scale
    )
    if mask_blk is not None:
        s = s + mask_blk
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # Guards for fully-masked blocks/queries (m or m_new still -inf):
    # exp(-inf - -inf) = nan must become exp(-inf) = 0 in both places.
    alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_new, -jnp.inf))
    p = jnp.exp(
        jnp.where(
            jnp.isfinite(m_new)[..., None], s - m_new[..., None], -jnp.inf
        )
    )
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32)
    )
    return m_new, l_new, o_new


def make_ring_attention(axis_name: str = "sp"):
    """Returns an attention fn with the dense_attention signature
    (q, k, v, mask, dtype) for use INSIDE shard_map, where q/k/v are the
    local sequence shards [B, H, L/n, Dh] and mask is the local additive
    mask [B, 1, 1, L/n] (or None). Drop-in for models.bert.dense_attention
    via BertEncoder(attention_fn=...)."""

    def ring_attention(q, k, v, mask, dtype):
        n = jax.lax.axis_size(axis_name)
        scale = 1.0 / np.sqrt(q.shape[-1])
        perm = [(i, (i + 1) % n) for i in range(n)]

        qf = q.astype(jnp.float32)
        m0 = jnp.full(q.shape[:-1], -jnp.inf, jnp.float32)
        l0 = jnp.zeros(q.shape[:-1], jnp.float32)
        o0 = jnp.zeros(q.shape, jnp.float32)
        mask0 = (
            mask.astype(jnp.float32)
            if mask is not None
            else jnp.zeros((q.shape[0], 1, 1, k.shape[2]), jnp.float32)
        )

        def body(_, carry):
            k_blk, v_blk, mask_blk, m, l, o = carry
            m, l, o = _online_block_update(
                qf, k_blk, v_blk, mask_blk, m, l, o, scale
            )
            # rotate K/V (and their mask) one step around the ring
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            mask_blk = jax.lax.ppermute(mask_blk, axis_name, perm)
            return k_blk, v_blk, mask_blk, m, l, o

        _, _, _, m, l, o = jax.lax.fori_loop(
            0, n, body, (k, v, mask0, m0, l0, o0)
        )
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)

    ring_attention.kind = "ring"
    return ring_attention


def sharded_attention(attn, q, k, v, mask, mesh, axis, dtype=jnp.float32):
    """Shared sequence-parallel driver for the long-context strategies:
    full [B,H,L,Dh] arrays in, exact attention out, with L sharded over
    ``axis`` and ``attn`` (a dense_attention-signature fn built for use
    inside shard_map, e.g. make_ring_attention/make_ulysses_attention)
    run on the local shards."""
    from jax.sharding import PartitionSpec as P

    def local(q_, k_, v_, mask_):
        return attn(q_, k_, v_, mask_, dtype)

    spec_qkv = P(None, None, axis, None)
    spec_mask = P(None, None, None, axis)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_mask),
        out_specs=spec_qkv,
        check_vma=False,
    )
    if mask is None:
        mask = jnp.zeros((q.shape[0], 1, 1, q.shape[2]), jnp.float32)
    return fn(q, k, v, mask)


def ring_attention_sharded(
    q, k, v, mask, mesh, axis: str = "sp", dtype=jnp.float32
):
    """Convenience wrapper: exact ring-parallel attention over ``axis``.
    Used directly in tests and by sequence-parallel model runs."""
    return sharded_attention(
        make_ring_attention(axis), q, k, v, mask, mesh, axis, dtype
    )
