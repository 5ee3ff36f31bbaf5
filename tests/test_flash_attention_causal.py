"""Causal masking and shared key/value heads in the flash kernel
(ops/flash_attention.py), through the Pallas interpreter, against dense
attention written out here; and the bidirectional, equal-heads kernel
left as it was.

Tolerance: kernel and oracle both keep scores and softmax in float32; the
kernel's online softmax rescales its running sum once a key block, a few
float32 roundings of values of order 1: 2e-5, as test_flash_attention.py
holds the bidirectional kernel to."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models.bert import dense_attention
from sparkdl_tpu.ops.flash_attention import (
    NEG_INF,
    dense_causal_attention,
    flash_attention,
    make_flash_attention_fn,
)

TOL = dict(atol=2e-5, rtol=2e-5)


def qkv(seed, B, H, Hkv, L, Dh, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(B, H, L, Dh)), dtype)
    k = jnp.asarray(r.normal(size=(B, Hkv, L, Dh)), dtype)
    v = jnp.asarray(r.normal(size=(B, Hkv, L, Dh)), dtype)
    return q, k, v


def dense(q, k, v, causal, mask=None):
    """Every query head against its own copy of its key/value head."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    L = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")
    s = s / np.sqrt(q.shape[-1])
    if mask is not None:
        s = s + mask[:, None, None, :]
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v, precision="highest"
    )


@pytest.mark.parametrize(
    "H, Hkv, L, block",
    [
        (4, 1, 256, 128),  # one shared head, two blocks: one skipped
        (4, 1, 200, 128),  # the sequence padded up to the block
        (4, 2, 192, 64),  # two groups of two; three blocks, three skipped
        (2, 2, 128, 32),  # causal alone, heads equal
    ],
)
def test_causal_and_shared_head_match_dense(H, Hkv, L, block):
    q, k, v = qkv(L + H, 2, H, Hkv, L, 32)
    got = flash_attention(
        q, k, v, block_q=block, block_k=block, interpret=True, causal=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense(q, k, v, True)), **TOL
    )


def test_shared_head_without_causal():
    q, k, v = qkv(1, 2, 6, 2, 96, 16)
    mask = np.zeros((2, 96), np.float32)
    mask[0, 70:] = NEG_INF
    mask = jnp.asarray(mask)
    got = flash_attention(q, k, v, mask, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense(q, k, v, False, mask)), **TOL
    )


def test_causal_with_a_key_mask():
    q, k, v = qkv(2, 2, 4, 1, 128, 32)
    mask = np.zeros((2, 128), np.float32)
    mask[1, 90:] = NEG_INF
    mask = jnp.asarray(mask)
    got = flash_attention(
        q, k, v, mask, block_q=64, block_k=64, interpret=True, causal=True
    )
    want = dense(q, k, v, True, mask)
    # a query past the mask still sees itself in the oracle's -inf form
    # and not in the kernel's large-negative one: compare the real ones
    np.testing.assert_allclose(
        np.asarray(got[1, :, :90]), np.asarray(want[1, :, :90]), **TOL
    )
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), **TOL)


def test_a_real_token_never_sees_a_later_pad():
    """Right padding needs no mask in a causal stack: what the real
    positions get does not depend on what lies after them."""
    q, k, v = qkv(3, 1, 4, 1, 128, 32)
    cut = 77
    k2 = jnp.concatenate([k[:, :, :cut], 50.0 + 0 * k[:, :, cut:]], axis=2)
    v2 = jnp.concatenate([v[:, :, :cut], 3 * v[:, :, cut:]], axis=2)
    kw = dict(block_q=64, block_k=64, interpret=True, causal=True)
    one = np.asarray(flash_attention(q, k, v, **kw))
    two = np.asarray(flash_attention(q, k2, v2, **kw))
    np.testing.assert_array_equal(one[:, :, :cut], two[:, :, :cut])
    assert np.abs(one[:, :, cut:] - two[:, :, cut:]).max() > 0.1


def test_dense_causal_fallback_is_the_same_function():
    q, k, v = qkv(4, 2, 4, 1, 64, 16)
    got = dense_causal_attention(q, k, v, None, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense(q, k, v, True)), **TOL
    )
    assert dense_causal_attention.kind == "dense"
    assert make_flash_attention_fn(causal=True) is dense_causal_attention
    fn = make_flash_attention_fn(block_q=32, block_k=32, interpret=True, causal=True)
    assert fn.kind == "flash"
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v, None, jnp.float32)), np.asarray(got), **TOL
    )


def test_what_the_causal_kernel_cannot_do_is_refused():
    q, k, v = qkv(5, 1, 4, 3, 64, 16)
    with pytest.raises(ValueError, match="4 query heads over 3"):
        flash_attention(q, k, v, interpret=True)
    q, k, v = qkv(5, 1, 2, 2, 64, 16)
    with pytest.raises(ValueError, match="square blocks"):
        flash_attention(q, k, v, block_q=32, block_k=64, interpret=True, causal=True)


def _kernel_jaxpr(**kw):
    q, k, v = qkv(6, 2, 4, kw.pop("Hkv", 4), 128, 32)
    mask = jnp.zeros((2, 128), jnp.float32)
    return str(
        jax.make_jaxpr(
            lambda q, k, v, m: flash_attention(q, k, v, m, block_q=64, block_k=64, **kw)
        )(q, k, v, mask)
    )


def test_bidirectional_equal_heads_kernel_is_untouched():
    """With `causal=False` and equal head counts the kernel traces to the
    program it was before either existed: no position arithmetic, no
    branch around the block, the plain index maps; and it still agrees
    with dense attention under a key mask. (That this jaxpr is to the
    letter the parent commit's was checked when the option was added:
    PERF.md, Findings, PR 28.)"""
    plain = _kernel_jaxpr()
    assert plain == _kernel_jaxpr(causal=False)
    assert "iota" not in plain and "min" not in plain.replace("reduce_min", "")
    # one branch each for the first and the last key block, none around
    # the block itself
    assert plain.count("cond[") == 2
    causal = _kernel_jaxpr(causal=True)
    assert "iota" in causal and causal.count("cond[") == 3
    assert causal != plain
    shared = _kernel_jaxpr(Hkv=1)
    assert shared != plain and "iota" not in shared

    q, k, v = qkv(7, 2, 4, 4, 96, 32)
    mask = np.zeros((2, 96), np.float32)
    mask[1, 60:] = NEG_INF
    mask = jnp.asarray(mask)
    got = flash_attention(q, k, v, mask, block_q=32, block_k=32, interpret=True)
    want = dense_attention(q, k, v, mask[:, None, None, :], jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
