"""The kernels of the benchmark's cells compiled at the published widths
for a v5e that is described and not attached: what the chip's compiler would
refuse (a slice off the tiling, too much fast memory) it refuses here, at
no chip time. Nothing runs, so nothing here says a result or a time.

The topology is described inside a fixture, after a test of this file has
started: only one process may load the TPU's library, and every worker
imports every test file. All such tests live in this one file."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sparkdl_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_packed,
)
from sparkdl_tpu.ops.selective_scan import selective_scan

ROWS, D_INNER, D_STATE = 8, 5120, 16  # a dispatch of the cell; Jamba2-3B
HEADS, HEAD_DIM = 20, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)


@pytest.mark.parametrize("length", [1024, 2048])
def test_selective_scan_compiles_at_the_published_widths(shape, length):
    bf16, f32 = jnp.bfloat16, jnp.float32
    wide, narrow = (ROWS, length, D_INNER), (ROWS, length, D_STATE)
    compiled = (
        jax.jit(functools.partial(selective_scan, out_dtype=bf16))
        .lower(
            shape(wide, f32), shape(wide, f32), shape(narrow, f32),
            shape(narrow, f32), shape(wide, f32), shape((D_INNER, D_STATE), f32),
            shape((D_INNER,), f32),
        )
        .compile()
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the name the trace will show: `%selective_scan.N = ... custom-call`
    assert "%selective_scan" in text
    # the state never leaves the kernel: what is written out is y, and
    # the transposed B and C
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * ROWS * length * D_STATE * 4 + (1 << 20)


def test_causal_flash_with_one_shared_head_compiles(shape):
    bf16 = jnp.bfloat16
    q = shape((ROWS, HEADS, 2048, HEAD_DIM), bf16)
    kv = shape((ROWS, 1, 2048, HEAD_DIM), bf16)
    compiled = (
        jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, block_q=512, block_k=512, causal=True
            )
        )
        .lower(q, kv, kv)
        .compile()
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the key/value head is found by the index map: nothing of the size of
    # 20 copies of it is made on the way in
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * ROWS * 2048 * HEAD_DIM * 2


@pytest.mark.parametrize("rows,length", [(2048, 128), (1024, 256), (512, 512)])
def test_packed_flash_compiles_at_bert_base_widths(shape, rows, length):
    """A dispatch of `bert-base-embed` (the 128 bucket) and as many
    tokens at the 256 and 512 buckets (two and four key blocks: the
    online softmax's scratch), float32, twelve heads of 64 side by side."""
    f32 = jnp.float32
    x = shape((rows, length, 768), f32)
    compiled = (
        jax.jit(functools.partial(flash_attention_packed, num_heads=12))
        .lower(x, x, x, shape((rows, length), f32))
        .compile()
    )
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the name the trace's readers look for
    assert "%flash_attention" in text
    # no padded, transposed or sliced copy on the way in or out: nothing
    # of the size of q is made beside the output
    assert compiled.memory_analysis().temp_size_in_bytes < rows * length * 768 * 4
