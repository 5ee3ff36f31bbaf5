"""The grouped matrix product (`ops/grouped_matmul.py`) under the Pallas
interpreter against a loop over the groups: empty groups, one group that
holds everything, rows past the last group, sizes that are no tile
multiple, a contraction in several steps; and the plain form."""

import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops import grouped_matmul as gm


def _loop(lhs, rhs, sizes):
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    start = 0
    for g, size in enumerate(sizes):
        out[start : start + size] = lhs[start : start + size] @ rhs[g]
        start += size
    return out, start


def _case(m, k, n, sizes, seed=0):
    rng = np.random.default_rng(seed)
    lhs = rng.standard_normal((m, k)).astype(np.float32)
    rhs = rng.standard_normal((len(sizes), k, n)).astype(np.float32)
    return lhs, rhs, np.asarray(sizes, np.int32)


CASES = {
    "even": (64, 32, 128, [16, 16, 16, 16], (16, 32, 128)),
    "rows_past_the_last_group": (96, 32, 128, [16, 20, 9, 3], (16, 32, 128)),
    "empty_groups": (64, 32, 128, [0, 37, 0, 0, 11, 0], (16, 32, 128)),
    "all_empty": (32, 32, 128, [0, 0, 0], (16, 32, 128)),
    "one_group_holds_everything": (64, 32, 128, [0, 64, 0], (16, 32, 128)),
    "no_tile_multiple": (75, 24, 256, [7, 1, 30, 19, 5], (16, 24, 128)),
    "several_k_steps": (64, 256, 256, [5, 40, 3, 16], (32, 128, 128)),
    "default_tiling": (300, 40, 384, [100, 0, 150, 40], None),
    # DeepSeek-V3.2's down product at an eighth: N = 7 x 128 lanes has no
    # power-of-two column tile, and the one chosen is 7 lane tiles wide
    "column_tile_of_seven_lane_tiles": (48, 64, 1792, [20, 0, 25], (16, 64, 896)),
    "seven_lane_tiles_by_default": (272, 256, 896, [100, 3, 150], None),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_interpreted_kernel_against_a_loop_over_groups(name):
    m, k, n, sizes, tiling = CASES[name]
    lhs, rhs, sizes = _case(m, k, n, sizes)
    want, covered = _loop(lhs, rhs, sizes)
    got = gm.grouped_matmul(
        jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(sizes),
        tiling=tiling, interpret=True,
    )
    assert got.shape == (m, n) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got)[:covered], want[:covered], rtol=1e-5, atol=1e-4
    )


def test_bfloat16_rows_accumulate_in_float32():
    lhs, rhs, sizes = _case(64, 32, 128, [10, 30, 24])
    lhs16, rhs16 = jnp.asarray(lhs, jnp.bfloat16), jnp.asarray(rhs, jnp.bfloat16)
    want, _ = _loop(
        np.asarray(lhs16, np.float32), np.asarray(rhs16, np.float32), sizes
    )
    got = gm.grouped_matmul(lhs16, rhs16, jnp.asarray(sizes), interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)


def test_metadata_visits_a_shared_tile_once_a_group():
    sizes = jnp.asarray([5, 0, 30, 1, 12], jnp.int32)  # tiles of 16: 0 | 0,1,2 | 2 | 2
    offsets, group_ids, tile_ids, steps = gm.group_metadata(sizes, 64, 16)
    steps = int(steps)
    assert offsets.tolist() == [0, 5, 5, 35, 36, 48]
    assert steps == 6
    assert group_ids[:steps].tolist() == [0, 2, 2, 2, 3, 4]
    assert tile_ids[:steps].tolist() == [0, 0, 1, 2, 2, 2]


def test_tiling_of_an_expert_layer_keeps_the_contraction_whole():
    # gate and up: [slots, 5120] x [40, 5120, 1536]; down: x [40, 1536, 5120]
    assert gm.choose_tiling(98304, 5120, 1536) == (256, 5120, 768)
    assert gm.choose_tiling(98304, 1536, 5120) == (256, 1536, 2560)
    assert gm.choose_tiling(40, 24, 64) == (48, 24, 64)


@pytest.mark.parametrize(
    "m, k, n, tiling",
    [
        # DeepSeek-V3.2, hidden 7,168 and experts of 2,048: the sized
        # buffer of a 16,384-token dispatch and a pass of the worst case
        (5120, 7168, 2048, (256, 7168, 512)),
        (16384, 7168, 2048, (256, 7168, 512)),
        (5120, 2048, 7168, (256, 2048, 1792)),  # 7,168 = 4 x 1,792 = 4 x 14 x 128
        (2560, 2048, 7168, (256, 2048, 1792)),
        (272, 256, 896, (256, 256, 896)),
    ],
)
def test_tiling_at_the_widths_of_the_second_expert_family(m, k, n, tiling):
    tm, tk, tn = gm.choose_tiling(m, k, n)
    assert (tm, tk, tn) == tiling
    assert k % tk == 0 and n % tn == 0 and tn % 128 == 0
    assert tk * tn * 2 <= gm._RHS_BLOCK_BYTES  # one weight block, bfloat16


def test_plain_form_and_the_build_time_choice():
    lhs, rhs, sizes = _case(48, 16, 32, [10, 0, 30])
    want, covered = _loop(lhs, rhs, sizes)
    plain = gm.make_grouped_matmul_fn()
    assert plain.kind == "ragged_dot"  # the tests run on the CPU
    got = plain(jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(got)[:covered], want[:covered], rtol=1e-5, atol=1e-4)
    assert not np.asarray(got)[covered:].any()
    kernel = gm.make_grouped_matmul_fn(interpret=True)
    assert kernel.kind == "pallas"
    got = kernel(jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(got)[:covered], want[:covered], rtol=1e-5, atol=1e-4)
