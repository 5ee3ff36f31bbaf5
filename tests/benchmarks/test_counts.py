"""The benchmark's own operation counts against XLA's `cost_analysis()` of
the plain reference's forward pass, lowered for the CPU at the published
widths from shapes alone. A stale count cannot pass as a gain later."""

import functools
import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.counts import bert as bert_counts  # noqa: E402
from benchmarks.reference import bert as bert_ref  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", f"{name}.json")) as f:
        return json.load(f)


def _xla_flops(reference, config, x):
    # shapes only: the published widths, no 400 MB of weights
    shapes = {
        k: jax.ShapeDtypeStruct(shape, np.float32)
        for k, shape in reference.weight_shapes(config).items()
    }
    lowered = jax.jit(
        functools.partial(reference._forward, config), static_argnums=(2,)
    ).lower(shapes, x, "reference")
    cost = lowered.cost_analysis()
    return float((cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"])


@pytest.mark.parametrize("reference, name", [(bert_ref, "bert-base")])
def test_weight_shapes_are_the_weights_made(reference, name):
    config = dict(_config(name))
    # the same code at a size a test can make
    config.update(vocab_size=64, hidden_size=16, intermediate_size=32,
                  num_hidden_layers=2, max_position_embeddings=8)
    made = reference.make_weights(config, 1)
    assert {k: v.shape for k, v in made.items()} == reference.weight_shapes(config)
    assert all(v.dtype == np.float32 for v in made.values())
    again = reference.make_weights(config, 1)
    assert all((made[k] == again[k]).all() for k in made)
    other = reference.make_weights(config, 2)
    assert any((made[k] != other[k]).any() for k in made)


@pytest.mark.parametrize("length", [128, 256, 512])
def test_bert_base_flops_per_row(length):
    config = _config("bert-base")
    ours = bert_counts.flops_per_row(config, length)
    if length == 512:
        # about 190 MFLOP a token at 512 positions
        assert ours / length == pytest.approx(190e6, rel=0.02)
    rows = 2
    x = jax.ShapeDtypeStruct((rows, length), np.int32)
    xla = _xla_flops(bert_ref, config, x)
    # XLA also counts softmax, GELU, norms and the pooling: under 5%
    assert ours * rows == pytest.approx(xla, rel=0.05)
    assert ours * rows <= xla


def test_bert_work_by_length_and_kernel_work():
    config = _config("bert-base")
    work = {"rows": 5, "rows_by_length": {"128": 3, "512": 2}}
    assert bert_counts.forward_flops(config, work) == pytest.approx(
        3 * bert_counts.flops_per_row(config, 128)
        + 2 * bert_counts.flops_per_row(config, 512)
    )
    flops, bytes_ = bert_counts.kernel_work(config, "flash_attention", work)
    layers, h = 12, 768
    assert flops == pytest.approx(
        layers * 4 * h * (3 * 128**2 + 2 * 512**2)
    )
    assert bytes_ == pytest.approx(layers * 4 * h * 4 * (3 * 128 + 2 * 512))
    # attention's two products are part of the forward count
    assert flops < bert_counts.forward_flops(config, work)
    assert bert_counts.kernel_work(config, "other", work) is None


def test_bert_pairs_are_those_of_the_rows_real_lengths():
    """The cell's passage: 102 tokens dispatched at 128. The dense layers
    run all 128; attention's pairs are 102 x 102, not causal."""
    config = _config("bert-base")
    layers, h, f = 12, 768, 3072
    at_edges = {"rows": 7, "rows_by_length": {"128": 7}}
    work = dict(at_edges, lengths_by_edge={"128": {102: 5, 128: 2}})
    dense = 7 * 128 * (4 * h * h + 2 * h * f)
    pairs = 5 * 102**2 + 2 * 128**2
    assert bert_counts.forward_flops(config, work) == pytest.approx(
        2 * layers * (dense + 2 * pairs * h)
    )
    flops, bytes_ = bert_counts.kernel_work(config, "flash_attention", work)
    assert flops == pytest.approx(layers * 4 * h * pairs)
    assert bytes_ == pytest.approx(layers * 4 * h * 4 * 7 * 128)  # a dispatched token
    # the kernel stays bound by its bytes at 102 of 128, as at the edge
    assert bytes_ / 819e9 > flops / 197e12
    # without real lengths the rows are as long as their edge
    full = dict(at_edges, lengths_by_edge={"128": {128: 7}})
    assert bert_counts.forward_flops(config, at_edges) == bert_counts.forward_flops(config, full)
    assert bert_counts.flops_per_row(config, 128, 128) == bert_counts.flops_per_row(config, 128)
    # scores are under 3% of the count at 128, so 102 moves it by about 1%
    all_102 = dict(at_edges, lengths_by_edge={"128": {102: 7}})
    ratio = bert_counts.forward_flops(config, all_102) / bert_counts.forward_flops(config, at_edges)
    assert 0.985 < ratio < 0.995
    unknown = dict(at_edges, pairs_unknown="text.tokens disagrees")
    assert bert_counts.forward_flops(config, unknown) is None
    assert bert_counts.kernel_work(config, "flash_attention", unknown) is None
