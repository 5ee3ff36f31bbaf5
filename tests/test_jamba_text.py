"""The Jamba family (models/jamba.py) on the offline embed path, at the
tiny preset with seeded random weights, against the plain reference
(benchmarks/reference/jamba.py) row by row.

Tolerances. In float32 the program and the reference at `highest` do the
same arithmetic in another order (a chunked scan against a token-by-token
one, dense attention both): errors of 1e-6 of the spread of the rows,
held to 1e-5. In bfloat16 both round the operands of every matrix product
to bfloat16 and accumulate in float32, and a sum taken in another order
now and then rounds an activation the other way: most rows agree to the
bit, the widest reads 0.0035, held to 0.01. The same reference with float8
operands reads 0.18 at the median and with a bfloat16 state 0.008
(tests/benchmarks/test_jamba_cell.py holds those)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmarks"))

from jamba_tiny import tiny_config, write_weights  # noqa: E402

from benchmarks import compare  # noqa: E402
from benchmarks.data import texts  # noqa: E402
from benchmarks.reference import jamba as reference  # noqa: E402
from sparkdl_tpu.dataframe import DataFrame  # noqa: E402
from sparkdl_tpu.models import get_model  # noqa: E402
from sparkdl_tpu.models import jamba  # noqa: E402
from sparkdl_tpu.ops.flash_attention import make_flash_attention_fn  # noqa: E402
from sparkdl_tpu.ops.selective_scan import make_selective_scan_fn  # noqa: E402
from sparkdl_tpu.transformers.text import TextEmbedder  # noqa: E402
from sparkdl_tpu.utils.metrics import metrics  # noqa: E402


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    config = tiny_config()
    path = str(tmp_path_factory.mktemp("jamba") / "tiny.npz")
    return config, write_weights(path, config), path


@pytest.fixture(scope="module")
def corpus():
    """Twelve texts whose token counts fall either side of the 128 edge,
    two of them full windows of 256."""
    data = {
        "rows": 12, "vocabulary_words": 300,
        "word_counts": [[254, 2], [10, 2], [60, 2], [100, 2], [130, 2], [200, 2]],
    }
    return list(texts.rows(data, np.random.default_rng(0), set()))


def _counters():
    return dict(metrics.scalar_snapshot()["counters"])


def test_tiny_preset_is_the_family(tiny):
    config, _, _ = tiny
    preset = jamba.jamba_tiny()
    assert reference.weight_shapes(config) == jamba.param_shapes(preset)
    kinds = [preset.is_attention(i) for i in range(preset.num_layers)]
    assert kinds == [False, False, True, False, False]
    assert (preset.d_state, preset.d_conv, preset.num_kv_heads) == (16, 4, 1)
    assert preset.scan_layers == 4


def test_published_preset_is_the_catalog_row():
    """Shapes only: nothing of 3 B parameters is made."""
    preset = jamba.jamba2_3b()
    shapes = jamba.param_shapes(preset)
    assert sum(int(np.prod(s)) for s in shapes.values()) == pytest.approx(3.03e9, rel=2e-3)
    assert [i for i in range(28) if preset.is_attention(i)] == [7, 21]
    assert preset.scan_layers == 26 and preset.d_inner == 5120
    spec = get_model("jamba2-3b")
    assert (spec.vocab_size, spec.feature_dim, spec.max_length) == (65536, 2560, 262144)
    with pytest.raises(ValueError, match="Unknown text-model mode"):
        spec.model_function(mode="generate")


@pytest.mark.parametrize(
    "dtype, precision, widest",
    [(jnp.float32, "highest", 1e-5), (jnp.bfloat16, "reference", 1e-2)],
)
def test_embedder_matches_the_reference_row_by_row(
    monkeypatch, tiny, corpus, dtype, precision, widest
):
    config, weights, path = tiny
    monkeypatch.setenv("SPARKDL_TEXT_BUCKETS", "128,256")
    monkeypatch.setenv("SPARKDL_TEXT_MIN_BUCKET", "128")
    mf = get_model("jamba-tiny").model_function(
        mode="embed", dtype=dtype, weights_file=path
    )
    assert (mf.attention, mf.scan, mf.vocab_size) == ("dense", "jnp", 512)
    rows = corpus[:5] + [None] + corpus[5:]
    df = DataFrame.fromColumns({"in": rows}, numPartitions=2)
    before = _counters()
    out = TextEmbedder(
        inputCol="in", outputCol="out", modelFunction=mf, maxLength=256,
        batchSize=4,
    ).transform(df).collect()
    delta = {k: v - before.get(k, 0) for k, v in _counters().items()}
    assert out[5]["out"] is None
    got = np.stack([np.asarray(r["out"], np.float32) for r in out if r["out"] is not None])
    assert got.shape == (12, 64)
    ref = reference.outputs(config, weights, corpus, precision=precision)
    errs = compare.row_errors(got, ref)
    assert errs.max() < widest, errs
    assert compare.rows_mismatched(got, ref) == 0
    # mixed lengths went through both buckets, and every dispatched token
    # (the rows that fill a batch of 4 and the pad tokens too) was scanned
    # once by each of the 4 Mamba layers
    short, full = delta["text.bucket_rows.128"], delta["text.bucket_rows.256"]
    assert short == 6 and full == 6
    scanned = delta["ssm.scan_tokens"]
    assert scanned >= 4 * (128 * short + 256 * full)
    assert scanned % (4 * 4 * 128) == 0
    assert scanned <= 4 * 4 * (128 + 256) * 3  # at most a batch more a bucket and partition


def test_right_padding_changes_nothing(tiny):
    """No mask inside the causal stack: the mask only says where a row
    ends, and the embedding is the state there, whatever follows."""
    config, _, path = tiny
    mf = jamba.jamba_model_function("jamba-tiny", weights_file=path)
    ids = np.zeros((2, 64), np.int32)
    ids[0, :40] = np.arange(4, 44)
    ids[1, :64] = np.arange(100, 164)
    wide = np.zeros((2, 128), np.int32)
    wide[:, :64] = ids
    params = jax.tree.map(jnp.asarray, mf.params)
    a, b = (np.asarray(jax.jit(mf.fn)(params, x)) for x in (ids, wide))
    np.testing.assert_allclose(a, b, atol=1e-5)
    # and it is the last real token's state, not the bucket's last position
    cut = np.array(ids)
    cut[0, 39] = 0
    c = np.asarray(jax.jit(mf.fn)(params, cut))
    assert np.abs(c[0] - a[0]).max() > 1e-2
    np.testing.assert_allclose(c[1], a[1], atol=1e-5)


def test_the_kernels_inside_the_model(tiny):
    """The model built over the Pallas kernels (interpreted) gives what
    the model built over plain jax.numpy gives: a sequence of one and a
    half chunks of the scan, three blocks of attention."""
    _, _, path = tiny
    plain = jamba.jamba_model_function("jamba-tiny", weights_file=path)
    kernels = jamba.jamba_model_function(
        "jamba-tiny",
        weights_file=path,
        attention_fn=make_flash_attention_fn(
            block_q=64, block_k=64, interpret=True, causal=True
        ),
        scan_fn=make_selective_scan_fn(interpret=True, block_d=128, out_dtype=jnp.float32),
    )
    assert (kernels.attention, kernels.scan) == ("flash", "pallas")
    ids = np.random.default_rng(1).integers(4, 512, (2, 192)).astype(np.int32)
    ids[1, 150:] = 0
    params = jax.tree.map(jnp.asarray, plain.params)
    want = np.asarray(jax.jit(plain.fn)(params, ids))
    got = np.asarray(jax.jit(kernels.fn)(params, ids))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_weights_file_is_read_strictly(tmp_path, tiny):
    config, weights, path = tiny
    preset = jamba.jamba_tiny()
    loaded = jamba.load_params(preset, path, jnp.bfloat16)
    leaf = loaded["layers"]["0"]["mlp"]["gate"]
    assert leaf.dtype == jnp.bfloat16  # the uint16 bit patterns, viewed
    np.testing.assert_array_equal(
        np.asarray(leaf, np.float32),
        np.asarray(reference.from_bits(weights["layers/0/mlp/gate"]), np.float32),
    )
    assert loaded["layers"]["0"]["mamba"]["A_log"].dtype == jnp.float32
    flat = {k: np.asarray(v) for k, v in weights.items()}
    flat.pop("layers/1/mamba/D")
    np.savez(tmp_path / "short.npz", **flat)
    with pytest.raises(ValueError, match="lacks 1 leaves"):
        jamba.load_params(preset, str(tmp_path / "short.npz"), jnp.float32)
    flat["layers/1/mamba/D"] = np.zeros((3,), np.uint16)
    np.savez(tmp_path / "bent.npz", **flat)
    with pytest.raises(ValueError, match=r"layers/1/mamba/D is \(3,\)"):
        jamba.load_params(preset, str(tmp_path / "bent.npz"), jnp.float32)
    with pytest.raises(ValueError, match="Unknown Jamba size"):
        jamba.jamba_model_function("jamba-huge")
