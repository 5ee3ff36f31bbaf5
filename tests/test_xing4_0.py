"""The Xing4.0 family (models/xing4_0.py) on the offline embed path, at the
tiny preset with seeded random weights, against the plain reference
(benchmarks/reference/xing4_0.py): the embedding row by row through
`TextEmbedder`, with the build-time fallbacks and with the interpreted
kernels (latent attention, the grouped product, the two hyper-connection
kernels); the two kernels against the plain equations and against the
reference's coefficients; the mixes far from the identity and the uniform;
the plain-residual fault and the float8 control, each caught; and the
counters.

Tolerances. In float32 the program and the reference at `highest` do the
same arithmetic in another order: 4e-7 of the spread of the rows, held to
1e-5. In bfloat16 both round the operands of every matrix product to
bfloat16 at other points (the program rounds u once where it leaves the
pre-mix, then the norm's output; the reference each product's operands),
and a token may choose another of its 4 experts: 0.016 at the median and
0.037 at the widest, held to 0.04 and 0.1. The float8 control reads 0.12 at
the median and the plain residual 0.27, each over the bfloat16 tolerance
(tests/benchmarks/test_xing_cell.py holds them against the cell's own
limits)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmarks"))

import xing_tiny  # noqa: E402
from xing_tiny import published_config, tiny_config, write_weights  # noqa: E402

from benchmarks import compare  # noqa: E402
from benchmarks.data import texts  # noqa: E402
from benchmarks.reference import xing4_0 as reference  # noqa: E402
from benchmarks.reference.jamba import from_bits, tokenize  # noqa: E402
from sparkdl_tpu.dataframe import DataFrame  # noqa: E402
from sparkdl_tpu.models import deepseek_v2, get_model  # noqa: E402
from sparkdl_tpu.models import xing4_0 as program  # noqa: E402
from sparkdl_tpu.ops import hyper_connection as hc  # noqa: E402
from sparkdl_tpu.ops.flash_attention import make_latent_attention_fn  # noqa: E402
from sparkdl_tpu.ops.grouped_matmul import make_grouped_matmul_fn  # noqa: E402
from sparkdl_tpu.transformers.text import TextEmbedder  # noqa: E402
from sparkdl_tpu.utils.metrics import metrics  # noqa: E402

F32 = dict(median=1e-5, widest=1e-5)
BF16 = dict(median=0.04, widest=0.1)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    config = tiny_config()
    path = str(tmp_path_factory.mktemp("xing") / "tiny.npz")
    return config, write_weights(path, config), path


@pytest.fixture(scope="module")
def corpus():
    """Twelve texts either side of the 64 edge, two of them full rows of
    256."""
    data = {
        "rows": 12, "vocabulary_words": 300,
        "word_counts": [[254, 2], [10, 2], [60, 2], [100, 2], [130, 2], [200, 2]],
    }
    return list(texts.rows(data, np.random.default_rng(0), set()))


@pytest.fixture(scope="module")
def want(tiny, corpus):
    """The reference's answers at `highest`, as stated, and in float8."""
    config, weights, _ = tiny
    return {
        p: reference.outputs(config, weights, corpus, precision=p)
        for p in ("highest", "reference", "float8")
    }


def _counters():
    return dict(metrics.scalar_snapshot()["counters"])


def _built(path, dtype, interpret):
    preset = program.xing4_0_tiny()
    if not interpret:
        return program.xing4_0_model_function("xing4.0-tiny", dtype=dtype, weights_file=path)
    return program.xing4_0_model_function(
        "xing4.0-tiny", dtype=dtype, weights_file=path,
        attention_fn=make_latent_attention_fn(
            preset.num_heads, preset.softmax_scale, block=64, interpret=True
        ),
        experts_fn=make_grouped_matmul_fn(interpret=True),
        hyper=hc.make_hyper_connection_fn(preset.hyper_constants, interpret=True),
    )


def _embed(path, inputs, dtype, interpret=False, fault=None):
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("SPARKDL_TEXT_BUCKETS", "64,256")
        patch.setenv("SPARKDL_TEXT_MIN_BUCKET", "64")
        if fault:
            fault(patch.setattr)
        mf = _built(path, dtype, interpret)
        out = TextEmbedder(
            inputCol="in", outputCol="out", modelFunction=mf, maxLength=256, batchSize=2,
        ).transform(DataFrame.fromColumns({"in": inputs}, numPartitions=2)).collect()
    return mf, np.stack([np.asarray(r["out"], np.float32) for r in out])


def test_tiny_preset_is_the_family(tiny):
    config, _, _ = tiny
    preset = program.xing4_0_tiny()
    assert reference.weight_shapes(config) == program.param_shapes(preset)
    assert (preset.expert_layers, preset.first_k_dense, preset.hc_mult) == (2, 1, 4)
    # DeepSeek's names, read by `route` and `_routed`, carry this family's keys
    assert (preset.scoring_func, preset.n_group, preset.norm_topk_prob) == ("sigmoid", 1, True)
    assert preset.hyper_constants == hc.Constants(4, 20, 1e-6, (-30.0, 30.0), 1e-6)


def test_published_preset_is_the_configuration_file():
    """Shapes only: nothing of 3.6 B parameters is made."""
    config = published_config()
    preset = program.xing4_0_29b_a4b()
    assert reference.weight_shapes(config) == program.param_shapes(preset)
    pairs = {
        "vocab_size": "vocab_size", "hidden_size": "hidden_size",
        "intermediate_size": "intermediate_size",
        "moe_intermediate_size": "moe_intermediate_size", "num_layers": "num_hidden_layers",
        "first_k_dense": "first_k_dense_replace", "num_heads": "num_attention_heads",
        "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
        "v_head_dim": "v_head_dim", "n_routed_experts": "n_routed_experts",
        "n_shared_experts": "n_shared_experts", "num_experts_per_tok": "num_experts_per_tok",
        "n_group": "n_group", "topk_group": "topk_group", "norm_topk_prob": "norm_topk_prob",
        "routed_scaling_factor": "routed_scaling_factor", "scoring_func": "scoring_func",
        "rms_norm_eps": "rms_norm_eps", "rope_theta": "rope_theta", "hc_mult": "hc_mult",
        "hc_sinkhorn_iters": "hc_sinkhorn_iters", "hc_eps": "hc_eps",
        "mhc_h_res_clamp_min": "mhc_h_res_clamp_min",
        "mhc_h_res_clamp_max": "mhc_h_res_clamp_max",
    }
    for mine, theirs in pairs.items():
        assert getattr(preset, mine) == config[theirs], mine
    scaling = config["rope_scaling"]
    assert (preset.rope_factor, preset.rope_mscale, preset.rope_mscale_all_dim) == (
        scaling["factor"], scaling["mscale"], scaling["mscale_all_dim"]
    )
    assert preset.rope_original_max_position == scaling["original_max_position_embeddings"]
    assert preset.experts_held == tuple(config["experts_held"]) == (0, 64)
    # the uncut model's defaults are the published config's
    whole = program.Xing4Config()
    assert (whole.num_layers, whole.first_k_dense) == (40, 2)
    spec = get_model("xing4.0-29b-a4b")
    assert (spec.feature_dim, spec.vocab_size, spec.max_length) == (3584, 131072, 262144)
    assert get_model("xing4.0-tiny").feature_dim == 64
    # every expert held: the slot buffer is every slot, one body
    assert deepseek_v2.slot_capacity(preset, 16384) == 16384 * 4


@pytest.mark.parametrize(
    "dtype, precision, interpret, tol",
    [
        (jnp.float32, "highest", False, F32),
        (jnp.float32, "highest", True, F32),
        (jnp.bfloat16, "reference", False, BF16),
        (jnp.bfloat16, "reference", True, BF16),
    ],
)
def test_embedder_matches_the_reference_row_by_row(tiny, corpus, want, dtype, precision, interpret, tol):
    _, _, path = tiny
    before = _counters()
    mf, got = _embed(path, corpus, dtype, interpret)
    kinds = ("flash", "pallas", "pallas") if interpret else ("dense", "ragged_dot", "xla")
    assert (mf.attention, mf.experts, mf.residual) == kinds
    assert mf.weights_as_arguments
    assert got.shape == (12, 64)  # the columns of counts are stripped
    errs = compare.row_errors(got, want[precision])
    assert np.median(errs) <= tol["median"] and errs.max() <= tol["widest"], errs
    assert compare.rows_mismatched(got, want[precision]) == 0
    delta = {k: v - before.get(k, 0) for k, v in _counters().items()}
    lengths = [len(reference.tokenize(t, 512, 256)) for t in corpus]
    dispatched = delta["mla.attention_tokens"] // 3
    assert dispatched >= sum(lengths) and dispatched % 64 == 0
    # two mixes a layer, three layers
    assert delta["mhc.tokens"] == 6 * dispatched
    # every expert held: every real token's 4 slots in 2 expert layers
    assert delta["moe.slots_routed"] == delta["moe.slots_held"] == sum(lengths) * 4 * 2
    assert delta.get("moe.buffer_sized", 0) == 0
    assert delta["moe.buffer_full"] == 2 * len(corpus)


@pytest.mark.parametrize("fault", xing_tiny.FAULTS)
def test_a_planted_fault_fails_the_tolerance(tiny, corpus, want, fault):
    _, _, path = tiny
    _, got = _embed(path, corpus, jnp.bfloat16, fault=getattr(xing_tiny, fault))
    errs = compare.row_errors(got, want["reference"])
    assert np.median(errs) > 3 * BF16["median"], (fault, np.median(errs))


def test_the_float8_control_fails_the_tolerance(want):
    errs = compare.row_errors(want["float8"], want["reference"])
    assert np.median(errs) > 2 * BF16["median"] and errs.max() > BF16["widest"], errs


# -- the two kernels ---------------------------------------------------------


@pytest.fixture(scope="module")
def streams(tiny, corpus):
    """Layer 0's attention mix of the reference's weights, and the four
    streams as the program starts them for two rows of the corpus: each
    the word embedding (bfloat16-exact values in float32), every token's
    stream a row of [T, n C]; the second stream's copy moved by a seeded
    perturbation so that the streams differ, as they do after a layer."""
    config, weights, _ = tiny
    ids = np.zeros((2, 128), np.int32)
    for r, text in enumerate(corpus[:2]):
        row = tokenize(text, 512, 128)
        ids[r, : len(row)] = row
    x = np.asarray(from_bits(weights["embed"]), np.float32)[ids]  # [2, 128, 64]
    X = np.repeat(x[:, :, None], 4, 2)
    X = X + 0.3 * np.random.default_rng(5).standard_normal(X.shape).astype(np.float32)
    w = {
        k: np.asarray(from_bits(weights[f"layers/0/hc_attn/{k}"]), np.float32)
        for k in ("phi", "bias", "alpha")
    }
    return config, X, w


def _halves(hyper, X, w, dtype):
    T = X.shape[0] * X.shape[1]
    flat = jnp.asarray(X.reshape(T, -1))
    u, post, res = hyper.pre(flat, w["phi"], w["bias"], w["alpha"], dtype)
    f = jnp.asarray(np.random.default_rng(9).standard_normal((T, X.shape[-1])), jnp.float32)
    return u, post, res, f, hyper.post(flat, f, post, res)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernels_are_the_equations(streams, dtype):
    """`hc_pre` and `hc_post` interpreted against the plain jax.numpy path:
    the coefficients and the new stream within float32 rounding, u within
    one rounding of its dtype."""
    config, X, w = streams
    k = program.xing4_0_tiny().hyper_constants
    kernel = _halves(hc.make_hyper_connection_fn(k, interpret=True), X, w, dtype)
    plain = _halves(hc.make_hyper_connection_fn(k), X, w, dtype)
    assert hc.make_hyper_connection_fn(k).kind == "xla"
    for name, a, b, tol in zip(
        ("u", "h_post", "h_res", "f", "x'"), kernel, plain,
        (jnp.finfo(dtype).eps * 8, 1e-6, 1e-6, 0, 1e-5),
    ):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        assert err <= tol, (name, err)


def test_the_kernels_give_the_references_coefficients(streams):
    """Against the reference's `hc_pre` (its 20 Sinkhorn steps written out)
    and `hc_post`: H_pre's mix u, H_post and H_res within 1e-5, the new
    streams within 1e-5 of their spread."""
    config, X, w = streams
    k = program.xing4_0_tiny().hyper_constants
    u, post, res, f, new = _halves(hc.make_hyper_connection_fn(k, interpret=True), X, w, jnp.float32)
    ru, rpost, rres = reference.hc_pre(config, {k: jnp.asarray(v) for k, v in w.items()}, jnp.asarray(X))
    T = X.shape[0] * X.shape[1]
    np.testing.assert_allclose(np.asarray(post), np.asarray(rpost).reshape(T, 4), atol=1e-5)
    np.testing.assert_allclose(np.asarray(res), np.asarray(rres).reshape(T, 16), atol=1e-5)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ru).reshape(T, -1), atol=1e-5 * X.std())
    rnew = reference.hc_post(jnp.asarray(X), f.reshape(X.shape[0], X.shape[1], -1), rpost, rres)
    np.testing.assert_allclose(np.asarray(new), np.asarray(rnew).reshape(T, -1), atol=1e-5 * X.std())


def test_h_res_is_doubly_stochastic_and_ten_steps_are_not_enough(streams):
    """After the 20 steps every column sums to 1 within 1e-5 (the last
    divide is the columns') and the median token's rows do too; after 10
    the median token's rows are 3e-4 off: a Sinkhorn cut to 10 steps fails
    the same bound, and its mix differs from the 20 steps' by more than
    the kernels' 1e-5."""
    config, X, w = streams
    k = program.xing4_0_tiny().hyper_constants
    T = X.shape[0] * X.shape[1]

    def mix(iters):
        hyper = hc.make_hyper_connection_fn(dataclasses.replace(k, iters=iters), interpret=True)
        return np.asarray(_halves(hyper, X, w, jnp.float32)[2]).reshape(T, 4, 4)

    def off(M):
        rows, cols = np.abs(M.sum(2) - 1).max(1), np.abs(M.sum(1) - 1).max(1)
        return np.median(rows), cols.max()

    full, ten = mix(20), mix(10)
    assert off(full)[0] < 1e-5 and off(full)[1] < 1e-5
    assert off(ten)[0] > 1e-5 and off(ten)[1] < 1e-5
    assert np.abs(full - ten).max() > 1e-4


def test_the_weights_mixes_are_far_from_the_identity_and_the_uniform(streams):
    """The reference's weights give every token a residual mix at least
    0.2 from the identity and from the uniform 1/4 in its largest entry,
    and pre- and post-mixes that vary by token (a plain residual would
    otherwise read inside the tolerance)."""
    config, X, w = streams
    _, post, M = reference.hc_pre(config, {k: jnp.asarray(v) for k, v in w.items()}, jnp.asarray(X))
    M = np.asarray(M).reshape(-1, 4, 4)
    assert np.abs(M - np.eye(4)).max(axis=(1, 2)).min() > 0.2
    assert np.abs(M - 0.25).max(axis=(1, 2)).min() > 0.1
    assert np.asarray(post).reshape(-1, 4).std(0).min() > 0.05
    alpha = w["alpha"]
    assert 0.5 <= alpha.min() and alpha.max() <= 1.5
    assert np.var(w["phi"]) * w["phi"].shape[0] == pytest.approx(1.0, rel=0.1)
    assert np.var(w["bias"]) == pytest.approx(1.0, abs=0.6)


def test_the_program_calls_each_half_twice_a_layer():
    """The pre-mix and post-mix of every sublayer, in order, each with its
    own weights; the MTP block is not built."""
    calls = []

    class Recording:
        kind = "recording"

        def pre(self, x, phi, bias, alpha, dtype):
            calls.append(("pre", phi.shape))
            T, width = x.shape
            return x[:, : width // 4].astype(dtype), jnp.ones((T, 4)), jnp.ones((T, 16)) / 4

        def post(self, x, f, h_post, h_res):
            calls.append(("post", f.shape))
            return x + jnp.tile(f, (1, 4))

    mf = program.xing4_0_model_function("xing4.0-tiny", hyper=Recording())
    jax.jit(mf.fn).lower(mf.params, jnp.ones((1, 64), jnp.int32))
    assert [c[0] for c in calls] == ["pre", "post"] * 6
    assert {c[1] for c in calls[::2]} == {(256, 24)} and {c[1] for c in calls[1::2]} == {(64, 64)}
    assert mf.residual == "recording"
    assert not any("nextn" in p or "mtp" in p for p in program.param_shapes(program.xing4_0_tiny()))
    assert mf.dispatched_token_counters == {"mla.attention_tokens": 3, "mhc.tokens": 6}
    assert mf.row_counters == ("moe.slots_held", "moe.buffer_sized", "moe.buffer_full")
