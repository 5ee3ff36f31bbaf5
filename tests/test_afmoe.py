"""The AFMoE family (models/afmoe.py, Trinity) on the offline embed path, at
the tiny preset with seeded random weights, against the plain reference
(benchmarks/reference/afmoe.py): the embedding row by row through
`TextEmbedder`, with the build-time fallbacks and with the interpreted
kernels (the window kernel on the sliding layers, the causal one on the
full layer, the grouped product); five faults planted in the program and
the float8 control, each caught; the gate by hand; one routed body and
no conditional where every expert is held; the rows' lengths handed to
the attentions that take them; and the counters, the query blocks at
the cell's own traffic among them.

Tolerances. In float32 the program and the reference at `highest` do the
same arithmetic in another order: 3e-7 of the spread of the rows, held
to 1e-5. In bfloat16 both round the operands of every matrix product to
bfloat16 at other points (the program rounds q, k and v once after their
norms and rotary, the reference each product's operands), and a token may
choose another of its 4 experts: 0.017 at the median and 0.067 at the
widest, held to 0.04 and 0.12. The float8 control reads 0.15 at the
median and every planted fault 0.137 or more, each over the bfloat16
tolerance (tests/benchmarks/test_trinity_cell.py holds them against the
cell's own limits)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmarks"))

import afmoe_tiny  # noqa: E402
from afmoe_tiny import published_config, tiny_config, write_weights  # noqa: E402

from benchmarks import compare  # noqa: E402
from benchmarks.data import texts  # noqa: E402
from benchmarks.reference import afmoe as reference  # noqa: E402
from sparkdl_tpu.dataframe import DataFrame  # noqa: E402
from sparkdl_tpu.models import afmoe as program  # noqa: E402
from sparkdl_tpu.models import deepseek_v2  # noqa: E402
from sparkdl_tpu.models import get_model  # noqa: E402
from sparkdl_tpu.ops.flash_attention import make_flash_attention_fn  # noqa: E402
from sparkdl_tpu.ops.grouped_matmul import make_grouped_matmul_fn  # noqa: E402
from sparkdl_tpu.transformers.text import TextEmbedder  # noqa: E402
from sparkdl_tpu.utils.metrics import metrics  # noqa: E402

F32 = dict(median=1e-5, widest=1e-5)
BF16 = dict(median=0.04, widest=0.12)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    config = tiny_config()
    path = str(tmp_path_factory.mktemp("afmoe") / "tiny.npz")
    return config, write_weights(path, config), path


@pytest.fixture(scope="module")
def corpus():
    """Twelve texts either side of the 64 edge, two of them full rows of
    256: every one longer than 14 words bands (a window of 16)."""
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
    preset = program.trinity_mini_tiny()
    if not interpret:
        return program.afmoe_model_function("trinity-mini-tiny", dtype=dtype, weights_file=path)
    return program.afmoe_model_function(
        "trinity-mini-tiny", dtype=dtype, weights_file=path,
        attention_fn=make_flash_attention_fn(16, 16, interpret=True, causal=True),
        window_attention_fn=make_flash_attention_fn(
            16, 16, interpret=True, causal=True, window=preset.sliding_window
        ),
        experts_fn=make_grouped_matmul_fn(interpret=True),
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
    preset = program.trinity_mini_tiny()
    assert reference.weight_shapes(config) == program.param_shapes(preset)
    assert [preset.is_sliding(i) for i in range(5)] == [True, True, False, True, True]
    assert (preset.sliding_layers, preset.expert_layers, preset.num_dense_layers) == (4, 4, 1)
    # DeepSeek's names, read by `route` and `_routed`, carry this family's keys
    assert (preset.n_routed_experts, preset.routed_scaling_factor) == (16, 2.826)
    assert (preset.norm_topk_prob, preset.scoring_func, preset.n_group) == (True, "sigmoid", 1)


def test_published_preset_is_the_configuration_file():
    """Shapes only: nothing of 3.8 B parameters is made."""
    config = published_config()
    preset = program.trinity_mini()
    assert reference.weight_shapes(config) == program.param_shapes(preset)
    assert list(preset.layer_types) == config["layer_types"]
    assert preset.experts_held == tuple(config["experts_held"]) == (0, 128)
    for key in (
        "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_dense_layers", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "sliding_window", "rope_theta", "num_experts",
        "num_experts_per_tok", "num_shared_experts", "n_group", "topk_group",
        "route_norm", "route_scale", "score_func", "rms_norm_eps", "mup_enabled",
    ):
        assert getattr(preset, key) == config[key], key
    # the uncut model's defaults are the published config's
    whole = program.AfmoeConfig()
    assert list(whole.layer_types) == config["published"]["layer_types"]
    assert (whole.num_hidden_layers, whole.num_dense_layers) == (32, 2)
    spec = get_model("trinity-mini")
    assert (spec.feature_dim, spec.vocab_size, spec.max_length) == (2048, 200192, 131072)
    assert get_model("trinity-mini-tiny").feature_dim == 64
    # every expert held: the slot buffer is every slot, one body
    assert deepseek_v2.slot_capacity(preset, 16384) == 16384 * 8


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
    kinds = ("flash", "flash", "pallas") if interpret else ("dense", "dense", "ragged_dot")
    assert (mf.attention, mf.window_attention, mf.experts) == kinds
    assert mf.weights_as_arguments
    assert got.shape == (12, 64)  # the columns of counts are stripped
    errs = compare.row_errors(got, want[precision])
    assert np.median(errs) <= tol["median"] and errs.max() <= tol["widest"], errs
    assert compare.rows_mismatched(got, want[precision]) == 0
    delta = {k: v - before.get(k, 0) for k, v in _counters().items()}
    lengths = [len(reference.tokenize(t, 512, 256)) for t in corpus]
    dispatched = delta["attn.full_tokens"]
    assert dispatched >= sum(lengths) and dispatched % 64 == 0
    assert delta["attn.window_tokens"] == 4 * dispatched
    # every expert held: every real token's 4 slots in 4 expert layers
    assert delta["moe.slots_routed"] == delta["moe.slots_held"] == sum(lengths) * 4 * 4
    # one buffer of every slot, no sized one: each live row counts 4 layers
    assert delta.get("moe.buffer_sized", 0) == 0
    assert delta["moe.buffer_full"] == 4 * len(corpus)
    if interpret:  # query blocks of 16 in five layers, run up to each row's length
        assert delta["attn.query_blocks"] == 5 * dispatched // 16
        assert delta["attn.query_blocks_run"] == 5 * sum(-(-n // 16) for n in lengths)
    else:  # the dense fallbacks say no block count
        assert not delta.get("attn.query_blocks") and not delta.get("attn.query_blocks_run")


@pytest.mark.parametrize("fault", afmoe_tiny.FAULTS)
def test_a_planted_fault_fails_the_tolerance(tiny, corpus, want, fault):
    _, _, path = tiny
    _, got = _embed(path, corpus, jnp.bfloat16, fault=getattr(afmoe_tiny, fault))
    errs = compare.row_errors(got, want["reference"])
    assert np.median(errs) > BF16["median"], (fault, np.median(errs))


def test_the_float8_control_fails_the_tolerance(want):
    errs = compare.row_errors(want["float8"], want["reference"])
    assert np.median(errs) > BF16["median"] and errs.max() > BF16["widest"], errs


def test_the_gate_by_hand():
    """`route`'s sigmoid gate at n_group 1: the choice on score + bias,
    the weights the chosen scores renormalised and scaled by 2.826."""
    preset = program.trinity_mini_tiny()
    r = np.random.default_rng(3)
    u = r.normal(size=(5, 64)).astype(np.float32)
    router = r.normal(size=(64, 16)).astype(np.float32) / 4
    bias = r.uniform(-0.05, 0.05, 16).astype(np.float32)
    experts, weights = deepseek_v2.route(preset, jnp.asarray(u), jnp.asarray(router), jnp.asarray(bias))
    scores = 1 / (1 + np.exp(-(u.astype(np.float64) @ router)))
    chosen = np.argsort(-(scores + bias), -1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(experts), -1), np.sort(chosen, -1))
    mine = np.take_along_axis(scores, np.asarray(experts), -1)
    np.testing.assert_allclose(
        np.asarray(weights), 2.826 * mine / mine.sum(-1, keepdims=True), rtol=1e-5
    )


def test_the_routed_layer_holds_one_body_and_no_conditional():
    """Every expert held: `_routed` builds the one buffer of every slot
    and no `lax.cond` on the load."""
    preset = program.trinity_mini_tiny()
    shapes = program.layer_shapes(preset, 1)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    moe = {
        "router": f32(*shapes["moe/router"]), "router_bias": f32(*shapes["moe/router_bias"]),
        "experts": {k: f32(*shapes[f"moe/experts/{k}"]) for k in ("gate", "up", "down")},
    }
    text = str(jax.make_jaxpr(
        lambda p, u, real: deepseek_v2._routed(preset, p, u, real, make_grouped_matmul_fn())
    )(moe, f32(2, 64, 64), jax.ShapeDtypeStruct((2, 64), jnp.bool_)))
    assert "cond[" not in text


def test_the_window_and_the_full_layer_use_their_own_kernels(monkeypatch):
    """The sliding layers call the attention built with the window, the
    full layer the one without, and only the sliding layers turn q and k."""
    calls = []

    def recording(kind):
        def fn(q, k, v, mask, dtype):
            calls.append(kind)
            return v.repeat(q.shape[1] // k.shape[1], 1).astype(dtype)

        return fn

    mf = program.afmoe_model_function(
        "trinity-mini-tiny", attention_fn=recording("full"),
        window_attention_fn=recording("window"),
    )
    jax.jit(mf.fn).lower(mf.params, jnp.ones((1, 64), jnp.int32))
    assert calls == ["window", "window", "full", "window", "window"]
    assert mf.dispatched_token_counters == {"attn.window_tokens": 4, "attn.full_tokens": 1}
    assert mf.row_counters == ("moe.slots_held", "moe.buffer_sized", "moe.buffer_full")


def _stand_in(**attributes):
    """An attention that returns its values, one a query head, and says
    what ``attributes`` say; it records the lengths it is handed."""
    seen = []

    def fn(q, k, v, mask, dtype, lengths=None):
        seen.append(lengths)
        return v.repeat(q.shape[1] // k.shape[1], 1).astype(dtype)

    fn.__dict__.update(attributes, seen=seen)
    return fn


def test_each_attention_is_handed_its_rows_lengths_where_it_takes_them():
    """The lengths (a row's last real position + 1, an id 0 inside a text
    cutting nothing short, 0 for a row of padding) reach the sliding
    layers' and the full layer's attention alike where it takes them, and
    nothing reaches one that does not."""
    window = _stand_in(takes_lengths=True)
    full = _stand_in()
    mf = program.afmoe_model_function(
        "trinity-mini-tiny", attention_fn=full, window_attention_fn=window
    )
    ids = np.zeros((3, 64), np.int32)
    ids[0, :10] = 5
    ids[1, :] = 7
    ids[1, 20] = 0
    mf.fn(mf.params, jnp.asarray(ids))
    assert len(window.seen) == 4 and full.seen == [None]
    for lengths in window.seen:
        assert np.asarray(lengths).tolist() == [10, 64, 0]
    # both take them: each of the five layers gets the same lengths
    full = _stand_in(takes_lengths=True)
    mf = program.afmoe_model_function(
        "trinity-mini-tiny", attention_fn=full, window_attention_fn=window
    )
    mf.fn(mf.params, jnp.asarray(ids))
    assert [np.asarray(n).tolist() for n in full.seen] == [[10, 64, 0]]


def _cell_dispatches():
    """The dispatches of one job of `trinity-mini-embed-long-docs`, as its
    traffic and its configuration's buckets make them: one row each, the
    live rows' tokens (words + 2) filled with a word's id."""
    from benchmarks.data import texts

    with open(os.path.join(ROOT, "benchmarks", "traffic", "embed-long-docs.json")) as f:
        data = json.load(f)["data"]
    with open(os.path.join(ROOT, "benchmarks", "configs", "trinity-mini.json")) as f:
        edges = [int(e) for e in json.load(f)["env"]["SPARKDL_TEXT_BUCKETS"].split(",")]
    tokens = texts.word_counts(data["rows"] - data["null_rows"], data["word_counts"]) + 2
    for n in tokens:
        ids = np.zeros((1, min(e for e in edges if e >= n)), np.int32)
        ids[0, :n] = 5
        yield ids


def _counted(mf, dispatches):
    total = {}
    for ids in dispatches:
        for name, count in mf.batch_counters(ids, ids != 0).items():
            total[name] = total.get(name, 0) + count
    return total


@pytest.mark.parametrize("takes_lengths", [True, False], ids=["lengths", "without"])
def test_query_blocks_of_the_cells_job(takes_lengths):
    """A job of the Trinity cell in blocks of 512 over the five layers:
    its ten rows hold 240 query blocks a layer (five of 16 at 8,192 and
    five of 32 at 16,384), 169 of them a real token. An attention that
    takes no lengths runs every block."""
    dispatches = list(_cell_dispatches())
    assert sum(int((ids != 0).sum()) for ids in dispatches) == 84223
    assert sum(ids.size for ids in dispatches) == 122880
    blocks = lambda n: -(-n // 512)  # noqa: E731
    attributes = dict(query_blocks=blocks)
    if takes_lengths:
        attributes["takes_lengths"] = True
    mf = program.afmoe_model_function(
        "trinity-mini-tiny", attention_fn=_stand_in(**attributes),
        window_attention_fn=_stand_in(**attributes),
    )
    assert _counted(mf, dispatches) == {
        "attn.query_blocks": 5 * 240,
        "attn.query_blocks_run": 5 * (169 if takes_lengths else 240),
    }


def test_the_built_kernels_count_their_query_blocks_and_the_fallbacks_none():
    """The builder's own choice: the interpreted kernels in blocks of 512
    count the cell's 5 x 169 of 5 x 240; the dense fallbacks off the TPU
    count nothing; a window kernel beside a dense full layer counts its
    four layers alone."""
    from sparkdl_tpu.ops.flash_attention import dense_causal_attention

    def built(full, window):
        return program.afmoe_model_function(
            "trinity-mini-tiny", attention_fn=full, window_attention_fn=window
        )

    kernel = make_flash_attention_fn(512, 512, interpret=True, causal=True)
    window = make_flash_attention_fn(512, 512, interpret=True, causal=True, window=16)
    dispatches = list(_cell_dispatches())
    assert _counted(built(kernel, window), dispatches) == {
        "attn.query_blocks": 5 * 240, "attn.query_blocks_run": 5 * 169,
    }
    assert _counted(program.afmoe_model_function("trinity-mini-tiny"), dispatches) == {}
    assert _counted(built(dense_causal_attention, window), dispatches) == {
        "attn.query_blocks": 4 * 240, "attn.query_blocks_run": 4 * 169,
    }
