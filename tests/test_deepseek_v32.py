"""The DeepSeek-V3.2 family (models/deepseek_v32.py) on the offline embed
path, at the tiny preset with seeded random weights, against the plain
reference (benchmarks/reference/deepseek_v32.py): the embedding row by
row, the selected sets themselves, the gate by hand, a row within
`index_topk` as dense causal MLA to the bit and without an indexer in its
program, the chip's share against the uncut layer, the worst-case arm in
passes against the one buffer, and the counters.

Tolerances. In float32 the program and the reference at `highest` do the
same arithmetic in another order, and a selection that swaps two nearly
equal index scores moves a row by one key's softmax weight: 1e-6 of the
spread of the rows, held to 1e-5. In bfloat16 both round the operands of
every matrix product to bfloat16 at other points, a token may choose
another expert and a query another last key: 0.04 at the median and 0.07
at the widest with the plain forms, held to 0.06 and 0.15; the float8
control reads 0.3 (tests/benchmarks/test_deepseek_v32_cell.py holds that)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmarks"))

from deepseek_v32_tiny import published_config, tiny_config, write_weights  # noqa: E402

from benchmarks import compare  # noqa: E402
from benchmarks.data import texts  # noqa: E402
from benchmarks.reference import deepseek_v32 as reference  # noqa: E402
from sparkdl_tpu.dataframe import DataFrame  # noqa: E402
from sparkdl_tpu.models import deepseek_v2  # noqa: E402
from sparkdl_tpu.models import moe as moe_layer  # noqa: E402
from sparkdl_tpu.models import deepseek_v32 as program  # noqa: E402
from sparkdl_tpu.models import get_model  # noqa: E402
from sparkdl_tpu.ops.dsa_indexer import make_indexer_fn  # noqa: E402
from sparkdl_tpu.ops.flash_attention import make_latent_attention_fn  # noqa: E402
from sparkdl_tpu.ops.grouped_matmul import make_grouped_matmul_fn  # noqa: E402
from sparkdl_tpu.transformers.text import TextEmbedder  # noqa: E402
from sparkdl_tpu.utils.metrics import metrics  # noqa: E402


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    config = tiny_config()
    path = str(tmp_path_factory.mktemp("deepseek32") / "tiny.npz")
    return config, write_weights(path, config), path


@pytest.fixture(scope="module")
def corpus():
    """Twelve texts either side of the 64 edge, two of them full windows
    of 256: every one longer than 14 words selects (`index_topk` 16)."""
    data = {
        "rows": 12, "vocabulary_words": 300,
        "word_counts": [[254, 2], [10, 2], [60, 2], [100, 2], [130, 2], [200, 2]],
    }
    return list(texts.rows(data, np.random.default_rng(0), set()))


def _counters():
    return dict(metrics.scalar_snapshot()["counters"])


def _pairs_computed(delta, edges, interpret, lengths, layers=3):
    """`mla.pairs_computed` by hand, as `test_deepseek_v2.py` reckons it:
    the square at the bucket's edge for every dispatched row for the
    dense fallback; for the kernel, which takes lengths, the blocks of 64
    at and under the diagonal of each live row's own query blocks."""
    if interpret:
        blocks = [-(-n // 64) for n in lengths]
        return layers * sum(n * (n + 1) // 2 * 64 * 64 for n in blocks)
    low, high = edges
    rows = delta["feeder.rows"] + delta.get("feeder.pad_rows", 0)
    at_high = (delta["mla.attention_tokens"] // layers - low * rows) // (high - low)
    return layers * ((rows - at_high) * low * low + at_high * high * high)


def _built(path, dtype, interpret):
    preset = program.deepseek_v32_tiny()
    return program.deepseek_v32_model_function(
        "deepseek-v3.2-exp-tiny", dtype=dtype, weights_file=path,
        attention_fn=make_latent_attention_fn(
            preset.num_heads, preset.softmax_scale, block=64, interpret=interpret
        ),
        experts_fn=make_grouped_matmul_fn(interpret=interpret),
        indexer_fn=make_indexer_fn(
            preset.index_n_heads, preset.index_topk, interpret=interpret
        ),
    )


def _embed(path, inputs, dtype, interpret, batch=2, max_length=256):
    mf = _built(path, dtype, interpret)
    out = TextEmbedder(
        inputCol="in", outputCol="out", modelFunction=mf, maxLength=max_length,
        batchSize=batch,
    ).transform(DataFrame.fromColumns({"in": inputs}, numPartitions=2)).collect()
    return mf, np.stack([np.asarray(r["out"], np.float32) for r in out])


def test_tiny_preset_is_the_family(tiny):
    config, _, _ = tiny
    preset = program.deepseek_v32_tiny()
    assert reference.weight_shapes(config) == program.param_shapes(preset)
    assert preset.expert_layers == 2 and preset.first_k_dense == 1
    assert (preset.n_routed_experts, preset.n_group, preset.topk_group) == (16, 4, 2)
    assert (preset.index_n_heads, preset.index_head_dim, preset.index_topk) == (4, 16, 16)
    assert preset.experts_held == (0, 4)
    assert preset.softmax_scale == pytest.approx(reference.softmax_scale(config))
    assert preset.scoring_func == "sigmoid"


def test_published_preset_is_the_configuration_file():
    """Shapes only: nothing of 3 B parameters is made."""
    config = published_config()
    preset = program.deepseek_v32()
    assert reference.weight_shapes(config) == program.param_shapes(preset)
    assert preset.experts_held == tuple(config["experts_held"]) == (0, 8)
    assert preset.n_routed_experts == config["published"]["n_routed_experts"] == 256
    for mine, theirs in (
        ("index_n_heads", "index_n_heads"), ("index_head_dim", "index_head_dim"),
        ("index_topk", "index_topk"), ("hidden_size", "hidden_size"),
        ("moe_intermediate_size", "moe_intermediate_size"),
        ("intermediate_size", "intermediate_size"), ("n_group", "n_group"),
        ("topk_group", "topk_group"), ("num_experts_per_tok", "num_experts_per_tok"),
        ("routed_scaling_factor", "routed_scaling_factor"),
        ("scoring_func", "scoring_func"),
        ("norm_topk_prob", "norm_topk_prob"), ("n_shared_experts", "n_shared_experts"),
    ):
        assert getattr(preset, mine) == config[theirs], mine
    # m = 0.1 * mscale_all_dim * ln(40) + 1, squared into the score's scale
    assert preset.softmax_scale == pytest.approx(192**-0.5 * 1.3689**2, rel=1e-4)
    # the uncut model's defaults are the published config's
    whole = program.DeepseekV32Config()
    assert (whole.num_layers, whole.first_k_dense, whole.vocab_size) == (61, 3, 129280)
    spec = get_model("deepseek-v3.2-exp")
    assert spec.feature_dim == 7168 and spec.vocab_size == 16160
    assert get_model("deepseek-v3.2-exp-tiny").feature_dim == 64
    # the slot buffer of the cell's two dispatches, and the worst case in passes
    assert moe_layer.slot_capacity(preset, 16384) == 5120
    assert moe_layer.slot_capacity(preset, 8192) == 2560
    assert (16384 * 8) % preset.worst_case_chunk_rows == 0


@pytest.mark.parametrize(
    "dtype, precision, interpret, median, widest",
    [
        (jnp.float32, "highest", False, 1e-5, 1e-5),
        (jnp.float32, "highest", True, 1e-5, 1e-5),
        (jnp.bfloat16, "reference", False, 6e-2, 1.5e-1),
        (jnp.bfloat16, "reference", True, 6e-2, 1.5e-1),
    ],
)
def test_embedder_matches_the_reference_row_by_row(
    monkeypatch, tiny, corpus, dtype, precision, interpret, median, widest
):
    config, weights, path = tiny
    monkeypatch.setenv("SPARKDL_TEXT_BUCKETS", "64,256")
    monkeypatch.setenv("SPARKDL_TEXT_MIN_BUCKET", "64")
    before = _counters()
    mf, got = _embed(path, corpus, dtype, interpret)
    assert (mf.attention, mf.experts, mf.indexer) == (
        ("flash", "pallas", "pallas") if interpret else ("dense", "ragged_dot", "jnp")
    )
    assert mf.weights_as_arguments and mf.row_counters == (
        "moe.slots_held", "moe.buffer_sized", "moe.buffer_full",
        "dsa.pairs_selected", "dsa.pairs_selected",
    )
    assert got.shape == (12, 64)  # the columns of counts are stripped
    want = reference.outputs(config, weights, corpus, precision=precision)
    errs = compare.row_errors(got, want)
    assert np.median(errs) <= median and errs.max() <= widest, errs
    assert compare.rows_mismatched(got, want) == 0
    delta = {k: v - before.get(k, 0) for k, v in _counters().items()}
    lengths = [len(reference.tokenize(t, 512, 256)) for t in corpus]
    # both buckets exceed index_topk: every dispatched token, 3 layers
    assert delta["dsa.index_tokens"] == delta["mla.attention_tokens"]
    assert delta["mla.attention_tokens"] / 3 >= sum(lengths)
    assert delta["moe.slots_routed"] == sum(lengths) * 3 * 2
    # counted on the device from the selection itself, against arithmetic
    assert delta["dsa.pairs_causal"] == 3 * sum(n * (n + 1) // 2 for n in lengths)
    assert delta["dsa.pairs_selected"] == 3 * sum(
        min(16, t + 1) for n in lengths for t in range(n)
    )
    assert delta["dsa.pairs_selected"] < 0.25 * delta["dsa.pairs_causal"]
    assert delta["mla.pairs_computed"] == _pairs_computed(
        delta, (64, 256), interpret, lengths
    )
    if interpret:
        assert delta["mla.query_blocks"] == delta["mla.attention_tokens"] // 64
        assert delta["mla.query_blocks_run"] == 3 * sum(-(-n // 64) for n in lengths)
    else:
        assert not delta.get("mla.query_blocks") and not delta.get("mla.query_blocks_run")


def test_a_large_count_of_pairs_rides_back_exactly():
    """A row of the cell's size has 1.6e8 selected pairs: float32 holds
    neither that nor a partition's sum of it, the two columns do."""
    split = moe_layer.WIDE_SPLIT
    pairs = np.array([5 * (2048 * 2049 // 2 + 14336 * 2048), 123456789], np.int64)
    high = (pairs // split * split).astype(np.float32)
    low = (pairs % split).astype(np.float32)
    assert (high.astype(np.int64) + low.astype(np.int64) == pairs).all()
    total = np.sum([high, high, high, high, high, high], 0)  # six rows a partition
    assert total.dtype == np.float32 and (total.astype(np.int64) == 6 * (pairs - pairs % split)).all()
    assert np.float32(pairs[1]) != pairs[1]


# -- the selection -------------------------------------------------------------


def _first_layer_operands(config, weights, text, length):
    """The indexer's operands of layer 0 for one row, from the reference
    (float32, `highest`) and from the program (float32)."""
    ids = np.zeros((1, length), np.int32)
    tokens = reference.tokenize(text, 512, length)
    ids[0, : len(tokens)] = tokens
    embed = np.asarray(reference.from_bits(weights["embed"]), np.float32)
    x = jnp.asarray(embed[ids])
    w = {
        k[len("layers/0/"):]: jnp.asarray(reference.from_bits(v), jnp.float32)
        for k, v in weights.items() if k.startswith("layers/0/")
    }
    u = reference._rms(x, w["norm_in"], config["rms_norm_eps"])
    product = reference._product("highest")
    c_q = reference._rms(
        product("bli,io->blo", u, w["attn/q_a"]), w["attn/q_norm"], config["rms_norm_eps"]
    )
    return len(tokens), u, c_q, w


def test_the_selected_sets_are_the_references(tiny, corpus):
    """Layer 0 of a row of 256 tokens: the program's selection against
    the reference's `lax.top_k`, query by query. Two float32 sums in
    another order may swap the last selected key with the first left out
    where their scores differ by rounding: a query is compared where its
    16th and 17th scores lie more than 1e-4 of the row's spread apart,
    and nearly all do."""
    config, weights, _ = tiny
    text = max(corpus, key=lambda t: len(t.split()))
    n, u, c_q, w = _first_layer_operands(config, weights, text, 256)
    assert n == 256
    with jax.default_matmul_precision("highest"):
        q_i, k_i, w_i = reference.index_operands(config, w, c_q, u, "highest")
        scores = reference.index_scores(q_i, k_i, w_i, "highest")
        want = np.asarray(reference.selected(scores, 0, 16))
        preset = program.deepseek_v32_tiny()
        p = {k[len("attn/indexer/"):]: v for k, v in w.items() if k.startswith("attn/indexer/")}
        tables = deepseek_v2.rope_tables(preset, 256)
        operands = program.index_inputs(preset, p, c_q, u, tables)
    np.testing.assert_allclose(
        np.asarray(operands[0]).reshape(1, 256, 4, 16), np.asarray(q_i), atol=2e-5
    )
    np.testing.assert_allclose(np.asarray(operands[1]), np.asarray(k_i), atol=2e-5)
    np.testing.assert_allclose(np.asarray(operands[2]), np.asarray(w_i), atol=2e-6)
    causal = np.tril(np.ones((256, 256), bool))
    ordered = np.sort(np.where(causal, np.asarray(scores[0]), -np.inf), -1)[:, ::-1]
    margin = 1e-4 * float(np.std(np.asarray(scores[0])[causal]))
    with np.errstate(invalid="ignore"):  # the first queries have no 17th key
        clear = (np.arange(256) < 16) | (ordered[:, 15] - ordered[:, 16] > margin)
    assert clear.mean() > 0.95
    for interpret in (False, True):
        got = np.asarray(make_indexer_fn(4, 16, interpret=interpret)(*operands)) != 0
        assert (got[0][clear] == want[0][clear]).all()
        assert (got[0].sum(-1) == np.minimum(16, np.arange(256) + 1)).all()


def test_a_row_within_index_topk_is_dense_causal_mla_to_the_bit(tiny):
    """A bucket of 16 tokens selects everything: its program has no
    indexer (no search loop, no selection operand) and gives what the
    DeepSeek-V2 stack gives over the same leaves."""
    _, _, path = tiny
    mf = _built(path, jnp.float32, False)
    ids = np.zeros((2, 16), np.int32)
    ids[0, :12], ids[1, :16] = np.arange(4, 16), np.arange(20, 36)
    short = str(jax.make_jaxpr(mf.fn)(mf.params, jnp.asarray(ids)))
    longer = str(jax.make_jaxpr(mf.fn)(mf.params, jnp.zeros((2, 64), jnp.int32)))
    # the scores' integer order, and the selection a byte a pair
    assert "bitcast_convert_type" in longer and "i8[" in longer
    assert "bitcast_convert_type" not in short and "i8[" not in short
    got = np.asarray(mf.fn(mf.params, jnp.asarray(ids)))
    preset = program.deepseek_v32_tiny()
    dense, held, sized = deepseek_v2.forward(
        preset, mf.params, jnp.asarray(ids), dtype=jnp.float32,
        attention_fn=make_latent_attention_fn(preset.num_heads, preset.softmax_scale),
        experts_fn=make_grouped_matmul_fn(),
    )
    assert (got[:, :64] == np.asarray(dense)).all()
    assert (got[:, 64] == np.asarray(held)).all()
    # everything is selected: the causal pairs of the real queries, 3 layers
    assert got[:, 67].tolist() == [0.0, 0.0]
    assert got[:, 68].tolist() == [3 * 12 * 13 // 2, 3 * 16 * 17 // 2]
    # and a longer row is NOT what dense attention gives
    ids = np.zeros((1, 64), np.int32)
    ids[0, :60] = np.arange(4, 64)
    sparse = np.asarray(mf.fn(mf.params, jnp.asarray(ids)))[:, :64]
    dense, _, _ = deepseek_v2.forward(
        preset, mf.params, jnp.asarray(ids), dtype=jnp.float32,
        attention_fn=make_latent_attention_fn(preset.num_heads, preset.softmax_scale),
        experts_fn=make_grouped_matmul_fn(),
    )
    assert np.abs(sparse - np.asarray(dense)).max() > 1e-3


def test_the_indexers_rotary_pairs_halves_where_mlas_pairs_neighbours():
    preset = program.deepseek_v32_tiny()
    cos, sin = deepseek_v2.rope_tables(preset, 8)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 8, 16)), jnp.float32)
    got = np.asarray(program._rotate_head(x, cos, sin))
    angle = np.arange(8)[:, None] * deepseek_v2.yarn_inv_freq(preset)  # [8, 4]
    a, b = np.asarray(x[0, :, :4]), np.asarray(x[0, :, 4:8])
    np.testing.assert_allclose(got[0, :, :4], a * np.cos(angle) - b * np.sin(angle), atol=1e-6)
    np.testing.assert_allclose(got[0, :, 4:8], b * np.cos(angle) + a * np.sin(angle), atol=1e-6)
    assert (got[0, :, 8:] == np.asarray(x[0, :, 8:])).all()  # past `rope`: as it was
    config = tiny_config()
    np.testing.assert_allclose(
        np.asarray(reference._rope_halves(config, x, 8)), got, atol=1e-6
    )


# -- the gate, by hand ---------------------------------------------------------


def _gate_by_hand(logits, bias, groups=4, keep=2, top=3, scaling=2.5):
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    c = s + bias
    per = len(s) // groups
    rank = [np.sort(c[g * per : (g + 1) * per])[-2:].sum() for g in range(groups)]
    kept = sorted(range(groups), key=lambda g: -rank[g])[:keep]
    chosen = sorted(
        (e for e in range(len(s)) if e // per in kept), key=lambda e: -c[e]
    )[:top]
    total = sum(s[e] for e in chosen)
    return chosen, [scaling * s[e] / total for e in chosen]


def test_the_gate_by_hand():
    """16 experts in 4 groups of which 2, top-3. Group 1 holds the single
    best score, but groups 0 and 2 have the better sums of two and group
    1 is dropped whole. The bias moves the choice and not the weight:
    expert 8's bias lifts it over expert 2, its weight is its sigmoid
    score all the same, and the weights sum to 2.5."""
    preset = program.deepseek_v32_tiny()
    logits = np.full(16, -3.0, np.float32)
    logits[0], logits[1], logits[2] = 1.0, 0.8, 0.45  # group 0
    logits[5] = 1.5  # group 1: the best single score, and nothing beside it
    logits[8], logits[9] = 0.4, 0.9  # group 2
    bias = np.zeros(16, np.float32)
    router = jnp.asarray(np.eye(64, 16, dtype=np.float32))
    u = np.zeros((2, 64), np.float32)
    u[0, :16] = u[1, :16] = logits
    experts, weights = moe_layer.route(preset, jnp.asarray(u), router, jnp.asarray(bias))
    chosen, by_hand = _gate_by_hand(logits, bias)
    assert chosen == [0, 9, 1] and 5 not in chosen
    assert np.asarray(experts[0]).tolist() == chosen
    np.testing.assert_allclose(np.asarray(weights[0]), by_hand, rtol=1e-5)
    assert float(weights[0].sum()) == pytest.approx(2.5, rel=1e-6)
    # the bias chooses: expert 8 over expert 1 ...
    bias[8] = 0.1
    experts, lifted = moe_layer.route(preset, jnp.asarray(u), router, jnp.asarray(bias))
    chosen, by_hand = _gate_by_hand(logits, bias)
    assert chosen == [0, 9, 8]
    assert np.asarray(experts[0]).tolist() == chosen
    # ... and is not in the weight: sigmoid(0.4) over the three scores' sum
    s = 1 / (1 + np.exp(-np.array([1.0, 0.9, 0.4])))
    np.testing.assert_allclose(np.asarray(lifted[0]), 2.5 * s / s.sum(), rtol=1e-5)
    assert float(lifted[0].sum()) == pytest.approx(2.5, rel=1e-6)
    # the reference gates the same way
    r_experts, r_weights = reference.route(
        tiny_config(), jnp.asarray(u), router, jnp.asarray(bias)
    )
    assert np.asarray(r_experts).tolist() == np.asarray(experts).tolist()
    np.testing.assert_allclose(np.asarray(r_weights), np.asarray(lifted), rtol=1e-6)


def test_a_negative_choice_in_a_kept_group_beats_a_dropped_groups_best():
    """score + bias may be negative; a dropped group is out whatever its
    scores (masked to -inf, not to 0)."""
    preset = program.DeepseekV32Config(
        **{**program.deepseek_v32_tiny().__dict__, "topk_group": 1}
    )
    logits = np.full(16, -6.0, np.float32)  # sigmoid 0.0025
    logits[0], logits[1] = 2.0, 1.0  # group 0 is kept; its third best is tiny
    logits[4] = 1.9  # group 1's best: dropped with its group
    bias = np.full(16, -0.04, np.float32)  # every other choice of group 0 negative
    router = jnp.asarray(np.eye(64, 16, dtype=np.float32))
    u = np.zeros((1, 64), np.float32)
    u[0, :16] = logits
    experts, _ = moe_layer.route(preset, jnp.asarray(u), router, jnp.asarray(bias))
    assert sorted(np.asarray(experts[0]).tolist()) == [0, 1, 2]


# -- the share adds up ---------------------------------------------------------


def _share(first, end):
    return program.DeepseekV32Config(
        **{**program.deepseek_v32_tiny().__dict__, "experts_held": (first, end)}
    )


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """One expert layer of the tiny preset: the routed parts that the
    four shares (4 experts each) compute in the program, plus the shared
    expert once, equal the uncut layer of the reference (all 16)."""
    uncut = tiny_config(held=None)
    weights = reference.make_weights(uncut, 3)
    name = "layers/1/moe/"
    moe = {
        k[len(name):]: jnp.asarray(reference.from_bits(v), jnp.float32)
        for k, v in weights.items() if k.startswith(name)
    }
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        experts, gates = reference.route(uncut, u, moe["router"], moe["router_bias"])
        shared = {k.split("/")[1]: v for k, v in moe.items() if k.startswith("shared/")}
        whole = reference.v2._swiglu("highest", shared, u)
        for e in range(16):
            w = {k.split("/")[1]: v[e] for k, v in moe.items() if k.startswith("experts/")}
            whole = whole + reference.v2._expert("highest", e, w, u, experts, gates)
    assert float(np.asarray(gates).sum(-1).max()) == pytest.approx(2.5, rel=1e-5)
    real = jnp.ones((2, 24), bool)
    total, slots = 0.0, 0
    for first in (0, 4, 8, 12):
        p = {
            "router": moe["router"],
            "router_bias": moe["router_bias"],
            "experts": {
                k: moe[f"experts/{k}"][first : first + 4] for k in ("gate", "up", "down")
            },
        }
        part, count, _ = moe_layer.routed(
            _share(first, first + 4), p, u, real, make_grouped_matmul_fn(),
            gate=moe_layer.route,
        )
        total, slots = total + part, slots + int(count.sum())
    assert slots == 2 * 24 * 3  # every slot is held by exactly one share
    p_shared = {k: moe[f"shared/{k}"] for k in ("gate", "up", "down")}
    total = total + deepseek_v2._swiglu(p_shared, u)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-4, rtol=2e-4)


# -- the worst-case arm in passes ----------------------------------------------


def _layer(preset, seed=0):
    params = program.init_params(preset, seed, jnp.float32)
    return params["layers"]["1"]["moe"]


@pytest.mark.parametrize("interpret", [False, True], ids=["ragged_dot", "pallas"])
@pytest.mark.parametrize("chunk", [64, 128, 384])
@pytest.mark.parametrize("load", ["spread", "all_on_the_held_group", "none_held"])
def test_the_worst_case_arm_in_passes_is_the_one_buffer(interpret, chunk, load):
    """`moe.routed` with `worst_case_chunk_rows`: the same sum as the one
    buffer of every slot, at a load the sized buffer holds (the `cond`
    takes the sized arm in both) and at one it does not (every slot on
    the held group: the worst-case arm, in passes against whole)."""
    whole = program.deepseek_v32_tiny()
    passes = program.DeepseekV32Config(
        **{**whole.__dict__, "worst_case_chunk_rows": chunk}
    )
    moe = _layer(whole)
    rng = np.random.default_rng(1)
    u = rng.standard_normal((2, 64, 64)).astype(np.float32)  # 384 slots
    if load != "spread":
        router = np.zeros((64, 16), np.float32)
        lift = slice(0, 4) if load == "all_on_the_held_group" else slice(4, 16)
        router[0, lift], u[..., 0] = 4.0, 3.0
        moe = dict(moe, router=jnp.asarray(router))
    real = np.ones((2, 64), bool)
    real[1, 50:] = False
    experts_fn = make_grouped_matmul_fn(interpret=interpret)
    u, real = jnp.asarray(u), jnp.asarray(real)
    want, held, fits = moe_layer.routed(whole, moe, u, real, experts_fn, gate=moe_layer.route)
    got, held_p, fits_p = moe_layer.routed(passes, moe, u, real, experts_fn, gate=moe_layer.route)
    assert bool(fits) == bool(fits_p) == (load != "all_on_the_held_group")
    assert np.asarray(held).tolist() == np.asarray(held_p).tolist()
    if load == "all_on_the_held_group":
        assert int(held.sum()) == 3 * int(real.sum())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    assert (np.abs(np.asarray(want)).max() > 1e-3) == (load != "none_held")


def test_the_published_preset_works_its_worst_case_in_passes():
    """Shapes only: the expert layer of the cell's 16,384-token dispatch
    has the `cond` on the load, and its worst-case arm is a scan of 8
    passes of 16,384 slot rows, not one buffer of 131,072."""
    preset = program.deepseek_v32()
    shapes = program.layer_shapes(preset, 1)
    moe = {
        "router": jax.ShapeDtypeStruct(shapes["moe/router"], jnp.float32),
        "router_bias": jax.ShapeDtypeStruct(shapes["moe/router_bias"], jnp.float32),
        "experts": {
            k: jax.ShapeDtypeStruct(shapes[f"moe/experts/{k}"], jnp.bfloat16)
            for k in ("gate", "up", "down")
        },
    }
    u = jax.ShapeDtypeStruct((1, 16384, preset.hidden_size), jnp.float32)
    real = jax.ShapeDtypeStruct((1, 16384), bool)
    text = str(jax.make_jaxpr(
        lambda p, u, real: moe_layer.routed(
            preset, p, u, real, make_grouped_matmul_fn(), gate=moe_layer.route
        )
    )(moe, u, real))
    assert "scan[" in text and "length=8" in text
    assert "f32[131072,7168]" not in text and "f32[16384,7168]" in text
    assert "bf16[5120,7168]" in text  # the sized arm's slot buffer


# -- the attention is handed its rows' lengths -----------------------------------


def _without_lengths(kernel):
    """The same kernel with `takes_lengths` taken away: what the parent
    commit built."""

    def blind(q, kv, k_rope, dtype, selection=None):
        return kernel(q, kv, k_rope, dtype, selection)

    blind.kind, blind.pairs_computed = kernel.kind, kernel.pairs_computed
    blind.query_blocks = kernel.query_blocks
    return blind


def _uneven_batch(length):
    """A row of zeros, and rows of 10, 65, 130 and `length` tokens (those
    the bucket holds)."""
    lengths = [n for n in (0, 10, 65, 130) if n < length] + [length]
    ids = np.zeros((len(lengths), length), np.int32)
    for row, n in enumerate(lengths):
        ids[row, :n] = np.random.default_rng(row).integers(1, 512, n)
    return jnp.asarray(ids)


@pytest.mark.parametrize("length", [16, 256], ids=["dense-16", "selected-256"])
def test_lengths_change_no_embedding_to_the_bit(tiny, length):
    """The tiny preset built with the interpreted kernels, a bucket that
    selects and one within `index_topk`: with the rows' lengths handed
    over every row's embedding and counters (the selected pairs too) are,
    to the bit, those of the same kernel without `takes_lengths`."""
    _, _, path = tiny
    preset = program.deepseek_v32_tiny()
    kernel = make_latent_attention_fn(
        preset.num_heads, preset.softmax_scale, block=64, interpret=True
    )

    def built(attention_fn):
        return program.deepseek_v32_model_function(
            "deepseek-v3.2-exp-tiny", dtype=jnp.bfloat16, weights_file=path,
            attention_fn=attention_fn,
            indexer_fn=make_indexer_fn(preset.index_n_heads, preset.index_topk, interpret=True),
        )

    ids = _uneven_batch(length)
    given, blind = built(kernel), built(_without_lengths(kernel))
    got = np.asarray(given.fn(given.params, ids))
    want = np.asarray(blind.fn(blind.params, ids))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    assert not got[0, :64].any() and np.abs(got[1:, :64]).min(1).max() > 0


def test_an_attention_of_five_arguments_still_builds_and_runs(tiny):
    """What stands in for `attention_fn` in a test or a planted fault
    takes (q, kv, k_rope, dtype, selection) and no more."""
    from sparkdl_tpu.ops.flash_attention import dense_latent_attention

    _, _, path = tiny
    preset = program.deepseek_v32_tiny()
    calls = []

    def five(q, kv, k_rope, dtype, selection=None):
        calls.append(selection is not None)
        return dense_latent_attention(
            q, kv, k_rope, dtype, selection, num_heads=preset.num_heads,
            scale=preset.softmax_scale,
        )

    mf = program.deepseek_v32_model_function(
        "deepseek-v3.2-exp-tiny", weights_file=path, attention_fn=five
    )
    plain = program.deepseek_v32_model_function("deepseek-v3.2-exp-tiny", weights_file=path)
    for length, selects in ((16, False), (64, True)):
        ids = _uneven_batch(length)
        del calls[:]
        got = np.asarray(mf.fn(mf.params, ids))
        assert calls == [selects] * 3
        np.testing.assert_array_equal(got, np.asarray(plain.fn(plain.params, ids)))
    counted = mf.batch_counters(np.asarray(ids), np.asarray(ids) != 0)
    assert not any(name.startswith("mla.") for name in counted)


def test_the_models_batch_counters_hold_the_attentions(tiny):
    _, _, path = tiny
    mf = _built(path, jnp.float32, True)
    ids = np.asarray(_uneven_batch(256))  # 0, 10, 65, 130, 256 tokens: 0, 1, 2, 3, 4 blocks
    counted = mf.batch_counters(ids, ids != 0)
    assert counted["mla.query_blocks"] == 3 * 5 * 4
    assert counted["mla.query_blocks_run"] == 3 * (0 + 1 + 2 + 3 + 4)
    assert counted["mla.pairs_computed"] == 3 * (0 + 1 + 3 + 6 + 10) * 64 * 64
    assert counted["dsa.index_tokens"] == 3 * 5 * 256


# -- the indexer is handed its rows' lengths -------------------------------------


def _indexer_without_lengths(indexer):
    """The same indexer with `takes_lengths` taken away: what the parent
    commit built."""

    def blind(q, k, w):
        return indexer(q, k, w)

    blind.kind = indexer.kind
    return blind


@pytest.mark.parametrize("attention", ["with-lengths", "without-lengths"])
def test_the_indexers_lengths_change_no_embedding_to_the_bit(tiny, attention):
    """The tiny preset with the interpreted kernels, rows of 0, 10, 65,
    300 and 512 tokens in a bucket of 512 (the index-scores kernel's
    blocks of 256: 0, 1, 1, 2 and 2 of them live): with the indexer
    handed the rows' lengths, every row's embedding and its counters
    (`dsa.pairs_selected` too) are those without, to the bit, and every
    layer's attention is finite at every position, the padding's too,
    with an attention that runs every query (`without-lengths`) as with
    one that skips the padding's blocks. The host counts the index
    kernel's query blocks at each row's length."""
    _, _, path = tiny
    preset = program.deepseek_v32_tiny()
    kernel = make_latent_attention_fn(
        preset.num_heads, preset.softmax_scale, block=64, interpret=True
    )
    if attention == "without-lengths":
        kernel = _without_lengths(kernel)
    outputs = []

    def attention_fn(*args, **by_length):
        out = kernel(*args, **by_length)
        outputs.append(np.asarray(out))
        return out

    for name in ("kind", "takes_lengths", "query_blocks", "pairs_computed"):
        if hasattr(kernel, name):
            setattr(attention_fn, name, getattr(kernel, name))
    indexer = make_indexer_fn(preset.index_n_heads, preset.index_topk, interpret=True)

    def built(indexer_fn):
        return program.deepseek_v32_model_function(
            "deepseek-v3.2-exp-tiny", dtype=jnp.bfloat16, weights_file=path,
            attention_fn=attention_fn, indexer_fn=indexer_fn,
        )

    lengths = [0, 10, 65, 300, 512]
    ids = np.zeros((len(lengths), 512), np.int32)
    for row, n in enumerate(lengths):
        ids[row, :n] = np.random.default_rng(row).integers(1, 512, n)
    given, blind = built(indexer), built(_indexer_without_lengths(indexer))
    got = np.asarray(given.fn(given.params, jnp.asarray(ids)))
    assert len(outputs) == 3 and all(np.isfinite(o).all() for o in outputs)
    want = np.asarray(blind.fn(blind.params, jnp.asarray(ids)))
    np.testing.assert_array_equal(got, want)
    assert got[1:, -2:].sum() > 0  # dsa.pairs_selected, in two columns
    counted = given.batch_counters(ids, ids != 0)
    assert counted["dsa.query_blocks"] == 3 * 5 * 2
    assert counted["dsa.query_blocks_run"] == 3 * (0 + 1 + 1 + 2 + 2)
    assert not any(name.startswith("dsa.query") for name in blind.batch_counters(ids, ids != 0))


@pytest.mark.parametrize("takes_lengths", [True, False], ids=["lengths", "without"])
def test_index_query_blocks_of_the_cells_job(tiny, takes_lengths):
    """A job of the V3.2 cell (its traffic, its configuration's buckets,
    one row a dispatch) in the index-scores kernel's blocks of 256: its
    ten rows hold 480 query blocks a layer (five of 32 at 8,192 and five
    of 64 at 16,384), 332 of them a real token; counted over the tiny
    preset's three layers (the cell's five count 5 x 480 and 5 x 332).
    An indexer that takes no lengths runs every block."""
    import json

    _, _, path = tiny
    with open(os.path.join(ROOT, "benchmarks", "traffic", "embed-long-docs.json")) as f:
        data = json.load(f)["data"]
    with open(os.path.join(ROOT, "benchmarks", "configs", "deepseek-v3.2-exp.json")) as f:
        edges = [int(e) for e in json.load(f)["env"]["SPARKDL_TEXT_BUCKETS"].split(",")]
    tokens = texts.word_counts(data["rows"] - data["null_rows"], data["word_counts"]) + 2
    indexer = built = make_indexer_fn(4, 16, interpret=True)
    if not takes_lengths:
        indexer = _indexer_without_lengths(built)
        indexer.query_blocks = built.query_blocks
    mf = program.deepseek_v32_model_function(
        "deepseek-v3.2-exp-tiny", weights_file=path, indexer_fn=indexer
    )
    total = {}
    for n in tokens:
        ids = np.zeros((1, min(e for e in edges if e >= n)), np.int32)
        ids[0, :n] = 5
        for name, count in mf.batch_counters(ids, ids != 0).items():
            total[name] = total.get(name, 0) + count
    assert total["dsa.index_tokens"] == 3 * 122880 and sum(tokens) == 84223
    assert total["dsa.query_blocks"] == 3 * 480
    assert total["dsa.query_blocks_run"] == 3 * (332 if takes_lengths else 480)
