"""The DeepSeek-V2 family (models/deepseek_v2.py) on the offline embed
path, at the tiny preset with seeded random weights, against the plain
reference (benchmarks/reference/deepseek_v2.py) row by row; the chip's
share against the uncut layer; routing, rotary and the published
configuration's count by hand.

Tolerances. In float32 the program and the reference at `highest` do the
same arithmetic in another order: 1e-6 of the spread of the rows, held to
1e-5. In bfloat16 both round the operands of every matrix product to
bfloat16, at other points: the program rounds a rotary query's two terms,
x cos and turn(x) sin, apart (models/deepseek_v2.py:_mla) and the
reference their sum, and a token whose last chosen expert swaps with the
next (routing is discrete) moves its row by its share of the row's mean,
most in a row of ten tokens: 0.008 at the median and 0.09 at the widest
with the plain forms, 0.013 and 0.03 with the interpreted kernels, held to
0.03 and 0.12; the float8 control reads 0.30
(tests/benchmarks/test_deepseek_v2_cell.py holds that)."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmarks"))

from deepseek_v2_tiny import published_config, tiny_config, write_weights  # noqa: E402

from benchmarks import compare  # noqa: E402
from benchmarks.data import texts  # noqa: E402
from benchmarks.reference import deepseek_v2 as reference  # noqa: E402
from sparkdl_tpu.dataframe import DataFrame  # noqa: E402
from sparkdl_tpu.models import deepseek_v2 as program  # noqa: E402
from sparkdl_tpu.models import get_model  # noqa: E402
from sparkdl_tpu.ops.flash_attention import make_latent_attention_fn  # noqa: E402
from sparkdl_tpu.ops.grouped_matmul import make_grouped_matmul_fn  # noqa: E402
from sparkdl_tpu.ops.moe_combine import make_moe_combine_fn  # noqa: E402
from sparkdl_tpu.transformers.text import TextEmbedder  # noqa: E402
from sparkdl_tpu.utils.metrics import metrics  # noqa: E402


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    config = tiny_config()
    path = str(tmp_path_factory.mktemp("deepseek") / "tiny.npz")
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


def _pairs_computed(delta, edges, interpret, lengths, layers=3):
    """What `mla.pairs_computed` should have counted, by hand: the dense
    fallback runs the square at the bucket's edge for every dispatched
    row (from the rows and tokens dispatched at the two edges), the
    kernel (blocks of 64, one sub-tile each; it takes lengths) the blocks
    at and under the diagonal of each live row's own query blocks, and
    nothing for a row that only fills a batch."""
    if interpret:
        blocks = [-(-n // 64) for n in lengths]
        return layers * sum(n * (n + 1) // 2 * 64 * 64 for n in blocks)
    low, high = edges
    rows = delta["feeder.rows"] + delta.get("feeder.pad_rows", 0)
    at_high = (delta["mla.attention_tokens"] // layers - low * rows) // (high - low)
    return layers * ((rows - at_high) * low * low + at_high * high * high)


def _embed(path, inputs, dtype, interpret, batch=4, max_length=256, edit=None):
    preset = program.deepseek_v2_tiny()
    mf = program.deepseek_v2_model_function(
        "deepseek-v2-tiny", dtype=dtype, weights_file=path,
        attention_fn=make_latent_attention_fn(
            preset.num_heads, preset.softmax_scale, block=64, interpret=interpret
        ),
        experts_fn=make_grouped_matmul_fn(interpret=interpret),
    )
    if edit is not None:
        edit(mf.params)
    out = TextEmbedder(
        inputCol="in", outputCol="out", modelFunction=mf, maxLength=max_length,
        batchSize=batch,
    ).transform(DataFrame.fromColumns({"in": inputs}, numPartitions=2)).collect()
    return mf, np.stack([np.asarray(r["out"], np.float32) for r in out])


def test_tiny_preset_is_the_family(tiny):
    config, _, _ = tiny
    preset = program.deepseek_v2_tiny()
    assert reference.weight_shapes(config) == program.param_shapes(preset)
    assert preset.expert_layers == 2 and preset.first_k_dense == 1
    assert (preset.n_routed_experts, preset.n_group, preset.topk_group) == (16, 4, 2)
    assert preset.experts_held == (0, 4)
    assert preset.softmax_scale == pytest.approx(reference.softmax_scale(config))


def test_published_preset_is_the_configuration_file():
    """Shapes only: nothing of 5 B parameters is made."""
    config = published_config()
    preset = program.deepseek_v2()
    assert reference.weight_shapes(config) == program.param_shapes(preset)
    assert preset.experts_held == tuple(config["experts_held"]) == (0, 40)
    assert preset.n_routed_experts == config["published"]["n_routed_experts"] == 160
    spec = get_model("deepseek-v2")
    assert spec.feature_dim == 5120 and spec.vocab_size == 25600
    assert get_model("deepseek-v2-tiny").feature_dim == 64


@pytest.mark.parametrize(
    "dtype, precision, interpret, median, widest",
    [
        (jnp.float32, "highest", False, 1e-5, 1e-5),
        (jnp.float32, "highest", True, 1e-5, 1e-5),
        (jnp.bfloat16, "reference", False, 3e-2, 1.2e-1),
        (jnp.bfloat16, "reference", True, 3e-2, 1.2e-1),
    ],
)
def test_embedder_matches_the_reference_row_by_row(
    monkeypatch, tiny, corpus, dtype, precision, interpret, median, widest
):
    config, weights, path = tiny
    monkeypatch.setenv("SPARKDL_TEXT_BUCKETS", "128,256")
    monkeypatch.setenv("SPARKDL_TEXT_MIN_BUCKET", "128")
    before = _counters()
    mf, got = _embed(path, corpus, dtype, interpret)
    assert (mf.attention, mf.experts) == (
        ("flash", "pallas") if interpret else ("dense", "ragged_dot")
    )
    assert mf.weights_as_arguments and mf.row_counters == (
        "moe.slots_held", "moe.buffer_sized", "moe.buffer_full"
    )
    assert got.shape == (12, 64)  # the columns of counts are stripped
    want = reference.outputs(config, weights, corpus, precision=precision)
    errs = compare.row_errors(got, want)
    assert np.median(errs) <= median and errs.max() <= widest, errs
    assert compare.rows_mismatched(got, want) == 0
    delta = {k: v - before.get(k, 0) for k, v in _counters().items()}
    # rows of both buckets in batches of 4: every dispatched token, 3 layers
    dispatched = delta["mla.attention_tokens"] / 3
    assert dispatched >= sum(len(reference.tokenize(t, 512, 256)) for t in corpus)
    real = sum(len(reference.tokenize(t, 512, 256)) for t in corpus)
    assert delta["moe.slots_routed"] == real * 3 * 2  # top-3, two expert layers
    # a quarter of the experts are held: about a quarter of the slots
    share = delta["moe.slots_held"] / delta["moe.slots_routed"]
    assert 0.1 < share < 0.45, share
    # every live row carries its dispatch's two expert layers, and a router
    # that spreads evenly leaves every one of them on the sized buffer
    assert delta["moe.buffer_sized"] == 12 * 2
    assert delta.get("moe.buffer_full", 0) == 0
    lengths = [len(reference.tokenize(t, 512, 256)) for t in corpus]
    assert delta["mla.pairs_computed"] == _pairs_computed(
        delta, (128, 256), interpret, lengths
    )
    if interpret:  # the kernel says its blocks: 2 and 4 of 64 a row of each bucket
        rows = delta["feeder.rows"] + delta.get("feeder.pad_rows", 0)
        assert delta["mla.query_blocks"] == delta["mla.attention_tokens"] // 64
        assert delta["mla.query_blocks_run"] == 3 * sum(-(-n // 64) for n in lengths)
        assert delta["mla.query_blocks_run"] < delta["mla.query_blocks"] <= 3 * rows * 4
    else:
        assert not delta.get("mla.query_blocks") and not delta.get("mla.query_blocks_run")


def test_a_row_does_not_change_with_what_pads_it(monkeypatch, tiny, corpus):
    """Alone in its batch (padded by empty rows), beside longer rows, in
    a wider bucket: the same answer."""
    _, _, path = tiny
    monkeypatch.setenv("SPARKDL_TEXT_BUCKETS", "128,256")
    monkeypatch.setenv("SPARKDL_TEXT_MIN_BUCKET", "128")
    short = [t for t in corpus if len(t.split()) < 120][:2]
    _, together = _embed(path, corpus, jnp.float32, False)
    _, alone = _embed(path, short, jnp.float32, False)
    at = [corpus.index(t) for t in short]
    np.testing.assert_allclose(alone, together[at], atol=2e-5)
    monkeypatch.setenv("SPARKDL_TEXT_BUCKETS", "256")
    monkeypatch.setenv("SPARKDL_TEXT_MIN_BUCKET", "256")
    _, wide = _embed(path, short, jnp.float32, False)
    np.testing.assert_allclose(wide, alone, atol=2e-5)


# -- the share adds up ---------------------------------------------------------


def test_four_shares_and_the_shared_experts_once_are_the_uncut_layer():
    """One expert layer of the tiny preset: the routed parts that the
    four shares (4 experts each) compute in the program, plus the shared
    experts once, equal the uncut layer of the reference (all 16)."""
    uncut = tiny_config(held=None)
    weights = reference.make_weights(uncut, 3)
    name = "layers/1/moe/"
    moe = {
        k[len(name):]: jnp.asarray(reference.from_bits(v), jnp.float32)
        for k, v in weights.items() if k.startswith(name)
    }
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)
    # the reference, whole: shared + all 16 experts as a masked loop
    with jax.default_matmul_precision("highest"):
        experts, gates = reference.route(uncut, u, moe["router"])
        shared = {k.split("/")[1]: v for k, v in moe.items() if k.startswith("shared/")}
        whole = reference._swiglu("highest", shared, u)
        for e in range(16):
            w = {k.split("/")[1]: v[e] for k, v in moe.items() if k.startswith("experts/")}
            whole = whole + reference._expert("highest", e, w, u, experts, gates)
    # the program, share by share
    real = jnp.ones((2, 24), bool)
    total, slots = 0.0, 0
    for first in (0, 4, 8, 12):
        preset = _share(first, first + 4)
        p = {
            "router": moe["router"],
            "experts": {
                k: moe[f"experts/{k}"][first : first + 4] for k in ("gate", "up", "down")
            },
        }
        part, count, _ = program._routed(preset, p, u, real, make_grouped_matmul_fn())
        total, slots = total + part, slots + int(count.sum())
    assert slots == 2 * 24 * 3  # every slot is held by exactly one share
    p_shared = {k: moe[f"shared/{k}"] for k in ("gate", "up", "down")}
    total = total + program._swiglu(p_shared, u)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-4, rtol=2e-4)


# -- the slot buffer -----------------------------------------------------------


def _share(first, end):
    """The tiny preset with another share of its 16 experts."""
    return program.DeepseekV2Config(
        **{**program.deepseek_v2_tiny().__dict__, "experts_held": (first, end)}
    )


@pytest.mark.parametrize(
    "preset, tokens, capacity",
    [
        # a quarter held: 1.25 x 1/4 of the slots, in whole tiles of 256 rows
        (program.deepseek_v2(), 8 * 2048, 30720),  # of 98,304: 120 tiles, exact
        (program.deepseek_v2(), 8 * 1024, 15360),
        (program.deepseek_v2_tiny(), 4 * 128, 512),  # 480 of 1,536 rounded up
        (program.deepseek_v2_tiny(), 4 * 1024, 3840),
        # every expert held, and shapes too small to round: all the slots
        (program.DeepseekV2Config(), 8 * 2048, 8 * 2048 * 6),
        (_share(0, 16), 4 * 128, 4 * 128 * 3),
        (program.deepseek_v2_tiny(), 48, 144),
    ],
)
def test_the_buffer_is_sized_by_the_held_share(preset, tokens, capacity):
    assert program.slot_capacity(preset, tokens) == capacity
    assert 1.25 <= program._CAPACITY_MARGIN <= 1.5


def _layer(preset, seed=0):
    params = program.init_params(preset, seed, jnp.float32)
    return params["layers"]["1"]["moe"]


def _to_the_held_group(moe, u, experts=4):
    """(moe, u) with a router whose first `experts` columns, and a first
    feature of every token, send all three of a token's slots to the held
    group (the first four experts), or one with `experts` 1: the other two
    then fall on the second group."""
    router = moe["router"].at[0, :].set(0.0).at[0, :experts].set(3.0).at[0, 4:8].add(1.5)
    return dict(moe, router=router), u.at[..., 0].set(8.0)


def _oracle(preset, moe, u, real):
    """The held experts' part by the plain reference: every token through
    every held expert, weighted by zero where it was not chosen."""
    first, end = preset.experts_held
    config = tiny_config(held=(first, end))
    with jax.default_matmul_precision("highest"):
        experts, gates = reference.route(config, u, moe["router"])
        total = 0.0
        for e in range(first, end):
            w = {k: v[e - first] for k, v in moe["experts"].items()}
            total = total + reference._expert("highest", e, w, u, experts, gates)
    return jnp.where(real[..., None], total, 0.0)


@pytest.mark.parametrize("interpret", [False, True], ids=["ragged_dot", "pallas"])
@pytest.mark.parametrize("forced", [False, True], ids=["sized", "full"])
def test_both_arms_are_the_oracle(interpret, forced):
    """An even router leaves the quarter share on the sized buffer; one
    that sends every slot to the held group overflows it: every slot is
    computed there too. The kernels' case runs both interpreted: the
    grouped product writes the rows apart and the combine fetches them."""
    preset = program.deepseek_v2_tiny()
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.standard_normal((4, 128, 64)), jnp.float32)
    moe = _layer(preset)
    if forced:
        moe, u = _to_the_held_group(moe, u)
    real = np.ones((4, 128), bool)
    real[3, 70:] = False
    real = jnp.asarray(real)
    part, count, fits = program._routed(
        preset, moe, u, real, make_grouped_matmul_fn(interpret=interpret),
        combine_fn=make_moe_combine_fn(interpret=interpret),
    )
    assert program.slot_capacity(preset, 4 * 128) == 512 < 4 * 128 * 3
    assert bool(fits) is not forced
    held = int(count.sum())
    if forced:
        assert held == 3 * int(real.sum()) > 512  # no slot dropped
    else:
        assert 0 < held <= 512
    want = _oracle(preset, moe, u, real)
    np.testing.assert_allclose(np.asarray(part), np.asarray(want), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("interpret", [False, True], ids=["ragged_dot", "pallas"])
def test_the_two_arms_are_bit_equal_on_a_load_both_hold(monkeypatch, interpret):
    """The first sum(sizes) rows are the same rows in both buffers. With
    room for one tile the same load overflows and the worst-case arm, the
    one body of a chip that holds everything, gives the same bits."""
    preset = program.deepseek_v2_tiny()
    u = jnp.asarray(np.random.default_rng(5).standard_normal((4, 128, 64)), jnp.float32)
    real, moe = jnp.ones((4, 128), bool), _layer(preset)
    experts_fn = make_grouped_matmul_fn(interpret=interpret)
    combine_fn = make_moe_combine_fn(interpret=interpret)
    sized, count, fits = program._routed(
        preset, moe, u, real, experts_fn, combine_fn=combine_fn
    )
    assert bool(fits) and int(count.sum()) > 256
    monkeypatch.setattr(program, "_CAPACITY_MARGIN", 0.01)
    assert program.slot_capacity(preset, 4 * 128) == 256
    full, count_full, fits = program._routed(
        preset, moe, u, real, experts_fn, combine_fn=combine_fn
    )
    assert not bool(fits) and count_full.tolist() == count.tolist()
    assert np.asarray(sized).tobytes() == np.asarray(full).tobytes()


@pytest.mark.parametrize("over", [0, 1])
def test_a_load_of_exactly_the_buffer_and_of_one_more(over):
    """One held slot a real token (the router's first column), so the
    load is the count of real tokens: 3,840 fill the buffer of 4 x 1,024
    tokens to its last row, 3,841 take the other arm."""
    preset = program.deepseek_v2_tiny()
    rng = np.random.default_rng(6)
    u = jnp.asarray(rng.standard_normal((4, 1024, 64)), jnp.float32)
    moe, u = _to_the_held_group(_layer(preset), u, experts=1)
    capacity = program.slot_capacity(preset, 4 * 1024)
    real = (np.arange(4 * 1024) < capacity + over).reshape(4, 1024)
    part, count, fits = program._routed(
        preset, moe, u, jnp.asarray(real), make_grouped_matmul_fn()
    )
    assert int(count.sum()) == capacity + over == 3840 + over
    assert bool(fits) is (over == 0)
    want = _oracle(preset, moe, u, jnp.asarray(real))
    np.testing.assert_allclose(np.asarray(part), np.asarray(want), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize(
    "preset, rows, length, conditionals, barriers",
    [
        (program.DeepseekV2Config(), 8, 2048, 0, 1),  # the parent's one body
        (program.deepseek_v2(), 8, 2048, 1, 1),  # the barrier is the worst-case arm's
        (_share(0, 16), 4, 128, 0, 0),  # top-3: one pass of three, no barrier
        (program.deepseek_v2_tiny(), 4, 128, 1, 0),
        (program.deepseek_v2_tiny(), 2, 24, 0, 0),  # too small to round
    ],
)
def test_a_chip_that_holds_every_expert_has_no_conditional(
    preset, rows, length, conditionals, barriers
):
    """One body where the two row counts are one, one `cond` where they
    differ. Shapes only: nothing of the published size is made."""
    shapes = program.layer_shapes(preset, preset.first_k_dense)
    moe = {
        "router": jax.ShapeDtypeStruct(shapes["moe/router"], jnp.float32),
        "experts": {
            k: jax.ShapeDtypeStruct(shapes[f"moe/experts/{k}"], jnp.bfloat16)
            for k in ("gate", "up", "down")
        },
    }
    u = jax.ShapeDtypeStruct((rows, length, preset.hidden_size), jnp.float32)
    real = jax.ShapeDtypeStruct((rows, length), bool)
    text = str(jax.make_jaxpr(
        lambda p, u, real: program._routed(preset, p, u, real, make_grouped_matmul_fn())
    )(moe, u, real))
    assert (text.count("cond["), text.count("optimization_barrier")) == (
        conditionals, barriers
    )


def test_the_buffer_counters_follow_the_arm_taken(monkeypatch, tiny):
    """A router that sends every slot to the held group, through the
    embedder: a lone live row of 120 words in a dispatch of 2 x 128 tokens
    already overflows the buffer of 256 rows, so every expert layer of
    every dispatch takes the worst-case arm, and every slot is held."""
    _, _, path = tiny
    monkeypatch.setenv("SPARKDL_TEXT_BUCKETS", "128")
    monkeypatch.setenv("SPARKDL_TEXT_MIN_BUCKET", "128")
    data = {"rows": 6, "vocabulary_words": 300, "word_counts": [[120, 6]]}
    inputs = list(texts.rows(data, np.random.default_rng(1), set()))

    def forced(params):  # the leaves of a weights file are numpy's
        params["embed"][:, 0] = 1000.0
        for i in ("1", "2"):
            router = params["layers"][i]["moe"]["router"]
            router[0, :], router[0, :4] = 0.0, 3.0

    before = _counters()
    _, got = _embed(path, inputs, jnp.float32, False, batch=2, max_length=128, edit=forced)
    delta = {k: v - before.get(k, 0) for k, v in _counters().items()}
    assert got.shape == (6, 64) and np.isfinite(got).all()
    assert delta["moe.buffer_full"] == 6 * 2 and delta.get("moe.buffer_sized", 0) == 0
    assert delta["moe.slots_held"] == delta["moe.slots_routed"] > 6 * 120 * 3 * 2


# -- routing by hand -----------------------------------------------------------


def _route_by_hand(logits, groups=4, keep=2, top=3, scaling=16.0):
    s = np.exp(logits - logits.max())
    s = s / s.sum()
    per = len(s) // groups
    best = [s[g * per : (g + 1) * per].max() for g in range(groups)]
    kept = sorted(range(groups), key=lambda g: -best[g])[:keep]
    masked = np.array([s[e] if e // per in kept else 0.0 for e in range(len(s))])
    chosen = sorted(range(len(s)), key=lambda e: -masked[e])[:top]
    return chosen, [scaling * s[e] for e in chosen]


def test_routing_by_hand():
    """16 experts in 4 groups of which 2, top-3. Group 3 holds the
    second-largest and third-largest scores but its best is under the
    bests of groups 0 and 2, so it is dropped whole: a high-scoring
    expert in a dropped group is not chosen. Weights are 16 x the
    softmax score and do not sum to 16."""
    preset = program.deepseek_v2_tiny()
    logits = np.full(16, -2.0, np.float32)
    logits[1] = 3.0  # group 0: the largest
    logits[9] = 2.6  # group 2: its best
    logits[12], logits[13] = 2.5, 2.4  # group 3: high, but dropped
    logits[2], logits[8] = 1.0, 0.5
    # a router that hands the logits through: u = one-hot rows of an identity
    router = jnp.asarray(np.eye(64, 16, dtype=np.float32))
    u = np.zeros((2, 64), np.float32)
    u[0, :16] = logits
    u[1, :16] = logits[::-1]
    experts, weights = program.route(preset, jnp.asarray(u), router)
    chosen, by_hand = _route_by_hand(logits)
    assert chosen == [1, 9, 2]
    assert 12 not in chosen and 13 not in chosen
    assert np.asarray(experts[0]).tolist() == chosen
    np.testing.assert_allclose(np.asarray(weights[0]), by_hand, rtol=1e-5)
    assert float(weights[0].sum()) != pytest.approx(16.0, rel=0.05)
    assert np.asarray(experts[1]).tolist() == _route_by_hand(logits[::-1])[0]
    # the reference routes the same way
    config = tiny_config()
    r_experts, r_weights = reference.route(config, jnp.asarray(u), router)
    assert np.asarray(r_experts).tolist() == np.asarray(experts).tolist()
    np.testing.assert_allclose(np.asarray(r_weights), np.asarray(weights), rtol=1e-6)


def test_a_pad_token_takes_no_slot():
    preset = program.deepseek_v2_tiny()
    params = program.init_params(preset, 0, jnp.float32)
    moe = params["layers"]["1"]["moe"]
    u = jnp.asarray(np.random.default_rng(1).standard_normal((2, 16, 64)), jnp.float32)
    real = np.ones((2, 16), bool)
    real[0, 5:] = False  # a row of five tokens
    real[1, :] = False  # a row that only fills the batch
    part, count, _ = program._routed(
        preset, moe, u, jnp.asarray(real), make_grouped_matmul_fn()
    )
    experts, _ = program.route(preset, u.reshape(-1, 64), moe["router"])
    held = np.asarray((experts >= 0) & (experts < 4)).reshape(2, 16, 3)
    assert count.tolist() == [int(held[0, :5].sum()), 0]
    assert not np.asarray(part[0, 5:]).any() and not np.asarray(part[1]).any()
    # the real tokens' part is what it is with every token real
    whole, _, _ = program._routed(
        preset, moe, u, jnp.ones((2, 16), bool), make_grouped_matmul_fn()
    )
    np.testing.assert_allclose(np.asarray(part[0, :5]), np.asarray(whole[0, :5]), atol=1e-6)


# -- rotary --------------------------------------------------------------------


def test_yarn_by_hand():
    config = published_config()
    preset = program.deepseek_v2()

    def d(rotations):  # 64 * ln(4096 / (2 pi r)) / (2 ln 10000)
        return 64 * math.log(4096 / (2 * math.pi * rotations)) / (2 * math.log(10000))

    assert (math.floor(d(32)), math.ceil(d(1))) == (10, 23)
    assert reference.yarn_range(config) == program.yarn_range(preset) == (10, 23)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.260804, abs=1e-6)
    assert m * m == pytest.approx(1.589626, abs=1e-6)
    assert preset.softmax_scale == pytest.approx(192**-0.5 * 1.589626, rel=1e-6)
    assert reference.softmax_scale(config) == pytest.approx(preset.softmax_scale)
    f = 10000.0 ** (-np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    by_hand = f * (1 - ramp) + f / 40 * ramp
    np.testing.assert_allclose(program.yarn_inv_freq(preset), by_hand, rtol=1e-6)
    np.testing.assert_allclose(reference.yarn_inv_freq(config), by_hand, rtol=1e-6)
    # the fastest pairs turn as unscaled rope does, the slowest 40 times slower
    assert by_hand[0] == 1.0 and by_hand[31] == pytest.approx(f[31] / 40)


def test_the_programs_pairing_gives_the_sources_scores():
    """The source de-interleaves a rotary vector and rotates halves; the
    program permutes the projection's columns and rotates halves. The
    rotated vectors are the same, and so is every score."""
    config = published_config()
    preset = program.deepseek_v2()
    rng = np.random.default_rng(2)
    length = 48
    q = jnp.asarray(rng.standard_normal((3, length, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((length, 64)), jnp.float32)
    tables = program.rope_tables(preset, length)
    perm = program._deinterleave(64)
    assert perm[:4].tolist() == [0, 2, 4, 6] and perm[32:35].tolist() == [1, 3, 5]
    ours_q = program._rotate(q[..., perm], *tables)
    ours_k = program._rotate(k[..., perm], *tables)
    source_q = reference._rope(config, q, length)
    source_k = reference._rope(config, k, length)
    np.testing.assert_allclose(np.asarray(ours_q), np.asarray(source_q), atol=1e-5)
    scores = lambda a, b: np.einsum("hqd,kd->hqk", np.asarray(a), np.asarray(b))  # noqa: E731
    np.testing.assert_allclose(scores(ours_q, ours_k), scores(source_q, source_k), atol=1e-4)
    # the pair (2i, 2i + 1) of position t turns by t * inv_freq[i]
    t, i = 7, 3
    angle = t * program.yarn_inv_freq(preset)[i]
    x0, x1 = float(q[0, t, 2 * i]), float(q[0, t, 2 * i + 1])
    assert float(source_q[0, t, i]) == pytest.approx(
        x0 * math.cos(angle) - x1 * math.sin(angle), abs=1e-5
    )
    assert float(source_q[0, t, 32 + i]) == pytest.approx(
        x1 * math.cos(angle) + x0 * math.sin(angle), abs=1e-5
    )
    # and a rotation of another pairing does not give these scores
    other = program._rotate(q, *tables)
    assert np.abs(np.asarray(other) - np.asarray(source_q)).max() > 0.1
    # the program leaves a query as [x cos | turn(x) sin] and writes the
    # rotated key twice: the product over both halves is rope(x) . k
    x = q[..., perm]
    split = jnp.concatenate([x * tables[0], program._turn(x) * tables[1]], -1)
    twice = jnp.concatenate([ours_k, ours_k], -1)
    np.testing.assert_allclose(scores(split, twice), scores(source_q, source_k), atol=1e-4)
    # and the projection writes turn(x) itself: x @ turn(w) == turn(x @ w)
    w = jnp.asarray(rng.standard_normal((24, 4 * (16 + 8))), jnp.float32)
    tiny = program.deepseek_v2_tiny()
    wide = program._query_weights(tiny, w).reshape(24, 4, 32)
    c = jnp.asarray(rng.standard_normal((5, 24)), jnp.float32)
    got = jnp.einsum("tr,rhd->thd", c, wide)
    plain = jnp.einsum("tr,rhd->thd", c, w.reshape(24, 4, 24))
    r = plain[..., 16:][..., program._deinterleave(8)]
    np.testing.assert_allclose(np.asarray(got[..., :16]), np.asarray(plain[..., :16]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[..., 16:24]), np.asarray(r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[..., 24:]), np.asarray(program._turn(r)), atol=1e-5)


def test_weights_file_is_read_strictly(tmp_path, tiny):
    config, weights, _ = tiny
    short = {k: np.asarray(v) for k, v in weights.items() if "router" not in k}
    path = str(tmp_path / "short.npz")
    np.savez(path, **short)
    with pytest.raises(ValueError, match="lacks 2 leaves"):
        program.deepseek_v2_model_function("deepseek-v2-tiny", weights_file=path)
    with pytest.raises(ValueError, match="Unknown DeepSeek-V2 size"):
        program.deepseek_v2_model_function("deepseek-v3")
    mf = program.deepseek_v2_model_function("deepseek-v2-tiny", weights_file=tiny[2])
    # the router stays float32 whatever the compute dtype, matrices follow it
    mf16 = program.deepseek_v2_model_function(
        "deepseek-v2-tiny", dtype=jnp.bfloat16, weights_file=tiny[2]
    )
    moe = mf16.params["layers"]["1"]["moe"]
    assert moe["router"].dtype == jnp.float32
    assert moe["experts"]["gate"].dtype == jnp.bfloat16
    assert mf.params["layers"]["0"]["attn"]["q_norm"].dtype == jnp.float32


# -- the family stays what it was beside the second one ------------------------


@pytest.mark.parametrize("normalise", [False, True], ids=["scaled", "renormalised"])
def test_the_first_gate_is_bit_for_bit_what_it_was(normalise):
    """`route` learned DeepSeek-V3.2's gate (sigmoid, a correction bias,
    renormalise AND scale); this family's branch is the parent's
    arithmetic written out again here, op for op: softmax, a group ranked
    by its best, top-k over the kept groups, and the weights EITHER
    renormalised OR scaled."""
    import dataclasses

    preset = dataclasses.replace(program.deepseek_v2_tiny(), norm_topk_prob=normalise)
    rng = np.random.default_rng(7)
    u = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((64, 16)) * 0.25, jnp.float32)
    experts, weights = program.route(preset, u, router)
    logits = jnp.einsum("ti,io->to", u, router, precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, -1)
    best = scores.reshape(-1, 4, 4).max(-1)
    _, kept = jax.lax.top_k(best, 2)
    keep = jnp.any(kept[..., None] == jnp.arange(4), -2)
    scores = jnp.where(jnp.repeat(keep, 4, -1), scores, 0.0)
    want, chosen = jax.lax.top_k(scores, 3)
    if normalise:
        want = want / (want.sum(-1, keepdims=True) + 1e-20)
    else:
        want = want * 16.0
    assert np.asarray(experts).tolist() == np.asarray(chosen).tolist()
    assert (np.asarray(weights).view(np.uint32) == np.asarray(want).view(np.uint32)).all()
    total = np.asarray(weights).sum(-1)
    assert np.allclose(total, 1.0, atol=1e-6) == normalise
    # the family's own key takes this branch, with or without the bias argument
    assert preset.scoring_func == "softmax" and preset.worst_case_chunk_rows is None
    again = program.route(preset, u, router, None)
    assert (np.asarray(again[1]) == np.asarray(weights)).all()


#: sha256 (16 hex digits) of the text of `jax.make_jaxpr` of the tiny
#: preset's program, by dtype and batch shape: the one taken at commit
#: 3c6d674 (before the second family shared this module's functions),
#: with one edit since, made on purpose: the combine moved to
#: `ops/moe_combine.py`, which takes the slots not held as -1 (the gather
#: form reads row 0 for them where it read the buffer's last, and masks it
#: as it did);
#: `test_the_embeddings_are_the_parents_combines_to_the_bit` holds the
#: embeddings to the earlier loop's. They change with jax's version too:
#: then take them again from a checkout of the commit that made that
#: edit, not from a later tree.
PARENT_JAXPR = {
    ("float32", (2, 32)): "dfc1652eaa7a5783",
    ("float32", (4, 128)): "87e3603b8ac5b59b",
    ("bfloat16", (2, 32)): "e7a8c4b907bc2b11",
    ("bfloat16", (4, 128)): "9172e2fa189fb204",
}


@pytest.mark.parametrize("dtype, shape", sorted(PARENT_JAXPR), ids=str)
def test_the_programs_jaxpr_is_the_parents(dtype, shape):
    """`_mla` was split for the family that selects its keys, `route` and
    `_routed` learned its gate and its worst-case arm in passes: this
    family's program is to the letter the one it was (no indexer, no
    second gate, no scan of passes)."""
    import hashlib

    mf = program.deepseek_v2_model_function("deepseek-v2-tiny", dtype=jnp.dtype(dtype))
    text = str(jax.make_jaxpr(mf.fn)(mf.params, jnp.zeros(shape, jnp.int32)))
    assert "scan[" not in text and "i8[" not in text and "bitcast_convert_type" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_JAXPR[dtype, shape]


def _parents_combine(y, slot, weights):
    """The combine as `_experts_and_combine` wrote it before
    `ops/moe_combine.py`, op for op: every slot's row gathered (the
    buffer's last for a slot not held), weighted, masked and added in
    slot order, three parts a pass where the buffer holds every slot."""
    rows, top_k = y.shape[0], slot.shape[1]
    held = slot >= 0
    at = jnp.where(held, slot, rows - 1)
    at_once = 3 if rows >= slot.size else top_k
    out = jnp.zeros((slot.shape[0], y.shape[1]), jnp.float32)
    for j in range(top_k):
        part = y[at[:, j]] * weights[:, j, None]
        out = out + jnp.where(held[:, j, None], part, 0.0)
        if (j + 1) % at_once == 0 and j + 1 < top_k:
            y, out = jax.lax.optimization_barrier((y, out))
    return out


@pytest.mark.parametrize("forced", [False, True], ids=["sized", "worst_case"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_embeddings_are_the_parents_combines_to_the_bit(tiny, monkeypatch, dtype, forced):
    """The program built off the TPU (`gather_combine`) and the same
    program with the combine loop it had before, on either arm: every
    row's embedding and counters alike, to the bit."""
    _, _, path = tiny
    if forced:
        monkeypatch.setattr(program, "_CAPACITY_MARGIN", 0.01)

    def built(combine_fn=None):
        return program.deepseek_v2_model_function(
            "deepseek-v2-tiny", dtype=dtype, weights_file=path, combine_fn=combine_fn
        )

    ids = np.random.default_rng(1).integers(1, 512, (4, 128)).astype(np.int32)
    ids[3, 70:] = 0
    new, old = built(), built(_parents_combine)
    assert new.combine == "gather" and old.combine == "custom"
    got = np.asarray(new.fn(new.params, jnp.asarray(ids)))
    want = np.asarray(old.fn(old.params, jnp.asarray(ids)))
    assert np.isfinite(got).all() and np.abs(got[:, :64]).min(1).min() > 0
    np.testing.assert_array_equal(got, want)
    # the counters' columns: the two expert layers took the arm asked for
    arm = -1 if forced else -2
    assert (got[:, arm] == 2).all() and (got[:, -3 - arm] == 0).all()


# -- the attention is handed its rows' lengths -----------------------------------


def _without_lengths(kernel):
    """The same kernel with `takes_lengths` taken away: what the parent
    commit built."""

    def blind(q, kv, k_rope, dtype, selection=None):
        return kernel(q, kv, k_rope, dtype, selection)

    blind.kind, blind.pairs_computed = kernel.kind, kernel.pairs_computed
    blind.query_blocks = kernel.query_blocks
    return blind


def _uneven_batch(length=256):
    """A row of zeros, and rows of 10, 65, 130 and `length` tokens (those
    the bucket holds)."""
    lengths = [n for n in (0, 10, 65, 130) if n < length] + [length]
    ids = np.zeros((len(lengths), length), np.int32)
    for row, n in enumerate(lengths):
        ids[row, :n] = np.random.default_rng(row).integers(1, 512, n)
    return jnp.asarray(ids)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_lengths_change_no_embedding_to_the_bit(tiny, dtype):
    """The tiny preset built with the interpreted kernel: with the rows'
    lengths handed over (the query blocks of padding alone not run, zeros
    in their place) every row's embedding and counters are, to the bit,
    those of the same kernel without `takes_lengths`."""
    _, _, path = tiny
    preset = program.deepseek_v2_tiny()
    kernel = make_latent_attention_fn(
        preset.num_heads, preset.softmax_scale, block=64, interpret=True
    )
    assert kernel.takes_lengths

    def built(attention_fn):
        return program.deepseek_v2_model_function(
            "deepseek-v2-tiny", dtype=dtype, weights_file=path,
            attention_fn=attention_fn, experts_fn=make_grouped_matmul_fn(),
        )

    ids = _uneven_batch()
    given, blind = built(kernel), built(_without_lengths(kernel))
    assert "i32[5]" in str(jax.make_jaxpr(given.fn)(given.params, ids)).split("pallas_call")[1]
    got = np.asarray(given.fn(given.params, ids))
    want = np.asarray(blind.fn(blind.params, ids))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    assert not got[0, :64].any() and np.abs(got[1:, :64]).min(1).max() > 0


def test_a_row_is_as_long_as_its_last_real_position():
    """Not the count of its real tokens: an id 0 inside a text could
    never cut a row short. Nothing is computed for an attention that
    does not say it takes lengths."""
    real = jnp.asarray(
        [[1, 1, 0, 1, 0, 0], [0, 0, 0, 0, 0, 0], [1, 1, 1, 1, 1, 1], [0, 0, 1, 0, 0, 0]], bool
    )

    def takes(*a, **k):
        raise AssertionError("not called")

    assert program.row_lengths(takes, real) == {}
    takes.takes_lengths = True
    (name, lengths), = program.row_lengths(takes, real).items()
    assert name == "lengths" and lengths.dtype == jnp.int32
    assert lengths.tolist() == [4, 0, 6, 3]


def test_an_attention_of_four_arguments_still_builds_and_runs(tiny):
    """What stands in for `attention_fn` in a test or a planted fault
    takes (q, kv, k_rope, dtype) and no more."""
    from sparkdl_tpu.ops.flash_attention import dense_latent_attention

    _, _, path = tiny
    preset = program.deepseek_v2_tiny()
    calls = []

    def four(q, kv, k_rope, dtype):
        calls.append(q.shape)
        return dense_latent_attention(
            q, kv, k_rope, dtype, num_heads=preset.num_heads, scale=preset.softmax_scale
        )

    mf = program.deepseek_v2_model_function(
        "deepseek-v2-tiny", weights_file=path, attention_fn=four,
        experts_fn=make_grouped_matmul_fn(),
    )
    plain = program.deepseek_v2_model_function("deepseek-v2-tiny", weights_file=path)
    ids = _uneven_batch(128)
    got = np.asarray(mf.fn(mf.params, ids))
    assert len(calls) == 3 and mf.attention == "custom"
    np.testing.assert_array_equal(got, np.asarray(plain.fn(plain.params, ids)))
    # it says nothing of what it runs: no attention counter
    assert mf.batch_counters(np.asarray(ids), np.asarray(ids) != 0) == {}


#: the ten live rows of `deepseek-v3.2-exp-embed-long-docs` (tokens =
#: words + 2), by bucket: 87 of their 120 query blocks of 1,024 hold a
#: real token
CLAIMED_CELL = {
    8192: (2690, 3797, 4343, 5361, 6453),
    16384: (8408, 10121, 12494, 14172, 16384),
}


@pytest.mark.parametrize("takes_lengths", [True, False])
def test_attention_counters_of_the_claimed_cells_job_by_hand(takes_lengths):
    """A job's ten dispatches of one row, 5 layers, blocks of 1,024 in
    sub-tiles of 256: a block under the diagonal counts 1 and a diagonal
    block 0.625 of 1,024 x 1,024 pairs."""
    kernel = make_latent_attention_fn(128, 0.1353, block=1024, interpret=True)
    if not takes_lengths:
        kernel = _without_lengths(kernel)
    total = {}
    for edge, lengths in CLAIMED_CELL.items():
        for n in lengths:
            ids = np.zeros((1, edge), np.int32)
            ids[0, :n] = 7
            ids[0, n // 2] = 0  # an id 0 inside the text
            counted = program.attention_batch_counters(kernel, 5, ids, ids != 0)
            for name, count in counted.items():
                total[name] = total.get(name, 0) + count
    steps = {True: 70.625 + 408.75, False: 5 * 33 + 5 * 130}[takes_lengths]
    assert total == {
        "mla.pairs_computed": 5 * int(steps * 1024 * 1024),
        "mla.query_blocks": 5 * 120,
        "mla.query_blocks_run": 5 * (87 if takes_lengths else 120),
    }


def test_attention_counters_of_a_batch_with_rows_of_padding():
    """`deepseek-v2-embed-windows`' kind of dispatch: 8 rows of 2,048 of
    which 3 only fill the batch, and a live row never has a query block
    of padding alone."""
    kernel = make_latent_attention_fn(128, 0.1147, block=1024, interpret=True)
    ids = np.zeros((8, 2048), np.int32)
    for row, n in enumerate((2048, 1025, 1500, 2048, 1026)):
        ids[row, :n] = 3
    counted = program.attention_batch_counters(kernel, 5, ids, ids != 0)
    assert counted == {
        "mla.pairs_computed": 5 * 5 * int(2.25 * 1024 * 1024),
        "mla.query_blocks": 5 * 8 * 2,
        "mla.query_blocks_run": 5 * 5 * 2,
    }
    dense = make_latent_attention_fn(128, 0.1147)
    assert program.attention_batch_counters(dense, 5, ids, ids != 0) == {
        "mla.pairs_computed": 5 * 8 * 2048 * 2048
    }
