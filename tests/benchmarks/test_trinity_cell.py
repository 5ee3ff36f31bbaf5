"""The Trinity-Mini cell's counts against a hand count at the published
widths and against XLA's `cost_analysis()` of the reference's parts; the
cell end to end through the harness on the CPU, its configuration
dropped in at the tiny preset's size; and `correct` able to come out
false: the float8 control and five faults planted in the program
(`afmoe_tiny.py`: the window ignored, the output gate ignored, rotary on
the full layer, QK-norm dropped, the shared expert dropped) fail the
cell's own limits, by a number named here.

The tiny preset runs the cell's rows at 1/32 of their lengths (84 to 512
words, buckets 256 and 512, one row a dispatch, a window of 16): rows of
16,384 tokens would hold a dense score square of a gigabyte a layer on
the CPU. At 64 wide, a row's rounding error is larger than the chip's at
2,048 wide, so the program as it is is held in ratio here: under a third
of what the control and every fault read, and those fail the cell's
limits outright. The published widths are never built on the CPU."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import afmoe_tiny  # noqa: E402
from afmoe_tiny import published_config, tiny_config, write_weights  # noqa: E402
from bench_checkout import ROOT, job_lengths_by_edge, make_checkout, run_cell  # noqa: E402

sys.path.insert(0, ROOT)

from benchmarks import compare, traffic_gen  # noqa: E402
from benchmarks.counts import afmoe as counts  # noqa: E402
from benchmarks.data import texts  # noqa: E402
from benchmarks.reference import afmoe as reference  # noqa: E402

CELL = "trinity-mini-embed-long-docs"


def _json(*parts):
    with open(os.path.join(ROOT, "benchmarks", *parts)) as f:
        return json.load(f)


# -- the counts, by hand -------------------------------------------------------

H, HEADS, KV, D = 2048, 32, 4, 128
Q = HEADS * D  # 4,096: q, the output gate and o
ATTENTION = 3 * H * Q + 2 * H * KV * D  # 27,262,976
EXPERT = 3 * H * 1024  # 6,291,456; the one shared expert is as wide
DENSE_MLP = 3 * H * 6144
ROUTER = H * 128
VECTORS = 4 * H + 2 * D  # a layer's four norms and QK-norm's two
VOCAB = 200192


def test_parameters_are_the_cut_3_83_billion():
    config = published_config()
    assert ATTENTION == 27_262_976 == counts.attention_params(config)
    assert EXPERT == 6_291_456 == counts.expert_params(config)
    assert counts.layer_params(config) == (
        ATTENTION + DENSE_MLP, ATTENTION + 129 * EXPERT + ROUTER
    )
    # the issue's arithmetic: q, gate and o 8.39 M each, k and v 1.05 M each
    assert 2048 * 4096 == 8_388_608 and 2048 * 512 == 1_048_576
    dense = ATTENTION + DENSE_MLP + VECTORS
    expert = ATTENTION + 129 * EXPERT + ROUTER + 128 + VECTORS  # the bias of 128
    assert dense == pytest.approx(65.0e6, rel=1e-3)
    assert expert == pytest.approx(839.1e6, rel=1e-3)
    assert 128 * EXPERT == pytest.approx(805.3e6, rel=1e-3)
    total = sum(int(np.prod(s)) for s in reference.weight_shapes(config).values())
    by_hand = dense + 4 * expert + VOCAB * H + H  # the embedding, the final norm
    assert total == by_hand
    assert total == pytest.approx(3_831.5e6, rel=1e-4)
    assert 2 * total == pytest.approx(7.66e9, rel=1e-3)  # bytes in bfloat16
    assert 2 * total / 17.18e9 == pytest.approx(0.446, abs=1e-3)  # of the chip
    # a fifth expert layer would leave the 16,384 row's temporaries too little
    assert 2 * (total + expert) == pytest.approx(9.34e9, rel=2e-3)
    # the untied head, not built on the embed path
    assert VOCAB * H == pytest.approx(410.0e6, rel=1e-3)


PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True,
    "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
}


def test_the_file_holds_every_number_of_the_catalogs_row():
    """The published config as the catalog beside the `model-configs`
    guide has it, every key but the three reduced ones unchanged; the cut
    is published layers 1-5."""
    config = published_config()
    differs = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "layer_types", "num_dense_layers", "num_hidden_layers",
    ]
    assert all(config["published"][k] == PUBLISHED[k] for k in config["reduced"])
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (5, 1)
    assert config["layer_types"] == PUBLISHED["layer_types"][1:6] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention",
    ]
    assert config["experts_held"] == [0, 128]
    assert "pipeline" in config["deployment"] and "seven" in config["deployment"]
    assert len(config["assumed"]) >= 10
    assert config["family"] == "afmoe" and config["max_length"] == 16384
    assert config["env"]["SPARKDL_TEXT_BUCKETS"] == "8192,16384"


C = counts.causal_pairs
PAIR = 2 * 2 * D * HEADS  # operations a pair and layer, every query head


@pytest.mark.parametrize(
    "length, window_pairs",
    [
        (2048, C(2048)),  # within the window a sliding layer is causal
        (2690, C(2048) + 642 * 2048),
        (16384, C(2048) + 14336 * 2048),
        (16, C(16)),
    ],
)
def test_a_sliding_layer_keeps_the_pairs_within_its_window(length, window_pairs):
    config = published_config()
    assert counts.window_pairs(config, length) == window_pairs
    assert counts.window_pairs(config, length) <= C(length)


def _job_of_the_cell():
    """A job's ten live rows by edge, as the driver's `work` gives them."""
    by_edge = job_lengths_by_edge("embed-long-docs", (8192, 16384))
    return {"rows": 10, "rows_by_length": {"8192": 5, "16384": 5}, "lengths_by_edge": by_edge}


def test_a_job_of_the_cell_is_about_100_tflop():
    """The issue's reckoning: 49.2 TFLOP of projections, dense MLP and
    shared experts at the dispatched tokens, 33.9 of routed experts at
    the real tokens' slots, 17.4 of attention at real lengths (37.3 with
    every layer full); a sliding layer keeps 151.5 M of the 454.7 M
    causal pairs, DSA's count in the long-documents cell to the pair."""
    config = published_config()
    job = _job_of_the_cell()
    real = sum(n * rows for of in job["lengths_by_edge"].values() for n, rows in of.items())
    assert real == 84_223
    assert counts.sliding_pairs(config, job) == 151_527_424
    assert counts.full_pairs(config, job) == 454_745_446
    assert counts.sliding_pairs(config, job) / counts.full_pairs(config, job) == pytest.approx(
        0.333, abs=1e-3
    )
    dense = 122_880 * counts.flops_per_token_dense_parts(config)
    assert dense == pytest.approx(49.2e12, rel=2e-3)
    slots = real * 8 * 4
    routed = slots * 2.0 * EXPERT
    assert routed == pytest.approx(33.9e12, rel=2e-3)
    attention = counts.score_flops(config, job)
    assert attention == pytest.approx(
        (4 * 151_527_424 + 454_745_446) * PAIR
    ) == pytest.approx(17.4e12, rel=2e-3)
    assert 5 * 454_745_446 * PAIR == pytest.approx(37.3e12, rel=2e-3)
    measured = dict(job, slots_held=slots)
    assert counts.forward_flops(config, measured) == pytest.approx(dense + routed + attention)
    assert counts.forward_flops(config, measured) == pytest.approx(100.5e12, rel=5e-3)
    # the routed experts a third of it, attention of two kinds a sixth
    assert routed / counts.forward_flops(config, measured) == pytest.approx(0.34, abs=0.01)
    assert attention / counts.forward_flops(config, measured) == pytest.approx(0.17, abs=0.01)
    # without the measured slots, every dispatched token's k
    assert counts.slots_held(config, job) == 122_880 * 8 * 4


def test_kernel_work_of_the_window_and_the_grouped_product():
    config = published_config()
    job = _job_of_the_cell()
    flops, bytes_ = counts.kernel_work(config, "flash_attention_window", job)
    assert flops == pytest.approx(4 * 151_527_424 * PAIR)
    # q and the result of 32 heads, k and v of 4, 2 bytes each, a
    # dispatched token and sliding layer
    assert bytes_ == pytest.approx(4 * 122_880 * (2 * HEADS + 2 * KV) * D * 2)
    # operations bound it: the window's pairs outlast its bytes
    assert flops / 197e12 > bytes_ / 819e9
    measured = dict(job, slots_held=1000, dispatches=10)
    flops, bytes_ = counts.kernel_work(config, "moe_grouped_matmul", measured)
    assert flops == pytest.approx(2 * 1000 * EXPERT)
    rows = 1000 * ((2 * H + 1024) * 2 + (2 * 1024 + H) * 4)
    assert bytes_ == pytest.approx(rows + 10 * 4 * 128 * EXPERT * 2)
    assert counts.kernel_work(config, "flash_attention", job) is None
    assert counts.KERNELS == ("flash_attention_window", "moe_grouped_matmul")
    unknown = dict(job, pairs_unknown="text.tokens disagrees")
    assert counts.forward_flops(config, unknown) is None
    assert counts.kernel_work(config, "flash_attention_window", unknown) is None


def _xla_flops(fn, *shapes):
    cost = jax.jit(fn).lower(*shapes).cost_analysis()
    return float((cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"])


@pytest.mark.parametrize("sliding", [True, False], ids=["sliding", "full"])
@pytest.mark.parametrize("length", [256, 1024])
def test_counts_against_xla_cost_analysis_of_the_reference(length, sliding):
    """The reference's attention block (projections, the gate, scores)
    lowered for the CPU at the published widths from shapes alone. XLA
    counts a loop's body once: of the loop over blocks of queries, one
    block's products against the whole row of keys (the reference masks
    the window; the count leaves what it masks out). Norms, rotary,
    softmax and the sigmoid lie within 2%."""
    config = published_config()
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32)  # noqa: E731
    shapes = reference.layer_shapes(config, 1)
    attend = {k: f32(*s) for k, s in shapes.items() if k.startswith(("attn/", "norm_"))}
    items = reference._scalars(config)
    xla = _xla_flops(
        functools.partial(reference._attend.__wrapped__, items, sliding, precision="highest"),
        attend, f32(1, length, H),
    )
    block = min(length, reference.QUERY_BLOCK)
    ours = length * 2 * ATTENTION + block * length * PAIR
    assert ours == pytest.approx(xla, rel=0.02)
    assert ours <= xla
    work = {"rows_by_length": {str(length): 1}, "slots_held": 0}
    assert counts.flops_per_token_dense_parts(config) == pytest.approx(
        2 * (5 * ATTENTION + DENSE_MLP + 4 * (EXPERT + ROUTER))
    )
    assert counts.forward_flops(config, work) == pytest.approx(
        length * counts.flops_per_token_dense_parts(config)
        + (4 * counts.window_pairs(config, length) + C(length)) * PAIR
    )


def test_weights_are_made_leaf_by_leaf_in_two_bytes():
    config = tiny_config()
    made = reference.make_weights(config, 1)
    assert {k: v.shape for k, v in made.items()} == reference.weight_shapes(config)
    bias = reference.from_bits(np.asarray(made["layers/1/moe/router_bias"]))
    assert bias.shape == (16,) and 0 < np.abs(bias.astype(np.float32)).max() <= 0.05
    embed = reference.from_bits(np.asarray(made["embed"])).astype(np.float32)
    # variance 1 / hidden: sqrt(hidden) gives the stream unit variance
    assert float(np.var(embed) * 64) == pytest.approx(1.0, rel=0.05)
    gain = reference.from_bits(np.asarray(made["layers/0/attn/q_norm"])).astype(np.float32)
    assert 0.8 <= gain.min() and gain.max() <= 1.2
    leaf = made["layers/1/moe/experts/gate"]
    assert leaf._bits is None and leaf.shape == (16, 64, 32)
    assert np.asarray(leaf).dtype == np.uint16
    assert "layers/0/moe/router" not in made  # the dense layer has no router


# -- the cell through the harness, its configuration at the tiny size ----------


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    """`benchmarks.run --rehearse-cpu --trace 1` of the cell with its
    configuration file replaced by the tiny preset's (float32, buckets 64
    and 128, rows of 20-100 words): the family's reference, counts and
    readers are found by name, the answers agree, the program's counters
    square with the benchmark's own account of the rows, and no device
    metric is written."""
    checkout = make_checkout(tmp_path / "checkout")
    config = dict(
        tiny_config(max_length=128), compute_dtype="float32",
        entry=dict(published_config()["entry"], model="trinity-mini-tiny"),
        env={"SPARKDL_TEXT_BUCKETS": "64,128", "SPARKDL_TEXT_MIN_BUCKET": "64"},
    )
    with open(os.path.join(checkout, "benchmarks", "configs", "trinity-mini.json"), "w") as f:
        json.dump(config, f)
    rc, last, err = run_cell(
        checkout, "--workload", CELL, "--seed", str(2**31 + 39), "--seconds", "0.5",
        "--trace", "1", "--rehearse-cpu",
    )
    assert rc == 0, err[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["compared"]["row_err_max"]["value"] < 1e-5
    assert last["work_check"]["ok"] is True
    assert set(last["metrics"]) == {"feeder.pad_rows_pct", "text.pad_tokens_pct"}
    assert "rehearsal" in last


# -- `correct` can come out false ----------------------------------------------

ROWS = 10
LIMITS = _json("limits", f"{CELL}.json")


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """Ten rows of a thirty-second of the cell's lengths on the tiny
    preset, and the reference's answers at the stated precision."""
    config = tiny_config(max_length=512)
    path = str(tmp_path_factory.mktemp("trinity") / "tiny.npz")
    weights = write_weights(path, config)
    data = _json("traffic", "embed-long-docs.json")["data"]
    histogram = [[max(1, words // 32), weight] for words, weight in data["word_counts"]]
    data = dict(data, rows=ROWS, null_rows=0, word_counts=histogram, vocabulary_words=300)
    inputs = list(traffic_gen.make_rows(data, 2**31 + 39))
    assert len(inputs) == ROWS and min(len(t.split()) for t in inputs) >= 80
    ref = reference.outputs(config, weights, inputs)
    return config, weights, path, inputs, ref


def _numbers(got, ref):
    return {
        "rows_misplaced": 0,
        "rows_mismatched": compare.rows_mismatched(got, ref),
        **compare.error_numbers(compare.row_errors(got, ref)),
    }


def _decide(got, ref):
    return compare.decide(_numbers(got, ref), LIMITS["limits"])


def _embed(job, fault=None):
    """The job's rows through `TextEmbedder`, one row a dispatch in two
    buckets as the cell has them, with `fault` planted in the program
    while it is built and traced."""
    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.models import afmoe
    from sparkdl_tpu.transformers.text import TextEmbedder

    _, _, path, inputs, _ = job
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("SPARKDL_TEXT_BUCKETS", "256,512")
        patch.setenv("SPARKDL_TEXT_MIN_BUCKET", "256")
        if fault:
            fault(patch.setattr)
        mf = afmoe.afmoe_model_function(
            "trinity-mini-tiny", dtype=jnp.bfloat16, weights_file=path
        )
        out = TextEmbedder(
            inputCol="in", outputCol="out", modelFunction=mf, maxLength=512,
            batchSize=1,
        ).transform(DataFrame.fromColumns({"in": inputs}, numPartitions=2)).collect()
    return np.stack([np.asarray(r["out"], np.float32) for r in out])


@pytest.fixture(scope="module")
def answers(job):
    return _embed(job)


def test_the_cell_holds_the_median_the_ninth_decile_and_both_counts():
    limits = LIMITS["limits"]
    assert limits["rows_misplaced"] == 0 and limits["rows_mismatched"] == 0
    assert 0 < limits["row_err_median"] <= limits["row_err_p90"] < 0.3
    for name in ("row_err_median", "row_err_p90"):
        assert LIMITS["set_from"][name]["held_by"] == "float8"
    assert "window_ignored" in LIMITS["set_from"]["where"]


def test_the_float8_control_fails_and_the_program_lies_far_under_it(job, answers):
    config, weights, _, inputs, ref = job
    assert reference.CONTROL_PRECISION[config["compute_dtype"]] == "float8"
    low = reference.outputs(config, weights, inputs, precision="float8")
    decided = _decide(low, ref)
    assert decided["row_err_median"]["ok"] is False and decided["row_err_p90"]["ok"] is False
    assert compare.all_ok(_decide(ref, ref))
    mine, theirs = _numbers(answers, ref), _numbers(low, ref)
    assert mine["rows_mismatched"] == 0
    for name in ("row_err_median", "row_err_p90"):
        assert 3 * mine[name] < theirs[name], (name, mine, theirs)


@pytest.mark.parametrize("fault", afmoe_tiny.FAULTS)
def test_fault_in_every_row_is_caught(job, answers, fault):
    broken = _embed(job, getattr(afmoe_tiny, fault))
    decided = _decide(broken, job[4])
    mine, theirs = _numbers(answers, job[4]), _numbers(broken, job[4])
    for name in ("row_err_median", "row_err_p90"):
        assert decided[name]["ok"] is False, decided
        assert theirs[name] > 3 * mine[name], (name, mine, theirs)


def test_fault_in_a_few_rows_is_caught(job, answers):
    """Two answers given to each other's rows: the count of mismatched
    rows, which the median cannot see."""
    swapped = answers.copy()
    swapped[[0, 1]] = answers[[1, 0]]
    decided = _decide(swapped, job[4])
    assert decided["rows_mismatched"]["value"] == 2
    assert not decided["rows_mismatched"]["ok"] and not compare.all_ok(decided)
