"""The DeepSeek-V2 cell's counts against a hand count at the published
widths and against XLA's `cost_analysis()` of the reference's parts, and
`correct` able to come out false: the control (the reference with float8
operands), three faults planted in every row of the program's answers
(`deepseek_v2_tiny.py`) and two in a few rows each fail the cell's own
limits, by a number named here, on the tiny preset at the cell's own
lengths (full windows of 2,048 tokens and remainders, two buckets). The
second control (the router's operands in bfloat16) is a reading and held
by no limit. The published widths are never built on the CPU."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_checkout import ROOT, job_lengths_by_edge  # noqa: E402
from deepseek_v2_tiny import (  # noqa: E402
    an_expert_slot_dropped,
    published_config,
    rotary_key_not_rotated,
    tiny_config,
    weights_renormalised,
    write_weights,
)

sys.path.insert(0, ROOT)

from benchmarks import compare, traffic_gen  # noqa: E402
from benchmarks.counts import deepseek_v2 as counts  # noqa: E402
from benchmarks.reference import deepseek_v2 as reference  # noqa: E402

CELL = "deepseek-v2-embed-windows"


def _json(*parts):
    with open(os.path.join(ROOT, "benchmarks", *parts)) as f:
        return json.load(f)


# -- the counts, by hand -------------------------------------------------------

H, HEADS, RQ, RKV = 5120, 128, 1536, 512
MLA = H * RQ + RQ * HEADS * 192 + H * 576 + RKV * HEADS * 256 + HEADS * 128 * H
EXPERT = 3 * H * 1536  # 23,592,960
SHARED = 2 * EXPERT  # one SwiGLU MLP of 3,072
DENSE_MLP = 3 * H * 12288  # 188,743,680
ROUTER = H * 160
VECTORS = 2 * H + RQ + RKV  # a layer's four norms


def test_parameters_are_the_cut_5_03_billion():
    config = published_config()
    assert MLA == 149_225_472 == counts.attention_params(config)
    assert counts.layer_params(config) == (
        MLA + DENSE_MLP, MLA + SHARED + ROUTER + 40 * EXPERT
    )
    assert MLA + DENSE_MLP == 337_969_152
    assert MLA + SHARED + ROUTER + 40 * EXPERT == 1_140_948_992
    total = sum(int(np.prod(s)) for s in reference.weight_shapes(config).values())
    by_hand = (
        (MLA + DENSE_MLP + VECTORS)
        + 4 * (MLA + SHARED + ROUTER + 40 * EXPERT + VECTORS)
        + 25600 * H  # the embedding's slice; the untied head is not built
        + H  # the final norm
    )
    assert total == by_hand
    assert total == pytest.approx(5032.9e6, rel=2e-5)
    assert 2 * total == pytest.approx(10.07e9, rel=1e-3)  # bytes in bfloat16
    # whole, an expert layer is 3,972 M: a chip cannot hold two
    assert MLA + SHARED + ROUTER + 160 * EXPERT == pytest.approx(3972e6, rel=1e-3)
    # the file states every published width and the cut
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 60, "n_routed_experts": 160, "vocab_size": 102400,
    }
    assert (config["num_hidden_layers"], config["n_routed_experts"]) == (5, 40)
    assert config["experts_held"] == [0, 40] and config["vocab_size"] == 25600


def test_forward_operations_are_2_96_gflop_a_token_at_2048():
    config = published_config()
    work = {"rows": 1, "rows_by_length": {"2048": 1}}
    scores = 2048 * (192 + 128) * HEADS  # two products over half the square
    routed = 6 * 40 / 160 * 2 * EXPERT  # 1.5 slots a token, expected
    per_token = (
        5 * (2 * MLA + scores) + 2 * DENSE_MLP + 4 * (2 * SHARED + 2 * ROUTER + routed)
    )
    assert counts.forward_flops(config, work) == pytest.approx(2048 * per_token)
    assert per_token == pytest.approx(2.96e9, rel=2e-3)
    share = lambda part: part / per_token  # noqa: E731
    assert share(5 * 2 * MLA) == pytest.approx(0.50, abs=0.01)
    assert share(5 * scores) == pytest.approx(0.14, abs=0.01)
    assert share(4 * 2 * SHARED) == pytest.approx(0.13, abs=0.01)
    assert share(2 * DENSE_MLP) == pytest.approx(0.13, abs=0.01)
    assert share(4 * routed) == pytest.approx(0.10, abs=0.01)
    job = {"rows": 60, "rows_by_length": {"1024": 9, "2048": 51}}
    assert counts.forward_flops(config, job) == pytest.approx(
        9 * counts.forward_flops(config, {"rows_by_length": {"1024": 1}})
        + 51 * counts.forward_flops(config, {"rows_by_length": {"2048": 1}})
    )
    # the routed experts at the measured slots where `work` carries them
    measured = dict(work, slots_held=2048 * 4 * 2)  # two slots a token and layer
    assert counts.forward_flops(config, measured) - counts.forward_flops(
        config, work
    ) == pytest.approx(2048 * 4 * 0.5 * 2 * EXPERT)


def test_kernel_work_of_both_kernels():
    config = published_config()
    work = {"rows": 5, "rows_by_length": {"1024": 2, "2048": 3}}
    tokens = 2 * 1024 + 3 * 2048
    flops, bytes_ = counts.kernel_work(config, "flash_attention", work)
    assert flops == pytest.approx(5 * HEADS * (192 + 128) * (2 * 1024**2 + 3 * 2048**2))
    # q and k of 192, v and the result of 128, 2 bytes each, once
    assert bytes_ == pytest.approx(tokens * 5 * HEADS * (192 + 192 + 128 + 128) * 2)
    assert flops < counts.forward_flops(config, work)
    measured = dict(work, slots_held=1000, dispatches=2)
    flops, bytes_ = counts.kernel_work(config, "moe_grouped_matmul", measured)
    assert flops == pytest.approx(2 * 1000 * 3 * H * 1536)
    rows = 1000 * ((2 * H + 1536) * 2 + (2 * 1536 + H) * 4)
    matrices = 2 * 4 * 40 * EXPERT * 2  # each dispatch and expert layer, once
    assert bytes_ == pytest.approx(rows + matrices)
    # without the measurement: the expectation, and no matrices
    flops, bytes_ = counts.kernel_work(config, "moe_grouped_matmul", work)
    assert flops == pytest.approx(tokens * 1.5 * 4 * 2 * EXPERT)
    assert counts.kernel_work(config, "selective_scan", work) is None


def test_attention_pairs_are_those_of_the_rows_real_lengths():
    """Two windows dispatched at 2,048 (one full, one of 1,500 tokens)
    and a remainder of 300 at 1,024: the scores at half the square of
    the real lengths, everything a token at the edges."""
    config = published_config()
    at_edges = {"rows": 3, "rows_by_length": {"1024": 1, "2048": 2}, "slots_held": 0}
    work = dict(at_edges, lengths_by_edge={"1024": {300: 1}, "2048": {2048: 1, 1500: 1}})
    squares = 300**2 + 2048**2 + 1500**2
    scores = 5 * HEADS * (192 + 128) * squares
    assert counts.score_flops(config, work) == pytest.approx(scores)
    tokens = 1024 + 2 * 2048
    per_token = 2 * (5 * MLA + DENSE_MLP + 4 * (SHARED + ROUTER))
    assert counts.forward_flops(config, work) == pytest.approx(tokens * per_token + scores)
    flops, bytes_ = counts.kernel_work(config, "flash_attention", work)
    assert flops == pytest.approx(scores)
    assert bytes_ == pytest.approx(tokens * 5 * HEADS * 640 * 2)  # a dispatched token
    # without real lengths the rows are as long as their edges, as before
    full = dict(at_edges, lengths_by_edge={"1024": {1024: 1}, "2048": {2048: 2}})
    assert counts.forward_flops(config, at_edges) == counts.forward_flops(config, full)
    assert counts.score_flops(config, at_edges) == pytest.approx(
        5 * HEADS * 320 * (1024**2 + 2 * 2048**2)
    )
    # real lengths that could not be squared with the counters: no pair term
    unknown = dict(at_edges, pairs_unknown="text.tokens disagrees")
    assert counts.forward_flops(config, unknown) is None
    assert counts.kernel_work(config, "flash_attention", unknown) is None
    assert counts.kernel_work(config, "moe_grouped_matmul", dict(unknown, dispatches=1))


def test_a_job_of_the_cell_counts_0_898_of_the_edges_scores():
    """The cell's own job: 42 full windows and 18 remainders (9 at each
    edge), as `embed-windows.json` gives them."""
    config = published_config()
    by_edge = job_lengths_by_edge("embed-windows", (1024, 2048))
    at_edges = {"rows": 60, "rows_by_length": {"1024": 9, "2048": 51}}
    work = dict(at_edges, lengths_by_edge=by_edge)
    assert {e: sum(of.values()) for e, of in by_edge.items()} == at_edges["rows_by_length"]
    assert by_edge["2048"][2048] == 42
    assert counts.score_flops(config, work) / counts.score_flops(
        config, at_edges
    ) == pytest.approx(0.8984, abs=1e-4)
    assert counts.forward_flops(config, work) / counts.forward_flops(
        config, at_edges
    ) == pytest.approx(0.9861, abs=1e-4)  # the routed experts at their expectation


def _xla_flops(fn, *shapes):
    cost = jax.jit(fn).lower(*shapes).cost_analysis()
    return float((cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"])


def test_counts_against_xla_cost_analysis_of_the_reference():
    """The reference's dense parts (attention with its scores, the dense
    MLP, the shared experts) lowered for the CPU at the published widths
    from shapes alone; the routed part by hand, since the reference runs
    every token through every held expert."""
    config = published_config()
    rows, length = 1, 256
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32)  # noqa: E731
    shapes = reference.layer_shapes(config, 1)
    attend = {k: f32(*s) for k, s in shapes.items() if k.startswith(("attn/", "norm_"))}
    items = reference._scalars_with_scaling(config)
    xla = _xla_flops(
        functools.partial(reference._attend.__wrapped__, items, precision="highest"),
        attend, f32(rows, length, H),
    )
    # XLA computes the whole square of scores, twice our causal half, and
    # counts the body of the reference's loop over head chunks once
    scores = length * (192 + 128) * HEADS  # ours, a token
    assert counts.score_flops(
        config, {"rows_by_length": {str(length): rows}}
    ) == pytest.approx(5 * rows * length * scores)
    seen = 2 * scores * reference.HEAD_CHUNK / HEADS
    assert rows * length * (2 * MLA + seen) == pytest.approx(xla, rel=0.01)
    assert rows * length * (2 * MLA + seen) <= xla  # softmax, norms, rotary
    for width, matrices in ((12288, DENSE_MLP), (3072, SHARED)):
        w = {"gate": f32(H, width), "up": f32(H, width), "down": f32(width, H)}
        xla = _xla_flops(
            functools.partial(reference._swiglu, "highest"), w, f32(rows, length, H)
        )
        assert rows * length * 2 * matrices == pytest.approx(xla, rel=0.01)
    # routed, by hand: a slot is one expert's three products over one token
    work = {"rows_by_length": {str(length): rows}, "slots_held": 100}
    dense = {"rows_by_length": {str(length): rows}, "slots_held": 0}
    assert counts.forward_flops(config, work) - counts.forward_flops(
        config, dense
    ) == pytest.approx(100 * 2 * 3 * H * 1536)
    per_token = counts.flops_per_token_dense_parts(config)
    assert per_token == pytest.approx(
        2 * (5 * MLA + DENSE_MLP + 4 * (SHARED + ROUTER))
    )


def test_weights_are_made_leaf_by_leaf_in_two_bytes():
    config = tiny_config()
    made = reference.make_weights(config, 1)
    assert {k: v.shape for k, v in made.items()} == reference.weight_shapes(config)
    leaf = made["layers/1/moe/experts/gate"]
    assert leaf._bits is None and leaf.shape == (4, 64, 32)
    bits = np.asarray(leaf)
    assert bits.dtype == np.uint16 and np.asarray(leaf) is bits
    again = reference.make_weights(config, 1)
    assert all((np.asarray(made[k]) == np.asarray(again[k])).all() for k in made)
    # stacked experts scale by their own fan-in (64), not by the stack (4)
    values = reference.from_bits(bits).astype(np.float32)
    assert values.std() == pytest.approx(1 / np.sqrt(64), rel=0.1)
    # the router's scores are spread: logits of deviation 2, not 1
    router = reference.from_bits(np.asarray(made["layers/1/moe/router"]))
    assert router.astype(np.float32).std() * np.sqrt(64) == pytest.approx(2.0, rel=0.15)


# -- `correct` can come out false ----------------------------------------------

ROWS = 12


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """Twelve rows of the cell's own length mix on the tiny preset, and
    the reference's answers at the stated precision."""
    config = tiny_config(max_length=2048)
    path = str(tmp_path_factory.mktemp("deepseek") / "tiny.npz")
    weights = write_weights(path, config)
    data = dict(_json("traffic", "embed-windows.json")["data"], rows=ROWS, null_rows=0)
    inputs = list(traffic_gen.make_rows(data, 2**31 + 5))
    ref = reference.outputs(config, weights, inputs)
    return config, weights, path, inputs, ref


def _decide(got, ref):
    numbers = {
        "rows_misplaced": 0,
        "rows_mismatched": compare.rows_mismatched(got, ref),
        **compare.error_numbers(compare.row_errors(got, ref)),
    }
    return compare.decide(numbers, _json("limits", f"{CELL}.json")["limits"])


def test_the_cell_holds_the_median_the_ninth_decile_and_both_counts():
    """The embedding is the mean over a row's real tokens, so a token that
    chose another expert moves its row by its share and no row hangs on
    one discrete choice: all but the widest row's error are held."""
    blob = _json("limits", f"{CELL}.json")
    limits = blob["limits"]
    assert limits["rows_misplaced"] == 0 and limits["rows_mismatched"] == 0
    assert 0 < limits["row_err_median"] < limits["row_err_p90"] < 0.3
    for name in ("row_err_median", "row_err_p90"):
        assert blob["set_from"][name]["held_by"] == "float8"
    assert "row_err_max" not in limits
    assert blob["set_from"]["row_err_max"].startswith("not held")


def test_control_in_lower_precision_fails_the_limits(job):
    config, weights, _, inputs, ref = job
    assert reference.CONTROL_PRECISION[config["compute_dtype"]] == "float8"
    low = reference.outputs(config, weights, inputs, precision="float8")
    decided = _decide(low, ref)
    assert not compare.all_ok(decided), decided
    assert decided["row_err_median"]["ok"] is False
    assert decided["row_err_p90"]["ok"] is False
    assert compare.all_ok(_decide(ref, ref))


def test_the_second_control_changes_routing_and_nothing_else(job):
    """The router's operands in bfloat16: a reading of
    `benchmarks.prove_released`, held by no limit (limits file, PERF.md
    section 2: it moves a row by no more than the stated precision's own
    rounding does)."""
    config, weights, _, inputs, ref = job
    assert reference.SECOND_CONTROL == "router_bfloat16"
    low = reference.outputs(config, weights, inputs, precision="router_bfloat16")
    assert np.median(compare.row_errors(low, ref)) > 0
    # with the router left alone it is the reference
    same = reference.route(config, jnp.ones((1, 4, 64)), jnp.ones((64, 16)))
    lowered = reference.route(config, jnp.ones((1, 4, 64)), jnp.ones((64, 16)), "router_bfloat16")
    assert np.asarray(same[0]).tolist() == np.asarray(lowered[0]).tolist()


def _embed(job, fault=None):
    """The job's rows through `TextEmbedder` in the cell's two buckets,
    with `fault` planted in the program while it is built and traced."""
    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.models import deepseek_v2
    from sparkdl_tpu.transformers.text import TextEmbedder

    _, _, path, inputs, _ = job
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("SPARKDL_TEXT_BUCKETS", "1024,2048")
        patch.setenv("SPARKDL_TEXT_MIN_BUCKET", "1024")
        if fault:
            fault(patch.setattr)
        mf = deepseek_v2.deepseek_v2_model_function(
            "deepseek-v2-tiny", dtype=jnp.bfloat16, weights_file=path
        )
        out = TextEmbedder(
            inputCol="in", outputCol="out", modelFunction=mf, maxLength=2048,
            batchSize=4,
        ).transform(DataFrame.fromColumns({"in": inputs}, numPartitions=2)).collect()
    return np.stack([np.asarray(r["out"], np.float32) for r in out])


@pytest.fixture(scope="module")
def answers(job):
    return _embed(job)


def test_the_program_as_it_is_passes(job, answers):
    decided = _decide(answers, job[4])
    assert compare.all_ok(decided), decided


@pytest.mark.parametrize(
    "fault, failing",
    [
        (an_expert_slot_dropped, ("row_err_median", "row_err_p90")),
        (weights_renormalised, ("row_err_median", "row_err_p90")),
        (rotary_key_not_rotated, ("row_err_median", "row_err_p90")),
    ],
)
def test_fault_in_every_row_is_caught(job, fault, failing):
    decided = _decide(_embed(job, fault), job[4])
    for name in failing:
        assert decided[name]["ok"] is False, decided


def test_fault_in_a_few_rows_is_caught(job, answers):
    """What the median cannot see. The 1,024 bucket's program alone
    broken (its rows with a slot dropped, a quarter of the job): the ninth
    decile. Two answers given to each other's rows: the count of
    mismatched rows."""
    config, _, _, inputs, ref = job
    short = np.array(
        [len(reference.tokenize(t, config["vocab_size"], 2048)) <= 1024 for t in inputs]
    )
    assert 2 <= short.sum() <= len(inputs) // 2
    broken = np.where(short[:, None], _embed(job, an_expert_slot_dropped), answers)
    decided = _decide(broken, ref)
    assert decided["row_err_median"]["ok"] and not decided["row_err_p90"]["ok"], decided
    swapped = answers.copy()
    swapped[[0, 1]] = answers[[1, 0]]
    decided = _decide(swapped, ref)
    assert decided["row_err_median"]["ok"], decided
    assert decided["rows_mismatched"]["value"] == 2
    assert not decided["rows_mismatched"]["ok"] and not compare.all_ok(decided)
