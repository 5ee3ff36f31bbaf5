"""The Xing4.0-29B-A4B cell's counts against a hand count at the published
widths and against XLA's `cost_analysis()` of the reference's parts; the
cell end to end through the harness on the CPU, its configuration dropped
in at the tiny preset's size; and `correct` able to come out false: the
float8 control and the hyper-connections replaced by a plain residual
(`xing_tiny.py:plain_residual`) fail the cell's own limits, by a number
named here.

The tiny preset runs rows of 20-250 words at a width of 64. There a row's
rounding error is larger than the chip's at 3,584 wide, so the program as
it is is held in ratio here: under a third of what the control and the
fault read, and those fail the cell's limits outright. The published
widths are never built on the CPU."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import xing_tiny  # noqa: E402
from bench_checkout import ROOT, job_lengths_by_edge, make_checkout, run_cell  # noqa: E402
from xing_tiny import published_config, tiny_config, write_weights  # noqa: E402

sys.path.insert(0, ROOT)

from benchmarks import compare  # noqa: E402
from benchmarks.counts import deepseek_v2 as v2_counts  # noqa: E402
from benchmarks.counts import xing4_0 as counts  # noqa: E402
from benchmarks.data import texts  # noqa: E402
from benchmarks.reference import xing4_0 as reference  # noqa: E402

CELL = "xing4.0-29b-a4b-embed-windows"


def _json(*parts):
    with open(os.path.join(ROOT, "benchmarks", *parts)) as f:
        return json.load(f)


# -- the counts, by hand -------------------------------------------------------

H, HEADS, N = 3584, 32, 4
MLA = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 + 4096 * 3584
HYPER = N * H * 24 + 24 + 3  # phi, its bias, three gains
DENSE_MLP = 3 * H * 9216
EXPERT = 3 * H * 1024
ROUTER = H * 64
VOCAB = 131072


def test_parameters_are_the_cut_3_58_billion():
    """The issue's count: MLA 28.41 M a layer, mHC 0.69 M, a dense layer
    128.2 M, an expert layer 745.0 M, the embedding 469.8 M: 3,577.9 M,
    7.16 GB in bfloat16, 41.7% of the chip before any activation."""
    config = published_config()
    assert MLA == v2_counts.attention_params(config)
    assert MLA == pytest.approx(28.41e6, rel=1e-3)
    assert counts.hyper_params(config) == HYPER
    assert 2 * HYPER == pytest.approx(0.69e6, rel=1e-2)
    vectors = 2 * H + 768 + 512  # norm_in, norm_ff, the two latent norms
    dense = MLA + DENSE_MLP + 2 * HYPER + vectors
    expert = MLA + 65 * EXPERT + ROUTER + 64 + 2 * HYPER + vectors  # and the gate's bias
    assert dense == pytest.approx(128.2e6, rel=1e-3)
    assert expert == pytest.approx(745.0e6, rel=1e-3)
    assert 64 * EXPERT == pytest.approx(704.6e6, rel=1e-3)
    assert VOCAB * H == pytest.approx(469.8e6, rel=1e-3)
    total = sum(int(np.prod(s)) for s in reference.weight_shapes(config).values())
    assert total == dense + 4 * expert + VOCAB * H + H  # the embedding, the final norm
    assert total == pytest.approx(3_577.9e6, rel=1e-4)
    assert 2 * total == pytest.approx(7.16e9, rel=1e-3)
    assert 2 * total / 17.18e9 == pytest.approx(0.417, abs=1e-3)


#: the catalog's row beside the `model-configs` guide, as published
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
    "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
    "max_position_embeddings": 262144, "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn",
    },
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
}


def test_the_file_holds_every_number_of_the_catalogs_row():
    """Every key of the published config, unchanged but the two reduced
    ones: one leading dense layer and four expert layers of 40, every
    width, every expert, the whole vocabulary."""
    config = published_config()
    differs = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == ["first_k_dense_replace", "num_hidden_layers"]
    assert all(config["published"][k] == PUBLISHED[k] for k in config["reduced"])
    assert (config["num_hidden_layers"], config["first_k_dense_replace"]) == (5, 1)
    assert config["experts_held"] == [0, 64]
    assert "pipeline" in config["deployment"] and "seven" in config["deployment"]
    assert len(config["assumed"]) >= 10
    assert any("multi-token-prediction" in a for a in config["assumed"])
    assert config["family"] == "xing4_0" and config["max_length"] == 2048
    assert config["env"]["SPARKDL_TEXT_BUCKETS"] == "1024,2048"
    assert "four residual streams" in config["precision"]


def _job_of_the_cell():
    """A job's sixty live rows by edge, as the driver's `work` gives them."""
    by_edge = job_lengths_by_edge("embed-windows", (1024, 2048))
    rows = {edge: sum(of.values()) for edge, of in by_edge.items()}
    return {"rows": 60, "rows_by_length": rows, "lengths_by_edge": by_edge}


def test_a_job_of_the_cell_and_its_mixes():
    """The issue's reckoning: about 0.9 MFLOP a token and sublayer of mHC
    and 193.5 KB moved; over a job's ten sublayers the mixes move
    253.6 GB or more, 0.31 s at 819 GB/s, against about 1 GFLOP a token of
    the rest."""
    config = published_config()
    job = _job_of_the_cell()
    assert job["rows_by_length"] == {"1024": 9, "2048": 51}
    tokens = 9 * 1024 + 51 * 2048
    assert counts.mix_flops(config) == 2 * N * H * (24 + 1 + 4 + 1) == 860_160
    pre_flops, pre_bytes = counts.kernel_work(config, "hc_pre", job)
    post_flops, post_bytes = counts.kernel_work(config, "hc_post", job)
    calls = tokens * 10
    assert (pre_flops + post_flops) / calls == pytest.approx(0.89e6, rel=0.01)
    # the stream read twice and written once, u written, F read, and the
    # two mixes written and read: 193.5 KB and 160 B
    assert (pre_bytes + post_bytes) / calls == 12 * N * H + 2 * H + 4 * H + 2 * 80
    assert pre_bytes / calls == 4 * N * H + 2 * H + 80
    # the dispatched job, pad rows too (131,072 tokens): 253.6 GB, 0.31 s
    assert 131072 * 10 * 193.5e3 == pytest.approx(253.6e9, rel=1e-3)
    assert (pre_bytes + post_bytes) / 819e9 == pytest.approx(tokens * 10 * 193_696 / 819e9)
    # both kernels are bound by their bytes
    for flops, bytes_ in ((pre_flops, pre_bytes), (post_flops, post_bytes)):
        assert bytes_ / 819e9 > 10 * flops / 197e12
    measured = dict(job, slots_held=tokens * 4 * 4)
    total = counts.forward_flops(config, measured)
    mixes = tokens * 10 * counts.mix_flops(config)
    assert total == pytest.approx(v2_counts.forward_flops(config, measured) + mixes)
    assert (total - mixes) / tokens == pytest.approx(1.02e9, rel=0.02)
    assert mixes / total == pytest.approx(0.0084, abs=0.001)
    unknown = dict(job, pairs_unknown="text.tokens disagrees")
    assert counts.forward_flops(config, unknown) is None
    assert counts.kernel_work(config, "flash_attention", unknown) is None
    assert counts.KERNELS == ("flash_attention", "moe_grouped_matmul", "hc_pre", "hc_post")


def _xla_flops(fn, *shapes):
    cost = jax.jit(fn).lower(*shapes).cost_analysis()
    return float((cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"])


@pytest.mark.parametrize("length", [128, 512])
def test_counts_against_xla_cost_analysis_of_the_reference(length):
    """The reference's hyper-connection lowered for the CPU at the
    published widths from shapes alone: phi's product, the pre-mix and the
    post-mix are the count; XLA adds the stream's norm (three operations a
    lane), the gates and the 20 Sinkhorn steps: 5.2% more."""
    config = published_config()
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32)  # noqa: E731
    w = {"phi": f32(N * H, 24), "bias": f32(24), "alpha": f32(3)}

    def mix(w, X, f):
        u, post, res = reference.hc_pre(config, w, X)
        return u, reference.hc_post(X, f, post, res)

    xla = _xla_flops(mix, w, f32(1, length, N, H), f32(1, length, H))
    ours = length * counts.mix_flops(config)
    assert ours == pytest.approx(xla, rel=0.06)
    assert ours <= xla


@pytest.mark.parametrize("length", [256, 512])
def test_attention_sublayer_against_xla_cost_analysis(length):
    """Xing4.0's attention sublayer as the reference computes it (the
    hyper-connection's pre-mix, MLA over the whole masked square, the
    post-mix), lowered for the CPU at the published widths from shapes
    alone: 2 x the projections' parameters and `counts/xing4_0.py:mix_flops`
    a token, and the two products over every (query, key) pair. XLA counts
    a loop's body once: of the loop over heads, one chunk of 16 heads'
    products. It also counts norms, softmax, rotary, the gates and the
    Sinkhorn steps: under 4%."""
    from benchmarks.reference import deepseek_v2 as v2_ref

    config = published_config()
    shapes = reference.layer_shapes(config, 0)
    w = {
        k: jax.ShapeDtypeStruct(s, np.float32)
        for k, s in shapes.items()
        if k.startswith(("attn/", "norm_in", "hc_attn/"))
    }
    X = jax.ShapeDtypeStruct((1, length, 4, config["hidden_size"]), np.float32)
    items = v2_ref._scalars_with_scaling(config)
    lowered = jax.jit(
        functools.partial(reference._attend.__wrapped__, items, precision="highest")
    ).lower(w, X)
    cost = lowered.cost_analysis()
    xla = float((cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"])
    square = length * length * v2_counts.score_width(config) * v2_ref.HEAD_CHUNK
    ours = length * (2 * v2_counts.attention_params(config) + counts.mix_flops(config))
    ours += 2 * square
    assert ours == pytest.approx(xla, rel=0.04)
    assert ours <= xla


# -- the three readers -----------------------------------------------------------


def _reader(name):
    from benchmarks.run import load_reader

    return load_reader(name)


def _ctx(trace, **over):
    from types import SimpleNamespace

    cell = SimpleNamespace(config=published_config(), work_dir="")
    ctx = {
        "cell": cell, "trace": trace, "chips": 1, "counts": counts,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "work": {"rows_by_length": {"2048": 8}}, "counters": {"mhc.tokens": 163840},
    }
    return dict(ctx, **over)


TARGET = 'custom_call_target="tpu_custom_call"'


@pytest.mark.parametrize("name, kernel", [("mhc_pre_roofline", "hc_pre"), ("mhc_post_roofline", "hc_post")])
def test_each_roofline_reads_its_kernel_by_its_own_name(name, kernel):
    """A kernel's events by their own name, not those that name it as an
    operand's producer (the other half's); nothing where the trace holds
    no such event, as the parent's does not."""
    from types import SimpleNamespace

    other = "hc_post" if kernel == "hc_pre" else "hc_pre"
    trace = SimpleNamespace(op_s={
        f"%{kernel}.3 = f32[16384,14336] custom-call(%{other}.2), {TARGET}": 0.002,
        f"%{kernel} = f32[16384,14336] custom-call(%fusion.9), {TARGET}": 0.002,
        f"%{other}.4 = f32[16384,3584] custom-call(%{kernel}.3), {TARGET}": 5.0,
        "%fusion.7 = f32[16384,3584] fusion(%flash_attention.1)": 5.0,
    })
    got = _reader(name)(_ctx(trace))
    flops, bytes_ = counts.kernel_work(published_config(), kernel, {"rows_by_length": {"2048": 8}})
    assert got["kernel_s"] == pytest.approx(0.004)
    assert got["bound_by"] == "bytes"
    assert got["value"] == pytest.approx(100 * bytes_ / 819e9 / 0.004)
    assert _reader(name)(_ctx(SimpleNamespace(op_s={}))) is None
    assert _reader(name)(_ctx(None)) is None


def test_the_mixes_time_per_thousand_tokens(monkeypatch):
    """Any-level `mhc.pre` and `mhc.post` over the counter `mhc.tokens`;
    nothing without the counter, as in a program that counts none."""
    from benchmarks import program_scopes

    found = program_scopes.ByScope(
        busy_s=10.0, any_s={"mhc.pre": 1.0, "mhc.post": 2.0, "mlp": 4.0}
    )
    monkeypatch.setattr(program_scopes, "reading", lambda ctx: found)
    got = _reader("mhc.ms_per_ktoken")(_ctx(object()))
    assert got["value"] == pytest.approx(1e3 * 3.0 / 163.84)
    assert (got["mhc.pre_s"], got["mhc.post_s"]) == (1.0, 2.0)
    assert _reader("mhc.ms_per_ktoken")(_ctx(object(), counters={})) is None


# -- the cell through the harness, its configuration at the tiny size ----------


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    """`benchmarks.run --rehearse-cpu --trace 1` of the cell with its
    configuration file replaced by the tiny preset's (float32, buckets 64
    and 128): the family's reference, counts and readers are found by
    name, the answers agree, the program's counters square with the
    benchmark's own account of the rows, and no device metric is written."""
    checkout = make_checkout(tmp_path / "checkout")
    config = dict(
        tiny_config(max_length=128), compute_dtype="float32",
        entry=dict(published_config()["entry"], model="xing4.0-tiny"),
        env={"SPARKDL_TEXT_BUCKETS": "64,128", "SPARKDL_TEXT_MIN_BUCKET": "64"},
    )
    path = os.path.join(checkout, "benchmarks", "configs", "xing4.0-29b-a4b.json")
    with open(path, "w") as f:
        json.dump(config, f)
    rc, last, err = run_cell(
        checkout, "--workload", CELL, "--seed", str(2**31 + 42), "--seconds", "0.5",
        "--trace", "1", "--rehearse-cpu",
    )
    assert rc == 0, err[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["compared"]["row_err_median"]["value"] < 1e-5
    assert last["work_check"]["ok"] is True
    # the CPU's dense attention counts no query blocks
    assert set(last["metrics"]) == {"feeder.pad_rows_pct", "text.pad_tokens_pct"}
    assert "rehearsal" in last


# -- `correct` can come out false ----------------------------------------------

LIMITS = _json("limits", f"{CELL}.json")


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """Twelve windows of 20-250 words on the tiny preset, and the
    reference's answers at the stated precision."""
    config = tiny_config()
    path = str(tmp_path_factory.mktemp("xing") / "tiny.npz")
    weights = write_weights(path, config)
    data = {
        "rows": 12, "vocabulary_words": 300,
        "word_counts": [[250, 6], [20, 2], [80, 2], [160, 2]],
    }
    inputs = list(texts.rows(data, np.random.default_rng(2**31 + 42), set()))
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
    """The job's rows through `TextEmbedder` in two buckets, with `fault`
    planted in the program while it is built and traced."""
    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.models import xing4_0
    from sparkdl_tpu.transformers.text import TextEmbedder

    _, _, path, inputs, _ = job
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("SPARKDL_TEXT_BUCKETS", "128,256")
        patch.setenv("SPARKDL_TEXT_MIN_BUCKET", "128")
        if fault:
            fault(patch.setattr)
        mf = xing4_0.xing4_0_model_function("xing4.0-tiny", dtype=jnp.bfloat16, weights_file=path)
        out = TextEmbedder(
            inputCol="in", outputCol="out", modelFunction=mf, maxLength=256, batchSize=4,
        ).transform(DataFrame.fromColumns({"in": inputs}, numPartitions=2)).collect()
    return np.stack([np.asarray(r["out"], np.float32) for r in out])


@pytest.fixture(scope="module")
def answers(job):
    return _embed(job)


def test_the_cell_holds_the_median_and_both_counts():
    """The ninth decile and the widest row are reported and not held: the
    chip's float8 control read under three times the program's there."""
    limits = LIMITS["limits"]
    assert limits["rows_misplaced"] == 0 and limits["rows_mismatched"] == 0
    assert 0 < limits["row_err_median"] < 0.3
    assert set(limits) == {"rows_misplaced", "rows_mismatched", "row_err_median"}
    assert LIMITS["set_from"]["row_err_median"]["held_by"] == "float8"
    assert "not held" in LIMITS["set_from"]["row_err_p90"]
    assert "plain_residual" in LIMITS["set_from"]["where"]


def test_the_float8_control_fails_and_the_program_lies_far_under_it(job, answers):
    config, weights, _, inputs, ref = job
    assert reference.CONTROL_PRECISION[config["compute_dtype"]] == "float8"
    low = reference.outputs(config, weights, inputs, precision="float8")
    decided = _decide(low, ref)
    assert decided["row_err_median"]["ok"] is False and not compare.all_ok(decided)
    assert compare.all_ok(_decide(ref, ref))
    mine, theirs = _numbers(answers, ref), _numbers(low, ref)
    assert mine["rows_mismatched"] == 0
    for name in ("row_err_median", "row_err_p90"):
        assert 3 * mine[name] < theirs[name], (name, mine, theirs)


@pytest.mark.parametrize("fault", xing_tiny.FAULTS)
def test_fault_in_every_row_is_caught(job, answers, fault):
    broken = _embed(job, getattr(xing_tiny, fault))
    decided = _decide(broken, job[4])
    mine, theirs = _numbers(answers, job[4]), _numbers(broken, job[4])
    assert decided["row_err_median"]["ok"] is False, decided
    for name in ("row_err_median", "row_err_p90"):
        assert theirs[name] > 3 * mine[name], (name, mine, theirs)


def test_fault_in_a_few_rows_is_caught(job, answers):
    """Two answers given to each other's rows: the count of mismatched
    rows, which the median cannot see."""
    swapped = answers.copy()
    swapped[[0, 1]] = answers[[1, 0]]
    decided = _decide(swapped, job[4])
    assert decided["rows_mismatched"]["value"] == 2
    assert not decided["rows_mismatched"]["ok"] and not compare.all_ok(decided)
