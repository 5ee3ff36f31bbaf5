"""The Jamba cell's counts against a hand count at the published widths,
and `correct` able to come out false: both controls (the reference with
float8 operands; the reference with its recurrent state kept in bfloat16)
and three faults planted in the program each fail the cell's own limits,
by a number named here, on the tiny preset at the cell's own lengths
(full windows of 2,048 tokens and remainders, two buckets). The
published widths are never built on the CPU."""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_checkout import ROOT, job_lengths_by_edge  # noqa: E402
from jamba_tiny import published_config, tiny_config, write_weights  # noqa: E402

sys.path.insert(0, ROOT)

from benchmarks import compare, traffic_gen  # noqa: E402
from benchmarks.counts import jamba as counts  # noqa: E402
from benchmarks.reference import jamba as reference  # noqa: E402

CELL = "jamba2-3b-embed-windows"


def _json(*parts):
    with open(os.path.join(ROOT, "benchmarks", *parts)) as f:
        return json.load(f)


# -- the counts, by hand -------------------------------------------------------

H, F, DI, N, R = 2560, 8192, 5120, 16, 160
MLP = 3 * H * F  # 62,914,560
MAMBA_MATRICES = H * 2 * DI + DI * (R + 2 * N) + R * DI + DI * H  # 41,123,840
MAMBA_VECTORS = 4 * DI + DI + DI * N + DI + DI + (R + N + N)  # conv, its bias, A_log, D, b_dt, norms
ATTENTION_MATRICES = 2 * H * 20 * 128 + 2 * H * 128  # 13,762,560


def test_parameters_are_the_published_3_03_billion():
    config = published_config()
    assert counts.layer_params(config) == (
        MAMBA_MATRICES + MLP, ATTENTION_MATRICES + MLP
    )
    assert MAMBA_MATRICES + MLP == 104_038_400
    assert ATTENTION_MATRICES + MLP == 76_677_120
    total = sum(int(np.prod(s)) for s in reference.weight_shapes(config).values())
    by_hand = (
        26 * (MAMBA_MATRICES + MAMBA_VECTORS + MLP + 2 * H)
        + 2 * (ATTENTION_MATRICES + MLP + 2 * H)
        + 65536 * H  # the embedding, tied to the head
        + H  # the final norm
    )
    assert total == by_hand
    assert total == pytest.approx(3.03e9, rel=1e-3)
    assert 2 * total == pytest.approx(6.06e9, rel=1e-3)  # bytes in bfloat16
    assert counts.mamba_layers(config) == 26


def test_forward_operations_are_5_74_gflop_a_token_at_2048():
    config = published_config()
    per_token = 2 * (26 * (MAMBA_MATRICES + MLP) + 2 * (ATTENTION_MATRICES + MLP))
    # two products over half the square a token: 2 * 2 * (L / 2) * 2,560
    attention = 2 * 2 * 2048 * 2560
    assert counts.flops_per_row(config, 2048) == pytest.approx(
        2048 * (per_token + attention)
    )
    assert counts.flops_per_row(config, 2048) / 2048 == pytest.approx(5.74e9, rel=2e-3)
    # what is left out, a token: the recurrence and the convolution
    left_out = 26 * (9 * DI * N + 2 * 4 * DI)
    assert left_out / per_token < 0.01
    # 94% of it lies in the Mamba layers and their MLPs
    assert 2 * 26 * (MAMBA_MATRICES + MLP) / (per_token + attention) == pytest.approx(0.94, abs=0.01)
    work = {"rows": 60, "rows_by_length": {"1024": 9, "2048": 51}}
    assert counts.forward_flops(config, work) == pytest.approx(
        9 * counts.flops_per_row(config, 1024) + 51 * counts.flops_per_row(config, 2048)
    )


def test_kernel_work_of_the_scan():
    config = published_config()
    work = {"rows": 5, "rows_by_length": {"1024": 2, "2048": 3}}
    flops, bytes_ = counts.kernel_work(config, "selective_scan", work)
    tokens = 2 * 1024 + 3 * 2048
    assert flops == pytest.approx(tokens * 26 * 9 * DI * N)
    # h, dt, z in at the 4 bytes the kernel is handed and y out at 2, x
    # 5,120; B and C at 4 bytes x 16
    assert bytes_ == pytest.approx(tokens * 26 * (DI * (3 * 4 + 2) + 2 * N * 4))
    assert flops < 0.01 * counts.forward_flops(config, work)
    assert counts.kernel_work(config, "flash_attention", work) is None
    # bound by its bytes, 1.75 times what 2 bytes each had said
    assert bytes_ / 819e9 > 10 * flops / 197e12
    assert bytes_ / (tokens * 26 * (4 * DI * 2 + 2 * N * 4)) == pytest.approx(1.7477, abs=1e-4)
    # every term of it grows with the tokens: real lengths change nothing,
    # and it is counted where the pairs are unknown
    real = dict(work, lengths_by_edge={"1024": {300: 2}, "2048": {2048: 3}})
    assert counts.kernel_work(config, "selective_scan", real) == (flops, bytes_)
    unknown = dict(work, pairs_unknown="text.tokens disagrees")
    assert counts.kernel_work(config, "selective_scan", unknown) == (flops, bytes_)
    assert counts.forward_flops(config, unknown) is None


def test_attention_pairs_are_those_of_the_rows_real_lengths():
    """Two attention layers of 28: a window of 1,500 tokens dispatched at
    2,048 counts its projections over 2,048 tokens and its two products
    over half the square of 1,500."""
    config = published_config()
    per_token = 2 * (26 * (MAMBA_MATRICES + MLP) + 2 * (ATTENTION_MATRICES + MLP))
    work = {"rows": 2, "rows_by_length": {"2048": 2},
            "lengths_by_edge": {"2048": {2048: 1, 1500: 1}}}
    products = 2 * 2 * 2560 * (2048**2 + 1500**2)  # 2 layers x 2 products x 2 / 2
    assert counts.forward_flops(config, work) == pytest.approx(2 * 2048 * per_token + products)
    assert counts.flops_per_row(config, 2048, 1500) == pytest.approx(
        2048 * per_token + 2 * 2 * 2560 * 1500**2
    )
    assert counts.flops_per_row(config, 2048, 2048) == counts.flops_per_row(config, 2048)
    # the cell's own job: 0.04% of the count is the padding's pairs
    by_edge = job_lengths_by_edge("embed-windows", (1024, 2048))
    at_edges = {"rows": 60, "rows_by_length": {"1024": 9, "2048": 51}}
    ratio = counts.forward_flops(config, dict(at_edges, lengths_by_edge=by_edge)) / (
        counts.forward_flops(config, at_edges)
    )
    assert ratio == pytest.approx(0.99964, abs=2e-5)


def test_weights_are_made_leaf_by_leaf_in_two_bytes():
    config = tiny_config()
    made = reference.make_weights(config, 1)
    assert {k: v.shape for k, v in made.items()} == reference.weight_shapes(config)
    leaf = made["layers/0/mamba/in_proj"]
    assert leaf._bits is None  # nothing is drawn before it is read
    bits = np.asarray(leaf)
    assert bits.dtype == np.uint16 and np.asarray(leaf) is bits
    again = reference.make_weights(config, 1)
    assert all((np.asarray(made[k]) == np.asarray(again[k])).all() for k in made)
    other = reference.make_weights(config, 2)
    assert (np.asarray(other["layers/0/mamba/in_proj"]) != bits).any()
    # the recurrence has a memory: steps in [0.001, 0.1], A = -(1..16), D = 1
    step = np.log1p(np.exp(reference.from_bits(made["layers/0/mamba/dt_bias"]).astype(np.float32)))
    assert 0.9e-3 < step.min() and step.max() < 0.11
    a = -np.exp(reference.from_bits(made["layers/0/mamba/A_log"]).astype(np.float32))
    np.testing.assert_allclose(a[0], -np.arange(1, 17), rtol=1e-2)
    decay = np.exp(step.max() * a.min()), np.exp(step.min() * a.max())
    assert decay[0] < 0.25 and decay[1] > 0.998


# -- `correct` can come out false ----------------------------------------------

ROWS = 12


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """Twelve rows of the cell's own length mix on the tiny preset, and
    the reference's answers at the stated precision."""
    config = tiny_config(max_length=2048)
    path = str(tmp_path_factory.mktemp("jamba") / "tiny.npz")
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


def test_the_cell_holds_numbers_with_a_tolerance():
    limits = _json("limits", f"{CELL}.json")["limits"]
    assert limits["rows_misplaced"] == 0 and limits["rows_mismatched"] == 0
    assert {"row_err_median", "row_err_p90", "row_err_max"} <= set(limits)
    assert limits["row_err_median"] <= limits["row_err_p90"] <= limits["row_err_max"]


@pytest.mark.parametrize(
    "precision, failing",
    [
        ("float8", "row_err_median"),
        # the one number this control lies three times above the program by
        ("state_bfloat16", "row_err_max"),
    ],
)
def test_control_in_lower_precision_fails_the_limits(job, precision, failing):
    config, weights, _, inputs, ref = job
    assert reference.CONTROL_PRECISION[config["compute_dtype"]] == "float8"
    assert reference.SECOND_CONTROL == "state_bfloat16"
    low = reference.outputs(config, weights, inputs, precision=precision)
    decided = _decide(low, ref)
    assert not compare.all_ok(decided), decided
    assert decided[failing]["ok"] is False
    # it is the precision that fails: every row is still nearest its own
    assert decided["rows_mismatched"]["value"] == 0
    assert compare.all_ok(_decide(ref, ref))


def _embed(monkeypatch, job, scan_fn=None):
    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.models import jamba
    from sparkdl_tpu.transformers.text import TextEmbedder

    _, _, path, inputs, _ = job
    monkeypatch.setenv("SPARKDL_TEXT_BUCKETS", "1024,2048")
    monkeypatch.setenv("SPARKDL_TEXT_MIN_BUCKET", "1024")
    mf = jamba.jamba_model_function(
        "jamba-tiny", dtype=jnp.bfloat16, weights_file=path, scan_fn=scan_fn
    )
    out = TextEmbedder(
        inputCol="in", outputCol="out", modelFunction=mf, maxLength=2048,
        batchSize=4,
    ).transform(DataFrame.fromColumns({"in": inputs}, numPartitions=2)).collect()
    return np.stack([np.asarray(r["out"], np.float32) for r in out])


def _state_dropped_at_chunk_edges(monkeypatch):
    """The scan restarted from an empty state every 128 tokens."""
    from sparkdl_tpu.ops.selective_scan import chunked_scan

    def scan(h, dt, b, c, z, a, d):
        pieces = [
            chunked_scan(*(t[:, s : s + 128] for t in (h, dt, b, c, z)), a, d)
            for s in range(0, h.shape[1], 128)
        ]
        return jnp.concatenate(pieces, axis=1)

    return scan


def _convolution_not_causal(monkeypatch):
    """The four taps centred on the token: it reads two later ones."""
    from sparkdl_tpu.models import jamba

    def centred(h, taps, bias):
        k, length = taps.shape[0], h.shape[1]
        around = jnp.pad(h, ((0, 0), (1, k - 2), (0, 0)))
        return bias + sum(taps[j] * around[:, j : j + length] for j in range(k))

    monkeypatch.setattr(jamba, "_causal_conv", centred)


def _bucket_end_for_row_end(monkeypatch):
    """The state at the bucket's last position, padding or not."""
    from sparkdl_tpu.models import jamba

    monkeypatch.setattr(jamba, "_last_real_state", lambda x, ids: x[:, -1])


@pytest.mark.parametrize(
    "fault, failing",
    [
        (None, None),
        (_state_dropped_at_chunk_edges, "row_err_median"),
        (_convolution_not_causal, "row_err_median"),
        # only the rows shorter than their bucket: the remainders, 30%
        (_bucket_end_for_row_end, "row_err_p90"),
    ],
)
def test_fault_in_the_program_is_caught(monkeypatch, job, fault, failing):
    scan_fn = fault(monkeypatch) if fault else None
    decided = _decide(_embed(monkeypatch, job, scan_fn), job[4])
    if fault is None:  # the same drive, nothing broken
        assert compare.all_ok(decided), decided
        return
    assert not compare.all_ok(decided)
    assert decided[failing]["ok"] is False, decided
    if fault is _bucket_end_for_row_end:
        assert decided["row_err_median"]["ok"] is True
