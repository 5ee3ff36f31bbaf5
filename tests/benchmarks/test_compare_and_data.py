"""The yardstick's small parts: what is said of a set of row errors, the
count of answers given to the wrong row, and the text lengths drawn from a
published histogram."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import compare  # noqa: E402
from benchmarks.data import texts  # noqa: E402


@pytest.mark.parametrize(
    "share, sees",
    [(0.0, set()), (0.05, {"row_err_max"}), (0.2, {"row_err_max", "row_err_p90"}),
     (0.6, {"row_err_max", "row_err_p90", "row_err_median"})],
)
def test_error_numbers_see_a_fault_by_the_share_of_rows_it_is_in(share, sees):
    errs = np.full(200, 0.03)
    errs[: int(share * 200)] = 0.2
    numbers = compare.error_numbers(np.random.default_rng(3).permutation(errs))
    assert {k for k, v in numbers.items() if v > 0.1} == sees


def test_row_errors_are_in_units_of_the_references_spread():
    rng = np.random.default_rng(1)
    ref = rng.normal(size=(50, 8)) + 100.0  # far from the origin
    assert compare.row_errors(ref, ref).max() == 0
    other = np.roll(ref, 1, axis=0)  # every answer is another row's
    assert np.median(compare.row_errors(other, ref)) == pytest.approx(1.4, abs=0.2)
    assert compare.rows_mismatched(other, ref) == 50
    assert compare.rows_mismatched(ref + 0.01, ref) == 0
    with pytest.raises(ValueError):
        compare.row_errors(ref[:, :4], ref)


def test_decide_fails_what_is_missing_or_not_finite():
    limits = {"a": 0.1, "b": 0, "c": 1.0}
    decided = compare.decide({"a": 0.05, "b": float("nan"), "extra": 7}, limits)
    assert decided["a"]["ok"] and not decided["b"]["ok"] and not decided["c"]["ok"]
    assert decided["extra"] == {"value": 7} and not compare.all_ok(decided)
    assert compare.all_ok(compare.decide({"a": 0.1, "b": 0, "c": 0.5}, limits))


@pytest.mark.parametrize(
    "histogram, rows, want",
    [
        ([[100, 1]], 5, [100] * 5),
        ([[300, 1], [10, 1]], 4, [10, 10, 300, 300]),
        ([[50, 3], [20, 1]], 8, [20, 20, 50, 50, 50, 50, 50, 50]),
        ([[7, 0.5], [9, 0.25], [8, 0.25]], 4, [7, 7, 8, 9]),
    ],
)
def test_word_counts_are_the_histograms_quantiles(histogram, rows, want):
    assert texts.word_counts(rows, histogram).tolist() == want


@pytest.mark.parametrize("histogram", [[], [[0, 1]], [[5, 0]], [[5, -1]]])
def test_word_counts_refuses_what_is_no_histogram(histogram):
    with pytest.raises(ValueError):
        texts.word_counts(4, histogram)


def test_word_list_is_distinct_and_bounded():
    words = texts.word_list(4096)
    assert len(set(words)) == 4096 and all(w.isalpha() for w in words)
    with pytest.raises(ValueError):
        texts.word_list(128**3 + 1)
