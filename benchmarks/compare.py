"""The comparison that decides `correct`.

Answers are rows; each is judged against the plain reference's answer
for the same input. A row's error is the distance between the two over
the root-mean-square distance of the reference's own rows from their
mean: 1.4 is another row's answer, 0 the reference's. `rows_mismatched`
counts the answers that went to the wrong row. `decide` holds each number
to its limit; a number that is missing, or not finite, fails.
"""

from __future__ import annotations

import math

import numpy as np


def _spread(ref: np.ndarray) -> float:
    """Root-mean-square distance of the reference's rows from their mean:
    the unit of every error here."""
    rms = math.sqrt(float(np.mean(np.sum((ref - ref.mean(0)) ** 2, -1))))
    return max(rms, 1e-30)


def row_errors(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise ValueError(f"answers {got.shape}, reference {ref.shape}")
    return np.linalg.norm(got - ref, axis=-1) / _spread(ref)


def error_numbers(errs: np.ndarray) -> dict:
    """What is said of a set of row errors: the median sees a fault in
    most rows, the ninth decile one in a tenth of them (one batch, one
    partition, one length), the widest a single row."""
    errs = np.asarray(errs, np.float64).ravel()
    return {
        "row_err_median": float(np.median(errs)),
        "row_err_p90": float(np.quantile(errs, 0.9)),
        "row_err_max": float(errs.max()),
    }


def rows_mismatched(got: np.ndarray, ref: np.ndarray) -> int:
    """How many answers lie nearer to another row's reference answer than
    to their own: an answer that went to the wrong row. Exact: the limit
    is 0 wherever the reference's rows lie further apart than twice the
    program's widest error (PERF.md gives the distances)."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    # |g - r|^2 = |g|^2 - 2 g.r + |r|^2, and |g|^2 is the same along a row
    d2 = (ref * ref).sum(-1)[None, :] - 2.0 * (got @ ref.T)
    return int(np.sum(d2.argmin(1) != np.arange(len(got))))


def nearest_pair(ref: np.ndarray) -> float:
    """The least distance between two of the reference's rows, in the unit
    of `row_errors`: `rows_mismatched` is exact while every row's error
    stays under half of it."""
    ref = np.asarray(ref, np.float64)
    sq = (ref * ref).sum(-1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * (ref @ ref.T)
    np.fill_diagonal(d2, np.inf)
    return math.sqrt(max(float(d2.min()), 0.0)) / _spread(ref)


def decide(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit", "ok"}} for every limit; `numbers` that
    have no limit are carried along without one."""
    out = {}
    for name, limit in limits.items():
        value = numbers.get(name)
        ok = (
            value is not None
            and math.isfinite(float(value))
            and float(value) <= float(limit)
        )
        out[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    for name, value in numbers.items():
        out.setdefault(name, {"value": value})
    return out


def all_ok(decided: dict) -> bool:
    return all(d["ok"] for d in decided.values() if "limit" in d)
