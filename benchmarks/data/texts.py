"""Sentences of synthetic words.

    {"kind": "texts", "rows": n, "null_rows": k, "vocabulary_words": v,
     "word_counts": [[words, weight], ...]}

`word_counts` is a histogram of text lengths in words, as a corpus
publishes it: the live rows take its quantiles, so every seed gives the
same multiset of lengths, in an order the seed shuffles, with words the
seed draws from a fixed list of `vocabulary_words` pronounceable words.
"""

from __future__ import annotations

import numpy as np

_SYLLABLES = [
    c + v for c in "bdfghjklmnprstvz" for v in ("a", "e", "i", "o", "u", "ai", "or", "en")
]


def word_list(n: int) -> list:
    """`n` distinct pronounceable words of two or three syllables."""
    k = len(_SYLLABLES)
    if n > k * k * k:
        raise ValueError(f"at most {k ** 3} words, asked for {n}")
    words = []
    for i in range(n):
        a, b, c = i % k, (i // k) % k, i // (k * k)
        words.append(_SYLLABLES[a] + _SYLLABLES[b] + (_SYLLABLES[c - 1] if c else ""))
    return words


def word_counts(rows: int, histogram: list) -> np.ndarray:
    """The histogram's quantiles at (i + 0.5) / rows, shortest first."""
    lengths = np.array([int(h[0]) for h in histogram], np.int64)
    weights = np.array([float(h[1]) for h in histogram], np.float64)
    if not len(lengths) or (weights <= 0).any() or (lengths < 1).any():
        raise ValueError("word_counts wants [[words >= 1, weight > 0], ...]")
    order = np.argsort(lengths, kind="stable")
    edges = np.cumsum(weights[order]) / weights.sum()
    at = np.searchsorted(edges, (np.arange(rows) + 0.5) / rows, side="left")
    return lengths[order][np.minimum(at, len(lengths) - 1)]


def rows(data: dict, rng, nulls: set):
    n = data["rows"]
    # the live rows' word counts: the same for every seed, in another order
    counts = rng.permutation(word_counts(n - len(nulls), data["word_counts"]))
    vocab = np.array(word_list(data["vocabulary_words"]), dtype=object)
    picks = vocab[rng.integers(0, len(vocab), size=int(counts.sum()))]
    at, live = 0, iter(counts.tolist())
    for i in range(n):
        if i in nulls:
            yield None
            continue
        c = next(live)
        yield " ".join(picks[at : at + c])
        at += c
