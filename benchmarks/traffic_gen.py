"""The one traffic generator. A traffic mix is a JSON file of parameters
under `traffic/`; this module turns its `data` block and a seed into the
rows of a job. The block names a `kind`, whose generator is the module
`data/<kind>.py`, found by that name. Every seed gets the same multiset
of sizes (lengths, null rows in each stretch of the job) in another order
with other contents, so the seed moves no work.
"""

from __future__ import annotations

import importlib

import numpy as np


def kind_of(data: dict):
    """The module `data/<kind>.py` that generates this block's rows."""
    return importlib.import_module(f"benchmarks.data.{data['kind']}")


def null_positions(rng, rows: int, nulls: int) -> set:
    """One null row in each of `nulls` equal stripes of the job, at a place
    the seed draws. However the job is cut into equal partitions, every
    seed then gives each partition as many nulls as every other seed does:
    a partition that holds a null can cost more than one that holds none,
    and nulls drawn anywhere made the seed set the rate (PERF.md,
    Findings, PR 24)."""
    nulls = min(nulls, rows)
    edges = [rows * k // nulls for k in range(nulls + 1)] if nulls else []
    return {int(rng.integers(edges[k], edges[k + 1])) for k in range(nulls)}


def make_rows(data: dict, seed: int):
    """Yields the rows of one job in order; `None` is a null row. Lazy, so
    that a caller that turns each row into its stored form never holds
    the job twice."""
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    nulls = null_positions(rng, data["rows"], data.get("null_rows", 0))
    return kind_of(data).rows(data, rng, nulls)
