"""Operations and bytes the algorithm needs, from shapes alone, one module
per model family. The benchmark's own: nothing here reads the program's
`utils/flops.py`. `tests/benchmarks/test_counts.py` holds each against
XLA's `cost_analysis()` of the plain reference.

A family module offers:

    forward_flops(config, work) -> FLOPs of the forward passes for `work`,
        the driver's account of what the window completed:
        {"rows": n} or {"rows_by_length": {padded length: rows}}, and for
        text, where the driver could tell, {"lengths_by_edge": {padded
        length: {real length: rows}}}
    kernel_work(config, kernel, work) -> (FLOPs, bytes) the named kernel's
        calls needed for `work`, or None where the family has no such kernel

**Pairs at real lengths, tokens as dispatched** (all families): every
term that grows with the PAIRS of a row (attention's two products, the
index scores, the selection's byte a pair, a score written a pair) is
counted at the row's real length; every term that grows with its TOKENS
(projections, MLPs, experts, the scan, a kernel's per-token bytes) stays
at the dispatched tokens, the padded edge. The padding's tokens do run
through every product; its pairs are what a kernel can leave out.
`pair_rows(work)` is how a family reads that: a `work` that carries no
real lengths (a hand-made one) reads every row as long as its edge, and
one whose real lengths the driver could not square with the program's
counters (`pairs_unknown`) has no pair term at all: the family returns
None for whatever holds one, and the metric is silent, never wrong.
"""

from __future__ import annotations


def pairs_unknown(work) -> str | None:
    """Why `work` has no pair term, where the driver says it has none."""
    return work.get("pairs_unknown")


def pair_rows(work) -> list:
    """[(dispatched edge, real length, rows)]: the rows the window
    completed, for the terms that grow with a row's pairs."""
    by_edge = work.get("lengths_by_edge")
    if by_edge is None:
        return [(int(n), int(n), rows) for n, rows in work["rows_by_length"].items()]
    return [
        (int(edge), int(real), rows)
        for edge, lengths in by_edge.items()
        for real, rows in lengths.items()
    ]
