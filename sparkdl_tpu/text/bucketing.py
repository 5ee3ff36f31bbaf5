"""Sequence-length bucketing: length-aware feeder geometries for text.

``transformers/text.py`` used to pad every tokenized row to
``maxLength`` — the text analogue of the image pad waste PR 2 killed:
a corpus whose lengths are uniform in [16, 512] wastes >50% of every
dispatched token on padding when padded to 512. This module makes
variable length first-class without giving up static shapes: a small
**ladder** of bucket edges is elected up front, each row pads only to
the smallest edge >= its length, and rows route to one device stream
per bucket. The DeviceFeeder already keys streams by (device_fn, batch
geometry) — buckets are just sibling geometries of ONE device fn, so
the whole continuous-batching engine (cross-partition coalescing,
staged H2D, async readback) applies per bucket with no new machinery,
and XLA compiles one program per (batch, bucket) pair.

Ladder election (``bucket_ladder``): the compile-count/pad-waste dial.

- ``pow2``: powers of two from ``SPARKDL_TEXT_MIN_BUCKET`` up to
  ``max_length`` — log2(max) programs, but lengths uniform within an
  octave average 25% pad (a row lands anywhere in (edge/2, edge]).
- ``half`` (default): powers of two plus the 3*2^k midpoints
  (16, 24, 32, 48, 64, ...) — 2x the programs, worst-case uniform pad
  ~12-17% per step (edge ratios alternate 4/3 and 3/2), under the 15%
  acceptance bar with real batching overheads included.
- an explicit comma list (``SPARKDL_TEXT_BUCKETS=32,48,64``) for
  corpora with known length clusters.

``max_length`` always caps the ladder (rows longer than the top edge
TRUNCATE to it — counted in ``text.truncated_rows``, the documented
lossy case), and every edge <= ``SPARKDL_TEXT_MIN_BUCKET`` collapses
into one smallest bucket: sub-16 buckets multiply compiled programs for
negligible pad savings.

Instrumentation (all consumed by ``obs report``'s text line and the
``BENCH_MODE=text`` record): ``text.bucket_rows.<bucket>`` counts rows
routed per elected edge, and ``text.tokens`` / ``text.pad_tokens`` split
dispatched tokens into real vs bucket-edge padding (the row-tail batch
padding below them rides the existing ``feeder.pad_rows``). The
``tokenize`` span times the routing loop of one chunk of a partition,
and ``text.tokenize_chunks`` counts the chunks.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from sparkdl_tpu.obs import span
from sparkdl_tpu.runtime import knobs
from sparkdl_tpu.utils.metrics import metrics


def bucketing_enabled() -> bool:
    """``SPARKDL_TEXT_BUCKETING`` gates the length-aware text path in
    BOTH engines (TextEmbedder's per-bucket streams and the serving
    router's token-payload bucketing); ``0``/``off`` restores
    pad-to-``maxLength`` — the A/B arm and the escape hatch."""
    return knobs.get_flag("SPARKDL_TEXT_BUCKETING")


def min_bucket() -> int:
    return max(1, knobs.get_int("SPARKDL_TEXT_MIN_BUCKET"))


def _pow2_edges(lo: int, hi: int) -> List[int]:
    edges = []
    e = 1
    while e < hi:
        e <<= 1
        if e >= lo:
            edges.append(e)
    return edges


def _half_edges(lo: int, hi: int) -> List[int]:
    # powers of two AND the 3*2^k midpoints: 16, 24, 32, 48, 64, ...
    edges = set(_pow2_edges(lo, hi))
    e = 3
    while e < hi:
        if lo <= e:
            edges.add(e)
        e <<= 1
    return sorted(edges)


def _parse_edges(spec: str) -> List[int]:
    try:
        edges = sorted({int(tok) for tok in spec.split(",") if tok.strip()})
    except ValueError:
        raise ValueError(
            f"SPARKDL_TEXT_BUCKETS={spec!r}: expected 'pow2', 'half', "
            "or a comma list of integer edges (e.g. '32,48,64')"
        ) from None
    if any(e < 1 for e in edges):
        raise ValueError(
            f"SPARKDL_TEXT_BUCKETS={spec!r}: edges must be >= 1"
        )
    return edges


def bucket_ladder(max_length: int, spec: Optional[str] = None) -> Tuple[int, ...]:
    """The elected bucket edges for ``max_length``, ascending, top edge
    always exactly ``max_length``. ``spec`` overrides the
    ``SPARKDL_TEXT_BUCKETS`` knob ('pow2' | 'half' | explicit comma
    list); edges beyond ``max_length`` are dropped, edges at or under
    ``SPARKDL_TEXT_MIN_BUCKET`` collapse into one smallest bucket."""
    max_length = int(max_length)
    if max_length < 1:
        raise ValueError(f"max_length must be >= 1, got {max_length}")
    spec = spec if spec is not None else knobs.get_str("SPARKDL_TEXT_BUCKETS")
    lo = min(min_bucket(), max_length)
    if spec == "pow2":
        edges = _pow2_edges(lo, max_length)
    elif spec in ("half", "", None):
        edges = _half_edges(lo, max_length)
    else:
        edges = [e for e in _parse_edges(spec) if lo <= e]
    edges = [e for e in edges if e < max_length]
    ladder = tuple([lo] + edges + [max_length]) if lo < max_length else (max_length,)
    # dedupe while preserving order (lo may equal the first pow2 edge)
    out: List[int] = []
    for e in ladder:
        if not out or e > out[-1]:
            out.append(e)
    return tuple(out)


def bucket_for(length: int, ladder: Sequence[int]) -> int:
    """Smallest ladder edge >= ``length``; the TOP edge for anything
    longer (the caller truncates to it — the documented lossy case)."""
    for e in ladder:
        if length <= e:
            return e
    return ladder[-1]


def next_bucket(length: int) -> int:
    """Smallest grid edge >= ``length`` on the configured ladder grid,
    UNCAPPED — the serving router's seq bucket (the online path has no
    ``maxLength`` of its own; the router caps the result at the
    registry spec's position table and rejects over-long payloads at
    admission). An explicit comma ladder falls back to ``length``
    itself past its last edge (served unbucketed rather than silently
    truncated)."""
    length = max(int(length), min_bucket())
    spec = knobs.get_str("SPARKDL_TEXT_BUCKETS")
    if spec not in ("pow2", "half", "", None):
        for e in _parse_edges(spec):
            if length <= e:
                return e
        return length
    e = 1
    while e < length:
        e <<= 1
    if spec == "pow2" or e <= min_bucket():
        return e
    mid = 3 * (e >> 2)  # the half-octave midpoint under e
    return mid if length <= mid and mid >= min_bucket() else e


#: Floor of a tokenize chunk: under it a tiny ``batchSize`` would make a
#: span, a counter update and a queue item of every few rows.
_MIN_CHUNK_ROWS = 32
#: Chunks in which a partition hands over its share of one dispatched
#: batch. The feeder's queue holds four items: with four chunks a share
#: the partitions block about one share ahead of the owner, who needs
#: the interpreter lock they tokenize under to pack and dispatch.
_CHUNKS_PER_SHARE = 4


def _route_chunk(
    cells: Sequence,
    start: int,
    stop: int,
    tokenize: Callable[[str], Sequence[int]],
    ladder: Sequence[int],
) -> dict:
    """Tokenize ``cells[start:stop]`` and route each row by its length:
    bucket edge -> ([original row index], [token id list]); null cells
    and cells the tokenizer raised on are left out. One ``tokenize``
    span a chunk, never one a row, and the chunk's share of the text
    counters."""
    routed: dict = {}
    with span("tokenize") as sp:
        for i, text in enumerate(cells[start:stop], start):
            if text is None:
                continue
            try:
                ids = tokenize(text)
            except Exception:
                continue
            b = bucket_for(len(ids), ladder)
            idxs, rows = routed.setdefault(b, ([], []))
            idxs.append(i)
            rows.append(ids)
        sp.add(
            rows=sum(len(rows) for _, rows in routed.values()),
            tokens=sum(
                len(ids) for _, rows in routed.values() for ids in rows
            ),
        )
    metrics.inc("text.tokenize_chunks")
    if not routed:
        return routed
    real_tokens = 0
    pad_tokens = 0
    for b, (idxs, rows) in routed.items():
        metrics.inc(f"text.bucket_rows.{b}", len(idxs))
        for ids in rows:
            k = min(len(ids), b)
            real_tokens += k
            pad_tokens += b - k
    metrics.inc("text.tokens", real_tokens)
    metrics.inc("text.pad_tokens", pad_tokens)
    return routed


def _pack(rows: Sequence[Sequence[int]], edge: int) -> np.ndarray:
    from sparkdl_tpu.transformers.text import pad_or_truncate

    batch = np.zeros((len(rows), edge), np.int32)
    for j, ids in enumerate(rows):
        batch[j] = pad_or_truncate(ids, edge)
    return batch


def run_bucketed(
    cells: Sequence,
    tokenize: Callable[[str], Sequence[int]],
    device_fn: Callable,
    batch_size: int,
    max_length: int,
    prefetch: Optional[int] = None,
    ladder: Optional[Sequence[int]] = None,
) -> List[Optional[np.ndarray]]:
    """Length-aware equivalent of the pad-to-``max_length`` text loop:
    same per-cell output contract as ``run_batched_shared`` (ndarray
    rows, None where the cell was null or tokenization failed).

    Tokenization runs on the partition thread, and a row's length
    decides its routing — a row's, not the partition's. The partition is
    tokenized in chunks, and each chunk's rows go to their (device_fn,
    bucket) feeder streams as soon as the chunk is routed, largest edge
    first: the device starts on the first batch that the concurrent
    partitions fill TOGETHER while they tokenize the rest, so a chunk is
    a quarter of one partition's share of a dispatched batch: its rows
    over the observed concurrency (1 outside an executor), in
    ``_CHUNKS_PER_SHARE``. Every bucket's stream stays open until the
    partition's last chunk is in (``feeder.run_shared``'s ``stream``),
    so no part-filled batch is flushed while rows are still to come, and
    the device fn compiles one program per bucket it actually sees.
    """
    from sparkdl_tpu.runtime.executor import current_task_context
    from sparkdl_tpu.runtime.feeder import ingest_span
    from sparkdl_tpu.transformers import execution

    n = len(cells)
    if n == 0:
        return []
    ladder = tuple(ladder) if ladder is not None else bucket_ladder(max_length)
    ctx = current_task_context()
    concurrency = 1 if ctx is None else max(1, ctx.concurrency)
    partition = None if ctx is None else ctx.partition_index

    def stream(dispatch_rows):
        chunk_rows = max(
            _MIN_CHUNK_ROWS,
            dispatch_rows // (concurrency * _CHUNKS_PER_SHARE),
        )
        for start in range(0, n, chunk_rows):
            routed = _route_chunk(
                cells, start, start + chunk_rows, tokenize, ladder
            )
            for b in sorted(routed, reverse=True):
                idxs, rows = routed[b]
                with ingest_span(start, partition) as sp:
                    batch = _pack(rows, b)
                    sp.add(rows=len(rows), bytes=int(batch.nbytes))
                yield np.asarray(idxs), batch

    return execution.run_batched_shared(
        cells, None, device_fn, batch_size, prefetch=prefetch, stream=stream
    )


__all__ = [
    "bucket_for",
    "bucket_ladder",
    "bucketing_enabled",
    "min_bucket",
    "run_bucketed",
]
