"""Forward operations of a BERT encoder: two per multiply-add of the
dense layers and of attention's two products; nothing for embeddings
(gathers), norms, softmax and GELU (under 2%). The dense layers are
counted at the tokens dispatched, attention's pairs (every real query
with every real key: not causal) at the row's real length, as
`benchmarks/counts/__init__.py` rules."""

from __future__ import annotations

from benchmarks.counts import pair_rows, pairs_unknown


def flops_per_row(config, length: int, real: int | None = None) -> float:
    """Of a row dispatched at `length` tokens of which `real` are its own
    (all of them where `real` is not given)."""
    real = length if real is None else real
    h, f = config["hidden_size"], config["intermediate_size"]
    dense = length * (4 * h * h + 2 * h * f)  # q, k, v, o and the two MLP products
    attention = 2 * real * real * h  # scores and weighted values
    return 2.0 * config["num_hidden_layers"] * (dense + attention)


def forward_flops(config, work):
    if pairs_unknown(work):
        return None
    return sum(
        flops_per_row(config, edge, real) * rows for edge, real, rows in pair_rows(work)
    )


def kernel_work(config, kernel, work):
    """`flash_attention`: per row and layer, 4 * real * real * hidden
    operations (the pairs of the row's real tokens) and q, k, v in and o
    out at `param_bytes` each over the dispatched length (the key mask is
    under 1%). Counted for the rows the window completed at the true head
    size: rows that only fill a batch, and the lanes that pad a 64-wide
    head to 128, are the kernel's cost and not its work."""
    if kernel != "flash_attention" or pairs_unknown(work):
        return None
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    item = {"float32": 4, "bfloat16": 2}[config["param_dtype"]]
    flops = bytes_ = 0.0
    for edge, real, rows in pair_rows(work):
        flops += rows * layers * 4.0 * real * real * h
        bytes_ += rows * layers * 4.0 * edge * h * item
    return flops, bytes_
