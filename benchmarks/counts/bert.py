"""Forward operations of a BERT encoder: two per multiply-add of the
dense layers and of attention's two products; nothing for embeddings
(gathers), norms, softmax and GELU (under 2%)."""

from __future__ import annotations


def flops_per_row(config, length: int) -> float:
    h, f = config["hidden_size"], config["intermediate_size"]
    dense = 4 * h * h + 2 * h * f  # q, k, v, o and the two MLP products
    attention = 2 * length * h  # scores and weighted values, per token
    return 2.0 * config["num_hidden_layers"] * length * (dense + attention)


def forward_flops(config, work) -> float:
    return sum(
        flops_per_row(config, int(length)) * rows
        for length, rows in work["rows_by_length"].items()
    )


def kernel_work(config, kernel, work):
    """`flash_attention`: per row and layer, 4*L*L*hidden operations and
    q, k, v in and o out at `param_bytes` each (the key mask is under 1%).
    Counted for the rows the window completed, at their padded lengths and
    the true head size: rows that only fill a batch, and the lanes that
    pad a 64-wide head to 128, are the kernel's cost and not its work."""
    if kernel != "flash_attention":
        return None
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    item = {"float32": 4, "bfloat16": 2}[config["param_dtype"]]
    flops = bytes_ = 0.0
    for length, rows in work["rows_by_length"].items():
        length = int(length)
        flops += rows * layers * 4.0 * length * length * h
        bytes_ += rows * layers * 4.0 * length * h * item
    return flops, bytes_
