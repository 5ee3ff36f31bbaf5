"""Forward operations of the Jamba hybrid on the embed path: two per
multiply-add of every projection (Mamba's in, x, dt and out; attention's
q, k, v and o; the SwiGLU MLP's three) and of attention's two products at
half the square, since causal. Nothing for the recurrence (9 * d_inner *
d_state a token and Mamba layer, 0.34% of the whole), the convolution,
the norms, the gates and the embedding's gather: under 1% together. The
tied output head is not computed on this path and not counted. The
projections are counted at the tokens dispatched, attention's pairs at
the row's real length, as `benchmarks/counts/__init__.py` rules."""

from __future__ import annotations

from benchmarks.counts import pair_rows, pairs_unknown

HEAD_DIM = 128


def _is_attention(config, i: int) -> bool:
    return i % config["attn_layer_period"] == config["attn_layer_offset"]


def mamba_layers(config) -> int:
    return sum(
        not _is_attention(config, i) for i in range(config["num_hidden_layers"])
    )


def layer_params(config) -> tuple:
    """(matrix parameters of a Mamba layer, of an attention layer): what
    a token is multiplied by."""
    h, f = config["hidden_size"], config["intermediate_size"]
    di = config["mamba_expand"] * h
    n, r = config["mamba_d_state"], config["mamba_dt_rank"]
    dh = config.get("head_dim", HEAD_DIM)
    q = config["num_attention_heads"] * dh
    kv = config["num_key_value_heads"] * dh
    mlp = 3 * h * f
    mamba = h * 2 * di + di * (r + 2 * n) + r * di + di * h + mlp
    attention = 2 * h * q + 2 * h * kv + mlp
    return mamba, attention


def flops_per_row(config, length: int, real: int | None = None) -> float:
    """Of a row dispatched at `length` tokens of which `real` are its own
    (all of them where `real` is not given)."""
    real = length if real is None else real
    layers = config["num_hidden_layers"]
    n_mamba = mamba_layers(config)
    mamba, attention = layer_params(config)
    q = config["num_attention_heads"] * config.get("head_dim", HEAD_DIM)
    # scores and weighted values: 2 products * 2 * (real / 2) keys * q a query
    products = 2.0 * real * real * q
    per_token = 2.0 * (n_mamba * mamba + (layers - n_mamba) * attention)
    return length * per_token + (layers - n_mamba) * products


def forward_flops(config, work):
    if pairs_unknown(work):
        return None
    return sum(
        flops_per_row(config, edge, real) * rows for edge, real, rows in pair_rows(work)
    )


def kernel_work(config, kernel, work):
    """`selective_scan`: per token and Mamba layer, 9 * d_inner * d_state
    operations (exp's argument, exp, two products and a sum for the
    state, a product and a sum for y, dt * h, the gate) and the bytes the
    kernel has to move once: h, dt and z in, and B and C, at `scan_dtype`
    (the configuration keeps the recurrence's inputs in it, and float32
    is what the kernel is handed), y out at `param_dtype`. Every term
    grows with the tokens: counted for the rows the window completed at
    their dispatched lengths; rows that only fill a batch are the
    kernel's cost and not its work."""
    if kernel != "selective_scan":
        return None
    di = config["mamba_expand"] * config["hidden_size"]
    n = config["mamba_d_state"]
    size = {"float32": 4, "bfloat16": 2}
    out, scan = size[config["param_dtype"]], size[config["scan_dtype"]]
    tokens = sum(
        int(length) * rows for length, rows in work["rows_by_length"].items()
    )
    token_layers = float(tokens * mamba_layers(config))
    return (
        token_layers * 9.0 * di * n,
        token_layers * (di * (3.0 * scan + out) + 2.0 * n * scan),
    )
