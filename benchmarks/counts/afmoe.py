"""Forward operations of one chip's share of AFMoE (Trinity) on the embed
path: two per multiply-add of every projection of attention (q, k, v, the
output gate, o), of the dense layer's SwiGLU MLP, of the shared expert, of
the router and of the routed experts, and of attention's two products
over the (query, key) pairs each layer's mask leaves:

- a full layer: every causal pair, `n (n + 1) / 2` for a row of n tokens;
- a sliding layer: the pairs within the window W, `W (W + 1) / 2 +
  (n - W) W` for n > W, and the causal pairs of a row no longer.

Nothing for the norms (four a layer, QK-norm, the final one), rotary,
softmax, the gate's sigmoid, sorting, the combine and the embedding's
gather. The untied output head is not computed on this path and not
counted. As `benchmarks/counts/__init__.py` rules, the pairs are those
of the rows' real lengths and everything that grows with the tokens is
counted at the tokens dispatched; the routed experts at the slots the
program measured (`work["slots_held"]`), else at their expectation over
the dispatched tokens (`counts/deepseek_v2.py`).
"""

from __future__ import annotations

from benchmarks.counts import pair_rows, pairs_unknown

KERNELS = ("flash_attention_window", "moe_grouped_matmul")


def _size(config) -> int:
    return {"float32": 4, "bfloat16": 2}[config["param_dtype"]]


def sliding_layers(config) -> int:
    return sum(
        config["layer_types"][i] == "sliding_attention"
        for i in range(config["num_hidden_layers"])
    )


def expert_layers(config) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def attention_params(config) -> int:
    """Matrix parameters of one layer's attention: q, the output gate and
    o over the query heads, k and v over the key/value heads."""
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    return 3 * h * q + 2 * h * config["num_key_value_heads"] * d


def expert_params(config) -> int:
    """Of one routed expert (the shared one is as wide): gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config) -> tuple:
    """(matrix parameters of a dense layer, of an expert layer): attention,
    the feed-forward, the router, every expert held."""
    h = config["hidden_size"]
    dense = attention_params(config) + 3 * h * config["intermediate_size"]
    expert = (
        attention_params(config)
        + (config["num_shared_experts"] + config["num_experts"]) * expert_params(config)
        + h * config["num_experts"]
    )
    return dense, expert


def causal_pairs(length: int) -> int:
    return length * (length + 1) // 2


def window_pairs(config, length: int) -> int:
    """(query, key) pairs a sliding layer reads in a row of `length`."""
    window = config["sliding_window"]
    if length <= window:
        return causal_pairs(length)
    return causal_pairs(window) + (length - window) * window


def pair_flops(config) -> float:
    """Attention's two products for one (query, key) pair of one layer,
    every query head: the score and the weighted value, head_dim each."""
    return 2.0 * 2 * config["head_dim"] * config["num_attention_heads"]


def _tokens(work) -> int:
    return sum(int(length) * rows for length, rows in work["rows_by_length"].items())


def slots_held(config, work) -> float:
    """Routed slots over all expert layers: measured where `work` carries
    them, else every dispatched token's k."""
    if work.get("slots_held") is not None:
        return float(work["slots_held"])
    return _tokens(work) * config["num_experts_per_tok"] * expert_layers(config)


def flops_per_token_dense_parts(config) -> float:
    """Everything but attention's products and the routed experts, a
    token through all the layers."""
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    n_expert = expert_layers(config)
    per_token = layers * attention_params(config)
    per_token += (layers - n_expert) * 3 * h * config["intermediate_size"]
    per_token += n_expert * (
        config["num_shared_experts"] * expert_params(config) + h * config["num_experts"]
    )
    return 2.0 * per_token


def sliding_pairs(config, work) -> int:
    """Every sliding layer's pairs of the rows' real lengths, once."""
    return sum(rows * window_pairs(config, real) for _edge, real, rows in pair_rows(work))


def full_pairs(config, work) -> int:
    """Every full layer's pairs of the rows' real lengths, once."""
    return sum(rows * causal_pairs(real) for _edge, real, rows in pair_rows(work))


def score_flops(config, work) -> float:
    sliding = sliding_layers(config)
    full = config["num_hidden_layers"] - sliding
    pairs = sliding * sliding_pairs(config, work) + full * full_pairs(config, work)
    return pairs * pair_flops(config)


def forward_flops(config, work):
    if pairs_unknown(work):
        return None
    return (
        _tokens(work) * flops_per_token_dense_parts(config)
        + score_flops(config, work)
        + slots_held(config, work) * 2.0 * expert_params(config)
    )


def kernel_work(config, kernel, work):
    """(operations, bytes) a kernel's calls needed for `work`.

    `flash_attention_window`: the sliding layers' two products over the
    pairs within the window of the rows' real lengths; q, k, v in and the
    result out once each at `param_dtype`, over the dispatched tokens.

    `moe_grouped_matmul`: 2 * slots * 3 * hidden * expert width; each
    slot's rows in (hidden twice, the expert width once, at
    `param_dtype`) and out (the expert width twice, hidden once, in
    float32), and every expert's three matrices once a call, for
    `work["dispatches"]` dispatches, as `counts/deepseek_v2.py` counts it."""
    size = _size(config)
    if kernel == "flash_attention_window":
        if pairs_unknown(work):
            return None
        layers, d = sliding_layers(config), config["head_dim"]
        heads = config["num_attention_heads"] + config["num_key_value_heads"]
        moved = 2 * heads * d * size  # q and the result; k and v
        return (
            layers * sliding_pairs(config, work) * pair_flops(config),
            float(_tokens(work) * layers * moved),
        )
    if kernel == "moe_grouped_matmul":
        h, f = config["hidden_size"], config["moe_intermediate_size"]
        slots = slots_held(config, work)
        rows = slots * ((2 * h + f) * size + (2 * f + h) * 4)
        calls = work.get("dispatches", 0) * expert_layers(config)
        matrices = calls * config["num_experts"] * expert_params(config) * size
        return slots * 2.0 * expert_params(config), float(rows + matrices)
    return None
