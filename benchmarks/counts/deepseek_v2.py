"""Forward operations of one chip's share of DeepSeek-V2 on the embed
path: two per multiply-add of every projection of latent attention (q_a,
q_b, kv_a, kv_b, o), of attention's two products at half the square
(causal) over the key size (nope + rope) and the value size, of the
dense layer's SwiGLU MLP, of the shared experts, of the router, and of
the routed experts held here. Nothing for the norms, softmax, rotary,
gates, sorting, the combine and the embedding's gather. The untied
output head is not computed on this path and not counted. Attention's
pairs are counted at the rows' real lengths and everything that grows
with the tokens at the tokens dispatched, as
`benchmarks/counts/__init__.py` rules.

**The routed experts are counted at the slots that fell on held
experts** where `work` carries them (`slots_held`, the window's delta of
the program's device-measured counter `moe.slots_held`, which the driver
puts there); where it does not (a hand-made `work`, a program without
the counter) they are counted at their expectation, dispatched tokens x
experts a token x held / routed, an expectation and not a measurement,
and over pad tokens too although the program routes none of them.
"""

from __future__ import annotations

from benchmarks.counts import pair_rows, pairs_unknown

KERNELS = ("flash_attention", "moe_grouped_matmul")


def _held(config) -> int:
    first, end = config.get("experts_held", (0, config["n_routed_experts"]))
    return end - first


def _routed(config) -> int:
    return config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"]
    )


def expert_layers(config) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def attention_params(config) -> int:
    """Matrix parameters of one layer's latent attention."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rq, rkv, dv = config["q_lora_rank"], config["kv_lora_rank"], config["v_head_dim"]
    return (
        h * rq + rq * heads * (nope + rope) + h * (rkv + rope)
        + rkv * heads * (nope + dv) + heads * dv * h
    )


def expert_params(config) -> int:
    """Of one routed expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config) -> tuple:
    """(matrix parameters of the dense layer, of an expert layer as held
    here): attention, the feed-forward, the router."""
    h = config["hidden_size"]
    dense = attention_params(config) + 3 * h * config["intermediate_size"]
    expert = (
        attention_params(config)
        + config["n_shared_experts"] * expert_params(config)
        + h * _routed(config)
        + _held(config) * expert_params(config)
    )
    return dense, expert


def score_width(config) -> int:
    """What a (query, key) pair costs in multiply-adds: the key size for
    the score and the value size for the weighted sum."""
    return (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    )


def expected_slots_per_token(config) -> float:
    """How many of a token's routed slots fall on held experts, a layer,
    if the router spreads them evenly."""
    return config["num_experts_per_tok"] * _held(config) / _routed(config)


def _tokens(work) -> int:
    return sum(int(length) * rows for length, rows in work["rows_by_length"].items())


def slots_held(config, work) -> float:
    """Routed slots on held experts over all expert layers: measured
    where `work` carries them, else the expectation."""
    if work.get("slots_held") is not None:
        return float(work["slots_held"])
    return _tokens(work) * expected_slots_per_token(config) * expert_layers(config)


def flops_per_token_dense_parts(config) -> float:
    """Everything but attention's scores and the routed experts, a token
    through all the layers."""
    h, layers = config["hidden_size"], config["num_hidden_layers"]
    n_expert = expert_layers(config)
    per_token = layers * attention_params(config)
    per_token += (layers - n_expert) * 3 * h * config["intermediate_size"]
    per_token += n_expert * (
        config["n_shared_experts"] * expert_params(config) + h * _routed(config)
    )
    return 2.0 * per_token


def score_flops(config, work) -> float:
    heads, layers = config["num_attention_heads"], config["num_hidden_layers"]
    # two products over half the square of the row's real tokens: 2 *
    # (real / 2) * width a real query
    return sum(
        rows * float(real) ** 2 * score_width(config) * heads * layers
        for _edge, real, rows in pair_rows(work)
    )


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

    `flash_attention`: the two products at half the square of the rows'
    real lengths over the key and the value size; q, k, v in and the
    result out once each at `param_dtype`, for the rows completed at
    their dispatched lengths.

    `moe_grouped_matmul`: 2 * slots * 3 * hidden * expert width; each
    slot's rows in (hidden twice, the expert width once, at
    `param_dtype`) and out (the expert width twice, hidden once, in
    float32), and each held expert's three matrices once a call, for
    `work["dispatches"]` dispatches where the reader gives them (without
    them the matrices are left out, and the share reads low)."""
    size = {"float32": 4, "bfloat16": 2}[config["param_dtype"]]
    if kernel == "flash_attention":
        if pairs_unknown(work):
            return None
        heads, layers = config["num_attention_heads"], config["num_hidden_layers"]
        keys = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        moved = heads * (2 * keys + 2 * config["v_head_dim"]) * size
        return score_flops(config, work), float(_tokens(work) * layers * moved)
    if kernel == "moe_grouped_matmul":
        h, f = config["hidden_size"], config["moe_intermediate_size"]
        slots = slots_held(config, work)
        rows = slots * ((2 * h + f) * size + (2 * f + h) * 4)
        calls = work.get("dispatches", 0) * expert_layers(config)
        matrices = calls * _held(config) * expert_params(config) * size
        return slots * 2.0 * expert_params(config), float(rows + matrices)
    return None
