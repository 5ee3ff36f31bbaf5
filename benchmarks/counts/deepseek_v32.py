"""Forward operations of one chip's share of DeepSeek-V3.2-Exp on the
embed path, counted as published: what `counts/deepseek_v2.py` counts
for latent attention's projections, the dense layer's MLP, the shared
expert, the router and the routed experts held here, and for DeepSeek
sparse attention

- the indexer's three projections (index queries from the query latent,
  the one index key and the heads' weights from the hidden state), two
  operations a multiply-add, in every layer, and
- its scores: every causal (query, key) pair x `index_n_heads` x
  `index_head_dim` multiply-adds (ReLU, the weights and the sum over
  heads are not counted), both in the rows longer than `index_topk`: a
  row no longer selects every key, and neither the program nor the
  reference runs an indexer for it;
- attention's two products over the SELECTED pairs only, over the key
  size (nope + rope) and the value size: a row of bucket L has
  `index_topk (index_topk + 1) / 2 + (L - index_topk) index_topk` of
  them and not half the square. The count is of the model's work,
  whatever implements it: a kernel that computes the masked causal
  square does 4.3 times the counted work at 16,384 and reads that much
  lower against its roofline; a gather would compute none extra.

Nothing for the norms, softmax, rotary, gates, the selection's search,
sorting, the combine and the embedding's gather; no output head and no
multi-token-prediction block. The routed experts are counted at the
measured slots where `work` carries them, else at their expectation, as
`counts/deepseek_v2.py` says.

As `benchmarks/counts/__init__.py` rules, the pairs (selected, causal)
are those of the rows' REAL lengths and everything that grows with the
tokens is counted at the tokens dispatched. Whether a row has an
indexer is decided by its DISPATCHED edge, since the program builds one
for a bucket and not for a row: a row of 1,500 tokens dispatched at
8,192 runs an indexer over its 1,500 (and selects every causal key).
"""

from __future__ import annotations

from benchmarks.counts import deepseek_v2 as v2
from benchmarks.counts import pair_rows, pairs_unknown
from benchmarks.counts.deepseek_v2 import (  # noqa: F401  attention_params: by this family's name too
    attention_params,
    expert_params,
    score_width,
    slots_held,
)

KERNELS = ("flash_attention", "dsa_index_scores", "moe_grouped_matmul")


def indexer_params(config) -> int:
    """Matrix parameters of one layer's indexer."""
    heads, dim = config["index_n_heads"], config["index_head_dim"]
    h = config["hidden_size"]
    return config["q_lora_rank"] * heads * dim + h * dim + h * heads


def layer_params(config) -> tuple:
    """(matrix parameters of the dense layer, of an expert layer as held
    here): attention with its indexer, the feed-forward, the router."""
    dense, expert = v2.layer_params(config)
    return dense + indexer_params(config), expert + indexer_params(config)


def causal_pairs(length: int) -> int:
    return length * (length + 1) // 2


def selected_pairs(config, length: int) -> int:
    """(query, key) pairs attention reads in a row of `length` tokens."""
    top_k = config["index_topk"]
    if length <= top_k:
        return causal_pairs(length)
    return causal_pairs(top_k) + (length - top_k) * top_k


def _rows(work):
    return [(int(length), rows) for length, rows in work["rows_by_length"].items()]


def _tokens(work) -> int:
    return sum(length * rows for length, rows in _rows(work))


def flops_per_token_dense_parts(config, selects: bool = True) -> float:
    """Everything but the index scores, attention's scores and the routed
    experts, a token through all the layers; the indexer's projections
    for the tokens of a row that selects."""
    layers = config["num_hidden_layers"]
    return v2.flops_per_token_dense_parts(config) + (
        2.0 * layers * indexer_params(config) if selects else 0.0
    )


def score_flops(config, work) -> float:
    """Attention's two products over the selected pairs of the rows' real
    lengths."""
    heads, layers = config["num_attention_heads"], config["num_hidden_layers"]
    return sum(
        rows * selected_pairs(config, real) * 2.0 * score_width(config) * heads * layers
        for _edge, real, rows in pair_rows(work)
    )


def indexed_pairs(config, work) -> int:
    """Every causal pair of the real tokens of the rows whose bucket has
    an indexer."""
    return sum(
        rows * causal_pairs(real)
        for edge, real, rows in pair_rows(work)
        if edge > config["index_topk"]
    )


def index_flops(config, work) -> float:
    """The index scores over every causal pair of the rows that select."""
    width = config["index_n_heads"] * config["index_head_dim"]
    return indexed_pairs(config, work) * 2.0 * width * config["num_hidden_layers"]


def forward_flops(config, work):
    if pairs_unknown(work):
        return None
    dense = sum(
        length * rows
        * flops_per_token_dense_parts(config, length > config["index_topk"])
        for length, rows in _rows(work)
    )
    return (
        dense
        + score_flops(config, work)
        + index_flops(config, work)
        + slots_held(config, work) * 2.0 * expert_params(config)
    )


def kernel_work(config, kernel, work):
    """(operations, bytes) a kernel's calls needed for `work`.

    `flash_attention`: the two products over the selected pairs; q, the
    up-projected keys and values and the result moved once each at
    `param_dtype` (a dispatched token), and the selection, a byte a
    causal pair of the real tokens, once.

    `dsa_index_scores`: `index_flops`; the index queries, the one index
    key a token (`param_dtype`) and the heads' weights (float32) in (a
    dispatched token), and one float32 score a causal pair of the real
    tokens out: the kernel writes the scores and another (`dsa_select`)
    reads them.

    `moe_grouped_matmul`: as `counts/deepseek_v2.py` counts it."""
    if kernel == "moe_grouped_matmul":
        return v2.kernel_work(config, kernel, work)
    if kernel not in KERNELS or pairs_unknown(work):
        return None
    size = {"float32": 4, "bfloat16": 2}[config["param_dtype"]]
    layers = config["num_hidden_layers"]
    pairs = indexed_pairs(config, work)
    if kernel == "flash_attention":
        heads = config["num_attention_heads"]
        keys = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        moved = heads * (2 * keys + 2 * config["v_head_dim"]) * size
        return (
            score_flops(config, work),
            float(_tokens(work) * layers * moved + pairs * layers),
        )
    heads, dim = config["index_n_heads"], config["index_head_dim"]
    tokens = sum(n * rows for n, rows in _rows(work) if n > config["index_topk"])
    moved = tokens * ((heads * dim + dim) * size + heads * 4) + pairs * 4
    return index_flops(config, work), float(moved * layers)
