"""Forward operations of Xing4.0 on the embed path: DeepSeek-V2's count of
latent attention, the dense layer, the shared expert, the router and the
routed experts at the slots the program measured (`counts/deepseek_v2.py`,
whose functions read this configuration's keys), and the mixes of the
manifold-constrained hyper-connections, two a layer, a dispatched token
each:

- `phi`'s product: 2 n C (2n + n^2), 688,128 at n 4 and C 3,584;
- the pre-mix u = sum_i H_pre[i] X[i]: 2 n C;
- the post-mix sum_i H_res[j, i] X[i] + H_post[j] F: 2 n^2 C + 2 n C.

Nothing for the stream's sum of squares, the gates, the 20 Sinkhorn steps
(about 1,300 operations a token), the norms, softmax, rotary, sorting, the
combine and the embedding's gather. The untied output head and the
multi-token-prediction block are not computed on this path and not
counted. As `benchmarks/counts/__init__.py` rules, attention's pairs are
those of the rows' real lengths and everything that grows with the tokens
is counted at the tokens dispatched.
"""

from __future__ import annotations

from benchmarks.counts import deepseek_v2 as v2
from benchmarks.counts import pairs_unknown

KERNELS = ("flash_attention", "moe_grouped_matmul", "hc_pre", "hc_post")
#: a layer's hyper-connections: one around attention, one around the feed-forward
SUBLAYERS = 2


def _size(config) -> int:
    return {"float32": 4, "bfloat16": 2}[config["param_dtype"]]


def coefficients(config) -> int:
    n = config["hc_mult"]
    return 2 * n + n * n


def hyper_params(config) -> int:
    """Of one hyper-connection: phi, its bias and three gains."""
    m = coefficients(config)
    return config["hc_mult"] * config["hidden_size"] * m + m + 3


def mix_flops(config) -> float:
    """One hyper-connection's counted operations a token: phi's product,
    the pre-mix and the post-mix."""
    n, hidden = config["hc_mult"], config["hidden_size"]
    return 2.0 * n * hidden * (coefficients(config) + 1 + n + 1)


def _tokens(work) -> int:
    return sum(int(length) * rows for length, rows in work["rows_by_length"].items())


def forward_flops(config, work):
    if pairs_unknown(work):
        return None
    mixes = SUBLAYERS * config["num_hidden_layers"] * mix_flops(config)
    return v2.forward_flops(config, work) + _tokens(work) * mixes


def kernel_work(config, kernel, work):
    """(operations, bytes) a kernel's calls needed for `work`.

    `hc_pre`, a token: phi's product (2 n C (2n + n^2)), the sum of squares
    (2 n C), the pre-mix (2 n C) and the Sinkhorn steps (4 n^2 each); the
    stream read once (4 n C bytes), u written at `param_dtype` and H_post
    and H_res in float32. `hc_post`, a token: 2 n^2 C + 2 n C; the stream
    and F read and the stream written in float32 (4 (2 n + 1) C bytes),
    H_post and H_res read. Both over the dispatched tokens, twice a layer;
    `phi` (1.4 MB a call, 0.13% of a call's bytes at 16,384 tokens) is left
    out. `flash_attention` and `moe_grouped_matmul`: `counts/deepseek_v2.py`."""
    if kernel not in ("hc_pre", "hc_post"):
        return v2.kernel_work(config, kernel, work)
    n, hidden = config["hc_mult"], config["hidden_size"]
    width, m = n * hidden, coefficients(config)
    calls = _tokens(work) * SUBLAYERS * config["num_hidden_layers"]
    mixes = 4 * (n + n * n)
    if kernel == "hc_pre":
        steps = 4 * n * n * config["hc_sinkhorn_iters"]
        flops = 2 * width * m + 4 * width + steps
        moved = 4 * width + _size(config) * hidden + mixes
    else:
        flops = 2 * n * n * hidden + 2 * width
        moved = 4 * (2 * width + hidden) + mixes
    return float(calls * flops), float(calls * moved)
