"""The routed-expert layer the four MoE text families share
(``models/deepseek_v2.py``, ``deepseek_v32.py``, ``afmoe.py``,
``xing4_0.py``), and the format in which what it counts rides back with
each row.

    Routed(u) = sum over the k experts the gate chose for u, of those
                this chip holds, of w_k SwiGLU_k(u)

**The gate** (:func:`route`) reads the configuration's DeepSeek names
(``n_routed_experts``, ``n_group``, ``topk_group``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``, ``scoring_func``); a family
whose published keys differ carries them as properties.

**The chip's share.** ``experts_held = (first, end)`` are the experts
whose weights are here. A (token, k) slot whose expert is absent is
computed by nobody here and nothing stands in for it: the partial sum
goes on. Pad tokens (id 0) are not routed. No slot is dropped at any
load: the slots are sorted by expert, absent ones last, and
``ops/grouped_matmul.py`` is given the held experts' group sizes. The
buffer they are sorted into has the rows the share implies
(:func:`slot_capacity`), and the worst-case buffer of every slot is the
other arm of a ``lax.cond`` on the measured load (:func:`routed`), so a
load over the share costs time and never a slot; a chip that holds every
expert has the one worst-case body and no conditional.

**The columns** (:func:`routed_model_function`): a routed family's
program returns [B, hidden + 3 + 2 x wide]: the embedding, then
``ROW_COUNTERS`` counted on the device, then each of the family's wide
counts in two columns. ``TextEmbedder`` strips them by
``mf.row_counters`` and adds each to the counter it names.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from sparkdl_tpu.models.layers import silu
from sparkdl_tpu.ops.moe_combine import gather_combine
from sparkdl_tpu.utils.profiler import scope

#: the columns every routed family's rows carry after the embedding: how
#: many of the row's routed slots fell on held experts, and how many of
#: its dispatch's expert layers worked on the sized slot buffer and on the
#: worst-case one (the two sum to the expert layers)
ROW_COUNTERS = ("moe.slots_held", "moe.buffer_sized", "moe.buffer_full")

#: a wide count rides back in two float32 columns, its multiple of this
#: and the rest: each is exact in float32, and so are their sums over a
#: partition's rows (a row's ``dsa.pairs_selected`` reaches 1.7e8)
WIDE_SPLIT = 4096

#: a routed family's kernel, by its keyword in the family's ``forward`` ->
#: the ModelFunction attribute its ``.kind`` is reported under
KERNEL_ROLES = {
    "attention_fn": "attention",
    "window_attention_fn": "window_attention",
    "indexer_fn": "indexer",
    "hyper": "residual",
    "experts_fn": "experts",
    "combine_fn": "combine",
}


def route(config, u, router, bias=None):
    """u [T, hidden], router [hidden, experts], both float32 ->
    (experts [T, k] int32, weights [T, k] float32) over all the model's
    experts. The configuration's own keys say which gate:

    - DeepSeek-V2 (``scoring_func`` softmax, ``group_limited_greedy``):
      softmax scores, a group ranked by
      its best score, top-k of the scores over the kept groups, and the
      weights EITHER renormalised (``norm_topk_prob``) OR scaled by
      ``routed_scaling_factor``.
    - DeepSeek-V3 and V3.2 (``scoring_func`` sigmoid, ``noaux_tc``):
      sigmoid scores s; the CHOICE is made on s + ``bias`` (a learned
      correction, [experts] float32), a group ranked by the sum of its
      two best, top-k over the kept groups; the weights are the chosen
      experts' s without the bias, renormalised (``norm_topk_prob``) and
      THEN scaled."""
    n, groups = config.n_routed_experts, config.n_group
    logits = jnp.einsum(
        "ti,io->to", u.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if config.scoring_func == "sigmoid":
        return _route_by_corrected_sigmoid(config, logits, bias)
    scores = jax.nn.softmax(logits, -1)
    if groups > 1:
        best = scores.reshape(-1, groups, n // groups).max(-1)
        _, kept = jax.lax.top_k(best, config.topk_group)
        keep = jnp.any(kept[..., None] == jnp.arange(groups), -2)  # [T, groups]
        scores = jnp.where(jnp.repeat(keep, n // groups, -1), scores, 0.0)
    weights, experts = jax.lax.top_k(scores, config.num_experts_per_tok)
    if config.norm_topk_prob and config.num_experts_per_tok > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    else:
        weights = weights * config.routed_scaling_factor
    return experts, weights


def _route_by_corrected_sigmoid(config, logits, bias):
    """``route``'s second gate (``noaux_tc``), from the router's logits."""
    n, groups, top_k = config.n_routed_experts, config.n_group, config.num_experts_per_tok
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias.astype(jnp.float32)
    if groups > 1:
        two_best, _ = jax.lax.top_k(choice.reshape(-1, groups, n // groups), 2)
        _, kept = jax.lax.top_k(two_best.sum(-1), config.topk_group)
        keep = jnp.any(kept[..., None] == jnp.arange(groups), -2)  # [T, groups]
        choice = jnp.where(jnp.repeat(keep, n // groups, -1), choice, -jnp.inf)
    _, experts = jax.lax.top_k(choice, top_k)
    weights = jnp.take_along_axis(scores, experts, -1)
    if config.norm_topk_prob and top_k > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts, weights * config.routed_scaling_factor


#: the grouped kernel's row tile at an expert layer's sizes
#: (``ops/grouped_matmul.py:choose_tiling``)
_ROW_TILE = 256
#: room above the held share's expectation in the sized slot buffer. The
#: load of a dispatch of 16,384 tokens spreads under 1% and its fullest
#: reading is 24.60% of the routed slots for a held quarter (PERF.md,
#: PR 32); a load over the buffer costs the worst-case arm's time and no
#: slot, so the low end of what covers every reading is the one to take
_CAPACITY_MARGIN = 1.25


def slot_capacity(config, tokens: int) -> int:
    """Rows of the routed path's slot buffer for a dispatch of ``tokens``:
    the chip's share of the ``tokens x k`` slots with ``_CAPACITY_MARGIN``
    of room, in whole row tiles; all the slots where that is no fewer (a
    chip that holds every expert, shapes too small to round)."""
    slots = tokens * config.num_experts_per_tok
    first, end = config.experts_held
    room = _CAPACITY_MARGIN * (end - first) / config.n_routed_experts * slots
    return min(slots, -(-math.ceil(room) // _ROW_TILE) * _ROW_TILE)


def _experts_and_combine(
    rows, experts_fn, experts, flat, order, sizes, slot, weights, held, *,
    combine_fn=gather_combine,
):
    """The held slots' three products over a buffer of ``rows`` slot rows
    (static; the sorted slots' first ``rows``, which must hold every held
    one) and their weighted sum back in token order [tokens, hidden]
    float32 by ``combine_fn`` (``ops/moe_combine.py``)."""
    top_k = weights.shape[1]
    with scope("moe.gather"):
        x = flat[order[:rows] // top_k]  # [rows, hidden]
    with scope("moe.experts"):
        gate = experts_fn(x, experts["gate"], sizes)
        up = experts_fn(x, experts["up"], sizes)
        y = experts_fn((silu(gate) * up).astype(x.dtype), experts["down"], sizes)
    with scope("moe.combine"):
        # back to (token, k) order; what the kernel left unwritten is not read
        return combine_fn(y, jnp.where(held, slot, -1), weights)


def _experts_in_chunks(
    rows, experts_fn, experts, flat, order, sizes, slot, weights, held
):
    """:func:`_experts_and_combine` over every slot, ``rows`` sorted slot
    rows a pass (static, a divisor of the slots): each pass gives the
    kernel the part of every group that lies in its rows and adds its
    weighted rows to their tokens. A pass past the last held slot does
    nothing, so the arm costs what the load costs. No slot is dropped."""
    tokens, top_k = weights.shape
    ends = jnp.cumsum(sizes)
    starts, total = ends - sizes, ends[-1]
    by_slot = weights.reshape(-1)

    def one_pass(out, lo):
        def compute(out):
            with scope("moe.gather"):
                at = jax.lax.dynamic_slice(order, (lo,), (rows,))
                part = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
                x = flat[at // top_k]
            with scope("moe.experts"):
                gate = experts_fn(x, experts["gate"], part)
                up = experts_fn(x, experts["up"], part)
                y = experts_fn((silu(gate) * up).astype(x.dtype), experts["down"], part)
            with scope("moe.combine"):
                # what the kernel left unwritten is not read
                mine = (lo + jnp.arange(rows) < total)[:, None]
                y = jnp.where(mine, y * by_slot[at][:, None], 0.0)
                return out.at[at // top_k].add(y)

        return jax.lax.cond(lo < total, compute, lambda out: out, out), None

    out = jnp.zeros((tokens, flat.shape[1]), jnp.float32)
    out, _ = jax.lax.scan(one_pass, out, jnp.arange(0, tokens * top_k, rows))
    return out


def routed(config, p, u, real, experts_fn, *, gate, combine_fn=gather_combine):
    """u [B, L, hidden] float32 (the norm's output), real [B, L] bool ->
    (the held experts' part of the routed sum [B, L, hidden] float32,
    how many of each row's slots fell on held experts [B] int32, whether
    the sized slot buffer held the load, a bool scalar). ``gate`` chooses
    each token's experts (:func:`route`, or what a family hands in its
    place); ``combine_fn`` sums the rows back in token order
    (``ops/moe_combine.py``; the worst-case arm in passes,
    ``worst_case_chunk_rows``, adds its own).

    The slot buffer is ``slot_capacity`` rows, and the worst-case buffer
    of every slot is the other arm of a ``lax.cond`` on the measured load:
    no slot is dropped in either. The slots are sorted held-first, so the
    first ``sum(sizes)`` rows are the same rows in both arms, the kernel
    visits the same tiles and the sized arm does the full one's arithmetic
    in its order: the same bits wherever the compiler fuses the two alike
    (PERF.md, PR 33). Where the two row counts are one there is one body."""
    rows, length, hidden = u.shape
    tokens, top_k = rows * length, config.num_experts_per_tok
    first, end = config.experts_held
    flat = u.reshape(tokens, hidden)
    # a gate without a correction bias is called with three arguments:
    # what stands in for ``route`` in a test or a planted fault takes three
    with scope("moe.route"):
        if "router_bias" in p:
            experts, weights = gate(config, flat, p["router"], p["router_bias"])
        else:
            experts, weights = gate(config, flat, p["router"])
    with scope("moe.routed"):
        held = (experts >= first) & (experts < end) & real.reshape(tokens, 1)
        # slots sorted by expert, those of absent experts (and of pad tokens) last
        key = jnp.where(held, experts - first, end - first).reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(
            key[:, None] == jnp.arange(end - first, dtype=key.dtype), 0, dtype=jnp.int32
        )
        slot = jnp.argsort(order).reshape(tokens, top_k)
        operands = (
            p["experts"], flat.astype(p["experts"]["gate"].dtype), order, sizes,
            slot, weights, held,
        )
        slots, capacity = tokens * top_k, slot_capacity(config, tokens)

        full = functools.partial(
            _experts_and_combine, slots, experts_fn, combine_fn=combine_fn
        )
        chunk = config.worst_case_chunk_rows
        if chunk and slots > chunk:
            full = functools.partial(_experts_in_chunks, chunk, experts_fn)
        if capacity == slots:
            fits, out = jnp.zeros((), bool), full(*operands)
        else:
            sized = functools.partial(
                _experts_and_combine, capacity, experts_fn, combine_fn=combine_fn
            )

            def worst_case(*operands):
                with scope("moe.worst_case"):
                    return full(*operands)

            fits = jnp.sum(sizes) <= capacity
            out = jax.lax.cond(fits, sized, worst_case, *operands)
        count = jnp.sum(held.reshape(rows, -1), 1, dtype=jnp.int32)
        return out.reshape(rows, length, hidden), count, fits


def routed_model_function(
    config, params, forward, kernels: dict, *, dtype, name: str,
    dispatched_token_counters: dict, batch_counters, wide_counters=(),
):
    """A routed family's ``embed`` ModelFunction over ids batches (or
    ``(ids, mask)`` tuples, as TextEmbedder feeds them).

    ``forward(config, params, ids, dtype=, **kernels)`` returns the
    embeddings [B, hidden] float32, the row's slots on held experts [B]
    int32 and how many expert layers worked on the sized slot buffer (an
    int32 scalar), then one [B] int32 count for each of ``wide_counters``.
    ``kernels``: each of ``forward``'s kernels by its keyword
    (``KERNEL_ROLES``); ``experts_fn`` and ``combine_fn`` default to the
    build-time choice of ``make_grouped_matmul_fn()`` and
    ``make_moe_combine_fn()``: the Pallas kernels on TPU.

    The program's result is [B, hidden + 3 + 2 x len(wide_counters)]: the
    embedding, the ``ROW_COUNTERS`` columns, and each wide count in two
    (``WIDE_SPLIT``), all named in order by ``mf.row_counters``; any
    caller but ``TextEmbedder`` slices them off. Per real token it counts
    ``moe.slots_routed`` (k x expert layers), per dispatched token
    ``dispatched_token_counters``, and for each dispatched batch
    ``batch_counters(ids, real)``."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.ops.grouped_matmul import make_grouped_matmul_fn
    from sparkdl_tpu.ops.moe_combine import make_moe_combine_fn

    kernels = dict(kernels)
    if kernels.get("experts_fn") is None:
        kernels["experts_fn"] = make_grouped_matmul_fn()
    if kernels.get("combine_fn") is None:
        kernels["combine_fn"] = make_moe_combine_fn()

    def fn(p, x):
        ids = x[0] if isinstance(x, (tuple, list)) else x
        out, slots_held, sized, *wide = forward(config, p, ids, dtype=dtype, **kernels)
        with scope("pool"):
            sized = jnp.broadcast_to(sized, slots_held.shape)
            columns = [slots_held, sized, config.expert_layers - sized]
            for count in wide:
                columns += [count // WIDE_SPLIT * WIDE_SPLIT, count % WIDE_SPLIT]
            # at most tokens x k x layers a row: exact in float32
            counts = jnp.stack(columns, 1)
            return jnp.concatenate([out, counts.astype(jnp.float32)], 1)

    mf = ModelFunction(fn, params, input_dtype=jnp.int32, name=name)
    mf.weights_as_arguments = True
    mf.vocab_size = config.vocab_size
    for keyword, kernel in kernels.items():
        setattr(mf, KERNEL_ROLES[keyword], getattr(kernel, "kind", "custom"))
    mf.row_counters = ROW_COUNTERS + tuple(n for n in wide_counters for _ in range(2))
    # per dispatched token (pad rows and pad tokens too), and per real one
    mf.dispatched_token_counters = dispatched_token_counters
    mf.real_token_counters = {
        "moe.slots_routed": config.num_experts_per_tok * config.expert_layers
    }
    mf.batch_counters = batch_counters
    return mf
