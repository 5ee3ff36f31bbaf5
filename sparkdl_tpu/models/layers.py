"""What every weights-as-arguments text family shares (``models/jamba.py``,
``deepseek_v2.py``, ``deepseek_v32.py``, ``afmoe.py``, ``xing4_0.py``):
the weights file's format, the products and norms every layer is made
of, the pooling, and what an attention that takes its rows' lengths is
handed and counted at.

A family module imports from here and from ``models/moe.py``, never the
private names of another family.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def unflatten(flat: dict) -> dict:
    """{"a/b/c": leaf} -> {"a": {"b": {"c": leaf}}}."""
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def load_flat(shapes: dict, weights_file: str, dtype, leaf_dtype) -> dict:
    """The tree of a flat ``.npz`` that holds ``shapes``' paths, each
    leaf as ``leaf_dtype(path, shape, dtype)``. A ``uint16`` leaf is the
    bit pattern of bfloat16 values (numpy has no bfloat16 of its own to
    store): 2 bytes a parameter on disk and on the way in."""
    flat = {}
    with np.load(weights_file, allow_pickle=False) as blob:
        missing = sorted(set(shapes) - set(blob.files))
        if missing:
            raise ValueError(
                f"{weights_file} lacks {len(missing)} leaves, the first "
                f"{missing[0]!r}"
            )
        for path, shape in shapes.items():
            leaf = blob[path]
            if leaf.dtype == np.uint16:
                leaf = leaf.view(jnp.bfloat16)
            if leaf.shape != shape:
                raise ValueError(
                    f"{weights_file}: {path} is {leaf.shape}, not {shape}"
                )
            flat[path] = leaf.astype(leaf_dtype(path, shape, dtype), copy=False)
    return unflatten(flat)


def leaf_dtype(path: str, shape: tuple, dtype) -> Any:
    """A routed family's leaves: matrices (and the embedding) are
    ``dtype``; norm weights and the router feed float32 arithmetic and
    stay float32."""
    small = len(shape) == 1 or path.endswith("/router")
    return jnp.float32 if small else dtype


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def dense(x, w):
    """x [.., in] @ w [in, out], accumulated in float32."""
    return jnp.einsum("...i,io->...o", x, w, preferred_element_type=jnp.float32)


def turn(x):
    """Rotate halves: (a | b) -> (-b | a) on the last axis."""
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def mean_real_state(x, real):
    """x [B, L, hidden] float32 averaged over each row's tokens that are
    not padding; a row of padding alone gives zeros."""
    count = jnp.maximum(jnp.sum(real, 1, dtype=jnp.float32), 1.0)
    return jnp.sum(jnp.where(real[..., None], x, 0.0), 1) / count[:, None]


# -- rows' lengths, as an attention takes them and as the host counts them --


def last_real_position(real, xp):
    """real [B, L] bool -> [B] int32, a row's last real position + 1 (0
    for a row of padding): its length. Not the count of its real tokens,
    so that an id 0 inside a text could never cut a row short. ``xp``:
    ``jax.numpy`` in the program, ``numpy`` where the host counts."""
    position = xp.arange(1, real.shape[1] + 1, dtype=xp.int32)
    return xp.max(xp.where(real, position, 0), 1)


def row_lengths(attention_fn, real):
    """What an attention that takes its rows' lengths is handed besides
    its operands, as keywords: real [B, L] bool -> ``{"lengths": [B]
    int32}`` (:func:`last_real_position`). Nothing for any other
    attention: what stands in for one in a test or a planted fault keeps
    its arguments."""
    if not getattr(attention_fn, "takes_lengths", False):
        return {}
    return {"lengths": last_real_position(real, jnp)}


def counted_lengths(attention_fn, ids, real) -> list:
    """Each row's length as the attention runs it: its last real position
    + 1 (as ``row_lengths`` hands it over) where the attention takes
    lengths, the bucket's edge where it takes none (every block runs)."""
    rows, edge = ids.shape
    if getattr(attention_fn, "takes_lengths", False):
        return last_real_position(real, np).tolist()
    return [edge] * rows


def query_block_counters(attention_fn, layers: int, ids, real, prefix: str = "mla") -> dict:
    """The query blocks of a dispatched batch ``ids`` [B, L] whose real
    tokens are ``real``, in ``layers`` layers of an attention that says
    how many a row has (``.query_blocks``; nothing for one that does not):

    - ``<prefix>.query_blocks``: rows x layers x the bucket's query blocks;
    - ``<prefix>.query_blocks_run``: those of them that hold a real token,
      every one where the attention takes no lengths."""
    blocks = getattr(attention_fn, "query_blocks", None)
    if blocks is None:
        return {}
    rows, edge = ids.shape
    lengths = counted_lengths(attention_fn, ids, real)
    return {
        f"{prefix}.query_blocks": rows * layers * blocks(edge),
        f"{prefix}.query_blocks_run": layers * sum(blocks(n) for n in lengths),
    }


def attention_batch_counters(attention_fn, layers: int, ids, real) -> dict:
    """What ``mf.batch_counters`` counts of latent attention for a
    dispatched batch ``ids`` [B, L] whose real tokens are ``real``, where
    the attention it was built with says what it runs
    (``.pairs_computed``, ``.query_blocks``, ``.takes_lengths``, as
    ``make_latent_attention_fn``'s do):

    - ``mla.pairs_computed``: layers x the (query, key) pairs a head's
      attention runs, summed over the rows: how much of the square was
      run, beside ``mla.attention_tokens``;
    - ``mla.query_blocks`` and ``mla.query_blocks_run``
      (:func:`query_block_counters`).

    A row counts at its length where the attention takes lengths, and at
    the bucket's edge where it takes none (:func:`counted_lengths`)."""
    pairs = getattr(attention_fn, "pairs_computed", None)
    if pairs is None:
        return {}
    lengths = counted_lengths(attention_fn, ids, real)
    counters = {"mla.pairs_computed": layers * sum(pairs(n) for n in lengths)}
    counters.update(query_block_counters(attention_fn, layers, ids, real))
    return counters


def preset(sizes: dict, size: str, family: str):
    """The configuration ``sizes[size]()``; an unknown size is refused
    with the ones there are."""
    if size not in sizes:
        raise ValueError(f"Unknown {family} size {size!r}; supported: {sorted(sizes)}")
    return sizes[size]()
