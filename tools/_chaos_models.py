"""Deterministic synthetic models for the serving-gang smokes.

Loaded INSIDE each serving worker subprocess via
``python -m sparkdl_tpu.serving worker --loader tools._chaos_models:loader``
(the workers run with the repo root as cwd, so the ``tools`` package is
importable), and inside the smoke process itself for the ``run_batched_shared``
parity oracle — one definition, so "row-identical to the oracle" is a
statement about the serving path, not about two model builds agreeing.

Import-light on purpose: no ``_common`` (that helper assumes script-dir
sys.path), no jax at module scope — a worker imports this before its
backend is configured.
"""

ROW = 8  # input width shared by every synthetic model here


def loader(name, mode):
    """``loader(name, mode) -> ModelFunction``: a tiny linear+tanh model
    whose weights are a pure function of ``name`` — a relaunched worker
    (or the oracle in another process) rebuilds bit-identical params,
    which is what lets the chaos smoke assert row-identical outputs
    across a crash/restart."""
    import numpy as np
    import jax.numpy as jnp

    from sparkdl_tpu.graph.function import ModelFunction

    import hashlib

    seed = int.from_bytes(
        hashlib.sha256(name.encode()).digest()[:4], "big"
    )
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(ROW, 4)).astype(np.float32) / ROW)
    return ModelFunction(
        lambda p, x: jnp.tanh(x @ p), w, input_shape=(ROW,), name=name
    )
