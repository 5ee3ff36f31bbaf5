"""Persistent XLA compilation cache placement + build ledger.

Every cold start of the engine — a fresh serving process, a bench
warmup, a relaunched gang rank — re-traces and re-compiles the same
programs: converter ∘ model ∘ flattener at the same batch geometry, on
the same jaxlib. jax's persistent compilation cache reuses the
serialized executable across processes instead; :func:`place` decides
WHERE it lives, once, at package import — before the first compile of
the process, whichever code path that compile comes from
(``module.init``, the train step, the sharded outer jit, the
generation programs and every ``ModelFunction`` build alike):

- where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
  cache: jax reads the variable itself and this module sets no other;
- where it is not, the cache is :data:`DEFAULT_DIR`, one fixed
  git-ignored directory inside the checkout. The path never varies by
  process, pid or time: a cache that moves never hits. The variable is
  exported too, so every child process (gateway workers, supervised
  ranks) lands in the same directory.

The thresholds are dropped to cache-everything because the programs
this engine rebuilds most often (CPU parity tests, small serving rungs)
are exactly the ones the default 1s-compile-time floor would skip.

jax's own cache keys on the HLO fingerprint and does not report whether
a given build hit. The **ledger** here gives the framework its own
deterministic attribution, keyed the way the engine thinks — (build
kind, model name, batch geometry, layout/donation/placement arms): the
first build of a key writes a marker under ``<dir>/ledger/`` and counts
``compile.cache_misses``; any later build of the same key — in this
process (a rebuilt transformer) or a later one (serving cold start,
second bench run) — counts ``compile.cache_hits``. ``obs report``
prints the pair next to the ``compile.warmup`` timer, so "how much
warmup is the cache saving" is one report line, not a profiler session.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
from typing import Optional

from sparkdl_tpu.runtime import locksmith
from sparkdl_tpu.utils.metrics import metrics

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The cache directory when the environment names none.
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

#: Cache EVERYTHING: jax's default floors (1 s compile time, a
#: filesystem-chosen entry size) skip exactly the small programs the
#: CPU tests and serving rungs rebuild most often. (jax option, value.)
_THRESHOLDS = (
    ("jax_persistent_cache_min_entry_size_bytes", -1),
    ("jax_persistent_cache_min_compile_time_secs", 0),
)

_stats_lock = locksmith.lock(
    "sparkdl_tpu/runtime/compile_cache.py::_stats_lock"
)
#: Process-lifetime tally, independent of the metrics registry: bench.py
#: resets the registry after its warmup — exactly when the builds (and
#: their ledger hits) happen — so the record reads this instead.
#: Mutated only under _stats_lock: concurrent first builds (the serving
#: completion pool warming several rungs at once) must not lose
#: increments to a racing read-modify-write.
_stats = {"cache_hits": 0, "cache_misses": 0}


def stats() -> dict:
    """Ledger hits/misses since process start (reset-immune)."""
    with _stats_lock:
        return dict(_stats)


def place() -> None:
    """Put the persistent cache where the module docstring says. Runs
    at ``import sparkdl_tpu``; never imports jax itself (the gateway
    parent must stay off it) and touches no backend.

    Settings travel by environment variable, which a later ``import
    jax`` — here or in a child process — reads as its defaults. A jax
    that was imported first has already taken its defaults, so the same
    values are written to its config as well; the cache directory is
    the exception the contract demands: it is written only when the
    variable was NOT set from outside."""
    jax = sys.modules.get("jax")
    if not os.environ.get(ENV_VAR):
        os.environ[ENV_VAR] = DEFAULT_DIR
        if jax is not None:
            jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    for option, value in _THRESHOLDS:
        if option.upper() not in os.environ:
            os.environ[option.upper()] = str(value)
            if jax is not None:
                jax.config.update(option, value)


def cache_dir() -> Optional[str]:
    """The cache directory in force, as jax itself resolved it; None
    where the process switched the persistent cache off
    (``jax_enable_compilation_cache``, as the test suite does)."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir


def note_build(kind: str, model: str, key: tuple) -> Optional[str]:
    """Record one program build against the ledger.

    Returns ``"hit"`` / ``"miss"`` (incrementing
    ``compile.cache_hits`` / ``compile.cache_misses``), or None when no
    cache directory is in force. A hit means this (model, geometry,
    arms) key was built before under the same cache dir — jax's
    persistent cache will serve the executable, so the build's warmup
    pays deserialization, not compilation."""
    d = cache_dir()
    if not d:
        return None
    digest = hashlib.sha256(
        repr((kind, model, key)).encode("utf-8")
    ).hexdigest()[:32]
    ledger = os.path.join(d, "ledger")
    path = os.path.join(ledger, f"{digest}.json")
    if os.path.exists(path):
        metrics.inc("compile.cache_hits")
        with _stats_lock:
            _stats["cache_hits"] += 1
        return "hit"
    try:
        os.makedirs(ledger, exist_ok=True)
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            # repr, not the raw tuple: keys carry dtypes and other
            # non-JSON values; the marker is for humans debugging a
            # surprising miss, the digest is the identity.
            json.dump({"kind": kind, "model": model, "key": repr(key)}, f)
        os.replace(tmp, path)
    except OSError:
        pass  # unwritable dir: jax's own cache may still work; no ledger
    metrics.inc("compile.cache_misses")
    with _stats_lock:
        _stats["cache_misses"] += 1
    return "miss"
