"""Test fixtures.

Tests run on CPU with 8 virtual XLA devices (the reference tested
distributed semantics on a local-mode SparkSession, SURVEY.md §5; we test
mesh/sharding semantics on a virtual device mesh). Env vars must be set
before jax initializes its backend, hence top-of-file.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the tests run on the CPU
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("KERAS_BACKEND", "jax")
# No persistent compile cache under test: runs must not share compiled
# state through the checkout's cache directory. Child processes inherit
# the variable; the cache-placement tests clear it for theirs.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

# A pytest plugin imports jax before this conftest runs, which latches
# jax's settings from the outer environment. The backend is not
# initialized yet at conftest time, so overriding via jax.config still
# takes effect.
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest

# Files dominated by real-model compiles, subprocess gangs, or example
# scripts: auto-marked ``slow`` so the fast iteration path
# (``pytest -m "not slow"``, < 5 min) covers the pure-logic layers (SQL,
# DataFrame, Column API, params, graph translation, imageIO, udf, ops
# oracles) without paying the model-zoo tax per edit. The FULL suite
# (no marker filter) remains the green-ness bar. Per-test @slow marks
# inside fast files still apply on top.
_SLOW_FILES = {
    "test_examples.py",         # every example as a subprocess
    "test_worker.py",           # multi-process gang rendezvous
    "test_worker_train.py",     # gang training + checkpoint resume
    "test_heartbeat.py",        # subprocess heartbeats
    "test_tuning.py",           # CrossValidator real fits
    "test_flops.py",            # XLA cost_analysis on real models
    "test_ulysses.py",          # BERT sequence-parallel compiles
    "test_attention_grads.py",  # grad-through-collectives compiles
    "test_bert_text.py",        # BERT parity vs HF
    "test_inception.py",
    "test_xception.py",
    "test_vgg.py",
    "test_mobilenet.py",
    "test_keras_weights.py",    # keras->flax parity conversions
    "test_named_models_keras.py",
    "test_resnet_scan.py",
    "test_streaming_train.py",
    "test_estimators.py",
    "test_persistence.py",
    "test_pipeline_parallel.py",
    "test_expert_parallel.py",
    "test_tensor_parallel.py",
    "test_zoo_ingest_corpus.py",
    "test_transformers.py",
    "test_keras_image_fused.py",
    "test_execution.py",
    "test_parallel.py",
    "test_manifest.py",         # golden end-to-end flow
    "test_tf_ingest.py",        # SavedModel/export round trips
}


def pytest_collection_modifyitems(config, items):
    seen = set()
    for item in items:
        base = os.path.basename(str(item.fspath))
        seen.add(base)
        if base in _SLOW_FILES:
            item.add_marker(pytest.mark.slow)
    # a renamed slow file must not silently rejoin the fast path —
    # stale entries fail loudly (only on full-tree collections, where
    # every file is expected to appear)
    stale = _SLOW_FILES - seen
    if stale and len(seen) > len(_SLOW_FILES):
        raise pytest.UsageError(
            f"tests/conftest.py _SLOW_FILES names missing files: "
            f"{sorted(stale)} — update the list after renames"
        )


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def tiny_image_dir(tmp_path_factory):
    """A directory of small real image files (written with PIL) plus one
    corrupt file, mirroring the reference's tiny fixture-image strategy."""
    from PIL import Image

    d = tmp_path_factory.mktemp("images")
    rng = np.random.default_rng(0)
    sizes = [(32, 48), (64, 64), (40, 56), (128, 96), (20, 20)]
    for i, (h, w) in enumerate(sizes):
        arr = rng.integers(0, 256, size=(h, w, 3), dtype=np.uint8)
        Image.fromarray(arr, "RGB").save(d / f"img_{i}.png")
    (d / "broken.png").write_bytes(b"this is not an image")
    return str(d)
