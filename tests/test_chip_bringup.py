"""What the chip bring-up rests on, as far as a CPU can check it: where
the persistent compile cache lands, that ``chip_smoke.py`` and
``bench.py`` refuse to run without a TPU, how the gang launchers divide
a host's chips, and that a text model says which attention it was built
with. The chip's own half is ``python chip_smoke.py`` through the chip
tool."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # bench.py sits at the repo root


def _run(code_or_argv, env_overrides, cwd=REPO, timeout=120):
    """A fresh interpreter with the suite's cache switch removed, so the
    child sees what a user's process sees."""
    env = {
        k: v
        for k, v in os.environ.items()
        if k
        not in (
            "JAX_ENABLE_COMPILATION_CACHE",
            "JAX_COMPILATION_CACHE_DIR",
            "BENCH_PLATFORM",
        )
    }
    env.update(env_overrides)
    argv = (
        [sys.executable, "-c", code_or_argv]
        if isinstance(code_or_argv, str)
        else [sys.executable, *code_or_argv]
    )
    return subprocess.run(
        argv, env=env, cwd=cwd, capture_output=True, text=True,
        timeout=timeout,
    )


# -- compile-cache placement --------------------------------------------------

_PLACEMENT_PROBE = """
import json, os, sys
{first_import}
import jax
updates = []
_update = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), _update(k, v))[1]
import sparkdl_tpu
from sparkdl_tpu.runtime import compile_cache
print(json.dumps({{
    "dir": compile_cache.cache_dir(),
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "env": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
    "default": compile_cache.DEFAULT_DIR,
    "updates": updates,
    "min_entry": jax.config.jax_persistent_cache_min_entry_size_bytes,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
}}))
"""


@pytest.mark.parametrize("jax_first", [True, False])
def test_cache_dir_from_environment_is_left_alone(tmp_path, jax_first):
    """JAX_COMPILATION_CACHE_DIR set: that directory is the cache and no
    code writes jax_compilation_cache_dir, whichever import came first."""
    code = _PLACEMENT_PROBE.format(
        first_import="" if jax_first else "import sparkdl_tpu"
    )
    r = _run(code, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["dir"] == out["jax_dir"] == out["env"] == str(tmp_path)
    assert "jax_compilation_cache_dir" not in out["updates"]
    assert out["min_entry"] == -1 and out["min_secs"] == 0


@pytest.mark.parametrize("jax_first", [True, False])
def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(jax_first):
    """Unset: the one fixed in-checkout directory, exported so children
    land there too — never a temp dir, a pid or a time."""
    code = _PLACEMENT_PROBE.format(
        first_import="" if jax_first else "import sparkdl_tpu"
    )
    r = _run(code, {})
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["default"] == os.path.join(REPO, ".jax_cache")
    assert out["dir"] == out["jax_dir"] == out["env"] == out["default"]
    assert out["min_entry"] == -1 and out["min_secs"] == 0


_BUILD_PROBE = """
import json
import numpy as np
import sparkdl_tpu
import jax.numpy as jnp
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.runtime import compile_cache
mf = ModelFunction(lambda p, x: x @ p, jnp.ones((4, 3)), name="probe")
y = np.asarray(mf.jitted_flat((2, 4))(np.ones(8, np.float32)))
assert y.shape == (2, 3)
print(json.dumps(compile_cache.stats()))
"""


def test_second_process_build_is_a_ledger_hit(tmp_path):
    """The same build in two processes under one cache directory: a miss
    then a hit, with jax's serialized executables and the ledger under
    that directory and nowhere else."""
    env = {
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
        "PYTHONPATH": REPO,
    }
    stats = []
    for _ in range(2):
        r = _run(_BUILD_PROBE, env, cwd=str(tmp_path))
        assert r.returncode == 0, r.stderr
        stats.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert stats == [
        {"cache_hits": 0, "cache_misses": 1},
        {"cache_hits": 1, "cache_misses": 0},
    ]
    cache = tmp_path / "cache"
    assert len(list((cache / "ledger").glob("*.json"))) == 1
    assert any(p.name.endswith("-cache") for p in cache.iterdir())
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cache"]


# -- no TPU, no run -----------------------------------------------------------


def _assert_refused(r, started):
    assert r.returncode not in (0, None)
    assert time.monotonic() - started < 60
    # no result of any kind: not one JSON line on stdout
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]


def test_chip_smoke_refuses_without_a_tpu():
    t0 = time.monotonic()
    r = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    _assert_refused(r, t0)
    assert "no TPU" in r.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    t0 = time.monotonic()
    r = _run(
        ["chip_smoke.py"],
        {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""},
        cwd=str(tmp_path),
    )
    _assert_refused(r, t0)


def test_bench_refuses_without_a_tpu(tmp_path):
    t0 = time.monotonic()
    r = _run(
        ["bench.py"], {"JAX_PLATFORMS": "cpu", "BENCH_MODE": "featurizer"}
    )
    _assert_refused(r, t0)
    assert "BENCH_PLATFORM=cpu" in r.stderr


def test_bench_affinity_arm_raises_off_the_cpu():
    """The gateway arm starts workers that need the chip the bench
    process already holds: on a TPU it says so instead of hanging."""
    import bench

    with pytest.raises(RuntimeError, match="one process at a time"):
        bench._bench_serving_affinity("tpu")


# -- one process per chip -----------------------------------------------------


def test_chip_env_is_a_pure_function_of_rank_and_chip_count():
    from sparkdl_tpu.resilience.supervisor import chip_env

    four = ["0", "1", "2", "3"]
    envs = [chip_env(r, 4, four) for r in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == four
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert chip_env(1, 2, four)["TPU_VISIBLE_CHIPS"] == "1"
    # rank r takes the r-th of the chips it was given, whatever they are
    assert chip_env(1, 2, ["2", "3"])["TPU_VISIBLE_CHIPS"] == "3"
    assert chip_env(0, 8, []) == {}  # no chips on this host: nothing to divide
    with pytest.raises(ValueError, match="2 ranks need 2 TPU chips"):
        chip_env(0, 2, ["0"])


def test_visible_chips_follows_platform_and_operator(monkeypatch):
    from sparkdl_tpu.resilience import supervisor

    monkeypatch.setattr(supervisor, "local_chip_count", lambda: 4)
    assert supervisor.visible_chips({}) == ["0", "1", "2", "3"]
    assert supervisor.visible_chips({"JAX_PLATFORMS": "tpu,cpu"}) == [
        "0", "1", "2", "3",
    ]
    # a rank that will not run on a TPU has no chips to divide
    assert supervisor.visible_chips({"JAX_PLATFORMS": "cpu"}) == []
    # the operator's list wins over the host's device nodes
    assert supervisor.visible_chips({"TPU_VISIBLE_CHIPS": "2, 3"}) == ["2", "3"]
    assert supervisor.visible_chips({"TPU_VISIBLE_CHIPS": ""}) == [
        "0", "1", "2", "3",
    ]


def _capture_launches(monkeypatch, module):
    launched = []
    monkeypatch.setattr(
        module.subprocess,
        "Popen",
        lambda argv, env, **kw: launched.append(env) or object(),
    )
    return launched


def test_worker_launcher_gives_rank_r_chip_r(monkeypatch, tmp_path):
    from sparkdl_tpu.resilience import supervisor

    monkeypatch.setattr(supervisor, "local_chip_count", lambda: 4)
    launched = _capture_launches(monkeypatch, supervisor)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    job = str(tmp_path / "job.json")
    launch = supervisor.worker_launcher(job, 4)
    for rank in range(4):
        launch(rank, 0)
    assert [e["TPU_VISIBLE_CHIPS"] for e in launched] == ["0", "1", "2", "3"]
    # a lone rank keeps the parent's view: one process over all four chips
    del launched[:]
    supervisor.worker_launcher(job, 1)(0, 0)
    assert "TPU_VISIBLE_CHIPS" not in launched[0]
    assert "TPU_PROCESS_BOUNDS" not in launched[0]
    # the operator's chips are divided, not overwritten with 0..n-1
    del launched[:]
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    two = supervisor.worker_launcher(job, 2)
    two(0, 0), two(1, 0)
    assert [e["TPU_VISIBLE_CHIPS"] for e in launched] == ["2", "3"]
    with pytest.raises(ValueError, match="this host has 2"):
        supervisor.worker_launcher(job, 3)(0, 0)
    # a CPU gang on the same host is not divided, however many ranks,
    # and may be a jax.distributed one whatever the operator listed
    del launched[:]
    cpu = supervisor.worker_launcher(job, 8, platform="cpu", distributed=True)
    cpu(5, 0)
    assert launched[0]["JAX_PLATFORMS"] == "cpu"
    assert launched[0]["TPU_VISIBLE_CHIPS"] == "2,3"
    assert "TPU_PROCESS_BOUNDS" not in launched[0]
    # a jax.distributed gang over one host's chips is not brought up
    dist = supervisor.worker_launcher(job, 2, distributed=True)
    with pytest.raises(ValueError, match="jax.distributed gang"):
        dist(0, 0)


def test_gateway_refuses_more_workers_than_chips(monkeypatch, tmp_path):
    from sparkdl_tpu.resilience import supervisor
    from sparkdl_tpu.serving.gateway import ServingGateway

    monkeypatch.setattr(supervisor, "local_chip_count", lambda: 1)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    gw = ServingGateway(num_workers=2, gang_dir=str(tmp_path))
    with pytest.raises(ValueError, match="2 ranks need 2 TPU chips"):
        gw.start()
    assert not os.listdir(tmp_path)  # refused before anything launched


def test_gateway_chip_view_is_fixed_at_construction(monkeypatch, tmp_path):
    """The gang is resized while its ranks run, so a worker's chips never
    depend on the gang's size of the moment: several workers at start
    means one chip each for good; one worker at start owns every chip
    (mesh serving), and such a gang will not grow on a TPU host."""
    from sparkdl_tpu.resilience import supervisor
    from sparkdl_tpu.serving import gateway

    monkeypatch.setattr(supervisor, "local_chip_count", lambda: 4)
    launched = _capture_launches(monkeypatch, gateway)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)

    one = gateway.ServingGateway(num_workers=1, gang_dir=str(tmp_path))
    one._launch_worker(0, 0)
    assert "TPU_VISIBLE_CHIPS" not in launched[0]
    with pytest.raises(ValueError, match="started with one worker"):
        one.resize(2)

    del launched[:]
    four = gateway.ServingGateway(num_workers=4, gang_dir=str(tmp_path))
    for rank in range(4):
        four._launch_worker(rank, 0)
    assert [e["TPU_VISIBLE_CHIPS"] for e in launched] == ["0", "1", "2", "3"]
    four.num_workers = 1  # shrunk: rank 0 relaunches on its own chip still
    del launched[:]
    four._launch_worker(0, 1)
    assert launched[0]["TPU_VISIBLE_CHIPS"] == "0"
    with pytest.raises(ValueError, match="5 ranks need 5 TPU chips"):
        four._check_chips(5)

    # on the CPU neither rule applies
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    gateway.ServingGateway(num_workers=1, gang_dir=str(tmp_path))._check_chips(8)


# -- recorded attention -------------------------------------------------------


def test_served_text_model_names_its_attention():
    """/v1/models rows say which attention a text model function was
    built with (the dense einsum off-TPU; the flash kernel on the chip,
    where chip_smoke.py asserts it); image rows carry no such key."""
    from sparkdl_tpu.serving import Router, ServingClient

    router = Router()
    try:
        client = ServingClient(router)
        ids = np.arange(1, 17, dtype=np.int32).reshape(1, 16)
        out = client.predict("bert-tiny", ids, mode="embed", timeout=300)
        assert np.asarray(out).shape == (1, 128)
        rows = {m["name"]: m for m in router.residency.models()}
        assert rows["bert-tiny"]["attention"] == "dense"
        assert rows["bert-tiny"]["attention_layout"] == "heads"
    finally:
        router.close()


# -- the native bridge says why it is missing ---------------------------------


def test_failed_native_build_warns_before_falling_back(monkeypatch, tmp_path):
    from sparkdl_tpu.runtime import native

    (tmp_path / "Makefile").write_text(
        "all:\n\t@echo 'imagebridge.cc: no such compiler' >&2; exit 1\n"
    )
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(
        native, "_SO_PATH", str(tmp_path / "build" / "libimagebridge.so")
    )
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", False)
    monkeypatch.delenv("SPARKDL_TPU_NO_NATIVE", raising=False)
    with pytest.raises(RuntimeError, match="no such compiler"):
        native.build()
    with pytest.warns(UserWarning, match="no such compiler"):
        assert native.available() is False


# -- the kernel inside the multi-device program -------------------------------


def test_flash_model_runs_inside_the_sharded_dp_program():
    """The multi-device dispatch partitions by hand (shard_map), so a
    model whose attention is a Pallas kernel keeps working when its
    batch fans out: each device runs the kernel on its own rows. (On
    the chip a plain sharded jit refuses to lower the Mosaic call at
    all; the interpreter stands in for the kernel here.)"""
    import jax

    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.models.bert import BertConfig, BertEncoder
    from sparkdl_tpu.ops.flash_attention import make_flash_attention_fn
    from sparkdl_tpu.transformers.execution import sharded_data_parallel_fn

    cfg = BertConfig(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
        intermediate_size=64, max_position_embeddings=32,
    )
    module = BertEncoder(
        cfg, attention_fn=make_flash_attention_fn(8, 8, interpret=True)
    )
    ids = np.random.default_rng(0).integers(1, 64, (8, 16)).astype(np.int32)
    params = module.init(jax.random.PRNGKey(0), ids[:1])
    mf = ModelFunction(
        lambda p, x: module.apply(p, x, pooled=True), params, name="flash"
    )
    fn = sharded_data_parallel_fn(mf.jitted(), devices=jax.devices()[:4])
    out = fn(ids)
    assert len({s.device for s in out.addressable_shards}) == 4
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mf.jitted()(ids)), atol=1e-5, rtol=1e-5
    )
