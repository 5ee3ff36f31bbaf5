"""Multi-process data-parallel TRAINING tests — the HorovodEstimator
operational claim (SURVEY.md §4.4), finally exercised for real: a gang of
2 worker subprocesses joins a genuine ``jax.distributed.initialize``
rendezvous (localhost coordinator), each contributing 4 virtual CPU
devices to one 8-device 'dp' mesh, and the per-step gradient all-reduce
crosses the process boundary. Oracle pattern as everywhere in this suite:
the gang's per-epoch losses and trained params must match a single-process
8-device fit on the same data.
"""

import json
import os
import pickle
import sys

import numpy as np
import pytest

from sparkdl_tpu.dataframe import DataFrame
from sparkdl_tpu.estimators import DataParallelEstimator
from sparkdl_tpu.persistence import save_stage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The model builder lives in a module file (written into the test tmp dir
# and put on the workers' PYTHONPATH) because that is the contract:
# HorovodEstimator's modelFn equivalent is CODE importable on every host,
# not a pickled closure.
BUILDER_SRC = '''
import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.graph.function import ModelFunction


def build(num_features=4, num_classes=3, hidden=8, seed=0):
    rng = np.random.default_rng(seed)
    params = {
        "w1": jnp.asarray(
            rng.normal(0, 0.1, (num_features, hidden)), jnp.float32),
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jnp.asarray(
            rng.normal(0, 0.1, (hidden, num_classes)), jnp.float32),
        "b2": jnp.zeros((num_classes,), jnp.float32),
    }

    def fn(p, x):
        h = jax.nn.relu(x @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]

    return ModelFunction(fn, params, input_shape=(num_features,), name="mlp")
'''


from _gang import free_port as _free_port, run_gang as _run_gang


@pytest.fixture(scope="module")
def train_fixture(tmp_path_factory):
    d = tmp_path_factory.mktemp("worker_train")
    (d / "gang_models.py").write_text(BUILDER_SRC)

    rng = np.random.default_rng(3)
    n = 96
    x = rng.normal(0, 1, (n, 4)).astype(np.float32)
    w_true = rng.normal(0, 1, (4, 3))
    y = np.argmax(x @ w_true + rng.normal(0, 0.1, (n, 3)), axis=1).astype(
        np.int32
    )
    df = DataFrame.fromColumns(
        {"features": list(x), "label": list(y)}, numPartitions=4
    )
    inp = str(d / "train.parquet")
    df.writeParquet(inp)
    return {"dir": d, "input_parquet": inp, "df": df}


def _make_estimator(**overrides):
    kw = dict(
        inputCol="features",
        labelCol="label",
        outputCol="logits",
        batchSize=32,
        epochs=3,
        stepSize=0.1,
    )
    kw.update(overrides)
    return DataParallelEstimator(**kw)


def _oracle_fit(train_fixture, **overrides):
    sys.path.insert(0, str(train_fixture["dir"]))
    try:
        import gang_models
    finally:
        sys.path.pop(0)
    est = _make_estimator(**overrides)
    est.model = gang_models.build()
    return est.fit(train_fixture["df"])


def _gang_cmd(train_fixture, job, n_proc=2):
    """(argv_for_rank, env) for a worker gang over this job — ONE place
    for the launch configuration, shared by waiting and crash tests."""
    job_path = str(train_fixture["dir"] / f"job_{os.path.basename(job['output_dir'])}.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    port = _free_port()
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": f"{train_fixture['dir']}:{REPO}",
    }
    argv = lambda i: [
        sys.executable, "-m", "sparkdl_tpu.worker",
        "--job", job_path,
        "--process-id", str(i),
        "--num-processes", str(n_proc),
        "--coordinator", f"localhost:{port}",
        "--platform", "cpu",
    ]
    return argv, env


def _launch_gang(train_fixture, job, n_proc=2):
    argv, env = _gang_cmd(train_fixture, job, n_proc)
    return _run_gang(argv, n_proc, env)


def _train_job(train_fixture, out_name, estimator, **extra):
    est_path = str(train_fixture["dir"] / f"est_{out_name}")
    save_stage(estimator, est_path, overwrite=True)
    return {
        "type": "train",
        "estimator_path": est_path,
        "model": {"builder": "gang_models:build", "kwargs": {}},
        "input_parquet": train_fixture["input_parquet"],
        "num_partitions": 4,
        "output_dir": str(train_fixture["dir"] / out_name),
        **extra,
    }


def test_estimator_refuses_to_persist_callables(tmp_path):
    est = _make_estimator()
    est.model = object()  # anything non-None
    with pytest.raises(ValueError, match="model builder"):
        save_stage(est, str(tmp_path / "bad"))


def test_builder_spec_validation():
    from sparkdl_tpu.worker import _resolve_model_builder

    with pytest.raises(ValueError, match="module:function"):
        _resolve_model_builder({"builder": "no_colon_here"})


def test_two_process_gang_matches_single_process_oracle(train_fixture):
    """REAL rendezvous: per-epoch losses and trained params of the
    2-process gang equal the single-process 8-device fit."""
    job = _train_job(
        train_fixture, "out_gang", _make_estimator()
    )
    # incomplete model spec must fail loudly before rendezvous weirdness
    with pytest.raises(ValueError):
        from sparkdl_tpu.worker import _resolve_model_builder

        _resolve_model_builder({"builder": ":build"})

    _launch_gang(train_fixture, job)

    out_dir = job["output_dir"]
    assert os.path.exists(os.path.join(out_dir, "_SUCCESS.train"))
    with open(os.path.join(out_dir, "history.json")) as f:
        gang_history = json.load(f)
    with open(os.path.join(out_dir, "trained_params.pkl"), "rb") as f:
        gang_params = pickle.load(f)

    oracle = _oracle_fit(train_fixture)
    assert len(gang_history) == len(oracle.history) == 3
    for gang_ep, orc_ep in zip(gang_history, oracle.history):
        assert gang_ep["steps"] == orc_ep["steps"]
        np.testing.assert_allclose(
            gang_ep["loss"], orc_ep["loss"], rtol=1e-4
        )
    orc_params = oracle.modelFunction.params
    for k in orc_params:
        np.testing.assert_allclose(
            gang_params[k], np.asarray(orc_params[k]), rtol=1e-4, atol=1e-5
        )
    # training actually moved: loss decreased across epochs
    assert gang_history[-1]["loss"] < gang_history[0]["loss"]


def test_gang_restart_resumes_from_checkpoint(train_fixture):
    """Kill-and-restart resume, the HorovodEstimator modelDir contract:
    gang run 1 checkpoints to modelDir; a fresh gang run 2 with the same
    modelDir resumes from the saved step instead of starting over."""
    model_dir = str(train_fixture["dir"] / "ckpt_gang")
    est = _make_estimator(
        epochs=1, modelDir=model_dir, checkpointEvery=100
    )
    job1 = _train_job(train_fixture, "out_resume1", est)
    _launch_gang(train_fixture, job1)

    steps_after_1 = _latest_step(model_dir)
    assert steps_after_1 == 3  # 96 rows / batch 32 = 3 steps

    # fresh gang, same modelDir: must restore step 3 and continue to 6
    job2 = _train_job(train_fixture, "out_resume2", est)
    _launch_gang(train_fixture, job2)
    assert _latest_step(model_dir) == 6

    # and the resumed run started from the trained params, not scratch:
    # its epoch loss is below run 1's (continued descent)
    with open(os.path.join(job1["output_dir"], "history.json")) as f:
        h1 = json.load(f)
    with open(os.path.join(job2["output_dir"], "history.json")) as f:
        h2 = json.load(f)
    assert h2[0]["loss"] < h1[0]["loss"]


def _latest_step(model_dir):
    steps = [
        int(name[5:])
        for name in os.listdir(model_dir)
        if name.startswith("step_") and name[5:].isdigit()
    ]
    return max(steps) if steps else None


def test_single_process_train_no_rendezvous(train_fixture, tmp_path):
    """--no-distributed single-process train: no coordinator needed."""
    from sparkdl_tpu.worker import run_train_worker

    sys.path.insert(0, str(train_fixture["dir"]))
    try:
        job = _train_job(
            train_fixture, "out_solo", _make_estimator(epochs=1)
        )
        fitted = run_train_worker(
            job, process_id=0, num_processes=1, distributed=False
        )
        assert os.path.exists(
            os.path.join(job["output_dir"], "_SUCCESS.train")
        )
        assert len(fitted.history) == 1

        with pytest.raises(ValueError, match="single-process"):
            run_train_worker(
                job, process_id=0, num_processes=2, distributed=False
            )
    finally:
        sys.path.pop(0)


def test_streaming_gang_trains_from_owned_partitions(train_fixture):
    """streaming=True in a 2-process gang: each rank feeds from ONLY its
    own partitions via the lazy parquet scan (executor-local feed), the
    per-step all-reduce still crosses processes, and training descends."""
    est = _make_estimator(
        epochs=4, streaming=True, shuffleBufferRows=48
    )
    job = _train_job(train_fixture, "out_stream_gang", est)
    _launch_gang(train_fixture, job)

    out_dir = job["output_dir"]
    assert os.path.exists(os.path.join(out_dir, "_SUCCESS.train"))
    with open(os.path.join(out_dir, "history.json")) as f:
        hist = json.load(f)
    assert len(hist) == 4
    # steps agreed gang-wide from the global row count: 96/32 = 3
    assert all(h["steps"] == 3 for h in hist)
    assert hist[-1]["loss"] < hist[0]["loss"]
    # the gang result is a working classifier comparable to the in-memory
    # oracle's accuracy on the training set (same model family/seed)
    with open(os.path.join(out_dir, "trained_params.pkl"), "rb") as f:
        params = pickle.load(f)
    import jax

    sys.path.insert(0, str(train_fixture["dir"]))
    try:
        import gang_models
    finally:
        sys.path.pop(0)
    mf = gang_models.build()
    cols = train_fixture["df"].collectColumns()
    x = np.stack([np.asarray(v) for v in cols["features"]])
    y = np.asarray(cols["label"])
    logits = np.asarray(mf.fn(params, x))
    acc = float(np.mean(np.argmax(logits, axis=1) == y))
    assert acc > 0.8, acc


def test_streaming_gang_unbalanced_partitions(train_fixture):
    """numPartitions=3 over 2 ranks: rank 0 owns 2/3 of the rows. The
    lockstep step count must follow the HEAVIEST rank (no silent surplus
    drop), with the light rank padding."""
    est = _make_estimator(
        epochs=2, streaming=True, shuffleBufferRows=48
    )
    job = _train_job(
        train_fixture, "out_stream_unbal", est, num_partitions=3
    )
    _launch_gang(train_fixture, job)
    with open(
        os.path.join(job["output_dir"], "history.json")
    ) as f:
        hist = json.load(f)
    # rank 0 owns partitions {0, 2} = 64 rows; per-host batch = 16
    # -> ceil(64/16) = 4 steps, not ceil(96/32) = 3
    assert all(h["steps"] == 4 for h in hist), hist
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_zero1_gang_matches_single_process_oracle(train_fixture):
    """ZeRO-1 (sharded optimizer state) in a 2-process gang: the
    reduce-scatter / shard-update / all-gather step crosses the process
    boundary and still matches the single-process 8-device ZeRO-1 fit."""
    est = _make_estimator(shardOptimizerState=True)
    job = _train_job(train_fixture, "out_zero1", est)
    _launch_gang(train_fixture, job)

    out_dir = job["output_dir"]
    with open(os.path.join(out_dir, "history.json")) as f:
        gang_history = json.load(f)
    with open(os.path.join(out_dir, "trained_params.pkl"), "rb") as f:
        gang_params = pickle.load(f)

    oracle = _oracle_fit(train_fixture, shardOptimizerState=True)
    assert len(gang_history) == len(oracle.history) == 3
    for g, o in zip(gang_history, oracle.history):
        np.testing.assert_allclose(g["loss"], o["loss"], rtol=1e-4)
    for k, v in oracle.modelFunction.params.items():
        np.testing.assert_allclose(
            gang_params[k], np.asarray(v), rtol=1e-4, atol=1e-5
        )


def test_zero1_gang_checkpoint_resume(train_fixture):
    """Sharded opt state checkpoints distributed (each rank writes its
    shards) and a restarted gang resumes from it."""
    model_dir = str(train_fixture["dir"] / "ckpt_zero1")
    est = _make_estimator(
        epochs=1, shardOptimizerState=True, modelDir=model_dir,
        checkpointEvery=100,
    )
    job1 = _train_job(train_fixture, "out_z1_resume1", est)
    _launch_gang(train_fixture, job1)
    assert _latest_step(model_dir) == 3

    job2 = _train_job(train_fixture, "out_z1_resume2", est)
    _launch_gang(train_fixture, job2)
    assert _latest_step(model_dir) == 6


def test_gang_killed_mid_training_resumes_from_checkpoint(train_fixture):
    """Crash semantics, not clean-exit semantics: SIGKILL the whole gang
    mid-training, then restart it. The orbax tmp-then-rename write
    discipline must leave a complete latest checkpoint, and the fresh
    gang must resume from it rather than step 0."""
    import time

    from _gang import spawn_gang

    model_dir = str(train_fixture["dir"] / "ckpt_kill")
    epochs = 12  # 36 steps: a wide window to catch mid-flight
    est = _make_estimator(
        epochs=epochs, modelDir=model_dir, checkpointEvery=2
    )
    job = _train_job(train_fixture, "out_kill1", est)
    argv, env = _gang_cmd(train_fixture, job)
    procs = spawn_gang(argv, 2, env)
    # wait for a mid-training checkpoint (well short of the final step
    # 36), then SIGKILL the whole gang
    deadline = time.time() + 300
    killed_at = None
    try:
        while time.time() < deadline:
            step = _latest_step(model_dir) if os.path.isdir(model_dir) else None
            if step is not None and 4 <= step < 30:
                killed_at = step
                break
            if all(p.poll() is not None for p in procs):
                break  # finished before we could kill — sizes too small
            time.sleep(0.02)
        assert killed_at is not None, "never saw a mid-training checkpoint"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait(timeout=30)

    assert not os.path.exists(
        os.path.join(job["output_dir"], "_SUCCESS.train")
    ), "gang was supposed to die before finishing"
    surviving = _latest_step(model_dir)
    assert surviving is not None and surviving >= killed_at

    # fresh gang, same modelDir: resumes from the surviving checkpoint
    job2 = _train_job(train_fixture, "out_kill2", est)
    _launch_gang(train_fixture, job2)
    final = _latest_step(model_dir)
    # epochs x 3 steps resumed ON TOP of the surviving step
    assert final == surviving + epochs * 3, (surviving, final)
    assert os.path.exists(
        os.path.join(job2["output_dir"], "_SUCCESS.train")
    )
