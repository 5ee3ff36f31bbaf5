"""Multi-host worker entrypoint tests.

Reference analogue: HorovodEstimator's gang launcher + Spark executors
(SURVEY.md §4.4). Distributedness is tested the way the reference tested
it — real multiple PROCESSES on one machine (the reference used local-mode
Spark; we gang-start actual worker subprocesses) — and the assertion is the
reference's oracle pattern: N-worker output must equal 1-process output
row-for-row.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from sparkdl_tpu.dataframe import DataFrame
from sparkdl_tpu.estimators import LogisticRegression
from sparkdl_tpu.persistence import save_stage
from sparkdl_tpu.worker import gather_results, run_worker


@pytest.fixture(scope="module")
def job_fixture(tmp_path_factory):
    """A fitted model stage + input parquet + expected single-process output."""
    d = tmp_path_factory.mktemp("worker_job")
    rng = np.random.default_rng(0)
    x = np.concatenate(
        [rng.normal(-2, 1, (40, 4)), rng.normal(2, 1, (40, 4))]
    ).astype(np.float32)
    y = np.concatenate([np.zeros(40), np.ones(40)]).astype(np.int64)
    train = DataFrame.fromColumns(
        {"features": list(x), "label": list(y)}, numPartitions=2
    )
    model = LogisticRegression(
        featuresCol="features", labelCol="label", predictionCol="pred",
        maxIter=20,
    ).fit(train)
    stage_path = str(d / "stage")
    save_stage(model, stage_path)

    x_test = rng.normal(0, 2, (30, 4)).astype(np.float32)
    test_df = DataFrame.fromColumns({"features": list(x_test)}, 1)
    input_parquet = str(d / "input.parquet")
    test_df.writeParquet(input_parquet)

    expected = [
        r.pred
        for r in model.transform(
            DataFrame.readParquet(input_parquet, numPartitions=6)
        ).collect()
    ]
    job = {
        "stage_path": stage_path,
        "input_parquet": input_parquet,
        "num_partitions": 6,
        "output_dir": None,  # set per test
    }
    return {"dir": d, "job": job, "expected": expected}


def _run_job(job_fixture, out_name, launch):
    job = dict(job_fixture["job"])
    job["output_dir"] = str(job_fixture["dir"] / out_name)
    launch(job)
    got_df = gather_results(job["output_dir"], num_processes=2)
    got = [r.pred for r in got_df.collect()]
    assert len(got) == len(job_fixture["expected"])
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float64),
        np.asarray(job_fixture["expected"], dtype=np.float64),
        rtol=1e-6,
    )


def test_two_workers_in_process_match_single_process(job_fixture):
    """In-process gang of 2 (fast path): identical output to 1-process."""

    def launch(job):
        owned0 = run_worker(job, 0, 2, distributed=False)
        owned1 = run_worker(job, 1, 2, distributed=False)
        assert sorted(owned0 + owned1) == list(range(6))
        assert not set(owned0) & set(owned1)

    _run_job(job_fixture, "out_inproc", launch)


def test_two_worker_subprocesses_match_single_process(job_fixture):
    """REAL 2-process gang via `python -m sparkdl_tpu.worker`."""

    def launch(job):
        job_path = str(job_fixture["dir"] / "job.json")
        with open(job_path, "w") as f:
            json.dump(job, f)
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
        }
        from _gang import run_gang

        run_gang(
            lambda pid: [
                sys.executable, "-m", "sparkdl_tpu.worker",
                "--job", job_path,
                "--process-id", str(pid),
                "--num-processes", "2",
                "--no-distributed",
                "--platform", "cpu",
            ],
            2,
            env,
            timeout=240,
        )

    _run_job(job_fixture, "out_subproc", launch)


def test_gather_detects_incomplete_gang(job_fixture, tmp_path):
    job = dict(job_fixture["job"])
    job["output_dir"] = str(tmp_path / "partial")
    run_worker(job, 0, 2, distributed=False)  # only worker 0 runs
    with pytest.raises(RuntimeError, match="Workers \\[1\\]"):
        gather_results(job["output_dir"], num_processes=2)


def test_owned_partition_reads_skip_foreign_row_groups(tmp_path):
    """Workers read only row groups intersecting their owned spans."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from sparkdl_tpu.worker import _read_owned_partitions

    n = 40
    table = pa.table({"v": list(range(n))})
    p = str(tmp_path / "rg.parquet")
    pq.write_table(table, p, row_group_size=5)  # 8 row groups

    got = dict(_read_owned_partitions(p, num_partitions=8, owned=[1, 4]))
    assert sorted(got) == [1, 4]
    assert [r.v for r in got[1].collect()] == list(range(5, 10))
    assert [r.v for r in got[4].collect()] == list(range(20, 25))

    # I/O restriction: count row-group reads via a probe
    reads = []
    orig = pq.ParquetFile.read_row_group

    def probe(self, i, *a, **k):
        reads.append(i)
        return orig(self, i, *a, **k)

    pq.ParquetFile.read_row_group = probe
    try:
        dict(_read_owned_partitions(p, num_partitions=8, owned=[2]))
    finally:
        pq.ParquetFile.read_row_group = orig
    assert reads == [2]  # exactly the one owned row group


def test_worker_crash_restart_recovers(job_fixture, monkeypatch):
    """Elastic recovery, the reference's gang model (SURVEY.md §6): a
    worker that crashes mid-job leaves its already-written part files
    (and possibly corrupt leftovers) but no success marker; restarting
    JUST that worker overwrites its partitions idempotently and the
    gather then matches the single-process oracle."""
    import sparkdl_tpu.worker as worker_mod

    def launch(job):
        run_worker(job, 0, 2, distributed=False)

        orig_write = worker_mod._write_partition_arrow
        calls = {"n": 0}

        def crash_on_second_write(table, path):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("simulated worker crash")
            orig_write(table, path)

        monkeypatch.setattr(
            worker_mod, "_write_partition_arrow", crash_on_second_write
        )
        with pytest.raises(RuntimeError, match="simulated worker crash"):
            run_worker(job, 1, 2, distributed=False)
        monkeypatch.setattr(
            worker_mod, "_write_partition_arrow", orig_write
        )

        # crashed worker published no marker -> gang detected incomplete
        with pytest.raises(RuntimeError, match="Workers \\[1\\]"):
            gather_results(job["output_dir"], num_processes=2)

        # a corrupt leftover at a final path (non-atomic filesystem
        # crash debris) must be overwritten by the restart, not gathered
        with open(
            os.path.join(job["output_dir"], "part-00003.arrow"), "wb"
        ) as f:
            f.write(b"garbage")

        # restart only the failed worker (owns partitions 1, 3, 5)
        run_worker(job, 1, 2, distributed=False)

    _run_job(job_fixture, "out_restart", launch)


def test_two_worker_subprocesses_with_rendezvous(job_fixture):
    """Inference gang WITH the jax.distributed rendezvous (no
    --no-distributed): process identity comes from the coordinator, and
    output still matches the single-process oracle."""
    from _gang import free_port, run_gang

    def launch(job):
        job_path = str(job_fixture["dir"] / "job_rdv.json")
        with open(job_path, "w") as f:
            json.dump(job, f)
        port = free_port()
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        }
        run_gang(
            lambda pid: [
                sys.executable, "-m", "sparkdl_tpu.worker",
                "--job", job_path,
                "--process-id", str(pid),
                "--num-processes", "2",
                "--coordinator", f"localhost:{port}",
                "--platform", "cpu",
            ],
            2,
            env,
            timeout=240,
        )

    _run_job(job_fixture, "out_rendezvous", launch)


# -- resilience plumbing (generation tags + resume) ---------------------------


def _no_fit_job(tmp_path, num_partitions=4):
    """A worker job around a DIRECTLY-constructed model (no fit): the
    resilience plumbing tests must run even where the training path's
    collectives are unavailable."""
    from sparkdl_tpu.estimators.logistic_regression import (
        LogisticRegressionModel,
    )
    from sparkdl_tpu.persistence import save_stage

    rng = np.random.default_rng(3)
    stage = LogisticRegressionModel(
        w=rng.normal(size=(4, 3)).astype(np.float32),
        b=rng.normal(size=(3,)).astype(np.float32),
        featuresCol="features", predictionCol="pred", probabilityCol=None,
    )
    stage_path = str(tmp_path / "stage")
    save_stage(stage, stage_path)
    inp = str(tmp_path / "in.parquet")
    DataFrame.fromColumns(
        {"features": list(rng.normal(size=(24, 4)).astype(np.float32))}, 1
    ).writeParquet(inp)
    return {
        "stage_path": stage_path,
        "input_parquet": inp,
        "num_partitions": num_partitions,
        "output_dir": str(tmp_path / "out"),
    }


def test_heartbeat_payload_carries_generation(tmp_path, monkeypatch):
    """The supervisor exports SPARKDL_GANG_GENERATION on every relaunch;
    the rank's beats must carry it so staleness tooling can tell this
    incarnation's files from a dead predecessor's."""
    job = _no_fit_job(tmp_path)
    job["heartbeat_dir"] = str(tmp_path / "hb")
    job["heartbeat_interval"] = 0.05
    monkeypatch.setenv("SPARKDL_GANG_GENERATION", "2")
    run_worker(job, 0, 1, distributed=False)
    with open(os.path.join(job["heartbeat_dir"], "hb.0")) as f:
        final = json.load(f)
    assert final["generation"] == 2
    assert final["done"] is True
    # generation-filtered staleness: this done beat satisfies gen 2 but
    # is NOT evidence for a hypothetical gen 3
    from sparkdl_tpu.runtime.heartbeat import stale_ranks

    assert stale_ranks(job["heartbeat_dir"], 1, 30.0, generation=2) == []
    assert stale_ranks(job["heartbeat_dir"], 1, 30.0, generation=3) == [0]


def test_worker_resume_skips_published_partitions(tmp_path, monkeypatch):
    """With resume armed (what the supervisor sets for generations > 0),
    a relaunched worker verifies + skips already-published outputs and
    recomputes only invalid/missing ones — and the result still matches
    a from-scratch run."""
    job = _no_fit_job(tmp_path)
    run_worker(job, 0, 1, distributed=False)
    expected = [r.pred for r in gather_results(job["output_dir"], 1).collect()]

    # corrupt one output in place (crash debris at a final path)
    victim = os.path.join(job["output_dir"], "part-00002.arrow")
    with open(victim, "wb") as f:
        f.write(b"garbage")
    monkeypatch.setenv("SPARKDL_GANG_RESUME", "1")
    monkeypatch.setenv("SPARKDL_GANG_GENERATION", "1")
    run_worker(job, 0, 1, distributed=False)
    with open(os.path.join(job["output_dir"], "_SUCCESS.0")) as f:
        marker = json.load(f)
    assert marker["generation"] == 1
    # valid outputs were skipped; the corrupt one was recomputed
    assert sorted(marker["resumed"]) == [0, 1, 3]
    got = [r.pred for r in gather_results(job["output_dir"], 1).collect()]
    np.testing.assert_allclose(
        np.asarray(got, np.float64), np.asarray(expected, np.float64),
        rtol=1e-6,
    )
