"""Chaos smoke: prove the resilience layer's recovery loop on CPU.

The acceptance drill for docs/RESILIENCE.md, fault-plan-driven and fully
deterministic: a 2-rank worker gang runs a saved model stage over 6
partitions with ``SPARKDL_FAULT_PLAN`` armed to **crash rank 1 at its
second partition** (``rank=1:step=1:crash``). The smoke asserts the
whole detect -> kill -> restart -> resume loop:

- the :class:`GangSupervisor` sees the rank die (liveness channel),
  kills the gang, and relaunches exactly ONE new generation;
- the fault's cross-process ``times=1`` claim (``SPARKDL_FAULT_STATE``)
  holds, so generation 1 runs clean and the job completes;
- the gathered output is IDENTICAL to a fault-free single-process run
  (restarts never change answers);
- generation 1 actually RESUMED: it skipped every partition generation
  0 had already published;
- replaying the same plan + seed from scratch yields the identical
  supervisor + fault event sequence (deterministic fields only: pids,
  timestamps, and the kill-race count are process-scheduling noise and
  are excluded by construction).

Exit 0 and a one-line JSON verdict on success; exit 1 naming what
failed. Callable standalone or via tools/preflight.sh::

    JAX_PLATFORMS=cpu python tools/chaos_smoke.py [--out-dir DIR]
"""

import argparse
import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


import numpy as np  # noqa: E402

NUM_RANKS = 2
NUM_PARTITIONS = 6
FAULT_PLAN = "rank=1:step=1:crash"


def _build_job(root: str) -> dict:
    """A saved stage + input parquet (no fit: fixed-weight logistic
    model, so the smoke runs on any CPU-only jax)."""
    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.estimators.logistic_regression import (
        LogisticRegressionModel,
    )
    from sparkdl_tpu.persistence import save_stage

    rng = np.random.default_rng(7)
    x = rng.normal(size=(48, 4)).astype(np.float32)
    stage = LogisticRegressionModel(
        w=rng.normal(size=(4, 3)).astype(np.float32),
        b=rng.normal(size=(3,)).astype(np.float32),
        featuresCol="features",
        predictionCol="pred",
        probabilityCol=None,
    )
    stage_path = os.path.join(root, "stage")
    save_stage(stage, stage_path)
    inp = os.path.join(root, "input.parquet")
    DataFrame.fromColumns({"features": list(x)}, 1).writeParquet(inp)
    oracle = [
        r.pred
        for r in stage.transform(
            DataFrame.readParquet(inp, numPartitions=NUM_PARTITIONS)
        ).collect()
    ]
    return {"stage_path": stage_path, "input_parquet": inp,
            "oracle": oracle}


def _event_signature(events, jsonl_path):
    """The deterministic projection of one chaos run's event stream:
    supervisor decisions (minus pids/kill-race counts) in order, then
    the fault firings from the JSONL log (minus timestamps). Two runs
    of the same plan + seed must produce the same signature."""
    sig = []
    for e in events:
        keep = {
            k: e[k]
            for k in (
                "event", "generation", "rank", "returncode",
                "dead_ranks", "stale_ranks", "num_ranks", "backoff_s",
            )
            if k in e
        }
        sig.append(keep)
    with open(jsonl_path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "fault":
                sig.append(
                    {
                        "fault": rec["rule"],
                        "site": rec["site"],
                        "coords": rec["coords"],
                    }
                )
    return sig


def _chaos_run(root: str, job_spec: dict, tag: str):
    """One supervised gang run under the armed fault plan; returns
    (SupervisorResult, gathered predictions, event signature, resumed)."""
    from sparkdl_tpu.resilience import GangSupervisor, RetryPolicy
    from sparkdl_tpu.resilience.supervisor import worker_launcher
    from sparkdl_tpu.worker import gather_results

    run_dir = os.path.join(root, tag)
    os.makedirs(run_dir)
    out_dir = os.path.join(run_dir, "out")
    hb_dir = os.path.join(run_dir, "hb")
    jsonl = os.path.join(run_dir, "events.jsonl")
    job = {
        "stage_path": job_spec["stage_path"],
        "input_parquet": job_spec["input_parquet"],
        "num_partitions": NUM_PARTITIONS,
        "output_dir": out_dir,
        "heartbeat_dir": hb_dir,
        "heartbeat_interval": 0.2,
    }
    job_path = os.path.join(run_dir, "job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)

    # The plan + state + seed ride ONLY the worker env (extra_env), so
    # the smoke's own in-process executor hooks can never match; the
    # supervisor's JSONL events need the env in THIS process too.
    os.environ["SPARKDL_OBS_JSONL"] = jsonl
    try:
        launch = worker_launcher(
            job_path,
            NUM_RANKS,
            platform="cpu",
            extra_env={
                "SPARKDL_FAULT_PLAN": FAULT_PLAN,
                "SPARKDL_FAULT_STATE": os.path.join(run_dir, "faults"),
                "SPARKDL_FAULT_SEED": "0",
                "SPARKDL_OBS_JSONL": jsonl,
                "JAX_PLATFORMS": "cpu",
            },
        )
        sup = GangSupervisor(
            launch,
            NUM_RANKS,
            heartbeat_dir=hb_dir,
            stale_after=30.0,
            poll_interval=0.2,
            restart_policy=RetryPolicy(
                max_attempts=3, base_delay_s=0.05, max_delay_s=0.5, seed=0
            ),
        )
        result = sup.run()
    finally:
        os.environ.pop("SPARKDL_OBS_JSONL", None)
    got = [r.pred for r in gather_results(out_dir, NUM_RANKS).collect()]
    faults_fired = [
        rec
        for rec in (json.loads(ln) for ln in open(jsonl) if ln.strip())
        if rec.get("kind") == "fault"
    ]
    # The crashed rank's generation-1 success marker records which
    # already-published partitions it skipped — the resume evidence.
    with open(os.path.join(out_dir, "_SUCCESS.1")) as f:
        success1 = json.load(f)
    return (
        result, got, _event_signature(result.events, jsonl),
        faults_fired, success1,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--out-dir", default=None,
        help="where job artifacts / event logs land (default: a temp dir)",
    )
    args = ap.parse_args(argv)
    root = args.out_dir or tempfile.mkdtemp(prefix="chaos_smoke_")
    os.makedirs(root, exist_ok=True)

    problems = []
    job_spec = _build_job(root)

    results = []
    for tag in ("run1", "run2"):
        try:
            results.append(_chaos_run(root, job_spec, tag))
        except Exception as e:  # noqa: BLE001
            problems.append(f"{tag} did not complete: {type(e).__name__}: {e}")
    if not problems:
        for tag, (result, got, sig, faults_fired, success1) in zip(
            ("run1", "run2"), results
        ):
            if result.restarts != 1:
                problems.append(
                    f"{tag}: expected exactly 1 supervisor restart, got "
                    f"{result.restarts}"
                )
            if result.generations != 2:
                problems.append(
                    f"{tag}: expected 2 generations, got "
                    f"{result.generations}"
                )
            if len(faults_fired) != 1:
                problems.append(
                    f"{tag}: fault fired {len(faults_fired)} times "
                    f"(times=1 claim across generations broken)"
                )
            if success1.get("generation") != 1:
                problems.append(
                    f"{tag}: rank 1's final success marker is generation "
                    f"{success1.get('generation')}, expected 1 (restart "
                    f"didn't replace the crashed incarnation)"
                )
            if 1 not in (success1.get("resumed") or []):
                problems.append(
                    f"{tag}: generation 1 recomputed partition 1 instead "
                    f"of resuming past it (resumed="
                    f"{success1.get('resumed')})"
                )
            if len(got) != len(job_spec["oracle"]):
                problems.append(
                    f"{tag}: gathered {len(got)} rows != "
                    f"{len(job_spec['oracle'])}"
                )
            elif not np.allclose(
                np.asarray(got, dtype=np.float64),
                np.asarray(job_spec["oracle"], dtype=np.float64),
                rtol=1e-6,
            ):
                problems.append(
                    f"{tag}: recovered output differs from fault-free "
                    f"oracle"
                )
        sig1, sig2 = results[0][2], results[1][2]
        if sig1 != sig2:
            problems.append(
                f"replay diverged: run1 events {sig1} != run2 events {sig2}"
            )
        expected_events = [
            "gang_start", "rank_dead", "gang_killed", "gang_restart",
            "gang_start", "gang_complete",
        ]
        got_events = [e["event"] for e in results[0][0].events]
        if got_events != expected_events:
            problems.append(
                f"event sequence {got_events} != {expected_events}"
            )

    verdict = {
        "chaos_smoke": "FAIL" if problems else "OK",
        "plan": FAULT_PLAN,
        "restarts": [r[0].restarts for r in results],
        "out_dir": root,
    }
    if problems:
        verdict["problems"] = problems
        print(json.dumps(verdict), file=sys.stderr)
        return 1
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
