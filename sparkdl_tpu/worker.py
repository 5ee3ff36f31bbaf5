"""Multi-host worker entrypoint: ``python -m sparkdl_tpu.worker``.

Reference analogue: the operational half of HorovodEstimator — the MPI
gang-launcher that started one worker per executor (SURVEY.md §4.4) — and
Spark's executor process itself (partition ownership + task execution +
result return, SURVEY.md §2 L1). TPU-native shape:

- one worker process per TPU host, gang-started by the operator's launcher
  (GKE/xmanager/mpirun — anything that can start N identical processes with
  a rank),
- control plane: ``jax.distributed.initialize`` (coordinator rendezvous)
  when collectives are needed; pure-inference jobs can run with explicit
  ``--process-id/--num-processes`` and no rendezvous at all, because the
  featurization path is embarrassingly parallel over partitions
  (SURVEY.md §1),
- data plane: each worker reads ONLY its own partitions (round-robin
  ownership, ``partitions_for_host``), executes the saved pipeline stage,
  and writes one Arrow IPC file per owned partition — the gather is plain
  files, no RPC fabric needed (SURVEY.md §6: "Arrow IPC/flight-style host
  data plane replaces shuffle").

Job spec (JSON file)::

    {
      "stage_path":   "<dir written by sparkdl_tpu.persistence.save_stage>",
      "input_parquet": "<input dataframe>",
      "num_partitions": 16,            # partitioning of the input
      "output_dir":   "<dir for part-*.arrow>",
    }

Gather with :func:`gather_results`, which returns the DataFrame in global
partition order (identical to a single-process ``transform``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
from typing import List, Optional

from sparkdl_tpu.dataframe.frame import DataFrame
from sparkdl_tpu.obs import span
from sparkdl_tpu.runtime import knobs


def _write_partition_arrow(table, path: str) -> None:
    import pyarrow as pa

    tmp = path + ".tmp"
    with pa.OSFile(tmp, "wb") as sink:
        with pa.ipc.new_file(sink, table.schema) as writer:
            writer.write_table(table)
    os.replace(tmp, path)  # atomic publish: gather never sees partial files


# The canonical balanced split shared with DataFrame.fromColumns — one
# definition, so driver and gang can never disagree on row ownership.
from sparkdl_tpu.dataframe.frame import (  # noqa: E402
    partition_row_spans as _partition_row_ranges,
)


def _read_owned_partitions(path: str, num_partitions: int, owned):
    """Yield ``(global_index, one-partition DataFrame)`` for the owned
    partitions, reading ONLY those row spans from the parquet file
    (streamed batch-wise; peak memory is one partition + one read batch,
    never the whole dataset)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    pf = pq.ParquetFile(path)
    spans = _partition_row_ranges(pf.metadata.num_rows, num_partitions)
    owned_set = {gi for gi in owned if gi < len(spans)}
    if not owned_set:
        return
    # Row-group row offsets: only row groups intersecting an owned span
    # are ever read/decoded — a W-worker gang costs ~1/W of the file in
    # I/O per worker, not W full scans.
    rg_spans = []
    row = 0
    for r in range(pf.metadata.num_row_groups):
        n_rows = pf.metadata.row_group(r).num_rows
        rg_spans.append((row, row + n_rows))
        row += n_rows

    def intersects_owned(lo, hi):
        return any(
            max(lo, spans[gi][0]) < min(hi, spans[gi][1])
            for gi in owned_set
        )

    pending = {gi: [] for gi in sorted(owned_set)}  # gi -> tables so far
    for r, (b_start, b_end) in enumerate(rg_spans):
        if not intersects_owned(b_start, b_end):
            continue
        table_rg = pf.read_row_group(r)
        for gi in sorted(owned_set):
            p_start, p_end = spans[gi]
            lo, hi = max(b_start, p_start), min(b_end, p_end)
            if lo < hi:
                pending[gi].append(table_rg.slice(lo - b_start, hi - lo))
        # emit complete partitions as soon as their span is fully read
        for gi in sorted(pending):
            if spans[gi][1] <= b_end and pending[gi]:
                table = pa.concat_tables(pending.pop(gi))
                owned_set.discard(gi)
                yield gi, DataFrame.fromArrow(table, numPartitions=1)
    # zero-row partitions (spans[gi] empty) still owe an output slot
    for gi in sorted(pending):
        if not pending[gi]:
            yield gi, DataFrame.fromArrow(
                pf.schema_arrow.empty_table(), numPartitions=1
            )


def run_worker(
    job: dict,
    process_id: Optional[int] = None,
    num_processes: Optional[int] = None,
    coordinator: Optional[str] = None,
    distributed: bool = True,
) -> List[int]:
    """Execute one worker's share of a job; returns owned partition indices.

    With ``distributed=True`` the worker joins the jax.distributed gang
    (required for training jobs / collectives). Inference-only jobs may pass
    ``distributed=False`` with explicit ids — no rendezvous, no ports.
    """
    from sparkdl_tpu.parallel import distributed as dist

    if distributed:
        dist.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
        pid, n = dist.process_index(), dist.process_count()
    else:
        if process_id is None or num_processes is None:
            raise ValueError(
                "distributed=False requires explicit process_id and "
                "num_processes"
            )
        pid, n = process_id, num_processes

    with _obs_services(job, pid), _maybe_heartbeat(job, pid):
        with span("worker.job", rank=pid, hosts=n):
            return _run_worker_body(job, pid, n)


def _gang_generation(job: dict) -> int:
    """This incarnation's gang generation: the supervisor exports it as
    ``SPARKDL_GANG_GENERATION`` on every (re)launch; an unsupervised run
    is generation 0 (or whatever the job spec pins)."""
    try:
        raw = knobs.get_int("SPARKDL_GANG_GENERATION")
    except ValueError:
        raw = None
    if raw is not None:
        return raw
    return int(job.get("generation", 0))


def _resume_enabled(job: dict) -> bool:
    """Whether this run may SKIP partitions whose output already
    published and verifies. The supervisor sets ``SPARKDL_GANG_RESUME=1``
    for generations > 0; a job spec can pin ``"resume": true`` for
    manual restarts. Off by default: a plain re-run recomputes
    everything (the pre-supervisor contract)."""
    if knobs.get_flag("SPARKDL_GANG_RESUME"):
        return True
    return bool(job.get("resume"))


def _valid_arrow_output(path: str) -> bool:
    """True if ``path`` is a complete, readable Arrow IPC file — the
    resume check. Crash debris (torn writes published non-atomically by
    a broken filesystem, or plain garbage) fails to open and is
    recomputed, so resume can never gather a corrupt partition."""
    import pyarrow as pa

    try:
        with pa.OSFile(path, "rb") as src:
            pa.ipc.open_file(src).schema
        return True
    except Exception:
        return False


def _run_worker_body(job: dict, pid: int, n: int) -> List[int]:
    from sparkdl_tpu.parallel import distributed as dist
    from sparkdl_tpu.persistence import load_stage
    from sparkdl_tpu.resilience.faults import maybe_fault
    from sparkdl_tpu.utils.metrics import metrics

    stage = load_stage(job["stage_path"])
    num_partitions = int(job["num_partitions"])
    owned = dist.partitions_for_host(
        num_partitions, host_index=pid, host_count=n
    )
    out_dir = job["output_dir"]
    os.makedirs(out_dir, exist_ok=True)
    generation = _gang_generation(job)
    resume = _resume_enabled(job)

    # Start marker: lets gather_results distinguish a rank that NEVER
    # started from one that died mid-write (its owned-partition list is
    # the evidence trail). Overwritten per generation — latest attempt
    # wins, like the partition outputs themselves.
    with open(os.path.join(out_dir, f"_STARTED.{pid}"), "w") as f:
        f.write(
            json.dumps(
                {
                    "process_id": pid,
                    "pid": os.getpid(),
                    "generation": generation,
                    "partitions": owned,
                }
            )
        )

    # Execute ONLY the owned partitions, streaming one at a time (bounded
    # memory: this worker reads just its own row ranges of the input, not
    # the whole dataset), and publish each as an Arrow IPC file keyed by
    # its GLOBAL partition index so the gather reassembles global order.
    # Each owned partition is one span (the heartbeat's compact status
    # therefore names the exact partition a quiet rank was chewing on).
    step = 0
    resumed: List[int] = []
    for gi, part_df in _read_owned_partitions(
        job["input_parquet"], num_partitions, owned
    ):
        out_path = os.path.join(out_dir, f"part-{gi:05d}.arrow")
        if resume and _valid_arrow_output(out_path):
            # A previous generation already published this partition
            # atomically; a restart re-pays only unfinished work.
            metrics.inc("worker.partitions.resumed")
            resumed.append(gi)
            step += 1
            continue
        maybe_fault(
            "worker.partition", rank=pid, step=step, partition=gi,
            gen=generation,
        )
        with span("worker.partition", partition=gi, rank=pid) as sp:
            result = stage.transform(part_df)
            table = result.toArrow()
            sp.add(rows=table.num_rows)
            # One file per GLOBAL input partition; a stage whose result
            # has multiple partitions is collapsed into that one table
            # (toArrow concatenates) so no batch is ever silently dropped.
            _write_partition_arrow(table, out_path)
        step += 1
    # Success marker: gather waits for one per worker (gang completion
    # detection without a control-plane RPC). `resumed`/`generation` are
    # additive keys — the restart evidence trail for supervisors and the
    # chaos smoke (which partitions this incarnation skipped as
    # already-published).
    with open(os.path.join(out_dir, f"_SUCCESS.{pid}"), "w") as f:
        f.write(
            json.dumps(
                {
                    "process_id": pid,
                    "partitions": owned,
                    "generation": generation,
                    "resumed": resumed,
                }
            )
        )
    return owned


def _maybe_heartbeat(job: dict, rank: int):
    """Heartbeat context for a worker when the job spec carries
    ``"heartbeat_dir"`` (SURVEY.md §6 failure detection: an external
    supervisor polls ``sparkdl_tpu.runtime.heartbeat`` staleness and
    gang-restarts — a dead rank otherwise leaves peers silently blocked
    in a collective); no-op context otherwise."""
    hb_dir = job.get("heartbeat_dir")
    if not hb_dir:
        return contextlib.nullcontext()
    from sparkdl_tpu.runtime.heartbeat import Heartbeat

    return Heartbeat(
        hb_dir,
        rank,
        interval=float(job.get("heartbeat_interval", 5.0)),
        generation=_gang_generation(job),
    )


@contextlib.contextmanager
def _obs_services(job: dict, rank: int):
    """Fleet-telemetry services around one gang rank's run:

    - tag the process with its rank (``SPARKDL_OBS_RANK``) so every
      snapshot / JSONL event it emits is attributable,
    - start the metrics time-series sampler (``SPARKDL_OBS_SAMPLE_S=0``
      or ``SPARKDL_OBS=0`` disable it),
    - when ``SPARKDL_OBS_PORT`` is set, expose /metrics on port+rank
      (co-hosted ranks must not collide),
    - on the way out, stop both and force-drop a final per-rank snapshot
      beside the heartbeat files so the cross-rank merge always has this
      rank's terminal state.

    Telemetry failures never propagate: a worker whose actual job is
    fine must not die because a port was busy or a disk was full."""
    prev_rank = knobs.get_raw("SPARKDL_OBS_RANK")
    os.environ["SPARKDL_OBS_RANK"] = str(rank)
    # Only stop what THIS context started: an in-process driver may run
    # its own sampler/exporter, and a worker run ending must not turn
    # the driver's telemetry dark.
    sampler = server = None
    try:
        from sparkdl_tpu.obs import serve, timeseries

        if not timeseries.get_sampler().running():
            sampler = timeseries.start_sampler()
        if serve.server_port() is None:
            server = serve.maybe_start_from_env(rank=rank)
    except Exception:
        pass
    try:
        yield
    finally:
        try:
            hb_dir = job.get("heartbeat_dir")
            if hb_dir:
                from sparkdl_tpu.obs.aggregate import (
                    maybe_write_rank_snapshot,
                )

                maybe_write_rank_snapshot(hb_dir, rank, force=True)
        except Exception:
            pass
        try:
            if sampler is not None:
                from sparkdl_tpu.obs import timeseries

                timeseries.stop_sampler()
        except Exception:
            pass
        try:
            if server is not None:
                from sparkdl_tpu.obs import serve

                serve.stop_server()
        except Exception:
            pass
        # Drop the rank tag so an in-process caller (driver, tests) does
        # not keep emitting artifacts misattributed to this gang rank.
        if prev_rank is None:
            os.environ.pop("SPARKDL_OBS_RANK", None)
        else:
            os.environ["SPARKDL_OBS_RANK"] = prev_rank


def _resolve_model_builder(spec: dict):
    """``{"builder": "pkg.mod:fn", "kwargs": {...}}`` → ModelFunction.

    The gang analogue of HorovodEstimator's ``modelFn`` argument
    (SURVEY.md §4.4): every worker CONSTRUCTS the model from code
    importable on its host (same binary everywhere, the MPI discipline);
    weights never ride the job spec. Deterministic builders (fixed init
    seed) give every rank an identical starting point, which the data-
    parallel step then keeps in lockstep via the per-step all-reduce.
    """
    import importlib

    target = spec["builder"]
    mod_name, sep, fn_name = target.partition(":")
    if not sep or not mod_name or not fn_name:
        raise ValueError(
            f"model builder {target!r} must be 'module:function'"
        )
    fn = getattr(importlib.import_module(mod_name), fn_name)
    return fn(**spec.get("kwargs", {}))


def run_train_worker(
    job: dict,
    process_id: Optional[int] = None,
    num_processes: Optional[int] = None,
    coordinator: Optional[str] = None,
    distributed: bool = True,
):
    """Gang-train a DataParallelEstimator: the HorovodEstimator
    operational path (SURVEY.md §4.4), TPU-native. Every worker joins the
    ``jax.distributed`` rendezvous (coordinator = rank 0's address), after
    which the device mesh spans all processes and the estimator's jitted
    step all-reduces gradients across them each step. Rank 0 publishes
    the trained params + history; orbax checkpoints (``modelDir`` on the
    saved estimator) give kill-and-restart resume.

    Job spec::

        {
          "type": "train",
          "estimator_path": "<saved DataParallelEstimator (no model)>",
          "model": {"builder": "mymodels:build_resnet", "kwargs": {...}},
          "input_parquet": "<training dataframe>",
          "num_partitions": 4,
          "output_dir": "<dir for trained_params.pkl / history.json>"
        }
    """
    from sparkdl_tpu.parallel import distributed as dist

    if distributed:
        dist.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    elif (num_processes or 1) > 1:
        raise ValueError(
            "distributed=False train jobs must be single-process: the "
            "cross-process gradient all-reduce needs the rendezvous"
        )
    rank = dist.process_index() if distributed else (process_id or 0)
    with _obs_services(job, rank), _maybe_heartbeat(job, rank):
        with span("worker.train", rank=rank):
            return _run_train_body(job, rank)


def _run_train_body(job: dict, rank: int):
    import pickle

    import jax
    import numpy as np

    from sparkdl_tpu.estimators import DataParallelEstimator
    from sparkdl_tpu.parallel import distributed as dist
    from sparkdl_tpu.persistence import load_stage

    est = load_stage(job["estimator_path"], DataParallelEstimator)
    est.model = _resolve_model_builder(job["model"])
    try:
        use_streaming = bool(est.getOrDefault("streaming"))
    except KeyError:
        use_streaming = False
    # Streaming estimators get the LAZY scan: each rank's partitions load
    # row-group-wise on demand (the "materialize partitions to
    # executor-local feed" discipline); nothing reads the whole file.
    reader = DataFrame.scanParquet if use_streaming else DataFrame.readParquet
    df = reader(
        job["input_parquet"],
        numPartitions=int(job.get("num_partitions", 1)),
    )
    fitted = est.fit(df)

    out_dir = job["output_dir"]
    if dist.is_coordinator():
        os.makedirs(out_dir, exist_ok=True)
        host_params = jax.tree_util.tree_map(
            np.asarray, fitted.modelFunction.params
        )
        tmp = os.path.join(out_dir, "trained_params.pkl.tmp")
        with open(tmp, "wb") as f:
            pickle.dump(host_params, f)
        os.replace(tmp, os.path.join(out_dir, "trained_params.pkl"))
        with open(os.path.join(out_dir, "history.json"), "w") as f:
            json.dump(fitted.history, f, indent=1)
        with open(os.path.join(out_dir, "_SUCCESS.train"), "w") as f:
            f.write(json.dumps({"epochs": len(fitted.history)}))
    return fitted


def _diagnose_missing_rank(output_dir: str, p: int) -> str:
    """One missing rank's story for the gather error: never-started
    (no ``_STARTED.p`` marker — the launcher/scheduler lost it) reads
    very differently from died-mid-write (started, published some of its
    owned partitions, maybe left ``.tmp`` debris) — the first is a
    launch problem, the second a crash the supervisor should have
    caught."""
    started_path = os.path.join(output_dir, f"_STARTED.{p}")
    try:
        with open(started_path) as f:
            started = json.load(f)
    except (OSError, json.JSONDecodeError):
        started = None
    if started is None:
        return f"rank {p} never started (no _STARTED.{p} marker)"
    owned = started.get("partitions") or []
    published = [
        gi
        for gi in owned
        if os.path.exists(os.path.join(output_dir, f"part-{gi:05d}.arrow"))
    ]
    try:
        debris = sorted(
            name
            for name in os.listdir(output_dir)
            if name.endswith(".tmp")
        )
    except OSError:
        debris = []
    msg = (
        f"rank {p} started (generation "
        f"{started.get('generation', 0)}, owns partitions {owned}) but "
        f"died before finishing: {len(published)}/{len(owned)} partition "
        f"outputs published"
    )
    if debris:
        msg += f", tmp write debris present ({', '.join(debris[:4])})"
    return msg


def gather_results(
    output_dir: str, num_processes: Optional[int] = None
) -> DataFrame:
    """Reassemble worker outputs into one DataFrame in global partition
    order. If ``num_processes`` is given, raises unless every worker's
    success marker is present (detects a partially-failed gang).

    The result is a partition-per-file *lazy* DataFrame: only the first
    file's schema is read here, and streaming consumers (iterPartitions /
    writeParquet) hold one partition's columns at a time — the gang path
    stays bounded-memory end-to-end."""
    if num_processes is not None:
        missing = [
            p
            for p in range(num_processes)
            if not os.path.exists(os.path.join(output_dir, f"_SUCCESS.{p}"))
        ]
        if missing:
            raise RuntimeError(
                f"Workers {missing} have not published success markers in "
                f"{output_dir}; gang incomplete or failed: "
                + "; ".join(_diagnose_missing_rank(output_dir, p)
                            for p in missing)
            )
    names = sorted(
        f for f in os.listdir(output_dir) if f.endswith(".arrow")
    )
    return DataFrame.fromArrowFiles(
        [os.path.join(output_dir, f) for f in names]
    )


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m sparkdl_tpu.worker",
        description="sparkdl_tpu multi-host worker (one per TPU host)",
    )
    ap.add_argument("--job", required=True, help="path to job spec JSON")
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument(
        "--coordinator",
        default=None,
        help="coordinator address host:port (jax.distributed)",
    )
    ap.add_argument(
        "--no-distributed",
        action="store_true",
        help="skip jax.distributed rendezvous (inference-only jobs with "
        "explicit --process-id/--num-processes)",
    )
    ap.add_argument(
        "--platform",
        default=None,
        help="force a jax backend (e.g. 'cpu'); the same as exporting "
        "JAX_PLATFORMS, applied before backend init.",
    )
    args = ap.parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    with open(args.job) as f:
        job = json.load(f)
    if job.get("type") == "train":
        if args.no_distributed and (args.num_processes or 1) > 1:
            ap.error(
                "train jobs need the jax.distributed rendezvous for "
                "cross-process gradient all-reduce; drop --no-distributed"
            )
        run_train_worker(
            job,
            process_id=args.process_id,
            num_processes=args.num_processes,
            coordinator=args.coordinator,
            distributed=not args.no_distributed,
        )
        print("train worker done")
        return
    owned = run_worker(
        job,
        process_id=args.process_id,
        num_processes=args.num_processes,
        coordinator=args.coordinator,
        distributed=not args.no_distributed,
    )
    print(f"worker done: partitions {owned}")


if __name__ == "__main__":
    main()
