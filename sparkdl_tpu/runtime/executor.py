"""Partitioned execution runtime.

The reference has no in-tree runtime: Spark supplies task scheduling, retries,
and data movement (SURVEY.md §2 "There is no scheduler/runtime layer
in-tree"). This framework replaces that with a small in-tree runtime:

- ``Executor`` — maps a function over DataFrame partitions on a worker pool
  with per-partition retry (the Spark ``spark.task.maxFailures`` semantics).
  On a TPU host there is ONE process per host pinned to the local chips
  (BASELINE north_star: executors pinned 1:1 to TPU VM hosts), so worker
  parallelism here is host-side threads feeding the single device stream —
  CPU-bound work (decode, layout) overlaps with device execution.
- ``TaskMetrics`` — per-partition timing/row counts, aggregated into
  throughput numbers (images/sec — the BASELINE metric).

Device-side batching/prefetch lives in sparkdl_tpu.runtime.feeder (the
shared ``DeviceFeeder``), entered through
sparkdl_tpu.transformers.execution.run_batched_shared.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor, as_completed, wait
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence

from sparkdl_tpu.obs import dump_on_failure, span
from sparkdl_tpu.resilience.faults import maybe_fault
from sparkdl_tpu.resilience.policy import RetryPolicy, policy_from_env
from sparkdl_tpu.runtime import locksmith
from sparkdl_tpu.utils.metrics import metrics as global_metrics


@dataclass(frozen=True)
class TaskContext:
    """What a partition task knows about the run it belongs to, published
    thread-locally for the duration of ``fn(i, part)``. The batch engine
    reads ``concurrency`` (a task of a sequential executor is alone on the
    shared device feeder, so its tail batch is flushed without the linger
    in which a concurrent partition could still join; the text engine
    sizes its tokenize chunks by it) and labels its streams with
    ``partition_index``.
    ``parent_span_id`` carries the ``executor.map_partitions`` span across
    the hand-off to a pool thread, so the task's ``executor.partition``
    span hangs under it and one job's spans form one tree; it is tracing
    metadata and no part of what makes two contexts equal."""

    partition_index: int
    num_partitions: int
    concurrency: int = 1
    parent_span_id: Optional[int] = field(default=None, compare=False)


_task_local = threading.local()


def current_task_context() -> Optional[TaskContext]:
    """The TaskContext of the map_partitions task running on THIS thread,
    or None outside one (direct calls, producer threads)."""
    return getattr(_task_local, "ctx", None)


@dataclass
class TaskMetrics:
    """Aggregated metrics across one map_partitions run."""

    num_partitions: int = 0
    num_failures: int = 0
    rows: int = 0
    wall_time_s: float = 0.0
    partition_times_s: List[float] = field(default_factory=list)

    @property
    def rows_per_sec(self) -> float:
        return self.rows / self.wall_time_s if self.wall_time_s > 0 else 0.0


class PartitionTaskError(RuntimeError):
    """A partition task exhausted its retries."""

    def __init__(self, partition_index: int, attempts: int, cause: BaseException):
        super().__init__(
            f"Partition task {partition_index} failed after {attempts} attempts: "
            f"{type(cause).__name__}: {cause}"
        )
        self.partition_index = partition_index
        self.attempts = attempts
        self.cause = cause


class Executor:
    """Thread-pool partition executor with bounded retry.

    ``ordered=True`` (always): results come back in partition order regardless
    of completion order, matching DataFrame semantics.
    """

    def __init__(
        self,
        max_workers: Optional[int] = None,
        max_failures: int = 2,
        retry_policy: Optional[RetryPolicy] = None,
    ):
        self.max_workers = max_workers or min(16, (os.cpu_count() or 4))
        self.max_failures = max(1, max_failures)
        # The shared RetryPolicy replaces the old bare
        # `range(max_failures)` loop: same attempt budget, but retries
        # now back off (a partition that failed because the device/pool
        # is momentarily sick shouldn't hammer it), jitter is seeded-
        # deterministic (chaos replays sleep the same schedule), and an
        # error the policy classifies FATAL stops retrying immediately.
        # `SPARKDL_EXEC_RETRY_*` env knobs override the defaults.
        self.retry_policy = retry_policy or policy_from_env(
            "SPARKDL_EXEC_RETRY",
            max_attempts=self.max_failures,
            base_delay_s=0.05,
            max_delay_s=2.0,
        )
        self._lock = locksmith.lock(
            "sparkdl_tpu/runtime/executor.py::Executor._lock"
        )
        self._pool: Optional[ThreadPoolExecutor] = None
        self._active_calls = 0
        self.last_metrics: Optional[TaskMetrics] = None

    # -- worker pool ---------------------------------------------------------

    def _acquire_pool(self):
        """The lazily-created persistent pool — thread spawn is paid once
        per Executor, not once per transform (``default_executor`` runs
        every DataFrame action). Nested/concurrent map_partitions calls
        (a partition fn that itself executes a DataFrame) get a private
        throwaway pool instead: handing them the shared, possibly-full
        pool could deadlock inner tasks behind the outer ones occupying
        every worker. Returns (pool, is_private)."""
        with self._lock:
            self._active_calls += 1
            if self._active_calls == 1:
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(
                        max_workers=self.max_workers,
                        thread_name_prefix="sparkdl-exec",
                    )
                return self._pool, False
        return (
            ThreadPoolExecutor(max_workers=self.max_workers),
            True,
        )

    def _release_pool(self, pool, private: bool) -> None:
        with self._lock:
            self._active_calls -= 1
        if private:
            pool.shutdown(wait=True)

    def close(self) -> None:
        """Shut down the persistent worker pool (idempotent) and the
        module-global H2D copy pools it fed (also lazily re-created —
        a concurrent feeder just gets a fresh pool for its next stage).
        The next map_partitions call re-creates the worker pool lazily."""
        with self._lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)
        from sparkdl_tpu.runtime.transfer import shutdown_transfer_pool

        shutdown_transfer_pool()

    def map_partitions(
        self,
        fn: Callable[[int, Any], Any],
        partitions: Sequence[Any],
        count_rows: Optional[Callable[[Any], int]] = None,
    ) -> List[Any]:
        """Run ``fn(index, partition)`` over all partitions; ordered results."""
        metrics = TaskMetrics(num_partitions=len(partitions))
        t0 = time.perf_counter()
        results: List[Any] = [None] * len(partitions)

        sequential = len(partitions) <= 1 or self.max_workers == 1
        concurrency = (
            1 if sequential else min(self.max_workers, len(partitions))
        )

        def run_one(i: int, part: Any, parent_span_id: Optional[int]) -> Any:
            prev_ctx = getattr(_task_local, "ctx", None)
            _task_local.ctx = ctx = TaskContext(
                partition_index=i,
                num_partitions=len(partitions),
                concurrency=concurrency,
                parent_span_id=parent_span_id,
            )
            try:
                return _run_one_in_ctx(i, part, ctx)
            finally:
                _task_local.ctx = prev_ctx

        def _run_one_in_ctx(i: int, part: Any, ctx: TaskContext) -> Any:
            policy = self.retry_policy
            last_err: Optional[BaseException] = None
            attempt = 0
            t_start = time.monotonic()
            while True:
                pt0 = time.perf_counter()
                try:
                    with span(
                        "executor.partition",
                        parent_id=ctx.parent_span_id,
                        partition=i,
                        attempt=attempt,
                    ) as sp:
                        maybe_fault(
                            "executor.partition", partition=i, attempt=attempt
                        )
                        out = fn(i, part)
                        rows = count_rows(out) if count_rows else None
                        if rows is not None:
                            sp.add(rows=rows)
                    dt = time.perf_counter() - pt0
                    # TaskMetrics stays the per-run aggregate; the global
                    # registry makes the same numbers visible to obs
                    # reports and heartbeat payloads process-wide.
                    global_metrics.record_time("executor.partition.time", dt)
                    with self._lock:
                        metrics.partition_times_s.append(dt)
                        if rows is not None:
                            metrics.rows += rows
                    if rows is not None:
                        global_metrics.inc("executor.rows", rows)
                    return out
                except Exception as e:  # retried; re-raised on exhaustion
                    last_err = e
                    global_metrics.inc("executor.partition.failures")
                    with self._lock:
                        metrics.num_failures += 1
                    if policy.classify(e) and policy.allows(
                        attempt + 1, time.monotonic() - t_start
                    ):
                        global_metrics.inc("executor.partition.retries")
                        delay = policy.delay_s(attempt)
                        if delay > 0.0:
                            time.sleep(delay)
                        attempt += 1
                        continue
                    break
            # Two distinct terminal stories: a budget actually spent on
            # retries vs an error classified fatal on sight ("exhausted"
            # must never exceed the retries that ran).
            global_metrics.inc(
                "executor.partition.retry_exhausted"
                if attempt > 0
                else "executor.partition.fatal_errors"
            )
            err = PartitionTaskError(i, attempt + 1, last_err)
            # Flight-recorder flush (env-gated): the ring buffer around a
            # retries-exhausted partition is exactly the context the
            # ad-hoc-log reconstruction of past failures lacked.
            dump_on_failure("partition_task_error")
            raise err

        with span(
            "executor.map_partitions", partitions=len(partitions)
        ) as job_sp:
            if sequential:
                for i, part in enumerate(partitions):
                    results[i] = run_one(i, part, job_sp.span_id)
            else:
                pool, private = self._acquire_pool()
                try:
                    futs = {
                        pool.submit(run_one, i, part, job_sp.span_id): i
                        for i, part in enumerate(partitions)
                    }
                    try:
                        for fut in as_completed(futs):
                            results[futs[fut]] = fut.result()
                    except BaseException:
                        # No task may outlive the call (the old per-call
                        # pool's shutdown(wait=True) guaranteed this):
                        # cancel what hasn't started, wait out the rest —
                        # otherwise orphan partitions would keep feeding
                        # the device/metrics behind the caller's back.
                        for f in futs:
                            f.cancel()
                        wait(list(futs))
                        raise
                finally:
                    self._release_pool(pool, private)

        metrics.wall_time_s = time.perf_counter() - t0
        self.last_metrics = metrics
        return results


_default_executor: Optional[Executor] = None
_default_lock = locksmith.lock(
    "sparkdl_tpu/runtime/executor.py::_default_lock"
)


def default_executor() -> Executor:
    global _default_executor
    with _default_lock:
        if _default_executor is None:
            _default_executor = Executor()
        return _default_executor


def set_default_executor(executor: Executor) -> None:
    global _default_executor
    with _default_lock:
        _default_executor = executor
