"""Profiler integration — jax.profiler traces as a context manager.

Reference analogue: none in-tree (SURVEY.md §6 — the reference relied on
the Spark UI; TF timelines required manual wiring). Here any transform or
training loop can be wrapped in :func:`profile_trace` to capture an XLA
trace viewable in TensorBoard/Perfetto, including HBM transfer and MXU
occupancy timelines on TPU.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Iterator, Optional


class ProfilerUnavailable(RuntimeError):
    """The jax.profiler backend cannot start a trace on this
    build/mesh (CPU test boxes, stripped builds) — the on-demand
    profiling endpoint maps this to a clean 501."""


class ProfilerBusy(RuntimeError):
    """A capture is already running — jax.profiler supports one trace
    session per process; the endpoint maps this to 409."""


_capture_lock = threading.Lock()
_capturing = False


def capture_profile(log_dir: str, seconds: float) -> str:
    """On-demand capture: start a jax.profiler trace into a fresh
    timestamped run directory under ``log_dir``, hold it open for
    ``seconds`` of live traffic, stop, and return the run directory.

    Raises :class:`ProfilerUnavailable` when the backend refuses to
    start (instead of the silent no-op :func:`profile_trace` prefers —
    an operator who ASKED for a trace must learn they didn't get one)
    and :class:`ProfilerBusy` when a capture is already in flight."""
    global _capturing
    import jax

    with _capture_lock:
        if _capturing:
            raise ProfilerBusy("a profiler capture is already running")
        _capturing = True
    try:
        run_dir = os.path.join(
            log_dir, time.strftime("profile-%Y%m%dT%H%M%S")
        )
        os.makedirs(run_dir, exist_ok=True)
        try:
            jax.profiler.start_trace(run_dir)
        except Exception as e:  # noqa: BLE001 — backend-specific failures
            try:
                os.rmdir(run_dir)  # nothing was written: don't leave junk
            except OSError:
                pass
            raise ProfilerUnavailable(
                f"jax.profiler could not start a trace: "
                f"{type(e).__name__}: {e}"
            ) from e
        try:
            time.sleep(max(0.0, float(seconds)))
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                raise ProfilerUnavailable(
                    f"jax.profiler could not stop the trace: "
                    f"{type(e).__name__}: {e}"
                ) from e
        return run_dir
    finally:
        with _capture_lock:
            _capturing = False


@contextlib.contextmanager
def profile_trace(
    log_dir: str, *, enabled: bool = True, host_tracer_level: int = 2
) -> Iterator[None]:
    """Capture a jax.profiler trace into ``log_dir`` for the duration of
    the block. No-op (but still a valid context) when ``enabled`` is False
    or the profiler backend is unavailable (e.g. CPU test meshes)."""
    if not enabled:
        yield
        return
    import jax

    try:
        jax.profiler.start_trace(log_dir)
        started = True
    except Exception:
        started = False
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass


class _NullAnnotation:
    """Degraded-mode stand-in for TraceAnnotation: a no-op context
    manager that also works as a pass-through decorator."""

    def __enter__(self) -> "_NullAnnotation":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def __call__(self, fn):
        return fn


def annotate(name: str, **attrs):
    """Named region inside a trace (TraceAnnotation); usable as decorator
    or context manager. ``attrs`` (scalars) become the event's stats.
    While no trace runs, entering it checks one flag. Degrades to a
    no-op — like :func:`profile_trace` already does — on CPU test meshes
    and jax-less callers, instead of raising. Its caller in the program
    is :mod:`sparkdl_tpu.obs.spans`: every span is a ``sparkdl:<name>``
    annotation."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(name, **attrs)
    except Exception:
        return _NullAnnotation()


#: prefix of every scope in the ``op_name`` of a device operation: the
#: spans' own (``sparkdl_tpu.obs.spans.ANNOTATION_PREFIX``), so that one
#: name reads the host's lines and the device's
SCOPE_PREFIX = "sparkdl:"


def scope(name: str):
    """A part of a model's program, named on the device's timeline:
    ``jax.named_scope("sparkdl:<name>")`` around the calls that make the
    part while the program is traced to a jaxpr. The name is metadata of
    the lowered operations (``op_name="jit(fn)/.../sparkdl:mlp/..."``),
    which any ``jax.profiler`` capture shows on the device's lines: it
    adds no operation, is in no jaxpr's text and in no compile-cache key,
    and costs nothing when the program runs. The text families' names
    are listed in docs/OBSERVABILITY.md."""
    import jax

    return jax.named_scope(SCOPE_PREFIX + name)
