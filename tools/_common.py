"""Shared setup for the diagnostic scripts in tools/."""

import os
import sys

# tools/ scripts are invoked as `python tools/<name>.py`; the repo root
# (the sparkdl_tpu package home) is their parent directory.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def lock_sanitizer_problems():
    """Shared smoke epilogue for ``SPARKDL_LOCK_SANITIZER=1`` runs:
    dump the observed lock graph ({"kind":"locks"} JSONL + report),
    fail on any runtime-observed cycle, and cross-check that every
    observed held-before edge is implied by the static analyzer's graph
    (an unknown edge means the analyzer lost a code path — a finding in
    its own right). Returns (problems, verdict_extras); both empty when
    the sanitizer is off."""
    from sparkdl_tpu.runtime import locksmith

    if not locksmith.sanitizer_enabled():
        return [], {}
    snap = locksmith.report()
    problems = [
        "lock-order cycle observed at runtime: " + " -> ".join(cycle)
        for cycle in snap["cycles"]
    ]
    try:
        from tools.lint import Project, REPO_ROOT, lockorder_check

        problems += locksmith.cross_check(
            lockorder_check.static_edges(Project(REPO_ROOT))
        )
    except Exception as e:  # noqa: BLE001 — a broken lint is a finding too
        problems.append(f"lock sanitizer static cross-check failed: {e}")
    return problems, {
        "lock_acquisitions": snap["acquisitions"],
        "lock_edges_observed": len(snap["edges"]),
        "locks_held_too_long": len(snap["held_too_long"]),
    }
