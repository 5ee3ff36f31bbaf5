"""The SPARKDL_* knob registry: every env knob declared exactly once.

Seven PRs of perf/serving/resilience work grew ~65 ``SPARKDL_*`` env
knobs read at ~84 scattered ``os.environ`` sites, each repeating its own
default literal (``SPARKDL_H2D_CHUNK_MB`` was parsed at 5 different
sites). This module is the single source of truth: one
:class:`Knob` declaration per knob — name, type, default, choices, a
one-line doc, the owning module — and typed accessors
(:func:`get_int` / :func:`get_float` / :func:`get_flag` / :func:`get_str`
/ :func:`get_raw`) that every runtime read goes through. Defaults are
stated HERE and nowhere else.

Enforced, not conventional: ``python -m tools.lint`` (tier-1
``tests/test_lint.py`` + ``tools/preflight.sh``) flags any raw
``os.environ`` read of a ``SPARKDL_*`` name outside this file, any knob
read but not declared, any declared knob that nothing reads, and a stale
``docs/KNOBS.md`` (generated from this registry by
``python -m tools.lint --write-docs``).

Deliberately import-light (stdlib only): the lint loads this file
standalone via importlib, and ``sparkdl_tpu/__init__`` reads the
premapped-buffer knobs from here before any backend import.

Semantics shared by every accessor:

- unset (or, for numeric kinds, empty-string) values fall back to the
  declared default; a ``None`` default means "unset" is a meaningful
  state the owner handles (:func:`get_raw` exposes set-vs-unset).
- ``flag`` knobs are ON unless the effective value is empty, ``0`` or
  ``off`` — the house A/B-arm convention (``SPARKDL_ASYNC_READBACK=off``
  disables, ``SPARKDL_DEVICE_PREPROC=1`` enables).
- malformed numeric values raise ``ValueError`` naming the knob (a
  chaos run with a typo'd knob must fail loudly, not silently use
  defaults — the ``policy_from_env`` discipline); call sites that
  deliberately tolerate garbage (``SPARKDL_OBS_PORT``) catch it.
- ``choices`` is registry metadata for docs/lint; bespoke call-site
  validation keeps its tested error messages.
- accessors reject undeclared ``SPARKDL_*`` names with ``KeyError`` —
  the runtime side of the lint's drift check. Non-``SPARKDL_`` names
  pass through undeclared (shared helpers like ``policy_from_env``
  accept arbitrary prefixes in tests).

Adding a knob: declare it here (the owning module's section), read it
through an accessor, run ``python -m tools.lint --write-docs``, and
commit the regenerated ``docs/KNOBS.md`` (the checklist lives in
docs/ARCHITECTURE.md).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

_KINDS = ("int", "float", "flag", "str")

#: Guards REGISTRY. Declarations run at import time today, but the
#: registry is process-global mutable state like the feeder/obs tables,
#: and the concurrency lint holds every such table to the same rule:
#: mutations only under the lock. (Deliberately a raw threading.Lock,
#: not a locksmith proxy — locksmith reads its knobs from here.)
_registry_lock = threading.Lock()


@dataclass(frozen=True)
class Knob:
    """One declared env knob. ``default`` is the raw string an unset env
    var behaves as (``None`` = genuinely unset); ``family`` marks knobs
    whose names are composed dynamically from a shared prefix (the retry
    suites, the per-class p95 targets) so the lint's liveness check can
    match the prefix instead of the full name."""

    name: str
    kind: str
    default: Optional[str]
    doc: str
    owner: str
    choices: Optional[Tuple[str, ...]] = None
    family: Optional[str] = None


#: name -> Knob. Populated by the declare() calls below; the lint loads
#: this module standalone and walks this dict.
REGISTRY: Dict[str, Knob] = {}


def declare(
    name: str,
    kind: str,
    default: Optional[str],
    doc: str,
    owner: str,
    choices: Optional[Tuple[str, ...]] = None,
    family: Optional[str] = None,
) -> None:
    if not name.startswith("SPARKDL_"):
        raise ValueError(f"knob {name!r} must start with SPARKDL_")
    if kind not in _KINDS:
        raise ValueError(f"knob {name}: kind {kind!r} not in {_KINDS}")
    if name in REGISTRY:
        raise ValueError(f"knob {name} declared twice")
    if default is not None and not isinstance(default, str):
        raise ValueError(
            f"knob {name}: default must be the raw env string, got "
            f"{default!r}"
        )
    with _registry_lock:
        REGISTRY[name] = Knob(
            name, kind, default, doc, owner, choices, family
        )


def _knob(name: str) -> Optional[Knob]:
    k = REGISTRY.get(name)
    if k is None and name.startswith("SPARKDL_"):
        raise KeyError(
            f"{name} is not a declared knob — declare it in "
            "sparkdl_tpu/runtime/knobs.py (python -m tools.lint enforces "
            "this)"
        )
    return k


def get_raw(name: str) -> Optional[str]:
    """The env value as set, or None when unset — NO default applied.
    For owners that key caches on the raw environment
    (``dispatch_env_key``) or treat set-vs-unset as meaningful
    (``feed_plan``'s platform-conditional chunk default)."""
    _knob(name)
    return os.environ.get(name)


def get_str(name: str) -> Optional[str]:
    """String value with the declared default applied (may be None)."""
    k = _knob(name)
    v = os.environ.get(name)
    if v is None:
        return k.default if k is not None else None
    return v


def _effective(name: str) -> Optional[str]:
    """Raw-or-default with numeric-kind empty-string treated as unset
    (the ``int(env or 4)`` idiom several sites relied on)."""
    k = _knob(name)
    v = os.environ.get(name)
    if v is None or v == "":
        return k.default if k is not None else None
    return v


def get_int(name: str) -> Optional[int]:
    raw = _effective(name)
    if raw is None or raw == "":
        return None
    try:
        return int(raw)
    except ValueError:
        pass
    try:
        f = float(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not numeric") from None
    # is_integer() is False for inf/nan too — int(f) on those would
    # escape as OverflowError past every except-ValueError caller
    if not f.is_integer():
        raise ValueError(f"{name}={raw!r} is not an integer")
    return int(f)


def get_float(name: str) -> Optional[float]:
    raw = _effective(name)
    if raw is None or raw == "":
        return None
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not numeric") from None


def get_port(name: str) -> Optional[int]:
    """A TCP port knob: positive int, or None when unset/``0``/invalid
    (0 means "off" for every port knob here; an ephemeral bind must be
    asked for in code, and a malformed port reads as off rather than
    crashing telemetry startup). The one parse shared by the obs
    exporter and the serving HTTP server."""
    try:
        port = get_int(name)
    except ValueError:
        return None
    if port is None or port <= 0:
        return None
    return port


def get_flag(name: str) -> bool:
    """True unless the effective value is unset, empty, ``0`` or
    ``off`` — so a flag's default is just ``"1"`` (on) or ``"0"``/None
    (off)."""
    k = _knob(name)
    v = os.environ.get(name)
    if v is None:
        v = k.default if k is not None else None
    return v is not None and v not in ("", "0", "off")


# ---------------------------------------------------------------------------
# Declarations, grouped by owning module. Keep each group beside its
# neighbors in the import graph; docs/KNOBS.md renders them sorted.
# ---------------------------------------------------------------------------

# -- host->device transfer + device-side staging (runtime/transfer.py) ------
declare(
    "SPARKDL_H2D_CHUNK_MODE", "str", "serial",
    "how a multi-chunk H2D transfer issues its puts: one device_put per "
    "chunk sequentially, ONE list-form device_put, or a thread pool",
    "runtime/transfer.py", choices=("serial", "onecall", "threads"),
)
declare(
    "SPARKDL_H2D_THREADS", "int", "4",
    "chunked-put fan-out pool workers ('threads' chunk mode)",
    "runtime/transfer.py",
)
declare(
    "SPARKDL_DEVICE_STAGE", "flag", "1",
    "staged H2D: the feeder hands each packed batch to the staging copy "
    "pool at pack time; 0/off restores transfer-inside-dispatch (A/B arm)",
    "runtime/transfer.py",
)
declare(
    "SPARKDL_DEVICE_STAGE_DEPTH", "int", "2",
    "staged copies riding ahead of dispatch (2 = classic double "
    "buffering); read at feeder construction — sizes the buffer ring",
    "runtime/transfer.py",
)
declare(
    "SPARKDL_DEVICE_STAGE_THREADS", "int", "2",
    "staging copy-pool workers (separate from SPARKDL_H2D_THREADS: a "
    "staged transfer in 'threads' mode fans puts into that pool)",
    "runtime/transfer.py",
)

# -- feed strategy (graph/function.py, transformers/execution.py) -----------
declare(
    "SPARKDL_H2D_CHUNK_MB", "int", "4",
    "H2D chunk size in MB, kept under the ~4-8 MB fast-path threshold; "
    "0 disables chunking; unset resolves platform-aware in feed_plan "
    "(4 on single-device TPU, off elsewhere)",
    "transformers/execution.py",
)
declare(
    "SPARKDL_H2D_FUSE", "str", "",
    "fused chunked feed: 'implicit' (chunk views straight to dispatch) "
    "or 'put' (one list-form device_put + dispatch); empty/0/off "
    "disables",
    "transformers/execution.py",
    choices=("", "0", "off", "implicit", "put"),
)
declare(
    "SPARKDL_PARAM_PLACEMENT", "str", "closure",
    "'chunked' pre-places the params pytree on device with every "
    "transfer sub-threshold; 'closure' (default) lets jit capture params",
    "graph/function.py", choices=("", "closure", "chunked"),
)
declare(
    "SPARKDL_DONATE_INPUT", "flag", "1",
    "flat-input buffer donation in jitted_flat/jitted_flat_parts "
    "(engages only where the backend implements donation — TPU/GPU)",
    "graph/function.py",
)
declare(
    "SPARKDL_PREFETCH_PER_DEVICE", "int", "2",
    "in-flight batches per device in the batched engine (more overlap, "
    "more HBM held by input+output buffers)",
    "runtime/feeder.py",
)
declare(
    "SPARKDL_INFERENCE_DEVICES", "int", None,
    "cap on local devices used for data-parallel inference; unset = all "
    "local devices; 1 restores single-device (parity tests)",
    "transformers/execution.py",
)
declare(
    "SPARKDL_INFERENCE_MODE", "str", "shard_map",
    "batch spread over the local pool: one mesh-sharded SPMD program "
    "('shard_map') or per-device round-robin dispatch ('roundrobin')",
    "transformers/execution.py", choices=("roundrobin", "shard_map"),
)
declare(
    "SPARKDL_DEVICE_PREPROC", "flag", "0",
    "move image resize+normalize INSIDE the jitted program (host ships "
    "source-geometry uint8 rows); opt-in A/B arm",
    "transformers/execution.py",
)

# -- SQL planner (sql.py) ---------------------------------------------------
declare(
    "SPARKDL_SQL_VECTORIZE", "flag", "1",
    "SQL optimizer arm: catalog model UDFs dispatch batched through the "
    "shared DeviceFeeder and the planner applies projection/predicate "
    "pushdown; 0/off restores the legacy row-path planner (A/B arm)",
    "sql.py",
)

# -- readback + compile cache + native bridge (runtime/) --------------------
declare(
    "SPARKDL_ASYNC_READBACK", "flag", "1",
    "dispatch-time D2H copy + dedicated drainer thread in both dispatch "
    "paths; 0/off restores the synchronous legacy drain (A/B arm)",
    "runtime/readback.py",
)
declare(
    "SPARKDL_TPU_NO_NATIVE", "flag", None,
    "skip building/loading the native imagebridge extension (pure-python "
    "fallback)",
    "runtime/native.py",
)
declare(
    "SPARKDL_LOCK_SANITIZER", "flag", "0",
    "runtime lock sanitizer: order-recording lock proxies build the "
    "observed held-before graph, detect cycles and long holds live, and "
    "cross-check against the static graph (read at lock creation)",
    "runtime/locksmith.py",
)
declare(
    "SPARKDL_LOCK_HELD_MS", "float", "500",
    "sanitizer threshold: a lock held longer than this at release is "
    "recorded as locks.held_too_long",
    "runtime/locksmith.py",
)

# -- shared device feeder (runtime/feeder.py) -------------------------------
declare(
    "SPARKDL_MAX_FEEDERS", "int", "8",
    "feeder-registry LRU cap; serving deployments raise it (model x "
    "rung x geometry populations) to avoid owner-thread respawn churn",
    "runtime/feeder.py",
)
declare(
    "SPARKDL_FEEDER_LINGER_MS", "float", "20",
    "quiet-period wait before the padded tail flush",
    "runtime/feeder.py",
)
declare(
    "SPARKDL_FEEDER_IDLE_S", "float", "30",
    "idle owner threads exit after this many seconds; 0 (or negative) = "
    "never exit — the serving keepalive",
    "runtime/feeder.py",
)

# -- gang worker (worker.py) ------------------------------------------------
declare(
    "SPARKDL_GANG_GENERATION", "int", None,
    "this incarnation's gang generation; exported by the supervisor on "
    "every (re)launch, rides heartbeats and fault coordinates",
    "worker.py",
)
declare(
    "SPARKDL_GANG_RESUME", "flag", None,
    "workers verify+skip already-published partition outputs; the "
    "supervisor sets it for generations > 0",
    "worker.py",
)

# -- flight recorder + fleet telemetry (obs/) -------------------------------
declare(
    "SPARKDL_OBS", "flag", "1",
    "span tracing; 0 turns spans into shared no-ops (call-site aggregate "
    "timers keep flowing) and disables the sampler",
    "obs/spans.py",
)
declare(
    "SPARKDL_OBS_RING", "int", "4096",
    "flight-recorder ring-buffer depth in spans; oldest fall off",
    "obs/spans.py",
)
declare(
    "SPARKDL_OBS_SAMPLE_S", "float", "1",
    "time-series sampling interval, seconds; 0 disables the sampler",
    "obs/timeseries.py",
)
declare(
    "SPARKDL_OBS_SERIES", "int", "720",
    "points kept per metric series; oldest fall off",
    "obs/timeseries.py",
)
declare(
    "SPARKDL_OBS_JSONL", "str", None,
    "append-only JSONL event log (samples, dump notices, gate verdicts) "
    "— the headless-campaign data plane",
    "obs/export.py",
)
declare(
    "SPARKDL_OBS_DUMP_DIR", "str", None,
    "failure edges flush the ring buffer to obs-<reason>-<stamp>.json "
    "here; unset = failure paths stay write-free",
    "obs/export.py",
)
declare(
    "SPARKDL_OBS_RANK", "int", None,
    "tags snapshots/JSONL events with the gang rank; set by the worker "
    "entrypoint around each run",
    "obs/export.py",
)
declare(
    "SPARKDL_OBS_SNAP_S", "float", "30",
    "min seconds between a rank's periodic snapshot drops; 0 disables "
    "(exit drops still forced)",
    "obs/aggregate.py",
)
declare(
    "SPARKDL_OBS_STRAGGLER_X", "float", "1.5",
    "slowest-vs-median per-span p95 factor that flags a straggler stage",
    "obs/aggregate.py",
)
declare(
    "SPARKDL_OBS_STRAGGLER_MIN_S", "float", "0.1",
    "absolute slowest-minus-median gap (seconds) also required to flag "
    "a straggler",
    "obs/aggregate.py",
)
declare(
    "SPARKDL_OBS_PORT", "int", None,
    "HTTP exporter port (gang rank r binds port+r); unset/0/invalid = "
    "off",
    "obs/serve.py",
)
declare(
    "SPARKDL_OBS_BIND", "str", "127.0.0.1",
    "exporter bind address; endpoints are unauthenticated, so 0.0.0.0 "
    "is an explicit operator choice",
    "obs/serve.py",
)
declare(
    "SPARKDL_TRACE_SAMPLE", "float", "0.01",
    "head-sampling rate for request traces (deterministic per trace "
    "id, clamped [0,1]); tail exemplars store regardless",
    "obs/trace.py",
)
declare(
    "SPARKDL_TRACE_RING", "int", "512",
    "trace ids retained per process; oldest unpinned fall off "
    "(exemplar-pinned traces survive eviction)",
    "obs/trace.py",
)
declare(
    "SPARKDL_TRACE_EXEMPLARS", "int", "4",
    "slowest completions kept per serve.latency class as tail "
    "exemplars (their traces pin in the store)",
    "obs/trace.py",
)
declare(
    "SPARKDL_SLO_AVAIL", "float", None,
    "availability SLO target in (0,1) applied to every SLA class "
    "unless a per-class override is set (failures/expiries/admission "
    "rejections spend the 1-target error budget); unset = objective "
    "unarmed",
    "obs/slo.py",
    family="SPARKDL_SLO_AVAIL",
)
for _cls in ("INTERACTIVE", "BATCH", "BACKGROUND"):
    declare(
        f"SPARKDL_SLO_AVAIL_{_cls}", "float", None,
        f"availability SLO target for the {_cls.lower()} SLA class "
        "(overrides SPARKDL_SLO_AVAIL; an explicit 0 disarms this "
        "class under a global target)",
        "obs/slo.py",
        family="SPARKDL_SLO_AVAIL",
    )
declare(
    "SPARKDL_SLO_P95_MS", "float", None,
    "latency SLO: p95 target in milliseconds applied to every SLA "
    "class unless a per-class override is set (a completion slower "
    "than the target spends the 5% tail budget); unset = objective "
    "unarmed",
    "obs/slo.py",
    family="SPARKDL_SLO_P95_MS",
)
for _cls in ("INTERACTIVE", "BATCH", "BACKGROUND"):
    declare(
        f"SPARKDL_SLO_P95_MS_{_cls}", "float", None,
        f"p95 latency SLO target for the {_cls.lower()} SLA class, "
        "milliseconds (overrides SPARKDL_SLO_P95_MS; an explicit 0 "
        "disarms this class under a global target)",
        "obs/slo.py",
        family="SPARKDL_SLO_P95_MS",
    )
declare(
    "SPARKDL_SLO_FAST_S", "float", "60",
    "fast burn-rate window, seconds (the 'is it bad RIGHT NOW' half "
    "of the multi-window pair; smokes/tests scale it down)",
    "obs/slo.py",
)
declare(
    "SPARKDL_SLO_SLOW_S", "float", "3600",
    "slow burn-rate window, seconds (the 'is it SUSTAINED' half; "
    "floored at the fast window)",
    "obs/slo.py",
)
declare(
    "SPARKDL_SLO_BURN_FAST", "float", "14",
    "burn-rate threshold the FAST window must reach to trip an SLO "
    "alert (14 = the classic 'exhausts a 30-day budget in ~2 days' "
    "pager line)",
    "obs/slo.py",
)
declare(
    "SPARKDL_SLO_BURN_SLOW", "float", "14",
    "burn-rate threshold the SLOW window must ALSO reach to trip "
    "(both windows burning = sustained, not a blip)",
    "obs/slo.py",
)
declare(
    "SPARKDL_SLO_MIN_REQUESTS", "int", "10",
    "fast-window event floor below which a trip is never evaluated "
    "(one bad request over a tiny sample is arithmetic, not an outage)",
    "obs/slo.py",
)

# -- sequence-bucketed text engine (sparkdl_tpu/text/) ----------------------
declare(
    "SPARKDL_TEXT_BUCKETING", "flag", "1",
    "length-aware text path: tokenized rows route to per-bucket feeder "
    "geometries padded to the bucket edge (offline TextEmbedder AND the "
    "serving router's token payloads); 0/off restores pad-to-maxLength "
    "(A/B arm)",
    "text/bucketing.py",
)
declare(
    "SPARKDL_TEXT_BUCKETS", "str", "half",
    "bucket ladder: 'pow2' (powers of two; worst-case ~25% pad on "
    "uniform lengths), 'half' (powers of two + 3*2^k midpoints; "
    "worst-case ~15%), or an explicit comma list of edges ('32,48,64')",
    "text/bucketing.py",
)
declare(
    "SPARKDL_TEXT_MIN_BUCKET", "int", "16",
    "smallest bucket edge elected; shorter rows pad up to it (tiny "
    "buckets multiply compiled programs for negligible pad savings)",
    "text/bucketing.py",
)

# -- models (models/) -------------------------------------------------------
declare(
    "SPARKDL_TPU_MODEL_CACHE", "str", None,
    "model-artifact store directory; unset = ~/.cache/sparkdl_tpu/models "
    "(resolved at the call site)",
    "models/fetcher.py",
)

# -- dataframe driver guard (dataframe/frame.py) ----------------------------
declare(
    "SPARKDL_DRIVER_COLLECT_MAX_ROWS", "int", "5000000",
    "fail-fast row cap for driver-side relational actions "
    "(orderBy/join collect); 0 disables the guard",
    "dataframe/frame.py",
)

# -- online serving (serving/) ----------------------------------------------
declare(
    "SPARKDL_SERVE_MAX_BATCH", "int", "32",
    "full batch geometry per serving dispatch — the throughput-mode rung",
    "serving/router.py",
)
declare(
    "SPARKDL_SERVE_WINDOW_MS", "float", "2",
    "how long a partially-filled request group may wait for late "
    "arrivals, milliseconds",
    "serving/router.py",
)
declare(
    "SPARKDL_SERVE_TARGET_P95_MS", "float", None,
    "latency objective applied to every SLA class unless a per-class "
    "override is set; unset = built-in per-class defaults (50/500/5000)",
    "serving/router.py",
    family="SPARKDL_SERVE_TARGET_P95_MS",
)
for _cls in ("INTERACTIVE", "BATCH", "BACKGROUND"):
    declare(
        f"SPARKDL_SERVE_TARGET_P95_MS_{_cls}", "float", None,
        f"p95 latency objective for the {_cls.lower()} SLA class, "
        "milliseconds (overrides SPARKDL_SERVE_TARGET_P95_MS)",
        "serving/router.py",
        family="SPARKDL_SERVE_TARGET_P95_MS",
    )
declare(
    "SPARKDL_SERVE_WORKERS", "int", "4",
    "completion-worker pool size (also bounds popped-but-unfinished "
    "request groups)",
    "serving/router.py",
)
declare(
    "SPARKDL_SERVE_DISPATCH_TIMEOUT_S", "float", "120",
    "hard bound on one group's device wait: a wedged backend fails "
    "requests loudly instead of hanging completion workers",
    "serving/router.py",
)
declare(
    "SPARKDL_SERVE_AGING_S", "float", "5",
    "seconds of queue age that promote a request one SLA class level; "
    "<=0 disables aging",
    "serving/request.py",
)
declare(
    "SPARKDL_SERVE_QUEUE_CAP", "int", "4096",
    "admission bound in ROWS (rows, not requests: one giant background "
    "submit can't squeeze out a thousand interactive ones)",
    "serving/request.py",
)
declare(
    "SPARKDL_SERVE_PORT", "int", None,
    "HTTP serving port; unset/0/invalid = off (an ephemeral bind must "
    "be asked for in code)",
    "serving/server.py",
)
declare(
    "SPARKDL_SERVE_BIND", "str", "127.0.0.1",
    "serving bind address; the predict endpoint is unauthenticated, so "
    "exposure is an explicit operator choice",
    "serving/server.py",
)
declare(
    "SPARKDL_SERVE_HTTP_TIMEOUT_S", "float", "300",
    "HTTP handler's bound on one request's end-to-end result wait",
    "serving/server.py",
)
declare(
    "SPARKDL_PROFILE_DIR", "str", None,
    "directory POST /admin/profile captures land in (one timestamped "
    "run dir per capture); unset = a sparkdl_profile_* temp dir",
    "serving/server.py",
)
declare(
    "SPARKDL_SERVE_HBM_BUDGET_MB", "float", None,
    "residency HBM budget in megabytes; unset/0 = unbounded "
    "(single-model deployments); malformed values raise",
    "serving/residency.py",
)
declare(
    "SPARKDL_SERVE_RETRY_AFTER_S", "float", "1",
    "Retry-After header value (seconds) on 429 admission-rejected and "
    "503 draining responses — the client back-off hint",
    "serving/server.py",
)
declare(
    "SPARKDL_SERVE_DRAIN_TIMEOUT_S", "float", "30",
    "worker drain bound: how long a SIGTERM'd serving worker waits for "
    "queued + in-flight requests to complete before exiting anyway",
    "serving/__main__.py",
)
declare(
    "SPARKDL_SERVE_CANARY_MODEL", "str", None,
    "base model name whose traffic is canary-split; unset = no canary "
    "(both _MODEL and _VERSION must be set to engage)",
    "serving/router.py",
)
declare(
    "SPARKDL_SERVE_CANARY_VERSION", "str", None,
    "canary model version (a registry/loader name) that receives "
    "SPARKDL_SERVE_CANARY_WEIGHT of the base model's requests",
    "serving/router.py",
)
declare(
    "SPARKDL_SERVE_CANARY_WEIGHT", "float", "0.1",
    "fraction [0,1] of the canaried model's requests routed to the "
    "canary version (deterministic Bresenham split over admissions)",
    "serving/router.py",
)
declare(
    "SPARKDL_SERVE_CANARY_TRIP_RATE", "float", "0.5",
    "canary failure-rate threshold that trips automatic rollback "
    "(subsequent requests route to the base version)",
    "serving/router.py",
)
declare(
    "SPARKDL_SERVE_CANARY_MIN_REQUESTS", "int", "20",
    "canary requests observed before the rollback trip is evaluated "
    "(a first-request failure must not condemn the version)",
    "serving/router.py",
)
declare(
    "SPARKDL_SERVE_CANARY_WAVES", "str", None,
    "comma-separated canary weight schedule (e.g. '0.05,0.25,1.0') the "
    "gateway's wave controller advances through, one wave per dwell, "
    "only while the canary arm stays healthy fleet-wide; unset = no "
    "wave controller (the static SPARKDL_SERVE_CANARY_WEIGHT applies)",
    "serving/gateway.py",
)
declare(
    "SPARKDL_SERVE_CANARY_WAVE_S", "float", "10",
    "canary wave dwell: how long the wave controller holds each weight "
    "rung (and re-checks burn/trip health) before widening to the next",
    "serving/gateway.py",
)
declare(
    "SPARKDL_SERVE_MESH_WIDTH", "int", None,
    "serving mesh width: chips one mesh-elected model's global batches "
    "fan out over (data-parallel NamedSharding program); unset = every "
    "local inference device, 1 = single-chip programs, capped at the "
    "local pool",
    "transformers/execution.py",
)
declare(
    "SPARKDL_SERVE_PRECISION", "str", "f32",
    "serving compute-precision rung applied to every SLA class unless "
    "a per-class override is set: f32 (the baseline arm), bf16 "
    "(half-width params + bf16 compute), or int8-dynamic (weight-only "
    "dynamic int8 quantization)",
    "graph/precision.py",
    choices=("f32", "bf16", "int8-dynamic"),
    family="SPARKDL_SERVE_PRECISION",
)
for _cls in ("INTERACTIVE", "BATCH", "BACKGROUND"):
    declare(
        f"SPARKDL_SERVE_PRECISION_{_cls}", "str", None,
        f"precision rung for the {_cls.lower()} SLA class "
        "(overrides SPARKDL_SERVE_PRECISION)",
        "graph/precision.py",
        choices=("f32", "bf16", "int8-dynamic"),
        family="SPARKDL_SERVE_PRECISION",
    )

# -- autoregressive generation (serving/generation.py) ----------------------
declare(
    "SPARKDL_GEN_MAX_SEQS", "int", "8",
    "decode-batch slot count per generation stream: how many sequences "
    "one continuous-batching decode step advances together (the "
    "token-level analogue of SPARKDL_SERVE_MAX_BATCH)",
    "serving/generation.py",
)
declare(
    "SPARKDL_GEN_MAX_NEW_TOKENS", "int", "64",
    "default AND cap for a generate request's max_new_tokens: the "
    "per-sequence KV charge (kv_bytes_per_token x (prompt + new)) is "
    "budgeted against SPARKDL_SERVE_HBM_BUDGET_MB at admission",
    "serving/generation.py",
)

# -- serving gateway (serving/gateway.py) -----------------------------------
declare(
    "SPARKDL_GATEWAY_WORKERS", "int", "2",
    "serving-gang size: how many supervised worker processes the "
    "gateway launches and routes across",
    "serving/gateway.py",
)
declare(
    "SPARKDL_GATEWAY_HEALTH_S", "float", "0.25",
    "gateway health-poll interval: how often each worker's port file + "
    "/healthz is probed for readiness/draining transitions",
    "serving/gateway.py",
)
declare(
    "SPARKDL_GATEWAY_PENDING_S", "float", "30",
    "how long a gateway request waits for a READY worker (covers the "
    "supervisor's kill -> backoff -> relaunch window) before 503",
    "serving/gateway.py",
)
declare(
    "SPARKDL_GATEWAY_FORWARD_TIMEOUT_S", "float", "300",
    "per-attempt bound on one forwarded request's worker response",
    "serving/gateway.py",
)
declare(
    "SPARKDL_GATEWAY_AFFINITY", "flag", "0",
    "model-affinity routing: consistent-hash each predict's placement "
    "key (model, precision, mesh) onto the ready-worker ring so every "
    "worker holds only its shard of the model catalog; off = the "
    "round-robin cursor (the byte-identical legacy path)",
    "serving/gateway.py",
)
declare(
    "SPARKDL_GATEWAY_AFFINITY_REPLICAS", "int", "64",
    "virtual nodes per rank on the affinity hash ring: more replicas "
    "= smoother key spread per rank at a linearly bigger ring",
    "serving/gateway.py",
)
declare(
    "SPARKDL_GATEWAY_SPILL_BUSY", "float", "0.9",
    "scraped util.busy_frac at or above which an affinity-preferred "
    "rank counts as saturated and its keys spill to the next ring "
    "position (draining/down ranks always spill)",
    "serving/gateway.py",
)

# -- fleet observability plane (obs/fleet.py) -------------------------------
declare(
    "SPARKDL_FLEET_SCRAPE_S", "float", "1.0",
    "gateway fleet-scrape cadence: how often each READY worker's "
    "/metrics + /v1/slo + /v1/models surfaces are pulled and fused "
    "into the fleet view",
    "obs/fleet.py",
)
declare(
    "SPARKDL_FLEET_SCRAPE_TIMEOUT_S", "float", "2.0",
    "per-worker bound on one fleet-scrape pull (each of the three "
    "endpoint reads individually) — a hung worker degrades to a stale "
    "sample instead of stalling the scrape cycle",
    "obs/fleet.py",
)
declare(
    "SPARKDL_FLEET_STALE_S", "float", "10.0",
    "age past which a rank's last-good fleet sample is marked stale "
    "and excluded from fleet aggregates/SLO fusion (its silence must "
    "not fabricate or mask a fleet alert)",
    "obs/fleet.py",
)
declare(
    "SPARKDL_FLEET_RECOMMEND_S", "float", "10.0",
    "advisory-recommender cadence: how often the fleet policy "
    "re-derives its scale-up/down/rebalance recommendation from the "
    "fused view (JSONL only — it actuates nothing)",
    "obs/fleet.py",
)
declare(
    "SPARKDL_FLEET_RING", "int", "360",
    "bounded fleet-sample history ring capacity (trend lines for "
    "`obs fleet` / the report) — at the default 1 s scrape cadence, "
    "six minutes of history",
    "obs/fleet.py",
)
declare(
    "SPARKDL_FLEET_SCALE_UP_BUSY", "float", "0.8",
    "fleet busy-fraction at or above which the advisory recommender "
    "suggests scale_up (also suggested on any fleet SLO trip)",
    "obs/fleet.py",
)
declare(
    "SPARKDL_FLEET_SCALE_DOWN_BUSY", "float", "0.2",
    "fleet busy-fraction at or below which the advisory recommender "
    "suggests scale_down (only with no fleet SLO alert active and "
    "more than one ready worker)",
    "obs/fleet.py",
)
declare(
    "SPARKDL_FLEET_AUTOSCALE", "flag", "0",
    "promote the fleet recommender from advisory to ACTUATING: "
    "scale_up/scale_down verdicts become GangSupervisor.resize() calls "
    "(each actuation logged as a {\"kind\": \"fleet_scale\"} JSONL "
    "event carrying the evidence it fired on)",
    "serving/gateway.py",
)
declare(
    "SPARKDL_FLEET_COOLDOWN_S", "float", "30",
    "autoscaler hysteresis: minimum seconds between two resize "
    "actuations, so one burst can't see-saw the gang",
    "serving/gateway.py",
)
declare(
    "SPARKDL_FLEET_MIN_WORKERS", "int", "1",
    "autoscaler floor: scale_down never shrinks the gang below this "
    "many workers",
    "serving/gateway.py",
)
declare(
    "SPARKDL_FLEET_MAX_WORKERS", "int", "4",
    "autoscaler ceiling: scale_up never grows the gang past this many "
    "workers",
    "serving/gateway.py",
)

# -- device-memory observability plane (obs/memory.py) ----------------------
declare(
    "SPARKDL_MEM_RING", "int", "256",
    "allocation-event ring depth in the memory ledger; the tail rides "
    "every `{\"kind\": \"oom\"}` forensic event",
    "obs/memory.py",
)
declare(
    "SPARKDL_MEM_WATERMARK_RING", "int", "512",
    "bounded memory-watermark history ring capacity (trend lines for "
    "`obs mem` / the report); one sample per watermark advance",
    "obs/timeseries.py",
)
declare(
    "SPARKDL_MEM_LEAK_TOL_MB", "float", "8",
    "ground-truth slack (megabytes) an evict/unload may leave behind "
    "before the ledger counts it leaked — generous by default because "
    "the CPU fallback sizes jax.live_arrays(), where jit-cache "
    "constants and GC timing add real noise",
    "obs/memory.py",
)

# -- deterministic fault injection (resilience/faults.py) -------------------
declare(
    "SPARKDL_FAULT_PLAN", "str", None,
    "arm deterministic fault injection at the named hook points "
    "(grammar: docs/RESILIENCE.md); unset = every hook is a no-op",
    "resilience/faults.py",
)
declare(
    "SPARKDL_FAULT_STATE", "str", None,
    "directory for cross-process/generation fault `times` claims "
    "(per-process counts otherwise)",
    "resilience/faults.py",
)
declare(
    "SPARKDL_FAULT_SEED", "int", "0",
    "seed for probabilistic (p=) fault rules",
    "resilience/faults.py",
)

# -- retry-policy families (resilience/policy.py adopters) ------------------
# policy_from_env(prefix) composes <PREFIX>_<SUFFIX> dynamically; each
# adopter's literal prefix at its call site keeps the family live for
# the lint. Defaults are None on purpose: the adopter's policy defaults
# (executor max_failures, fetcher 3 attempts, ...) are its own.
for _prefix, _adopter, _what in (
    ("SPARKDL_EXEC_RETRY", "runtime/executor.py",
     "executor partition retry backoff"),
    ("SPARKDL_FETCH_RETRY", "models/fetcher.py",
     "model-artifact download retries"),
    ("SPARKDL_SERVE_RETRY", "serving/router.py",
     "serving dispatch retry (transient residency/device errors)"),
    ("SPARKDL_GATEWAY_RETRY", "serving/gateway.py",
     "gateway re-dispatch budget (requests stranded on a dead or "
     "draining worker hedge onto another)"),
    ("SPARKDL_SUPERVISOR_RETRY", "resilience/supervisor.py",
     "gang restart budget (attempts = 1 launch + N restarts)"),
):
    for _suffix, _kind, _doc in (
        ("ATTEMPTS", "int", "max attempts, first try included"),
        ("BASE_MS", "float", "base backoff delay, milliseconds"),
        ("MAX_MS", "float", "backoff delay cap, milliseconds"),
        ("DEADLINE_S", "float", "whole-loop deadline, seconds"),
        ("SEED", "int", "deterministic jitter seed"),
    ):
        declare(
            f"{_prefix}_{_suffix}", _kind, None,
            f"{_what}: {_doc}",
            _adopter, family=_prefix,
        )
