"""Request router: SLA-classed continuous batching over feeder streams.

The dataflow shape (TensorFlow's input-pipeline decoupling, the
geometry-keyed compiled programs of TPU full-compilation) applied to the
online path: requests are admitted into ONE class-aware queue
(``request.py``), a dispatcher thread groups them by
``(model, mode, row shape, dtype)``, and each group rides the existing
shared-feeder machinery — ``get_feeder`` keyed by ``(device_fn,
dispatch geometry)`` gives one compiled program + one owner thread per
(model, batch-size rung), exactly the per-(model, geometry) stream model
of the batch engine, reused unchanged.

**Adaptive batch sizing** is the router's core policy. Each dispatch
uses a batch-size *rung* — the smallest power of two covering the rows
on hand, capped at ``SPARKDL_SERVE_MAX_BATCH`` — so:

- shallow queue -> a request dispatches immediately at a short rung
  (latency mode: a 1-row interactive request runs a 1-row program, not
  a 32-row one padded 97%);
- deep queue -> groups assemble to the full geometry before dispatch
  (throughput mode: the chip sees full batches, padding ~0).

Between those regimes a small **batch window**
(``SPARKDL_SERVE_WINDOW_MS``) lets a partially-full group linger for
late arrivals — but only while the group's strictest class is UNDER its
target p95 (``SPARKDL_SERVE_TARGET_P95_MS[_<CLASS>]``, observed from a
recent-completion window — see ``request.recent_p95_s``): once the SLA
is threatened the router stops trading latency for fill. Every dispatch records its rung
into ``serve.batch_rows`` (min = the latency-mode floor, max = the
full geometry under load — the smoke asserts both).

Submitting a group pads it to an exact multiple of the rung geometry, so
the feeder's buffer FILLS and flushes immediately — serving never waits
out the batch path's quiet-period linger. Padding is counted
(``serve.pad_rows``); discarded pad outputs are never returned.

Failure handling rides the resilience layer: each group dispatch runs
under a RetryPolicy (``SPARKDL_SERVE_RETRY_*`` knobs) so a transient
device error retries before failing the requests, and
``maybe_fault("serve.request", request=<admission ordinal>, ...)`` gives
chaos plans a per-request hook (``SPARKDL_FAULT_PLAN=
"site=serve.request:request=3:raise=RuntimeError"`` fails exactly the
fourth admitted request while its groupmates complete).

Two gang-lifecycle features live here too (docs/RESILIENCE.md):

- **graceful drain** (:meth:`Router.drain`): admission closes
  (:class:`~sparkdl_tpu.serving.request.Draining` -> HTTP 503 +
  ``Retry-After``) while everything already admitted completes; once
  queue + in-flight quiesce, resident models unload and their feeder
  streams close (``close_feeders_for``). A SIGTERM'd serving worker
  drains before exiting, so a supervisor-killed gang loses no accepted
  request the worker could still answer.
- **canary rollout**: when ``SPARKDL_SERVE_CANARY_MODEL`` /
  ``_VERSION`` are set, a deterministic Bresenham split routes
  ``SPARKDL_SERVE_CANARY_WEIGHT`` of the base model's admissions to
  the canary version (a separate ResidencyManager-backed model), with
  per-arm ``serve.canary.*`` / ``serve.primary.*`` latency + failure
  metrics. A canary whose failure rate reaches
  ``SPARKDL_SERVE_CANARY_TRIP_RATE`` (after ``_MIN_REQUESTS``
  observations) trips an automatic **rollback**: later requests route
  to the base version and a ``{"kind": "canary_rollback"}`` JSONL
  event records the decision.
"""

from __future__ import annotations

import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional

import numpy as np

from sparkdl_tpu.obs import span
from sparkdl_tpu.resilience.faults import maybe_fault
from sparkdl_tpu.resilience.policy import policy_from_env
from sparkdl_tpu.runtime import knobs, locksmith
from sparkdl_tpu.serving.request import (
    AdmissionQueue,
    AdmissionRejected,
    DeadlineExceeded,
    Draining,
    PRIORITY_CLASSES,
    Request,
)
from sparkdl_tpu.serving.residency import ResidencyManager
from sparkdl_tpu.utils.metrics import metrics

#: Per-class default target p95, milliseconds (override all with
#: SPARKDL_SERVE_TARGET_P95_MS, per class with _INTERACTIVE/_BATCH/...).
_DEFAULT_TARGET_P95_MS = {
    "interactive": 50.0,
    "batch": 500.0,
    "background": 5000.0,
}



def max_batch_rows() -> int:
    """Full batch geometry per dispatch (``SPARKDL_SERVE_MAX_BATCH``,
    default 32) — the throughput-mode rung."""
    return max(1, knobs.get_int("SPARKDL_SERVE_MAX_BATCH"))


def batch_window_s() -> float:
    """How long a partially-filled group may wait for late arrivals
    (``SPARKDL_SERVE_WINDOW_MS``, default 2)."""
    return max(0.0, knobs.get_float("SPARKDL_SERVE_WINDOW_MS")) / 1e3


def target_p95_s(priority: str) -> float:
    """The class's latency objective, seconds."""
    # precedence: per-class override, then the global target, then the
    # built-in class default — unset/0 at each level falls through
    for name in (
        f"SPARKDL_SERVE_TARGET_P95_MS_{priority.upper()}",
        "SPARKDL_SERVE_TARGET_P95_MS",
    ):
        target = knobs.get_float(name)
        if target:
            return target / 1e3
    return _DEFAULT_TARGET_P95_MS[priority] / 1e3


def observed_p95_s(priority: str) -> Optional[float]:
    """Observed p95 the batch window consults: the RECENT completion
    window (``request.recent_p95_s``), not the lifetime registry
    reservoir — cold-start load latencies age out of the signal and a
    fresh regression surfaces within one window."""
    from sparkdl_tpu.serving.request import recent_p95_s

    return recent_p95_s(priority)


def choose_rung(
    rows: int, max_rows: Optional[int] = None, mesh_width: int = 1
) -> int:
    """PER-CHIP batch-size rung for ``rows`` rows on hand: the smallest
    power of two >= each chip's share, clamped to the full geometry.
    Rung quantization keeps the compiled-program population per
    (model, row shape) at log2(max) + 1 instead of one program per
    observed group size.

    ``mesh_width``: chips one dispatch of this model's program engages
    (the device fn's ``batch_multiplier``). The cap scales with the
    mesh — ``max_rows`` stays the PER-CHIP ceiling, so a width-8 mesh
    dispatches global batches of up to ``8 * max_rows`` rows — and the
    chooser quantizes the per-chip share, so 100 rows on a width-4
    mesh run a 32-per-chip program (128 global, 28 pad), not a
    32-global one padded past 150. Width 1 is exactly the historical
    single-chip arithmetic."""
    cap = max_rows if max_rows is not None else max_batch_rows()
    width = max(1, int(mesh_width))
    per_chip = -(-max(1, int(rows)) // width)  # ceil-div: each chip's share
    if per_chip >= cap:
        return cap
    return min(cap, 1 << max(0, math.ceil(math.log2(per_chip))))


def canary_config() -> Optional[tuple]:
    """``(base_name_lower, canary_version, weight)`` when a canary
    rollout is configured (both ``SPARKDL_SERVE_CANARY_MODEL`` and
    ``_VERSION`` set), else None. Weight clamps to [0, 1]; the split is
    applied per admission by a deterministic Bresenham counter, so an
    N-request flood routes ``round(N * weight) ± 1`` requests to the
    canary — exact enough for the smoke's ratio assertion without an
    RNG anywhere in the path."""
    base = knobs.get_str("SPARKDL_SERVE_CANARY_MODEL")
    version = knobs.get_str("SPARKDL_SERVE_CANARY_VERSION")
    if not base or not version:
        return None
    weight = min(1.0, max(0.0, knobs.get_float("SPARKDL_SERVE_CANARY_WEIGHT")))
    return (base.lower(), version, weight)


def choose_seq_bucket(seq_len: int) -> int:
    """The sequence-length sibling of :func:`choose_rung`: the grid
    bucket a token payload of ``seq_len`` pads up to (uncapped here;
    ``_bucket_token_payload`` caps at the registry spec's position
    table and rejects over-long payloads at admission).
    Two rungs now quantize every text dispatch: batch rows (power of
    two up to the geometry) x sequence length (the configured text
    ladder grid), so nearby request lengths share one compiled program
    instead of compiling per observed length."""
    from sparkdl_tpu.text.bucketing import next_bucket

    return next_bucket(seq_len)


def _is_text_model(model: str) -> bool:
    """Whether ``model`` resolves to a registry text spec (a dict
    lookup, no build). Custom-loader models return False — for those,
    only an explicit ``mode="embed"`` engages token bucketing."""
    try:
        from sparkdl_tpu.models import NamedTextModel, get_model

        return isinstance(get_model(model), NamedTextModel)
    except ValueError:
        return False


def _bucket_token_payload(model: str, payload: np.ndarray):
    """Seq-bucket an ``embed``-mode token payload [rows, L] at
    admission: pad the sequence axis with id 0 (registry text models
    derive their mask on device as ``ids != 0``, so zero seq padding
    never changes a pooled embedding) up to :func:`choose_seq_bucket`'s
    edge. Runs BEFORE the Request is built, so the router's grouping
    key — which reads ``payload.shape[1:]`` — carries the bucket and
    nearby lengths coalesce into one feeder stream. int32-normalized:
    JSON token ids arrive int64 and must not fragment streams (or
    fight the model's int32 input) by dtype.

    For REGISTRY text models the spec's ``max_length`` (the position
    table) is the hard ceiling: an over-long payload raises
    ``ValueError`` (HTTP 400) — JAX clamps out-of-bounds position
    gathers, so dispatching it would return a silently wrong embedding
    (the offline builder refuses the same case) — and the bucket edge
    is capped at ``max_length`` so a coarse grid never pads a valid
    payload past the table. Custom-loader models (no registry spec)
    bucket uncapped; their model fn owns the ceiling.

    Returns ``(payload, real_tokens, pad_tokens)``; the caller counts
    the tokens only AFTER admission succeeds, so rejected submits
    never inflate the text counters."""
    if payload.ndim != 2:
        return payload, 0, 0
    max_len = None
    try:
        from sparkdl_tpu.models import get_model

        max_len = getattr(get_model(model), "max_length", None)
    except ValueError:
        pass  # custom-loader model: no registry spec to size against
    if not np.issubdtype(payload.dtype, np.integer):
        # JSON bodies default to float32; registry text models take
        # int32 token ids, and letting a float payload through would
        # silently skip BOTH the position-table guard and the seq
        # bucketing. Coerce integral floats (the omitted-"dtype" HTTP
        # case), reject real-valued ones loudly; payloads for
        # custom-loader models pass through untouched.
        if max_len is None:
            return payload, 0, 0
        if not np.all(np.mod(payload, 1) == 0):
            raise ValueError(
                f"model {model!r} expects integer token ids; got "
                f"non-integral {payload.dtype} values"
            )
    payload = payload.astype(np.int32, copy=False)
    rows, length = payload.shape
    if max_len is not None and length > max_len:
        raise ValueError(
            f"token payload length {length} exceeds model {model!r}'s "
            f"position table ({max_len})"
        )
    # Real tokens by the masking invariant itself (ids != 0), not the
    # payload width: a client that pre-pads its rows must not inflate
    # text.tokens/deflate pad_ratio relative to the offline path.
    real = int(np.count_nonzero(payload))
    if not knobs.get_flag("SPARKDL_TEXT_BUCKETING"):
        return payload, real, rows * length - real
    bucket = choose_seq_bucket(length)
    if max_len is not None:
        bucket = min(bucket, max_len)
    if bucket > length:
        payload = np.concatenate(
            [payload, np.zeros((rows, bucket - length), np.int32)], axis=1
        )
    return payload, real, rows * bucket - real


def _validate_generate(model: str, payload: np.ndarray, gen_params):
    """Admission-time screening of a generate request. Returns
    ``(payload [1, L] int32, prompt_len, params, kv_bytes)`` or raises
    ``ValueError`` (HTTP 400):

    - single sequence only (one admission = one decode slot);
    - integer token ids, like the embed path's coercion;
    - ``prompt_len + max_new_tokens`` must fit the spec's position
      table — JAX clamps out-of-bounds position gathers, so letting an
      over-long sequence through would return silently wrong tokens
      instead of an error (the same contract the embed path enforces);
    - ``max_new_tokens`` caps at ``SPARKDL_GEN_MAX_NEW_TOKENS`` (also
      its default), the bound the KV budget charge is computed from.
    """
    from sparkdl_tpu.models import NamedTextModel, get_model
    from sparkdl_tpu.serving.generation import max_new_tokens_cap

    spec = get_model(model)  # ValueError (400) for unknown names
    if not isinstance(spec, NamedTextModel) or not spec.supports_generate():
        raise ValueError(
            f"model {model!r} does not support mode='generate'"
        )
    if payload.ndim == 1:
        payload = payload.reshape(1, -1)
    if payload.ndim != 2 or payload.shape[0] != 1:
        raise ValueError(
            "generate mode takes ONE prompt per request (shape [1, "
            f"prompt_len] or [prompt_len]); got {payload.shape}"
        )
    if not np.issubdtype(payload.dtype, np.integer):
        if not np.all(np.mod(payload, 1) == 0):
            raise ValueError(
                f"model {model!r} expects integer token ids; got "
                f"non-integral {payload.dtype} values"
            )
    payload = payload.astype(np.int32, copy=False)
    prompt_len = int(payload.shape[1])
    if prompt_len < 1:
        raise ValueError("generate prompt must hold at least one token")
    params = dict(gen_params or {})
    cap = max_new_tokens_cap()
    max_new = int(params.get("max_new_tokens") or cap)
    if max_new < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1; got {max_new}"
        )
    max_new = min(max_new, cap)
    if prompt_len + max_new > spec.max_length:
        raise ValueError(
            f"prompt_len {prompt_len} + max_new_tokens {max_new} "
            f"exceeds model {model!r}'s position table "
            f"({spec.max_length}); shorten the prompt or request "
            "fewer tokens"
        )
    params["max_new_tokens"] = max_new
    kv_per_token = spec.kv_bytes_per_token() or 0
    kv_bytes = kv_per_token * (prompt_len + max_new)
    return payload, prompt_len, params, kv_bytes


class Router:
    """Admission queue + dispatcher + completion pool over a residency
    manager. One router per serving process; :class:`ServingClient` and
    the HTTP server are thin front-ends over :meth:`submit`."""

    def __init__(
        self,
        loader: Optional[Callable] = None,
        budget_bytes: Optional[int] = None,
        max_batch: Optional[int] = None,
        workers: Optional[int] = None,
    ):
        self.queue = AdmissionQueue()
        self.residency = ResidencyManager(
            loader=loader, budget_bytes=budget_bytes
        )
        self._max_batch = max_batch
        self._workers = workers or max(
            2, knobs.get_int("SPARKDL_SERVE_WORKERS")
        )
        self._lock = locksmith.lock(
            "sparkdl_tpu/serving/router.py::Router._lock"
        )
        self._ordinal = 0
        self._dispatcher: Optional[threading.Thread] = None
        self._pool: Optional[ThreadPoolExecutor] = None
        #: one slot per completion worker: the dispatcher acquires a
        #: slot BEFORE popping, so at most `workers` groups are ever
        #: popped-but-unfinished. Everything else stays in the admission
        #: queue, where strict-priority-with-aging keeps applying — an
        #: interactive arrival under a background flood waits out at
        #: most the in-flight groups, never a FIFO'd backlog parked in
        #: the pool's internal queue.
        self._slots = threading.Semaphore(self._workers)
        self._stop = threading.Event()
        self._started = False
        self._closed = False
        #: drain state: flag flips in drain(), the event sets once the
        #: queue + in-flight groups have quiesced and resident models
        #: (and their feeder streams) are unloaded. _idle_cv guards the
        #: in-flight group count the quiesce check reads.
        self._draining = False
        self._drained = threading.Event()
        self._idle_cv = locksmith.condition(
            "sparkdl_tpu/serving/router.py::Router._idle_cv"
        )
        self._inflight = 0
        #: canary split state (guarded by _lock, like the ordinal): a
        #: deterministic admission counter for the Bresenham split and
        #: the sticky rollback trip. The trip compares metric DELTAS
        #: against this router's construction-time baseline — the
        #: registry is process-global and cumulative, so absolute
        #: counts would leak failures across router lifetimes (tests,
        #: restarts) into the rollback decision.
        self._canary_count = 0
        self._canary_tripped = False
        #: wave-controller weight override (gateway POST /admin/canary):
        #: when set it replaces SPARKDL_SERVE_CANARY_WEIGHT so the
        #: rollout widens wave-by-wave without an env change + relaunch
        self._canary_weight_override: Optional[float] = None
        #: lazy generation engine (serving/generation.py): built by the
        #: dispatcher on the first generate admission, closed with the
        #: router. Guarded by _lock like the other lifecycle state.
        self._gen_engine = None
        self._canary_base_requests = metrics.counter("serve.canary.requests")
        self._canary_base_failures = metrics.counter("serve.canary.failures")

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "Router":
        with self._lock:
            if self._closed:
                raise RuntimeError("Router is closed")
            if self._started:
                return self
            self._started = True
            self._stop.clear()
            self._pool = ThreadPoolExecutor(
                max_workers=self._workers,
                thread_name_prefix="sparkdl-serve-worker",
            )
            self._dispatcher = threading.Thread(
                target=self._dispatch_loop,
                name="sparkdl-serve-dispatch",
                daemon=True,
            )
            self._dispatcher.start()
        return self

    def close(self, timeout: float = 10.0) -> None:
        """Stop admitting, fail queued requests, drain in-flight groups,
        and unload every resident model."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            dispatcher, pool = self._dispatcher, self._pool
            self._dispatcher, self._pool = None, None
        self.queue.close()
        self._stop.set()
        if dispatcher is not None and dispatcher.is_alive():
            dispatcher.join(timeout=timeout)
        if pool is not None:
            pool.shutdown(wait=True)
        gen = self._gen_engine
        if gen is not None:
            # decode threads stop (failing any still-active sequences)
            # BEFORE residency unloads — a pinned generator entry must
            # be released to be evictable
            gen.close(timeout=timeout)
        self.residency.unload_all()
        # a drain interrupted by close still terminates: queued work was
        # failed (never silently dropped) and nothing is in flight
        self._drained.set()

    # -- submission ---------------------------------------------------------

    def submit(
        self,
        model: str,
        payload,
        priority: str = "batch",
        deadline_s: Optional[float] = None,
        mode: str = "features",
        trace_id: Optional[str] = None,
        gen_params: Optional[dict] = None,
    ) -> Request:
        """Admit one request (raises :class:`AdmissionRejected` /
        ``ValueError`` synchronously); the returned request's
        ``result()`` blocks for the answer. Starts the router lazily so
        in-process clients need no explicit ``start()``.

        ``mode="generate"`` admits ONE prompt for autoregressive decode
        (``gen_params``: max_new_tokens / temperature / top_k / eos_id /
        seed): the sequence's KV-cache bytes reserve against the HBM
        budget HERE — an over-budget sequence is rejected (429) before
        anything touches the device — and tokens stream back through
        ``req.iter_tokens`` while ``req.result()`` returns the full
        [1, n_new] token array."""
        tokens = pad_tokens = 0
        gen_kv_bytes = 0
        if mode == "generate":
            payload, prompt_len, gen_params, gen_kv_bytes = (
                _validate_generate(model, np.asarray(payload), gen_params)
            )
        elif mode == "embed" or _is_text_model(model):
            # Text workload: seq-bucket the token payload so the
            # grouping key below carries (batch rung x seq bucket).
            # Registry text models bucket REGARDLESS of mode — they
            # accept 'features' as an alias of 'embed', and the
            # position-table guard must not be bypassable by an alias.
            payload, tokens, pad_tokens = _bucket_token_payload(
                model, np.asarray(payload)
            )
        req = Request(
            model,
            payload,
            priority=priority,
            deadline_s=deadline_s,
            mode=mode,
            trace_id=trace_id,
        )
        if mode == "generate":
            req.gen_params = gen_params
            req.prompt_len = prompt_len
        # Precision rung, resolved at ADMISSION from the request's SLA
        # class (SPARKDL_SERVE_PRECISION[_<CLASS>]): it rides the
        # grouping key and the residency key, so each rung is its own
        # compiled stream and resident entry — a first-class arm, like
        # the batch rung it composes with.
        from sparkdl_tpu.graph.precision import (
            precision_active,
            serve_precision,
        )

        req.precision = serve_precision(priority)
        req.precision_armed = precision_active()
        if mode == "generate":
            # Generation always runs the generator's own f32 programs;
            # the precision-rung machinery is an embed/feature arm.
            req.precision = "f32"
            req.precision_armed = False
            if gen_kv_bytes:
                # Phase one of the KV charge: reserve against the HBM
                # budget BEFORE enqueueing (AdmissionRejected -> 429).
                # The completion hook releases it on every finishing
                # path; a failed put below releases it explicitly.
                try:
                    self.residency.reserve_kv(gen_kv_bytes)
                except AdmissionRejected:
                    from sparkdl_tpu.obs import slo

                    slo.note_bad(req.priority, "rejected")
                    raise
                req.kv_bytes = gen_kv_bytes
                req._kv_release = (
                    lambda n=gen_kv_bytes: self.residency.release_kv(n)
                )
        if not self._started:
            self.start()
        # The ordinal chaos plans target is the ADMISSION ordinal: a
        # rejected submit must not consume one, or load-dependent
        # rejections would shift which request a replayed plan hits.
        # put() never blocks, so holding the router lock across it keeps
        # (assign ordinal, enqueue) atomic — the dispatcher can only pop
        # the request after its ordinal is final. The canary split uses
        # its own admission counter under the same lock, so the routed
        # arm is a pure function of admission order too.
        tripped_now = None
        try:
            with self._lock:
                tripped_now = self._canary_resolve_locked(req)
                req.ordinal = self._ordinal
                self.queue.put(req)  # raises on rejection: ordinal unspent
                self._ordinal += 1
        except AdmissionRejected:
            # Capacity shed spends the availability budget (the operator
            # promised admission they didn't have); Draining does NOT —
            # a drain is a deliberate operational move, not an outage.
            from sparkdl_tpu.obs import slo

            slo.note_bad(req.priority, "rejected")
            req._run_kv_release()
            raise
        except BaseException:
            # Draining / close raced the put: the request was never
            # admitted, so its KV reservation must not strand.
            req._run_kv_release()
            raise
        finally:
            # the trip is STICKY, so this admission is the only one that
            # will ever carry the rollback info — emit the JSONL event
            # even when the very submit that tripped it was rejected
            if tripped_now is not None:
                self._emit_canary_rollback(tripped_now)
        # Counted only after admission SUCCEEDED: a rejected (or
        # retried-by-the-client) submit must not inflate the token
        # accounting behind obs report's text line.
        if tokens:
            metrics.inc("text.tokens", tokens)
        if pad_tokens:
            metrics.inc("text.pad_tokens", pad_tokens)
        if req.canary_arm is not None:
            metrics.inc(
                "serve.canary.requests"
                if req.canary_arm == "canary"
                else "serve.primary.requests"
            )
        if req.precision_armed:
            metrics.inc(f"serve.precision.{req.precision}.requests")
            metrics.inc(f"serve.precision.{req.precision}.rows", req.rows)
        return req

    # -- canary rollout -----------------------------------------------------

    def _canary_resolve_locked(self, req: Request) -> Optional[dict]:
        """Apply the canary split to one admission (caller holds
        ``_lock``). Rewrites ``req.model`` to the canary version on the
        Bresenham take and tags ``req.canary_arm`` either way, so
        completion records the per-version latency/failure pair.
        Returns rollback info when THIS admission's trip evaluation
        fired (the caller emits the JSONL event outside the lock)."""
        cfg = canary_config()
        if cfg is None:
            return None
        base, version, weight = cfg
        if self._canary_weight_override is not None:
            weight = self._canary_weight_override
        if str(req.model).lower() != base:
            return None
        tripped_now = self._maybe_trip_canary_locked(base, version)
        take = False
        if not self._canary_tripped and weight > 0.0:
            n = self._canary_count
            take = math.floor((n + 1) * weight) > math.floor(n * weight)
        self._canary_count += 1
        if take:
            req.model = version
            req.canary_arm = "canary"
        else:
            req.canary_arm = "primary"
        return tripped_now

    def _maybe_trip_canary_locked(
        self, base: str, version: str
    ) -> Optional[dict]:
        """Evaluate the rollback trip: canary failure rate (this
        router's deltas) >= ``SPARKDL_SERVE_CANARY_TRIP_RATE`` after at
        least ``SPARKDL_SERVE_CANARY_MIN_REQUESTS`` canary requests.
        Sticky: once tripped, every later admission routes primary
        until the operator reconfigures (a new router re-arms)."""
        if self._canary_tripped:
            return None
        reqs = (
            metrics.counter("serve.canary.requests")
            - self._canary_base_requests
        )
        if reqs < max(1, knobs.get_int("SPARKDL_SERVE_CANARY_MIN_REQUESTS")):
            return None
        fails = (
            metrics.counter("serve.canary.failures")
            - self._canary_base_failures
        )
        trip_rate = knobs.get_float("SPARKDL_SERVE_CANARY_TRIP_RATE")
        rate = fails / reqs
        if trip_rate <= 0 or rate < trip_rate:
            return None
        self._canary_tripped = True
        metrics.inc("serve.canary.rollbacks")
        return {
            "model": base,
            "version": version,
            "requests": int(reqs),
            "failures": int(fails),
            "rate": round(rate, 4),
        }

    @staticmethod
    def _emit_canary_rollback(info: dict) -> None:
        from sparkdl_tpu.obs import append_jsonl, dump_on_failure

        append_jsonl(
            {"kind": "canary_rollback", "ts": round(time.time(), 3), **info}
        )
        # Dump-on-failure edge: a tripped rollback means real canary
        # failures crossed the rate — flush the recorder (with the
        # rollback decision attached) while the failing requests' spans
        # and stored traces are still in the ring.
        dump_on_failure("canary_rollback", **info)

    def set_canary_weight(self, weight: float) -> dict:
        """Override the canary split weight at runtime (the gateway's
        wave controller POSTs this through ``/admin/canary``). Clamped
        to [0, 1]; the override wins over the env knob until the router
        is replaced. Setting a weight does NOT clear a sticky trip —
        a rolled-back router stays rolled back."""
        w = min(1.0, max(0.0, float(weight)))
        with self._lock:
            self._canary_weight_override = w
            tripped = self._canary_tripped
        return {"weight": w, "tripped": tripped}

    @property
    def canary_tripped(self) -> bool:
        with self._lock:
            return self._canary_tripped

    # -- graceful drain -----------------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self) -> "Router":
        """Begin graceful drain: close admission (later submits raise
        :class:`~sparkdl_tpu.serving.request.Draining` -> HTTP 503 +
        ``Retry-After``) while queued and in-flight requests complete.
        Non-blocking; the dispatcher finishes the drain once quiesced
        (resident models unload, closing their feeder streams) and
        :meth:`wait_drained` observes it. Idempotent, and terminal for
        this router: a drained worker restarts via the supervisor
        rather than re-opening admission."""
        with self._lock:
            already = self._draining
            self._draining = True
            started, closed = self._started, self._closed
            if not already:
                # under the SAME lock submit() holds across queue.put:
                # once we release, no submit can slip an admission in
                # after a quiesce check already declared the drain done
                self.queue.drain()
        if already:
            return self
        metrics.inc("serve.drains")
        if closed or not started:
            # nothing queued, nothing in flight, no dispatcher to
            # finish the job — the drain is trivially complete
            self._finish_drain()
        return self

    def wait_drained(self, timeout: Optional[float] = None) -> bool:
        """Block until the drain completes (queue empty, in-flight
        groups done, models unloaded); False on timeout."""
        return self._drained.wait(timeout=timeout)

    def _maybe_finish_drain(self) -> None:
        """Dispatcher-side quiesce check: the dispatcher is the only
        thread that pops, so when IT sees an empty queue with no groups
        in flight while draining, no request can still be en route to
        the device (admission is already closed)."""
        if not self._draining or self._drained.is_set():
            return
        with self._idle_cv:
            if self._inflight > 0:
                return
        if self.queue.depth() == 0:
            self._finish_drain()

    def _finish_drain(self) -> None:
        if self._drained.is_set():
            return
        gen = self._gen_engine
        if gen is not None:
            # quiesced: no generations in flight, streams are idle —
            # closing them releases their residency pins so the unload
            # below can actually evict the generator entries
            gen.close()
        self.residency.unload_all()
        self._drained.set()

    def _inflight_inc(self) -> None:
        with self._idle_cv:
            self._inflight += 1

    def _inflight_dec(self) -> None:
        with self._idle_cv:
            self._inflight -= 1
            self._idle_cv.notify_all()

    # -- dispatcher ---------------------------------------------------------

    @staticmethod
    def _stream_key(req: Request) -> tuple:
        # (model, mode, row shape incl. the seq bucket, dtype,
        # precision): the full coordinate of one compiled feeder
        # stream — batch rung x seq bucket x precision rung never mix.
        return (
            req.model,
            req.mode,
            tuple(req.payload.shape[1:]),
            str(req.payload.dtype),
            req.precision,
        )

    def _dispatch_loop(self) -> None:
        while not self._stop.is_set():
            # Backpressure: hold a worker slot before popping, so the
            # admission queue (where priority lives) stays the ONLY
            # backlog — the pool's FIFO never buffers more groups than
            # it has workers.
            if not self._slots.acquire(timeout=0.2):
                continue
            submitted = False
            popped = False
            try:
                req = self.queue.pop(timeout=0.2)
                if req is None:
                    # The dispatcher is the only popper, so an empty
                    # queue observed HERE (with no groups in flight) is
                    # the drain's quiesce point.
                    self._maybe_finish_drain()
                    continue
                if req.mode == "generate":
                    # Token-level work: hand the sequence to the
                    # generation engine (its own decode threads) and
                    # free this worker slot immediately — a decode that
                    # runs for hundreds of steps must not hold an
                    # embed-path completion worker. The engine carries
                    # the in-flight count until the sequence retires,
                    # so drain still waits for running generations.
                    self._inflight_inc()
                    try:
                        self._generation_engine().enroll(req)
                    except BaseException as e:  # noqa: BLE001
                        req.set_error(e)
                        self._inflight_dec()
                    continue
                self._inflight_inc()
                popped = True
                group = self._assemble_group(req)
                if not group:
                    continue
                pool = self._pool
                if pool is None:
                    self._fail_group(group)
                    return
                try:
                    pool.submit(self._serve_group_slot, group)
                    submitted = True
                except RuntimeError:  # close() raced us: pool shut down
                    self._fail_group(group)
                    return
            finally:
                if not submitted:
                    self._slots.release()
                    if popped:
                        self._inflight_dec()

    def _generation_engine(self):
        with self._lock:
            engine = self._gen_engine
            if engine is None or engine._closed:
                from sparkdl_tpu.serving.generation import GenerationEngine

                engine = self._gen_engine = GenerationEngine(self)
            return engine

    @staticmethod
    def _fail_group(group: List[Request]) -> None:
        for r in group:
            r.set_error(
                RuntimeError("serving shut down"), count_failure=False
            )

    def _serve_group_slot(self, group: List[Request]) -> None:
        try:
            self._serve_group(group)
        finally:
            self._slots.release()
            self._inflight_dec()

    def _assemble_group(self, first: Request) -> List[Request]:
        """Grow a same-stream group from the queue: immediately absorb
        everything already waiting (queue depth IS the load signal), and
        only when still short of the full geometry — and the strictest
        class on hand is under its p95 target — linger the batch window
        for late arrivals."""
        key = self._stream_key(first)
        cap = (self._max_batch or max_batch_rows()) * self._group_width()
        group = [first]
        rows = first.rows
        pred = lambda r: self._stream_key(r) == key
        if rows < cap:
            group += self.queue.pop_matching(pred, cap - rows)
            rows = sum(r.rows for r in group)
        window = batch_window_s()
        if rows < cap and window > 0.0:
            strictest = min(group, key=lambda r: r.class_index).priority
            p95 = observed_p95_s(strictest)
            if p95 is None or p95 < target_p95_s(strictest):
                deadline = time.monotonic() + window
                gen = self.queue.put_generation()
                while rows < cap and time.monotonic() < deadline:
                    if self._stop.wait(timeout=min(0.001, window)):
                        break
                    new_gen = self.queue.put_generation()
                    if new_gen == gen:
                        continue  # nothing admitted since the last scan
                    gen = new_gen
                    more = self.queue.pop_matching(pred, cap - rows)
                    if more:
                        group += more
                        rows = sum(r.rows for r in group)
        return group

    @staticmethod
    def _group_width() -> int:
        """How many chips a group's dispatch will likely engage — the
        group-assembly cap scales with it so a mesh is FED at mesh
        width (a width-8 mesh whose groups stop at 32 rows would pad
        7/8 of every global batch). The dispatch-side rung math uses
        the loaded device fn's true multiplier; this hint only shapes
        how many rows assembly is allowed to gather."""
        from sparkdl_tpu.transformers.execution import (
            inference_devices,
            inference_mode,
            serve_mesh_width,
        )

        width = serve_mesh_width()
        if width is not None:
            return max(1, width)
        if inference_mode() == "shard_map":
            return max(1, len(inference_devices()))
        return 1

    # -- completion workers --------------------------------------------------

    def _serve_group(self, group: List[Request]) -> None:
        """One group end-to-end: chaos/deadline screening, residency
        acquire (pin), retried dispatch through the feeder stream,
        scatter back into per-request results."""
        live: List[Request] = []
        for req in group:
            if req.expired():
                metrics.inc("serve.expired")
                req.set_error(
                    DeadlineExceeded(
                        f"request {req.id} expired before dispatch"
                    )
                )
                continue
            try:
                maybe_fault(
                    "serve.request",
                    request=getattr(req, "ordinal", req.id),
                    model=req.model,
                    cls=req.priority,
                )
            except BaseException as e:  # noqa: BLE001 — injected fault
                from sparkdl_tpu.obs import memory as mem_mod

                if mem_mod.is_oom_error(e):
                    # allocation-failure forensics: the {"kind":"oom"}
                    # event + dump name the models resident at failure
                    mem_mod.record_oom("dispatch", req.model, e)
                req.set_error(e)
                continue
            live.append(req)
        if not live:
            return
        try:
            policy = policy_from_env(
                "SPARKDL_SERVE_RETRY",
                max_attempts=2,
                base_delay_s=0.01,
                max_delay_s=0.5,
            )
            # acquire() runs INSIDE the retried callable: transient
            # residency contention (a concurrent first-load holding the
            # budget reservation) resolves on retry, once the other load
            # has landed and become evictable.
            out, starts = policy.call(self._acquire_and_dispatch, live)
            t_scatter = time.monotonic()
            for req, start in zip(live, starts):
                rows = out[start : start + req.rows]
                if any(r is None for r in rows):
                    raise RuntimeError(
                        f"serving dispatch dropped rows for request "
                        f"{req.id} ({req.model})"
                    )
                # the waterfall's last segment: result split + delivery
                # time up to THIS request's completion, so each
                # request's segments sum to its own e2e latency
                req.trace_segments["scatter"] = max(
                    0.0, time.monotonic() - t_scatter
                )
                req.set_result(np.stack(rows))
        except BaseException as e:  # noqa: BLE001 — fail, never hang
            for req in live:
                req.set_error(e)
            # Dump-on-failure edge: a group failing AFTER the retry
            # policy gave up is the "why was request X lost" moment —
            # flush the flight recorder naming the failing trace id(s)
            # so the post-mortem starts from the waterfall, not logs.
            from sparkdl_tpu.obs import dump_on_failure
            from sparkdl_tpu.obs import memory as mem_mod

            if mem_mod.is_oom_error(e):
                # no-op when the load path already recorded this error
                # (record_oom marks the exception) — a dispatch-path
                # RESOURCE_EXHAUSTED gets its forensics here
                mem_mod.record_oom("dispatch", live[0].model, e)
            dump_on_failure(
                "serve_retry_exhausted",
                trace_id=live[0].trace_id,
                trace_ids=[r.trace_id for r in live],
                model=live[0].model,
                error=f"{type(e).__name__}: {e}",
            )

    def _acquire_and_dispatch(self, group: List[Request]):
        entry = self.residency.acquire(
            group[0].model, group[0].mode, precision=group[0].precision
        )
        try:
            return self._dispatch_once(entry, group)
        finally:
            self.residency.release(entry)

    def _dispatch_once(self, entry, group: List[Request]):
        """Pad the group to an exact multiple of the rung geometry and
        push it through the (device_fn, geometry) feeder stream. Exact
        fill means the feeder flushes every batch immediately — no
        linger on the serving path."""
        from sparkdl_tpu.runtime.feeder import default_prefetch, get_feeder

        # Waterfall edges: queue_wait ends at the pop stamp, group_wait
        # ends HERE — so the batch window, the worker-slot wait, the
        # residency acquire (model load included; serve.model_load
        # attributes it separately), and any earlier attempt's retry
        # backoff all land in group_wait. Overwritten per attempt: the
        # attempt that lands is the one the completion records.
        t_dispatch0 = time.monotonic()
        for req in group:
            dequeued = (
                req.dequeue_t if req.dequeue_t is not None else req.enqueue_t
            )
            req.trace_segments["queue_wait"] = max(
                0.0, dequeued - req.enqueue_t
            )
            req.trace_segments["group_wait"] = max(
                0.0, t_dispatch0 - dequeued
            )
        rows = np.concatenate([r.payload for r in group], axis=0)
        n = int(rows.shape[0])
        # The rung is PER-CHIP: a mesh program's dispatch geometry is
        # rung x width (its batch_multiplier), so the global batch pads
        # to exact global-rung multiples and each chip still runs a
        # power-of-two program from the same ladder as single-chip.
        multiplier = getattr(entry.device_fn, "batch_multiplier", 1)
        rung = choose_rung(n, self._max_batch, mesh_width=multiplier)
        dispatch_rows = rung * multiplier
        n_batches = max(1, math.ceil(n / dispatch_rows))
        total = n_batches * dispatch_rows
        pad = total - n
        if pad:
            rows = np.concatenate(
                [rows, np.zeros((pad, *rows.shape[1:]), rows.dtype)], axis=0
            )
        out: List[Optional[np.ndarray]] = [None] * total

        def _open():
            feeder = get_feeder(
                entry.device_fn,
                dispatch_rows,
                rows.shape[1:],
                rows.dtype,
                default_prefetch(entry.device_fn),
            )
            return feeder, feeder.open_handle(out)

        # Same closed-under-us race as run_shared's handle open: LRU
        # feeder eviction (or a model eviction racing a new request)
        # can close a feeder between registry lookup and first use —
        # the batch engine's policy covers it, shared so tuning stays
        # in one place.
        from sparkdl_tpu.runtime.feeder import open_handle_policy

        feeder, handle = open_handle_policy.call(_open)
        with span(
            "serve.dispatch",
            model=entry.name,
            rows=n,
            rung=rung,
            batches=n_batches,
            group=len(group),
            mesh_width=multiplier,
            precision=entry.precision,
            trace_id=group[0].trace_id,
        ):
            try:
                feeder.submit_rows(handle, np.arange(total), rows)
            finally:
                try:
                    feeder.finish(handle)
                except RuntimeError:
                    pass  # feeder closed underneath us; handle failed
            handle.wait(timeout=self._dispatch_timeout_s())
        # Device-side waterfall attribution: the handle is fresh per
        # group, so its accumulated stage_wait/drain_wait are THIS
        # group's residuals; everything else inside the handle-wait wall
        # (the device program + feeder-internal queueing) is the
        # dispatch segment — the three sum to the wall by construction,
        # so each request's segments sum to its e2e latency.
        wall = max(0.0, time.monotonic() - t_dispatch0)
        feeder_segs = handle.segments_snapshot()
        stage_wait = min(wall, max(0.0, feeder_segs.get("stage_wait", 0.0)))
        drain_wait = min(
            wall - stage_wait, max(0.0, feeder_segs.get("drain_wait", 0.0))
        )
        dispatch_s = max(0.0, wall - stage_wait - drain_wait)
        for req in group:
            req.trace_segments["stage_wait"] = stage_wait
            req.trace_segments["dispatch"] = dispatch_s
            req.trace_segments["drain_wait"] = drain_wait
        # Counted only AFTER the group's results landed: a failed
        # attempt that the retry policy re-runs must not double-count
        # into the bench-gate-protected dispatch/row/rung stats (the
        # queue/group-wait reservoirs follow the same discipline — the
        # bench's waterfall extras must never include doomed attempts).
        metrics.record_times(
            "serve.queue_wait",
            [r.trace_segments["queue_wait"] for r in group],
        )
        metrics.record_times(
            "serve.group_wait",
            [r.trace_segments["group_wait"] for r in group],
        )
        for _ in range(n_batches):
            metrics.record_time("serve.batch_rows", float(rung))
        metrics.inc("serve.dispatches", n_batches)
        metrics.inc("serve.dispatched_rows", n)
        if multiplier > 1:
            # Per-chip accounting for the mesh arm: each chip saw
            # n_batches programs of `rung` rows (pad included — the
            # geometry is what the chip pays for).
            metrics.inc("serve.mesh.chip_rows", n_batches * rung)
        flops_per_row = entry.flops_per_item
        if entry.flops_fn is not None and rows.ndim == 2:
            # Seq-bucketed text dispatch: charge the FLOPs of the
            # bucket that RAN (the payload's padded seq length), not
            # the spec's max_length — a short-context request on a
            # long-context model must not inflate serve.mfu by the
            # bucket ratio.
            flops_per_row = entry.flops_fn(int(rows.shape[1]))
        if flops_per_row:
            # Goodput ledger: analytic FLOPs of the REAL rows that
            # landed (pad rows are chip time, not goodput) feed the
            # rolling serve.mfu gauge, devices-normalized like the
            # bench wiring. Counted with the other landed-only stats.
            from sparkdl_tpu.obs import utilization

            utilization.note_flops(
                flops_per_row * n, devices=multiplier
            )
        if pad:
            metrics.inc("serve.pad_rows", pad)
        starts = []
        off = 0
        for req in group:
            starts.append(off)
            off += req.rows
        return out, starts

    @staticmethod
    def _dispatch_timeout_s() -> float:
        """Hard bound on one group's device wait
        (``SPARKDL_SERVE_DISPATCH_TIMEOUT_S``, default 120): a wedged
        backend fails requests loudly instead of hanging completion
        workers forever."""
        return knobs.get_float("SPARKDL_SERVE_DISPATCH_TIMEOUT_S")

    # -- introspection ------------------------------------------------------

    def stats(self) -> dict:
        """Live status for ``/v1/models`` + the CLI."""
        per_class: Dict[str, dict] = {}
        for cls in PRIORITY_CLASSES:
            stat = metrics.timing(f"serve.latency.{cls}")
            if stat is None or not stat.count:
                continue
            per_class[cls] = {
                "count": stat.count,
                "p50_ms": round(stat.percentile(50) * 1e3, 2),
                "p95_ms": round(stat.percentile(95) * 1e3, 2),
            }
        out = {
            "queue_depth_rows": self.queue.depth_rows(),
            "queued_requests": self.queue.depth(),
            "models": self.residency.models(),
            "latency": per_class,
            "admitted": int(metrics.counter("serve.admitted")),
            "completed": int(metrics.counter("serve.completed")),
            "rejected": int(metrics.counter("serve.rejected")),
            "expired": int(metrics.counter("serve.expired")),
            "failures": int(metrics.counter("serve.failures")),
            "evictions": int(metrics.counter("serve.evictions")),
            "draining": self._draining,
        }
        widths = [
            m.get("mesh_width", 1) for m in out["models"]
        ]
        if any(w > 1 for w in widths):
            out["mesh"] = {
                "width": max(widths),
                "chip_rows": int(metrics.counter("serve.mesh.chip_rows")),
            }
        from sparkdl_tpu.graph.precision import PRECISIONS, precision_active

        if precision_active():
            arms = {}
            for p in PRECISIONS:
                reqs = int(metrics.counter(f"serve.precision.{p}.requests"))
                if not reqs:
                    continue
                arm = {"requests": reqs}
                stat = metrics.timing(f"serve.precision.{p}.latency")
                if stat is not None and stat.count:
                    arm["p95_ms"] = round(stat.percentile(95) * 1e3, 2)
                arms[p] = arm
            if arms:
                out["precision"] = arms
        from sparkdl_tpu.obs import slo

        try:
            slo_status = slo.engine_status()
        except ValueError as e:
            # a malformed SLO knob must not take /v1/models down with
            # it — the residency/latency stats still answer, the slo
            # block names the config error (GET /v1/slo raises loudly)
            slo_status = {"armed": True, "error": str(e)}
        if slo_status is not None:
            # the live burn-rate view (same payload as GET /v1/slo):
            # reading stats IS an evaluation, so a quiet tripped class
            # recovers the moment an operator looks at it
            out["slo"] = slo_status
        from sparkdl_tpu.obs import utilization as util_mod

        util = util_mod.utilization_status()
        if util is not None:
            # the device-utilization roll-up (additive key, like slo):
            # the gateway's fleet scrape reads it off /v1/models so the
            # capacity-headroom model sees each rank's busy fraction
            # without a fourth endpoint pull
            out["utilization"] = util
        from sparkdl_tpu.obs import memory as mem_mod

        mem = mem_mod.memory_status()
        if mem is not None:
            # the device-memory roll-up (additive key, like slo and
            # utilization): the fleet scrape reads it off /v1/models so
            # fleet.mem.* aggregates need no fourth endpoint pull; the
            # budget rides along so headroom is computable fleet-side
            try:
                mem["budget_bytes"] = self.residency.budget_bytes()
            except ValueError:
                mem["budget_bytes"] = None  # malformed knob: /v1/models stays up
            out["memory"] = mem
        gen = self._gen_engine
        if gen is not None:
            # the generation roll-up (additive key, like slo/memory):
            # per-stream slot occupancy + the gen.* counters the
            # OBSERVABILITY table documents
            out["generation"] = gen.status()
        cfg = canary_config()
        if cfg is not None:
            base, version, weight = cfg
            if self._canary_weight_override is not None:
                weight = self._canary_weight_override
            out["canary"] = {
                "model": base,
                "version": version,
                "weight": weight,
                "requests": int(
                    metrics.counter("serve.canary.requests")
                    - self._canary_base_requests
                ),
                "failures": int(
                    metrics.counter("serve.canary.failures")
                    - self._canary_base_failures
                ),
                "tripped": self._canary_tripped,
            }
        return out


__all__ = [
    "Router",
    "batch_window_s",
    "canary_config",
    "choose_rung",
    "choose_seq_bucket",
    "max_batch_rows",
    "observed_p95_s",
    "target_p95_s",
]
