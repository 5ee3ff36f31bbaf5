"""Multi-model device residency: load on demand, LRU-evict under budget.

A serving process fields requests for MANY named models but a chip holds
a finite HBM. This manager is the layer between the request router and
``models/registry.py``: the first request for a model loads it (builds
the ModelFunction, wraps it in the standard multi-device dispatch fn)
and every subsequent request reuses the resident copy; when loading one
more model would push the total param footprint past
``SPARKDL_SERVE_HBM_BUDGET_MB``, the **least-recently-used idle** model
is evicted first — its compiled feeder streams are closed
(``runtime.feeder.close_feeders_for``) so the registry's strong
device_fn reference cannot keep the params alive.

Two hard rules:

- A model with OPEN STREAMS (requests in flight) is never evicted, no
  matter how over-budget the manager is — evicting under a live dispatch
  would fail user-visible requests to make room for other ones. Pinning
  is refcount-shaped: ``acquire`` pins, ``release`` unpins.
- Sizing is honest: the budget compares against
  ``models.registry.param_bytes`` of the ACTUAL loaded pytree (not the
  eval_shape estimate), so a model loaded with bf16 weights charges half
  its float32 estimate.

The budget intentionally covers params only. Activations/IO buffers
scale with batch geometry, not model count, and are bounded by the
feeder's ring + prefetch window; params are the per-model cost that
accumulates.

Model resolution defaults to the named-model registry
(``get_model(name).model_function(mode=...)``) but accepts any
``loader(name, mode) -> ModelFunction`` — tests and smokes serve tiny
synthetic models through the identical residency/eviction machinery.
"""

from __future__ import annotations

import math
import os
import threading
import time
from typing import Callable, Dict, List, Optional

from sparkdl_tpu.runtime import knobs, locksmith
from sparkdl_tpu.utils.metrics import metrics


def hbm_budget_bytes() -> Optional[int]:
    """``SPARKDL_SERVE_HBM_BUDGET_MB`` as bytes; None/0 = no budget
    (residency grows unbounded — single-model deployments). Malformed
    values raise like every other numeric knob: a fat-fingered budget
    silently meaning "unbounded" is exactly the OOM the knob exists to
    prevent."""
    try:
        mb = knobs.get_float("SPARKDL_SERVE_HBM_BUDGET_MB")
    except ValueError as e:
        raise ValueError(
            f"{e}: expected a number of megabytes (0/unset disables "
            "the budget)"
        ) from None
    if mb is None:
        return None
    if not math.isfinite(mb) or mb < 0:
        raise ValueError(
            "SPARKDL_SERVE_HBM_BUDGET_MB="
            f"{knobs.get_raw('SPARKDL_SERVE_HBM_BUDGET_MB')!r}: "
            "expected a finite, non-negative number of megabytes "
            "(0/unset disables the budget)"
        )
    return int(mb * 2**20) if mb > 0 else None


def _default_loader(name: str, mode: str, precision: str = "f32"):
    """Registry-backed loader. ``precision`` is the serving rung
    (``graph/precision.py``): ``bf16`` builds the module with bf16
    compute dtype where the builder supports it (the flax perf path's
    MXU-native arm) — the manager then applies the rung's param/edge
    casts on top, same as it does for custom loaders that never heard
    of precision."""
    from sparkdl_tpu.models import get_model

    spec = get_model(name)
    if mode == "generate":
        # Autoregressive path: a BertGenerator (prefill + decode jit
        # programs over the same param tree the embed builder inits),
        # not a ModelFunction — residency loads it through the
        # dedicated generator branch, which skips precision wrapping
        # and mesh election (generation runs f32, single-stream).
        return spec.generate_function()
    if precision == "bf16":
        import jax.numpy as jnp

        try:
            return spec.model_function(mode=mode, dtype=jnp.bfloat16)
        except TypeError:
            pass  # builder without a dtype knob: the edge casts still apply
    return spec.model_function(mode=mode)


class ResidentModel:
    """One loaded model: the ModelFunction, its dispatch fn, and the
    bookkeeping the eviction policy reads. ``param_bytes`` is the
    PER-CHIP charge the budget compares: for a mesh program whose
    params genuinely shard across chips (``params_sharded``), each chip
    holds only its slice, so the full pytree size divided by the mesh
    width — replicated data-parallel params keep the full charge."""

    __slots__ = (
        "key", "name", "mode", "model_function", "device_fn",
        "param_bytes", "pins", "loads", "last_used", "requests",
        "precision", "mesh_width", "flops_per_item", "flops_fn",
        "estimate_bytes", "measured_bytes", "mem_charge",
        "mem_baseline",
    )

    def __init__(
        self, key, name, mode, model_function, device_fn, nbytes,
        precision="f32", mesh_width=1, flops_per_item=None,
        flops_fn=None,
    ):
        self.key = key
        self.name = name
        self.mode = mode
        self.model_function = model_function
        self.device_fn = device_fn
        self.param_bytes = int(nbytes)
        self.pins = 0  # in-flight request groups holding this model
        self.loads = 1
        self.last_used = time.monotonic()
        self.requests = 0
        self.precision = precision
        self.mesh_width = int(mesh_width)
        #: analytic forward FLOPs per row (the registry spec's
        #: flops_per_item), or None for custom-loader models — the
        #: live serve.mfu gauge only claims what the spec actually
        #: knows. ``flops_fn`` (text specs) maps a DISPATCHED sequence
        #: length to per-row FLOPs: seq-bucketed dispatches must charge
        #: the bucket they ran, not the position table's max_length —
        #: a 128-token request on bert-long-2048 is ~16x cheaper than
        #: the scalar would claim.
        self.flops_per_item = (
            float(flops_per_item) if flops_per_item else None
        )
        self.flops_fn = flops_fn
        #: the spec-side size estimate the budget WOULD have charged,
        #: kept beside whatever ``param_bytes`` became (the measured
        #: charge on backends with a real allocator probe) so the
        #: models() rows can show the drift; ``mem_charge`` is the
        #: (per_chip, width) the memory ledger was told at load —
        #: evict subtracts the identical charge; ``mem_baseline`` is
        #: the (ground_truth, tracked) pair before the load, the
        #: leak-check reference.
        self.estimate_bytes = int(nbytes)
        self.measured_bytes: Optional[int] = None
        self.mem_charge: Optional[tuple] = None
        self.mem_baseline: Optional[tuple] = None

    @property
    def busy(self) -> bool:
        return self.pins > 0


class ResidencyManager:
    """Thread-safe residency table keyed by ``(model name, mode)``.

    ``acquire`` returns a PINNED :class:`ResidentModel`; callers must
    ``release`` it when their dispatch completes (the router does this in
    its completion stage). Loading happens outside the table lock —
    building ResNet50 must not stall lookups of already-resident models —
    with a per-key load lock so concurrent first requests build once."""

    def __init__(
        self,
        loader: Optional[Callable] = None,
        budget_bytes: Optional[int] = None,
    ):
        self._loader = loader or _default_loader
        # Custom loaders predate precision rungs and take (name, mode);
        # precision-aware ones (the default) take a third parameter.
        # Sniffed once so acquire never TypeErrors mid-request.
        import inspect

        try:
            params = inspect.signature(self._loader).parameters.values()
            self._loader_takes_precision = (
                sum(
                    1
                    for p in params
                    if p.kind
                    in (
                        inspect.Parameter.POSITIONAL_ONLY,
                        inspect.Parameter.POSITIONAL_OR_KEYWORD,
                    )
                )
                >= 3
                or any(
                    p.kind == inspect.Parameter.VAR_POSITIONAL
                    for p in params
                )
            )
        except (TypeError, ValueError):
            self._loader_takes_precision = False
        self._budget_override = budget_bytes
        self._lock = locksmith.lock(
            "sparkdl_tpu/serving/residency.py::ResidencyManager._lock"
        )
        self._models: Dict[tuple, ResidentModel] = {}
        self._load_locks: Dict[tuple, threading.Lock] = {}
        #: bytes reserved by loads in flight (key -> size): the budget
        #: check counts these alongside resident models, so two
        #: concurrent first-loads of DIFFERENT models cannot each pass
        #: the check and jointly blow the budget.
        self._reserved: Dict[tuple, int] = {}
        #: KV-cache bytes reserved by admitted generate sequences
        #: (reserve_kv/release_kv): counted against the same budget as
        #: params, so a flood of long-context sequences is refused at
        #: admission (429) instead of OOMing a decode step.
        self._kv_bytes = 0

    def _budget(self) -> Optional[int]:
        if self._budget_override is not None:
            return self._budget_override or None
        return hbm_budget_bytes()

    def budget_bytes(self) -> Optional[int]:
        """The effective HBM budget (constructor override or the
        ``SPARKDL_SERVE_HBM_BUDGET_MB`` knob); None = unbounded."""
        return self._budget()

    # -- introspection ------------------------------------------------------

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(m.param_bytes for m in self._models.values())

    def models(self) -> List[dict]:
        """Status rows for ``/v1/models``."""
        now = time.monotonic()
        with self._lock:
            return [
                {
                    "name": m.name,
                    "mode": m.mode,
                    "precision": m.precision,
                    "mesh_width": m.mesh_width,
                    "param_mb": round(m.param_bytes / 2**20, 2),
                    "param_bytes": m.param_bytes,
                    "estimate_bytes": m.estimate_bytes,
                    "measured_bytes": m.measured_bytes,
                    "estimate_delta_bytes": (
                        m.measured_bytes - m.estimate_bytes
                        if m.measured_bytes is not None
                        else None
                    ),
                    "busy": m.busy,
                    "loads": m.loads,
                    "requests": m.requests,
                    "idle_s": round(now - m.last_used, 3),
                    # text models: 'flash' (the compiled Pallas kernel)
                    # or 'dense', and the layout it reads ('packed': the
                    # projections' own), as decided when the function
                    # was built
                    **{
                        key: getattr(m.model_function, key)
                        for key in ("attention", "attention_layout")
                        if hasattr(m.model_function, key)
                    },
                }
                for m in self._models.values()
            ]

    def _publish_gauges_locked(self) -> None:
        metrics.gauge("serve.resident_models", len(self._models))
        metrics.gauge(
            "serve.resident_mb",
            sum(m.param_bytes for m in self._models.values()) / 2**20,
        )
        # The WIDEST resident mesh, not the last load's width: a
        # single-chip model loading after a width-4 one must not make
        # the report claim the mesh traffic ran on one chip.
        metrics.gauge(
            "serve.mesh.width",
            max(
                (m.mesh_width for m in self._models.values()),
                default=0,
            ),
        )

    # -- KV-cache reservations (generation engine) --------------------------

    def reserve_kv(self, nbytes: int) -> int:
        """Reserve ``nbytes`` of KV-cache room against the HBM budget at
        ADMISSION time — phase one of the two-phase KV charge (the
        memory ledger's ``kv_cache`` attribution lands at slot
        assignment, phase two). Raises the serving layer's
        ``AdmissionRejected`` (HTTP 429) when params + in-flight loads +
        existing KV reservations leave no room: the sequence is refused
        before any device allocation, never OOM'd mid-decode."""
        from sparkdl_tpu.serving.request import AdmissionRejected

        nbytes = int(nbytes)
        budget = self._budget()
        with self._lock:
            if budget is not None:
                used = (
                    sum(m.param_bytes for m in self._models.values())
                    + sum(self._reserved.values())
                    + self._kv_bytes
                )
                if used + nbytes > budget:
                    metrics.inc("gen.kv_rejected")
                    raise AdmissionRejected(
                        f"KV-cache reservation of {nbytes / 2**20:.2f} MB "
                        f"refused: HBM budget {budget / 2**20:.1f} MB has "
                        f"{used / 2**20:.1f} MB resident/reserved"
                    )
            self._kv_bytes += nbytes
            metrics.gauge("gen.kv_bytes", self._kv_bytes)
        return nbytes

    def release_kv(self, nbytes: int) -> None:
        """Return a sequence's KV reservation (retirement, or a failure
        between admission and slot assignment). Floor at zero — a
        double release must not open phantom budget room."""
        with self._lock:
            self._kv_bytes = max(0, self._kv_bytes - int(nbytes))
            metrics.gauge("gen.kv_bytes", self._kv_bytes)

    def kv_reserved_bytes(self) -> int:
        with self._lock:
            return self._kv_bytes

    # -- the acquire/release protocol ---------------------------------------

    def acquire(
        self,
        name: str,
        mode: str = "features",
        precision: Optional[str] = None,
    ) -> ResidentModel:
        """The resident entry for ``name`` (loading + possibly evicting
        on a miss), pinned against eviction until :meth:`release`.

        Keys are case-folded: the named-model registry resolves names
        case-insensitively, so "MobileNetV2" and "mobilenetv2" MUST hit
        one resident copy — two would double-charge the HBM budget.
        ``precision`` is part of the key: each rung is a distinct
        loaded program (distinct params dtype, distinct jit caches), so
        a bf16 interactive arm and an f32 batch arm of the same model
        coexist as two honest residency entries."""
        precision = precision or "f32"
        key = (str(name).lower(), str(mode), str(precision))
        with self._lock:
            entry = self._models.get(key)
            if entry is not None:
                entry.pins += 1
                entry.requests += 1
                entry.last_used = time.monotonic()
                return entry
            load_lock = self._load_locks.setdefault(
                key,
                locksmith.lock(
                    "sparkdl_tpu/serving/residency.py::"
                    "ResidencyManager._load_locks"
                ),
            )
        with load_lock:
            # double-check: a racing first request may have loaded it
            with self._lock:
                entry = self._models.get(key)
                if entry is not None:
                    entry.pins += 1
                    entry.requests += 1
                    entry.last_used = time.monotonic()
                    return entry
            try:
                entry = self._load(key, name, mode, precision)
                with self._lock:
                    # install and drop the reservation in ONE locked
                    # section — a concurrent budget check must never see
                    # the model counted both resident and reserved
                    self._models[key] = entry
                    self._reserved.pop(key, None)
                    entry.pins += 1
                    entry.requests += 1
                    self._publish_gauges_locked()
                return entry
            finally:
                with self._lock:  # no-op on success; frees a failed load
                    self._reserved.pop(key, None)

    def release(self, entry: ResidentModel) -> None:
        with self._lock:
            entry.pins = max(0, entry.pins - 1)
            entry.last_used = time.monotonic()

    def _mesh_election(self, name: str, mf) -> Optional[int]:
        """The mesh width this model's programs build at: the loader's
        ModelFunction may elect (``mf.mesh``), else the registry spec,
        else the default 'dp' fan-out; ``'none'`` (or a whole-mesh
        single_stream program, which owns its own sharding) pins
        single-chip. Returns None for "legacy inference-mode behavior"
        when no explicit serving width is configured."""
        election = getattr(mf, "mesh", None)
        if election is None:
            try:
                from sparkdl_tpu.models import get_model

                election = getattr(get_model(name), "mesh", "dp")
            except Exception:  # noqa: BLE001 — custom-loader name
                election = "dp"
        if election == "none" or getattr(mf, "single_stream", False):
            return 1
        from sparkdl_tpu.transformers.execution import serve_mesh_width

        return serve_mesh_width()

    @staticmethod
    def _effective_width(mf, election: Optional[int]) -> int:
        """The mesh width ``model_device_fn`` WILL build at, computed
        without building it — the per-chip byte charge must be known
        before eviction runs, and eviction must run before the device
        fn exists (a jit build under ``SPARKDL_PARAM_PLACEMENT=chunked``
        places the full param tree on device; doing that while the
        evictable models still hold their HBM is exactly the OOM the
        budget exists to prevent)."""
        from sparkdl_tpu.transformers.execution import (
            inference_devices,
            inference_mode,
        )

        if getattr(mf, "single_stream", False):
            return 1
        n = len(inference_devices())
        if election is not None:
            return max(1, min(int(election), n))
        return max(1, n) if inference_mode() == "shard_map" else 1

    def _load(self, key, name: str, mode: str, precision: str) -> ResidentModel:
        from sparkdl_tpu.graph.precision import apply_precision
        from sparkdl_tpu.models.registry import param_bytes
        from sparkdl_tpu.obs import memory as mem_mod
        from sparkdl_tpu.obs import span
        from sparkdl_tpu.transformers.execution import model_device_fn

        # Ground-truth baseline BEFORE any allocation this load makes:
        # the measured-bytes delta and the evict-time leak check both
        # reference it.
        truth0, _src0 = mem_mod.ground_truth_bytes()
        tracked0 = mem_mod.tracked_bytes()
        if mode == "generate":
            return self._load_generator(key, name, precision, truth0, tracked0)
        try:
            with span(
                "serve.model_load", model=name, mode=mode,
                precision=precision,
            ):
                if self._loader_takes_precision:
                    mf = self._loader(name, mode, precision)
                else:
                    mf = self._loader(name, mode)
                # The rung's param/edge casts apply uniformly — a loader
                # that already built at the rung (tagged mf.precision) is
                # left alone; everyone else (the default registry loader,
                # every custom test/smoke loader) gets the standard wrap.
                mf = apply_precision(mf, precision)
                nbytes = param_bytes(mf)
                election = self._mesh_election(name, mf)
                mesh_width = self._effective_width(mf, election)
                if getattr(mf, "params_sharded", False) and mesh_width > 1:
                    # Tensor/weight-sharded mesh programs hold 1/width of
                    # the pytree per chip; charging the full bytes would
                    # under-fill the budget by exactly the mesh width (the
                    # single-device assumption this sizing used to bake in).
                    nbytes = -(-nbytes // mesh_width)
                # Evict BEFORE the device fn exists: its jit build may
                # place params on device (chunked param placement), and
                # that copy must land in freed budget, not beside victims.
                self._evict_for(key, nbytes, loading=name)
                device_fn = model_device_fn(mf, mesh_width=election)
                mesh_width = int(
                    getattr(device_fn, "mesh_width", mesh_width)
                )
        except Exception as e:
            if mem_mod.is_oom_error(e):
                mem_mod.record_oom("load", name, e)
            raise
        # Measured-on-load bytes: the ground-truth delta across the
        # whole load (params + device copies). The budget charge runs
        # on the measurement only where ground truth is the backend's
        # own allocator (`memory_stats`) — the live_arrays fallback
        # sees the whole probe window (host-side copies, jit
        # constants, concurrent loads) and would over-charge CPU runs.
        truth1, src1 = mem_mod.ground_truth_bytes()
        measured = None
        if truth0 is not None and truth1 is not None and truth1 > truth0:
            measured = int(truth1 - truth0)
            if getattr(mf, "params_sharded", False) and mesh_width > 1:
                measured = -(-measured // mesh_width)
        charge = nbytes
        if measured is not None and src1 == "memory_stats":
            charge = measured
        metrics.inc("serve.model_loads")
        flops = flops_fn = None
        try:
            from sparkdl_tpu.models import get_model

            spec = get_model(name)
            flops = spec.flops_per_item()
            flops_fn = getattr(spec, "flops_fn", None)
        except Exception:  # noqa: BLE001 — custom-loader name / no spec
            flops = flops_fn = None
        entry = ResidentModel(
            key, name, mode, mf, device_fn, charge,
            precision=precision, mesh_width=mesh_width,
            flops_per_item=flops, flops_fn=flops_fn,
        )
        entry.estimate_bytes = int(nbytes)
        entry.measured_bytes = measured
        entry.mem_charge = (charge, entry.mesh_width)
        entry.mem_baseline = (truth0, tracked0)
        mem_mod.note_model_loaded(name, charge, width=entry.mesh_width)
        if measured is not None:
            # estimate drift is published regardless of which probe
            # measured it — the gauge is the drift report, the budget
            # feedback above is the part that demands allocator truth
            metrics.gauge(
                f"mem.estimate_error.{name}", measured - int(nbytes)
            )
        return entry

    def _load_generator(
        self, key, name: str, precision: str, truth0, tracked0
    ) -> ResidentModel:
        """Generate-mode load: the loader returns a generator object
        (``BertGenerator``-shaped: ``prefill``/``decode_step``/
        ``kv_bytes_per_token``/``param_bytes``) rather than a
        ModelFunction, so the precision wrap, mesh election, and
        device-fn build are all skipped — the engine drives the
        generator's own jit programs directly. Budget/eviction/ledger
        bookkeeping is identical to the embed path: the param tree is
        a resident charge, evictable when no stream pins it."""
        from sparkdl_tpu.models.registry import param_bytes
        from sparkdl_tpu.obs import memory as mem_mod
        from sparkdl_tpu.obs import span

        try:
            with span(
                "serve.model_load", model=name, mode="generate",
                precision=precision,
            ):
                if self._loader_takes_precision:
                    gen = self._loader(name, "generate", precision)
                else:
                    gen = self._loader(name, "generate")
                nbytes = int(
                    getattr(gen, "param_bytes", 0) or param_bytes(gen)
                )
                self._evict_for(key, nbytes, loading=name)
        except Exception as e:
            if mem_mod.is_oom_error(e):
                mem_mod.record_oom("load", name, e)
            raise
        metrics.inc("serve.model_loads")
        entry = ResidentModel(
            key, name, "generate", gen, None, nbytes,
            precision=precision, mesh_width=1,
        )
        entry.mem_charge = (nbytes, 1)
        entry.mem_baseline = (truth0, tracked0)
        mem_mod.note_model_loaded(name, nbytes, width=1)
        return entry

    # -- eviction -----------------------------------------------------------

    def _evict_for(self, key, incoming_bytes: int, loading: str) -> None:
        """Make room for ``incoming_bytes`` under the budget by closing
        LRU idle models, then RESERVE the bytes (released when the load
        lands or fails) so a concurrent load of a different model sees
        them. Raises when the budget cannot be met — either the new
        model alone exceeds it (a configuration error worth failing
        loudly) or everything resident is busy (the caller's request
        should fail/retry rather than evict live work)."""
        budget = self._budget()
        if budget is None:
            return
        while True:
            with self._lock:
                used = (
                    sum(m.param_bytes for m in self._models.values())
                    + sum(self._reserved.values())
                    + self._kv_bytes
                )
                if used + incoming_bytes <= budget:
                    self._reserved[key] = incoming_bytes
                    return
                idle = [
                    m for m in self._models.values() if not m.busy
                ]
                if not idle:
                    raise RuntimeError(
                        f"cannot load model {loading!r} "
                        f"({incoming_bytes / 2**20:.1f} MB): HBM budget "
                        f"{budget / 2**20:.1f} MB has "
                        f"{used / 2**20:.1f} MB resident/reserved and "
                        "nothing idle to evict (open streams or loads "
                        "in flight)"
                    )
                victim = min(idle, key=lambda m: m.last_used)
                del self._models[victim.key]
                self._publish_gauges_locked()
            self._close_entry(victim)

    def _close_entry(self, victim: ResidentModel) -> None:
        from sparkdl_tpu.obs import append_jsonl
        from sparkdl_tpu.runtime.feeder import close_feeders_for

        closed = close_feeders_for(victim.device_fn)
        self._release_memory(victim)
        metrics.inc("serve.evictions")
        append_jsonl(
            {
                "kind": "serve_eviction",
                "ts": round(time.time(), 3),
                "model": victim.name,
                "mode": victim.mode,
                "param_mb": round(victim.param_bytes / 2**20, 2),
                "feeders_closed": closed,
                "requests_served": victim.requests,
            }
        )

    @staticmethod
    def _release_memory(victim: ResidentModel) -> None:
        """Evict-side memory bookkeeping: subtract the exact charge
        the load noted, DROP the entry's strong param refs (the entry
        itself must not be what keeps the pytree alive), then assert
        ground truth returned to the pre-load baseline — the leak
        detector."""
        from sparkdl_tpu.obs import memory as mem_mod

        charge, baseline = victim.mem_charge, victim.mem_baseline
        if charge is not None:
            mem_mod.note_model_evicted(
                victim.name, charge[0], width=charge[1]
            )
            victim.mem_charge = None
        victim.model_function = None
        victim.device_fn = None
        if baseline is not None:
            mem_mod.leak_check(victim.name, baseline[0], baseline[1])
            victim.mem_baseline = None

    def unload_all(self) -> None:
        """Evict everything (shutdown/tests); busy models too — the
        router guarantees no requests are in flight when it calls this."""
        with self._lock:
            victims = list(self._models.values())
            self._models.clear()
            self._publish_gauges_locked()
        from sparkdl_tpu.runtime.feeder import close_feeders_for

        for v in victims:
            close_feeders_for(v.device_fn)
            self._release_memory(v)


__all__ = ["ResidencyManager", "ResidentModel", "hbm_budget_bytes"]
