"""Device-memory smoke: prove the HBM ledger end to end on CPU — the
acceptance drill for docs/OBSERVABILITY.md "Device memory".

One in-process Router + HTTP server (the chaos-models loader) with TWO
models under a budget that holds only one at a time, so the flood
churns real load/evict cycles:

1. **attribution + watermark**: an alternating two-model flood leaves
   exactly one model's bytes tracked at steady state, with the
   watermark strictly above it (the staged/readback traffic and the
   second model peaked through); the watermark ring banked samples;
2. **reconciliation**: ``/v1/memory`` reports ground truth from a real
   probe (``live_arrays`` on CPU) with ``mem.unattributed_bytes``
   bounded — the ledger's story stays within shouting distance of
   what the backend admits to;
3. **OOM forensics**: an injected allocation failure
   (``site=serve.request:model=...:raise=MemoryError``) fails that
   request AND lands a ``{"kind": "oom"}`` JSONL event plus an
   ``obs-oom-*`` dump whose per-model table names the models resident
   at failure;
4. **evict-to-baseline**: closing the router unloads everything —
   tracked bytes return to ZERO and the clean path emits no
   ``{"kind": "mem_leak"}`` event (the leak detector ran on every
   evict and stayed quiet).

Standard closing checks: no leaked ``sparkdl-*`` threads, lock
sanitizer verdict clean when run under ``SPARKDL_LOCK_SANITIZER=1``
(preflight does). Exit 0 + one-line JSON verdict on success::

    JAX_PLATFORMS=cpu python tools/memory_smoke.py [--out-dir D]
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("SPARKDL_INFERENCE_MODE", "roundrobin")
os.environ.setdefault("SPARKDL_INFERENCE_DEVICES", "1")
os.environ.setdefault("SPARKDL_FEEDER_IDLE_S", "0")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


from _chaos_models import ROW  # noqa: E402

#: chaos-models params are 8x4 f32 = 128 bytes; this budget admits one
#: model but never two, so the alternating flood MUST evict every swap
BUDGET_BYTES = 200
N_FLOOD = 40
#: live_arrays ground truth on CPU counts jit-cache constants and every
#: committed array in the process — "bounded" means the unattributed
#: gap stays within one generous envelope, not that it is zero
UNATTRIBUTED_CAP = 64 * 2**20
FAULT_PLAN = "site=serve.request:model=beta:raise=MemoryError"


def _get(port, path, timeout=10):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as resp:
        return resp.status, json.loads(resp.read())


def _events(jsonl_path, kind):
    out = []
    try:
        with open(jsonl_path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if ev.get("kind") == kind:
                    out.append(ev)
    except OSError:
        pass
    return out


def _flood_phase(client, port, problems, verdict):
    """Alternating two-model flood under the one-model budget."""
    import numpy as np

    from sparkdl_tpu.utils.metrics import metrics

    rng = np.random.default_rng(3)
    evictions0 = metrics.counter("serve.evictions")

    def run_one(i):
        model = ("alpha", "beta")[i % 2]
        rows = 1 if i % 3 else 4
        x = rng.normal(size=(rows, ROW)).astype(np.float32)
        client.predict(model, x, timeout=300)

    # sequential on purpose: concurrent groups for both models would
    # deadlock the tiny budget (each pins its model; nothing is idle) —
    # the serving layer handles that by failing the load, but this
    # phase measures churn, not contention
    for i in range(N_FLOOD):
        run_one(i)
    evictions = metrics.counter("serve.evictions") - evictions0
    verdict["evictions"] = int(evictions)
    if evictions < N_FLOOD - 4:
        problems.append(
            f"only {evictions} evictions over {N_FLOOD} alternating "
            "requests under a one-model budget — residency churn did "
            "not engage the ledger"
        )

    status, payload = _get(port, "/v1/memory")
    verdict["memory"] = {
        k: payload.get(k)
        for k in (
            "tracked_bytes", "watermark_bytes", "unattributed_bytes",
            "ground_truth_source", "leaked_bytes", "oom_events",
        )
    }
    if status != 200:
        problems.append(f"/v1/memory returned {status}")
        return
    if payload.get("budget_bytes") != BUDGET_BYTES:
        problems.append(
            f"/v1/memory budget_bytes {payload.get('budget_bytes')} != "
            f"the router's {BUDGET_BYTES}"
        )
    # steady state: exactly one model resident (128 bytes tracked)
    tracked = payload.get("tracked_bytes") or 0
    if not 0 < tracked <= BUDGET_BYTES:
        problems.append(
            f"steady-state tracked_bytes {tracked} outside "
            f"(0, {BUDGET_BYTES}] — attribution drifted from residency"
        )
    if len(payload.get("models") or {}) != 1:
        problems.append(
            f"steady state should hold ONE resident model, ledger says: "
            f"{payload.get('models')}"
        )
    # the watermark saw the flood's staged/readback traffic on top of
    # the resident params: strictly above the quiesced steady state
    if not payload.get("watermark_bytes", 0) > tracked:
        problems.append(
            f"watermark {payload.get('watermark_bytes')} not above "
            f"steady-state tracked {tracked} — transfer traffic was "
            "never attributed"
        )
    if payload.get("ground_truth_bytes") is None:
        problems.append("no ground-truth probe available (CPU should "
                        "fall back to live_arrays)")
    unattr = payload.get("unattributed_bytes")
    if unattr is None or abs(unattr) > UNATTRIBUTED_CAP:
        problems.append(
            f"unattributed_bytes {unattr} outside +/-"
            f"{UNATTRIBUTED_CAP} — reconciliation is lying"
        )

    from sparkdl_tpu.obs import timeseries as ts

    if not ts.mem_series():
        problems.append("watermark ring banked no samples over the flood")


def _oom_phase(client, jsonl, dump_dir, problems, verdict):
    """Inject an allocation failure and demand its forensics."""
    import numpy as np

    os.environ["SPARKDL_FAULT_PLAN"] = FAULT_PLAN
    try:
        try:
            client.predict(
                "beta", np.zeros((1, ROW), np.float32), timeout=300
            )
            problems.append("injected MemoryError did not fail the request")
        except MemoryError:
            pass
        except Exception as e:  # noqa: BLE001
            problems.append(
                f"injected MemoryError surfaced as {type(e).__name__}: {e}"
            )
    finally:
        os.environ.pop("SPARKDL_FAULT_PLAN", None)
    ooms = _events(jsonl, "oom")
    if len(ooms) != 1:
        problems.append(
            f"expected exactly one {{'kind':'oom'}} event, got {len(ooms)}"
        )
        return
    ev = ooms[0]
    verdict["oom_event"] = {
        "phase": ev.get("phase"),
        "model": ev.get("model"),
        "models": sorted(ev.get("models") or {}),
    }
    if ev.get("phase") != "dispatch" or ev.get("model") != "beta":
        problems.append(f"oom event misattributed: {ev}")
    if not ev.get("models"):
        problems.append("oom event carries an empty per-model table")
    if not ev.get("recent_allocations"):
        problems.append("oom event carries no allocation-ring tail")
    dumps = (
        [p for p in os.listdir(dump_dir) if "oom" in p]
        if os.path.isdir(dump_dir)
        else []
    )
    verdict["dumps"] = len(dumps)
    if not dumps:
        problems.append("oom recorded but no obs-oom-* dump landed")
        return
    with open(os.path.join(dump_dir, dumps[0])) as f:
        snap = json.load(f)
    table = (snap.get("memory") or {}).get("models")
    if not table:
        problems.append(
            "oom dump's memory key names no resident models — the "
            "forensic table is the point of the dump"
        )
    else:
        verdict["dump_resident_table"] = sorted(table)


def _baseline_phase(jsonl, problems, verdict):
    """Post-close: the ledger must be back at zero with no leak page."""
    from sparkdl_tpu.obs import memory
    from sparkdl_tpu.utils.metrics import metrics

    tracked = memory.tracked_bytes()
    if tracked != 0:
        problems.append(
            f"{tracked} bytes still tracked after unload_all — evict "
            "bookkeeping does not conserve"
        )
    leaks = _events(jsonl, "mem_leak")
    if leaks:
        problems.append(
            f"clean load/evict path emitted {len(leaks)} mem_leak "
            f"event(s): {leaks[:1]}"
        )
    gauges = metrics.snapshot()["gauges"]
    if gauges.get("mem.device_bytes.0") != 0:
        problems.append(
            f"mem.device_bytes.0 gauge is {gauges.get('mem.device_bytes.0')}"
            ", not 0, after unload"
        )
    verdict["leaked_bytes"] = int(metrics.counter("mem.leaked_bytes"))


def _leaked_threads():
    return [
        t
        for t in threading.enumerate()
        if t.is_alive() and t.name.startswith("sparkdl-")
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--out-dir", default=None,
        help="event log + failure dumps land here (default: a temp dir)",
    )
    args = ap.parse_args(argv)
    root = args.out_dir or tempfile.mkdtemp(prefix="memory_smoke_")
    os.makedirs(root, exist_ok=True)
    jsonl = os.path.join(root, "events.jsonl")
    dump_dir = os.path.join(root, "dumps")
    os.environ["SPARKDL_OBS_JSONL"] = jsonl
    os.environ["SPARKDL_OBS_DUMP_DIR"] = dump_dir

    problems = []
    verdict = {"out_dir": root}

    from _chaos_models import loader

    import numpy as np

    from sparkdl_tpu.obs import memory
    from sparkdl_tpu.obs import timeseries as ts
    from sparkdl_tpu.serving import Router, ServingClient
    from sparkdl_tpu.serving.server import ServingServer

    memory.reset()
    ts.mem_clear()
    router = Router(loader=loader, budget_bytes=BUDGET_BYTES, max_batch=8)
    client = ServingClient(router)
    server = ServingServer(router, port=0)
    try:
        # warm/compile both models once (each load evicts the other)
        for name in ("alpha", "beta"):
            client.predict(
                name, np.zeros((1, ROW), np.float32), timeout=300
            )
        _flood_phase(client, server.port, problems, verdict)
        _oom_phase(client, jsonl, dump_dir, problems, verdict)
    finally:
        server.stop(close_router=True)
        os.environ.pop("SPARKDL_OBS_JSONL", None)
        os.environ.pop("SPARKDL_OBS_DUMP_DIR", None)
    _baseline_phase(jsonl, problems, verdict)

    from sparkdl_tpu.runtime.feeder import shutdown_feeders

    shutdown_feeders()
    leaked = _leaked_threads()
    if leaked:
        time.sleep(0.5)
        leaked = _leaked_threads()
    if leaked:
        problems.append(
            "leaked threads after smoke: "
            + ", ".join(t.name for t in leaked)
        )

    lock_problems, lock_stats = _common.lock_sanitizer_problems()
    problems += lock_problems
    verdict.update(lock_stats)

    verdict = {
        "memory_smoke": "FAIL" if problems else "OK",
        "plan": FAULT_PLAN,
        **verdict,
    }
    if problems:
        verdict["problems"] = problems
        print(json.dumps(verdict), file=sys.stderr)
        return 1
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
