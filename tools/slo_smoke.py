"""SLO + goodput smoke: prove burn-rate alerting BOTH directions and
utilization conservation on CPU — the acceptance drill for
docs/OBSERVABILITY.md "SLOs and burn-rate alerts" / "Device
utilization".

One in-process Router + HTTP server (the chaos-models loader) under
scaled-down windows (fast 1.5 s / slow 6 s), availability armed for
every class and a p95 objective on ``interactive``:

1. **no false alert**: a healthy mixed flood (3 classes, single- and
   multi-row) trips NOTHING — ``/v1/slo`` shows every class untripped,
   no ``{"kind": "slo_alert"}`` event, every ``slo_alert_*`` gauge 0;
2. **conservation**: over that measured flood, the goodput ledger's
   per-device ``busy + idle`` equals the smoke's own externally
   measured wall within ``max(10 ms, 5%)``, with busy > 0 — the
   wall-clock bookkeeping is checked against a clock the ledger never
   saw;
3. **deterministic trip**: an injected-latency fault plan
   (``site=serve.request:cls=interactive:times=0:sleep=...`` — the
   straggler action, every interactive request) pushes every
   interactive completion past its p95 target; the fast-burn alert
   trips within the scaled window, the JSONL event names the class,
   both windows, burn rates, and exemplar trace ids that RESOLVE in
   the trace store, and ``dump_on_failure`` left an ``obs-slo_burn-*``
   snapshot naming the class;
4. **recovery**: clearing the plan and flooding healthy traffic clears
   the alert — distinct ``{"kind": "slo_recovery"}`` event, sticky
   gauge back to 0;
5. **on-demand profiling**: ``POST /admin/profile`` answers 200 with a
   real run directory, or degrades to a clean 501 where this build's
   profiler backend is unavailable (both are correct; 500 is not).

Standard closing checks: no leaked ``sparkdl-*`` threads, lock
sanitizer verdict clean when run under ``SPARKDL_LOCK_SANITIZER=1``
(preflight does). Exit 0 + one-line JSON verdict on success::

    JAX_PLATFORMS=cpu python tools/slo_smoke.py [--out-dir D]
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("SPARKDL_INFERENCE_MODE", "roundrobin")
os.environ.setdefault("SPARKDL_INFERENCE_DEVICES", "1")
os.environ.setdefault("SPARKDL_FEEDER_IDLE_S", "0")

FAST_S = 1.5
SLOW_S = 6.0
P95_TARGET_MS = 300.0
FAULT_SLEEP_S = 0.5
os.environ["SPARKDL_SLO_FAST_S"] = str(FAST_S)
os.environ["SPARKDL_SLO_SLOW_S"] = str(SLOW_S)
os.environ["SPARKDL_SLO_BURN_FAST"] = "10"
os.environ["SPARKDL_SLO_BURN_SLOW"] = "2"
os.environ["SPARKDL_SLO_MIN_REQUESTS"] = "3"
os.environ["SPARKDL_SLO_AVAIL"] = "0.99"
os.environ["SPARKDL_SLO_P95_MS_INTERACTIVE"] = str(P95_TARGET_MS)

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


from _chaos_models import ROW  # noqa: E402

FAULT_PLAN = (
    f"site=serve.request:cls=interactive:times=0:sleep={FAULT_SLEEP_S}"
)
N_HEALTHY = 90
CONSERVATION_ABS_S = 0.010
CONSERVATION_REL = 0.05


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


def _healthy_flood(client, problems, verdict):
    """Mixed flood across all classes; returns the measured wall."""
    import numpy as np

    from sparkdl_tpu.obs import utilization

    rng = np.random.default_rng(7)
    jobs = []
    for i in range(N_HEALTHY):
        rows = 1 if i % 3 else 4
        cls = ("interactive", "batch", "background")[i % 3]
        jobs.append(
            (cls, rng.normal(size=(rows, ROW)).astype(np.float32))
        )
    utilization.reset()
    t0 = time.monotonic()

    def run_one(job):
        cls, x = job
        client.predict("prim", x, priority=cls, timeout=120)

    with ThreadPoolExecutor(
        max_workers=8, thread_name_prefix="slo-client"
    ) as pool:
        list(pool.map(run_one, jobs))
    wall = time.monotonic() - t0
    status = utilization.utilization_status()
    verdict["healthy_flood_wall_s"] = round(wall, 3)
    if status is None:
        problems.append("utilization ledger empty after a real flood")
        return wall
    verdict["busy_frac"] = status["busy_frac"]
    tol = max(CONSERVATION_ABS_S, CONSERVATION_REL * wall)
    for d, st in status["devices"].items():
        busy_idle_s = (st["busy_ms"] + st["idle_ms"]) / 1e3
        # exact by construction, modulo the status dict's 3-decimal ms
        # rounding (three independently rounded terms: up to ~2 µs)
        if abs(busy_idle_s - st["wall_ms"] / 1e3) > 5e-6:
            problems.append(
                f"device {d}: busy+idle {busy_idle_s:.4f}s != ledger "
                f"wall {st['wall_ms'] / 1e3:.4f}s (internal "
                "conservation broke)"
            )
        # the external check: the ledger's wall vs OUR clock around
        # the flood (the ledger starts at the first program, so it may
        # run a hair short of the submit-to-result wall, never long)
        if abs(busy_idle_s - wall) > tol:
            problems.append(
                f"device {d}: busy+idle {busy_idle_s:.4f}s vs measured "
                f"flood wall {wall:.4f}s exceeds max({CONSERVATION_ABS_S}s, "
                f"{CONSERVATION_REL:.0%})"
            )
        if st["busy_ms"] <= 0:
            problems.append(f"device {d}: zero busy time over a flood")
    return wall


def _assert_untripped(port, problems, where):
    status, payload = _get(port, "/v1/slo")
    if status != 200 or not payload.get("armed"):
        problems.append(f"{where}: /v1/slo not armed: {payload}")
        return
    for cls, st in payload["classes"].items():
        if st.get("tripped"):
            problems.append(
                f"{where}: class {cls} tripped on a healthy flood: {st}"
            )


def _fault_phase(client, port, jsonl, problems, verdict):
    """Arm the sleep plan, flood interactive, wait for the trip."""
    import numpy as np

    from sparkdl_tpu.obs.trace import get_store

    os.environ["SPARKDL_FAULT_PLAN"] = FAULT_PLAN
    stop = threading.Event()
    errors = []

    def flood():
        x = np.zeros((1, ROW), np.float32)
        while not stop.is_set():
            try:
                client.predict(
                    "prim", x, priority="interactive", timeout=120
                )
            except Exception as e:  # noqa: BLE001
                errors.append(f"{type(e).__name__}: {e}")
                return

    threads = [
        threading.Thread(
            target=flood, name=f"sparkdl-slo-fault-{k}", daemon=False
        )
        for k in range(4)
    ]
    for t in threads:
        t.start()
    tripped = False
    deadline = time.monotonic() + 30.0
    try:
        while time.monotonic() < deadline:
            _, payload = _get(port, "/v1/slo")
            st = (payload.get("classes") or {}).get("interactive") or {}
            if st.get("tripped"):
                tripped = True
                break
            time.sleep(0.25)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        os.environ.pop("SPARKDL_FAULT_PLAN", None)
    if errors:
        problems.append(f"fault-phase request errors: {errors[:2]}")
    if not tripped:
        problems.append(
            "interactive SLO never tripped under the injected-latency "
            f"plan within 30s (plan {FAULT_PLAN!r})"
        )
        return
    alerts = [
        e for e in _events(jsonl, "slo_alert")
        if e.get("cls") == "interactive"
    ]
    if not alerts:
        problems.append("tripped but no {'kind':'slo_alert'} JSONL event")
        return
    alert = alerts[0]
    verdict["alert"] = {
        k: alert.get(k)
        for k in (
            "cls", "objective", "burn_fast", "burn_slow",
            "fast_window_s", "slow_window_s",
        )
    }
    for key in (
        "objective", "burn_fast", "burn_slow", "fast_window_s",
        "slow_window_s",
    ):
        if alert.get(key) is None:
            problems.append(f"slo_alert event missing {key!r}: {alert}")
    exemplars = alert.get("exemplar_trace_ids") or []
    if not exemplars:
        problems.append(f"slo_alert carries no exemplar trace ids: {alert}")
        return
    resolved = [tid for tid in exemplars if get_store().get(tid)]
    if not resolved:
        problems.append(
            f"no alert exemplar resolves in the trace store: {exemplars}"
        )
    else:
        verdict["alert_exemplar"] = resolved[0]


def _recovery_phase(client, port, jsonl, problems, verdict):
    import numpy as np

    x = np.zeros((1, ROW), np.float32)
    cleared = False
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        for _ in range(4):
            client.predict("prim", x, priority="interactive", timeout=120)
        _, payload = _get(port, "/v1/slo")
        st = (payload.get("classes") or {}).get("interactive") or {}
        if not st.get("tripped"):
            cleared = True
            break
        time.sleep(0.2)
    if not cleared:
        problems.append(
            "interactive SLO stayed tripped 20s after the fault cleared"
        )
        return
    recoveries = [
        e for e in _events(jsonl, "slo_recovery")
        if e.get("cls") == "interactive"
    ]
    if not recoveries:
        problems.append(
            "alert cleared but no {'kind':'slo_recovery'} JSONL event"
        )
    from sparkdl_tpu.utils.metrics import metrics

    gauge = metrics.snapshot()["gauges"].get("slo.alert.interactive")
    if gauge != 0:
        problems.append(f"slo.alert.interactive gauge is {gauge}, not 0")
    verdict["recovered"] = cleared


def _profile_probe(port, problems, verdict):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/admin/profile",
        data=json.dumps({"seconds": 0.2}).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            body = json.loads(resp.read())
            if not os.path.isdir(body.get("path", "")):
                problems.append(
                    f"/admin/profile 200 but path missing: {body}"
                )
            verdict["profile"] = {"status": 200, "path": body.get("path")}
    except urllib.error.HTTPError as e:
        if e.code != 501:
            problems.append(
                f"/admin/profile failed with {e.code} (only 200 or a "
                f"clean 501 degrade are acceptable): {e.read()[:200]}"
            )
        else:
            verdict["profile"] = {"status": 501}


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
    root = args.out_dir or tempfile.mkdtemp(prefix="slo_smoke_")
    os.makedirs(root, exist_ok=True)
    jsonl = os.path.join(root, "events.jsonl")
    dump_dir = os.path.join(root, "dumps")
    os.environ["SPARKDL_OBS_JSONL"] = jsonl
    os.environ["SPARKDL_OBS_DUMP_DIR"] = dump_dir
    os.environ["SPARKDL_PROFILE_DIR"] = os.path.join(root, "profiles")

    problems = []
    verdict = {"out_dir": root}

    from _chaos_models import loader

    import numpy as np

    from sparkdl_tpu.obs import slo, utilization
    from sparkdl_tpu.obs import trace as trace_mod
    from sparkdl_tpu.serving import Router, ServingClient
    from sparkdl_tpu.serving.server import ServingServer

    slo.reset()
    utilization.reset()
    trace_mod.reset()
    router = Router(loader=loader, max_batch=8)
    client = ServingClient(router)
    server = ServingServer(router, port=0)
    try:
        # warm/compile outside every measured window
        client.predict(
            "prim", np.zeros((1, ROW), np.float32), timeout=300
        )
        _healthy_flood(client, problems, verdict)
        _assert_untripped(server.port, problems, "healthy flood")
        if _events(jsonl, "slo_alert"):
            problems.append("healthy flood emitted an slo_alert event")
        # the healthy interactive traffic must age out of the SLOW
        # window before the fault, or its good events dilute the slow
        # burn below threshold and the trip waits on decay, not on us
        time.sleep(SLOW_S + 2 * slo.get_engine().bucket_s)
        _fault_phase(client, server.port, jsonl, problems, verdict)
        dumps = (
            [p for p in os.listdir(dump_dir) if "slo_burn" in p]
            if os.path.isdir(dump_dir)
            else []
        )
        if verdict.get("alert") and not dumps:
            problems.append("trip fired but no obs-slo_burn-* dump landed")
        verdict["dumps"] = len(dumps)
        _recovery_phase(client, server.port, jsonl, problems, verdict)
        _profile_probe(server.port, problems, verdict)
    finally:
        server.stop(close_router=True)
        os.environ.pop("SPARKDL_OBS_JSONL", None)
        os.environ.pop("SPARKDL_OBS_DUMP_DIR", None)
        os.environ.pop("SPARKDL_PROFILE_DIR", None)

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
        "slo_smoke": "FAIL" if problems else "OK",
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
