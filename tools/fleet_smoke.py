"""Fleet observability smoke: prove the gateway's fused fleet plane on
CPU — the acceptance drill for docs/OBSERVABILITY.md "Fleet view".

One in-process :class:`ServingGateway` fronts 2 worker subprocesses
(the chaos-models loader) under scaled SLO windows with a p95
objective on ``interactive`` and ``SPARKDL_SLO_MIN_REQUESTS=8``. A
fault plan makes exactly the first 12 interactive requests slow
(``times=12:sleep=0.5``), round-robined 6/6 across the gang — each
worker sees 6 fast-window events, UNDER its own floor. Asserts:

- **fleet-level trip, per-worker quiet**: the gateway's fleet SLO
  fusion (burns over the SUMMED windowed counts) trips
  ``interactive`` while BOTH workers' own ``/v1/slo`` stay untripped —
  the sub-floor asymmetry the fleet plane exists for. The
  ``{"kind": "fleet_slo_alert"}`` JSONL event names both contributing
  ranks and exemplar trace ids drawn from the flood's own replies
  (reply trace ids ARE store-resolvable ids — the worker minted them);
- **federated /metrics**: one 200 text exposition carrying
  rank-labeled lines from BOTH workers, the fleet aggregate gauges,
  and a ``fleet_busy_frac`` that agrees with ``GET /v1/fleet``'s fused
  ``busy_frac`` within rounding;
- **recovery**: a healthy interactive flood (faults exhausted) dilutes
  the burn below threshold — distinct ``fleet_slo_recovery`` event,
  sticky gauge back to 0 in the federated text;
- **advisory only**: at least one ``{"kind": "fleet_recommendation"}``
  event with evidence (busy fraction, ready workers, burns) landed,
  and the gang still has exactly 2 workers — the recommender actuated
  nothing;
- **churn degrades, never 500s**: SIGKILL one worker mid-scrape — the
  federated ``/metrics`` keeps answering 200, the dead rank degrades
  to a ``fleet_scrape_stale{rank=...} 1`` marker, NO new fleet alert
  is fabricated, and after the supervisor's gang restart (generation
  1) the fleet view converges back to 2 fresh workers with reset rate
  baselines (no negative/poisoned aggregates);
- **no leaked ``sparkdl-*`` threads** after ``gateway.stop()``, plus
  the lock-sanitizer verdict when preflight runs this under
  ``SPARKDL_LOCK_SANITIZER=1``.

Exit 0 and a one-line JSON verdict on success; exit 1 naming what
failed. Callable standalone or via tools/preflight.sh::

    JAX_PLATFORMS=cpu python tools/fleet_smoke.py [--out-dir D]
"""

import argparse
import json
import os
import re
import signal
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

# SLO windows wide enough to hold the whole smoke (recovery works by
# DILUTION, not aging); the floor is the star of this drill: 12 slow
# requests round-robin to 6 per worker — under 8 — while the fleet sum
# crosses it.
FAULT_SLEEP_S = 0.5
P95_TARGET_MS = 300.0
MIN_REQUESTS = 8
N_SLOW = 12
N_RECOVER = 60
os.environ["SPARKDL_SLO_FAST_S"] = "30"
os.environ["SPARKDL_SLO_SLOW_S"] = "120"
os.environ["SPARKDL_SLO_BURN_FAST"] = "10"
os.environ["SPARKDL_SLO_BURN_SLOW"] = "2"
os.environ["SPARKDL_SLO_MIN_REQUESTS"] = str(MIN_REQUESTS)
os.environ["SPARKDL_SLO_P95_MS_INTERACTIVE"] = str(P95_TARGET_MS)
os.environ.pop("SPARKDL_SLO_AVAIL", None)
os.environ["SPARKDL_FLEET_SCRAPE_S"] = "0.25"
os.environ["SPARKDL_FLEET_SCRAPE_TIMEOUT_S"] = "2"
os.environ["SPARKDL_FLEET_STALE_S"] = "1.5"
os.environ["SPARKDL_FLEET_RECOMMEND_S"] = "0.5"

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


from _chaos_models import ROW  # noqa: E402

NUM_WORKERS = 2
FAULT_PLAN = (
    f"site=serve.request:cls=interactive:times={N_SLOW}"
    f":sleep={FAULT_SLEEP_S}"
)


def _get_json(port, path, timeout=10):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as resp:
        return resp.status, json.loads(resp.read())


def _get_text(port, path, timeout=10):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as resp:
        return resp.status, resp.read().decode()


def _predict(port, rows, timeout=300):
    import numpy as np

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/predict",
        data=json.dumps(
            {
                "model": "prim",
                "inputs": np.asarray(rows).tolist(),
                "class": "interactive",
            }
        ).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _flood(gw_port, n, problems, phase):
    """n sequential-ish interactive requests (2 clients — the gateway
    round-robins, so the split stays 50/50); returns reply trace ids."""
    import numpy as np

    rng = np.random.default_rng(11)
    trace_ids = []
    lock = threading.Lock()

    def one(i):
        status, body = _predict(
            gw_port, rng.normal(size=(1, ROW)).astype(np.float32)
        )
        if status != 200:
            with lock:
                problems.append(f"{phase} flood request {i} -> {status}")
            return
        tid = body.get("trace_id")
        if tid:
            with lock:
                trace_ids.append(tid)

    with ThreadPoolExecutor(max_workers=2) as pool:
        list(pool.map(one, range(n)))
    return trace_ids


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


def _wait(predicate, timeout, interval=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            if predicate():
                return True
        except (urllib.error.URLError, OSError, json.JSONDecodeError):
            pass
        time.sleep(interval)
    return False


def _wait_ready(gw, want, timeout, generation=None):
    def ok():
        stats = gw.stats()
        ready = sum(
            1 for w in stats["workers"] if w["status"] == "ready"
        )
        return ready >= want and (
            generation is None or stats["generation"] == generation
        )

    return _wait(ok, timeout)


def _fleet_tripped(gw_port, cls="interactive"):
    _, fleet = _get_json(gw_port, "/v1/fleet")
    classes = ((fleet.get("fused") or {}).get("slo") or {}).get(
        "classes"
    ) or {}
    return bool(classes.get(cls, {}).get("tripped"))


def _metric_value(text, name):
    m = re.search(rf"^{re.escape(name)} ([0-9.eE+-]+)$", text, re.M)
    return float(m.group(1)) if m else None


def _check_trip_asymmetry(gw, jsonl, flood_ids, problems, verdict):
    """The tentpole claim: fleet tripped, every worker quiet."""
    if not _wait(lambda: _fleet_tripped(gw.port), timeout=20):
        _, fleet = _get_json(gw.port, "/v1/fleet")
        problems.append(
            "fleet SLO never tripped on interactive: "
            + json.dumps((fleet.get("fused") or {}).get("slo"))
        )
        return
    for w in gw.stats()["workers"]:
        if w["status"] != "ready" or not w.get("port"):
            continue
        _, wslo = _get_json(w["port"], "/v1/slo")
        if wslo.get("rank") != w["rank"]:
            problems.append(
                f"worker {w['rank']} /v1/slo rank field: "
                f"{wslo.get('rank')!r}"
            )
        for cls, st in (wslo.get("classes") or {}).items():
            if st.get("tripped"):
                problems.append(
                    f"worker {w['rank']} tripped {cls} locally — the "
                    "per-worker floor should have kept it quiet"
                )
        wins = (wslo.get("windows") or {}).get("interactive") or {}
        if wins.get("ok_fast", 0) >= MIN_REQUESTS:
            problems.append(
                f"worker {w['rank']} saw {wins.get('ok_fast')} fast "
                f"events — not under the floor ({MIN_REQUESTS}); the "
                "asymmetry claim is untested"
            )
    alerts = _events(jsonl, "fleet_slo_alert")
    if len(alerts) != 1:
        problems.append(
            f"expected exactly 1 fleet_slo_alert event, saw "
            f"{len(alerts)}"
        )
        return
    alert = alerts[0]
    if alert.get("cls") != "interactive":
        problems.append(f"fleet alert names class {alert.get('cls')!r}")
    if sorted(alert.get("ranks") or []) != [0, 1]:
        problems.append(
            f"fleet alert ranks {alert.get('ranks')!r} — both workers "
            "contributed slow events and both should be named"
        )
    exemplars = alert.get("exemplar_trace_ids") or []
    if not exemplars:
        problems.append("fleet alert carries no exemplar trace ids")
    elif not set(exemplars) & set(flood_ids):
        problems.append(
            "no fleet-alert exemplar id resolves to a flood reply "
            f"trace id (exemplars {exemplars[:3]}...)"
        )
    verdict["alert_ranks"] = alert.get("ranks")
    verdict["alert_exemplars"] = len(exemplars)


def _check_federation(gw, problems, verdict):
    """Both ranks in one exposition; busy_frac agrees with /v1/fleet."""
    status, text = _get_text(gw.port, "/metrics")
    if status != 200:
        problems.append(f"federated /metrics -> {status}")
        return
    for rank in range(NUM_WORKERS):
        if f'rank="{rank}"' not in text:
            problems.append(
                f"federated /metrics carries no rank={rank} lines"
            )
    if _metric_value(text, "fleet_ready_workers") != float(NUM_WORKERS):
        problems.append(
            "fleet_ready_workers gauge != 2 in federated /metrics"
        )
    # /v1/fleet and the exported gauge must tell the same busy story
    # (scrapes keep landing between the two GETs — retry, then allow
    # one cycle of drift)
    for _ in range(10):
        _, text = _get_text(gw.port, "/metrics")
        _, fleet = _get_json(gw.port, "/v1/fleet")
        gauge = _metric_value(text, "fleet_busy_frac")
        fused = (fleet.get("fused") or {}).get("busy_frac")
        if gauge is None and fused is None:
            return
        if (
            gauge is not None
            and fused is not None
            and abs(gauge - fused) <= 0.05
        ):
            verdict["busy_frac"] = fused
            return
        time.sleep(0.3)
    problems.append(
        f"federated fleet_busy_frac {gauge} never agreed with "
        f"/v1/fleet busy_frac {fused}"
    )


def _check_recovery(gw, jsonl, problems):
    if not _wait(
        lambda: not _fleet_tripped(gw.port), timeout=30
    ):
        problems.append(
            "fleet SLO alert never recovered after the healthy flood"
        )
        return
    if len(_events(jsonl, "fleet_slo_recovery")) != 1:
        problems.append("expected exactly 1 fleet_slo_recovery event")
    _, text = _get_text(gw.port, "/metrics")
    if _metric_value(text, "fleet_slo_alert_interactive") != 0.0:
        problems.append(
            "sticky fleet_slo_alert_interactive gauge not back to 0"
        )


def _check_recommendation(gw, jsonl, problems, verdict):
    recs = _events(jsonl, "fleet_recommendation")
    if not recs:
        problems.append("no fleet_recommendation JSONL event emitted")
        return
    evidenced = [
        r
        for r in recs
        if (r.get("evidence") or {}).get("busy_frac") is not None
        and (r.get("evidence") or {}).get("ready_workers")
    ]
    if not evidenced:
        problems.append(
            "no fleet_recommendation carries evidence (busy_frac + "
            "ready_workers)"
        )
    # the alert window should have driven at least one scale_up verdict
    if not any(r.get("action") == "scale_up" for r in recs):
        problems.append(
            "no scale_up recommendation during the fleet alert: "
            + json.dumps([r.get("action") for r in recs])
        )
    # advisory ONLY: the gang still has exactly NUM_WORKERS workers
    _, workers = _get_json(gw.port, "/v1/workers")
    if len(workers.get("workers") or []) != NUM_WORKERS:
        problems.append(
            f"worker count changed to {len(workers.get('workers'))} — "
            "the recommender must actuate nothing"
        )
    verdict["recommendations"] = [r.get("action") for r in recs]


def _check_churn(gw, jsonl, problems, verdict):
    """SIGKILL one worker mid-scrape: degrade, never 500, no false
    alert; the relaunched generation converges clean."""
    alerts_before = len(_events(jsonl, "fleet_slo_alert"))
    victim = next(
        w for w in gw.stats()["workers"] if w["rank"] == 1 and w["pid"]
    )
    os.kill(victim["pid"], signal.SIGKILL)

    def stale_marked():
        status, text = _get_text(gw.port, "/metrics")
        if status != 200:
            problems.append(f"federated /metrics -> {status} after kill")
            return True  # stop waiting; the problem is recorded
        return 'fleet_scrape_stale{rank="1"} 1' in text

    if not _wait(stale_marked, timeout=30):
        problems.append(
            "dead rank 1 never degraded to a stale-marked sample in "
            "the federated /metrics"
        )
    # the supervisor relaunches the gang at generation 1; the fleet
    # view must converge back to 2 fresh workers with the new
    # generation and sane (non-negative) rate baselines
    if not _wait_ready(gw, NUM_WORKERS, timeout=60, generation=1):
        problems.append(
            f"gang did not settle at generation 1: {gw.stats()}"
        )
        return

    def converged():
        _, fleet = _get_json(gw.port, "/v1/fleet")
        fused = fleet.get("fused") or {}
        gens = {
            w["rank"]: w.get("generation")
            for w in fleet.get("workers") or []
        }
        return (
            fused.get("ready_workers") == NUM_WORKERS
            and not fused.get("stale_ranks")
            and gens.get(0) == 1
            and gens.get(1) == 1
        )

    if not _wait(converged, timeout=30):
        _, fleet = _get_json(gw.port, "/v1/fleet")
        problems.append(
            "fleet view never converged on the generation-1 gang: "
            + json.dumps(fleet.get("workers"))
        )
    _, fleet = _get_json(gw.port, "/v1/fleet")
    rps = (fleet.get("fused") or {}).get("req_per_s")
    if rps is not None and rps < 0:
        problems.append(f"negative fused req_per_s {rps} after restart")
    if len(_events(jsonl, "fleet_slo_alert")) != alerts_before:
        problems.append(
            "worker churn fabricated a fleet SLO alert (empty "
            "generation-1 windows must not trip)"
        )
    verdict["churn"] = "degraded-then-converged"


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
        help="gang dir + event logs land here (default: a temp dir)",
    )
    args = ap.parse_args(argv)
    root = args.out_dir or tempfile.mkdtemp(prefix="fleet_smoke_")
    os.makedirs(root, exist_ok=True)
    gang_dir = os.path.join(root, "gang")
    jsonl = os.path.join(root, "events.jsonl")

    from sparkdl_tpu.resilience.policy import RetryPolicy
    from sparkdl_tpu.serving.gateway import ServingGateway

    problems = []
    verdict = {"out_dir": root}
    os.environ["SPARKDL_OBS_JSONL"] = jsonl
    gw = ServingGateway(
        num_workers=NUM_WORKERS,
        port=0,
        gang_dir=gang_dir,
        loader_spec="tools._chaos_models:loader",
        max_batch=32,
        extra_env={
            "JAX_PLATFORMS": "cpu",
            "SPARKDL_INFERENCE_MODE": "roundrobin",
            "SPARKDL_INFERENCE_DEVICES": "1",
            # exactly the first N_SLOW interactive requests are slow,
            # fleet-wide (the O_EXCL claim dir carries the cap across
            # workers and generations)
            "SPARKDL_FAULT_PLAN": FAULT_PLAN,
            "SPARKDL_FAULT_STATE": os.path.join(root, "faults"),
            "SPARKDL_FAULT_SEED": "0",
            "SPARKDL_OBS_JSONL": jsonl,
        },
        restart_policy=RetryPolicy(
            max_attempts=3, base_delay_s=0.2, max_delay_s=1.0, seed=0
        ),
        stale_after=30.0,
    ).start()
    try:
        if not _wait_ready(gw, NUM_WORKERS, timeout=90):
            problems.append(
                f"gang never became ready: {gw.stats()['workers']}"
            )
        else:
            slow_ids = _flood(gw.port, N_SLOW, problems, "slow")
            verdict["slow_flood"] = len(slow_ids)
            if not problems:
                _check_trip_asymmetry(
                    gw, jsonl, slow_ids, problems, verdict
                )
                _check_federation(gw, problems, verdict)
                _flood(gw.port, N_RECOVER, problems, "recovery")
                _check_recovery(gw, jsonl, problems)
                _check_recommendation(gw, jsonl, problems, verdict)
                _check_churn(gw, jsonl, problems, verdict)
    finally:
        gw.stop()
        os.environ.pop("SPARKDL_OBS_JSONL", None)

    leaked = _leaked_threads()
    if leaked:
        time.sleep(0.5)
        leaked = _leaked_threads()
    if leaked:
        problems.append(
            "leaked fleet/serving threads after gateway stop: "
            + ", ".join(t.name for t in leaked)
        )

    lock_problems, lock_stats = _common.lock_sanitizer_problems()
    problems += lock_problems
    verdict.update(lock_stats)

    verdict = {
        "fleet_smoke": "FAIL" if problems else "OK",
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
