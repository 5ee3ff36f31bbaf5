"""Closed-loop fleet smoke: prove affinity routing shards the model
catalog and the actuating autoscaler resizes the gang on its own
evidence — the acceptance drill for PR 20's control loop.

Two sequential gangs, one process:

**Phase A — catalog sharding (2 workers, autoscaler off).** The same
gang serves two arms floods of two chaos models each:

- *round-robin arm* (``SPARKDL_GATEWAY_AFFINITY`` unset): sequential
  same-model requests alternate ranks, so BOTH models go resident on
  BOTH workers — 4 cold loads (counted off each worker's own
  ``serve_model_loads_total``);
- *affinity arm* (knob flipped to 1, fresh model names whose ring homes
  differ): every request consistent-hashes to its placement key's home
  rank, so each model loads on exactly ONE worker — 2 cold loads,
  strictly fewer than the round-robin arm. Asserts the resident sets
  (worker ``/v1/models``) are disjoint, land on the ring-predicted
  homes, and the per-rank ``/v1/memory`` ``models`` byte tables are
  disjoint too. Zero non-200 replies in either arm.

**Phase B — SLO-driven elasticity (2 workers, autoscaler ON:**
``SPARKDL_FLEET_AUTOSCALE=1``, ``MIN=2``, ``MAX=3``, ``COOLDOWN=2`` s
**).** A fault plan makes exactly the first 12 interactive requests
slow, tripping the fleet SLO fusion:

- **flood trips scale_up**: the standing ``scale_up`` recommendation
  actuates ``resize(3)`` — a ``{"kind": "fleet_scale"}`` JSONL event
  lands with ``action=scale_up``, ``from=2``, ``to=3`` and evidence
  naming the tripped class; the gang grows to 3 READY workers at
  generation 0 (growth is a launch, not a restart);
- **SIGKILL under flood while the autoscaler converges**: rank 1 dies
  mid-healthy-flood — the supervisor relaunches the gang at generation
  1 *at the autoscaled size 3*, and every accepted request still
  answers 200 (zero lost);
- **recovery observed**: the healthy flood + fresh generation windows
  clear the burn — ``fleet_slo_recovery`` lands and ``/v1/fleet``
  reads untripped;
- **dilution trips scale_down**: idle busy_frac decays under
  ``SPARKDL_FLEET_SCALE_DOWN_BUSY`` — the autoscaler drains rank 2
  (pinned ``/admin/drain`` -> supervisor retire -> SIGTERM -> exit 0)
  and a ``fleet_scale`` ``scale_down`` event lands. The planned exit is
  NEVER counted as gang death: exactly 1 ``gang_restart`` supervisor
  event total (the SIGKILL), no new ``rank_dead``, generation still 1,
  and ``SPARKDL_FLEET_MIN_WORKERS=2`` holds the floor;
- **no leaked ``sparkdl-*`` threads** after both gateways stop, plus
  the lock-sanitizer verdict when preflight runs this under
  ``SPARKDL_LOCK_SANITIZER=1``.

Exit 0 and a one-line JSON verdict on success; exit 1 naming what
failed. Callable standalone or via tools/preflight.sh::

    JAX_PLATFORMS=cpu python tools/autoscale_smoke.py [--out-dir D]
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

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("SPARKDL_INFERENCE_MODE", "roundrobin")
os.environ.setdefault("SPARKDL_INFERENCE_DEVICES", "1")
os.environ.setdefault("SPARKDL_FEEDER_IDLE_S", "0")

# the affinity/autoscale knobs are set PER PHASE (main/_phase_b), never
# at module scope — phase A's round-robin arm is the control and must
# run the byte-identical legacy path
for _k in ("SPARKDL_GATEWAY_AFFINITY", "SPARKDL_FLEET_AUTOSCALE"):
    os.environ.pop(_k, None)

# fleet_smoke's SLO geometry: 12 slow requests round-robin 6/6 across a
# 2-gang — each worker under the floor of 8 while the fleet sum trips
FAULT_SLEEP_S = 0.5
N_SLOW = 12
N_RECOVER = 30
os.environ["SPARKDL_SLO_FAST_S"] = "30"
os.environ["SPARKDL_SLO_SLOW_S"] = "120"
os.environ["SPARKDL_SLO_BURN_FAST"] = "10"
os.environ["SPARKDL_SLO_BURN_SLOW"] = "2"
os.environ["SPARKDL_SLO_MIN_REQUESTS"] = "8"
os.environ["SPARKDL_SLO_P95_MS_INTERACTIVE"] = "300"
os.environ.pop("SPARKDL_SLO_AVAIL", None)
os.environ["SPARKDL_FLEET_SCRAPE_S"] = "0.25"
os.environ["SPARKDL_FLEET_SCRAPE_TIMEOUT_S"] = "2"
os.environ["SPARKDL_FLEET_STALE_S"] = "1.5"
os.environ["SPARKDL_FLEET_RECOMMEND_S"] = "0.5"

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


from _chaos_models import ROW  # noqa: E402

NUM_WORKERS = 2
MAX_WORKERS = 3
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


def _predict(port, model, rows, timeout=300):
    import numpy as np

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/predict",
        data=json.dumps(
            {
                "model": model,
                "inputs": np.asarray(rows).tolist(),
                "class": "interactive",
            }
        ).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _flood(gw_port, model, n, problems, phase):
    """n SEQUENTIAL same-model requests: under round-robin the cursor
    alternates ranks request-to-request (so one model provably lands on
    every rank); under affinity every one hashes to the same home."""
    import numpy as np

    rng = np.random.default_rng(17)
    ok = 0
    for i in range(n):
        try:
            status, _ = _predict(
                gw_port, model, rng.normal(size=(1, ROW)).astype(np.float32)
            )
        except (urllib.error.URLError, OSError) as e:
            problems.append(f"{phase} flood {model} request {i}: {e}")
            continue
        if status != 200:
            problems.append(
                f"{phase} flood {model} request {i} -> {status}"
            )
        else:
            ok += 1
    return ok


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


def _sup_events(jsonl_path, event):
    return [
        ev
        for ev in _events(jsonl_path, "supervisor")
        if ev.get("event") == event
    ]


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
        return (
            len(stats["workers"]) == want
            and ready >= want
            and (
                generation is None
                or stats["generation"] == generation
            )
        )

    return _wait(ok, timeout)


def _fleet_tripped(gw_port, cls="interactive"):
    _, fleet = _get_json(gw_port, "/v1/fleet")
    classes = ((fleet.get("fused") or {}).get("slo") or {}).get(
        "classes"
    ) or {}
    return bool(classes.get(cls, {}).get("tripped"))


def _worker_ports(gw):
    return {
        w["rank"]: w["port"]
        for w in gw.stats()["workers"]
        if w["status"] == "ready" and w.get("port")
    }


def _model_loads(port):
    """This worker's cold-load counter (``serve.model_loads`` via its
    own /metrics exposition; 0 before the first load)."""
    _, text = _get_text(port, "/metrics")
    m = re.search(
        r"^serve_model_loads_total(?:\{[^}]*\})? ([0-9.eE+-]+)$",
        text,
        re.M,
    )
    return float(m.group(1)) if m else 0.0


def _resident_names(port):
    _, stats = _get_json(port, "/v1/models")
    return {
        m.get("name")
        for m in stats.get("models") or []
        if m.get("name")
    }


def _memory_models(port):
    _, mem = _get_json(port, "/v1/memory")
    return mem.get("models") or {}


def _leaked_threads():
    return [
        t
        for t in threading.enumerate()
        if t.is_alive() and t.name.startswith("sparkdl-")
    ]


def _gateway(num_workers, gang_dir, jsonl, fault_root=None):
    from sparkdl_tpu.resilience.policy import RetryPolicy
    from sparkdl_tpu.serving.gateway import ServingGateway

    extra_env = {
        "JAX_PLATFORMS": "cpu",
        "SPARKDL_INFERENCE_MODE": "roundrobin",
        "SPARKDL_INFERENCE_DEVICES": "1",
        "SPARKDL_OBS_JSONL": jsonl,
    }
    if fault_root:
        # exactly the first N_SLOW interactive requests are slow,
        # fleet-wide (the O_EXCL claim dir carries the cap across
        # workers, generations, and resizes)
        extra_env.update(
            {
                "SPARKDL_FAULT_PLAN": FAULT_PLAN,
                "SPARKDL_FAULT_STATE": fault_root,
                "SPARKDL_FAULT_SEED": "0",
            }
        )
    os.environ["SPARKDL_OBS_JSONL"] = jsonl
    return ServingGateway(
        num_workers=num_workers,
        port=0,
        gang_dir=gang_dir,
        loader_spec="tools._chaos_models:loader",
        max_batch=32,
        extra_env=extra_env,
        restart_policy=RetryPolicy(
            max_attempts=3, base_delay_s=0.2, max_delay_s=1.0, seed=0
        ),
        stale_after=30.0,
    ).start()


def _pick_shard_models(problems):
    """Four chaos-model names: two (ring homes 0 and 1) for the
    affinity arm, two fresh ones for the round-robin control — chosen
    with the gateway's OWN ring + placement key, so the smoke's
    home predictions are the router's, not a reimplementation."""
    from sparkdl_tpu.serving.gateway import (
        AffinityRing,
        affinity_replicas,
        placement_key,
    )

    ring = AffinityRing(range(NUM_WORKERS), affinity_replicas())
    homes = {}
    by_home = {}
    for i in range(64):
        name = f"shard-{i}"
        key = placement_key(json.dumps({"model": name}).encode())
        if key is None:
            problems.append(f"placement_key rejected {name!r}")
            return None
        home = ring.order(key)[0]
        homes[name] = home
        by_home.setdefault(home, []).append(name)
        if len(by_home.get(0, [])) >= 2 and len(by_home.get(1, [])) >= 2:
            break
    if len(by_home.get(0, [])) < 2 or len(by_home.get(1, [])) < 2:
        problems.append(
            f"no 2-per-home split in 64 candidate names: {by_home}"
        )
        return None
    # affinity arm gets one model per home; the rr control arm reuses
    # the spares (their homes are irrelevant — round-robin ignores them)
    return {
        "affinity": {0: by_home[0][0], 1: by_home[1][0]},
        "rr": [by_home[0][1], by_home[1][1]],
    }


def _phase_a(root, problems, verdict):
    """Catalog sharding A/B: round-robin control arm, then the knob
    flips ON and fresh models shard onto their ring homes."""
    jsonl = os.path.join(root, "events_a.jsonl")
    gw = _gateway(NUM_WORKERS, os.path.join(root, "gang_a"), jsonl)
    try:
        if not _wait_ready(gw, NUM_WORKERS, timeout=90):
            problems.append(
                f"phase A gang never ready: {gw.stats()['workers']}"
            )
            return
        models = _pick_shard_models(problems)
        if models is None:
            return
        ports = _worker_ports(gw)
        if sorted(ports) != list(range(NUM_WORKERS)):
            problems.append(f"phase A ready ports by rank: {ports}")
            return

        # -- round-robin control arm: both models land on both ranks --
        loads0 = {r: _model_loads(p) for r, p in ports.items()}
        for name in models["rr"]:
            _flood(gw.port, name, 5, problems, "rr-arm")
        for rank, port in ports.items():
            missing = set(models["rr"]) - _resident_names(port)
            if missing:
                problems.append(
                    f"rr arm: rank {rank} is missing {sorted(missing)} "
                    "— 5 sequential same-model requests must alternate "
                    "both ranks under round-robin"
                )
        rr_loads = sum(
            _model_loads(p) - loads0[r] for r, p in ports.items()
        )
        if rr_loads < 2 * NUM_WORKERS:
            problems.append(
                f"rr arm cold loads {rr_loads} < {2 * NUM_WORKERS} — "
                "the control arm did not replicate the catalog"
            )

        # -- affinity arm: same gang, knob ON, fresh models ------------
        os.environ["SPARKDL_GATEWAY_AFFINITY"] = "1"
        loads1 = {r: _model_loads(p) for r, p in ports.items()}
        for home in sorted(models["affinity"]):
            _flood(
                gw.port, models["affinity"][home], 5, problems,
                "affinity-arm",
            )
        aff_loads = sum(
            _model_loads(p) - loads1[r] for r, p in ports.items()
        )
        aff_names = set(models["affinity"].values())
        resident = {
            rank: _resident_names(port) & aff_names
            for rank, port in ports.items()
        }
        for home, name in models["affinity"].items():
            if resident.get(home) is None or name not in resident[home]:
                problems.append(
                    f"affinity arm: {name} not resident on its ring "
                    f"home rank {home}: {resident}"
                )
        if resident.get(0, set()) & resident.get(1, set()):
            problems.append(
                f"affinity arm resident sets overlap: {resident} — "
                "the catalog did not shard"
            )
        mem = {
            rank: set(_memory_models(port)) & aff_names
            for rank, port in ports.items()
        }
        if mem.get(0, set()) & mem.get(1, set()):
            problems.append(
                f"per-rank /v1/memory model tables overlap: {mem}"
            )
        for home, name in models["affinity"].items():
            bytes_ = _memory_models(ports[home]).get(name)
            if not bytes_:
                problems.append(
                    f"/v1/memory on rank {home} has no bytes for "
                    f"{name}: {mem}"
                )
        if aff_loads != len(aff_names):
            problems.append(
                f"affinity arm cold loads {aff_loads} != "
                f"{len(aff_names)} (one per model)"
            )
        if aff_loads >= rr_loads:
            problems.append(
                f"affinity cold loads {aff_loads} not strictly fewer "
                f"than the round-robin arm's {rr_loads}"
            )
        verdict["sharding"] = {
            "rr_loads": rr_loads,
            "affinity_loads": aff_loads,
            "resident": {r: sorted(s) for r, s in resident.items()},
        }
    finally:
        os.environ.pop("SPARKDL_GATEWAY_AFFINITY", None)
        gw.stop()


def _phase_b(root, problems, verdict):
    """The actuating control loop: trip -> scale_up -> SIGKILL churn at
    the scaled size -> recovery -> idle dilution -> drained scale_down."""
    jsonl = os.path.join(root, "events_b.jsonl")
    os.environ["SPARKDL_FLEET_AUTOSCALE"] = "1"
    os.environ["SPARKDL_FLEET_COOLDOWN_S"] = "2"
    os.environ["SPARKDL_FLEET_MIN_WORKERS"] = str(NUM_WORKERS)
    os.environ["SPARKDL_FLEET_MAX_WORKERS"] = str(MAX_WORKERS)
    gw = _gateway(
        NUM_WORKERS,
        os.path.join(root, "gang_b"),
        jsonl,
        fault_root=os.path.join(root, "faults"),
    )
    try:
        if not _wait_ready(gw, NUM_WORKERS, timeout=90):
            problems.append(
                f"phase B gang never ready: {gw.stats()['workers']}"
            )
            return

        # -- flood trips scale_up ----------------------------------------
        _flood(gw.port, "prim", N_SLOW, problems, "slow")
        if not _wait(lambda: _fleet_tripped(gw.port), timeout=30):
            problems.append("fleet SLO never tripped on the slow flood")
            return
        if not _wait(
            lambda: any(
                ev.get("action") == "scale_up"
                for ev in _events(jsonl, "fleet_scale")
            ),
            timeout=30,
        ):
            problems.append(
                "no fleet_scale scale_up actuation while tripped; "
                "recommendations standing: "
                + json.dumps(gw.fleet.recommendation())
            )
            return
        up = next(
            ev
            for ev in _events(jsonl, "fleet_scale")
            if ev.get("action") == "scale_up"
        )
        if (up.get("from"), up.get("to")) != (NUM_WORKERS, MAX_WORKERS):
            problems.append(
                f"scale_up event resized {up.get('from')} -> "
                f"{up.get('to')}, expected {NUM_WORKERS} -> {MAX_WORKERS}"
            )
        if not (up.get("evidence") or {}).get("tripped_classes"):
            problems.append(
                "scale_up event carries no tripped_classes evidence: "
                + json.dumps(up)
            )
        if not _wait_ready(gw, MAX_WORKERS, timeout=90, generation=0):
            problems.append(
                "gang never grew to 3 READY workers at generation 0 "
                f"(growth must be a launch, not a restart): {gw.stats()}"
            )
            return
        verdict["scale_up"] = {"from": up["from"], "to": up["to"]}

        # -- SIGKILL under flood while the autoscaler converges ----------
        victim = next(
            w
            for w in gw.stats()["workers"]
            if w["rank"] == 1 and w["pid"]
        )
        flood_problems = []
        flood = threading.Thread(
            target=_flood,
            args=(gw.port, "prim", N_RECOVER, flood_problems, "churn"),
            name="sparkdl-autoscale-smoke-flood",
            daemon=True,
        )
        flood.start()
        time.sleep(0.3)
        os.kill(victim["pid"], signal.SIGKILL)
        if not _wait_ready(gw, MAX_WORKERS, timeout=120, generation=1):
            problems.append(
                "gang did not converge back to the autoscaled size 3 "
                f"at generation 1 after SIGKILL: {gw.stats()}"
            )
            return
        flood.join(timeout=300)
        if flood.is_alive():
            problems.append("churn flood never completed")
            return
        problems.extend(flood_problems)  # zero lost: every reply 200

        # -- recovery observed -------------------------------------------
        # top the fresh generation's windows past the fleet floor with
        # healthy traffic, so recovery is a dilution verdict over real
        # requests, not a below-floor technicality
        _flood(gw.port, "prim", 16, problems, "recovery")
        if not _wait(
            lambda: not _fleet_tripped(gw.port), timeout=60
        ):
            problems.append(
                "fleet SLO never recovered after the healthy flood"
            )
            return
        if not _events(jsonl, "fleet_slo_recovery"):
            problems.append("no fleet_slo_recovery JSONL event landed")

        # -- idle dilution trips scale_down, drain is not death ----------
        restarts_before = len(_sup_events(jsonl, "gang_restart"))
        deaths_before = len(_sup_events(jsonl, "rank_dead"))
        if not _wait(
            lambda: any(
                ev.get("action") == "scale_down"
                for ev in _events(jsonl, "fleet_scale")
            ),
            timeout=90,
        ):
            problems.append(
                "no fleet_scale scale_down actuation after the fleet "
                "went idle; standing recommendation: "
                + json.dumps(gw.fleet.recommendation())
            )
            return
        down = next(
            ev
            for ev in _events(jsonl, "fleet_scale")
            if ev.get("action") == "scale_down"
        )
        if (down.get("from"), down.get("to")) != (
            MAX_WORKERS,
            NUM_WORKERS,
        ):
            problems.append(
                f"scale_down event resized {down.get('from')} -> "
                f"{down.get('to')}, expected {MAX_WORKERS} -> "
                f"{NUM_WORKERS}"
            )
        if not _wait_ready(gw, NUM_WORKERS, timeout=60, generation=1):
            problems.append(
                "gang never settled at 2 READY workers (generation 1) "
                f"after scale_down: {gw.stats()}"
            )
            return
        time.sleep(1.5)  # grace: a mistaken death would restart here
        if len(_sup_events(jsonl, "gang_restart")) != restarts_before:
            problems.append(
                "scale_down triggered a gang_restart — the drained "
                "rank's exit 0 was counted as gang death"
            )
        if len(_sup_events(jsonl, "rank_dead")) != deaths_before:
            problems.append(
                "scale_down landed a rank_dead supervisor event — a "
                "retired rank must never be polled as a death"
            )
        if len(_sup_events(jsonl, "gang_restart")) != 1:
            problems.append(
                f"expected exactly 1 gang_restart (the SIGKILL), saw "
                f"{len(_sup_events(jsonl, 'gang_restart'))}"
            )
        if not _sup_events(jsonl, "gang_resize"):
            problems.append("no gang_resize supervisor event landed")
        # the floor holds: standing scale_down at MIN actuates nothing
        time.sleep(3)
        if len(gw.stats()["workers"]) != NUM_WORKERS:
            problems.append(
                "autoscaler shrank below SPARKDL_FLEET_MIN_WORKERS="
                f"{NUM_WORKERS}: {gw.stats()['workers']}"
            )
        verdict["scale_down"] = {
            "from": down["from"],
            "to": down["to"],
            "reason": down.get("reason"),
        }
        verdict["churn"] = "sigkill-converged-at-autoscaled-size"
    finally:
        gw.stop()
        for k in (
            "SPARKDL_FLEET_AUTOSCALE",
            "SPARKDL_FLEET_COOLDOWN_S",
            "SPARKDL_FLEET_MIN_WORKERS",
            "SPARKDL_FLEET_MAX_WORKERS",
        ):
            os.environ.pop(k, None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--out-dir", default=None,
        help="gang dirs + event logs land here (default: a temp dir)",
    )
    args = ap.parse_args(argv)
    root = args.out_dir or tempfile.mkdtemp(prefix="autoscale_smoke_")
    os.makedirs(root, exist_ok=True)

    problems = []
    verdict = {"out_dir": root}
    try:
        _phase_a(root, problems, verdict)
        if not problems:
            _phase_b(root, problems, verdict)
    finally:
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
        "autoscale_smoke": "FAIL" if problems else "OK",
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
