"""Serving-gang chaos smoke: prove the supervised serving tier survives
a worker crash mid-flood on CPU — the acceptance drill for the gateway
(docs/RESILIENCE.md "Serving gang").

One in-process :class:`ServingGateway` fronts 2 worker subprocesses
(``python -m sparkdl_tpu.serving worker``), with a canary split armed
(25% of the ``prim`` model's traffic -> ``prim_v2``) and a fault plan
that **crashes worker 0 at its 7th admitted request** (``os._exit(77)``
mid-request, the SIGKILL-shaped death). A mixed flood (two models,
three SLA classes, single- and multi-row payloads) then runs through
the REAL HTTP path while the crash, the supervisor's gang restart, and
the gateway's re-dispatch all happen underneath it. Asserts:

- **zero lost accepted requests**: every flood request returns 200 —
  requests stranded on the dying worker re-dispatch to a survivor or
  wait out the relaunch window;
- **exactly one supervisor restart** (the fault's ``times=1`` claim
  holds across generations via ``SPARKDL_FAULT_STATE``), and the
  post-restart gang reaches generation 1 with every worker ready;
- **row-identical outputs**: every response (including post-restart
  ones) matches a direct ``run_batched_shared`` oracle over the SAME model
  builds (``tools/_chaos_models.py`` is deterministic per name) — the
  response's ``model`` field names the version that served it, so
  canary-served rows check against the canary oracle;
- **canary split within tolerance**: the deterministic Bresenham split
  lands the observed canary share near the configured 25% even across
  the crash (per-worker counters reset with the worker — the split is
  per-router, the assertion is over served responses);
- **drain semantics live**: ``POST /admin/drain`` flips worker 0 to
  draining — its ``/healthz`` says so, a direct submit to it gets
  503 + ``Retry-After``, and the gateway keeps answering 200 around
  it;
- **no leaked ``sparkdl-*`` threads** after ``gateway.stop()`` (which
  TERMs the gang: workers drain and exit), plus the standard
  lock-sanitizer verdict when preflight runs this smoke under
  ``SPARKDL_LOCK_SANITIZER=1``.

Exit 0 and a one-line JSON verdict on success; exit 1 naming what
failed. Callable standalone or via tools/preflight.sh::

    JAX_PLATFORMS=cpu python tools/serving_chaos_smoke.py [--out-dir D]
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

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


from _chaos_models import ROW, loader  # noqa: E402

NUM_WORKERS = 2
N_FLOOD = 120          # flood requests (also the canary-ratio sample)
CANARY_WEIGHT = 0.25
CRASH_ORDINAL = 6      # worker 0 dies at its 7th admitted request
FAULT_PLAN = f"site=serve.request:rank=0:request={CRASH_ORDINAL}:crash"


def _predict(port, payload, timeout=300):
    """One POST /v1/predict; returns (status, parsed body, headers)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/predict",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        try:
            body = json.loads(e.read() or b"{}")
        except json.JSONDecodeError:
            body = {}
        return e.code, body, dict(e.headers)


def _get(port, path, timeout=10):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as resp:
        return json.loads(resp.read())


def _offline_outputs(name, rows):
    """run_batched_shared over the identical model build: the parity
    oracle."""
    from sparkdl_tpu.transformers.execution import (
        arrays_to_batch,
        model_device_fn,
        run_batched_shared,
    )

    device_fn = model_device_fn(loader(name, "features"))
    return run_batched_shared(
        list(rows), arrays_to_batch, device_fn, batch_size=32
    )


def _wait_ready(gw, want, timeout, generation=None):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        stats = gw.stats()
        ready = sum(
            1 for w in stats["workers"] if w["status"] == "ready"
        )
        if ready >= want and (
            generation is None or stats["generation"] == generation
        ):
            return True
        time.sleep(0.2)
    return False


def _flood(gw_port, problems):
    """The mixed flood: N_FLOOD requests over a small client pool while
    worker 0 crashes underneath. Returns the (payload_rows, response)
    pairs for the parity + canary-ratio checks."""
    import numpy as np

    rng = np.random.default_rng(7)
    jobs = []
    for i in range(N_FLOOD):
        model = "prim" if i % 5 != 4 else "other"
        rows = 1 if i % 3 else 4
        priority = ("interactive", "batch", "background")[i % 3]
        x = rng.normal(size=(rows, ROW)).astype(np.float32)
        jobs.append(
            (
                x,
                {
                    "model": model,
                    "inputs": x.tolist(),
                    "priority": priority,
                },
            )
        )

    results = [None] * len(jobs)

    def run_one(i):
        status, body, headers = _predict(gw_port, jobs[i][1])
        results[i] = (status, body)

    with ThreadPoolExecutor(
        max_workers=16, thread_name_prefix="chaos-client"
    ) as pool:
        list(pool.map(run_one, range(len(jobs))))

    lost = [
        i for i, (status, _) in enumerate(results) if status != 200
    ]
    if lost:
        detail = [
            {"i": i, "status": results[i][0], "body": results[i][1]}
            for i in lost[:3]
        ]
        problems.append(
            f"{len(lost)}/{len(jobs)} accepted requests lost "
            f"(non-200): {detail}"
        )
    return jobs, results


def _check_parity(jobs, results, problems):
    """Every 200 response row-identical to the run_batched_shared oracle
    of the model VERSION that served it."""
    import numpy as np

    by_version = {}
    for (x, payload), (status, body) in zip(jobs, results):
        if status != 200:
            continue
        by_version.setdefault(body["model"], []).append(
            (x, np.asarray(body["outputs"], np.float32))
        )
    for version, pairs in sorted(by_version.items()):
        flat_in = [row for x, _ in pairs for row in x]
        expected = _offline_outputs(version, flat_in)
        served = [row for _, out in pairs for row in out]
        for i, (got, want) in enumerate(zip(served, expected)):
            if not np.allclose(got, want, rtol=1e-5, atol=1e-5):
                problems.append(
                    f"serving/offline mismatch for {version} at row {i} "
                    "(outputs across the restart are not row-identical "
                    "to the oracle)"
                )
                break
    return sorted(by_version)


def _check_canary(jobs, results, problems):
    prim_total = canary = 0
    for (x, payload), (status, body) in zip(jobs, results):
        if status != 200 or payload["model"] != "prim":
            continue
        prim_total += 1
        if body["model"] == "prim_v2":
            canary += 1
    ratio = canary / prim_total if prim_total else 0.0
    if not (CANARY_WEIGHT - 0.12 <= ratio <= CANARY_WEIGHT + 0.12):
        problems.append(
            f"canary split ratio {ratio:.3f} ({canary}/{prim_total}) "
            f"outside tolerance around {CANARY_WEIGHT}"
        )
    return {"canary_served": canary, "prim_requests": prim_total,
            "ratio": round(ratio, 3)}


def _check_drain(gw, problems):
    """Admin-drain worker 0: healthz flips, direct submits 503 with
    Retry-After, the gateway routes around it."""
    import numpy as np

    # resolve worker 0's port BEFORE draining (state is live either way)
    w0 = next(
        (w for w in gw.stats()["workers"] if w["rank"] == 0), None
    )
    if w0 is None or not w0.get("port"):
        problems.append("drain phase: worker 0 has no published port")
        return {}
    status, body, _ = _predict(
        gw.port, {"model": "prim", "inputs": [[0.5] * ROW]}, timeout=60
    )  # warm the gateway path before the topology changes
    req = urllib.request.Request(
        f"http://127.0.0.1:{gw.port}/admin/drain",
        data=json.dumps({"rank": 0}).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        verdict = json.loads(resp.read())
    if verdict.get("status") != "draining":
        problems.append(
            f"admin drain did not report draining: {verdict}"
        )
    hz = _get(w0["port"], "/healthz")
    if hz.get("status") != "draining":
        problems.append(
            f"draining worker /healthz says {hz.get('status')!r}, "
            "expected 'draining'"
        )
    status, body, headers = _predict(
        w0["port"], {"model": "prim", "inputs": [[1.0] * ROW]}, timeout=30
    )
    if status != 503:
        problems.append(
            f"direct submit to draining worker returned {status}, "
            "expected 503"
        )
    retry_after = headers.get("Retry-After")
    if not retry_after:
        problems.append(
            "503 from draining worker carries no Retry-After header"
        )
    # the gateway keeps serving around the drained worker
    x = np.full((1, ROW), 0.25, np.float32)
    status, body, _ = _predict(
        gw.port, {"model": "other", "inputs": x.tolist()}, timeout=120
    )
    if status != 200:
        problems.append(
            f"gateway predict during drain returned {status} "
            "(should route around the draining worker)"
        )
    else:
        expected = _offline_outputs(body["model"], [x[0]])
        if not np.allclose(
            np.asarray(body["outputs"], np.float32)[0],
            expected[0],
            rtol=1e-5,
            atol=1e-5,
        ):
            problems.append("drain-phase gateway output mismatch")
    return {"drain_retry_after": retry_after}


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
    root = args.out_dir or tempfile.mkdtemp(prefix="serving_chaos_")
    os.makedirs(root, exist_ok=True)
    gang_dir = os.path.join(root, "gang")
    jsonl = os.path.join(root, "events.jsonl")

    from sparkdl_tpu.resilience.policy import RetryPolicy
    from sparkdl_tpu.serving.gateway import ServingGateway
    from sparkdl_tpu.utils.metrics import metrics

    problems = []
    verdict = {"out_dir": root}
    os.environ["SPARKDL_OBS_JSONL"] = jsonl
    restarts_before = metrics.counter("supervisor.restarts")
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
            # canary rollout: 25% of 'prim' traffic -> 'prim_v2'
            "SPARKDL_SERVE_CANARY_MODEL": "prim",
            "SPARKDL_SERVE_CANARY_VERSION": "prim_v2",
            "SPARKDL_SERVE_CANARY_WEIGHT": str(CANARY_WEIGHT),
            # the chaos: crash worker 0 mid-flood, exactly once across
            # generations (the O_EXCL claim dir holds the times=1 cap)
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
            jobs, results = _flood(gw.port, problems)
            # post-restart: the gang must settle at generation 1 with
            # every worker ready again
            if not _wait_ready(
                gw, NUM_WORKERS, timeout=60, generation=1
            ):
                problems.append(
                    "gang did not settle ready at generation 1 after "
                    f"the crash: {gw.stats()}"
                )
            restarts = int(
                metrics.counter("supervisor.restarts") - restarts_before
            )
            if restarts != 1:
                problems.append(
                    f"expected exactly 1 supervisor restart, saw "
                    f"{restarts}"
                )
            versions = _check_parity(jobs, results, problems)
            verdict["versions_served"] = versions
            if "prim_v2" not in versions:
                problems.append(
                    "canary version prim_v2 never served a request"
                )
            verdict.update(_check_canary(jobs, results, problems))
            # fault fired exactly once (times=1 across generations)
            faults = []
            try:
                with open(jsonl) as f:
                    faults = [
                        json.loads(ln)
                        for ln in f
                        if ln.strip()
                        and json.loads(ln).get("kind") == "fault"
                    ]
            except OSError:
                pass
            if len(faults) != 1:
                problems.append(
                    f"fault fired {len(faults)} times (times=1 claim "
                    "across generations broken)"
                )
            verdict["restarts"] = restarts
            verdict.update(_check_drain(gw, problems))
    finally:
        gw.stop()
        os.environ.pop("SPARKDL_OBS_JSONL", None)

    # the oracle ran run_batched_shared in THIS process: its feeders and
    # H2D pools must shut down before the leak check, as in serving_smoke
    from sparkdl_tpu.runtime.feeder import shutdown_feeders

    shutdown_feeders()
    leaked = _leaked_threads()
    if leaked:
        time.sleep(0.5)
        leaked = _leaked_threads()
    if leaked:
        problems.append(
            "leaked serving threads after gateway stop: "
            + ", ".join(t.name for t in leaked)
        )

    lock_problems, lock_stats = _common.lock_sanitizer_problems()
    problems += lock_problems
    verdict.update(lock_stats)

    verdict = {
        "serving_chaos_smoke": "FAIL" if problems else "OK",
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
