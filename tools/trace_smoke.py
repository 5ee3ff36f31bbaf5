"""Request-tracing smoke: prove the end-to-end trace story on CPU — the
acceptance drill for docs/OBSERVABILITY.md "Request tracing".

Phase 1 — the traced gang. One in-process :class:`ServingGateway`
fronts 2 worker subprocesses with tracing armed at sample rate 1 and a
fault plan that crashes worker 0 mid-flood (the serving_chaos_smoke
death). A 60-request HTTP flood then proves:

- **zero lost requests, every reply named**: all flood responses are
  200 and every body carries a 16-hex ``trace_id`` matching its
  ``X-Sparkdl-Trace`` response header;
- **the full waterfall**: after the gang settles and drops its exit
  snapshots, flood trace ids resolve to worker-side records carrying
  ALL seven segments (queue_wait, group_wait, stage_wait, dispatch,
  decode, drain_wait, scatter) whose sum matches the record's own e2e within
  tolerance — and that e2e is bounded by the client-measured latency;
- **stitched re-dispatch**: the crash strands at least one forwarded
  request -> the gateway's trace record shows >= 2 attempts (first
  transport/503, last ok) under ONE trace_id, and that request's flood
  reply was still 200;
- **exemplar -> waterfall**: a post-restart worker's ``/metrics``
  exports ``serve_latency_*_seconds_exemplar{trace_id="..."}`` lines,
  and that id renders a real waterfall via the ``obs trace`` CLI over
  the gang dir (gateway drop included, labeled lane), plus the merged
  Chrome trace carries cross-lane flow events for the stitched trace;

Phase 2 — the overhead A/B. One in-process router floods the DEFAULT
tracing config (SPARKDL_TRACE_SAMPLE=0.01 — what a deployment runs)
vs tracing-off (=0), interleaved best-of-N; the traced arm must hold
within 3% of the off arm. Segment measurement is always-on either way
— the knob only dials storage — so this assertion is what keeps the
always-on half cheap. (Sample rate 1, phase 1's setting, stores every
record and measurably costs a few percent on a CPU flood at ~300 us/
request; that is the debugging dial, not the default.)

Standard closing checks: no leaked ``sparkdl-*`` threads, lock
sanitizer verdict clean when run under ``SPARKDL_LOCK_SANITIZER=1``
(preflight does). Exit 0 + one-line JSON verdict on success::

    JAX_PLATFORMS=cpu python tools/trace_smoke.py [--out-dir D]
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
os.environ.setdefault("SPARKDL_TRACE_SAMPLE", "1")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


from _chaos_models import ROW  # noqa: E402

NUM_WORKERS = 2
N_FLOOD = 60
CRASH_ORDINAL = 6
FAULT_PLAN = f"site=serve.request:rank=0:request={CRASH_ORDINAL}:crash"
AB_REQUESTS = 400  # per arm run, phase 2
AB_RUNS = 5        # best-of per arm (alternating order cancels drift)
AB_ESCALATION = 3  # extra rounds per arm before calling it a regression
AB_TOLERANCE = 0.03


def _post(port, payload, headers=None, timeout=300):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/predict",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
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
    import numpy as np

    rng = np.random.default_rng(11)
    jobs = []
    for i in range(N_FLOOD):
        rows = 1 if i % 3 else 4
        priority = ("interactive", "batch", "background")[i % 3]
        x = rng.normal(size=(rows, ROW)).astype(np.float32)
        jobs.append(
            {"model": "prim", "inputs": x.tolist(), "priority": priority}
        )
    results = [None] * len(jobs)

    def run_one(i):
        t0 = time.monotonic()
        status, body, headers = _post(gw_port, jobs[i])
        results[i] = (status, body, headers, time.monotonic() - t0)

    with ThreadPoolExecutor(
        max_workers=12, thread_name_prefix="trace-client"
    ) as pool:
        list(pool.map(run_one, range(len(jobs))))

    lost = [i for i, (s, *_rest) in enumerate(results) if s != 200]
    if lost:
        problems.append(
            f"{len(lost)}/{len(jobs)} flood requests lost (non-200): "
            + str(
                [
                    {"i": i, "status": results[i][0], "body": results[i][1]}
                    for i in lost[:3]
                ]
            )
        )
    for status, body, headers, _ in results:
        if status != 200:
            continue
        tid = body.get("trace_id")
        if not tid or len(tid) != 16:
            problems.append(f"200 reply without a 16-hex trace_id: {body}")
            break
        if headers.get("X-Sparkdl-Trace") != tid:
            problems.append(
                "X-Sparkdl-Trace header disagrees with the body trace_id"
            )
            break
    return results


def _check_waterfalls(results, snaps, problems, verdict):
    """Flood trace ids -> worker-side records with all seven segments
    whose sum matches the record's e2e (and is bounded by the
    client-measured latency)."""
    from sparkdl_tpu.obs.trace import SEGMENTS, collect_trace

    client_latency = {}
    for status, body, headers, dt in results:
        if status == 200:
            client_latency[body["trace_id"]] = dt
    checked = 0
    for tid, dt in client_latency.items():
        records = [
            r
            for r in collect_trace(tid, snaps)
            if r.get("kind") == "serve" and r.get("status") == "ok"
        ]
        if not records:
            continue  # served by a pre-restart worker: store died with it
        rec = records[-1]
        segs = rec.get("segments") or {}
        if set(segs) != set(SEGMENTS):
            problems.append(
                f"trace {tid}: segments {sorted(segs)} != {SEGMENTS}"
            )
            return
        if any(v < 0 for v in segs.values()):
            problems.append(f"trace {tid}: negative segment in {segs}")
            return
        seg_sum, e2e = sum(segs.values()), rec["e2e_s"]
        if abs(seg_sum - e2e) > max(0.02, 0.10 * e2e):
            problems.append(
                f"trace {tid}: segment sum {seg_sum:.4f}s inconsistent "
                f"with worker e2e {e2e:.4f}s"
            )
            return
        # the worker's e2e must fit inside what the client measured
        # (gateway + HTTP overhead rides on top), with scheduling slack
        if e2e > dt + 0.25:
            problems.append(
                f"trace {tid}: worker e2e {e2e:.4f}s exceeds client "
                f"latency {dt:.4f}s"
            )
            return
        checked += 1
    if checked < 5:
        problems.append(
            f"only {checked} flood traces resolved to full waterfalls "
            "(expected most post-restart requests to)"
        )
    verdict["waterfalls_checked"] = checked


def _check_stitching(results, snaps, problems, verdict):
    """The crash yields >= 1 gateway record with two attempts under one
    trace_id whose flood reply was still 200 — and the merged Chrome
    trace stitches it across lanes with flow events."""
    from sparkdl_tpu.obs import aggregate
    from sparkdl_tpu.obs.trace import get_store

    ok_ids = {
        body["trace_id"] for status, body, *_ in results if status == 200
    }
    stitched = [
        recs[0]
        for tid in ok_ids
        for recs in [get_store().get(tid)]
        if recs and len(recs[0].get("attempts") or []) >= 2
    ]
    if not stitched:
        problems.append(
            "no gateway trace shows >= 2 attempts — the crash should "
            "have stranded at least one forwarded request"
        )
        return
    rec = stitched[0]
    attempts = rec["attempts"]
    if attempts[-1]["outcome"] != "ok":
        problems.append(
            f"stitched trace {rec['trace_id']}: last attempt is "
            f"{attempts[-1]['outcome']!r}, not 'ok'"
        )
    if attempts[0]["outcome"] == "ok":
        problems.append(
            f"stitched trace {rec['trace_id']}: first attempt already "
            "'ok' — nothing was re-dispatched"
        )
    verdict["stitched_trace"] = rec["trace_id"]
    verdict["stitched_attempts"] = len(attempts)
    # cross-lane flow: the merged trace must bind this id across pids
    # when a worker-side record survived for it too
    merged = aggregate.merge_chrome_trace(snaps)
    flows = [
        e
        for e in merged["traceEvents"]
        if e.get("ph") in ("s", "t", "f")
        and e.get("args", {}).get("trace_id")
    ]
    if not flows:
        problems.append(
            "merged Chrome trace carries no request flow events"
        )
    else:
        verdict["merged_flow_traces"] = len(
            {e["args"]["trace_id"] for e in flows}
        )


def _check_exemplar(gw, gang_dir, problems, verdict):
    """A live worker's /metrics exemplar line resolves via the obs
    trace CLI (over the gang dir's snapshot drops) to a waterfall."""
    ready = [
        w for w in gw.stats()["workers"] if w["status"] == "ready"
    ]
    if not ready:
        problems.append("no ready worker to scrape /metrics from")
        return None
    port = ready[0]["port"]
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30
    ) as resp:
        text = resp.read().decode()
    ex_lines = [
        ln
        for ln in text.splitlines()
        if "_seconds_exemplar{" in ln and ln.startswith("serve_latency_")
    ]
    if not ex_lines:
        problems.append(
            "worker /metrics carries no serve_latency_*_seconds_exemplar "
            "line"
        )
        return None
    tid = ex_lines[0].split('trace_id="')[1].split('"')[0]
    verdict["exemplar_trace"] = tid
    verdict["exemplar_lines"] = len(ex_lines)
    return tid


def _resolve_exemplar_cli(tid, gang_dir, problems):
    from sparkdl_tpu.obs.__main__ import main as obs_main

    import contextlib
    import io

    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            rc = obs_main(["trace", tid, "--rank-dir", gang_dir])
    except SystemExit as e:
        problems.append(
            f"obs trace {tid} --rank-dir failed to resolve: {e}"
        )
        return
    out = buf.getvalue()
    if rc != 0 or "segments sum" not in out or "dispatch" not in out:
        problems.append(
            f"obs trace {tid} did not render a waterfall:\n{out[:500]}"
        )


def _phase_gang(root, problems, verdict):
    from sparkdl_tpu.obs import aggregate, export
    from sparkdl_tpu.obs import trace as trace_mod
    from sparkdl_tpu.resilience.policy import RetryPolicy
    from sparkdl_tpu.serving.gateway import ServingGateway
    from sparkdl_tpu.utils.metrics import metrics

    gang_dir = os.path.join(root, "gang")
    jsonl = os.path.join(root, "events.jsonl")
    os.environ["SPARKDL_OBS_JSONL"] = jsonl
    trace_mod.reset()
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
            "SPARKDL_TRACE_SAMPLE": "1",
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
            return
        results = _flood(gw.port, problems)
        if not _wait_ready(gw, NUM_WORKERS, timeout=60, generation=1):
            problems.append(
                "gang did not settle ready at generation 1 after the "
                f"crash: {gw.stats()}"
            )
            return
        restarts = int(
            metrics.counter("supervisor.restarts") - restarts_before
        )
        if restarts != 1:
            problems.append(
                f"expected exactly 1 supervisor restart, saw {restarts}"
            )
        verdict["restarts"] = restarts
        # a little post-restart traffic so both gen-1 workers hold
        # exemplars + traces their exit drops will publish
        import numpy as np

        for i in range(8):
            x = np.full((1, ROW), 0.1 * i, np.float32)
            status, _, _ = _post(
                gw.port, {"model": "prim", "inputs": x.tolist()}
            )
            if status != 200:
                problems.append(
                    f"post-restart request {i} returned {status}"
                )
                return
        exemplar_tid = _check_exemplar(gw, gang_dir, problems, verdict)
    finally:
        gw.stop()
        os.environ.pop("SPARKDL_OBS_JSONL", None)
    # the workers drain + exit under gw.stop(): their Heartbeat exits
    # force-drop obs.rank.<r>.json (traces included) into the gang dir.
    # The gateway runs IN THIS PROCESS: drop its snapshot beside them,
    # role-labeled so the merge renders a "gateway" lane.
    aggregate.write_rank_snapshot(
        gang_dir,
        NUM_WORKERS,
        {**export.snapshot(rank=NUM_WORKERS), "role": "gateway"},
    )
    snaps = aggregate.load_rank_snapshots(gang_dir)
    if len(snaps) < NUM_WORKERS + 1:
        problems.append(
            f"expected {NUM_WORKERS + 1} snapshot drops (workers + "
            f"gateway), found {sorted(snaps)}"
        )
        return
    _check_waterfalls(results, snaps, problems, verdict)
    _check_stitching(results, snaps, problems, verdict)
    if exemplar_tid is not None:
        _resolve_exemplar_cli(exemplar_tid, gang_dir, problems)


def _ab_flood(client, n):
    """One timed in-process flood: submit n single-row requests over a
    small pool, wait all, return req/s."""
    import numpy as np

    rng = np.random.default_rng(3)
    xs = [
        rng.normal(size=(1, ROW)).astype(np.float32) for _ in range(16)
    ]
    t0 = time.perf_counter()
    reqs = []

    def submit(lo, hi):
        for i in range(lo, hi):
            reqs.append(
                client.submit("prim", xs[i % len(xs)], priority="batch")
            )

    threads = [
        threading.Thread(
            target=submit,
            args=(k * n // 4, (k + 1) * n // 4),
            name=f"sparkdl-trace-ab-{k}",
            daemon=False,
        )
        for k in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for r in list(reqs):
        r.result(timeout=300)
    return n / (time.perf_counter() - t0)


def _phase_overhead(problems, verdict):
    """Interleaved best-of-N A/B: tracing armed (sample 1) vs off
    (sample 0) on ONE warmed router — the knob only dials storage, so
    the armed arm must hold within AB_TOLERANCE."""
    from _chaos_models import loader

    from sparkdl_tpu.obs import trace as trace_mod
    from sparkdl_tpu.serving import Router, ServingClient

    import numpy as np

    router = Router(loader=loader, max_batch=32)
    client = ServingClient(router)
    best = {"on": 0.0, "off": 0.0}

    # "on" is the DEFAULT sample rate — the config whose cost the 3%
    # claim is about; rate 1 (phase 1) is the store-everything
    # debugging dial and pays for its storage.
    arms = (("off", "0"), ("on", "0.01"))

    def _round(order):
        for arm, rate in order:
            os.environ["SPARKDL_TRACE_SAMPLE"] = rate
            trace_mod.reset()
            rps = _ab_flood(client, AB_REQUESTS)
            best[arm] = max(best[arm], rps)

    try:
        client.predict(
            "prim", np.zeros((1, ROW), np.float32), timeout=300
        )  # warm/compile outside the clock
        for i in range(AB_RUNS):
            # alternate which arm runs first so box drift (thermal,
            # background load) never systematically favors one arm
            _round(arms if i % 2 == 0 else arms[::-1])
        if best["on"] < (1.0 - AB_TOLERANCE) * best["off"]:
            # single-box CPU floods have shown multi-percent swings on
            # identical configs (bench-gate history); before calling a
            # ~0-cost arm a regression, buy more samples for both arms
            for i in range(AB_ESCALATION):
                _round(arms if i % 2 == 0 else arms[::-1])
    finally:
        os.environ["SPARKDL_TRACE_SAMPLE"] = "1"
        router.close()
    verdict["ab_rps_on"] = round(best["on"], 1)
    verdict["ab_rps_off"] = round(best["off"], 1)
    if best["on"] < (1.0 - AB_TOLERANCE) * best["off"]:
        problems.append(
            f"tracing-on flood {best['on']:.1f} req/s fell more than "
            f"{AB_TOLERANCE:.0%} below tracing-off {best['off']:.1f} "
            "req/s"
        )


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
    root = args.out_dir or tempfile.mkdtemp(prefix="trace_smoke_")
    os.makedirs(root, exist_ok=True)

    problems = []
    verdict = {"out_dir": root}

    _phase_gang(root, problems, verdict)
    _phase_overhead(problems, verdict)

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
        "trace_smoke": "FAIL" if problems else "OK",
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
