"""Telemetry smoke: prove the fleet-telemetry layer end-to-end on CPU.

Mirrors tools/obs_smoke.py (flight recorder) and tools/feeder_smoke.py
(shared feeder) for PR 3's layer. One small shared-feeder workload runs
through the REAL engine while the time-series sampler ticks, then:

- the sampler must hold a NON-EMPTY series including ``feeder.rows``
  (cumulative matches the dispatched rows) and at least one derived
  ``/s`` rate series;
- the JSONL event log must contain parseable sample lines;
- an in-test HTTP GET against the exporter's ``/metrics`` must return
  parseable Prometheus text including ``feeder_queue_depth``;
- two simulated ranks' snapshots (the workload re-run under a second
  rank tag, plus one synthetic straggler span injected into rank 1 so
  detection has something to detect) must merge into a valid Chrome
  trace with DISTINCT per-rank lanes, and the cross-rank report must
  flag the straggler stage.

Exit 0 and a one-line JSON verdict on success; exit 1 naming what
failed. Callable standalone or via tools/preflight.sh::

    JAX_PLATFORMS=cpu python tools/telemetry_smoke.py [--out-dir DIR]
"""

import argparse
import json
import os
import sys
import tempfile
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("SPARKDL_INFERENCE_MODE", "roundrobin")
os.environ.setdefault("SPARKDL_INFERENCE_DEVICES", "1")
os.environ.setdefault("SPARKDL_FEEDER_LINGER_MS", "200")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


N_PARTITIONS = 4
ROWS_PER_PARTITION = 40
BATCH_SIZE = 16


def _run_workload():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.runtime.executor import Executor
    from sparkdl_tpu.transformers.execution import (
        arrays_to_batch,
        data_parallel_device_fn,
        run_batched_shared,
    )

    device_fn = data_parallel_device_fn(
        jax.jit(lambda b: jnp.tanh(b).sum(axis=1, keepdims=True)),
        devices=[jax.devices()[0]],
    )
    rng = np.random.default_rng(0)
    parts = [
        [
            rng.normal(size=(8,)).astype(np.float32)
            for _ in range(ROWS_PER_PARTITION)
        ]
        for _ in range(N_PARTITIONS)
    ]
    Executor(max_workers=N_PARTITIONS).map_partitions(
        lambda i, cells: run_batched_shared(
            cells, arrays_to_batch, device_fn, batch_size=BATCH_SIZE
        ),
        parts,
        count_rows=len,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--out-dir", default=None,
        help="where rank snapshots / merged trace / jsonl land "
        "(default: a temp dir)",
    )
    args = ap.parse_args(argv)
    out_dir = args.out_dir or tempfile.mkdtemp(prefix="telemetry_smoke_")
    os.makedirs(out_dir, exist_ok=True)

    from sparkdl_tpu import obs
    from sparkdl_tpu.obs import aggregate, serve
    from sparkdl_tpu.obs.timeseries import MetricsSampler
    from sparkdl_tpu.runtime.feeder import shutdown_feeders
    from sparkdl_tpu.utils.metrics import metrics

    problems = []
    jsonl = os.path.join(out_dir, "telemetry_events.jsonl")

    # -- rank 0: workload under an actively-ticking sampler -------------------
    metrics.reset()
    obs.get_recorder().clear()
    sampler = MetricsSampler(interval=0.05, capacity=512, jsonl_path=jsonl)
    sampler.start()
    _run_workload()
    shutdown_feeders()  # owner exits => depth gauges zeroed (satellite)
    sampler.stop()

    series = sampler.series()
    total_rows = N_PARTITIONS * ROWS_PER_PARTITION
    if not series:
        problems.append("sampler recorded no series at all")
    if not series.get("feeder.rows"):
        problems.append("no feeder.rows series")
    elif series["feeder.rows"][-1][1] != total_rows:
        problems.append(
            f"feeder.rows final sample {series['feeder.rows'][-1][1]:.0f} "
            f"!= {total_rows}"
        )
    if not any(name.endswith("/s") and pts for name, pts in series.items()):
        problems.append("no derived /s rate series")
    q = series.get("feeder.queue_depth")
    if not q:
        problems.append("no feeder.queue_depth series")
    elif q[-1][1] != 0:
        problems.append(
            f"queue_depth not cleared after owner exit (last={q[-1][1]})"
        )
    try:
        with open(jsonl) as f:
            events = [json.loads(ln) for ln in f if ln.strip()]
        if not any(e.get("kind") == "sample" for e in events):
            problems.append("jsonl log has no sample events")
    except (OSError, json.JSONDecodeError) as e:
        problems.append(f"jsonl log unreadable: {e}")

    # -- Prometheus over HTTP -------------------------------------------------
    server = serve.start_server(port=0)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()
        parsed = {}
        for line in text.splitlines():
            if line.startswith("#") or not line.strip():
                continue
            name, _, val = line.rpartition(" ")
            parsed[name] = float(val)  # every sample line must parse
        if "feeder_queue_depth" not in parsed:
            problems.append("prometheus text lacks feeder_queue_depth")
        if parsed.get("feeder_rows_total") != float(total_rows):
            problems.append(
                f"feeder_rows_total {parsed.get('feeder_rows_total')} "
                f"!= {total_rows}"
            )
    except Exception as e:  # noqa: BLE001
        problems.append(f"/metrics scrape failed: {type(e).__name__}: {e}")
    finally:
        serve.stop_server()

    # -- two simulated ranks: merge + straggler -------------------------------
    snap0 = obs.snapshot(rank=0)
    aggregate.write_rank_snapshot(out_dir, 0, snap0)
    obs.get_recorder().clear()
    _run_workload()
    shutdown_feeders()
    snap1 = obs.snapshot(rank=1)
    # Synthetic straggler, clearly labeled: rank 1 "spends" 10x the
    # gang's drain-stage total in one extra span (2 s floor keeps its
    # per-span p95 far above the detector's absolute gap floor), so the
    # detector has a known-divergent stage to flag (the mechanism under
    # test, not a measurement). The drain stage's NAME is arm-dependent
    # (drain_wait under the async-readback default, device_wait legacy),
    # so inject into whichever stage this run actually recorded — the
    # detector needs the stage present on both ranks.
    drain_stage = (
        "drain_wait"
        if any(s["name"] == "drain_wait" for s in snap1["spans"])
        else "device_wait"
    )
    dev_total = sum(
        s["dur_s"] for s in snap1["spans"] if s["name"] == drain_stage
    )
    snap1["spans"].append(
        {
            "name": drain_stage,
            "span_id": 10**9,
            "parent_id": None,
            "thread_id": 1,
            "thread_name": "synthetic-straggler",
            "start_unix": snap1["generated_unix"],
            "dur_s": max(2.0, 10 * dev_total),
            "attrs": {"synthetic": True},
        }
    )
    aggregate.write_rank_snapshot(out_dir, 1, snap1)

    snaps = aggregate.load_rank_snapshots(out_dir)
    if sorted(snaps) != [0, 1]:
        problems.append(f"expected ranks [0, 1], loaded {sorted(snaps)}")
    trace_path = os.path.join(out_dir, "merged_trace.json")
    aggregate.write_merged_trace(trace_path, snaps)
    try:
        with open(trace_path) as f:
            trace = json.load(f)
        lanes = {
            e["pid"] for e in trace["traceEvents"] if e.get("ph") == "X"
        }
        if lanes != {0, 1}:
            problems.append(f"merged trace lanes {sorted(lanes)} != [0, 1]")
        if not any(
            e.get("ph") == "M" and e.get("name") == "process_name"
            for e in trace["traceEvents"]
        ):
            problems.append("merged trace lacks process_name lane labels")
    except (OSError, json.JSONDecodeError, KeyError) as e:
        problems.append(f"merged trace invalid: {e}")
    flagged = aggregate.straggler_summary(snaps)
    if not any(
        f["stage"] == drain_stage and f["slowest_rank"] == 1
        for f in flagged
    ):
        problems.append(
            f"synthetic {drain_stage} straggler on rank 1 not flagged "
            f"(flagged: {flagged})"
        )
    report_text = aggregate.render_rank_report(snaps)
    if "straggler" not in report_text:
        problems.append("rank report does not mention the straggler")
    print(report_text)

    verdict = {
        "telemetry_smoke": "FAIL" if problems else "OK",
        "series": len(series),
        "merged_trace": trace_path,
        "stragglers_flagged": len(flagged),
        "out_dir": out_dir,
    }
    if problems:
        verdict["problems"] = problems
        print(json.dumps(verdict), file=sys.stderr)
        return 1
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
