#!/usr/bin/env bash
# One-command CPU preflight (every drill here is a CPU drill): proves the flight
# recorder (obs_smoke), the shared device feeder (feeder_smoke, incl.
# the async-readback arm A/B + thread-leak check), the SQL optimizer
# arm (sql_smoke: mixed query flood with cross-partition coalesced UDF
# batches — sql.udf.batches < partition count — a pruned metadata scan
# decoding zero probe cells, and vectorized/legacy row parity), the
# device-resident
# input half (resident_smoke: staged-H2D overlap counters, staging /
# device-preproc arm parity, compile-cache ledger hit, no leaked
# feeder/transfer threads), the fleet-telemetry layer (telemetry_smoke),
# the resilience layer's gang-restart loop (chaos_smoke:
# fault-plan-crashed rank -> supervisor restart -> resumed job, output
# identical to fault-free), the online serving layer (serving_smoke:
# SLA-class separation, adaptive batch sizing, residency eviction under
# budget, parity with the offline engine), the supervised serving gang
# (serving_chaos_smoke: gateway + 2 workers, fault-plan worker crash
# mid-flood -> exactly 1 supervisor restart, zero lost accepted
# requests, outputs row-identical to the run_batched_shared oracle, canary
# split within tolerance, drain semantics, no leaked threads), and the
# sequence-bucketed text engine (text_smoke: per-bucket pad ratio,
# bucketed-vs-unbucketed row parity, long-context model over
# POST /v1/predict), the end-to-end request tracing layer (trace_smoke:
# traced flood gateway -> worker with all waterfall segments
# summing to the measured e2e, a mid-flood worker crash stitched as two
# attempts under one trace_id with zero lost requests, /metrics p99
# exemplar resolving via `obs trace` to a real waterfall, default-rate
# tracing within 3% of tracing-off), the live SLO engine + goodput
# ledger (slo_smoke: healthy flood trips nothing, an injected-latency
# fault plan trips the fast-burn alert with a resolvable exemplar
# trace id in the JSONL event, clearing it recovers, and per-device
# busy+idle conserves against the measured flood wall within
# max(10ms, 5%)), the autoregressive generation engine
# (generation_smoke: streamed generate flood gateway -> worker, every
# sequence token-identical to a cacheless greedy oracle, mid-batch
# joins + slot reuse observed, KV bytes back to zero, no leaked
# threads), the device-memory ledger (memory_smoke: two models
# churning under a one-model HBM budget — per-swap evictions all
# attributed, watermark above steady state, /v1/memory reconciling
# against ground truth, an injected allocation failure landing an OOM
# forensic dump that names the resident table, and close returning
# tracked bytes to zero with no leak event), the fleet observability
# plane (fleet_smoke: gateway
# + 2 workers each under the per-worker SLO floor while the fleet sum
# crosses it -> fleet alert trips with contributing ranks + resolvable
# exemplars while every worker stays quiet, federated rank-labeled
# /metrics agreeing with /v1/fleet, recovery, advisory-only
# recommendation JSONL, SIGKILL-mid-scrape degrading to a stale marker
# with no false alert), the closed fleet control loop (autoscale_smoke:
# affinity routing shards a 2-model flood onto disjoint ring homes with
# strictly fewer cold loads than the round-robin control arm, then the
# actuating autoscaler grows the gang on a fleet SLO trip, converges
# through a mid-flood SIGKILL at the scaled size with zero lost
# requests, observes recovery, and drain-shrinks on idle dilution
# without ever counting the planned exit as gang death), and the
# mesh/precision serving arms (mesh_smoke:
# 4 emulated chips — width-4 serving row-identical to width-1 at f32,
# within tolerance at bf16/int8-dynamic, exact global-rung accounting,
# aggregate flood throughput > 1.5x the 1-chip arm, per-class precision
# residency keying) end-to-end on CPU before any chip time is spent. When BENCH_HISTORY.json has banked full records it also
# self-checks the perf regression gate: the newest banked record is
# re-gated against the rest of its pool (tools/bench_gate.py,
# --no-append), proving the gate machinery + history consistency without
# running a benchmark. Each step prints a one-line JSON verdict; this
# wrapper runs them all under timeouts and exits nonzero if ANY failed,
# so a caller can gate on a single command:
#
#   tools/preflight.sh || { echo "preflight failed"; exit 1; }
#
# PREFLIGHT_TIMEOUT_S (default 300) bounds each step individually.

set -u
cd "$(dirname "$0")/.."

TMO="${PREFLIGHT_TIMEOUT_S:-300}"
rc=0

# Static analysis first: knob-registry drift, metrics-surface rot,
# concurrency discipline, stale docs/KNOBS.md (tools/lint). Cheapest
# step and the one that catches convention drift before any runtime
# smoke spends cycles on it. Same per-step timeout + one-line JSON
# verdict contract as the smokes.
echo "== preflight: lint" >&2
if ! timeout -k 10 "$TMO" python -m tools.lint; then
  echo "PREFLIGHT FAIL: lint" >&2
  rc=1
fi

# feeder + serving smokes run under the runtime lock sanitizer
# (SPARKDL_LOCK_SANITIZER=1): order-recording lock proxies build the
# observed held-before graph, and the smokes fail on any observed
# cycle or on an edge the static analyzer (tools/lint/lockorder_check)
# does not imply. The other smokes run plain — chaos_smoke spawns
# worker subprocesses whose timing the proxies would skew.
# serving_chaos_smoke (the gateway/gang drill: worker crash mid-flood ->
# 1 supervisor restart, zero lost accepted requests, canary split,
# drain semantics) runs sanitized too: the gateway process's own locks
# are the ones under test there.
for smoke in obs_smoke feeder_smoke sql_smoke resident_smoke telemetry_smoke chaos_smoke serving_smoke serving_chaos_smoke text_smoke mesh_smoke trace_smoke slo_smoke memory_smoke fleet_smoke autoscale_smoke generation_smoke; do
  extra_env=()
  case "$smoke" in
    feeder_smoke|sql_smoke|serving_smoke|serving_chaos_smoke|text_smoke|mesh_smoke|trace_smoke|slo_smoke|memory_smoke|fleet_smoke|autoscale_smoke|generation_smoke) extra_env=(SPARKDL_LOCK_SANITIZER=1) ;;
  esac
  echo "== preflight: $smoke" >&2
  if ! JAX_PLATFORMS=cpu timeout -k 10 "$TMO" \
      env "${extra_env[@]}" python "tools/$smoke.py"; then
    echo "PREFLIGHT FAIL: $smoke" >&2
    rc=1
  fi
done

# Bench-gate self-check, only when records are banked (a fresh checkout
# has none: nothing to gate, not a failure). Wide thresholds on purpose:
# this catches broken gate machinery and gross banked regressions, not
# CPU-measurement noise (BENCH_HISTORY has shown >2x swings on identical
# CPU configs — a tight threshold here would make preflight flaky).
echo "== preflight: bench_gate" >&2
gate_record="$(mktemp /tmp/preflight_gate_record.XXXXXX.json)"
trap 'rm -f "$gate_record"' EXIT
if JAX_PLATFORMS=cpu python - "$gate_record" <<'PY'
import json, sys

try:
    with open("BENCH_HISTORY.json") as f:
        hist = json.load(f)
except (OSError, json.JSONDecodeError):
    sys.exit(3)
records = hist.get("records") or {}
# newest banked record = the last runs[] entry whose key has a pool
for run in reversed(hist.get("runs") or []):
    key = f"{run.get('mode')}/{run.get('config')}"
    pool = records.get(key)
    if pool:
        with open(sys.argv[1], "w") as f:
            json.dump(pool[-1], f)
        sys.exit(0)
sys.exit(3)
PY
then
  if ! JAX_PLATFORMS=cpu timeout -k 10 "$TMO" python tools/bench_gate.py \
      --record "$gate_record" --no-append \
      --threshold 0.5 --stage-threshold 0.6; then
    echo "PREFLIGHT FAIL: bench_gate" >&2
    rc=1
  fi
else
  echo '{"bench_gate": "SKIP", "reason": "no banked bench records"}' >&2
fi

if [ "$rc" -eq 0 ]; then
  echo '{"preflight": "OK"}'
else
  echo '{"preflight": "FAIL"}' >&2
fi
exit $rc
