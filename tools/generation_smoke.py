"""Generation smoke: streamed autoregressive serving through the REAL
gang path (HTTP client -> gateway -> worker -> GenerationEngine), on
CPU, no chip required.

One supervised worker (bert-tiny off the registry, 2 decode slots via
``SPARKDL_GEN_MAX_SEQS=2``) takes a concurrent staggered-length flood
of streamed ``mode="generate"`` requests plus one blocking request.
Asserts:

- **oracle parity** — every streamed token sequence matches an
  in-process cacheless ``greedy_oracle`` over the same (seed-
  deterministic) weights, row-identically: the KV-cache decode path
  reproduces full-recompute greedy decoding exactly.
- **continuous batching observed** — the worker's ``generation`` stats
  (read back through the gateway's forwarded ``/v1/models``) show
  mid-batch ``joins`` > 0 (a sequence enrolled into a RUNNING decode
  batch) and ``slot_reuse`` > 0 (6 sequences over 2 slots: a retired
  sequence's slot was handed to a newcomer).
- **trace continuity** — every streamed frame carries the reply
  header's trace id (gateway-minted, worker-threaded).
- **KV bytes return to baseline** — the worker's ``/v1/memory`` device
  ledger shows zero resident ``kv_cache`` bytes after the flood.
- **zero leaked threads** — no live ``sparkdl-*`` thread in THIS
  process after the gateway stops (the decode stream's shutdown hook
  reaps ``sparkdl-gen-*`` threads worker-side; the worker's own exit
  is supervised).

Exit 0 and a one-line JSON verdict on success; exit 1 naming what
failed.

Usage (also wired into tools/preflight.sh, under the lock sanitizer)::

    JAX_PLATFORMS=cpu python tools/generation_smoke.py
"""

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("SPARKDL_INFERENCE_MODE", "roundrobin")
os.environ.setdefault("SPARKDL_INFERENCE_DEVICES", "1")
# 2 decode slots x 6 sequences: slot reuse is GUARANTEED, not lucky —
# rides into the worker env through the gateway launch.
os.environ.setdefault("SPARKDL_GEN_MAX_SEQS", "2")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


MODEL = "bert-tiny"
N_SEQS = 6
READY_TIMEOUT_S = 120.0
REQUEST_TIMEOUT_S = 300.0


def _prompts():
    """Staggered lengths so prefill buckets differ across the flood."""
    return [list(range(1, 4 + i)) for i in range(N_SEQS)]


def _max_new(i):
    return 4 + (i % 3)


def _oracle_tokens():
    """Sequential cacheless greedy decode over an independently built
    generator — registry inits are seed-deterministic, so this is the
    same function the worker serves, minus the KV cache under test."""
    import numpy as np

    from sparkdl_tpu.models.registry import get_model

    gen = get_model(MODEL).generate_function()
    return [
        [int(t) for t in gen.greedy_oracle(np.asarray(p, np.int32), _max_new(i))]
        for i, p in enumerate(_prompts())
    ]


def _wait_ready(base, problems):
    deadline = time.monotonic() + READY_TIMEOUT_S
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(f"{base}/healthz", timeout=5) as r:
                if json.loads(r.read()).get("status") == "ok":
                    return True
        except Exception:
            pass
        time.sleep(0.25)
    problems.append(f"no ready worker within {READY_TIMEOUT_S:.0f}s")
    return False


def _stream_one(base, i, out, errors):
    """POST one streamed generate; collect (tokens, trace_ok, done)."""
    body = json.dumps(
        {
            "model": MODEL,
            "inputs": _prompts()[i],
            "mode": "generate",
            "max_new_tokens": _max_new(i),
            "stream": True,
        }
    ).encode()
    req = urllib.request.Request(f"{base}/v1/predict", data=body)
    try:
        with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as resp:
            trace = resp.headers.get("X-Sparkdl-Trace")
            records = [json.loads(ln) for ln in resp if ln.strip()]
        tokens = [r["token"] for r in records if "token" in r]
        done = records[-1] if records else {}
        out[i] = {
            "tokens": tokens,
            "trace_ok": bool(trace)
            and all(r.get("trace_id") == trace for r in records),
            "done": done,
        }
    except Exception as e:
        errors.append(f"seq {i}: {type(e).__name__}: {e}")


def _get_json(base, path):
    with urllib.request.urlopen(f"{base}{path}", timeout=30) as r:
        return json.loads(r.read())


def _flood(base, problems):
    expected = _oracle_tokens()
    out = {}
    errors = []
    threads = [
        threading.Thread(
            target=_stream_one,
            args=(base, i, out, errors),
            name=f"sparkdl-gensmoke-{i}",
            daemon=True,
        )
        for i in range(N_SEQS)
    ]
    for i, t in enumerate(threads):
        t.start()
        time.sleep(0.05 * i)  # staggered arrivals: joins, not a batch
    for t in threads:
        t.join(timeout=REQUEST_TIMEOUT_S)
    problems += errors
    matched = 0
    for i in range(N_SEQS):
        got = out.get(i)
        if got is None:
            continue
        if got["tokens"] != expected[i]:
            problems.append(
                f"seq {i} streamed tokens {got['tokens']} != oracle "
                f"{expected[i]}"
            )
        else:
            matched += 1
        if not got["trace_ok"]:
            problems.append(f"seq {i} frames missing/mismatching trace id")
        if got["done"].get("tokens") != [expected[i]]:
            problems.append(f"seq {i} final record tokens != oracle")

    # one blocking (non-stream) request for the other reply shape
    body = json.dumps(
        {
            "model": MODEL,
            "inputs": _prompts()[0],
            "mode": "generate",
            "max_new_tokens": _max_new(0),
        }
    ).encode()
    req = urllib.request.Request(f"{base}/v1/predict", data=body)
    try:
        with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as resp:
            payload = json.loads(resp.read())
        if payload.get("tokens") != [expected[0]]:
            problems.append("blocking generate tokens != oracle")
    except Exception as e:
        problems.append(f"blocking generate failed: {type(e).__name__}: {e}")

    # continuous batching + catalog, read off the worker via the gateway
    models = _get_json(base, "/v1/models")
    gen_stats = models.get("generation") or {}
    if gen_stats.get("joins", 0) < 1:
        problems.append(
            f"no mid-batch join observed (joins={gen_stats.get('joins')})"
        )
    if gen_stats.get("slot_reuse", 0) < 1:
        problems.append(
            "no slot reuse observed "
            f"(slot_reuse={gen_stats.get('slot_reuse')})"
        )
    rows = {r["name"]: r for r in models.get("supported") or []}
    tiny = rows.get(MODEL) or {}
    if tiny.get("modes") != ["embed", "generate"] or not tiny.get(
        "kv_bytes_per_token"
    ):
        problems.append(
            f"/v1/models catalog row for {MODEL} missing modes/kv "
            f"advertisement: {tiny}"
        )

    # KV bytes back to baseline on the worker's device ledger
    mem = _get_json(base, "/v1/memory")
    kv_left = sum(
        d.get("kv_bytes", 0)
        for d in (mem.get("devices") or {}).values()
    )
    if kv_left:
        problems.append(f"{kv_left} KV bytes still resident after flood")
    return {
        "seqs_matched": matched,
        "joins": int(gen_stats.get("joins", 0)),
        "slot_reuse": int(gen_stats.get("slot_reuse", 0)),
        "tokens_out": int(gen_stats.get("tokens_out", 0)),
        "kv_bytes_after": int(kv_left),
    }


def _leaked_threads():
    return [
        t
        for t in threading.enumerate()
        if t.is_alive() and t.name.startswith("sparkdl-")
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.parse_args(argv)

    from sparkdl_tpu.serving import ServingGateway

    problems = []
    stats = {}
    # workers are `python -m sparkdl_tpu.serving` subprocesses: put the
    # repo root on their path so the smoke runs from any cwd
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pythonpath = os.pathsep.join(
        p for p in (root, os.environ.get("PYTHONPATH")) if p
    )
    gw = ServingGateway(
        num_workers=1,
        port=0,
        extra_env={
            "PYTHONPATH": pythonpath,
            "JAX_PLATFORMS": "cpu",
            "SPARKDL_INFERENCE_MODE": "roundrobin",
            "SPARKDL_INFERENCE_DEVICES": "1",
            "SPARKDL_GEN_MAX_SEQS": "2",
        },
    ).start()
    base = f"http://127.0.0.1:{gw.port}"
    try:
        if _wait_ready(base, problems):
            stats = _flood(base, problems)
    finally:
        gw.stop()

    from sparkdl_tpu.runtime.feeder import shutdown_feeders

    shutdown_feeders()
    leaked = _leaked_threads()
    if leaked:
        time.sleep(0.5)
        leaked = _leaked_threads()
    if leaked:
        problems.append(
            "leaked threads after stop: "
            + ", ".join(t.name for t in leaked)
        )

    lock_problems, lock_stats = _common.lock_sanitizer_problems()
    problems += lock_problems

    verdict = {
        "generation_smoke": "FAIL" if problems else "OK",
        **stats,
        **lock_stats,
    }
    if problems:
        verdict["problems"] = problems
        print(json.dumps(verdict), file=sys.stderr)
        return 1
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
