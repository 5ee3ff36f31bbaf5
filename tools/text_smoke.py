"""Text-engine smoke: prove the sequence-bucketed text path end-to-end
on CPU, no chip or vocab download required (mirrors serving_smoke.py).

Two phases over the REAL stack:

1. **Bucketed feeder geometries** (TextEmbedder over a 2-layer
   encoder): a mixed-length corpus (two-thirds uniform in [16, 512] —
   the ladder's worst-case distribution — plus a short-document third,
   ``maxLength`` 512) with a null row and an over-long row. Asserts:

   - bucket-edge pad fraction (``text.pad_tokens`` over dispatched
     tokens) < 15%, where the pad-to-``maxLength`` arm wastes > 50% of
     every dispatched token on the same corpus (computed analytically
     from the identical lengths),
   - rows routed across >= 4 distinct bucket geometries
     (``text.bucket_rows.<bucket>``), truncation observable
     (``text.truncated_rows`` >= 1 from the over-long row),
   - outputs ROW-IDENTICAL (allclose) to the unbucketed
     ``SPARKDL_TEXT_BUCKETING=0`` arm, nulls riding through — the
     cross-bucket scatter preserves row order exactly.

2. **Long-context serving** (seq >= 2048): the registry's
   ``bert-long-2048`` (flash-attention composition; dense einsum
   self-selected on CPU) served through a real HTTP
   ``POST /v1/predict`` round-trip. Two requests of different lengths
   seq-bucket to ONE 2048 stream (router grouping key carries the
   bucket); outputs match a direct ``run_batched_shared`` oracle over the
   same model function.

Epilogue: zero leaked ``sparkdl-*`` threads after shutdown, and the
lock-sanitizer cross-check when preflight runs this smoke under
``SPARKDL_LOCK_SANITIZER=1`` (house style from the lock-discipline PR).

Usage (also wired into tools/preflight.sh)::

    JAX_PLATFORMS=cpu python tools/text_smoke.py
"""

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# One device, round-robin: dispatched geometry == configured batch, so
# the pad arithmetic below is platform-independent.
os.environ.setdefault("SPARKDL_INFERENCE_MODE", "roundrobin")
os.environ.setdefault("SPARKDL_INFERENCE_DEVICES", "1")
os.environ.setdefault("SPARKDL_FEEDER_IDLE_S", "0")
# The serving phase multiplies feeder streams (model x rung x seq
# bucket); keep them out of LRU churn, like the serve CLI does.
os.environ.setdefault("SPARKDL_MAX_FEEDERS", "32")

import _common  # noqa: E402,F401  (puts the repo root on sys.path)


MAX_LEN = 512
BATCH = 8
N_ROWS = 240
LONG_MODEL = "bert-long-2048"


def _model_function():
    """Scaled-down encoder with a FULL 512-position table: big enough
    to exercise every bucket the corpus elects, small enough that the
    unbucketed A/B arm stays cheap on a host core."""
    from sparkdl_tpu.models.bert import BertConfig, bert_model_function

    return bert_model_function(
        config=BertConfig(
            vocab_size=2048,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            intermediate_size=128,
            max_position_embeddings=MAX_LEN,
        ),
        max_length=MAX_LEN,
    )


def _corpus():
    """Deterministic mixed-length corpus: token length = words + 2
    (CLS/SEP). Two-thirds uniform in [16, 512] (the ladder's worst-case
    distribution) plus a short-document third in [16, 96] (real corpora
    are short-skewed) — mean length ~195, so the pad-to-maxLength arm
    wastes >60% of its dispatched tokens where the ladder pads ~14%.
    One null row, one over-long row (truncates at the 512 top edge —
    the documented lossy case)."""
    import numpy as np

    rng = np.random.default_rng(7)
    lengths = np.concatenate(
        [
            rng.integers(16, 513, size=2 * N_ROWS // 3),
            rng.integers(16, 97, size=N_ROWS - 2 * N_ROWS // 3),
        ]
    )
    rng.shuffle(lengths)
    texts = [
        " ".join(f"w{i}t{j}" for j in range(int(l) - 2))
        for i, l in enumerate(lengths)
    ]
    texts[5] = None
    lengths[5] = 0
    over = 600
    texts[11] = " ".join(f"ww{j}" for j in range(over - 2))
    lengths[11] = over
    return texts, lengths


def _phase_bucketing(problems):
    import numpy as np

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.transformers.text import TextEmbedder
    from sparkdl_tpu.utils.metrics import metrics

    texts, lengths = _corpus()
    df = DataFrame.fromColumns({"text": texts}, numPartitions=4)
    mf = _model_function()

    def run(bucketing):
        os.environ["SPARKDL_TEXT_BUCKETING"] = "1" if bucketing else "0"
        try:
            emb = TextEmbedder(
                inputCol="text", outputCol="e", modelFunction=mf,
                maxLength=MAX_LEN, batchSize=BATCH,
            )
            return [r.e for r in emb.transform(df).collect()]
        finally:
            os.environ.pop("SPARKDL_TEXT_BUCKETING", None)

    metrics.reset()
    t0 = time.perf_counter()
    bucketed = run(True)
    bucketed_s = time.perf_counter() - t0
    counters = metrics.snapshot()["counters"]
    real = counters.get("text.tokens", 0)
    pad = counters.get("text.pad_tokens", 0)
    dispatched = real + pad
    pad_ratio = pad / dispatched if dispatched else 1.0
    buckets = sorted(
        int(k.rsplit(".", 1)[-1])
        for k in counters
        if k.startswith("text.bucket_rows.")
    )
    if pad_ratio >= 0.15:
        problems.append(
            f"bucketed pad ratio {pad_ratio:.1%} >= 15% on the mixed "
            f"corpus (buckets {buckets})"
        )
    # the arm this engine replaces: EVERY row pays maxLength tokens
    valid = [int(min(l, MAX_LEN)) for l in lengths if l]
    unbucketed_waste = 1.0 - sum(valid) / (len(valid) * MAX_LEN)
    if unbucketed_waste <= 0.5:
        problems.append(
            f"corpus no longer demonstrates the pad-to-maxLength waste "
            f"(got {unbucketed_waste:.1%}, want > 50%)"
        )
    if len(buckets) < 4:
        problems.append(
            f"expected >= 4 distinct bucket geometries, saw {buckets}"
        )
    routed = sum(
        int(v) for k, v in counters.items()
        if k.startswith("text.bucket_rows.")
    )
    if routed != len(valid):
        problems.append(
            f"bucket_rows total {routed} != {len(valid)} valid rows"
        )
    if counters.get("text.truncated_rows", 0) < 1:
        problems.append(
            "over-long row did not record text.truncated_rows"
        )

    # ordering parity: the cross-bucket scatter must hand every row its
    # own embedding, exactly where the unbucketed path puts it
    unbucketed = run(False)
    if not (bucketed[5] is None and unbucketed[5] is None):
        problems.append("null row did not ride through as None")
    mismatch = sum(
        1
        for a, b in zip(bucketed, unbucketed)
        if (a is None) != (b is None)
        or (
            a is not None
            and not np.allclose(a, b, rtol=2e-4, atol=2e-4)
        )
    )
    if mismatch:
        problems.append(
            f"{mismatch} rows differ between bucketed and unbucketed "
            "paths (cross-bucket scatter broke row order)"
        )
    return {
        "pad_ratio": round(pad_ratio, 4),
        "unbucketed_waste": round(unbucketed_waste, 4),
        "buckets": buckets,
        "rows": len(valid),
        "truncated_rows": int(counters.get("text.truncated_rows", 0)),
        "bucketed_s": round(bucketed_s, 1),
    }


def _phase_long_context(problems):
    import numpy as np

    from sparkdl_tpu.models import get_model
    from sparkdl_tpu.serving import Router, start_server
    from sparkdl_tpu.transformers.execution import (
        model_device_fn,
        run_batched_shared,
    )
    from sparkdl_tpu.utils.metrics import metrics

    spec = get_model(LONG_MODEL)
    rng = np.random.default_rng(3)
    seqs = []
    for length in (1800, 2048):  # different lengths, ONE 2048 bucket
        row = np.zeros((2048,), np.int64)
        row[:length] = rng.integers(4, spec.vocab_size, length)
        seqs.append((length, row))

    router = Router()
    server = start_server(router, port=0)
    before_pad = metrics.counter("text.pad_tokens")
    outputs = []
    try:
        for length, row in seqs:
            body = json.dumps(
                {
                    "model": LONG_MODEL,
                    "inputs": [row[:length].tolist()],
                    "dtype": "int32",
                    "mode": "embed",
                    "priority": "batch",
                }
            ).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/predict",
                data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=600) as resp:
                reply = json.loads(resp.read())
            outputs.append(np.asarray(reply["outputs"], np.float32))
        if any(o.shape != (1, spec.feature_dim) for o in outputs):
            problems.append(
                f"long-context outputs misshapen: "
                f"{[o.shape for o in outputs]}"
            )
        # the 1800-row request must have seq-bucketed up to 2048
        pad_added = metrics.counter("text.pad_tokens") - before_pad
        if pad_added < 2048 - 1800:
            problems.append(
                "1800-token request did not seq-bucket to the 2048 "
                f"stream (pad tokens added: {pad_added:.0f})"
            )
        # oracle: the same rows through the batch engine's one entry
        # over the same registry model function
        dfn = model_device_fn(spec.model_function(mode="embed"))

        def to_batch(chunk):
            return np.stack(chunk), np.ones((len(chunk),), bool)

        oracle = run_batched_shared(
            [row.astype(np.int32) for _, row in seqs],
            to_batch,
            dfn,
            batch_size=2,
        )
        for i, (got, want) in enumerate(zip(outputs, oracle)):
            if not np.allclose(got[0], want, rtol=2e-4, atol=2e-4):
                problems.append(
                    f"long-context serving/run_batched_shared mismatch at "
                    f"request {i}"
                )
        resident = [
            m["name"] for m in router.residency.models()
        ]
        if LONG_MODEL not in resident:
            problems.append(
                f"{LONG_MODEL} not in residency table: {resident}"
            )
        return {
            "long_model": LONG_MODEL,
            "long_param_mb": round(spec.param_bytes_estimate() / 2**20, 2),
            "seq_bucket_pad_tokens": int(pad_added),
        }
    finally:
        server.stop()
        router.close()


def _leaked_threads():
    return [
        t
        for t in threading.enumerate()
        if t.is_alive() and t.name.startswith("sparkdl-")
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.parse_args(argv)

    problems = []
    bucketing = _phase_bucketing(problems)
    long_ctx = _phase_long_context(problems)

    from sparkdl_tpu.runtime.feeder import shutdown_feeders

    shutdown_feeders()
    leaked = _leaked_threads()
    if leaked:
        time.sleep(0.5)
        leaked = _leaked_threads()
    if leaked:
        problems.append(
            "leaked threads after shutdown: "
            + ", ".join(t.name for t in leaked)
        )

    lock_problems, lock_stats = _common.lock_sanitizer_problems()
    problems += lock_problems

    verdict = {
        "text_smoke": "FAIL" if problems else "OK",
        **bucketing,
        **long_ctx,
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
