"""Benchmarks for the five BASELINE.json configs and the serving paths.

``python bench.py`` runs the selected mode in THIS process on the TPU
and prints one JSON record as its last line: the measured metric, the
``platform``, ``device_kind`` and device ``count`` it ran on, and the
mode's extras. There is no fallback: with no TPU the run exits non-zero
before measuring anything, and a mode that fails exits non-zero with
its traceback. ``BENCH_PLATFORM=cpu`` is the explicit CPU rehearsal
(tiny sizes; the record says ``"platform": "cpu"`` and its number is
not a device metric). One process holds the chip, so nothing here
starts a child that needs it.

Mode selection via ``BENCH_MODE``:

  featurizer   DeepImageFeaturizer(ResNet50) images/sec/chip   [default]
  keras_image  KerasImageFileTransformer(ResNet50) over files, images/sec/chip
  udf          registerKerasImageUDF(MobileNetV2) scoring, images/sec/chip
  udf_sql      the same scoring through sql("SELECT udf(image) ...") —
               the SQL-planner overhead A/B against udf
  bert         TextEmbedder BERT-base, examples/sec/chip
  text         sequence-bucketed TextEmbedder over a MIXED-length
               corpus, tokens/sec/chip (real tokens; pad ratio and the
               bucket mix ride the extras)
  train        DataParallelEstimator ResNet50 fine-tune, mean step time (s)
  serving      online serving layer (router + adaptive batching +
               residency) under mixed-class synthetic load, requests/sec
               (per-class p50/p95 latency in extras)
  generate     autoregressive generation engine (bert-tiny prefill +
               KV-cached continuous-batching decode), tokens/sec/chip
               (prefill vs decode attributed separately in extras)

Baselines in BENCH_HISTORY.json (created on first run, git-ignored) are
keyed by mode + platform + every variant marker (``_config_for_record``),
so numbers measured under different configurations are never compared.
"""

import json
import os
import sys
import time

_MODES = (
    "featurizer", "keras_image", "udf", "udf_sql", "bert", "text",
    "train", "serving", "generate",
)

# Metrics where lower is better (vs_baseline inverts accordingly).
_TIME_METRICS = {"train"}


def _mode() -> str:
    mode = os.environ.get("BENCH_MODE", "featurizer")
    if mode not in _MODES:
        raise ValueError(f"BENCH_MODE={mode!r}; expected one of {_MODES}")
    return mode


def _is_cpu(platform: str) -> bool:
    return platform == "cpu"


# ---------------------------------------------------------------------------
# Benchmark implementations. Each returns (metric, value, unit, extras).
# Sizes are chosen per-platform: the CPU rehearsal exists to prove the
# path end-to-end, not to grind ImageNet on a host core.
# ---------------------------------------------------------------------------


def _synthetic_structs(n, h=224, w=224, seed=0):
    import numpy as np

    from sparkdl_tpu.image import imageIO

    rng = np.random.default_rng(seed)
    return [
        imageIO.imageArrayToStruct(
            rng.integers(0, 256, size=(h, w, 3), dtype=np.uint8)
        )
        for _ in range(n)
    ]


def _feed_knob_fields() -> dict:
    """Feed-path A/B knobs, recorded by ENGAGEMENT, not env
    presence: the runtime silently falls back to the baseline path when
    a knob's preconditions don't hold (multi-device, CPU, chunking
    disabled), and an A/B record labeled with the treatment arm while
    the baseline ran would bank a lie. Engagement comes from the SAME
    functions the runtime gates on (execution.feed_plan,
    function.param_placement_engaged) — never a hand-copied predicate."""
    from sparkdl_tpu.graph.function import param_placement_engaged
    from sparkdl_tpu.runtime import knobs
    from sparkdl_tpu.transformers.execution import feed_plan

    plan = feed_plan()
    out = {}
    if plan["fuse"]:
        out["h2d_fuse"] = plan["fuse"]
        out["h2d_fuse_engaged"] = plan["fuse_engaged"]
    mode = knobs.get_raw("SPARKDL_H2D_CHUNK_MODE")
    if mode:
        out["h2d_chunk_mode"] = mode
        out["h2d_chunk_mode_engaged"] = (
            plan["chunk_engaged"] and not plan["fuse_engaged"]
        )
    placement = knobs.get_raw("SPARKDL_PARAM_PLACEMENT")
    if placement and placement != "closure":
        out["param_placement"] = placement
        out["param_placement_engaged"] = param_placement_engaged()
    return out


def _stage_breakdown(metrics_registry) -> dict:
    """mean ms/batch for the hot loop's own stage timers."""
    snap = metrics_registry.snapshot().get("timers", {})
    return {
        k.split(".")[-1]: round(v["mean_s"] * 1e3, 1)
        for k, v in snap.items()
        if k in ("transform.host_batch", "transform.device_wait")
    }


def _obs_reset() -> None:
    """Clear the flight-recorder ring alongside _metrics.reset() so the
    obs stage attribution embedded in the record covers ONLY the
    measured run, never the warmup/compile spans. The trace store and
    tail-exemplar reservoirs reset too — a warmup completion's (slow,
    compile-laden) latency must not pin itself as the measured run's
    p99 exemplar — and the device-utilization ledger + SLO windows
    restart so the banked busy-fraction covers the measured flood, not
    the warmup's compile stalls."""
    from sparkdl_tpu import obs
    from sparkdl_tpu.obs import memory as _mem
    from sparkdl_tpu.obs import slo as _slo
    from sparkdl_tpu.obs import timeseries as _ts
    from sparkdl_tpu.obs import trace as _trace
    from sparkdl_tpu.obs import utilization as _util

    obs.get_recorder().clear()
    _trace.reset()
    _util.reset()
    _slo.reset()
    # the fleet ring too: banked fleet samples from a warmup gateway
    # must not ride into the measured flood's record
    _ts.fleet_clear()
    # and the memory ledger + watermark ring: the warmup's staged
    # batches must not pin the measured flood's HBM watermark
    _mem.reset()
    _ts.mem_clear()


def _resident_loop(fn, x, iters):
    """Shared resident-feed measurement: warm/compile once, keep the
    device queue full with ``iters`` async dispatches, block once at the
    end. One implementation so resident numbers stay methodologically
    comparable across modes. Returns wall seconds."""
    fn(x).block_until_ready()  # compile + warm outside the clock
    t0 = time.perf_counter()
    y = None
    for _ in range(max(1, iters)):
        y = fn(x)
    y.block_until_ready()
    return time.perf_counter() - t0



#: BENCH_SIZE -> registry text-model name (models/registry.py); the
#: long-context entry's name carries its geometry, so f"bert-{size}"
#: alone would miss it. Validated up front — a bad size must fail
#: BEFORE the measured run, not while assembling the record.
_BERT_SPECS = {"base": "bert-base", "tiny": "bert-tiny",
               "long": "bert-long-2048"}


def _bert_spec_name(size: str) -> str:
    if size not in _BERT_SPECS:
        raise ValueError(
            f"BENCH_SIZE={size!r}; expected one of {sorted(_BERT_SPECS)}"
        )
    return _BERT_SPECS[size]

def _bench_image_resident(platform, model_name, mode, metric):
    """``BENCH_FEED=resident``: the featurizer/udf device program with its
    input ALREADY on device — stage one flat uint8 batch once, dispatch it
    ``BENCH_ITERS`` times, block once at the end. Measures pure program
    throughput with zero H2D per iteration, so (end-to-end, resident)
    pairs split "the program is slow" from "the feed is slow" without a
    profiler. Runs the identical compiled program as the end-to-end path:
    converter ∘ model ∘ flattener via jitted_flat (image_model.py
    _build_device_fn), channel-major flat layout and all."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.graph.pieces import build_flattener, build_image_converter
    from sparkdl_tpu.models import get_model

    cpu = _is_cpu(platform)
    batch_size = int(os.environ.get("BENCH_BATCH", "16" if cpu else "128"))
    iters = int(os.environ.get("BENCH_ITERS", "5" if cpu else "50"))
    spec = get_model(model_name)
    # Precision rung as a resident A/B arm: SPARKDL_SERVE_PRECISION
    # flips the SAME compiled pipeline to bf16 params/edges or
    # int8-dynamic weights, so the program-level speedup of a rung is
    # measured here with zero feed noise (the serving bench then shows
    # the end-to-end delta). Default f32 keeps historical records
    # comparable (the TPU arm's bf16 module dtype predates the rung
    # knob and stays as-was).
    from sparkdl_tpu.graph.precision import apply_precision, serve_precision

    precision = serve_precision()
    mf = spec.model_function(
        mode=mode, dtype=jnp.float32 if cpu else jnp.bfloat16
    )
    mf = apply_precision(mf, precision)
    converter = build_image_converter(
        channel_order_in="BGR", preprocessing=spec.preprocessing
    )
    pipeline = converter.and_then(mf).and_then(build_flattener())
    shape = (batch_size, spec.height, spec.width, 3)
    # donate=False: the resident loop dispatches the SAME staged array
    # BENCH_ITERS times; a donated input is dead after the first call.
    flat_fn = pipeline.jitted_flat(shape, layout="nchw", donate=False)
    rng = np.random.default_rng(0)
    batch = rng.integers(
        0, 256, size=(batch_size, 3, spec.height, spec.width), dtype=np.uint8
    ).reshape(-1)
    x = jax.device_put(batch)
    # Attribute the one staged input to the memory ledger so the
    # resident record banks the HBM watermark its throughput ran at
    # (the program's whole device footprint for this single-chip loop).
    from sparkdl_tpu.obs import memory as _mem

    staged_bytes = int(getattr(x, "nbytes", 0) or 0)
    _mem.note_staged(flat_fn, staged_bytes)
    try:
        wall = _resident_loop(flat_fn, x, iters)
        mem_extras = _serving_memory()
    finally:
        _mem.release_staged(flat_fn, staged_bytes)
    ips = batch_size * iters / wall
    return (
        metric,
        ips,
        "images/sec/chip",
        {
            "feed": "resident",
            "batch_size": batch_size,
            # n_cfg keys the CPU baseline by configured problem size
            # (batch = the program-defining knob here), matching every
            # other mode's '@n' history keying
            "n_cfg": batch_size,
            "iters": iters,
            "devices": 1,
            # Arm fields (house style: record what RAN): the resident
            # loop is a single-chip program; precision is the rung the
            # measured program was actually built at.
            "mesh_width": 1,
            "precision": precision,
            "flops_per_item": spec.flops_per_item(),
            "memory": mem_extras,
        },
    )


def _bench_featurizer(platform):
    import jax

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.runtime import knobs
    from sparkdl_tpu.transformers import DeepImageFeaturizer
    from sparkdl_tpu.transformers.execution import (
        inference_mode,
        prefetch_per_device,
    )
    from sparkdl_tpu.models import get_model

    if os.environ.get("BENCH_FEED") == "resident":
        return _bench_image_resident(
            platform,
            "ResNet50",
            "features",
            "DeepImageFeaturizer_ResNet50_images_per_sec_per_chip",
        )

    cpu = _is_cpu(platform)
    n_images = int(os.environ.get("BENCH_IMAGES", "128" if cpu else "2048"))
    batch_size = int(os.environ.get("BENCH_BATCH", "16" if cpu else "128"))

    structs = _synthetic_structs(n_images)
    df = DataFrame.fromColumns({"image": structs}, numPartitions=4)
    feat = DeepImageFeaturizer(
        inputCol="image",
        outputCol="features",
        modelName="ResNet50",
        computeDtype="bfloat16",
        batchSize=batch_size,
    )
    warm = DataFrame.fromColumns({"image": structs[:batch_size]})
    feat.transform(warm).count()

    from sparkdl_tpu.utils.metrics import metrics as _metrics

    _metrics.reset()  # isolate the measured run from the warmup
    _obs_reset()
    t0 = time.perf_counter()
    n_done = sum(
        1 for r in feat.transform(df).collect() if r.features is not None
    )
    wall = time.perf_counter() - t0
    ips = n_done / wall / max(1, jax.local_device_count())
    # Per-stage breakdown from the hot loop's own timers: every banked
    # number carries its mini-profile (host assembly vs device wait),
    # so regressions localize without a separate profiler run.
    stage_ms = _stage_breakdown(_metrics)
    return (
        "DeepImageFeaturizer_ResNet50_images_per_sec_per_chip",
        ips,
        "images/sec/chip",
        {
            "n_images": n_done,
            "n_cfg": n_images,
            "batch_size": batch_size,
            "devices": jax.local_device_count(),
            # the RESOLVED mode (the env default lives in execution.py and
            # has changed once already; asking it keeps history keys honest)
            "infer_mode": inference_mode(),
            "prefetch": prefetch_per_device(),
            # resolved value: execution.py defaults to 4 MB chunks on
            # TPU when the env var is unset; chunked puts only engage
            # single-device, so a pool records the truth (no chunking)
            # rather than the inert default
            "h2d_chunk_mb": knobs.get_raw("SPARKDL_H2D_CHUNK_MB")
            or (
                "4"
                if platform == "tpu" and jax.local_device_count() == 1
                else None
            ),
            **_feed_knob_fields(),
            "stage_ms": stage_ms,
            "flops_per_item": get_model("ResNet50").flops_per_item(),
        },
    )


def _bench_keras_image(platform):
    import tempfile

    import jax
    import numpy as np
    from PIL import Image

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.transformers import KerasImageFileTransformer
    from sparkdl_tpu.models import get_model

    cpu = _is_cpu(platform)
    n_images = int(os.environ.get("BENCH_IMAGES", "64" if cpu else "1024"))
    batch_size = int(os.environ.get("BENCH_BATCH", "16" if cpu else "64"))

    import keras

    model = keras.applications.ResNet50(
        weights=None, input_shape=(224, 224, 3)
    )

    rng = np.random.default_rng(0)
    tmp = tempfile.mkdtemp(prefix="bench_imgs_")
    uris = []
    for i in range(n_images):
        arr = rng.integers(0, 256, size=(224, 224, 3), dtype=np.uint8)
        p = os.path.join(tmp, f"img_{i}.jpg")
        Image.fromarray(arr).save(p, quality=90)
        uris.append(p)
    df = DataFrame.fromColumns({"uri": uris}, numPartitions=4)

    xf = KerasImageFileTransformer(
        inputCol="uri",
        outputCol="features",
        model=model,
        batchSize=batch_size,
        preprocessing="caffe",
    )
    warm = DataFrame.fromColumns({"uri": uris[:batch_size]})
    xf.transform(warm).count()

    from sparkdl_tpu.utils.metrics import metrics as _metrics

    _metrics.reset()
    _obs_reset()
    t0 = time.perf_counter()
    n_done = sum(
        1 for r in xf.transform(df).collect() if r.features is not None
    )
    wall = time.perf_counter() - t0
    ips = n_done / wall / max(1, jax.local_device_count())
    return (
        "KerasImageFileTransformer_ResNet50_images_per_sec_per_chip",
        ips,
        "images/sec/chip",
        {"n_images": n_done, "n_cfg": n_images, "batch_size": batch_size,
         "stage_ms": _stage_breakdown(_metrics),
         **_feed_knob_fields(),
         "flops_per_item": get_model("ResNet50").flops_per_item()},
    )


def _bench_udf(platform):
    import jax

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.udf.registry import apply_udf, registerKerasImageUDF
    from sparkdl_tpu.models import get_model

    if os.environ.get("BENCH_FEED") == "resident":
        return _bench_image_resident(
            platform,
            "MobileNetV2",
            "probabilities",
            "registerKerasImageUDF_MobileNetV2_images_per_sec_per_chip",
        )

    cpu = _is_cpu(platform)
    n_images = int(os.environ.get("BENCH_IMAGES", "128" if cpu else "2048"))
    batch_size = int(os.environ.get("BENCH_BATCH", "16" if cpu else "128"))

    registerKerasImageUDF(
        "bench_mnv2", "MobileNetV2", batch_size=batch_size
    )
    structs = _synthetic_structs(n_images)
    df = DataFrame.fromColumns({"image": structs}, numPartitions=4)
    warm = DataFrame.fromColumns({"image": structs[:batch_size]})
    apply_udf("bench_mnv2", warm, "image", "probs").count()

    from sparkdl_tpu.utils.metrics import metrics as _metrics

    _metrics.reset()
    _obs_reset()
    t0 = time.perf_counter()
    out = apply_udf("bench_mnv2", df, "image", "probs")
    n_done = sum(1 for r in out.collect() if r.probs is not None)
    wall = time.perf_counter() - t0
    ips = n_done / wall / max(1, jax.local_device_count())
    return (
        "registerKerasImageUDF_MobileNetV2_images_per_sec_per_chip",
        ips,
        "images/sec/chip",
        {"n_images": n_done, "n_cfg": n_images, "batch_size": batch_size,
         "stage_ms": _stage_breakdown(_metrics),
         **_feed_knob_fields(),
         "flops_per_item": get_model("MobileNetV2").flops_per_item()},
    )


def _bench_udf_sql(platform):
    """BASELINE config[2] through the SQL TEXT path: the same
    registerKerasImageUDF scoring as BENCH_MODE=udf, but routed through
    sql("SELECT udf(image) FROM images") — planner, projection and row
    machinery included. The delta vs the direct udf mode is the SQL
    layer's end-to-end cost on an identical device program; history key
    udf_sql/<platform> should sit within ~10% of udf/<platform>.

    The SPARKDL_SQL_VECTORIZE=1 arm (the default) banks under the
    ``@vectorized`` key: catalog UDF calls dispatch whole partitions
    through run_batched_shared instead of row-at-a-time, a different
    machine perf-wise. SPARKDL_SQL_VECTORIZE=0 keeps the legacy plain
    key, so the old row-path history pool stays comparable."""
    import jax

    from sparkdl_tpu import sql as sqlmod
    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.udf import sql_vectorize_enabled
    from sparkdl_tpu.udf.registry import registerKerasImageUDF
    from sparkdl_tpu.models import get_model

    cpu = _is_cpu(platform)
    n_images = int(os.environ.get("BENCH_IMAGES", "128" if cpu else "2048"))
    batch_size = int(os.environ.get("BENCH_BATCH", "16" if cpu else "128"))

    registerKerasImageUDF(
        "bench_mnv2_sql", "MobileNetV2", batch_size=batch_size
    )
    structs = _synthetic_structs(n_images)
    ctx = sqlmod.SQLContext()
    ctx.registerDataFrameAsTable(
        DataFrame.fromColumns({"image": structs}, numPartitions=4),
        "images",
    )
    ctx.registerDataFrameAsTable(
        DataFrame.fromColumns({"image": structs[:batch_size]}), "warm"
    )
    ctx.sql("SELECT bench_mnv2_sql(image) AS probs FROM warm").count()

    from sparkdl_tpu.utils.metrics import metrics as _metrics

    _metrics.reset()
    _obs_reset()
    t0 = time.perf_counter()
    out = ctx.sql("SELECT bench_mnv2_sql(image) AS probs FROM images")
    n_done = sum(1 for r in out.collect() if r.probs is not None)
    wall = time.perf_counter() - t0
    ips = n_done / wall / max(1, jax.local_device_count())
    counters = _metrics.snapshot().get("counters", {})
    return (
        "sql_select_udf_MobileNetV2_images_per_sec_per_chip",
        ips,
        "images/sec/chip",
        {"n_images": n_done, "n_cfg": n_images, "batch_size": batch_size,
         "vectorized": sql_vectorize_enabled(),
         "udf_batches": int(counters.get("sql.udf.batches", 0)),
         "pushdown_skipped_rows": int(
             counters.get("sql.pushdown.skipped_rows", 0)),
         "stage_ms": _stage_breakdown(_metrics),
         **_feed_knob_fields(),
         "flops_per_item": get_model("MobileNetV2").flops_per_item()},
    )


def _bench_bert(platform):
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.models import get_model
    from sparkdl_tpu.models.bert import bert_model_function
    from sparkdl_tpu.transformers.text import TextEmbedder

    cpu = _is_cpu(platform)
    n_examples = int(os.environ.get("BENCH_EXAMPLES", "64" if cpu else "2048"))
    batch_size = int(os.environ.get("BENCH_BATCH", "8" if cpu else "64"))
    max_len = int(os.environ.get("BENCH_SEQLEN", "128"))

    # BENCH_ATTN=dense forces the einsum path so the Pallas flash kernel
    # (the default on TPU) can be A/B-compared on identical configs.
    attn = os.environ.get("BENCH_ATTN", "flash")
    if attn not in ("flash", "dense"):
        raise ValueError(f"BENCH_ATTN={attn!r}; expected 'flash' or 'dense'")
    attention_fn = None
    if attn == "dense":
        from sparkdl_tpu.models.bert import dense_attention

        attention_fn = dense_attention
    # BENCH_SIZE=tiny: the smallest model that exercises the same code path.
    size = os.environ.get("BENCH_SIZE", "base")
    spec_name = _bert_spec_name(size)
    mf = bert_model_function(
        size=size,
        dtype=jnp.float32 if cpu else jnp.bfloat16,
        max_length=max_len,
        attention_fn=attention_fn,
    )
    if os.environ.get("BENCH_FEED") == "resident":
        # device-resident program throughput: token ids staged once,
        # encoder dispatched BENCH_ITERS times — the program-vs-feed
        # discriminator for BASELINE config[3]
        import numpy as np

        iters = int(os.environ.get("BENCH_ITERS", "3" if cpu else "30"))
        rng = np.random.default_rng(0)
        ids = jax.device_put(
            rng.integers(0, 30000, (batch_size, max_len)).astype(np.int32)
        )
        mask = jax.device_put(
            np.ones((batch_size, max_len), np.float32)
        )
        wall = _resident_loop(mf.jitted(), (ids, mask), iters)
        return (
            f"KerasTransformer_BERT_{size}_examples_per_sec_per_chip",
            batch_size * iters / wall,
            "examples/sec/chip",
            {
                "feed": "resident",
                "batch_size": batch_size,
                "n_cfg": batch_size,
                "iters": iters,
                "seq_len": max_len,
                "size": size,
                "attn": "dense" if (attention_fn is not None or cpu) else "flash",
                "flops_per_item": get_model(spec_name).flops_per_item(max_len),
            },
        )
    texts = [
        f"benchmark sentence number {i} with deep learning pipelines on tpu"
        for i in range(n_examples)
    ]
    df = DataFrame.fromColumns({"text": texts}, numPartitions=4)
    emb = TextEmbedder(
        inputCol="text",
        outputCol="embedding",
        modelFunction=mf,
        maxLength=max_len,
        batchSize=batch_size,
    )
    warm = DataFrame.fromColumns({"text": texts[:batch_size]})
    emb.transform(warm).count()

    t0 = time.perf_counter()
    n_done = sum(
        1 for r in emb.transform(df).collect() if r.embedding is not None
    )
    wall = time.perf_counter() - t0
    eps = n_done / wall / max(1, jax.local_device_count())
    return (
        f"KerasTransformer_BERT_{size}_examples_per_sec_per_chip",
        eps,
        "examples/sec/chip",
        {
            "n_examples": n_done,
            "n_cfg": n_examples,
            "batch_size": batch_size,
            "seq_len": max_len,
            "size": size,
            # Resolved path: the flash wrapper self-selects the dense
            # einsum on non-TPU backends, so a CPU run is "dense"
            # regardless of BENCH_ATTN.
            "attn": "dense" if (attention_fn is not None or cpu) else "flash",
            "flops_per_item": get_model(spec_name).flops_per_item(max_len),
        },
    )


def _bench_text(platform):
    """Sequence-bucketed text engine under a MIXED-length corpus:
    tokens/sec/chip through TextEmbedder's per-bucket feeder
    geometries (the throughput number pad-to-maxLength was hiding —
    the unbucketed arm dispatches ~2x the tokens for the same work).
    The metric counts REAL tokens only, so the bucketed and
    ``SPARKDL_TEXT_BUCKETING=0`` arms are directly comparable: pad
    elimination shows up as throughput, not as a redefined metric.
    ``flops_per_item`` is analytic FLOPs per REAL token over the
    dispatched bucket mix (registry spec flops_fn), so MFU works on
    sequences of every length."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.models import get_model
    from sparkdl_tpu.text.bucketing import bucket_ladder, bucketing_enabled
    from sparkdl_tpu.transformers.text import TextEmbedder
    from sparkdl_tpu.utils.metrics import metrics as _metrics

    cpu = _is_cpu(platform)
    n_examples = int(
        os.environ.get("BENCH_EXAMPLES", "256" if cpu else "2048")
    )
    batch_size = int(os.environ.get("BENCH_BATCH", "8" if cpu else "64"))
    max_len = int(os.environ.get("BENCH_SEQLEN", "128"))
    size = os.environ.get("BENCH_SIZE", "tiny" if cpu else "base")
    spec = get_model(_bert_spec_name(size))
    mf = spec.model_function(
        mode="embed", dtype=jnp.float32 if cpu else jnp.bfloat16
    )

    # mixed-length corpus: lengths uniform over the bucket range — the
    # shape the ladder exists for (uniform is its WORST case; clustered
    # corpora pad less)
    rng = np.random.default_rng(0)
    lengths = rng.integers(16, max_len + 1, size=n_examples)
    texts = [
        " ".join(f"tok{i}w{j}" for j in range(max(1, l - 2)))
        for i, l in enumerate(lengths)
    ]
    df = DataFrame.fromColumns({"text": texts}, numPartitions=4)
    emb = TextEmbedder(
        inputCol="text",
        outputCol="embedding",
        modelFunction=mf,
        maxLength=max_len,
        batchSize=batch_size,
    )
    # warm every bucket geometry the corpus can hit (compile outside
    # the clock): one row per elected bucket edge
    ladder = bucket_ladder(max_len)
    warm_texts = [
        " ".join(f"w{j}" for j in range(max(1, edge - 2)))
        for edge in ladder
    ]
    warm = DataFrame.fromColumns({"text": warm_texts})
    emb.transform(warm).count()

    _metrics.reset()
    _obs_reset()
    t0 = time.perf_counter()
    n_done = sum(
        1 for r in emb.transform(df).collect() if r.embedding is not None
    )
    wall = time.perf_counter() - t0
    counters = _metrics.snapshot()["counters"]
    real_tokens = int(counters.get("text.tokens", 0))
    pad_tokens = int(counters.get("text.pad_tokens", 0))
    if not real_tokens:  # unbucketed A/B arm: no text counters flow
        rows_done = n_done or n_examples
        real_tokens = int(
            sum(min(l, max_len) for l in lengths[:rows_done])
        )
        # every row pays the full maxLength geometry on this arm — the
        # banked pad_ratio must say so, not claim zero padding
        pad_tokens = rows_done * max_len - real_tokens
    tps = real_tokens / wall / max(1, jax.local_device_count())
    # analytic FLOPs per REAL token over the dispatched bucket mix:
    # attention is quadratic in the bucket edge, so the mix matters.
    # The mix comes from the text.bucket_rows.* counters run_bucketed
    # actually emitted — never recomputed from intended corpus lengths,
    # which would silently diverge if the tokenizer's length contract
    # drifted. The unbucketed arm dispatches every row at max_len.
    bucket_rows = {
        int(k.rsplit(".", 1)[-1]): int(v)
        for k, v in counters.items()
        if k.startswith("text.bucket_rows.")
    }
    if not bucket_rows:
        bucket_rows = {max_len: n_done or n_examples}
    total_flops = sum(
        rows * spec.flops_per_item(edge)
        for edge, rows in bucket_rows.items()
    )
    dispatched = real_tokens + pad_tokens
    return (
        f"TextEmbedder_BERT_{size}_tokens_per_sec_per_chip",
        tps,
        "tokens/sec/chip",
        {
            "n_examples": n_done,
            "n_cfg": n_examples,
            "batch_size": batch_size,
            "seq_len": max_len,
            "size": size,
            "bucketed": bucketing_enabled(),
            "buckets": sorted(bucket_rows),
            "tokens": real_tokens,
            "pad_tokens": pad_tokens,
            "pad_ratio": round(pad_tokens / dispatched, 4)
            if dispatched
            else None,
            "stage_ms": _stage_breakdown(_metrics),
            "flops_per_item": total_flops / real_tokens
            if real_tokens
            else None,
        },
    )


def _bench_train(platform):
    import jax
    import numpy as np

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.estimators import DataParallelEstimator
    from sparkdl_tpu.graph.ingest import ModelIngest
    from sparkdl_tpu.models.resnet import ResNet50
    from sparkdl_tpu.utils.flops import model_flops_per_image

    cpu = _is_cpu(platform)
    n_dev = max(1, jax.local_device_count())
    # ResNet50 fine-tune step (BASELINE config[4]); the CPU rehearsal shrinks
    # the image so the step compiles+runs in seconds, same program structure.
    side = int(os.environ.get("BENCH_IMG_SIDE", "64" if cpu else "224"))
    per_dev_batch = int(os.environ.get("BENCH_BATCH", "2" if cpu else "32"))
    batch = per_dev_batch * n_dev
    n_rows = batch * int(os.environ.get("BENCH_STEPS", "4"))

    model = ResNet50(num_classes=10)
    import jax.numpy as jnp

    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, side, side, 3), jnp.float32)
    )
    mf = ModelIngest.from_flax(model, params, input_shape=(side, side, 3))

    rng = np.random.default_rng(0)
    # BENCH_TRAIN_INPUT=image: fine-tune from the image-struct column
    # (BASELINE config[4]'s actual workload) — a uint8 step feed with the
    # float cast fused into the jitted step, vs the generic float32
    # tensor-column feed (4x the bytes per step).
    input_kind = os.environ.get("BENCH_TRAIN_INPUT", "tensor")
    if input_kind not in ("tensor", "image"):
        raise ValueError(
            f"BENCH_TRAIN_INPUT={input_kind!r}; expected 'tensor' or 'image'"
        )
    # feats draw FIRST: the tensor branch must consume rng(0) in the same
    # order as every historically banked run of this config.
    if input_kind == "image":
        feats = _synthetic_structs(n_rows, h=side, w=side)
    else:
        feats = [
            rng.normal(size=(side, side, 3)).astype(np.float32)
            for _ in range(n_rows)
        ]
    labels = rng.integers(0, 10, size=(n_rows,)).astype(np.int32)
    df = DataFrame.fromColumns(
        {"features": feats, "label": list(labels)}, numPartitions=2
    )

    # BENCH_STREAMING=1: the executor-local-feed path (scanParquet input
    # + shuffle-buffer + producer-thread prefetch) instead of in-memory —
    # the A/B for whether host feeding keeps up with the chip.
    streaming = os.environ.get("BENCH_STREAMING") == "1"
    tmp_dir = None

    est = DataParallelEstimator(
        model=mf,
        inputCol="features",
        labelCol="label",
        outputCol="logits",
        batchSize=batch,
        epochs=2,
        stepSize=0.01,
        streaming=streaming,
        **(
            {"targetHeight": side, "targetWidth": side}
            if input_kind == "image"
            else {}
        ),
    )
    from sparkdl_tpu.utils.metrics import metrics as _metrics

    try:
        if streaming:
            import tempfile

            tmp_dir = tempfile.mkdtemp(prefix="bench_train_")
            pq_path = os.path.join(tmp_dir, "train.parquet")
            df.writeParquet(pq_path)
            df = DataFrame.scanParquet(pq_path, numPartitions=2)
        _metrics.reset()
        _obs_reset()
        fitted = est.fit(df)
    finally:
        if tmp_dir is not None:
            import shutil

            shutil.rmtree(tmp_dir, ignore_errors=True)
    # first epoch pays compile; report the steady-state epoch's mean step
    step_time = fitted.history[-1]["mean_step_time_s"]
    return (
        "HorovodEstimator_ResNet50_mean_step_time_s",
        step_time,
        "seconds/step",
        {
            "batch_size": batch,
            "n_cfg": batch,
            "n_devices": n_dev,
            "image_side": side,
            "epochs": len(fitted.history),
            "streaming": streaming,
            "train_input": input_kind,
            # streaming only: mean time the step loop sat waiting for the
            # producer — data-starved vs device-bound at a glance
            "data_wait_ms": round(
                _metrics.snapshot()["timers"]
                .get("train.data_wait", {})
                .get("mean_s", 0.0) * 1e3, 1,
            )
            if streaming
            else None,
            # step-time definition (changed once: blocked device-step
            # mean -> pipelined epoch_wall/steps); lets readers of
            # BENCH_HISTORY compare like with like
            "timing": fitted.history[-1].get("timing", "blocked_step"),
            # fwd+bwd ≈ 3x forward per image, scaled to the configured
            # spatial size (the CPU rehearsal shrinks to 64x64)
            "flops_per_item": 3.0
            * model_flops_per_image("ResNet50", height=side, width=side),
        },
    )


def _bench_serving_affinity(platform):
    """Gateway-path A/B arm (``BENCH_SERVE_AFFINITY=1``): req/s through
    a REAL worker gang — gateway + ``BENCH_SERVE_WORKERS`` subprocesses
    — with model-affinity routing ON and a catalog of
    ``BENCH_SERVE_MODELS`` chaos models flooding ``POST /v1/predict``.
    A different machine than the in-process router path, so it banks
    under its own ``serving/cpu@affinity`` key (``_config_for_record``
    reads the ``affinity`` field). The extras carry the arm's value
    claim: per-worker resident sets summing to ~the catalog (sharded,
    not replicated N x) and the fleet's total cold loads
    (``serve.model_loads`` summed across workers — affinity pays one
    load per model; round-robin pays one per model PER RANK)."""
    import re as _re
    import tempfile
    import threading
    import urllib.request

    import numpy as np

    from sparkdl_tpu.serving.gateway import ServingGateway
    from sparkdl_tpu.utils.metrics import metrics as _metrics
    from tools._chaos_models import ROW

    if not _is_cpu(platform):
        raise RuntimeError(
            "BENCH_SERVE_AFFINITY=1 starts gateway workers, and this "
            f"process already holds the {platform} chips they would need: "
            "a chip belongs to one process at a time. The arm runs as a "
            "CPU rehearsal only (BENCH_PLATFORM=cpu)"
        )
    num_workers = int(os.environ.get("BENCH_SERVE_WORKERS", "2"))
    n_models = int(os.environ.get("BENCH_SERVE_MODELS", "6"))
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "240"))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "32"))
    catalog = [f"bench-aff-{i}" for i in range(n_models)]

    def post(port, path, payload, timeout=300):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status

    def get_text(port, path, timeout=10):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as resp:
            return resp.read().decode()

    root = tempfile.mkdtemp(prefix="bench_affinity_")
    os.environ["SPARKDL_GATEWAY_AFFINITY"] = "1"
    gw = ServingGateway(
        num_workers=num_workers,
        port=0,
        gang_dir=os.path.join(root, "gang"),
        loader_spec="tools._chaos_models:loader",
        max_batch=max_batch,
        extra_env={
            "JAX_PLATFORMS": "cpu",
            "SPARKDL_INFERENCE_MODE": "roundrobin",
            "SPARKDL_INFERENCE_DEVICES": "1",
        },
        stale_after=60.0,
    ).start()
    rng = np.random.default_rng(0)
    lat_lock = threading.Lock()
    latencies = []
    errors = [0]

    def one(i):
        x = rng.normal(size=(1, ROW)).astype(np.float32)
        t = time.perf_counter()
        try:
            status = post(
                gw.port,
                "/v1/predict",
                {
                    "model": catalog[i % n_models],
                    "inputs": x.tolist(),
                    "class": "interactive",
                },
            )
        except Exception:
            status = None
        dt = time.perf_counter() - t
        with lat_lock:
            if status == 200:
                latencies.append(dt)
            else:
                errors[0] += 1

    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            ready = [
                w
                for w in gw.stats()["workers"]
                if w["status"] == "ready" and w.get("port")
            ]
            if len(ready) >= num_workers:
                break
            time.sleep(0.25)
        else:
            raise RuntimeError(
                f"gang never became ready: {gw.stats()['workers']}"
            )
        # absorb every cold load outside the clock — the measured flood
        # is steady-state routing; the load COUNT is still the arm's
        # claim (totals read from worker /metrics below cover warmup)
        for i in range(n_models):
            one(i)
        with lat_lock:
            latencies.clear()
            errors[0] = 0
        _metrics.reset()
        _obs_reset()
        t0 = time.perf_counter()
        threads = [
            threading.Thread(
                target=lambda k=k: [
                    one(i)
                    for i in range(
                        k * n_requests // 4, (k + 1) * n_requests // 4
                    )
                ],
                name=f"sparkdl-bench-affinity-{k}",
                daemon=False,
            )
            for k in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        resident = {}
        cold_loads = 0
        for w in gw.stats()["workers"]:
            if w["status"] != "ready" or not w.get("port"):
                continue
            text = get_text(w["port"], "/metrics")
            m = _re.search(
                r"^serve_model_loads_total(?:\{[^}]*\})? "
                r"([0-9.eE+-]+)$",
                text,
                _re.M,
            )
            cold_loads += int(float(m.group(1))) if m else 0
            stats = json.loads(get_text(w["port"], "/v1/models"))
            resident[w["rank"]] = sorted(
                m2.get("name")
                for m2 in stats.get("models") or []
                if m2.get("name")
            )
    finally:
        gw.stop()
        os.environ.pop("SPARKDL_GATEWAY_AFFINITY", None)
    done = len(latencies)
    rps = done / wall if wall > 0 else 0.0
    lat_sorted = sorted(latencies)
    resident_total = sum(len(v) for v in resident.values())
    return (
        "serving_requests_per_sec",
        rps,
        "req/s",
        {
            "affinity": True,
            "gateway_workers": num_workers,
            "n_requests": done,
            "rejected": errors[0],
            "max_batch": max_batch,
            "catalog_models": n_models,
            "per_worker_resident": {
                str(r): v for r, v in sorted(resident.items())
            },
            "resident_total": resident_total,
            # 1.0 = perfectly sharded (each model on exactly one rank);
            # the round-robin arm replicates to ~num_workers
            "replication_factor": round(
                resident_total / max(1, n_models), 2
            ),
            "cold_loads": cold_loads,
            "latency": {
                "interactive": {
                    "n": done,
                    "p50_ms": round(
                        lat_sorted[done // 2] * 1e3, 2
                    ),
                    "p95_ms": round(
                        lat_sorted[int(done * 0.95)] * 1e3, 2
                    ),
                }
            }
            if done
            else {},
            "mesh_width": 1,
            "precision": "f32",
            "n_devices": 1,
        },
    )


def _bench_serving(platform):
    """Online serving layer under mixed-class synthetic load: req/s
    through the full admission -> router -> feeder-stream -> completion
    path, with per-class p50/p95 in the extras so bench_gate protects
    tail latency alongside throughput. The model is a small jitted MLP
    on purpose — the measured object is the serving machinery's
    overhead, not a CNN's FLOPs (the featurizer/udf modes own those).
    ``BENCH_SERVE_AFFINITY=1`` selects the gateway-path affinity arm
    instead (its own history key: ``serving/cpu@affinity``)."""
    if os.environ.get("BENCH_SERVE_AFFINITY", "") not in ("", "0"):
        return _bench_serving_affinity(platform)
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.serving import Router, ServingClient
    from sparkdl_tpu.utils.metrics import metrics as _metrics

    cpu = _is_cpu(platform)
    n_requests = int(
        os.environ.get("BENCH_SERVE_REQUESTS", "300" if cpu else "2000")
    )
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "32"))
    # One set of MLP dims shared by the loader AND the analytic FLOPs
    # below — restating them in the mfu math would let a model edit
    # silently desynchronize every banked utilization.
    row_dim, hidden_dim, out_dim = 256, 512, 128

    def loader(name, mode):
        rng = np.random.default_rng(7)
        w1 = jnp.asarray(
            rng.normal(size=(row_dim, hidden_dim)).astype(np.float32) / 16
        )
        w2 = jnp.asarray(
            rng.normal(size=(hidden_dim, out_dim)).astype(np.float32) / 16
        )
        return ModelFunction(
            lambda p, x: jnp.tanh(jnp.tanh(x @ p[0]) @ p[1]),
            (w1, w2),
            input_shape=(row_dim,),
            name=name,
        )

    # class mix: mostly background bulk, a batch middle, an interactive
    # tail — the shape the SLA separation exists for
    rng = np.random.default_rng(0)
    plan = []
    for i in range(n_requests):
        if i % 10 == 0:
            plan.append(("interactive", 1))
        elif i % 10 in (1, 2):
            plan.append(("batch", 4))
        else:
            plan.append(("background", 8))
    inputs = [
        rng.normal(size=(rows, row_dim)).astype(np.float32)
        for _, rows in plan
    ]

    router = Router(loader=loader, max_batch=max_batch)
    client = ServingClient(router)
    try:
        # warm every rung the plan can hit (compile outside the clock)
        for rows in (1, 2, 4, 8, 16, max_batch):
            client.predict(
                "bench", np.zeros((rows, row_dim), np.float32), timeout=300
            )
        _metrics.reset()
        _obs_reset()
        t0 = time.perf_counter()
        reqs = []
        accepted_rows = []
        submit_errors = [0]

        def submit_range(lo, hi):
            for i in range(lo, hi):
                cls, rows = plan[i]
                try:
                    req = client.submit("bench", inputs[i], priority=cls)
                except Exception:
                    submit_errors[0] += 1
                else:
                    reqs.append(req)
                    accepted_rows.append(rows)

        threads = [
            threading.Thread(
                target=submit_range,
                args=(k * n_requests // 4, (k + 1) * n_requests // 4),
                name=f"sparkdl-bench-submit-{k}",
                daemon=False,  # joined below; must not die mid-submit
            )
            for k in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for r in list(reqs):
            r.result(timeout=600)
        wall = time.perf_counter() - t0
        resident_rows = router.residency.models()  # before close unloads
    finally:
        router.close()
    done = len(reqs)
    rps = done / wall if wall > 0 else 0.0
    latency = {}
    for cls in ("interactive", "batch", "background"):
        stat = _metrics.timing(f"serve.latency.{cls}")
        if stat is None or not stat.count:
            continue
        latency[cls] = {
            "n": stat.count,
            "p50_ms": round(stat.percentile(50) * 1e3, 2),
            "p95_ms": round(stat.percentile(95) * 1e3, 2),
        }
    rows_stat = _metrics.timing("serve.batch_rows")
    # Admission-side waterfall attribution: queue_wait (admission ->
    # popped) and group_wait (popped -> dispatch start) alongside the
    # stage attribution the record already carries — when a serving
    # number regresses, bench_gate's reader can name "admission
    # backlog" (these grew) vs "device" (the dispatch stages grew).
    waterfall = {}
    for seg, metric in (
        ("queue_wait_ms", "serve.queue_wait"),
        ("group_wait_ms", "serve.group_wait"),
    ):
        stat = _metrics.timing(metric)
        if stat is not None and stat.count:
            waterfall[seg] = {
                "mean": round(stat.mean_s * 1e3, 3),
                "p95": round(stat.percentile(95) * 1e3, 3),
            }
    # Mesh/precision arm fields, recorded by what actually SERVED (the
    # resident entries at measurement end), never by a knob alone: a
    # per-class precision override splits traffic across rungs, and a
    # record claiming ONE rung would bank mixed-arm throughput into
    # that rung's baseline pool. One resident rung names the arm;
    # several name it "mixed" (its own history key). Throughput
    # normalizes PER CHIP (rows/sec divided by the mesh width) so an
    # 8-chip record and a 1-chip record argue about the same number —
    # the per-chip scaling factor IS the mesh's value claim.
    from sparkdl_tpu.graph.precision import serve_precision
    from sparkdl_tpu.transformers.execution import serve_mesh_width

    mesh_width = max(
        [m.get("mesh_width", 1) for m in resident_rows]
        or [serve_mesh_width() or 1]
    )
    served_rungs = sorted(
        {m.get("precision", "f32") for m in resident_rows}
    )
    if not served_rungs:
        served_rungs = [serve_precision()]
    precision = served_rungs[0] if len(served_rungs) == 1 else "mixed"
    rows_total = int(sum(accepted_rows))
    rows_per_sec = rows_total / wall if wall > 0 else 0.0
    # Analytic forward FLOPs for one ROW of the bench MLP (2 matmuls +
    # elementwise tanh, FLOPs = 2 x MACs) — the serving mode's
    # flops_per_item so its records carry a real MFU on known devices
    # instead of the "mfu": null this satellite existed to kill.
    mlp_flops_per_row = 2.0 * (
        row_dim * hidden_dim + hidden_dim * out_dim
    )
    return (
        "serving_requests_per_sec",
        rps,
        "req/s",
        {
            "n_requests": done,
            "rows_total": rows_total,
            "rejected": submit_errors[0],
            "max_batch": max_batch,
            "latency": latency,
            "batch_rows": {
                "min": int(rows_stat.min_s),
                "mean": round(rows_stat.mean_s, 1),
                "max": int(rows_stat.max_s),
            }
            if rows_stat and rows_stat.count
            else None,
            "serve_dispatches": int(_metrics.counter("serve.dispatches")),
            "serve_pad_rows": int(_metrics.counter("serve.pad_rows")),
            **waterfall,
            "serve_chip_rows": int(
                _metrics.counter("serve.mesh.chip_rows")
            ),
            "n_devices": max(1, jax.local_device_count()),
            "mesh_width": int(mesh_width),
            "precision": precision,
            "rows_per_sec": round(rows_per_sec, 1),
            "items_per_sec_per_chip": round(
                rows_per_sec / max(1, mesh_width), 2
            ),
            "flops_per_item": mlp_flops_per_row,
            # goodput ledger roll-up over the measured flood (the
            # ledger was reset at _obs_reset): chips-busy fraction +
            # per-device ms, so a banked serving record names "the
            # chips idled 60% of this flood" without a profiler rerun
            "utilization": _serving_utilization(),
            # memory-ledger roll-up (satellite of the HBM ledger): the
            # flood's HBM watermark peak + per-model measured bytes, so
            # a banked record carries the memory claim its throughput
            # was bought at — a regression that traded bytes for req/s
            # is visible without rerunning
            "memory": _serving_memory(resident_rows),
        },
    )


def _serving_memory(resident_rows=None):
    """Memory-ledger extras for banked records: watermark peak over the
    measured flood (the gauge envelope's max, not the last sample — the
    peak may have passed before measurement end), plus each resident
    model's estimate-vs-measured bytes from the residency rows."""
    from sparkdl_tpu.obs import memory as _mem
    from sparkdl_tpu.utils.metrics import metrics as _metrics

    status = _mem.memory_status()
    if status is None:
        return None
    out = {
        "tracked_bytes": status.get("tracked_bytes"),
        "watermark_bytes": status.get("watermark_bytes"),
        "unattributed_bytes": status.get("unattributed_bytes"),
        "ground_truth_source": status.get("ground_truth_source"),
        "leaked_bytes": status.get("leaked_bytes"),
        "oom_events": status.get("oom_events"),
    }
    peak = None
    for d in status.get("devices") or {}:
        stat = _metrics.gauge_stats(f"mem.watermark_bytes.{d}")
        if stat is not None:
            peak = max(peak or 0, int(stat["max"]))
    if peak is not None:
        out["watermark_peak_bytes"] = peak
    if resident_rows:
        out["models"] = {
            m["name"]: {
                "param_bytes": m.get("param_bytes"),
                "measured_bytes": m.get("measured_bytes"),
                "estimate_delta_bytes": m.get("estimate_delta_bytes"),
            }
            for m in resident_rows
        }
    return out


def _serving_utilization():
    from sparkdl_tpu.obs import utilization as _util

    status = _util.utilization_status()
    if status is None:
        return None
    return {
        "busy_frac": status.get("busy_frac"),
        "devices": {
            d: {
                "busy_ms": st["busy_ms"],
                "idle_ms": st["idle_ms"],
                "h2d_ms": st["h2d_ms"],
                "d2h_ms": st["d2h_ms"],
            }
            for d, st in (status.get("devices") or {}).items()
        },
        **({"mfu": status["mfu"]} if "mfu" in status else {}),
    }


def _bench_generate(platform):
    """Autoregressive generation under a concurrent flood: tokens/sec
    through the full admission -> KV reservation -> GenStream
    continuous-batching decode path on bert-tiny. The topline is NEW
    tokens per second per chip (generation dispatches width-1); the
    extras attribute prefill and decode separately — the
    ``gen.prefill_ms`` / ``gen.decode_step_ms`` reservoirs record
    MILLISECOND values, read as-is — so a regression names "prompt
    processing got slower" vs "the per-step decode did". The measured
    object is the token-level scheduler + KV-cache decode machinery,
    not model FLOPs (bert-tiny on purpose)."""
    import numpy as np

    from sparkdl_tpu.serving import Router
    from sparkdl_tpu.serving.generation import max_seqs
    from sparkdl_tpu.utils.metrics import metrics as _metrics

    cpu = _is_cpu(platform)
    n_seqs = int(os.environ.get("BENCH_GEN_SEQS", "12" if cpu else "64"))
    max_new = int(os.environ.get("BENCH_GEN_NEW_TOKENS", "16"))

    def submit(router, i):
        # lengths 4..7 share one prefill bucket (8): the warmup request
        # compiles every program the measured flood hits
        prompt = np.arange(1, 5 + (i % 4), dtype=np.int32).reshape(1, -1)
        return router.submit(
            "bert-tiny",
            prompt,
            mode="generate",
            gen_params={"max_new_tokens": max_new},
        )

    router = Router()
    try:
        submit(router, 0).result(timeout=600)  # compile outside the clock
        _metrics.reset()
        _obs_reset()
        t0 = time.perf_counter()
        reqs = [submit(router, i) for i in range(n_seqs)]
        tokens = sum(
            int(np.asarray(r.result(timeout=600)).size) for r in reqs
        )
        wall = time.perf_counter() - t0
    finally:
        router.close()
    tps = tokens / wall if wall > 0 else 0.0
    extras = {
        "n_seqs": n_seqs,
        "max_new_tokens": max_new,
        "tokens_out": tokens,
        "slots": max_seqs(),
        "joins": int(_metrics.counter("gen.joins")),
        "slot_reuse": int(_metrics.counter("gen.slot_reuse")),
        "tokens_per_sec_per_chip": round(tps, 2),  # width-1 dispatch
        "precision": "f32",  # generation pins the f32 rung
    }
    prefill = _metrics.timing("gen.prefill_ms")
    if prefill is not None and prefill.count:
        extras["prefill"] = {
            "n": prefill.count,
            "mean_ms": round(prefill.mean_s, 3),
            "p95_ms": round(prefill.percentile(95), 3),
            "total_ms": round(prefill.mean_s * prefill.count, 1),
        }
    decode = _metrics.timing("gen.decode_step_ms")
    if decode is not None and decode.count:
        decode_total_ms = decode.mean_s * decode.count
        extras["decode"] = {
            "steps": decode.count,
            "mean_step_ms": round(decode.mean_s, 3),
            "p95_step_ms": round(decode.percentile(95), 3),
            "total_ms": round(decode_total_ms, 1),
            # decode-only rate: the first token of each sequence came
            # from its prefill, the rest from decode steps
            "tokens_per_sec": round(
                (tokens - n_seqs) / (decode_total_ms / 1e3), 2
            )
            if decode_total_ms > 0
            else None,
        }
    kv = _metrics.gauge_stats("gen.kv_bytes")
    if kv is not None:
        extras["kv_peak_bytes"] = int(kv["max"])
    return "generation_tokens_per_sec", tps, "tok/s", extras


_BENCH_FNS = {
    "featurizer": _bench_featurizer,
    "keras_image": _bench_keras_image,
    "udf": _bench_udf,
    "udf_sql": _bench_udf_sql,
    "bert": _bench_bert,
    "text": _bench_text,
    "train": _bench_train,
    "serving": _bench_serving,
    "generate": _bench_generate,
}


def _measure(mode: str, platform: str) -> dict:
    """Run ``mode`` on ``platform`` and assemble its record."""
    import jax

    # BENCH_PROFILE=<dir>: capture a jax.profiler trace of the measured
    # run (TensorBoard/Perfetto; HBM + MXU timelines on TPU).
    profile_dir = os.environ.get("BENCH_PROFILE")
    from sparkdl_tpu.utils.profiler import profile_trace

    # CPU rehearsal numbers are noisy; report the median of BENCH_REPS
    # full measurements so vs_baseline means something. TPU runs stay
    # single-shot.
    # Profiled runs stay single-shot: they never record baselines, and a
    # trace of three back-to-back runs is useless for per-op analysis.
    default_reps = "3" if platform == "cpu" and not profile_dir else "1"
    reps = int(os.environ.get("BENCH_REPS", default_reps))
    with profile_trace(profile_dir or ".", enabled=bool(profile_dir)):
        runs = [_BENCH_FNS[mode](platform) for _ in range(reps)]
    metric, _, unit, extras = runs[0]
    # Flight-recorder attribution rides every record: per-stage
    # p50/p95/p99 (+ host/device overlap) from the measured run's spans,
    # so an A/B regression localizes to a stage without a rerun.
    # Each bench fn clears the ring at its own _obs_reset(), so with
    # reps>1 the attribution covers the LAST rep only (the reported
    # value is the median rep) — the "_rep" marker keeps readers honest.
    # BENCH_OBS_SNAPSHOT=<path> additionally writes the full snapshot
    # (span-level, Chrome-trace convertible via python -m sparkdl_tpu.obs).
    from sparkdl_tpu import obs as _obs

    obs_snap = _obs.snapshot()
    obs_summary = _obs.stage_summary(obs_snap)
    if reps > 1:
        obs_summary["_rep"] = f"last_of_{reps}"
    extras = {**extras, "obs": obs_summary}
    # Shared-feeder attribution: pad_rows/coalesced_batches for the
    # measured run (the ring+registry were reset with the warmup), so
    # BENCH_HISTORY can attribute throughput deltas to padding-waste
    # elimination vs program speed. Recorded by ENGAGEMENT: the counters
    # only exist when the feeder actually coalesced batches.
    from sparkdl_tpu.graph.function import input_donation_engaged
    from sparkdl_tpu.obs.report import feeder_summary as _feeder_summary
    from sparkdl_tpu.runtime.readback import async_readback_enabled
    from sparkdl_tpu.runtime.transfer import device_stage_enabled
    from sparkdl_tpu.transformers.execution import device_preproc_enabled

    feeder = _feeder_summary(obs_snap)
    # Compile-cache attribution comes from the module's reset-immune
    # tally, NOT the snapshot: the builds (and their ledger hits) happen
    # during warmup, before each bench fn's metrics reset.
    from sparkdl_tpu.runtime import compile_cache as _compile_cache

    cstats = _compile_cache.stats()
    compiled = cstats if any(cstats.values()) else None
    # Staging overlap attribution: stage_hits proves copies were in
    # flight BEFORE dispatch needed them.
    _counters = (obs_snap.get("metrics") or {}).get("counters") or {}
    staging = {
        k.split(".")[-1]: int(_counters.get(k, 0))
        for k in ("transfer.stage_hits", "transfer.stage_misses")
    }
    if not any(staging.values()):
        staging = {}  # both keys or neither, matching feeder_summary
    extras = {
        **extras,
        # The feed-path A/B arms ride every record (the feeder block —
        # when present — additionally carries the async-readback and
        # device-staging hit/miss counters), so tools/bench_gate.py can
        # tell a drain/dispatch-stage regression from an arm flip.
        "async_readback": async_readback_enabled(),
        "device_stage": device_stage_enabled(),
        "device_preproc": device_preproc_enabled(),
        # donation is recorded by ENGAGEMENT (gate AND a backend that
        # implements it): on CPU the knob is inert and both arms run the
        # identical program — a record labeled by the env var alone
        # would bank a lie (house style, see _feed_knob_fields).
        "donation": input_donation_engaged(),
        **({"feeder": feeder} if feeder else {}),
        **({"transfer": staging} if staging else {}),
        **({"compile": compiled} if compiled else {}),
    }
    snap_path = os.environ.get("BENCH_OBS_SNAPSHOT")
    if snap_path:
        _obs.write_snapshot(snap_path, obs_snap)
        extras["obs_snapshot"] = snap_path
    values = sorted(r[1] for r in runs)
    value = values[len(values) // 2]
    if reps > 1:
        extras = {**extras, "reps": reps,
                  "spread": round(float(values[-1] - values[0]), 4)}
    if profile_dir:
        extras = {**extras, "profile_dir": profile_dir}
    # MFU: how much of one chip's bf16 peak the measured throughput
    # implies — the number that says whether a plateau is the program or
    # the feed. null off-TPU (no meaningful peak) or when value==0.
    dev = jax.devices()[0]
    fpi = extras.get("flops_per_item")
    if fpi:
        from sparkdl_tpu.utils.flops import mfu as _mfu

        if "items_per_sec_per_chip" in extras:
            # Modes whose topline is NOT items/sec/chip (serving req/s)
            # provide the normalized rate explicitly — aggregate
            # rows/sec over the mesh divided by its width.
            per_chip = float(extras["items_per_sec_per_chip"])
        elif mode in _TIME_METRICS:  # seconds/step -> items/sec/chip
            per_chip = (
                extras["batch_size"]
                / float(value)
                / max(1, extras.get("n_devices", 1))
                if value
                else 0.0
            )
        else:
            per_chip = float(value)
        m = _mfu(fpi, per_chip, dev.device_kind)
        extras = {**extras, "mfu": round(m, 5) if m is not None else None}
    return {
        "metric": metric,
        "value": round(float(value), 4),
        "unit": unit,
        "mode": mode,
        "platform": platform,
        "device_kind": dev.device_kind,
        "count": len(jax.devices()),
        **extras,
    }


# ---------------------------------------------------------------------------
# History keys + the entry point
# ---------------------------------------------------------------------------


def _config_for_record(name: str, result: dict) -> str:
    """Baseline key suffix for one bench record: the platform name plus
    every variant marker that makes runs incomparable — model size,
    dense-attention arm, resident feed, CPU problem size / device mesh,
    streaming input. One definition shared by ``main`` and
    ``tools/bench_gate.py`` so the gate can never look up a record under
    a different key than the one it was banked with."""
    config = name
    # Variant knobs (the BERT dense/flash A/B) get their own baseline
    # key so variants never contaminate each other. On CPU there is no
    # variant — flash self-selects the dense einsum, so every CPU run IS
    # the dense path and shares the plain key.
    if result.get("attn") == "dense" and result.get("platform") != "cpu":
        config += "_dense"
    # Non-default model sizes get their own baseline key: a tiny-model
    # number must never become the base-model baseline.
    if result.get("size") not in (None, "base"):
        config += f"@{result['size']}"
    if result.get("train_input") == "image":
        config += "@image"
    # The text engine's pad-to-maxLength A/B arm dispatches ~2x the
    # tokens per real token — a different workload, never the bucketed
    # baseline.
    if result.get("bucketed") is False:
        config += "@unbucketed"
    # Device-resident runs measure a different thing (program
    # throughput, zero per-batch H2D) — never the end-to-end baseline.
    if result.get("feed") == "resident":
        config += "@resident"
    # Mesh-width and precision arms are different machines perf-wise: a
    # width-8 record must never baseline a single-chip run, and a bf16
    # number must never baseline the f32 arm (each rung gets its own
    # history pool; bench_gate additionally notes cross-arm pools).
    if (result.get("mesh_width") or 1) > 1:
        config += f"@mesh{result['mesh_width']}"
    if result.get("precision") not in (None, "f32"):
        config += f"@{result['precision']}"
    if name == "cpu":
        # Key CPU baselines by the CONFIGURED problem size: a number
        # measured at n=128 must never be the baseline for a run at
        # n=512, and a partial failure (n_done < configured) must not
        # fragment the key and hide the very slowdown it causes.
        size = result.get("n_cfg")
        if size:
            config += f"@n{size}"
        # multi-device CPU-mesh A/B runs get their own keys; with one
        # device every mode runs the identical program, so the mode
        # suffix only applies on a real pool
        if result.get("devices", 1) > 1:
            config += f"@dev{result['devices']}"
            if result.get("infer_mode", "roundrobin") != "roundrobin":
                config += f"@{result['infer_mode']}"
    # The SQL planner's vectorized arm (SPARKDL_SQL_VECTORIZE=1, the
    # default) dispatches catalog UDFs as whole-partition batches — an
    # order-of-magnitude different machine than the legacy row path, so
    # it banks under its own key while knob-off runs keep the old pool.
    if result.get("vectorized"):
        config += "@vectorized"
    # The gateway affinity arm serves through real worker subprocesses
    # with consistent-hash routing — a different machine than the
    # in-process router path, never the plain serving baseline.
    if result.get("affinity"):
        config += "@affinity"
    if result.get("streaming"):
        config += "@streaming"
    return config


#: Full records banked per history key — enough for the regression gate's
#: per-stage comparison without re-running anything.
_HISTORY_RECORDS_KEPT = 8


def _history_vs_baseline(
    mode: str,
    config: str,
    value: float,
    record: bool = True,
    full_record: dict = None,
) -> float:
    """Read (and with ``record``, update) BENCH_HISTORY.json.

    Baselines are keyed by mode + config (``_config_for_record``).
    ``record=False`` (profiled runs) compares against an existing
    baseline without writing anything — profiler overhead must never
    become a baseline.
    """
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_HISTORY.json")
    hist = {}
    try:
        with open(path) as f:
            hist = json.load(f)
    except (OSError, json.JSONDecodeError):
        hist = {}
    baselines = hist.setdefault("baselines", {})
    hist.setdefault("schema", 3)
    key = f"{mode}/{config}"
    baseline = baselines.get(key)
    if baseline:
        vs = baseline / value if mode in _TIME_METRICS else value / baseline
    elif record:
        baselines[key] = value
        vs = 1.0
    else:
        # profiled run with nothing to compare against: 0 (the error-path
        # sentinel), NOT a fictitious 1.0 "parity"
        vs = 0.0
    if not record:
        return round(vs, 4)
    hist.setdefault("runs", []).append(
        {"mode": mode, "config": config, "value": value,
         "time": time.strftime("%Y-%m-%dT%H:%M:%S")}
    )
    # Bank the COMPLETE record (obs stage attribution included) per key,
    # bounded to the last few: tools/bench_gate.py compares a fresh
    # record's per-stage totals against the median of these, so the gate
    # always has a stage-attributed baseline without hand-curation.
    if full_record is not None:
        recs = hist.setdefault("records", {}).setdefault(f"{mode}/{config}", [])
        recs.append(dict(full_record))
        del recs[:-_HISTORY_RECORDS_KEPT]
    try:
        with open(path, "w") as f:
            json.dump(hist, f, indent=1)
    except OSError:
        pass
    return round(vs, 4)


def main() -> None:
    mode = _mode()
    rehearsal = os.environ.get("BENCH_PLATFORM") == "cpu"
    if rehearsal:
        # before jax is imported, so jax itself reads them
        os.environ["JAX_PLATFORMS"] = "cpu"
        # BENCH_DEVICES=<k>: k virtual CPU devices — the multi-device
        # round-robin vs shard_map inference A/B runs on this mesh.
        if os.environ.get("BENCH_DEVICES"):
            os.environ["JAX_NUM_CPU_DEVICES"] = os.environ["BENCH_DEVICES"]

    import sparkdl_tpu  # noqa: F401  (places the compile cache)
    import jax

    platform = jax.default_backend()
    if platform != "tpu" and not rehearsal:
        sys.exit(
            f"bench.py: no TPU (jax's default backend is {platform!r}); "
            "nothing was measured. BENCH_PLATFORM=cpu asks for the CPU "
            "rehearsal explicitly."
        )
    result = _measure(mode, platform)
    result["vs_baseline"] = _history_vs_baseline(
        mode,
        _config_for_record(platform, result),
        result["value"],
        # Diagnostic runs (profiler traces, BENCH_NO_RECORD=1) compare
        # against history but never overwrite it.
        record=not os.environ.get("BENCH_PROFILE")
        and os.environ.get("BENCH_NO_RECORD") != "1",
        full_record=result,
    )
    print(json.dumps(result))


if __name__ == "__main__":
    main()
