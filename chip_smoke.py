"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives both hot paths and the trainer once, through the entry points a
user would call, at the full width of ResNet50 / BERT-base, on whatever
TPU chips jax finds (one process holds them all), and checks what comes
out against the repo's own references:

  device   the chips, the versions, the compile cache in force; the native
           image bridge rebuilt from native/imagebridge.cc
  offline  DeepImageFeaturizer(ResNet50, bf16, batch 128) over 1,024
           synthetic 224x224x3 image structs in 4 partitions -> shared
           feeder -> one jitted program -> readback
  online   Router + ServingServer (what `python -m sparkdl_tpu.serving
           serve` builds) over HTTP: ResNet50 predicts, bert-base embeds
           at seq 128 and 512, bert-long-2048 at 2048, one streamed
           generate on bert-tiny; /v1/models and /v1/memory
  trainer  DataParallelEstimator, ResNet50 at 224x224, 32 rows per
           device, two epochs of 4 steps
  kernel   the compiled Pallas flash kernel, blocked and packed (heads
           under the lane width, side by side), against dense attention, and
           the hybrid family's: selective scan, causal shared-head flash,
           and the expert family's at its cell's widths: causal latent
           attention over the projections' arrays, the routed experts'
           grouped product

Each phase prints one JSON line: wall seconds, the seconds jax spent
compiling (or fetching from the persistent cache) and tracing, and the
seconds of the steady part after the warm-up, apart. A phase that fails raises:
nothing is caught and carried on from, and the exit code is non-zero.
The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

With no TPU the script exits 2 before doing any work and prints no
result; weights and inputs come from seeds, nothing is fetched.
``--rehearse-cpu`` is the explicit CPU rehearsal of this script's own
control flow at cut sizes: it says so on every line and never prints
the ``ok`` record.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Sizes:
    images: int = 1024
    batch: int = 128
    partitions: int = 4
    base_lengths: Tuple[int, ...] = (128, 512)
    train_side: int = 224
    train_rows_per_device: int = 32
    kernel_lengths: Tuple[int, ...] = (256, 2048)
    kernel_heads: int = 12


#: The rehearsal keeps every width and cuts counts, lengths and the
#: trainer's image side, so the CPU finishes in minutes.
REHEARSAL = Sizes(
    images=32,
    batch=8,
    base_lengths=(128,),
    train_side=64,
    train_rows_per_device=2,
    kernel_lengths=(256,),
    kernel_heads=2,
)

#: bf16-class agreement: the largest absolute difference allowed, as a
#: share of the reference's largest magnitude. Both sides of each
#: comparison compute their matrix products in bf16 on the MXU (bf16
#: programs, and float32 ones at the MXU's default precision); a
#: float16- or int8-class error would be an order of magnitude larger.
REL_TOL = 2e-2


class CompileMeter:
    """Sums what jax reports about compilation while the smoke runs:
    ``compile_s`` is the backend's share (XLA compiling, or the
    persistent cache's fetch that replaced it: the part a warm cache
    removes), ``trace_s`` is tracing plus lowering (a jit traced inside
    another is counted in both, so it can exceed the wall clock), and
    the persistent cache's hit and miss events are counted. Compiles
    happen on the feeder's and the server's threads too, hence the lock."""

    _SECONDS = {
        "/jax/core/compile/backend_compile_duration": "compile_s",
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_s",
    }
    _COUNTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self._t = dict.fromkeys(
            ("compile_s", "trace_s", "compiles", "cache_hits", "cache_misses"),
            0,
        )
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        key = self._SECONDS.get(event)
        if key:
            with self._lock:
                self._t[key] += duration
                self._t["compiles"] += key == "compile_s"

    def _event(self, event: str, **_kw) -> None:
        key = self._COUNTS.get(event)
        if key:
            with self._lock:
                self._t[key] += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._t)


def _check_close(what: str, got, want) -> float:
    """The largest difference between ``got`` and its reference as a
    share of the reference's range; raises past REL_TOL, on a shape
    mismatch and on non-finite values."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{what}: {got.shape}, reference {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{what}: non-finite values")
    err = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))
    if err > REL_TOL:
        raise AssertionError(
            f"{what}: differs from its reference by {err:.3g} of the "
            f"reference's range (limit {REL_TOL})"
        )
    return round(err, 5)


def _check_latent(what: str, got, want, lengths, block: int) -> float:
    """Latent attention that was handed its rows' ``lengths``: a row's
    real positions against the reference's (`_check_close`), every
    position of a query block of padding alone exactly zero, and nothing
    non-finite anywhere."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError(f"{what}: non-finite values")
    err = 0.0
    for row, length in enumerate(lengths):
        dead = -(-length // block) * block
        if got[row, dead:].any():
            raise AssertionError(f"{what}: row {row} is not zero from {dead} on")
        if length:
            err = max(
                err,
                _check_close(f"{what} row {row}", got[row, :length], want[row, :length]),
            )
    return err


def _device_marks() -> Optional[List[Dict[str, int]]]:
    """Per-device allocator counters, or None where the backend keeps
    none (the CPU). ``num_allocs`` only ever grows, so it moves on every
    device that did work; ``peak_bytes_in_use`` is a high-water mark an
    earlier phase may already have set higher."""
    import jax

    marks = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats:
            return None
        marks.append(
            {
                "num_allocs": int(stats["num_allocs"]),
                "peak_bytes_in_use": int(stats["peak_bytes_in_use"]),
            }
        )
    return marks


def _every_device_worked(what: str, before, after) -> Optional[List[int]]:
    """The peak bytes per device after the phase; raises unless every
    device's allocation counter moved during it."""
    if before is None:
        return None
    idle = [
        i
        for i, (b, a) in enumerate(zip(before, after))
        if a["num_allocs"] <= b["num_allocs"]
    ]
    if idle:
        raise AssertionError(f"{what}: devices {idle} allocated nothing")
    return [a["peak_bytes_in_use"] for a in after]


# -- phases -----------------------------------------------------------------


def phase_device(rehearsal: bool) -> dict:
    import jax
    import jaxlib

    from sparkdl_tpu.runtime import compile_cache, native
    from sparkdl_tpu.utils.flops import device_peak_flops

    dev = jax.devices()[0]
    peak = device_peak_flops(dev.device_kind)
    if peak is None and not rehearsal:
        raise AssertionError(
            f"utils/flops.py has no peak for device kind {dev.device_kind!r}"
        )
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    # a clean build: whatever native/build/ held was not built from the
    # source this checkout carries
    native.build(clean=True)
    if not native.available():
        raise AssertionError("native image bridge built but did not load")
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "count": len(jax.devices()),
        "local_device_count": jax.local_device_count(),
        "peak_bf16_flops": peak,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "compile_cache_dir": compile_cache.cache_dir(),
        "native_bridge": True,
    }


def phase_offline(sizes: Sizes) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.graph.pieces import build_flattener, build_image_converter
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.models import get_model
    from sparkdl_tpu.transformers import DeepImageFeaturizer
    from sparkdl_tpu.transformers.execution import flat_device_fn

    n_dev = jax.local_device_count()
    spec = get_model("ResNet50")
    side = spec.height
    rng = np.random.default_rng(0)
    images = rng.integers(
        0, 256, size=(sizes.images, side, side, 3), dtype=np.uint8
    )
    structs = [imageIO.imageArrayToStruct(a) for a in images]
    feat = DeepImageFeaturizer(
        inputCol="image",
        outputCol="features",
        modelName="ResNet50",
        computeDtype="bfloat16",
        batchSize=sizes.batch,
    )
    before = _device_marks()

    # the first transform compiles; one global batch is enough for that
    warm_rows = min(sizes.images, sizes.batch * n_dev)
    t0 = time.perf_counter()
    feat.transform(DataFrame.fromColumns({"image": structs[:warm_rows]})).count()
    warm_s = time.perf_counter() - t0

    df = DataFrame.fromColumns(
        {"image": structs}, numPartitions=sizes.partitions
    )
    t0 = time.perf_counter()
    rows = feat.transform(df).collect()
    run_s = time.perf_counter() - t0
    if len(rows) != sizes.images:
        raise AssertionError(f"{len(rows)} rows out, {sizes.images} in")
    got = np.stack([np.asarray(r.features, np.float32) for r in rows])
    if got.shape[1] != spec.feature_dim:
        raise AssertionError(f"features {got.shape}")

    # the same composed program, called directly
    mf = spec.model_function(mode="features", dtype=jnp.bfloat16)
    pipeline = (
        build_image_converter(
            channel_order_in="BGR", preprocessing=spec.preprocessing
        )
        .and_then(mf)
        .and_then(build_flattener())
    )
    # Every row, not only the first batch: a staging slot donated while
    # the feeder's ring still owned it would corrupt a LATER batch.
    direct = jax.jit(pipeline.fn)
    want = np.concatenate(
        [
            np.asarray(direct(pipeline.params, images[i : i + sizes.batch]))
            for i in range(0, sizes.images, sizes.batch)
        ]
    )
    err = _check_close("featurizer output", got, want)

    # the device stage the featurizer builds, on one global batch (the
    # first batch, repeated): its output must still be spread over every
    # device (nothing gathered)
    global_rows = sizes.batch * n_dev
    dev_fn = flat_device_fn(pipeline, (sizes.batch, side, side, 3))
    y = dev_fn(
        np.resize(images[: sizes.batch], (global_rows, side, side, 3))
    )
    shard_devices = {s.device for s in y.addressable_shards}
    if len(shard_devices) != n_dev or y.shape[0] != global_rows:
        raise AssertionError(
            f"device stage output {y.shape} sits on {len(shard_devices)} "
            f"of {n_dev} devices"
        )
    _check_close(
        "device stage", np.asarray(y)[: sizes.batch], want[: sizes.batch]
    )
    return {
        "rows": len(rows),
        "feature_dim": int(got.shape[1]),
        "rel_err_vs_direct": err,
        "devices": n_dev,
        "output_shard_devices": len(shard_devices),
        "peak_bytes_per_device": _every_device_worked(
            "offline", before, _device_marks()
        ),
        "warm_s": round(warm_s, 2),
        "run_s": round(run_s, 2),
    }


def _http(port: int, path: str, payload: Optional[dict] = None, lines=False):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if payload is None else json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=900) as resp:
            if lines:  # chunked ndjson, one record a line
                return [json.loads(ln) for ln in resp if ln.strip()]
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        # the server's own words, not just the status
        raise RuntimeError(
            f"{path} -> HTTP {e.code}: {e.read().decode(errors='replace')}"
        ) from e


def phase_online(sizes: Sizes, on_tpu: bool) -> dict:
    import jax
    import numpy as np

    from sparkdl_tpu.models import get_model
    from sparkdl_tpu.obs.memory import ground_truth_bytes
    from sparkdl_tpu.serving.__main__ import _serving_env_defaults
    from sparkdl_tpu.serving.router import Router
    from sparkdl_tpu.serving.server import ServingServer

    rng = np.random.default_rng(1)
    # Every request and its reference answer first, while no server
    # exists: what the directly called programs leave on the device is
    # then part of the baseline that serving's own growth is read from.
    requests = []  # (label, model, mode, inputs, reference)
    spec = get_model("ResNet50")
    direct = spec.model_function(mode="features").jitted()
    for i, n_rows in enumerate((1, 1, 2)):
        x = rng.normal(size=(n_rows, *spec.input_shape)).astype(np.float32)
        x = np.round(x, 3)  # what the JSON body carries
        requests.append(
            (f"ResNet50/{i}", "ResNet50", "features", x, np.asarray(direct(x)))
        )
    texts = [("bert-base", n) for n in sizes.base_lengths]
    texts.append(("bert-long-2048", 2048))
    for name, length in texts:
        spec = get_model(name)
        mf = spec.model_function(mode="embed")
        if on_tpu:
            # both presets' heads are narrower than a lane tile (64, 32)
            built = (mf.attention, mf.attention_layout)
            if built != ("flash", "packed"):
                raise AssertionError(f"{name} built with {built}")
            # params as an argument: as constants they would be
            # printed into the module text
            ids0 = np.ones((2, length), np.int32)
            if "tpu_custom_call" not in (
                jax.jit(mf.fn).lower(mf.params, ids0).as_text()
            ):
                raise AssertionError(f"{name}: no Mosaic call lowered")
        ids = rng.integers(1, spec.vocab_size, size=(2, length), dtype=np.int32)
        ids[1, length - length // 4 :] = 0  # a padded tail: the mask
        requests.append(
            (f"{name}/{length}", name, "embed", ids, np.asarray(mf.jitted()(ids)))
        )
    max_new = 8
    prompt = [1, 2, 3, 4, 5]
    gen = get_model("bert-tiny").generate_function()
    oracle = gen.greedy_oracle(np.asarray(prompt, np.int32), max_new)
    del direct, mf, gen
    truth0, _source = ground_truth_bytes()

    _serving_env_defaults()
    router = Router().start()
    server = ServingServer(router, port=0)
    replies: Dict[str, dict] = {}
    run_s = 0.0
    try:
        for label, name, mode, x, want in requests:
            # The same request twice: the first pays the load and the
            # compile, the second is the steady one. Both must be right.
            body = {
                "model": name,
                "mode": mode,
                "inputs": x.tolist(),
                "dtype": str(x.dtype),
            }
            t0 = time.perf_counter()
            cold = _http(server.port, "/v1/predict", body)
            t1 = time.perf_counter()
            warm = _http(server.port, "/v1/predict", body)
            t2 = time.perf_counter()
            run_s += t2 - t1
            for reply in (cold, warm):
                if reply["rows"] != x.shape[0]:
                    raise AssertionError(f"{label}: {reply['rows']} rows back")
                err = _check_close(label, reply["outputs"], want)
            replies[label] = {
                "rows": x.shape[0],
                "first_s": round(t1 - t0, 2),
                "second_s": round(t2 - t1, 3),
                "rel_err_vs_direct": err,
                "device_mb_grown": round(
                    (ground_truth_bytes()[0] - truth0) / 2**20, 1
                ),
            }

        t0 = time.perf_counter()
        records = _http(
            server.port,
            "/v1/predict",
            {
                "model": "bert-tiny",
                "mode": "generate",
                "inputs": prompt,
                "max_new_tokens": max_new,
                "stream": True,
            },
            lines=True,
        )
        gen_s = time.perf_counter() - t0
        streamed = [r["token"] for r in records if "token" in r]
        done = records[-1]
        if (
            not done.get("done")
            or done.get("error")
            or len(streamed) != max_new
            or np.ravel(done["tokens"]).tolist() != streamed
        ):
            raise AssertionError(f"generate stream: {records}")
        vocab = get_model("bert-tiny").vocab_size
        if not all(0 <= t < vocab for t in streamed):
            raise AssertionError(f"token out of vocabulary: {streamed}")
        # The first token comes from the prefill program the oracle runs
        # too, so it must match. Later ones come from the cached decode
        # program: with random weights a near-tie between two logits may
        # round the other way there, so their agreement is reported.
        if streamed[0] != oracle[0]:
            raise AssertionError(f"first token {streamed[0]} != {oracle[0]}")
        agree = sum(a == b for a, b in zip(streamed, oracle))

        models = _http(server.port, "/v1/models")["models"]
        resident = {(m["name"], m["mode"]): m for m in models}
        wanted = [("ResNet50", "features"), ("bert-tiny", "generate")]
        wanted += sorted({(name, "embed") for name, _ in texts})
        missing = [k for k in wanted if k not in resident]
        if missing:
            raise AssertionError(f"not resident: {missing}: {models}")
        attention = {
            name: resident[(name, "embed")].get("attention")
            for name, _ in texts
        }
        if on_tpu and set(attention.values()) != {"flash"}:
            raise AssertionError(f"served attention: {attention}")

        memory = _http(server.port, "/v1/memory")
        truth = "memory_stats" if on_tpu else "live_arrays"
        if (
            memory.get("ground_truth_source") != truth
            or not memory.get("tracked_bytes")
            or memory.get("unattributed_bytes") is None
        ):
            raise AssertionError(f"/v1/memory did not reconcile: {memory}")
        # The allocator's count against the ledger's. The ledger tracks
        # weights, staged batches and K/V state; each compiled shape's
        # program image and buffers are the allocator's alone (a second
        # bert-base length adds ~300 MiB to a model already resident). So
        # all the ledger tracks must be on the device, and what the device
        # gained while serving may be up to three times that.
        tracked = memory["tracked_bytes"]
        in_use = memory["ground_truth_bytes"]
        grown = in_use - truth0
        if not (tracked <= in_use and grown <= 3 * tracked):
            raise AssertionError(
                f"/v1/memory tracks {tracked} bytes; the device holds "
                f"{in_use}, {grown} of them gained while serving (at most "
                f"3x tracked allowed): {memory}"
            )
    finally:
        server.stop(close_router=True)
    return {
        "requests": replies,
        "generate": {
            "tokens": len(streamed),
            "s": round(gen_s, 2),
            "oracle_agreement": f"{agree}/{max_new}",
        },
        "resident": [f"{n}[{m}]" for n, m in wanted],
        "attention": attention,
        "memory": {
            "ground_truth_source": memory["ground_truth_source"],
            "tracked_bytes": tracked,
            "tracked_by_model": memory["models"],
            "device_bytes_before_serving": truth0,
            "device_bytes_grown_while_serving": grown,
        },
        "run_s": round(run_s, 2),
    }


def phase_trainer(sizes: Sizes) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.dataframe import DataFrame
    from sparkdl_tpu.estimators import DataParallelEstimator
    from sparkdl_tpu.graph.ingest import ModelIngest
    from sparkdl_tpu.models.resnet import ResNet50

    n_dev = jax.local_device_count()
    side, steps = sizes.train_side, 4
    batch = sizes.train_rows_per_device * n_dev
    model = ResNet50(num_classes=10)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, side, side, 3), jnp.float32)
    )
    mf = ModelIngest.from_flax(model, params, input_shape=(side, side, 3))
    rng = np.random.default_rng(2)
    feats = [
        rng.normal(size=(side, side, 3)).astype(np.float32)
        for _ in range(batch * steps)
    ]
    labels = rng.integers(0, 10, size=(batch * steps,)).astype(np.int32)
    df = DataFrame.fromColumns(
        {"features": feats, "label": list(labels)}, numPartitions=2
    )
    est = DataParallelEstimator(
        model=mf,
        inputCol="features",
        labelCol="label",
        outputCol="logits",
        batchSize=batch,
        epochs=2,  # the first epoch pays the compile, the second is steady
        stepSize=0.01,
    )
    before = _device_marks()
    fitted = est.fit(df)
    history = fitted.history
    if [h["steps"] for h in history] != [steps, steps]:
        raise AssertionError(f"step counter: {history}")
    losses = [float(h["loss"]) for h in history]
    if not np.isfinite(losses).all():
        raise AssertionError(f"loss: {losses}")
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(fitted.modelFunction.params),
        )
    )
    if not moved:
        raise AssertionError("parameters did not change")
    return {
        "devices": n_dev,
        "global_batch": batch,
        "steps": sum(h["steps"] for h in history),
        "losses": [round(x, 4) for x in losses],
        "peak_bytes_per_device": _every_device_worked(
            "trainer", before, _device_marks()
        ),
        "warm_s": round(history[0]["epoch_time_s"], 2),
        "run_s": round(history[1]["epoch_time_s"], 2),
    }


def phase_kernel(sizes: Sizes, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.models.bert import dense_attention
    from sparkdl_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_packed,
        packs,
    )

    flash = jax.jit(
        lambda q, k, v, m: flash_attention(q, k, v, m, interpret=interpret)
    )

    @jax.jit
    def packed(q, k, v, m):
        # the same heads side by side, [B, L, H*Dh], as a projection
        # writes them; the answer back in the blocked kernel's layout
        B, H, L, dh = q.shape
        q, k, v = (
            t.transpose(0, 2, 1, 3).reshape(B, L, H * dh) for t in (q, k, v)
        )
        out = flash_attention_packed(
            q, k, v, m, num_heads=H, interpret=interpret
        )
        return out.reshape(B, L, H, dh).transpose(0, 2, 1, 3)

    @jax.jit
    def dense(q, k, v, m):
        f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
        return dense_attention(
            f32(q), f32(k), f32(v), m[:, None, None, :], jnp.float32
        )

    rng = np.random.default_rng(3)
    B, H = 2, sizes.kernel_heads
    errs = {}
    run_s = 0.0
    for dtype in (jnp.float32, jnp.bfloat16):
        for dh in (64, 128):
            for length in sizes.kernel_lengths:
                q, k, v = (
                    jnp.asarray(rng.normal(size=(B, H, length, dh)), dtype)
                    for _ in range(3)
                )
                # a different mask in each batch row, as BertEncoder
                # builds it: finfo.min on the padded keys
                mask = np.zeros((B, length), np.float32)
                mask[0, length // 2 :] = np.finfo(np.float32).min
                mask[1, length - 3 :] = np.finfo(np.float32).min
                mask = jnp.asarray(mask)
                got = flash(q, k, v, mask).block_until_ready()
                t0 = time.perf_counter()
                flash(q, k, v, mask).block_until_ready()
                run_s += time.perf_counter() - t0
                with jax.default_matmul_precision("highest"):
                    want = dense(q, k, v, mask)
                tag = f"{np.dtype(dtype).name}/dh{dh}/L{length}"
                errs[tag] = _check_close(f"flash {tag}", got, want)
                if packs(H, dh):
                    errs[f"{tag}/packed"] = _check_close(
                        f"packed flash {tag}", packed(q, k, v, mask), want
                    )
    return {
        "compiled": not interpret,
        "rel_err_vs_dense": errs,
        "run_s": round(run_s, 3),
        **_hybrid_kernels(sizes, interpret),
        **_expert_kernels(sizes, interpret),
        **_sparse_attention_kernels(sizes, interpret),
    }


def _hybrid_kernels(sizes: Sizes, interpret: bool) -> dict:
    """The kernels of the hybrid text family (models/jamba.py): the
    selective scan against the plain chunked scan, causal flash over one
    shared key/value head against dense; and which of each a model built
    here gets."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.models import get_model
    from sparkdl_tpu.ops.flash_attention import (
        dense_causal_attention,
        flash_attention,
    )
    from sparkdl_tpu.ops.selective_scan import chunked_scan, selective_scan

    rng = np.random.default_rng(4)
    rows, d_inner, n = 2, 256, 16
    errs = {}
    for length in sizes.kernel_lengths:
        wide = (rows, length, d_inner)
        h, z = (jnp.asarray(rng.normal(size=wide), jnp.bfloat16) for _ in range(2))
        dt = jnp.asarray(np.exp(rng.uniform(-6.9, -2.3, size=wide)), jnp.float32)
        b, c = (
            jnp.asarray(rng.normal(size=(rows, length, n)), jnp.float32)
            for _ in range(2)
        )
        a = -jnp.broadcast_to(jnp.arange(1.0, n + 1), (d_inner, n))
        d = jnp.ones((d_inner,), jnp.float32)
        got = jax.jit(
            lambda *t: selective_scan(*t, interpret=interpret, out_dtype=jnp.float32)
        )(h, dt, b, c, z, a, d)
        want = jax.jit(
            lambda *t: chunked_scan(*t, out_dtype=jnp.float32)
        )(h, dt, b, c, z, a, d)
        errs[f"scan/L{length}"] = _check_close(f"scan L{length}", got, want)
        q = jnp.asarray(rng.normal(size=(rows, 4, length, 128)), jnp.bfloat16)
        k, v = (
            jnp.asarray(rng.normal(size=(rows, 1, length, 128)), jnp.bfloat16)
            for _ in range(2)
        )
        got = jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, interpret=interpret, causal=True
            )
        )(q, k, v)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(
                lambda q, k, v: dense_causal_attention(
                    *(t.astype(jnp.float32) for t in (q, k, v)), None, jnp.float32
                )
            )(q, k, v)
        errs[f"causal_flash/L{length}"] = _check_close(
            f"causal flash L{length}", got, want
        )
    mf = get_model("jamba-tiny").model_function(mode="embed")
    return {
        "hybrid_rel_err": errs,
        "jamba_built_with": {"attention": mf.attention, "scan": mf.scan},
    }


def _expert_kernels(sizes: Sizes, interpret: bool) -> dict:
    """The kernels of the latent-attention, routed-expert family
    (models/deepseek_v2.py) at its cell's widths: causal latent attention
    over the projections' arrays, handed its rows' lengths as the cells
    hand them, against dense (`_check_latent`), and the grouped product
    of an expert layer's gate and down shapes against ``lax.ragged_dot``
    over the rows the groups cover; and which of each a model built here
    gets. A rehearsal keeps the head sizes and cuts the rest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.models import get_model
    from sparkdl_tpu.ops.flash_attention import (
        dense_latent_attention,
        flash_attention_latent,
    )
    from sparkdl_tpu.ops.grouped_matmul import grouped_matmul, ragged_matmul

    rng = np.random.default_rng(5)
    errs = {}
    rows, heads = 3, 2 if interpret else 16
    scale = 192**-0.5 * 1.589626
    for length in sizes.kernel_lengths:
        block = 128 if interpret else min(1024, length)  # the model's blocks
        # as the cells call it: a whole row, one whose real tokens end
        # inside its first query block, and a row that only fills a batch
        lengths = [length, block - 3, 0]
        # the model's own entry: the projections' arrays, a head's
        # [nope | rope] query, [key | value] and the shared rotary key
        nope = 128
        q = jnp.asarray(rng.normal(size=(rows, length, heads * 2 * nope)), jnp.bfloat16)
        kv = jnp.asarray(rng.normal(size=(rows, length, heads * 2 * nope)), jnp.bfloat16)
        kr = jnp.asarray(rng.normal(size=(rows, length, nope)), jnp.bfloat16)
        got = jax.jit(
            lambda q, kv, kr, lengths: flash_attention_latent(
                q, kv, kr, None, lengths, num_heads=heads, scale=scale,
                block=block, interpret=interpret,
            )
        )(q, kv, kr, jnp.asarray(lengths, jnp.int32))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(
                lambda q, kv, kr: dense_latent_attention(
                    *(t.astype(jnp.float32) for t in (q, kv, kr)), jnp.float32,
                    num_heads=heads, scale=scale,
                )
            )(q, kv, kr)
        errs[f"latent_flash/L{length}"] = _check_latent(
            f"latent flash L{length}", got, want, lengths, block
        )
    # slots sorted by expert: 40 held experts, uneven, one empty, and a
    # tail of slots whose expert is elsewhere
    hidden, width, groups = (256, 128, 5) if interpret else (5120, 1536, 40)
    slots = 640 if interpret else 32768
    share = rng.dirichlet(np.full(groups, 2.0)) * 0.7 * slots
    sizes_ = np.floor(share).astype(np.int32)
    sizes_[1] = 0
    covered = int(sizes_.sum())
    for k_dim, n_dim in ((hidden, width), (width, hidden)):
        lhs = jnp.asarray(rng.normal(size=(slots, k_dim)), jnp.bfloat16)
        rhs = jnp.asarray(
            rng.normal(size=(groups, k_dim, n_dim)) / np.sqrt(k_dim), jnp.bfloat16
        )
        got = grouped_matmul(lhs, rhs, jnp.asarray(sizes_), interpret=interpret)
        want = ragged_matmul(lhs, rhs, jnp.asarray(sizes_))
        errs[f"grouped/{k_dim}x{n_dim}"] = _check_close(
            f"grouped product {k_dim}x{n_dim}", got[:covered], want[:covered]
        )
    mf = get_model("deepseek-v2-tiny").model_function(mode="embed")
    return {
        "expert_rel_err": errs,
        "deepseek_v2_built_with": {"attention": mf.attention, "experts": mf.experts},
    }


def _sparse_attention_kernels(sizes: Sizes, interpret: bool) -> dict:
    """The kernels of the family that selects each query's keys
    (models/deepseek_v32.py, ops/dsa_indexer.py) at its widths: the index
    scores of 64 heads of 128 against their dense sum; the selection
    against ``lax.top_k`` over the masked rows, query by query; latent
    attention over the selected keys of a row whose last query block is
    padding alone, against dense; and which of each a
    model built here gets. A rehearsal keeps the head sizes and cuts the
    rest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.models import get_model
    from sparkdl_tpu.ops import dsa_indexer
    from sparkdl_tpu.ops.flash_attention import (
        dense_latent_attention,
        flash_attention_latent,
    )

    rng = np.random.default_rng(6)
    errs = {}
    index_heads, heads = (4, 2) if interpret else (64, 8)
    scale = 192**-0.5 * 1.3689**2
    for length in sizes.kernel_lengths:
        top_k = length // 8
        q = jnp.asarray(rng.normal(size=(1, length, index_heads * 128)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(1, length, 128)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(1, length, index_heads)) * 0.1, jnp.float32)
        scores = jax.jit(
            lambda q, k, w: dsa_indexer.dsa_index_scores(
                q, k, w, num_heads=index_heads, interpret=interpret
            )
        )(q, k, w)
        want = jax.jit(
            lambda q, k, w: dsa_indexer.index_scores(q, k, w, num_heads=index_heads)
        )(q, k, w)
        causal = jnp.tril(jnp.ones((length, length), bool))
        errs[f"index_scores/L{length}"] = _check_close(
            f"index scores L{length}", jnp.where(causal, scores, 0.0),
            jnp.where(causal, want, 0.0),
        )
        selection = jax.jit(
            lambda s: dsa_indexer.dsa_select(
                s, top_k=top_k, block_q=32 if interpret else 64,
                chunk=128 if interpret else 512, interpret=interpret,
            )
        )(scores)
        _, best = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), top_k)
        by_top_k = np.zeros((1, length, length), np.int8)
        np.put_along_axis(by_top_k, np.asarray(best), 1, -1)
        by_top_k &= np.asarray(causal)
        differing = int((np.asarray(selection) != by_top_k).sum())
        if differing:
            raise RuntimeError(
                f"selection L{length}: {differing} pairs differ from lax.top_k"
            )
        errs[f"selection/L{length}"] = 0.0
        block = 128 if interpret else min(1024, length)
        qa = jnp.asarray(rng.normal(size=(1, length, heads * 256)), jnp.bfloat16)
        kv = jnp.asarray(rng.normal(size=(1, length, heads * 256)), jnp.bfloat16)
        kr = jnp.asarray(rng.normal(size=(1, length, 128)), jnp.bfloat16)
        # the last of several query blocks is padding alone
        lengths = [max(length - block, block) - 5]
        got = jax.jit(
            lambda qa, kv, kr, sel, lengths: flash_attention_latent(
                qa, kv, kr, sel, lengths, num_heads=heads, scale=scale,
                block=block, interpret=interpret,
            )
        )(qa, kv, kr, selection, jnp.asarray(lengths, jnp.int32))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(
                lambda qa, kv, kr, sel: dense_latent_attention(
                    *(t.astype(jnp.float32) for t in (qa, kv, kr)), jnp.float32,
                    sel, num_heads=heads, scale=scale,
                )
            )(qa, kv, kr, selection)
        errs[f"selecting_flash/L{length}"] = _check_latent(
            f"selecting flash L{length}", got, want, lengths, block
        )
    mf = get_model("deepseek-v3.2-exp-tiny").model_function(mode="embed")
    return {
        "sparse_attention_rel_err": errs,
        "deepseek_v32_built_with": {
            "attention": mf.attention, "experts": mf.experts, "indexer": mf.indexer,
        },
    }


# -- driver -----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--rehearse-cpu",
        action="store_true",
        help="rehearse this script's control flow on the CPU at cut sizes; "
        "proves nothing about a chip and never prints the ok record",
    )
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_cpu
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import sparkdl_tpu  # places the compile cache; absent = not the repo

    if os.path.dirname(os.path.dirname(sparkdl_tpu.__file__)) != HERE:
        print(
            f"chip_smoke: sparkdl_tpu came from {sparkdl_tpu.__file__}, not "
            "from this checkout",
            file=sys.stderr,
        )
        return 2
    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not rehearsal:
        print(
            f"chip_smoke: no TPU (jax found {dev.platform!r} devices); "
            "nothing was run",
            file=sys.stderr,
        )
        return 2
    sizes = REHEARSAL if rehearsal else Sizes()
    meter = CompileMeter()

    def run(name: str, fn: Callable[[], dict]) -> None:
        c0, t0 = meter.snapshot(), time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        c1 = meter.snapshot()
        line = {
            "phase": name,
            **({"rehearsal": "cpu"} if rehearsal else {}),
            "wall_s": round(wall, 2),
            **{k: round(c1[k] - c0[k], 2) for k in c1},
            **out,
        }
        print(json.dumps(line), flush=True)

    run("device", lambda: phase_device(rehearsal))
    run("offline", lambda: phase_offline(sizes))
    run("online", lambda: phase_online(sizes, on_tpu))
    run("trainer", lambda: phase_trainer(sizes))
    run("kernel", lambda: phase_kernel(sizes, interpret=rehearsal))

    from sparkdl_tpu.runtime import compile_cache

    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    cache_dir = compile_cache.cache_dir()
    cache_files = [
        os.path.join(root, f)
        for root, _dirs, files in os.walk(cache_dir)
        for f in files
    ]
    print(
        json.dumps(
            {
                "build_ledger": compile_cache.stats(),
                "compile_cache_dir": cache_dir,
                "compile_cache_files": len(cache_files),
                "compile_cache_mb": round(
                    sum(map(os.path.getsize, cache_files)) / 2**20, 1
                ),
            }
        ),
        flush=True,
    )
    if rehearsal:
        print(json.dumps({"rehearsal": "cpu", "phases": "passed", "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
