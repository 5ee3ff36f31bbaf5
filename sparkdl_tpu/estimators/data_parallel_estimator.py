"""DataParallelEstimator — distributed synchronous training on the mesh.

Reference analogue: ``HorovodEstimator`` (BASELINE config[4]; SURVEY.md
§4.4): gang-started workers, per-step NCCL ring all-reduce of gradients,
rank-0 TF checkpoints to modelDir with auto-resume. TPU-native redesign:

- the train step is ONE jitted SPMD program (shard_map over the 'dp' mesh
  axis, psum gradient reduction over ICI) — see parallel/data_parallel.py;
- checkpoints are orbax (async-capable, pytree-native), written each
  ``checkpointEvery`` steps to ``modelDir``; ``fit`` auto-resumes from the
  latest checkpoint exactly like HorovodEstimator's modelDir resume;
- input: a feature column of fixed-shape arrays (or image structs via
  targetHeight/targetWidth) + integer label column; the host pipeline
  shards each global batch across 'dp'.

Returns a DataParallelModel — a Transformer applying the trained params —
so fit().transform() composes in pipelines like every other stage.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from sparkdl_tpu.dataframe import DataFrame
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.graph.pieces import image_structs_to_batch
from sparkdl_tpu.parallel import (
    TrainState,
    create_train_state,
    make_data_parallel_step,
    make_mesh,
    make_zero1_data_parallel_step,
    pad_batch_to_multiple,
)
from sparkdl_tpu.params import (
    HasBatchSize,
    HasInputCol,
    HasLabelCol,
    HasOutputCol,
    Param,
    TypeConverters,
    keyword_only,
)
from sparkdl_tpu.pipeline import Estimator, Model
from sparkdl_tpu.transformers.execution import (
    arrays_to_batch,
    dispatch_env_key,
    model_device_fn,
    prefetch_iter,
    run_batched_shared,
)
from sparkdl_tpu.utils.metrics import metrics as metrics_registry


class DataParallelModel(Model):
    def __init__(
        self,
        model_function: ModelFunction,
        inputCol: str,
        outputCol: str,
        batchSize: int = 64,
        image_geometry: Optional[Tuple[int, int]] = None,
        history: Optional[List[dict]] = None,
    ):
        super().__init__()
        self.modelFunction = model_function
        self._input_col = inputCol
        self._output_col = outputCol
        self._batch_size = batchSize
        self._geometry = image_geometry
        self.history = history or []
        self._device_fns: Dict[tuple, Callable] = {}

    def _device_fn(self):
        # Same multi-device dispatch as every other transformer
        # (shard_map / round-robin over the local pool per
        # SPARKDL_INFERENCE_MODE), keyed so mid-session A/B knob flips
        # never reuse a stale strategy. Image-geometry models score
        # through the flat channel-major feed — the program unpacks to
        # the identical uint8 NHWC batch the plain jit would receive,
        # but the transfer avoids the narrow-minor-dim lane padding.
        key = dispatch_env_key()
        fn = self._device_fns.get(key)
        if fn is None:
            if self._geometry is not None:
                from sparkdl_tpu.transformers.execution import flat_device_fn

                h, w = self._geometry
                fn = flat_device_fn(
                    self.modelFunction, (self._batch_size, h, w, 3)
                )
            else:
                fn = model_device_fn(self.modelFunction)
            self._device_fns[key] = fn
        return fn

    def _transform(self, dataset: DataFrame) -> DataFrame:
        in_col, out_col = self._input_col, self._output_col
        geom = self._geometry
        device_fn = self._device_fn()

        def run_partition(part):
            cells = part[in_col]
            if geom is not None:
                to_batch = lambda chunk: image_structs_to_batch(
                    chunk,
                    height=geom[0],
                    width=geom[1],
                    chw=getattr(device_fn, "nchw", False),
                )
            else:
                to_batch = arrays_to_batch
            # Concurrent partitions coalesce into one continuous-batching
            # stream of the shared feeder.
            outputs = run_batched_shared(
                cells, to_batch=to_batch, device_fn=device_fn,
                batch_size=self._batch_size,
            )
            return {out_col: outputs}

        return dataset.withColumnPartition(out_col, run_partition)


class DataParallelEstimator(
    Estimator, HasInputCol, HasOutputCol, HasLabelCol, HasBatchSize
):
    """Synchronous data-parallel trainer.

    ``model`` is a ModelFunction (fn(params, x) -> logits) whose params are
    the init point; ``lossFn`` defaults to softmax cross-entropy on integer
    labels. ``batchSize`` is the GLOBAL batch; it is split evenly across
    the 'dp' mesh axis each step.
    """

    epochs = Param(None, "epochs", "training epochs", TypeConverters.toInt)
    stepSize = Param(None, "stepSize", "learning rate", TypeConverters.toFloat)
    modelDir = Param(
        None, "modelDir",
        "orbax checkpoint directory (enables save + auto-resume)",
        TypeConverters.toString,
    )
    checkpointEvery = Param(
        None, "checkpointEvery", "steps between checkpoints",
        TypeConverters.toInt,
    )
    targetHeight = Param(
        None, "targetHeight", "image input height (image-struct columns)",
        TypeConverters.toInt,
    )
    targetWidth = Param(
        None, "targetWidth", "image input width (image-struct columns)",
        TypeConverters.toInt,
    )
    meshAxes = Param(
        None, "meshAxes", "mesh axes dict, e.g. {'dp': -1}",
        TypeConverters.toDict,
    )
    gradAccumSteps = Param(
        None, "gradAccumSteps",
        "microbatches per step (local grad accumulation before the "
        "all-reduce; global batch must divide by dp_size * this)",
        TypeConverters.toInt,
    )
    computeDtype = Param(
        None, "computeDtype",
        "forward/backward dtype ('bfloat16' for the MXU path); master "
        "params and optimizer state stay float32",
        TypeConverters.toString,
    )
    streaming = Param(
        None, "streaming",
        "feed training from partitions through a shuffle buffer (RSS "
        "bounded at O(buffer + partition)) instead of materializing the "
        "dataset to host RAM — the executor-local-feed discipline of the "
        "reference's Horovod path. With scanParquet input the whole path "
        "is bounded; in a multi-process gang each rank reads ONLY its own "
        "partitions",
        TypeConverters.toBoolean,
    )
    shuffleBufferRows = Param(
        None, "shuffleBufferRows",
        "shuffle-buffer size in rows for streaming=True (coarse order "
        "comes from the epoch's partition permutation; fine order from "
        "this buffer)",
        TypeConverters.toInt,
    )
    shardOptimizerState = Param(
        None, "shardOptimizerState",
        "ZeRO-1 weight-update sharding: optimizer state split 1/N across "
        "the dp axis (reduce-scatter grads, all-gather updated params); "
        "cuts Adam state memory per device by the dp size. Requires an "
        "ELEMENTWISE optimizer (sgd/momentum/adam/adamw...) — transforms "
        "needing whole-tree structure (clip_by_global_norm, per-layer "
        "schedules) would compute per-shard and diverge, so a build-time "
        "probe rejects them loudly (parallel/data_parallel.py "
        "_assert_elementwise_optimizer)",
        TypeConverters.toBoolean,
    )
    validateOptimizer = Param(
        None, "validateOptimizer",
        "run the ZeRO-1 elementwise-optimizer probe at build time "
        "(default True); set False only for optimizers independently "
        "verified shard-consistent that the bare-array probe cannot "
        "exercise",
        TypeConverters.toBoolean,
    )

    @keyword_only
    def __init__(
        self,
        model: Optional[ModelFunction] = None,
        lossFn: Optional[Callable] = None,
        optimizer: Optional[Any] = None,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        labelCol: Optional[str] = None,
        batchSize: Optional[int] = None,
        epochs: Optional[int] = None,
        stepSize: Optional[float] = None,
        modelDir: Optional[str] = None,
        checkpointEvery: Optional[int] = None,
        targetHeight: Optional[int] = None,
        targetWidth: Optional[int] = None,
        meshAxes: Optional[dict] = None,
        gradAccumSteps: Optional[int] = None,
        computeDtype: Optional[str] = None,
        shardOptimizerState: Optional[bool] = None,
        validateOptimizer: Optional[bool] = None,
        streaming: Optional[bool] = None,
        shuffleBufferRows: Optional[int] = None,
    ):
        super().__init__()
        self._setDefault(
            batchSize=64, epochs=1, stepSize=1e-3, checkpointEvery=100,
            labelCol="label", gradAccumSteps=1, streaming=False,
            shuffleBufferRows=4096, validateOptimizer=True,
        )
        kwargs = {
            k: v
            for k, v in self._input_kwargs.items()
            if k not in ("model", "lossFn", "optimizer")
        }
        self._set(**kwargs)
        self.model = model
        self.lossFn = lossFn
        self.optimizer = optimizer

    # -- persistence ----------------------------------------------------------
    # The model/loss/optimizer are CODE, not params: in the gang path they
    # travel as a builder spec in the train job (the reference's
    # HorovodEstimator took a modelFn for exactly this reason — SURVEY.md
    # §4.4) and every worker reconstructs them. A saved estimator therefore
    # carries only its Params; saving one whose callables are set would
    # silently drop them, so it refuses.

    def _save_extra(self, path):
        set_attrs = [
            k
            for k in ("model", "lossFn", "optimizer")
            if getattr(self, k) is not None
        ]
        if set_attrs:
            raise ValueError(
                f"DataParallelEstimator cannot persist {set_attrs}: pass a "
                "model builder in the train job spec (sparkdl_tpu.worker) "
                "and keep these None when saving"
            )
        return None

    def _load_extra(self, path, meta):
        self.model = None
        self.lossFn = None
        self.optimizer = None

    # -- checkpointing (orbax) ------------------------------------------------

    def _checkpointer(self):
        import orbax.checkpoint as ocp

        return ocp.StandardCheckpointer()

    def _latest_step(self, model_dir: str) -> Optional[int]:
        if not os.path.isdir(model_dir):
            return None
        steps = []
        for name in os.listdir(model_dir):
            if name.startswith("step_") and name[5:].isdigit():
                steps.append(int(name[5:]))
        return max(steps) if steps else None

    @staticmethod
    def _to_host(a):
        """Replicated/host leaves -> numpy; gang-sharded global arrays
        (ZeRO-1 opt state) stay jax.Arrays — orbax writes each shard from
        the rank that owns it."""
        if isinstance(a, jax.Array) and not a.is_fully_addressable:
            return a
        return np.asarray(a)

    def _save(self, model_dir: str, state: TrainState) -> None:
        ckptr = self._checkpointer()
        step = int(state.step)
        path = os.path.join(os.path.abspath(model_dir), f"step_{step}")
        host_state = jax.tree_util.tree_map(self._to_host, state)
        ckptr.save(path, host_state, force=True)
        ckptr.wait_until_finished()

    def _restore(self, model_dir: str, state: TrainState) -> TrainState:
        step = self._latest_step(model_dir)
        if step is None:
            return state

        def abstract_of(a):
            if isinstance(a, jax.Array) and not a.is_fully_addressable:
                # restore sharded leaves AS sharded (each rank reads its
                # own shards)
                return jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=a.sharding
                )
            return np.asarray(a)

        ckptr = self._checkpointer()
        abstract = jax.tree_util.tree_map(abstract_of, state)
        restored = ckptr.restore(
            os.path.join(os.path.abspath(model_dir), f"step_{step}"), abstract
        )
        return jax.tree_util.tree_map(
            lambda r: r if isinstance(r, jax.Array) else jnp.asarray(r),
            restored,
        )

    # -- data -----------------------------------------------------------------

    def _decode_chunk(self, cells, labels):
        """(x, y) arrays from raw column chunks: null rows dropped, image
        structs decoded to targetHeight×targetWidth (undecodable structs
        dropped — never train on zero-image/real-label pairs)."""
        keep = [
            i
            for i in range(len(cells))
            if cells[i] is not None and labels[i] is not None
        ]
        image_mode = self.isDefined("targetHeight")
        if image_mode:
            h = self.getOrDefault("targetHeight")
            w = self.getOrDefault("targetWidth")
            batch, mask = image_structs_to_batch(
                [cells[i] for i in keep], height=h, width=w
            )
            # Stay uint8: the host->device step feed is the training hot
            # path's biggest wire cost (4x fewer bytes than float32 on
            # 224^2 images); the cast to float happens inside the jitted
            # step, where XLA fuses it into the first conv.
            x = batch[mask]
            keep = [i for i, ok in zip(keep, mask) if ok]
        else:
            x = (
                np.stack([np.asarray(cells[i], np.float32) for i in keep])
                if keep
                else np.zeros((0,), np.float32)
            )
        y = np.asarray([int(labels[i]) for i in keep], np.int32)
        return x, y

    def _materialize(self, dataset: DataFrame):
        in_col, label_col = self.getInputCol(), self.getLabelCol()
        cols = dataset.select(in_col, label_col).collectColumns()
        return self._decode_chunk(cols[in_col], cols[label_col])

    def _stream_chunks(self, dataset: DataFrame, owned, epoch: int):
        """Decoded (x, y) chunks from ``owned`` partitions in an
        epoch-seeded permuted order, one partition in memory at a time."""
        in_col, label_col = self.getInputCol(), self.getLabelCol()
        proj = dataset.select(in_col, label_col)
        rng = np.random.default_rng(982_451 + epoch)
        order = [owned[i] for i in rng.permutation(len(owned))]
        for part in proj.iterPartitions(order=order):
            x, y = self._decode_chunk(
                list(part[in_col]), list(part[label_col])
            )
            if x.shape[0]:
                yield x, y

    def _stream_batches(
        self, dataset: DataFrame, owned, epoch: int, batch_rows: int,
        buffer_rows: int,
    ):
        """Yield host batches of exactly ``batch_rows`` rows (last may be
        short) through a shuffle buffer of ~``buffer_rows`` rows: the
        tf.data/Horovod executor-feed discipline — partition permutation
        for coarse shuffling, within-buffer permutation for fine, RSS
        bounded at O(buffer + partition) regardless of dataset size."""
        rng = np.random.default_rng(77_003 + epoch)
        buf_x: List[np.ndarray] = []
        buf_y: List[np.ndarray] = []
        held = 0

        def drain(final: bool):
            nonlocal buf_x, buf_y, held
            x = np.concatenate(buf_x) if len(buf_x) > 1 else buf_x[0]
            y = np.concatenate(buf_y) if len(buf_y) > 1 else buf_y[0]
            perm = rng.permutation(x.shape[0])
            x, y = x[perm], y[perm]
            emit_end = x.shape[0] if final else (
                x.shape[0] // batch_rows
            ) * batch_rows
            for s in range(0, emit_end, batch_rows):
                yield x[s : s + batch_rows], y[s : s + batch_rows]
            buf_x, buf_y = [x[emit_end:]], [y[emit_end:]]
            held = x.shape[0] - emit_end

        for x, y in self._stream_chunks(dataset, owned, epoch):
            buf_x.append(x)
            buf_y.append(y)
            held += x.shape[0]
            if held >= max(buffer_rows, batch_rows):
                yield from drain(final=False)
        if held:
            yield from drain(final=True)

    # -- fit ------------------------------------------------------------------

    def _fit(self, dataset: DataFrame) -> DataParallelModel:
        if self.model is None:
            raise ValueError("model (ModelFunction) must be provided")
        streaming = bool(self.getOrDefault("streaming"))
        x = y = None
        if not streaming:
            x, y = self._materialize(dataset)

        model_fn = self.model.fn
        loss_fn = self.lossFn
        if loss_fn is None:

            def loss_fn(params, batch):
                bx, by, bm = batch
                logits = model_fn(params, bx)
                per_ex = optax.softmax_cross_entropy_with_integer_labels(
                    logits, by
                )
                return jnp.sum(per_ex * bm) / jnp.maximum(jnp.sum(bm), 1.0)

        # The image feed arrives as uint8 (see _decode_chunk); cast to
        # float INSIDE the jitted step so user loss fns (and the default
        # above) always see the float batch they were written for. Only
        # uint8 — an integer feature column (token ids) must reach the
        # model as ints. The dtype test is static at trace time — float
        # feeds compile to a no-op wrapper.
        inner_loss = loss_fn

        def loss_fn(params, batch):
            bx, by, bm = batch
            if jnp.asarray(bx).dtype == jnp.uint8:
                bx = jnp.asarray(bx).astype(jnp.float32)
            return inner_loss(params, (bx, by, bm))

        optimizer = self.optimizer or optax.adam(self.getOrDefault("stepSize"))
        mesh = make_mesh(
            self.getOrDefault("meshAxes") if self.isDefined("meshAxes") else None
        )
        n_dev = int(mesh.devices.size)
        compute_dtype = (
            jnp.dtype(self.getOrDefault("computeDtype"))
            if self.isDefined("computeDtype")
            else None
        )
        zero1 = self.isDefined("shardOptimizerState") and self.getOrDefault(
            "shardOptimizerState"
        )
        # Multi-process gang (jax.distributed rendezvous done by the
        # caller, e.g. sparkdl_tpu.worker train jobs): the mesh spans every
        # process's devices and the SAME jitted step runs unchanged — only
        # the batch staging differs (host numpy must become global arrays).
        multiproc = jax.process_count() > 1
        # Copy init params: the donated train step consumes its input buffers,
        # and self.model.params must survive for re-fits / other transformers.
        init_params = jax.tree_util.tree_map(
            lambda a: jnp.array(a, copy=True), self.model.params
        )
        if zero1:
            step_fn, zero1_init = make_zero1_data_parallel_step(
                loss_fn,
                optimizer,
                mesh,
                init_params,
                compute_dtype=compute_dtype,
                grad_accum_steps=self.getOrDefault("gradAccumSteps"),
                microbatch_weight_fn=lambda b: jnp.sum(b[2]),
                validate_elementwise=self.getOrDefault("validateOptimizer"),
            )
            state = zero1_init(init_params)
        else:
            step_fn = make_data_parallel_step(
                loss_fn,
                optimizer,
                mesh,
                grad_accum_steps=self.getOrDefault("gradAccumSteps"),
                compute_dtype=compute_dtype,
                # weight microbatches by their valid-row count so padded
                # tail batches train identically to gradAccumSteps=1
                microbatch_weight_fn=lambda b: jnp.sum(b[2]),
            )
            state = create_train_state(init_params, optimizer)

        model_dir = (
            self.getOrDefault("modelDir") if self.isDefined("modelDir") else None
        )
        if model_dir:
            state = self._restore(model_dir, state)

        if streaming:
            # SOURCE row counts per partition (metadata-only; never
            # executes the plan): cheap and identical on every rank, so
            # the gang agrees on the per-epoch step count without
            # communication. A rank short of rows (dropped nulls, pending
            # filters) runs fully-masked pad steps to stay in lockstep.
            part_counts = dataset.partitionRowCounts()
            n = sum(part_counts)
        else:
            n = x.shape[0]
        if n == 0:
            raise ValueError(
                "No training data: every row was null or undecodable"
            )
        accum = max(1, self.getOrDefault("gradAccumSteps"))
        # every device shard must split into `accum` equal microbatches
        pad_unit = n_dev * accum
        global_batch = max(self.getBatchSize(), pad_unit)
        if global_batch % pad_unit:
            global_batch += pad_unit - global_batch % pad_unit
        nproc = jax.process_count()
        if n_dev % nproc:
            raise ValueError(
                f"mesh has {n_dev} devices over {nproc} processes; "
                "per-process device counts must be equal"
            )
        per_host_batch = global_batch // nproc
        ckpt_every = self.getOrDefault("checkpointEvery")
        history: List[dict] = []
        if not streaming:
            order = np.arange(n)
            rng = np.random.default_rng(0)
        if multiproc:
            from sparkdl_tpu.parallel.distributed import partitions_for_host

            owned = partitions_for_host(dataset.numPartitions)
        else:
            owned = list(range(dataset.numPartitions))
        if streaming and multiproc:
            # Lockstep step count = the HEAVIEST rank's load (every rank
            # computes the same value from the same metadata): no rank
            # ever has surplus batches silently dropped, and lighter
            # ranks pad with fully-masked steps.
            rank_rows = [
                sum(
                    part_counts[i]
                    for i in range(len(part_counts))
                    if i % nproc == r
                )
                for r in range(nproc)
            ]
            steps_per_epoch = max(
                -(-rr // per_host_batch) for rr in rank_rows
            )
        else:
            steps_per_epoch = -(-n // global_batch)

        batch_sharding = NamedSharding(mesh, PartitionSpec("dp"))

        def stage_batch(b):
            # In-memory multi-process staging: every process holds the same
            # host batch (identical data + seeded shuffle), and each
            # contributes the slices its local devices own — jit cannot
            # shard plain numpy across non-addressable devices.
            if not multiproc:
                return b
            return tuple(
                jax.make_array_from_callback(
                    a.shape, batch_sharding, lambda idx, a=a: a[idx]
                )
                for a in b
            )

        def stage_local(b, global_rows):
            # Streaming multi-process staging: each rank holds ONLY its own
            # per_host_batch rows (read from its own partitions); assemble
            # the global batch from the per-process shards.
            if not multiproc:
                return b
            return tuple(
                jax.make_array_from_process_local_data(
                    batch_sharding, a, (global_rows, *a.shape[1:])
                )
                for a in b
            )

        def pad_rows(hx, hy, target):
            k = hx.shape[0]
            mask = np.zeros((target,), np.float32)
            mask[:k] = 1.0
            if k < target:
                hx = np.concatenate(
                    [hx, np.zeros((target - k, *hx.shape[1:]), hx.dtype)]
                )
                hy = np.concatenate([hy, np.zeros((target - k,), hy.dtype)])
            return hx, hy, mask

        # Host-side mirror of state.step: reading the device counter
        # (int(state.step)) would force a device sync per step and
        # end the async chaining of steps. One read here (covers
        # checkpoint resume), then the host counts along.
        host_step = int(state.step)
        epoch_steps = 0
        # Sync cadence: without any block the host could decode and
        # dispatch an entire epoch of doomed batches before a device
        # failure (XLA OOM, bad program) surfaces at the epoch-end loss
        # fetch. One block every _SYNC_EVERY steps bounds the wasted
        # work at ~32 steps while amortizing the round-trip to noise.
        _SYNC_EVERY = 32

        def run_step(batch):
            nonlocal state, host_step, epoch_steps
            # Async dispatch, no per-step block: the device chains steps
            # through its own state dependency while the host stages the
            # next batch — transfers overlap compute, and the per-step
            # readback round-trip disappears. Sync points: every
            # _SYNC_EVERY steps, checkpoint saves (which pull state to
            # host), and the epoch-end loss fetch.
            state, metrics = step_fn(state, batch)
            host_step += 1
            epoch_steps += 1
            if model_dir and host_step % ckpt_every == 0:
                self._save(model_dir, state)
            elif host_step % _SYNC_EVERY == 0:
                jax.block_until_ready(metrics["loss"])
            return metrics

        feat_shape: Optional[Tuple[int, ...]] = None
        metrics: Optional[dict] = None
        for epoch in range(self.getOrDefault("epochs")):
            epoch_t0 = time.perf_counter()
            epoch_steps = 0
            if streaming:
                # producer-thread prefetch: decode/shuffle of batch i+1
                # overlaps the device step on batch i. Closed explicitly
                # in the finally — an exception surfacing in the loop
                # (staging failures immediately; device failures at the
                # next _SYNC_EVERY block) must stop the producer then,
                # not when the traceback lets go of the generator.
                gen = prefetch_iter(
                    self._stream_batches(
                        dataset, owned, epoch, per_host_batch,
                        self.getOrDefault("shuffleBufferRows"),
                    )
                )
                try:
                    for _ in range(steps_per_epoch):
                        t_wait = time.perf_counter()
                        nxt = next(gen, None)
                        # data-starved vs device-bound: if this wait
                        # dominates step time, the producer (decode/
                        # shuffle) is the bottleneck, not the chip
                        metrics_registry.record_time(
                            "train.data_wait",
                            time.perf_counter() - t_wait,
                        )
                        if nxt is None and not multiproc:
                            # single process answers to nobody: stop when
                            # the data ends rather than spinning masked
                            # pad steps (which would report loss 0.0 and
                            # still nudge momentum-bearing optimizers)
                            break
                        if nxt is None:
                            # this rank ran dry (dropped nulls, pending
                            # filters); keep gang lockstep, masked pads
                            if feat_shape is None:
                                if self.model.input_shape is None:
                                    raise ValueError(
                                        "rank received no data and the "
                                        "model records no input_shape to "
                                        "pad with; use more partitions "
                                        "than processes"
                                    )
                                feat_shape = tuple(self.model.input_shape)
                            # pad dtype MUST match the live feed's: in a
                            # gang, a lone f32 pad against uint8 image
                            # batches would be a different program on this
                            # rank than on the others (SPMD mismatch)
                            pad_dtype = (
                                np.uint8
                                if self.isDefined("targetHeight")
                                else np.float32
                            )
                            hx = np.zeros((0, *feat_shape), pad_dtype)
                            hy = np.zeros((0,), np.int32)
                        else:
                            hx, hy = nxt
                            feat_shape = tuple(hx.shape[1:])
                        metrics = run_step(
                            stage_local(
                                pad_rows(hx, hy, per_host_batch),
                                global_batch,
                            )
                        )
                finally:
                    gen.close()
            else:
                rng.shuffle(order)
                for start in range(0, n, global_batch):
                    idx = order[start : start + global_batch]
                    (bx, by), mask = pad_batch_to_multiple(
                        (x[idx], y[idx]), pad_unit
                    )
                    metrics = run_step(
                        stage_batch((bx, by, mask.astype(np.float32)))
                    )
            if not epoch_steps:
                # metadata said there were rows, decode dropped them all
                # (nulls / pending filters): same contract as the n==0 case
                raise ValueError(
                    "No training data: every row was null or undecodable"
                )
            # float() blocks on the last step's loss; every earlier step
            # is ordered before it through the state dependency, so this
            # one sync closes the whole epoch. mean_step_time_s is epoch
            # wall / steps — the pipelined-throughput definition, which
            # INCLUDES host decode/staging (pre-async-dispatch versions
            # reported the blocked device-step mean that excluded
            # inter-step host work; "timing" flags the semantics for
            # anyone comparing across versions).
            loss_val = float(metrics["loss"])
            epoch_time = time.perf_counter() - epoch_t0
            history.append(
                {
                    "epoch": epoch,
                    "loss": loss_val,
                    "steps": epoch_steps,
                    "mean_step_time_s": epoch_time / epoch_steps,
                    "epoch_time_s": epoch_time,
                    "timing": "epoch_wall_over_steps",
                }
            )
        if model_dir:
            self._save(model_dir, state)

        trained = self.model.with_params(state.params)
        geom = (
            (
                self.getOrDefault("targetHeight"),
                self.getOrDefault("targetWidth"),
            )
            if self.isDefined("targetHeight")
            else None
        )
        return DataParallelModel(
            trained,
            inputCol=self.getInputCol(),
            outputCol=self.getOutputCol()
            if self.isDefined("outputCol")
            else "prediction",
            batchSize=self.getBatchSize(),
            image_geometry=geom,
            history=history,
        )


# Reference-compatible alias (the Horovod-backed estimator capability)
HorovodEstimator = DataParallelEstimator
