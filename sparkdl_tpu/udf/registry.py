"""Model-as-UDF registry and one-call deployment.

Reference analogues (SURVEY.md §3 #7, #14): ``makeGraphUDF`` registered a
frozen TF graph as a Spark SQL UDF via TensorFrames' JVM catalog;
``registerKerasImageUDF`` composed loader + model + flattener and
registered the result under a SQL name. Without a JVM catalog, the
TPU-native registry is an in-process function catalog: a name maps to a
column-level UDF (a ModelFunction plus its host-side batching recipe), and
``DataFrame.selectExpr``-style application (``apply_udf`` /
``callUDF``) runs it over any DataFrame column — same composition, no SQL
parser dependency. The registry is process-global, like a SQL function
catalog, and thread-safe.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import numpy as np

from sparkdl_tpu.dataframe import DataFrame
from sparkdl_tpu.runtime import knobs
from sparkdl_tpu.utils.metrics import metrics


@dataclass
class RegisteredUDF:
    name: str
    # fn(partition_cells: list) -> list of output cells (None-preserving)
    partition_fn: Callable[[list], list]
    doc: str = ""
    # vectorized surface: same cells->cells contract, but dispatching
    # through run_batched_shared / the DeviceFeeder so concurrent
    # partition scans coalesce into shared device batches. None for
    # plain Python UDFs — they keep the partition_fn path always.
    batch_fn: Optional[Callable[[list], list]] = None

    @property
    def vectorized(self) -> bool:
        return self.batch_fn is not None


_registry: Dict[str, RegisteredUDF] = {}
_lock = threading.Lock()


def sql_vectorize_enabled() -> bool:
    """SPARKDL_SQL_VECTORIZE gates the SQL optimizer arm (default ON):
    batched catalog-UDF dispatch through the shared feeder plus the
    planner's projection/predicate pushdown; 0/off restores the legacy
    row-path planner — the A/B arm and the escape hatch."""
    return knobs.get_flag("SPARKDL_SQL_VECTORIZE")


class _CountingDeviceFn:
    """Registration-time wrapper around a model UDF's device function for
    the vectorized arm: counts device dispatches as ``sql.udf.batches``
    (under feeder coalescing that is one count per GLOBAL batch, which is
    how the smoke proves batches < partitions). Created once per
    registration so its identity is stable — the feeder registry keys
    producers by ``id(device_fn)``, and a per-query wrapper would defeat
    feeder reuse. Every feed-protocol attribute the engine probes
    (``stage_put``, ``single_stream``, ``batch_multiplier``, ``nchw``,
    ``host_prepare``) forwards to the wrapped function."""

    def __init__(self, fn):
        self._fn = fn

    def __call__(self, batch):
        metrics.inc("sql.udf.batches")
        return self._fn(batch)

    def __getattr__(self, name):
        return getattr(self._fn, name)


def register(
    name: str,
    partition_fn: Callable[[list], list],
    doc: str = "",
    batch_fn: Optional[Callable[[list], list]] = None,
) -> None:
    with _lock:
        _registry[name] = RegisteredUDF(name, partition_fn, doc, batch_fn)


def unregister(name: str) -> None:
    with _lock:
        _registry.pop(name, None)


def get(name: str) -> RegisteredUDF:
    with _lock:
        if name not in _registry:
            raise KeyError(
                f"No UDF registered under {name!r}; registered: "
                f"{sorted(_registry)}"
            )
        return _registry[name]


def list_udfs() -> list:
    with _lock:
        return sorted(_registry)


def apply_udf(
    name: str, dataset: DataFrame, inputCol: str, outputCol: str
) -> DataFrame:
    """SELECT <name>(<inputCol>) AS <outputCol> — partition-vectorized.

    Model UDFs carrying a ``batch_fn`` dispatch through the shared
    feeder when the SQL optimizer arm is on (``SPARKDL_SQL_VECTORIZE``);
    plain Python UDFs — and the knob-off legacy arm — run the original
    per-partition ``partition_fn`` unchanged."""
    udf = get(name)
    vectorized = udf.batch_fn is not None and sql_vectorize_enabled()
    metrics.gauge("sql.udf.vectorized", 1.0 if vectorized else 0.0)
    fn = udf.batch_fn if vectorized else udf.partition_fn

    def op(part):
        return {outputCol: fn(part[inputCol])}

    return dataset.withColumnPartition(outputCol, op)


# `callUDF(df, "name", ...)` ergonomics, mirroring spark.sql callUDF
callUDF = apply_udf


def registerModelUDF(
    udfName: str,
    model_function,
    to_batch: Optional[Callable] = None,
    batch_size: int = 32,
    doc: str = "",
) -> None:
    """Register any ModelFunction as a UDF over array cells."""
    from sparkdl_tpu.transformers.execution import (
        arrays_to_batch,
        model_device_fn,
        run_batched_shared,
    )

    device_fn = model_device_fn(model_function)
    tb = to_batch or arrays_to_batch

    def partition_fn(cells):
        return run_batched_shared(
            cells, to_batch=tb, device_fn=device_fn, batch_size=batch_size
        )

    vec_device_fn = _CountingDeviceFn(device_fn)

    def batch_fn(cells):
        metrics.inc(
            "sql.udf.batch_rows", sum(c is not None for c in cells)
        )
        return run_batched_shared(
            cells,
            to_batch=tb,
            device_fn=vec_device_fn,
            batch_size=batch_size,
        )

    register(udfName, partition_fn, doc=doc, batch_fn=batch_fn)


def makeGraphUDF(
    graph,
    udfName: str,
    outputs=None,
    blocked: bool = True,
    batch_size: int = 32,
) -> None:
    """Reference-compatible alias (upstream graph/tensorframes_udf.py
    ``makeGraphUDF(graph, udfName, outputs, blocked)``, SURVEY.md §3 #7):
    register a graph function as a SQL-callable UDF. ``graph`` is a
    ModelFunction (the GraphFunction analogue); ``outputs`` is accepted
    for signature parity but unused — a ModelFunction has exactly one
    output already; execution is always batched ("blocked")."""
    if not blocked:
        raise ValueError(
            "Row-at-a-time UDF execution (blocked=False) is not "
            "supported: batches are the TPU execution unit"
        )
    registerModelUDF(udfName, graph, batch_size=batch_size)


def registerImageUDF(
    udfName: str,
    kerasModelOrFile,
    preprocessor: Optional[Callable[[np.ndarray], np.ndarray]] = None,
    height: Optional[int] = None,
    width: Optional[int] = None,
    batch_size: int = 32,
) -> None:
    """One-call deployment of an image model as a named UDF over an
    image-struct column (reference: ``registerKerasImageUDF(udfName,
    keras_model_or_file, preprocessor)`` — python/sparkdl/udf/
    keras_image_model.py).

    ``kerasModelOrFile``: a Keras model, a model file path, a registry
    model name (e.g. "MobileNetV2"), or a ModelFunction.
    ``preprocessor``: optional host-side fn(HWC uint8 RGB) -> HWC float
    applied per image before batching (the loader-graph analogue).
    """
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.graph.ingest import ModelIngest
    from sparkdl_tpu.graph.pieces import (
        build_flattener,
        build_image_converter,
        image_structs_to_batch,
    )
    from sparkdl_tpu.transformers.execution import (
        flat_device_fn,
        model_device_fn,
        run_batched_shared,
    )

    preprocessing = "none"
    if isinstance(kerasModelOrFile, ModelFunction):
        mf = kerasModelOrFile
    elif isinstance(kerasModelOrFile, str) and (
        kerasModelOrFile.endswith((".keras", ".h5", ".hdf5"))
    ):
        mf = ModelIngest.from_keras_file(kerasModelOrFile)
    elif isinstance(kerasModelOrFile, str):
        from sparkdl_tpu.models.registry import get_image_model

        spec = get_image_model(kerasModelOrFile)
        mf = spec.model_function(mode="probabilities")
        preprocessing = spec.preprocessing
        height, width = height or spec.height, width or spec.width
    else:
        mf = ModelIngest.from_keras(kerasModelOrFile)

    if height is None or width is None:
        if mf.input_shape and len(mf.input_shape) == 3:
            height, width = mf.input_shape[0], mf.input_shape[1]
        else:
            raise ValueError("height/width required for this model")

    if preprocessor is not None:
        # User preprocessing replaces the converter: host stage emits the
        # final float batch (preprocessor sees HWC uint8 RGB per image).
        # Image-shaped outputs ride the flat channel-major feed (the
        # NHWC minor-dim transfer cliff applies to floats too); other
        # output geometries keep the plain jit.
        pre_pipeline = mf.and_then(build_flattener())
        if mf.input_shape is not None and len(mf.input_shape) == 3:
            device_fn = flat_device_fn(
                pre_pipeline, (batch_size, *map(int, mf.input_shape))
            )
        else:
            device_fn = model_device_fn(mf, jitted=pre_pipeline.jitted())

        def to_batch(chunk):
            batch, mask = image_structs_to_batch(
                chunk, height=height, width=width
            )
            processed = np.stack(
                [
                    np.asarray(
                        preprocessor(batch[i][..., ::-1]), dtype=np.float32
                    )
                    for i in range(batch.shape[0])
                ]
            )
            return processed, mask

    else:
        converter = build_image_converter(
            channel_order_in="BGR", preprocessing=preprocessing
        )
        # Flat channel-major feed, same as DeepImageFeaturizer: a plain
        # 4-D NHWC uint8 transfer lane-pads the 3-wide minor dim on
        # device; the flat chw buffer keeps every transfer allocation
        # ~1x the batch bytes.
        pipeline_mf = converter.and_then(mf).and_then(build_flattener())
        device_fn = flat_device_fn(
            pipeline_mf, (batch_size, height, width, 3)
        )

        def to_batch(chunk):
            return image_structs_to_batch(
                chunk,
                height=height,
                width=width,
                chw=getattr(device_fn, "nchw", False),
            )

    def partition_fn(cells):
        return run_batched_shared(
            cells,
            to_batch=to_batch,
            device_fn=device_fn,
            batch_size=batch_size,
        )

    vec_device_fn = _CountingDeviceFn(device_fn)

    def batch_fn(cells):
        metrics.inc(
            "sql.udf.batch_rows", sum(c is not None for c in cells)
        )
        return run_batched_shared(
            cells,
            to_batch=to_batch,
            device_fn=vec_device_fn,
            batch_size=batch_size,
        )

    register(
        udfName,
        partition_fn,
        doc=f"image UDF over {getattr(mf, 'name', 'model')}",
        batch_fn=batch_fn,
    )


# Reference-compatible alias
registerKerasImageUDF = registerImageUDF
