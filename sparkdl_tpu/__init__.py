"""sparkdl_tpu — TPU-native Deep Learning Pipelines.

A brand-new framework with the capabilities of Deep Learning Pipelines for
Apache Spark (reference: MrBago/spark-deep-learning — see SURVEY.md), built
idiomatically on JAX/XLA for TPU:

- partitioned Arrow-interoperable DataFrames with an ImageSchema-compatible
  image struct column (sparkdl_tpu.dataframe, sparkdl_tpu.image)
- pure jitted "ModelFunctions" replace frozen TF GraphDefs
  (sparkdl_tpu.graph)
- pipeline Transformers/Estimators with spark.ml Param semantics
  (sparkdl_tpu.params, sparkdl_tpu.pipeline, sparkdl_tpu.transformers)
- named pretrained-architecture featurizers (DeepImageFeaturizer et al.)
  over a flax-native model zoo (sparkdl_tpu.models)
- one-call model-as-UDF registration (sparkdl_tpu.udf)
- data-parallel training via XLA collectives over a device mesh, replacing
  Horovod/NCCL (sparkdl_tpu.parallel, sparkdl_tpu.estimators)
"""

import os as _os

# Keras 3 must use the JAX backend so ingested Keras models compile via XLA
# on TPU. Must be set before any `import keras` anywhere in the process.
_os.environ.setdefault("KERAS_BACKEND", "jax")

# The persistent compile cache has to be placed before the first compile
# of the process, and any submodule below may be the one that compiles.
from sparkdl_tpu.runtime import compile_cache as _compile_cache

_compile_cache.place()

__version__ = "0.1.0"

from sparkdl_tpu.dataframe import DataFrame, Row
from sparkdl_tpu.image import imageIO

__all__ = ["DataFrame", "Row", "imageIO", "__version__"]


def __getattr__(name):
    """Lazy re-exports of the public API (keeps `import sparkdl_tpu` light —
    jax/model imports happen only when the symbols are touched)."""
    from importlib import import_module

    lazy = {
        # graph layer
        "ModelFunction": "sparkdl_tpu.graph",
        "GraphFunction": "sparkdl_tpu.graph",
        "IsolatedSession": "sparkdl_tpu.graph",
        "ModelIngest": "sparkdl_tpu.graph",
        "TFInputGraph": "sparkdl_tpu.graph",
        "imageInputPlaceholder": "sparkdl_tpu.graph",
        # pipeline layer
        "Transformer": "sparkdl_tpu.pipeline",
        "Estimator": "sparkdl_tpu.pipeline",
        "Pipeline": "sparkdl_tpu.pipeline",
        "PipelineModel": "sparkdl_tpu.pipeline",
        # transformers
        "DeepImageFeaturizer": "sparkdl_tpu.transformers",
        "DeepImagePredictor": "sparkdl_tpu.transformers",
        "ImageModelTransformer": "sparkdl_tpu.transformers",
        "TFImageTransformer": "sparkdl_tpu.transformers",
        "ModelTransformer": "sparkdl_tpu.transformers",
        "TFTransformer": "sparkdl_tpu.transformers",
        "KerasTransformer": "sparkdl_tpu.transformers",
        "KerasImageFileTransformer": "sparkdl_tpu.transformers",
        # estimators
        "KerasImageFileEstimator": "sparkdl_tpu.estimators",
        "ImageFileEstimator": "sparkdl_tpu.estimators",
        "DataParallelEstimator": "sparkdl_tpu.estimators",
        "HorovodEstimator": "sparkdl_tpu.estimators",
        "LogisticRegression": "sparkdl_tpu.estimators",
        # udf
        "registerImageUDF": "sparkdl_tpu.udf",
        "registerKerasImageUDF": "sparkdl_tpu.udf",
        "registerUDF": "sparkdl_tpu.udf",
        "makeGraphUDF": "sparkdl_tpu.udf",
        # tuning / evaluation
        "ParamGridBuilder": "sparkdl_tpu.tuning",
        "CrossValidator": "sparkdl_tpu.tuning",
        "CrossValidatorModel": "sparkdl_tpu.tuning",
        "TrainValidationSplit": "sparkdl_tpu.tuning",
        "TrainValidationSplitModel": "sparkdl_tpu.tuning",
        "Evaluator": "sparkdl_tpu.evaluation",
        "MulticlassClassificationEvaluator": "sparkdl_tpu.evaluation",
        "BinaryClassificationEvaluator": "sparkdl_tpu.evaluation",
        "RegressionEvaluator": "sparkdl_tpu.evaluation",
        # persistence
        "load": "sparkdl_tpu.persistence",
        # sql — note: the sql() *function* is NOT lazy-exported; the name
        # would collide with the sparkdl_tpu.sql submodule attribute and
        # become order-dependent. Use `from sparkdl_tpu import sql;
        # sql.sql(...)` or SQLContext.
        "SQLContext": "sparkdl_tpu.sql",
        "registerDataFrameAsTable": "sparkdl_tpu.sql",
        # column expressions (from sparkdl_tpu import functions as F)
        "Column": "sparkdl_tpu.dataframe.column",
        "col": "sparkdl_tpu.functions",
        "lit": "sparkdl_tpu.functions",
        "when": "sparkdl_tpu.functions",
        "Window": "sparkdl_tpu.dataframe.window",
        "WindowSpec": "sparkdl_tpu.dataframe.window",
        "SparkSession": "sparkdl_tpu.session",
    }
    if name in lazy:
        return getattr(import_module(lazy[name]), name)
    raise AttributeError(f"module 'sparkdl_tpu' has no attribute {name!r}")
