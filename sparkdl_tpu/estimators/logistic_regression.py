"""LogisticRegression head over feature-vector columns.

The reference's north-star pipeline chains DeepImageFeaturizer with Spark
MLlib's LogisticRegression (BASELINE config[0]; SURVEY.md §4.1 "downstream:
LogisticRegression on feature column"). MLlib isn't present here, so the
head is in-tree: a multinomial logistic regression trained with optax on
the device mesh — the train step is the same shard_map+psum SPMD unit the
big trainer uses, so the whole pipeline (featurize -> fit head) runs on
TPU end-to-end with no third framework.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sparkdl_tpu.dataframe import DataFrame
from sparkdl_tpu.parallel import (
    create_train_state,
    make_data_parallel_step,
    make_mesh,
    pad_batch_to_multiple,
)
from sparkdl_tpu.params import (
    HasBatchSize,
    HasLabelCol,
    Param,
    TypeConverters,
    keyword_only,
)
from sparkdl_tpu.pipeline import Estimator, Model
from sparkdl_tpu.transformers.execution import (
    arrays_to_batch,
    run_batched_shared,
)


class LogisticRegressionModel(Model):
    def __init__(
        self, w: np.ndarray, b: np.ndarray, featuresCol: str,
        predictionCol: str, probabilityCol: Optional[str],
    ):
        super().__init__()
        self.w = jnp.asarray(w)
        self.b = jnp.asarray(b)
        self._features_col = featuresCol
        self._prediction_col = predictionCol
        self._probability_col = probabilityCol
        self._jit = jax.jit(
            lambda x: jax.nn.softmax(x @ self.w + self.b, axis=-1)
        )

    @property
    def numClasses(self) -> int:
        return int(self.b.shape[0])

    # -- persistence (MLlib LogisticRegressionModel.save/load parity) --------

    def _save_extra(self, path):
        import os

        np.savez(
            os.path.join(path, "model.npz"),
            w=np.asarray(self.w),
            b=np.asarray(self.b),
        )
        return {
            "featuresCol": self._features_col,
            "predictionCol": self._prediction_col,
            "probabilityCol": self._probability_col,
        }

    def _load_extra(self, path, meta):
        import os

        blob = np.load(os.path.join(path, "model.npz"))
        extra = meta["extra"]
        self.w = jnp.asarray(blob["w"])
        self.b = jnp.asarray(blob["b"])
        self._features_col = extra["featuresCol"]
        self._prediction_col = extra["predictionCol"]
        self._probability_col = extra["probabilityCol"]
        self._jit = jax.jit(
            lambda x: jax.nn.softmax(x @ self.w + self.b, axis=-1)
        )

    def _transform(self, dataset: DataFrame) -> DataFrame:
        f_col = self._features_col
        p_col = self._prediction_col
        prob_col = self._probability_col

        def op(part):
            probs = run_batched_shared(
                part[f_col],
                to_batch=arrays_to_batch,
                device_fn=self._jit,
                batch_size=256,
            )
            out = dict(part)
            out[p_col] = [
                None if p is None else int(np.argmax(p)) for p in probs
            ]
            if prob_col:
                out[prob_col] = probs
            return out

        new_cols = dataset.columns + [p_col] + ([prob_col] if prob_col else [])
        return dataset.mapPartitions(op, new_cols)


class LogisticRegression(Estimator, HasLabelCol, HasBatchSize):
    featuresCol = Param(
        None, "featuresCol", "feature vector column", TypeConverters.toString
    )
    predictionCol = Param(
        None, "predictionCol", "predicted class index column",
        TypeConverters.toString,
    )
    probabilityCol = Param(
        None, "probabilityCol", "class probability column (optional)",
        TypeConverters.toString,
    )
    maxIter = Param(None, "maxIter", "training epochs", TypeConverters.toInt)
    stepSize = Param(None, "stepSize", "learning rate", TypeConverters.toFloat)
    regParam = Param(
        None, "regParam", "L2 regularization strength", TypeConverters.toFloat
    )
    numClasses = Param(
        None, "numClasses", "number of classes (inferred if unset)",
        TypeConverters.toInt,
    )
    seed = Param(None, "seed", "init seed", TypeConverters.toInt)

    @keyword_only
    def __init__(
        self,
        featuresCol: str = None,
        labelCol: str = None,
        predictionCol: str = None,
        probabilityCol: str = None,
        maxIter: int = None,
        stepSize: float = None,
        regParam: float = None,
        batchSize: int = None,
        numClasses: int = None,
        seed: int = None,
    ):
        super().__init__()
        self._setDefault(
            featuresCol="features",
            labelCol="label",
            predictionCol="prediction",
            maxIter=100,
            stepSize=0.05,
            regParam=1e-4,
            batchSize=512,
            seed=0,
        )
        self._set(**self._input_kwargs)

    def _fit(self, dataset: DataFrame) -> LogisticRegressionModel:
        cols = dataset.select(
            self.getOrDefault("featuresCol"), self.getLabelCol()
        ).collectColumns()
        feats = [f for f in cols[self.getOrDefault("featuresCol")]]
        labels = cols[self.getLabelCol()]
        keep = [i for i, (f, l) in enumerate(zip(feats, labels))
                if f is not None and l is not None]
        x = np.stack([np.asarray(feats[i], np.float32).ravel() for i in keep])
        y = np.asarray([int(labels[i]) for i in keep], np.int32)
        n, d = x.shape
        k = (
            self.getOrDefault("numClasses")
            if self.isDefined("numClasses")
            else int(y.max()) + 1
        )

        reg = self.getOrDefault("regParam")

        def loss_fn(params, batch):
            bx, by, bm = batch
            logits = bx @ params["w"] + params["b"]
            per_ex = optax.softmax_cross_entropy_with_integer_labels(
                logits, by
            )
            # masked mean: padding rows contribute zero
            loss = jnp.sum(per_ex * bm) / jnp.maximum(jnp.sum(bm), 1.0)
            return loss + reg * jnp.sum(params["w"] ** 2)

        rng = np.random.default_rng(self.getOrDefault("seed"))
        params = {
            "w": jnp.asarray(
                rng.normal(scale=0.01, size=(d, k)), jnp.float32
            ),
            "b": jnp.zeros((k,), jnp.float32),
        }
        optimizer = optax.adam(self.getOrDefault("stepSize"))
        mesh = make_mesh()
        n_dev = mesh.devices.size
        step_fn = make_data_parallel_step(loss_fn, optimizer, mesh)
        state = create_train_state(params, optimizer)

        batch_size = min(self.getBatchSize(), max(n_dev, n))
        epochs = self.getOrDefault("maxIter")
        order = np.arange(n)
        shuffle_rng = np.random.default_rng(self.getOrDefault("seed") + 1)
        for _ in range(epochs):
            shuffle_rng.shuffle(order)
            for start in range(0, n, batch_size):
                idx = order[start : start + batch_size]
                (bx, by), mask = pad_batch_to_multiple(
                    (x[idx], y[idx]), max(n_dev, 1)
                )
                state, _ = step_fn(
                    state, (bx, by, mask.astype(np.float32))
                )

        w = np.asarray(state.params["w"])
        b = np.asarray(state.params["b"])
        return LogisticRegressionModel(
            w,
            b,
            featuresCol=self.getOrDefault("featuresCol"),
            predictionCol=self.getOrDefault("predictionCol"),
            probabilityCol=self.getOrDefault("probabilityCol")
            if self.isDefined("probabilityCol")
            else None,
        )
