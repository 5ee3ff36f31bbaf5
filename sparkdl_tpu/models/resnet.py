"""Flax-native ResNet family (ResNet50/101/152).

Reference analogue: the named-model registry entries backed by
``keras.applications.ResNet50`` (python/sparkdl/transformers/
keras_applications.py, SURVEY.md §3 #8b). This is an original flax
implementation designed for TPU execution, not a port: NHWC layout
(XLA:TPU's native conv layout), parameterized compute dtype (bfloat16 on
the MXU by default, float32 params), and a stateless BatchNorm in
inference mode so the whole forward pass is a pure function.

Feature geometry matches the reference registry so downstream pipelines
are drop-in compatible: 224×224×3 input, 2048-d global-average-pooled
features, 1000-way logits head, 'caffe'-mode preprocessing.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int] = (1, 1)
    projection: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        bn = partial(
            nn.BatchNorm,
            use_running_average=True,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
        )
        residual = x
        y = conv(self.filters, (1, 1), strides=self.strides, name="conv1")(x)
        y = bn(name="bn1")(y)
        y = nn.relu(y)
        y = conv(self.filters, (3, 3), padding=[(1, 1), (1, 1)], name="conv2")(y)
        y = bn(name="bn2")(y)
        y = nn.relu(y)
        y = conv(self.filters * 4, (1, 1), name="conv3")(y)
        y = bn(name="bn3")(y)
        if self.projection:
            residual = conv(
                self.filters * 4, (1, 1), strides=self.strides, name="conv_proj"
            )(residual)
            residual = bn(name="bn_proj")(residual)
        return nn.relu(y + residual)


class _ScanBody(nn.Module):
    """lax.scan body: one identity bottleneck block, scanned over stacked
    per-block params."""

    filters: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, _):
        y = BottleneckBlock(
            filters=self.filters,
            strides=(1, 1),
            projection=False,
            dtype=self.dtype,
            name="block",
        )(x)
        return y, None


class ResNet(nn.Module):
    """Bottleneck ResNet. ``stage_sizes``: blocks per stage.

    ``__call__`` returns logits; ``features`` returns the pooled 2048-d
    penultimate representation (the DeepImageFeaturizer bottleneck output).

    ``scan_blocks``: compile each stage's run of identical identity blocks
    as ONE ``lax.scan`` over stacked params instead of unrolled HLO. Same
    math, much smaller executable (ResNet50: 16 block bodies -> 8), which
    cuts compile time and the program-load footprint. Param layout differs
    (identity blocks stacked on a leading axis), so keep it off when
    loading per-block weight files.
    """

    stage_sizes: Sequence[int]
    num_classes: int = 1000
    dtype: Any = jnp.float32
    scan_blocks: bool = False

    @nn.compact
    def __call__(self, x, features_only: bool = False):
        x = x.astype(self.dtype)
        x = nn.Conv(
            64, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
            use_bias=False, dtype=self.dtype, name="conv_init",
        )(x)
        x = nn.BatchNorm(
            use_running_average=True, momentum=0.9, epsilon=1e-5,
            dtype=self.dtype, name="bn_init",
        )(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for i, block_count in enumerate(self.stage_sizes):
            filters = 64 * 2**i
            strides = (2, 2) if i > 0 else (1, 1)
            x = BottleneckBlock(
                filters=filters,
                strides=strides,
                projection=True,
                dtype=self.dtype,
                name=f"stage{i+1}_block1",
            )(x)
            n_identity = block_count - 1
            if n_identity <= 0:
                continue
            if self.scan_blocks:
                scanned = nn.scan(
                    _ScanBody,
                    variable_axes={"params": 0, "batch_stats": 0},
                    split_rngs={"params": True},
                    length=n_identity,
                    metadata_params={nn.meta.PARTITION_NAME: None},
                )(filters=filters, dtype=self.dtype, name=f"stage{i+1}_rest")
                x, _ = scanned(x, None)
            else:
                for j in range(n_identity):
                    x = BottleneckBlock(
                        filters=filters,
                        strides=(1, 1),
                        projection=False,
                        dtype=self.dtype,
                        name=f"stage{i+1}_block{j+2}",
                    )(x)
        x = jnp.mean(x, axis=(1, 2))  # global average pool -> [N, 2048]
        if features_only:
            return x.astype(jnp.float32)
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x)
        return x.astype(jnp.float32)

    def features(self, x):
        return self(x, features_only=True)


def ResNet50(
    dtype=jnp.float32, num_classes: int = 1000, scan_blocks: bool = False
) -> ResNet:
    return ResNet(
        stage_sizes=[3, 4, 6, 3],
        num_classes=num_classes,
        dtype=dtype,
        scan_blocks=scan_blocks,
    )


def ResNet101(
    dtype=jnp.float32, num_classes: int = 1000, scan_blocks: bool = False
) -> ResNet:
    return ResNet(
        stage_sizes=[3, 4, 23, 3],
        num_classes=num_classes,
        dtype=dtype,
        scan_blocks=scan_blocks,
    )


def ResNet152(
    dtype=jnp.float32, num_classes: int = 1000, scan_blocks: bool = False
) -> ResNet:
    return ResNet(
        stage_sizes=[3, 8, 36, 3],
        num_classes=num_classes,
        dtype=dtype,
        scan_blocks=scan_blocks,
    )
