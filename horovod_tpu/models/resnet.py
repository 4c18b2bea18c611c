"""ResNet v1.5 in flax.linen — the benchmark flagship.

Reference context: Horovod's headline numbers are ResNet-50/101 synthetic
throughput via ``examples/pytorch_synthetic_benchmark.py`` and
``examples/tensorflow2_synthetic_benchmark.py`` (see ``docs/benchmarks.rst``).
This is a TPU-first implementation: NHWC layout (XLA-TPU native), bfloat16 compute
with float32 params/batch-stats, conv+BN+ReLU left for XLA to fuse onto the MXU.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class ResNetBlock(nn.Module):
    """Basic block (ResNet-18/34)."""
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class BottleneckResNetBlock(nn.Module):
    """Bottleneck block (ResNet-50/101/152)."""
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    act: Callable = nn.relu

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype,
                                 param_dtype=jnp.float32)
        norm = functools.partial(nn.BatchNorm, use_running_average=not train,
                                 momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                                 param_dtype=jnp.float32, axis_name=None)
        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                 name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = self.act(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_size in enumerate(self.stage_sizes):
            # flax runs each module under a scope of its name, so every
            # instruction's ``op_name`` already says ``<Block>_<n>/Conv_0``;
            # the stage groups the blocks of one width and resolution.
            with jax.named_scope(f"stage{i}"):
                for j in range(block_size):
                    strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                    x = self.block_cls(
                        self.num_filters * 2 ** i, conv=conv, norm=norm,
                        act=self.act, strides=strides)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


ResNet18 = functools.partial(ResNet, stage_sizes=[2, 2, 2, 2],
                             block_cls=ResNetBlock)
ResNet34 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=ResNetBlock)
ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BottleneckResNetBlock)
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3],
                              block_cls=BottleneckResNetBlock)
ResNet152 = functools.partial(ResNet, stage_sizes=[3, 8, 36, 3],
                              block_cls=BottleneckResNetBlock)
