"""ResNet family (v1.5), TPU-first flax implementation.

The reference benchmarks Horovod with ResNet-50/101 training scripts
(horovod `examples/` + `docs/benchmarks.rst`; SURVEY.md §6) — those scripts
are torch/TF models fed through ``hvd.DistributedOptimizer``.  This module is
the equivalent flagship model for this framework, written for the MXU:

- NHWC layout (XLA:TPU's native conv layout) with channel counts that are
  multiples of 128 in the deep stages, so convs tile cleanly onto the
  128x128 systolic array;
- bfloat16 activations / fp32 parameters (the standard TPU mixed-precision
  recipe) — pass ``dtype=jnp.bfloat16``;
- BatchNorm with optional cross-replica statistics: pass ``bn_axis_name`` to
  sync batch statistics over the data-parallel mesh axis via psum (the
  TPU-native equivalent of the reference's horovod/torch/sync_batch_norm.py).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class ResNetBlock(nn.Module):
    """Basic residual block (ResNet-18/34)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("hvd_block"):
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
    """Bottleneck residual block (ResNet-50/101/152), v1.5 variant:
    stride lives on the 3x3 conv, which is what the reference benchmark
    models use and what keeps the MXU busy."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("hvd_block"):
            residual = x
            y = self.conv(self.filters, (1, 1))(x)
            y = self.norm()(y)
            y = self.act(y)
            y = self.conv(self.filters, (3, 3), self.strides)(y)
            y = self.norm()(y)
            y = self.act(y)
            y = self.conv(self.filters * 4, (1, 1))(y)
            # Zero-init the last BN scale so blocks start as identity — the
            # standard large-batch trick (He et al.; also used by the Horovod
            # paper's training recipes).
            y = self.norm(scale_init=nn.initializers.zeros_init())(y)
            if residual.shape != y.shape:
                residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                     name="conv_proj")(residual)
                residual = self.norm(name="norm_proj")(residual)
            return self.act(residual + y)


class ResNet(nn.Module):
    """ResNet v1.5 over NHWC images.

    Args:
      stage_sizes: blocks per stage, e.g. ``[3, 4, 6, 3]`` for ResNet-50.
      block_cls: :class:`ResNetBlock` or :class:`BottleneckResNetBlock`.
      num_classes: classifier width.
      dtype: activation dtype (``jnp.bfloat16`` on TPU).
      bn_axis_name: mesh axis for cross-replica (sync) BatchNorm, or None
        for per-replica statistics.
    """

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.float32
    act: Callable = nn.relu
    bn_axis_name: Optional[str] = None
    bn_momentum: float = 0.9
    bn_epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(
            nn.Conv, use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.variance_scaling(
                2.0, "fan_out", "normal"),
        )
        norm = functools.partial(
            nn.BatchNorm, use_running_average=not train,
            momentum=self.bn_momentum, epsilon=self.bn_epsilon,
            dtype=self.dtype, axis_name=self.bn_axis_name,
        )
        with jax.named_scope("hvd_stem"):
            x = jnp.asarray(x, self.dtype)
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
            x = norm(name="bn_init")(x)
            x = self.act(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2),
                            padding=((1, 1), (1, 1)))
        for i, block_size in enumerate(self.stage_sizes):
            for j in range(block_size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    self.num_filters * 2 ** i, strides=strides,
                    conv=conv, norm=norm, act=self.act,
                )(x)
        with jax.named_scope("hvd_head"):
            x = jnp.mean(x, axis=(1, 2))
            # Classifier head in fp32 for numerically stable softmax/loss.
            return nn.Dense(self.num_classes, dtype=jnp.float32,
                            name="head")(x.astype(jnp.float32))


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

# Tiny variant for tests / CPU dry-runs: same topology, 1/4 width.
ResNetTiny = functools.partial(ResNet, stage_sizes=[1, 1, 1, 1],
                               block_cls=ResNetBlock, num_filters=16)
