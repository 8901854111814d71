"""ResNet-50 (v1.5, NHWC) — the north-star throughput model.

The reference has no ResNet, but BASELINE.json sets ResNet-50 samples/sec/chip
as the build's headline metric, so it lives in the zoo alongside the parity
models.  Bottleneck blocks with the stride on the 3x3 conv (v1.5), bfloat16
compute via ``dtype``, float32 BN statistics, zero-init of the final BN scale
in each block (standard large-batch trick).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

conv_init = nn.initializers.variance_scaling(2.0, "fan_out", "truncated_normal")


class BottleneckBlock(nn.Module):
    filters: int
    stride: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype)
        conv = partial(nn.Conv, use_bias=False, kernel_init=conv_init,
                       dtype=self.dtype)
        residual = x
        y = conv(self.filters, (1, 1))(x)
        y = nn.relu(norm()(y))
        y = conv(self.filters, (3, 3), strides=(self.stride, self.stride),
                 padding=1)(y)
        y = nn.relu(norm()(y))
        y = conv(self.filters * 4, (1, 1))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters * 4, (1, 1),
                            strides=(self.stride, self.stride))(residual)
            residual = norm()(residual)
        return nn.relu(y + residual)


class SpaceToDepthStem(nn.Module):
    """The 7x7/2 stem conv, computed in space-to-depth form (MXU-friendly).

    The standard stem convolves a 3-channel 224x224 image with a 7x7 stride-2
    kernel — on the TPU that contraction (7*7*3 = 147) runs the MXU at ~4%
    utilisation and the f32 image is the single largest tensor the step reads
    (no cell times a CNN: its share of the step is not measured).
    Rewriting it over a 2x2 space-to-depth view of the image — input
    [N,224,224,3] -> [N,112,112,12], kernel [7,7,3,64] zero-padded to 8x8 and
    regrouped to [4,4,12,64], stride 1 — computes the *identical* function
    (verified to exact equality in tests/test_resnet.py) with 4x fewer,
    denser MXU passes.

    The parameter keeps the canonical [7,7,3,64] shape — porting weights
    to/from a standard stem is a value copy (note the param *path* differs:
    ``SpaceToDepthStem_0/kernel`` vs ``Conv_0/kernel``, so checkpoints from
    a ``s2d_stem=False`` model need that one-key rename).  The pad+regroup
    is a constant-time transform inside the forward pass and gradients flow
    through it to the 7x7 weights.
    """
    features: int = 64
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        n, h, w, c = x.shape
        kernel = self.param("kernel", conv_init, (7, 7, c, self.features),
                            jnp.float32)
        # zero-pad the taps to an 8x8 window (offset -4..3 about each output
        # pixel: original offsets -3..3 plus one dead row/col at -4), then
        # regroup (2b+s) -> (block b, subpixel s) to match the s2d input.
        k8 = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
        k = k8.reshape(4, 2, 4, 2, c, self.features)
        k = k.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c, self.features)
        # space-to-depth: [N,H,W,C] -> [N,H/2,W/2,4C], channel = (s, t, c)
        xs = x.reshape(n, h // 2, 2, w // 2, 2, c)
        xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4 * c)
        dtype = self.dtype
        return jax.lax.conv_general_dilated(
            xs.astype(dtype), k.astype(dtype), window_strides=(1, 1),
            padding=((2, 1), (2, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


class ResNet(nn.Module):
    """``s2d_stem`` is **opt-in** (like PyramidNet's ``channel_align``): it
    renames the stem parameter path (``SpaceToDepthStem_0/kernel`` vs
    ``Conv_0/kernel``), so flipping it silently breaks restore of any
    snapshot taken with the other setting.  The default keeps the canonical
    checkpoint tree interchangeable with reference-format weight ports;
    ``examples/imagenet_resnet50.py`` enables it by flag."""
    stage_sizes: Sequence[int] = (3, 4, 6, 3)
    num_classes: int = 1000
    dtype: Any = jnp.float32
    s2d_stem: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        if self.s2d_stem and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
            x = SpaceToDepthStem(64, dtype=self.dtype)(x)
        else:
            x = nn.Conv(64, (7, 7), strides=(2, 2), padding=3, use_bias=False,
                        kernel_init=conv_init, dtype=self.dtype)(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                         epsilon=1e-5, dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                stride = 2 if i > 0 and j == 0 else 1
                x = BottleneckBlock(64 * 2 ** i, stride,
                                    dtype=self.dtype)(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)


ResNet50 = partial(ResNet, stage_sizes=(3, 4, 6, 3))


def resnet50(dtype=jnp.float32, num_classes: int = 1000,
             s2d_stem: bool = False) -> ResNet:
    return ResNet50(num_classes=num_classes, dtype=dtype, s2d_stem=s2d_stem)
