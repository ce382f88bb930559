"""ResNet (CIFAR and ImageNet variants) in Flax, NHWC.

Capability match for the reference ``networks/resnet.py:13-180``
(torchvision-style pre-2016 ResNet): BasicBlock/Bottleneck, CIFAR stem
(3x3, 16 planes, 3 stages) for depth 6n+2 / 9n+2, ImageNet stem
(7x7/2 + maxpool 3x3/2, 4 stages) for depths {18, 34, 50, 101, 152,
200}.  He-normal fan-out conv init, BN gamma=1/beta=0
(``resnet.py:126-132``); downsample shortcut is 1x1-conv + BN.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from fast_autoaugment_tpu.core import scopes
from fast_autoaugment_tpu.models.layers import BatchNorm, global_avg_pool, he_normal_fanout

__all__ = ["ResNet", "IMAGENET_LAYERS"]

IMAGENET_LAYERS = {
    18: ("basic", (2, 2, 2, 2)),
    34: ("basic", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
    152: ("bottleneck", (3, 8, 36, 3)),
    200: ("bottleneck", (3, 24, 36, 3)),
}


def _conv(features, kernel, stride, dtype=None, name=None):
    return nn.Conv(
        features,
        (kernel, kernel),
        strides=(stride, stride),
        padding=[(kernel // 2, kernel // 2)] * 2,
        use_bias=False,
        kernel_init=he_normal_fanout,
        dtype=dtype,
        name=name,
    )


class BasicBlock(nn.Module):
    features: int
    stride: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool):
        residual = x
        out = _conv(self.features, 3, self.stride, dtype=self.dtype, name="conv1")(x)
        out = BatchNorm(name="bn1")(out, train)
        out = nn.relu(out)
        out = _conv(self.features, 3, 1, dtype=self.dtype, name="conv2")(out)
        out = BatchNorm(name="bn2")(out, train)
        if self.stride != 1 or x.shape[-1] != self.features:
            residual = _conv(self.features, 1, self.stride, dtype=self.dtype,
                             name="downsample_conv")(x)
            residual = BatchNorm(name="downsample_bn")(residual, train)
        return nn.relu(out + residual)


class Bottleneck(nn.Module):
    features: int  # bottleneck width; output is 4x
    stride: int = 1
    expansion: int = 4
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool):
        out_features = self.features * self.expansion
        residual = x
        out = _conv(self.features, 1, 1, dtype=self.dtype, name="conv1")(x)
        out = nn.relu(BatchNorm(name="bn1")(out, train))
        out = _conv(self.features, 3, self.stride, dtype=self.dtype, name="conv2")(out)
        out = nn.relu(BatchNorm(name="bn2")(out, train))
        out = _conv(out_features, 1, 1, dtype=self.dtype, name="conv3")(out)
        out = BatchNorm(name="bn3")(out, train)
        if self.stride != 1 or x.shape[-1] != out_features:
            residual = _conv(out_features, 1, self.stride, dtype=self.dtype,
                             name="downsample_conv")(x)
            residual = BatchNorm(name="downsample_bn")(residual, train)
        return nn.relu(out + residual)


class ResNet(nn.Module):
    """dataset='cifar' (depth 6n+2 basic / 9n+2 bottleneck) or 'imagenet'."""

    dataset: str
    depth: int
    num_classes: int
    bottleneck: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        if self.dataset.startswith("cifar") or self.dataset in ("svhn",):
            if self.bottleneck:
                n = (self.depth - 2) // 9
                block, widths = Bottleneck, (16, 32, 64)
            else:
                n = (self.depth - 2) // 6
                block, widths = BasicBlock, (16, 32, 64)
            out = _conv(16, 3, 1, dtype=self.dtype, name="conv1")(x)
            out = nn.relu(BatchNorm(name="bn1")(out, train))
            for stage, width in enumerate(widths):
                for i in range(n):
                    stride = 2 if (stage > 0 and i == 0) else 1
                    out = block(width, stride, dtype=self.dtype,
                                name=f"layer{stage + 1}_{i}")(out, train)
        elif self.dataset == "imagenet":
            kind, counts = IMAGENET_LAYERS[self.depth]
            block = BasicBlock if kind == "basic" else Bottleneck
            with jax.named_scope(scopes.RESNET_STEM):
                out = nn.Conv(
                    64, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
                    use_bias=False, kernel_init=he_normal_fanout,
                    dtype=self.dtype, name="conv1",
                )(x)
                out = nn.relu(BatchNorm(name="bn1")(out, train))
                out = nn.max_pool(out, (3, 3), strides=(2, 2),
                                  padding=[(1, 1), (1, 1)])
            for stage, (width, count) in enumerate(zip((64, 128, 256, 512), counts)):
                for i in range(count):
                    stride = 2 if (stage > 0 and i == 0) else 1
                    out = block(width, stride, dtype=self.dtype,
                                name=f"layer{stage + 1}_{i}")(out, train)
        else:
            raise ValueError(f"unknown dataset {self.dataset!r}")

        out = global_avg_pool(out).astype(jnp.float32)
        return nn.Dense(self.num_classes, name="fc")(out)
