"""Shake-Shake ResNet / ResNeXt (26-layer, 3-stage) in Flax, NHWC.

Capability match for the reference
``networks/shakeshake/shake_resnet.py:12-81`` and
``shake_resnext.py:12-84``: each block computes two parallel branches
mixed by the stochastic :func:`~fast_autoaugment_tpu.ops.shake.shake_shake`
op (per-sample forward alpha, fresh backward beta), with the two-path
1x1-conv downsampling ``Shortcut`` (second path shifted one pixel via
crop-and-pad before subsampling, reference ``shakeshake.py:29-48``).
He-normal fan-out init, zero linear bias (reference
``shake_resnet.py:55-63``).

Noise keys come from the ``'shake'`` RNG collection when ``train=True``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from fast_autoaugment_tpu.core import scopes
from fast_autoaugment_tpu.models.layers import BatchNorm, global_avg_pool, he_normal_fanout
from fast_autoaugment_tpu.ops.shake import (
    sample_shake_shake_noise,
    shake_shake,
    shake_shake_eval,
)

__all__ = ["ShakeResNet", "ShakeResNeXt"]


def _conv(features, kernel, stride=1, groups=1, bias=False, dtype=None, name=None):
    return nn.Conv(
        features,
        (kernel, kernel),
        strides=(stride, stride),
        padding=[(kernel // 2, kernel // 2)] * 2,
        feature_group_count=groups,
        use_bias=bias,
        kernel_init=he_normal_fanout,
        dtype=dtype,
        name=name,
    )


class Shortcut(nn.Module):
    """Two-path strided 1x1 shortcut (reference ``shakeshake.py:29-48``).

    Path 1 subsamples at even offsets; path 2 shifts by one pixel
    (crop top-left, zero-pad bottom-right) before subsampling, so the
    two paths see complementary pixels; halves concatenated then BN.
    """

    out_ch: int
    stride: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool):
        with jax.named_scope(scopes.SHAKE_SHORTCUT):
            h = nn.relu(x)
            s = self.stride
            h1 = h[:, ::s, ::s, :]
            h1 = _conv(self.out_ch // 2, 1, dtype=self.dtype, name="conv1")(h1)
            # F.pad(h, (-1, 1, -1, 1)): crop first row/col, pad one at the end
            h2 = jnp.pad(h[:, 1:, 1:, :], ((0, 0), (0, 1), (0, 1), (0, 0)))[:, ::s, ::s, :]
            h2 = _conv(self.out_ch // 2, 1, dtype=self.dtype, name="conv2")(h2)
            return BatchNorm(name="bn")(jnp.concatenate([h1, h2], axis=-1), train)


class _ShakeBranchBasic(nn.Module):
    """relu-conv3-BN-relu-conv3-BN branch (reference ``shake_resnet.py:29-36``)."""

    out_ch: int
    stride: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool):
        h = nn.relu(x)
        h = _conv(self.out_ch, 3, self.stride, dtype=self.dtype, name="conv1")(h)
        h = BatchNorm(name="bn1")(h, train)
        h = nn.relu(h)
        h = _conv(self.out_ch, 3, 1, dtype=self.dtype, name="conv2")(h)
        return BatchNorm(name="bn2")(h, train)


class _ShakeBranchBottleneck(nn.Module):
    """1x1 - grouped 3x3 - 1x1 branch (reference ``shake_resnext.py:29-38``)."""

    mid_ch: int
    out_ch: int
    cardinality: int
    stride: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool):
        h = _conv(self.mid_ch, 1, dtype=self.dtype, name="conv1")(x)
        h = nn.relu(BatchNorm(name="bn1")(h, train))
        h = _conv(self.mid_ch, 3, self.stride, groups=self.cardinality,
                  dtype=self.dtype, name="conv2")(h)
        h = nn.relu(BatchNorm(name="bn2")(h, train))
        h = _conv(self.out_ch, 1, dtype=self.dtype, name="conv3")(h)
        return BatchNorm(name="bn3")(h, train)


class _ShakeMix(nn.Module):
    """Mix two branches with shake-shake noise from the 'shake' RNG stream."""

    @nn.compact
    def __call__(self, h1, h2, train: bool):
        with jax.named_scope(scopes.SHAKE_MIX):
            if train:
                key = self.make_rng("shake")
                alpha, beta = sample_shake_shake_noise(key, h1.shape[0], h1.dtype)
                return shake_shake(h1, h2, alpha, beta)
            return shake_shake_eval(h1, h2)


class ShakeResNet(nn.Module):
    """Shake-Shake-26 2x{w_base}d (reference ``shake_resnet.py:39-81``)."""

    depth: int
    w_base: int
    num_classes: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        n_units = (self.depth - 2) // 6
        chs = (16, self.w_base, self.w_base * 2, self.w_base * 4)
        h = _conv(chs[0], 3, bias=True, dtype=self.dtype, name="c_in")(x)
        for stage in range(3):
            out_ch = chs[stage + 1]
            for i in range(n_units):
                stride = 2 if (stage > 0 and i == 0) else 1
                in_ch = h.shape[-1]
                h1 = _ShakeBranchBasic(out_ch, stride, dtype=self.dtype,
                                       name=f"s{stage}_{i}_branch1")(h, train)
                h2 = _ShakeBranchBasic(out_ch, stride, dtype=self.dtype,
                                       name=f"s{stage}_{i}_branch2")(h, train)
                mixed = _ShakeMix(name=f"s{stage}_{i}_mix")(h1, h2, train)
                if in_ch == out_ch:
                    h0 = h
                else:
                    h0 = Shortcut(out_ch, stride, dtype=self.dtype,
                                  name=f"s{stage}_{i}_shortcut")(h, train)
                h = mixed + h0
        h = nn.relu(h)
        h = global_avg_pool(h).astype(jnp.float32)
        return nn.Dense(self.num_classes, bias_init=nn.initializers.zeros, name="fc_out")(h)


class ShakeResNeXt(nn.Module):
    """Shake-Shake-26 2x{w_base}d ResNeXt, cardinality 4
    (reference ``shake_resnext.py:42-84``)."""

    depth: int
    w_base: int
    cardinality: int
    num_classes: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        n_units = (self.depth - 2) // 9
        n_chs = (64, 128, 256, 1024)
        h = _conv(n_chs[0], 3, bias=True, dtype=self.dtype, name="c_in")(x)
        for stage in range(3):
            mid_ch = n_chs[stage] * (self.w_base // 64) * self.cardinality
            out_ch = n_chs[stage] * 4
            for i in range(n_units):
                stride = 2 if (stage > 0 and i == 0) else 1
                in_ch = h.shape[-1]
                h1 = _ShakeBranchBottleneck(
                    mid_ch, out_ch, self.cardinality, stride, dtype=self.dtype,
                    name=f"s{stage}_{i}_branch1"
                )(h, train)
                h2 = _ShakeBranchBottleneck(
                    mid_ch, out_ch, self.cardinality, stride, dtype=self.dtype,
                    name=f"s{stage}_{i}_branch2"
                )(h, train)
                mixed = _ShakeMix(name=f"s{stage}_{i}_mix")(h1, h2, train)
                if in_ch == out_ch:
                    h0 = h
                else:
                    h0 = Shortcut(out_ch, stride, dtype=self.dtype,
                                  name=f"s{stage}_{i}_shortcut")(h, train)
                h = mixed + h0
        h = nn.relu(h)
        h = global_avg_pool(h).astype(jnp.float32)
        return nn.Dense(self.num_classes, bias_init=nn.initializers.zeros, name="fc_out")(h)
