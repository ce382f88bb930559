"""Plain float32 ResNet in its ImageNet layout: forward pass, loss and gradients.

Written from He et al., "Deep Residual Learning for Image Recognition"
(arXiv:1512.03385, the ImageNet table: a 7x7 stride-2 stem of 64
channels, BatchNorm, ReLU, a 3x3 stride-2 max-pool, four stages of
bottleneck blocks at widths 64/128/256/512 with four times that coming
out, global average pooling, one linear layer) in ``jax.numpy`` and
``lax`` only: no flax module, no code of the program (the forward pass
of a block of images and the training pass are each jitted as they
stand, so that a cold run pays one compilation and not one an
operation).  It reads
the parameter tree the system checkpoints (flax naming: ``conv1``,
``bn1``, ``layer<stage>_<i>/{conv1, bn1, conv2, bn2, conv3, bn3,
downsample_conv, downsample_bn}``, ``fc``; a BatchNorm's leaves sit
under ``BatchNorm_0``), every product at
``jax.default_matmul_precision("highest")`` — on a TPU a float32
convolution otherwise runs in one bfloat16 pass.

A bottleneck block is ``relu(x' + bn3(conv1x1(relu(bn2(conv3x3(relu(bn1(
conv1x1(x)))))))))``; ``x'`` is ``x``, or ``bn(conv1x1(x))`` where the
block changes the width or strides.  Departures from the paper, the
reference implementation's (torchvision's, which the PyTorch code this
system was ported from uses) and kept so that the two compute the same
function: the stride of a stage's first block sits on its 3x3
convolution, where the paper's first 1x1 strides; no convolution has a
bias; every convolution pads by half its kernel, and the max-pool pads
by one with minus infinity; BatchNorm uses epsilon 1e-5 and updates its
running statistics by 0.1 of the batch's.  One departure is the
program's own: the running variance takes the batch's biased variance
(flax) where PyTorch takes the unbiased one.  Evaluation normalises by
ImageNet's channel mean and standard deviation.  Initialisation is not
this file's business.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
_BN_EPS = 1e-5
_BN_MOMENTUM = 0.1   # the weight of the new batch's statistics
#: images a pass of :func:`forward`
_FORWARD_BLOCK = 64


def preprocess(images_u8: np.ndarray) -> jax.Array:
    """uint8 NHWC -> normalised float32, as evaluation feeds the model."""
    x = jnp.asarray(images_u8, jnp.float32) / 255.0
    return (x - jnp.asarray(IMAGENET_MEAN, jnp.float32)) / jnp.asarray(
        IMAGENET_STD, jnp.float32)


def _conv(x, p, stride: int = 1):
    kernel = jnp.asarray(p["kernel"], jnp.float32)
    pad = kernel.shape[0] // 2
    return jax.lax.conv_general_dilated(
        x, kernel, window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _max_pool_3x3_stride_2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])


def _net(params, batch_stats, x, model, training: bool):
    """``(logits, moments)``.  Evaluation: running statistics, `moments`
    empty.  Training: batch statistics, and `moments` holds each
    BatchNorm's batch ``(mean, var)`` by its path in the tree."""
    moments: dict[tuple, tuple] = {}

    def bn(h, *path):
        p, s = params, batch_stats
        for key in path:
            p, s = p[key], s[key]
        p, s = p["BatchNorm_0"], s["BatchNorm_0"]
        if training:
            mean = h.mean(axis=(0, 1, 2))
            var = jnp.square(h - mean).mean(axis=(0, 1, 2))
            moments[path] = (mean, var)
        else:
            mean = jnp.asarray(s["mean"], jnp.float32)
            var = jnp.asarray(s["var"], jnp.float32)
        y = (h - mean) * jax.lax.rsqrt(var + _BN_EPS)
        return y * jnp.asarray(p["scale"], jnp.float32) + jnp.asarray(
            p["bias"], jnp.float32)

    def bottleneck(h, name, stride):
        p = params[name]
        out = jnp.maximum(bn(_conv(h, p["conv1"]), name, "bn1"), 0.0)
        out = jnp.maximum(bn(_conv(out, p["conv2"], stride), name, "bn2"), 0.0)
        out = bn(_conv(out, p["conv3"]), name, "bn3")
        if "downsample_conv" in p:
            h = bn(_conv(h, p["downsample_conv"], stride), name, "downsample_bn")
        return jnp.maximum(out + h, 0.0)

    h = jnp.maximum(bn(_conv(x, params["conv1"], 2), "bn1"), 0.0)
    h = _max_pool_3x3_stride_2(h)
    for stage, count in enumerate(model["blocks"]):
        for i in range(int(count)):
            h = bottleneck(h, f"layer{stage + 1}_{i}",
                           2 if (stage > 0 and i == 0) else 1)
    h = h.mean(axis=(1, 2))
    logits = jnp.dot(h, jnp.asarray(params["fc"]["kernel"], jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return logits + jnp.asarray(params["fc"]["bias"], jnp.float32), moments


def forward(params: dict, batch_stats: dict, images_u8: np.ndarray,
            model: dict) -> np.ndarray:
    """Evaluation logits ``[n, num_classes]`` (float32, on the host) for
    uint8 images under the given parameter and running-statistics trees,
    a block of images at a time."""
    blocks = tuple(int(n) for n in model["blocks"])

    @jax.jit
    def block_logits(p, s, block_u8):
        return _net(p, s, preprocess(block_u8), {"blocks": blocks},
                    training=False)[0]

    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(images_u8), _FORWARD_BLOCK):
            out.append(np.asarray(block_logits(
                params, batch_stats, images_u8[lo:lo + _FORWARD_BLOCK])))
    return np.concatenate(out)


def loss_and_grads(params: dict, batch_stats: dict, images, labels,
                   model: dict):
    """One training-mode pass on the float `images` the model sees after
    the augmentation: ``(loss, grads, new_batch_stats)``, the mean
    cross-entropy over the batch (no label smoothing: the conf has none),
    its gradient for every parameter, and the running statistics after
    the step.  Jitted whole, as :func:`forward` is."""
    blocks = tuple(int(n) for n in model["blocks"])

    @jax.jit
    def step(p, s, x, y):
        def loss_fn(p):
            logits, moments = _net(p, s, x, {"blocks": blocks}, training=True)
            log_p = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                         keepdims=True)
            picked = jnp.take_along_axis(log_p, y[:, None], axis=-1)
            return -picked.mean(), moments

        return jax.value_and_grad(loss_fn, has_aux=True)(p)

    with jax.default_matmul_precision("highest"):
        (loss, moments), grads = step(
            jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params),
            batch_stats, jnp.asarray(images, jnp.float32), jnp.asarray(labels))
    new_stats: dict = {}
    for path, (mean, var) in moments.items():
        old, node = batch_stats, new_stats
        for key in path:
            old, node = old[key], node.setdefault(key, {})
        old = old["BatchNorm_0"]
        node["BatchNorm_0"] = {
            "mean": (1 - _BN_MOMENTUM) * jnp.asarray(old["mean"])
            + _BN_MOMENTUM * mean,
            "var": (1 - _BN_MOMENTUM) * jnp.asarray(old["var"])
            + _BN_MOMENTUM * var}
    return (float(loss), jax.tree.map(np.asarray, grads),
            jax.tree.map(np.asarray, new_stats))


def sgd_nesterov_first_step(params: dict, grads: dict, lr: float,
                            decay: float, momentum: float = 0.9) -> dict:
    """How far the first step of SGD with Nesterov momentum moves every
    parameter from an empty momentum buffer (torch's form: the buffer
    becomes ``g'`` and the step is ``g' + momentum * buffer``):
    ``-lr * (1 + momentum) * g'``, where ``g' = g + decay * p`` for every
    parameter but a BatchNorm's scale and bias, which are not decayed."""
    def walk(p, g, decayed):
        if isinstance(p, dict):
            return {key: walk(p[key], g[key], decayed and key != "BatchNorm_0")
                    for key in p}
        p, g = np.asarray(p, np.float64), np.asarray(g, np.float64)
        return -lr * (1 + momentum) * (g + decay * p if decayed else g)

    return walk(params, grads, True)
