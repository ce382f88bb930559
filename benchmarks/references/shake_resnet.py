"""Plain float32 Shake-Shake-26 2x{w}d: forward pass, loss and gradients.

Written from Gastaldi, "Shake-Shake regularization" (arXiv:1705.07485,
CIFAR form, the "Shake-Shake-Image" variant: one coefficient per image
and block, drawn anew for the backward pass) in ``jax.numpy`` and
``lax.conv_general_dilated`` only: no flax module, no ``custom_vjp``, no
code of the program (the evaluation pass is jitted as it stands, so that
a cold run pays one compilation and not one an operation).  It reads the parameter tree the
system checkpoints (flax naming: ``c_in``, ``s<stage>_<i>_branch{1,2}/
{conv1, bn1, conv2, bn2}``, ``s<stage>_<i>_shortcut/{conv1, conv2, bn}``,
``fc_out``; a BatchNorm's leaves sit under ``BatchNorm_0``), every
product at ``jax.default_matmul_precision("highest")`` — on a TPU a
float32 convolution otherwise runs in one bfloat16 pass.

The rule under test.  A block computes ``x + a * f1(x) + (1 - a) * f2(x)``
with ``a ~ U(0, 1)`` per image, and its backward pass multiplies the
incoming gradient by ``b`` and ``1 - b`` for a fresh ``b ~ U(0, 1)``
where autodiff would use ``a``.  Here that is one expression autodiff
can take as it stands::

    b * x1 + (1 - b) * x2 + stop_gradient((a - b) * (x1 - x2))

which has ``a``'s value and ``b``'s gradient: an independent statement
of the rule, so a comparison with it tests ``ops/shake.py``'s
``custom_vjp``.  The noise is an argument; drawing it is the program's.

Departures from the paper, all the reference implementation's (the
PyTorch code this system was ported from) and kept so that the two
compute the same function: evaluation mixes by 0.5; the shortcut's
second path shifts by one pixel by cropping the first row and column and
zero-padding the last; only the stem convolution and the linear layer
carry a bias; BatchNorm uses epsilon 1e-5 and updates its running
statistics by 0.1 of the batch's.  One departure is the program's own:
the running variance takes the batch's biased variance (flax) where
PyTorch takes the unbiased one.  Initialisation is not this file's
business.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CIFAR_MEAN = (0.4914, 0.4822, 0.4465)
CIFAR_STD = (0.2023, 0.1994, 0.2010)
_BN_EPS = 1e-5
_BN_MOMENTUM = 0.1   # the weight of the new batch's statistics


def preprocess(images_u8: np.ndarray) -> jax.Array:
    """uint8 NHWC -> normalised float32, as evaluation feeds the model."""
    x = jnp.asarray(images_u8, jnp.float32) / 255.0
    return (x - jnp.asarray(CIFAR_MEAN, jnp.float32)) / jnp.asarray(
        CIFAR_STD, jnp.float32)


def _conv(x, p, stride: int = 1):
    kernel = jnp.asarray(p["kernel"], jnp.float32)
    pad = kernel.shape[0] // 2
    y = jax.lax.conv_general_dilated(
        x, kernel, window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    return y + jnp.asarray(p["bias"], jnp.float32) if "bias" in p else y


def _net(params, batch_stats, x, model, noise):
    """``(logits, moments)``.  `noise` None: evaluation (running
    statistics, the 0.5 mix, `moments` empty).  Else ``(alpha, beta)``,
    each ``[blocks, batch]``: training (batch statistics, the decoupled
    mix), and `moments` holds each BatchNorm's batch ``(mean, var)`` by
    its path in the tree."""
    moments: dict[tuple, tuple] = {}

    def bn(h, *path):
        p = params
        for key in path:
            p = p[key]
        p = p["BatchNorm_0"]
        if noise is None:
            s = batch_stats
            for key in path:
                s = s[key]
            mean = jnp.asarray(s["BatchNorm_0"]["mean"], jnp.float32)
            var = jnp.asarray(s["BatchNorm_0"]["var"], jnp.float32)
        else:
            mean = h.mean(axis=(0, 1, 2))
            var = jnp.square(h - mean).mean(axis=(0, 1, 2))
            moments[path] = (mean, var)
        y = (h - mean) * jax.lax.rsqrt(var + _BN_EPS)
        return y * jnp.asarray(p["scale"], jnp.float32) + jnp.asarray(
            p["bias"], jnp.float32)

    def branch(h, name, stride):
        p = params[name]
        h = _conv(jnp.maximum(h, 0.0), p["conv1"], stride)
        h = jnp.maximum(bn(h, name, "bn1"), 0.0)
        return bn(_conv(h, p["conv2"]), name, "bn2")

    def shortcut(h, name, stride):
        p = params[name]
        h = jnp.maximum(h, 0.0)
        even = h[:, ::stride, ::stride, :]
        shifted = jnp.pad(h[:, 1:, 1:, :], ((0, 0), (0, 1), (0, 1), (0, 0)))
        odd = shifted[:, ::stride, ::stride, :]
        both = jnp.concatenate(
            [_conv(even, p["conv1"]), _conv(odd, p["conv2"])], axis=-1)
        return bn(both, name, "bn")

    def mix(block, x1, x2):
        if noise is None:
            return 0.5 * (x1 + x2)
        a = jnp.asarray(noise[0], jnp.float32)[block][:, None, None, None]
        b = jnp.asarray(noise[1], jnp.float32)[block][:, None, None, None]
        return (b * x1 + (1.0 - b) * x2
                + jax.lax.stop_gradient((a - b) * (x1 - x2)))

    h = _conv(x, params["c_in"])
    block = 0
    for stage, stride in enumerate((1, 2, 2)):
        for i in range((int(model["depth"]) - 2) // 6):
            name, st = f"s{stage}_{i}", (stride if i == 0 else 1)
            mixed = mix(block, branch(h, f"{name}_branch1", st),
                        branch(h, f"{name}_branch2", st))
            h = mixed + (shortcut(h, f"{name}_shortcut", st)
                         if f"{name}_shortcut" in params else h)
            block += 1
    h = jnp.maximum(h, 0.0).mean(axis=(1, 2))
    logits = jnp.dot(h, jnp.asarray(params["fc_out"]["kernel"], jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return logits + jnp.asarray(params["fc_out"]["bias"], jnp.float32), moments


def forward(params: dict, batch_stats: dict, images_u8: np.ndarray,
            model: dict) -> np.ndarray:
    """Evaluation logits ``[n, num_classes]`` (float32, on the host) for
    uint8 images under the given parameter and running-statistics trees."""
    @jax.jit
    def logits(p, s, images):
        return _net(p, s, preprocess(images), model, None)[0]

    with jax.default_matmul_precision("highest"):
        return np.asarray(logits(params, batch_stats, images_u8))


def loss_and_grads(params: dict, batch_stats: dict, images, labels,
                   noise, model: dict):
    """One training-mode pass on the float `images` the model sees after
    the augmentation: ``(loss, grads, new_batch_stats)``, the mean
    cross-entropy over the batch (no label smoothing: the conf has none),
    its gradient for every parameter under `noise` = ``(alpha, beta)``,
    each ``[blocks, batch]``, and the running statistics after the step."""
    labels = jnp.asarray(labels)
    images = jnp.asarray(images, jnp.float32)

    def loss_fn(p):
        logits, moments = _net(p, batch_stats, images, model, noise)
        log_p = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                     keepdims=True)
        picked = jnp.take_along_axis(log_p, labels[:, None], axis=-1)
        return -picked.mean(), moments

    with jax.default_matmul_precision("highest"):
        (loss, moments), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params))
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
