"""Plain float32 forward pass of WRN-d-k, for the correctness check.

Written from the paper's description (arXiv:1605.07146, CIFAR form) in
``jax.numpy`` and ``lax.conv_general_dilated`` only: no flax module, no
code of the program (the forward pass is jitted as it stands, so that a
cold run pays one compilation and not one an operation: 60-80 s of a
cold run's 300; my chip runs, PR 34).  It reads the parameter tree the system
checkpoints (flax naming: ``conv1``, ``layer<stage>_<i>/{bn1, conv1,
bn2, conv2, shortcut}``, ``bn1``, ``linear``; a BatchNorm's leaves sit
under ``BatchNorm_0``) and evaluates in inference mode, every product at
``jax.default_matmul_precision("highest")`` — on a TPU a float32
convolution otherwise runs in one bfloat16 pass.

Departures from the paper, both the program's own and kept so that the
two compute the same function: convolutions carry a bias, and BatchNorm
uses epsilon 1e-5 (the PyTorch defaults of the implementation this
system was ported from).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CIFAR_MEAN = (0.4914, 0.4822, 0.4465)
CIFAR_STD = (0.2023, 0.1994, 0.2010)
_BN_EPS = 1e-5


def preprocess(images_u8: np.ndarray) -> jax.Array:
    """uint8 NHWC -> normalised float32, as evaluation feeds the model."""
    x = jnp.asarray(images_u8, jnp.float32) / 255.0
    return (x - jnp.asarray(CIFAR_MEAN, jnp.float32)) / jnp.asarray(
        CIFAR_STD, jnp.float32)


def _conv(x, p, stride: int):
    kernel = jnp.asarray(p["kernel"], jnp.float32)
    pad = kernel.shape[0] // 2
    y = jax.lax.conv_general_dilated(
        x, kernel, window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    return y + jnp.asarray(p["bias"], jnp.float32)


def _bn_relu(x, p, stats):
    p, stats = p["BatchNorm_0"], stats["BatchNorm_0"]
    inv = jax.lax.rsqrt(jnp.asarray(stats["var"], jnp.float32) + _BN_EPS)
    y = (x - jnp.asarray(stats["mean"], jnp.float32)) * inv
    y = y * jnp.asarray(p["scale"], jnp.float32) + jnp.asarray(
        p["bias"], jnp.float32)
    return jnp.maximum(y, 0.0)


def forward(params: dict, batch_stats: dict, images_u8: np.ndarray,
            model: dict) -> np.ndarray:
    """Logits ``[n, num_classes]`` (float32, on the host) for uint8
    images under the given parameter and running-statistics trees."""
    n = (int(model["depth"]) - 4) // 6

    @jax.jit
    def net(params, batch_stats, images_u8):
        x = _conv(preprocess(images_u8), params["conv1"], 1)
        for stage, stride in zip((1, 2, 3), (1, 2, 2)):
            for i in range(n):
                name = f"layer{stage}_{i}"
                p, s = params[name], batch_stats[name]
                st = stride if i == 0 else 1
                out = _conv(_bn_relu(x, p["bn1"], s["bn1"]), p["conv1"], 1)
                out = _conv(_bn_relu(out, p["bn2"], s["bn2"]), p["conv2"], st)
                x = out + (_conv(x, p["shortcut"], st)
                           if "shortcut" in p else x)
        x = _bn_relu(x, params["bn1"], batch_stats["bn1"]).mean(axis=(1, 2))
        logits = jnp.dot(x, jnp.asarray(params["linear"]["kernel"],
                                        jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        return logits + jnp.asarray(params["linear"]["bias"], jnp.float32)

    with jax.default_matmul_precision("highest"):
        return np.asarray(net(params, batch_stats, images_u8))
