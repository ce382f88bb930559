"""On-device train/eval preprocessing stacks.

The reference runs its baseline transforms (RandomCrop+pad, HFlip,
Normalize, Cutout) per-image on CPU DataLoader workers
(``data.py:38-47,111-112``).  Here the full train-time stack — baseline
transforms, the augmentation *policy*, normalization and post-normalize
cutout — is one jit-compiled batched function executed on device, fused
with the train step.  The host only supplies raw uint8 batches.

Order reproduces the reference exactly (``data.py:88-112``): the policy
is applied FIRST (inserted at transforms[0], on raw pixels), then random
crop + flip, then normalize, then CutoutDefault (which zeroes a box on
the *normalized* tensor — so the fill is the per-channel mean, unlike
the policy's gray Cutout op on raw pixels).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fast_autoaugment_tpu.core import scopes
from fast_autoaugment_tpu.ops.augment import (
    apply_policy,
    apply_policy_batch_grouped,
    apply_policy_scalar_single,
    check_aug_dispatch,
)

__all__ = [
    "CIFAR_MEAN",
    "CIFAR_STD",
    "IMAGENET_MEAN",
    "IMAGENET_STD",
    "normalize",
    "random_crop_with_pad",
    "random_hflip",
    "cutout_default",
    "cifar_train_batch",
    "cifar_eval_batch",
]

CIFAR_MEAN = (0.4914, 0.4822, 0.4465)  # reference data.py:34
CIFAR_STD = (0.2023, 0.1994, 0.2010)
IMAGENET_MEAN = (0.485, 0.456, 0.406)  # reference data.py:71
IMAGENET_STD = (0.229, 0.224, 0.225)


def normalize(img: jax.Array, mean: Sequence[float], std: Sequence[float]) -> jax.Array:
    """uint8-valued [0..255] float -> normalized float (ToTensor + Normalize)."""
    mean = jnp.asarray(mean, img.dtype)
    std = jnp.asarray(std, img.dtype)
    return (img / 255.0 - mean) / std


def random_crop_with_pad(img: jax.Array, key: jax.Array, pad: int = 4) -> jax.Array:
    """torchvision RandomCrop(size, padding=pad) with zero fill: pad all
    sides then take a random crop at the original size."""
    h, w = img.shape[:2]
    padded = jnp.pad(img, ((pad, pad), (pad, pad), (0, 0)))
    ky, kx = jax.random.split(key)
    oy = jax.random.randint(ky, (), 0, 2 * pad + 1)
    ox = jax.random.randint(kx, (), 0, 2 * pad + 1)
    # one static slice per offset and a select, rows then columns: a
    # dynamic_slice vmapped over per-image offsets is a gather, which
    # XLA:TPU runs as a loop over the batch (58 ms a step at 2,048
    # images where these selects take under one; PERF.md section 6, PR 25)
    rows = padded[:h]
    for k in range(1, 2 * pad + 1):
        rows = jnp.where(oy == k, padded[k:k + h], rows)
    out = rows[:, :w]
    for k in range(1, 2 * pad + 1):
        out = jnp.where(ox == k, rows[:, k:k + w], out)
    return out


def random_hflip(img: jax.Array, key: jax.Array) -> jax.Array:
    return jnp.where(jax.random.uniform(key) < 0.5, img[:, ::-1], img)


def cutout_default(img: jax.Array, key: jax.Array, length: int) -> jax.Array:
    """DARTS-style cutout on the normalized tensor (reference
    ``CutoutDefault``, ``data.py:228-250``): zero a length x length box
    centered at a uniform integer pixel, clipped at the borders."""
    h, w = img.shape[0], img.shape[1]
    ky, kx = jax.random.split(key)
    y = jax.random.randint(ky, (), 0, h)
    x = jax.random.randint(kx, (), 0, w)
    ys, xs = jnp.mgrid[0:h, 0:w]
    inside = (
        (ys >= y - length // 2)
        & (ys < y + length // 2)
        & (xs >= x - length // 2)
        & (xs < x + length // 2)
    )
    return jnp.where(inside[..., None], 0.0, img)


def _cifar_train_one(img, policy, key, cutout_length, mean, std,
                     single_sub_scalar=False):
    k_policy, k_crop, k_flip, k_cutout = jax.random.split(key, 4)
    if policy is not None:
        with jax.named_scope(scopes.AUG_POLICY):
            if single_sub_scalar:
                # bitwise-identical to apply_policy on a [1, num_op, 3]
                # tensor, but the op index stays scalar under the batch vmap
                img = apply_policy_scalar_single(img, policy, k_policy)
            else:
                img = apply_policy(img, policy, k_policy)
    with jax.named_scope(scopes.AUG_FIXED):
        img = random_crop_with_pad(img, k_crop, 4)
        img = random_hflip(img, k_flip)
        img = normalize(img, mean, std)
        if cutout_length > 0:
            img = cutout_default(img, k_cutout, cutout_length)
    return img


def cifar_train_batch(
    images: jax.Array,
    key: jax.Array,
    policy: jax.Array | None = None,
    cutout_length: int = 16,
    mean: Sequence[float] = CIFAR_MEAN,
    std: Sequence[float] = CIFAR_STD,
    aug_dispatch: str = "exact",
    aug_groups: int = 8,
) -> jax.Array:
    """Full CIFAR/SVHN train-time stack on a [B, H, W, C] uint8-valued batch.

    `policy` is a [num_sub, num_op, 3] tensor (or None for 'default' aug).
    ``aug_dispatch="exact"`` (default) is bit-for-bit the historical
    per-image path; ``"grouped"`` applies the policy through
    :func:`apply_policy_batch_grouped` (scalar op dispatch, stratified
    per-chunk sub-policy draws, `aug_groups` chunks) before the
    per-image crop/flip/normalize/cutout stack.  A single-sub-policy
    tensor under "grouped" takes the bitwise-exact scalar path instead
    (no selection to stratify)."""
    check_aug_dispatch(aug_dispatch)
    with jax.named_scope(scopes.AUG_FIXED):
        images = images.astype(jnp.float32)
    single_sub = policy is not None and int(policy.shape[0]) == 1
    if aug_dispatch == "grouped" and policy is not None and not single_sub:
        key, key_pol = jax.random.split(key)
        with jax.named_scope(scopes.AUG_POLICY):
            images = apply_policy_batch_grouped(images, policy, key_pol,
                                                groups=aug_groups)
        policy = None
    scalar = aug_dispatch == "grouped" and single_sub
    with jax.named_scope(scopes.AUG_FIXED):
        keys = jax.random.split(key, images.shape[0])
    return jax.vmap(
        lambda im, k: _cifar_train_one(im, policy, k, cutout_length, mean, std,
                                       single_sub_scalar=scalar)
    )(images, keys)


def cifar_eval_batch(
    images: jax.Array,
    mean: Sequence[float] = CIFAR_MEAN,
    std: Sequence[float] = CIFAR_STD,
) -> jax.Array:
    """Eval stack: normalize only (reference ``data.py:45-47``)."""
    return normalize(images.astype(jnp.float32), mean, std)
