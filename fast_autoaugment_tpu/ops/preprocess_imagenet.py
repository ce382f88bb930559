"""ImageNet preprocessing: host crop/resize + on-device batched augment.

The reference's ImageNet train stack (``data.py:60-74``) is
EfficientNetRandomCrop -> bicubic resize -> HFlip -> ColorJitter(0.4,
0.4, 0.4) -> ToTensor -> PCA Lighting(0.1) -> Normalize, all per-image
on CPU workers.  TPU-native split:

- **Host** (variable-size source images): decode, pick the TF
  ``sample_distorted_bounding_box``-style crop (the exact rejection-
  sampling loop of ``EfficientNetRandomCrop``, ``data.py:267-320``, with
  the same center-crop fallback, ``data.py:323-345``), crop + bicubic
  resize to the static target size.  Scalar math + PIL's native resize;
  this is the only part that genuinely needs variable shapes.
- **Device** (static [B, S, S, 3]): augmentation policy, horizontal
  flip, ColorJitter with torchvision semantics (factors ~ U(1-s, 1+s),
  the three adjustments applied in random order — each adjustment is
  the PIL-exact enhance kernel from ``ops/augment``), AlexNet-style PCA
  lighting noise (``augmentations.py:197-215``), normalize.

Deliberate deviation: the reference inserts the policy at transforms[0]
(full-resolution source image); here it applies after crop/resize at
the network resolution — required for static shapes, and harmless to
density matching since all geometric op magnitudes are
resolution-relative or resolution-independent.
"""

from __future__ import annotations

import math

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
from fast_autoaugment_tpu.ops.augment import brightness as _brightness
from fast_autoaugment_tpu.ops.augment import color as _saturation
from fast_autoaugment_tpu.ops.augment import contrast as _contrast
from fast_autoaugment_tpu.ops.preprocess import IMAGENET_MEAN, IMAGENET_STD, normalize

__all__ = [
    "random_crop_box",
    "center_crop_box",
    "host_train_image",
    "host_eval_image",
    "imagenet_train_batch",
    "imagenet_eval_batch",
]

# reference data.py:21-33
_PCA_EIGVAL = np.array([0.2175, 0.0188, 0.0045], np.float32)
_PCA_EIGVEC = np.array(
    [[-0.5675, 0.7192, 0.4009],
     [-0.5808, -0.0045, -0.8140],
     [-0.5836, -0.6948, 0.4203]],
    np.float32,
)


# ---------------------------------------------------------------------------
# host side
# ---------------------------------------------------------------------------


def center_crop_box(width: int, height: int, imgsize: int):
    """EfficientNetCenterCrop box (``data.py:326-345``)."""
    short = min(width, height)
    crop_size = float(imgsize) / (imgsize + 32) * short
    top = int(round((height - crop_size) / 2.0))
    left = int(round((width - crop_size) / 2.0))
    return left, top, left + crop_size, top + crop_size


def random_crop_box(rng: np.random.Generator, width: int, height: int, imgsize: int,
                    min_covered=0.1, aspect_ratio_range=(3.0 / 4, 4.0 / 3),
                    area_range=(0.08, 1.0), max_attempts=10):
    """The TF sample-distorted-bounding-box rejection loop
    (``data.py:281-320``); falls back to the center crop."""
    min_area = area_range[0] * width * height
    max_area = area_range[1] * width * height
    for _ in range(max_attempts):
        aspect_ratio = rng.uniform(*aspect_ratio_range)
        h = int(round(math.sqrt(min_area / aspect_ratio)))
        max_h = int(round(math.sqrt(max_area / aspect_ratio)))
        if max_h * aspect_ratio > width:
            max_h = int((width + 0.5 - 1e-7) / aspect_ratio)
            if max_h * aspect_ratio > width:
                max_h -= 1
        max_h = min(max_h, height)
        if h >= max_h:
            h = max_h
        h = int(round(rng.uniform(h, max_h)))
        w = int(round(h * aspect_ratio))
        area = w * h
        if area < min_area or area > max_area:
            continue
        if w > width or h > height:
            continue
        if area < min_covered * width * height:
            continue
        if w == width and h == height:
            return center_crop_box(width, height, imgsize)
        x = int(rng.integers(0, width - w + 1))
        y = int(rng.integers(0, height - h + 1))
        return x, y, x + w, y + h
    return center_crop_box(width, height, imgsize)


def host_train_image(img, rng: np.random.Generator, imgsize: int) -> np.ndarray:
    """PIL image -> cropped + bicubic-resized uint8 [S, S, 3]."""
    import PIL.Image

    box = random_crop_box(rng, img.width, img.height, imgsize)
    out = img.crop(box).resize((imgsize, imgsize), PIL.Image.BICUBIC)
    return np.asarray(out, np.uint8)


def host_eval_image(img, imgsize: int) -> np.ndarray:
    import PIL.Image

    box = center_crop_box(img.width, img.height, imgsize)
    out = img.crop(box).resize((imgsize, imgsize), PIL.Image.BICUBIC)
    return np.asarray(out, np.uint8)


# ---------------------------------------------------------------------------
# device side
# ---------------------------------------------------------------------------


def _color_jitter(img, key, strength: float = 0.4):
    """torchvision ColorJitter(brightness/contrast/saturation = s):
    each factor ~ U(1-s, 1+s), the three adjustments in random order."""
    k_perm, k_b, k_c, k_s = jax.random.split(key, 4)
    fb = jax.random.uniform(k_b, (), minval=1 - strength, maxval=1 + strength)
    fc = jax.random.uniform(k_c, (), minval=1 - strength, maxval=1 + strength)
    fs = jax.random.uniform(k_s, (), minval=1 - strength, maxval=1 + strength)

    def b(im):
        return _brightness(im, fb, None)

    def c(im):
        return _contrast(im, fc, None)

    def s(im):
        return _saturation(im, fs, None)

    orders = [(b, c, s), (b, s, c), (c, b, s), (c, s, b), (s, b, c), (s, c, b)]
    branches = [
        (lambda fns: (lambda im: fns[2](fns[1](fns[0](im)))))(fns) for fns in orders
    ]
    idx = jax.random.randint(k_perm, (), 0, len(branches))
    return jax.lax.switch(idx, branches, img)


def _lighting(img01, key, alphastd: float = 0.1):
    """AlexNet PCA noise on the [0,1]-scaled image (``augmentations.py:197-215``)."""
    alpha = jax.random.normal(key, (3,)) * alphastd
    rgb = (jnp.asarray(_PCA_EIGVEC) * alpha[None, :] * jnp.asarray(_PCA_EIGVAL)[None, :]).sum(1)
    return img01 + rgb[None, None, :]


def _train_one(img, policy, key, cutout_length, single_sub_scalar=False):
    from fast_autoaugment_tpu.ops.preprocess import cutout_default

    k_pol, k_flip, k_jit, k_light, k_cut = jax.random.split(key, 5)
    if policy is not None:
        with jax.named_scope(scopes.AUG_POLICY):
            if single_sub_scalar:
                img = apply_policy_scalar_single(img, policy, k_pol)
            else:
                img = apply_policy(img, policy, k_pol)
    with jax.named_scope(scopes.AUG_FIXED):
        img = jnp.where(jax.random.uniform(k_flip) < 0.5, img[:, ::-1], img)
        with jax.named_scope(scopes.AUG_JITTER):
            img = _color_jitter(img, k_jit)
        img01 = img / 255.0
        with jax.named_scope(scopes.AUG_LIGHTING):
            img01 = _lighting(img01, k_light)
        mean = jnp.asarray(IMAGENET_MEAN, img01.dtype)
        std = jnp.asarray(IMAGENET_STD, img01.dtype)
        out = (img01 - mean) / std
        if cutout_length > 0:
            # CutoutDefault applies post-normalize on every dataset family
            # when conf cutout > 0 (reference data.py:111-112)
            out = cutout_default(out, k_cut, cutout_length)
    return out


def imagenet_train_batch(images: jax.Array, key: jax.Array,
                         policy: jax.Array | None = None,
                         cutout_length: int = 0,
                         aug_dispatch: str = "exact",
                         aug_groups: int = 8) -> jax.Array:
    """Device-side ImageNet train stack on host-cropped uint8 batches.

    ``aug_dispatch``/``aug_groups`` mirror
    :func:`fast_autoaugment_tpu.ops.preprocess.cifar_train_batch`:
    "exact" (default) is the historical per-image path bit-for-bit,
    "grouped" applies the policy with scalar op dispatch (stratified
    per-chunk sub-policy draws) before the per-image jitter stack."""
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
    return jax.vmap(lambda im, k: _train_one(im, policy, k, cutout_length,
                                             single_sub_scalar=scalar))(images, keys)


def imagenet_eval_batch(images: jax.Array) -> jax.Array:
    return normalize(images.astype(jnp.float32), IMAGENET_MEAN, IMAGENET_STD)
