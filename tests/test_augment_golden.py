"""Golden tests: on-device augmentation kernels vs PIL semantics.

Each case reproduces exactly what the reference does with PIL
(``/root/reference/FastAutoAugment/augmentations.py``) and checks the
jnp kernel matches bit-exactly (or within a documented tolerance) on
random uint8 images.  Mirroring randomness is bypassed by calling the
deterministic op functions directly with signed values.
"""

import numpy as np
import PIL.Image
import PIL.ImageDraw
import PIL.ImageEnhance
import PIL.ImageFilter
import PIL.ImageOps
import pytest

import jax
import jax.numpy as jnp

from fast_autoaugment_tpu.ops import augment as A


def _rand_img(seed, h=32, w=32):
    return np.random.default_rng(seed).integers(0, 256, (h, w, 3), dtype=np.uint8)


def _check(jnp_out, pil_img, atol=0):
    got = np.asarray(jnp_out).astype(np.int32)
    want = np.asarray(pil_img).astype(np.int32)
    assert got.shape == want.shape
    diff = np.abs(got - want)
    assert diff.max() <= atol, f"max diff {diff.max()} at {np.unravel_index(diff.argmax(), diff.shape)}"


KEY = jax.random.PRNGKey(0)
SIZES = [(32, 32), (17, 23)]


@pytest.mark.parametrize("h,w", SIZES)
@pytest.mark.parametrize("v", [-0.3, -0.1, 0.17, 0.3])
def test_shear(h, w, v):
    img = _rand_img(0, h, w)
    pim = PIL.Image.fromarray(img)
    _check(A.shear_x(jnp.float32(img), jnp.float32(v), KEY),
           pim.transform(pim.size, PIL.Image.AFFINE, (1, v, 0, 0, 1, 0)))
    _check(A.shear_y(jnp.float32(img), jnp.float32(v), KEY),
           pim.transform(pim.size, PIL.Image.AFFINE, (1, 0, 0, v, 1, 0)))


@pytest.mark.parametrize("h,w", SIZES)
@pytest.mark.parametrize("v", [-0.45, -0.2, 0.11, 0.45])
def test_translate_fractional(h, w, v):
    img = _rand_img(1, h, w)
    pim = PIL.Image.fromarray(img)
    _check(A.translate_x(jnp.float32(img), jnp.float32(v), KEY),
           pim.transform(pim.size, PIL.Image.AFFINE, (1, 0, v * w, 0, 1, 0)))
    _check(A.translate_y(jnp.float32(img), jnp.float32(v), KEY),
           pim.transform(pim.size, PIL.Image.AFFINE, (1, 0, 0, 0, 1, v * h)))


@pytest.mark.parametrize("v", [-10, -3, 0, 7, 10])
def test_translate_abs(v):
    img = _rand_img(2)
    pim = PIL.Image.fromarray(img)
    _check(A.translate_x_abs(jnp.float32(img), jnp.float32(v), KEY),
           pim.transform(pim.size, PIL.Image.AFFINE, (1, 0, v, 0, 1, 0)))
    _check(A.translate_y_abs(jnp.float32(img), jnp.float32(v), KEY),
           pim.transform(pim.size, PIL.Image.AFFINE, (1, 0, 0, 0, 1, v)))


@pytest.mark.parametrize("h,w", SIZES)
@pytest.mark.parametrize("v", [-30.0, -12.5, 7.3, 30.0])
def test_rotate(h, w, v):
    img = _rand_img(3, h, w)
    pim = PIL.Image.fromarray(img)
    _check(A.rotate(jnp.float32(img), jnp.float32(v), KEY), pim.rotate(v))


def _pil_affine(pim, coeffs):
    return pim.transform(pim.size, PIL.Image.AFFINE, coeffs)


#: the ends of the five ranges, where a tile's window is fullest
AFFINE_ENDS_224 = {
    "Rotate": (A.rotate, 30.0, lambda pim, v: pim.rotate(v)),
    "ShearX": (A.shear_x, 0.3, lambda pim, v: _pil_affine(pim, (1, v, 0, 0, 1, 0))),
    "ShearY": (A.shear_y, 0.3, lambda pim, v: _pil_affine(pim, (1, 0, 0, v, 1, 0))),
    "TranslateX": (A.translate_x, 0.45, lambda pim, v: _pil_affine(
        pim, (1, 0, v * pim.size[0], 0, 1, 0))),
    "TranslateY": (A.translate_y, 0.45, lambda pim, v: _pil_affine(
        pim, (1, 0, 0, 0, 1, v * pim.size[1]))),
}


@pytest.mark.parametrize("h,w", [(224, 224), (160, 224)])
@pytest.mark.parametrize("sign", [-1, 1])
@pytest.mark.parametrize("name", AFFINE_ENDS_224)
def test_affine_ends_at_224_px(name, sign, h, w):
    """ImageNet size, where the warp goes tile by tile (``_warp_tiling``)."""
    assert A._warp_tiling(h, w) is not None
    fn, end, pil = AFFINE_ENDS_224[name]
    img = _rand_img(8, h, w)
    _check(fn(jnp.float32(img), jnp.float32(sign * end), KEY),
           pil(PIL.Image.fromarray(img), sign * end))


@pytest.mark.parametrize("seed", range(4))
def test_autocontrast(seed):
    img = _rand_img(seed)
    if seed == 1:  # low dynamic range exercises the stretch
        img = (img // 4 + 64).astype(np.uint8)
    pim = PIL.Image.fromarray(img)
    # atol=1: we use the exact integer LUT; PIL's double-precision
    # truncation occasionally lands 1 lower (see ops/augment.py).
    _check(A.auto_contrast(jnp.float32(img), jnp.float32(0), KEY),
           PIL.ImageOps.autocontrast(pim), atol=1)


def test_autocontrast_constant_channel():
    img = np.full((8, 8, 3), 77, np.uint8)
    pim = PIL.Image.fromarray(img)
    _check(A.auto_contrast(jnp.float32(img), jnp.float32(0), KEY),
           PIL.ImageOps.autocontrast(pim))


@pytest.mark.parametrize("seed", range(4))
def test_equalize(seed):
    img = _rand_img(seed)
    if seed == 2:  # skewed histogram
        img = (img.astype(np.float32) ** 2 / 255.0).astype(np.uint8)
    pim = PIL.Image.fromarray(img)
    _check(A.equalize(jnp.float32(img), jnp.float32(0), KEY), PIL.ImageOps.equalize(pim))


def test_equalize_constant_image():
    img = np.full((8, 8, 3), 9, np.uint8)
    _check(A.equalize(jnp.float32(img), jnp.float32(0), KEY),
           PIL.ImageOps.equalize(PIL.Image.fromarray(img)))


def test_invert():
    img = _rand_img(5)
    _check(A.invert(jnp.float32(img), jnp.float32(0), KEY),
           PIL.ImageOps.invert(PIL.Image.fromarray(img)))


@pytest.mark.parametrize("v", [0, 77.5, 128, 255, 256])
def test_solarize(v):
    img = _rand_img(6)
    _check(A.solarize(jnp.float32(img), jnp.float32(v), KEY),
           PIL.ImageOps.solarize(PIL.Image.fromarray(img), v))


@pytest.mark.parametrize("v", [0, 1, 2.7, 4, 4.9, 6, 8])
def test_posterize(v):
    img = _rand_img(7)
    _check(A.posterize(jnp.float32(img), jnp.float32(v), KEY),
           PIL.ImageOps.posterize(PIL.Image.fromarray(img), int(v)))
    _check(A.posterize2(jnp.float32(img), jnp.float32(v), KEY),
           PIL.ImageOps.posterize(PIL.Image.fromarray(img), int(v)))


@pytest.mark.parametrize("v", [0.1, 0.6, 1.0, 1.33, 1.9])
@pytest.mark.parametrize("enhancer,fn", [
    (PIL.ImageEnhance.Contrast, A.contrast),
    (PIL.ImageEnhance.Color, A.color),
    (PIL.ImageEnhance.Brightness, A.brightness),
])
def test_enhance_exact(v, enhancer, fn):
    img = _rand_img(8)
    pim = PIL.Image.fromarray(img)
    _check(fn(jnp.float32(img), jnp.float32(v), KEY), enhancer(pim).enhance(v))


@pytest.mark.parametrize("h,w", SIZES)
@pytest.mark.parametrize("v", [0.1, 0.6, 1.0, 1.9])
def test_sharpness(h, w, v):
    img = _rand_img(9, h, w)
    pim = PIL.Image.fromarray(img)
    _check(A.sharpness(jnp.float32(img), jnp.float32(v), KEY),
           PIL.ImageEnhance.Sharpness(pim).enhance(v))


@pytest.mark.parametrize("v", [0.0, 4.0, 11.3, 20.0])
def test_cutout_abs_matches_pil_rectangle(v):
    """Replicate the jax random draws on the host, then compare against
    the reference CutoutAbs drawing (augmentations.py:127-146)."""
    img = _rand_img(10)
    key = jax.random.PRNGKey(42)
    got = A.cutout_abs(jnp.float32(img), jnp.float32(v), key)

    h, w = img.shape[:2]
    kx, ky = jax.random.split(key)
    x0f = float(jax.random.uniform(kx, (), minval=0.0, maxval=float(w)))
    y0f = float(jax.random.uniform(ky, (), minval=0.0, maxval=float(h)))
    x0 = int(max(0, x0f - v / 2.0))
    y0 = int(max(0, y0f - v / 2.0))
    x1 = min(w, x0 + v)
    y1 = min(h, y0 + v)
    pim = PIL.Image.fromarray(img).copy()
    PIL.ImageDraw.Draw(pim).rectangle((x0, y0, x1, y1), tuple(int(c) for c in A.CUTOUT_COLOR))
    _check(got, pim)


def test_cutout_zero_is_identity():
    img = jnp.float32(_rand_img(11))
    out = A.cutout(img, jnp.float32(0.0), jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(img))


def test_flip():
    img = _rand_img(12)
    _check(A.flip(jnp.float32(img), jnp.float32(0), KEY),
           PIL.ImageOps.mirror(PIL.Image.fromarray(img)))


# ---------------------------------------------------------------------------
# dispatch machinery
# ---------------------------------------------------------------------------


def test_registry_matches_reference():
    names = [n for n, _, _ in A.augment_list(False)]
    assert names == [
        "ShearX", "ShearY", "TranslateX", "TranslateY", "Rotate",
        "AutoContrast", "Invert", "Equalize", "Solarize", "Posterize",
        "Contrast", "Color", "Brightness", "Sharpness", "Cutout",
    ]
    assert len(A.augment_list(True)) == 19
    assert "Flip" not in A.OP_NAMES


def test_apply_op_jits_with_traced_index():
    img = jnp.float32(_rand_img(13))

    @jax.jit
    def run(op_idx, level, key):
        return A.apply_op(img, op_idx, level, key)

    key = jax.random.PRNGKey(0)
    out_inv = run(jnp.int32(6), jnp.float32(0.5), key)
    _check(out_inv, PIL.ImageOps.invert(PIL.Image.fromarray(np.asarray(img, np.uint8))))
    # same compiled fn serves another op id — policy-as-data
    out_eq = run(jnp.int32(7), jnp.float32(0.5), key)
    _check(out_eq, PIL.ImageOps.equalize(PIL.Image.fromarray(np.asarray(img, np.uint8))))


def test_cutout_abs_never_mirrors_through_dispatch():
    """Regression: CutoutAbs must NOT sign-flip its value in apply_op —
    a negative value silently disables it (reference CutoutAbs has no
    mirror, augmentations.py:127-131)."""
    img = jnp.float32(np.zeros((32, 32, 3), np.uint8))
    keys = jax.random.split(jax.random.PRNGKey(11), 64)
    # op 15 = CutoutAbs at level 1.0 -> 20px box; on a black image the
    # gray fill must appear for EVERY key
    outs = jax.vmap(lambda k: A.apply_op(img, jnp.int32(15), jnp.float32(1.0), k))(keys)
    changed = (np.asarray(outs) != 0).any(axis=(1, 2, 3))
    assert changed.all(), f"CutoutAbs was a no-op for {int((~changed).sum())}/64 keys"


def test_mirror_flips_sign_half_the_time():
    img = jnp.float32(_rand_img(14))
    keys = jax.random.split(jax.random.PRNGKey(7), 200)
    # TranslateX at level 1.0 -> value +0.45 or -0.45; look at which side keeps pixels
    outs = jax.vmap(lambda k: A.apply_op(img, jnp.int32(2), jnp.float32(1.0), k))(keys)
    left_zero = (np.asarray(outs)[:, :, :10, :] == 0).all(axis=(1, 2, 3))
    frac = left_zero.mean()
    assert 0.3 < frac < 0.7, frac


def test_apply_policy_batch_shapes_and_determinism():
    imgs = jnp.float32(np.stack([_rand_img(s) for s in range(8)]))
    policy = jnp.float32(
        [[[6, 1.0, 0.0], [8, 1.0, 0.5]],
         [[7, 0.5, 0.0], [12, 1.0, 0.9]]]
    )
    key = jax.random.PRNGKey(5)
    out1 = A.apply_policy_batch(imgs, policy, key)
    out2 = A.apply_policy_batch(imgs, policy, key)
    assert out1.shape == imgs.shape
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # different key -> different augmentation
    out3 = A.apply_policy_batch(imgs, policy, jax.random.PRNGKey(6))
    assert not np.array_equal(np.asarray(out1), np.asarray(out3))


def test_prob_zero_policy_is_identity():
    imgs = jnp.float32(np.stack([_rand_img(s) for s in range(4)]))
    policy = jnp.float32([[[4, 0.0, 1.0], [0, 0.0, 1.0]]])
    out = A.apply_policy_batch(imgs, policy, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(imgs))
