"""Equalize and AutoContrast in their dense forms against the plain reference.

``ops/augment.py`` computes both with no sort, search, gather or scatter
(those are what the TPU runs slowest): AutoContrast by arithmetic on each
pixel, Equalize by addressing the 256 levels as 16 x 16, two one-hots
through the MXU.  The reference below is the sort + searchsorted + gather
construction they replaced, kept here verbatim; the form between the two,
a compare of every pixel against all 256 levels and a select, is kept
verbatim as a second reference for Equalize.  The dense forms must give
the same float32 pixels element for element, and their jaxprs must stay
free of data-dependent addressing.  The random crop of the fixed
stack (``ops/preprocess.py``) is held to the same two rules at the end.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fast_autoaugment_tpu.ops import augment as A
from fast_autoaugment_tpu.ops import preprocess

KEY = jax.random.PRNGKey(0)
V = jnp.float32(0)


# ---------------------------------------------------------------------------
# the plain reference: sorted pixels, binary-searched bin edges, gathered LUT
# ---------------------------------------------------------------------------


def _ref_apply_lut(img, lut):
    ii = A._to_int(img)
    out = jnp.stack([lut[c][ii[..., c]] for c in range(img.shape[-1])], axis=-1)
    return out.astype(jnp.float32)


def _ref_histogram256(channel_int):
    flat = channel_int.reshape(-1)
    s = jnp.sort(flat)
    edges = jnp.arange(257, dtype=jnp.int32)
    ranks = jnp.searchsorted(s, edges, side="left").astype(jnp.int32)
    return jnp.diff(ranks)


def _ref_auto_contrast(img, v, key):
    ii = A._to_int(img)
    lo = ii.min(axis=(0, 1))
    hi = ii.max(axis=(0, 1))
    ix = jnp.arange(256, dtype=jnp.int32)
    span = jnp.maximum(hi - lo, 1)
    lut = jnp.clip((ix[None, :] - lo[:, None]) * 255 // span[:, None], 0, 255)
    identity = hi <= lo
    lut = jnp.where(identity[:, None], ix[None, :], lut)
    return _ref_apply_lut(img, lut)


def _ref_equalize(img, v, key):
    ii = A._to_int(img)

    def one_channel(ch):
        h = _ref_histogram256(ch)
        total = jnp.sum(h)
        nonzero = h > 0
        num_nonzero = jnp.sum(nonzero)
        last_idx = 255 - jnp.argmax(nonzero[::-1])
        h_last = h[last_idx]
        step = (total - h_last) // 255
        csum = jnp.cumsum(h) - h
        n = step // 2 + csum
        lut = jnp.clip(n // jnp.maximum(step, 1), 0, 255)
        ix = jnp.arange(256, dtype=jnp.int32)
        use_identity = (num_nonzero <= 1) | (step == 0)
        return jnp.where(use_identity, ix, lut)

    lut = jnp.stack([one_channel(ii[..., c]) for c in range(img.shape[-1])])
    return _ref_apply_lut(img, lut)


# ---------------------------------------------------------------------------
# the second reference: Equalize as it stood before the two levels, a compare
# of every pixel against all 256 levels, twice (the counts, then the table)
# ---------------------------------------------------------------------------

_LEVELS = np.arange(256, dtype=np.int32)


def _compare_select_count_below(ii):
    flat = ii.reshape(-1, ii.shape[-1])
    return jnp.sum(flat[None, :, :] < _LEVELS[:, None, None], axis=1,
                   dtype=jnp.int32)


def _compare_select_equalize(img, v, key):
    ii = A._to_int(img)
    below = _compare_select_count_below(ii)  # [256, C]
    # the last nonzero bin is the channel maximum's
    h_last = jnp.sum(ii == ii.max(axis=(0, 1)), axis=(0, 1), dtype=jnp.int32)
    step = (ii.shape[0] * ii.shape[1] - h_last) // 255
    lut = jnp.clip((step // 2 + below) // jnp.maximum(step, 1), 0, 255)
    # a single nonzero bin holds every pixel, so it gives step == 0 too
    lut = jnp.where(step == 0, _LEVELS[:, None], lut)
    # lut[p] without a gather: select each pixel's level, reduce over levels
    picked = jnp.where(ii[None] == _LEVELS[:, None, None, None],
                       lut[:, None, None, :], 0)
    return jnp.sum(picked, axis=0, dtype=jnp.int32).astype(jnp.float32)


# ---------------------------------------------------------------------------
# inputs: each case draws one [H, W, 3] float32 image from a generator
# ---------------------------------------------------------------------------


def _random(rng, h=32, w=32):
    return rng.integers(0, 256, (h, w, 3)).astype(np.float32)


def _constant(rng):
    return np.full((32, 32, 3), rng.integers(0, 256), np.float32)


def _constant_channel(rng):
    img = _random(rng)
    img[..., rng.integers(0, 3)] = rng.integers(0, 256)
    return img


def _two_valued(rng):
    a, b = rng.choice(256, 2, replace=False)
    return np.where(rng.random((32, 32, 3)) < 0.5, a, b).astype(np.float32)


def _three_adjacent(rng):
    base = rng.integers(0, 254)
    return (base + rng.integers(0, 3, (32, 32, 3))).astype(np.float32)


def _skewed_step_zero(rng):
    # the last nonzero bin holds > 769 of the 1,024 pixels, so PIL's
    # step = (1024 - h_last) // 255 is 0 and the channel passes unchanged
    top = rng.integers(100, 256)
    img = np.full((1024, 3), top, np.float32)
    for c in range(3):
        img[rng.choice(1024, 200, replace=False), c] = rng.integers(0, top, 200)
    return img.reshape(32, 32, 3)


def _skewed_low_level(rng):
    # as heavy a bin, but not the last one: step stays above 0
    img = _random(rng).reshape(-1, 3)
    img[rng.choice(1024, 800, replace=False)] = rng.integers(0, 100)
    return img.reshape(32, 32, 3)


def _float_out_of_range(rng):
    # non-integral and outside [0, 255]: what _to_int clips and truncates
    return rng.uniform(-40.0, 300.0, (32, 32, 3)).astype(np.float32)


CASES = {
    **{f"random{seed}": _random for seed in range(5)},
    "constant": _constant,
    "constant_channel": _constant_channel,
    "two_valued": _two_valued,
    "three_adjacent": _three_adjacent,
    "skewed_step_zero": _skewed_step_zero,
    "skewed_low_level": _skewed_low_level,
    "8x8": lambda rng: _random(rng, 8, 8),
    "224x224": lambda rng: _random(rng, 224, 224),
    "float_out_of_range": _float_out_of_range,
}


def _draw(case, count):
    rng = np.random.default_rng(sorted(CASES).index(case))
    return jnp.asarray(np.stack([CASES[case](rng) for _ in range(count)]))


OPS = {
    "equalize": (A.equalize, _ref_equalize),
    "auto_contrast": (A.auto_contrast, _ref_auto_contrast),
    "equalize_vs_compare_select": (A.equalize, _compare_select_equalize),
}
EQUALIZE_REFS = {"sort": _ref_equalize, "compare_select": _compare_select_equalize}


def _batched(fn):
    return jax.jit(jax.vmap(fn, in_axes=(0, None, None)))


def _assert_identical(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype == np.float32
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("op", OPS)
def test_dense_form_equals_reference_under_jit(op, case):
    new, ref = OPS[op]
    img = _draw(case, 1)[0]
    _assert_identical(jax.jit(new)(img, V, KEY), jax.jit(ref)(img, V, KEY))


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("op", OPS)
def test_dense_form_equals_reference_under_vmap(op, case):
    new, ref = OPS[op]
    imgs = _draw(case, 16)
    _assert_identical(_batched(new)(imgs, V, KEY), _batched(ref)(imgs, V, KEY))


def _heavy_bin_380(rng):
    # 380 x 380 = 144,400 pixels, 97% of them one value: a bin over 65,536,
    # which float32 counts exactly and bfloat16 (or 16 bits) would not
    img = rng.integers(0, 256, (380, 380, 3)).astype(np.float32)
    heavy = rng.random((380, 380, 3)) < 0.97
    return np.where(heavy, rng.integers(0, 256, 3).astype(np.float32), img)


@pytest.mark.parametrize("ref", EQUALIZE_REFS)
def test_equalize_at_380_px_with_a_bin_over_65536(ref):
    rng = np.random.default_rng(380)
    imgs = jnp.asarray(np.stack([_heavy_bin_380(rng), _heavy_bin_380(rng)]))
    counts = np.bincount(np.asarray(imgs[0, ..., 0], np.int64).ravel())
    assert counts.max() > 65536
    _assert_identical(_batched(A.equalize)(imgs, V, KEY),
                      _batched(EQUALIZE_REFS[ref])(imgs, V, KEY))
    _assert_identical(jax.jit(A.equalize)(imgs[0], V, KEY),
                      jax.jit(EQUALIZE_REFS[ref])(imgs[0], V, KEY))


@pytest.mark.parametrize("ref", EQUALIZE_REFS)
def test_equalize_over_a_batch_of_2048_at_32_px(ref):
    # the batch `wrn40x2_train` augments in one step; every kind of image in it
    kinds = sorted(set(CASES) - {"8x8", "224x224"})
    rng = np.random.default_rng(2048)
    imgs = jnp.asarray(np.stack(
        [CASES[kinds[i % len(kinds)]](rng) for i in range(2048)]))
    _assert_identical(_batched(A.equalize)(imgs, V, KEY),
                      _batched(EQUALIZE_REFS[ref])(imgs, V, KEY))


@pytest.mark.parametrize("case", ["random0", "skewed_low_level",
                                  "skewed_step_zero", "224x224",
                                  "float_out_of_range"])
@pytest.mark.parametrize("precision", ["bfloat16", "float32", "highest"])
def test_equalize_is_exact_under_every_ambient_matmul_precision(precision, case):
    # the float32 check of `shake26_2x96d_train` runs the policy under
    # "highest"; the products state their operand types, so none of it shows
    imgs = _draw(case, 4)
    want = _batched(_compare_select_equalize)(imgs, V, KEY)
    with jax.default_matmul_precision(precision):
        got = _batched(A.equalize)(imgs, V, KEY)
        one = jax.jit(A.equalize)(imgs[0], V, KEY)
    _assert_identical(got, want)
    _assert_identical(one, want[0])


@pytest.mark.parametrize("case", CASES)
def test_count_below_differences_are_the_histogram(case):
    ii = A._to_int(_draw(case, 1)[0])
    hist = A._histogram256(*A._nibble_one_hots(ii))  # [C, 16, 16]: bin 16a + b
    below = np.asarray(A._count_below(hist))  # [C, 256]
    hist = np.asarray(hist).reshape(3, 256)
    assert hist.dtype == below.dtype == np.int32
    pixels = ii.shape[0] * ii.shape[1]
    diffs = np.diff(below, axis=1, append=np.full((3, 1), pixels, np.int32))
    np.testing.assert_array_equal(
        below.T, _compare_select_count_below(ii))  # as it was counted before
    for c in range(3):
        np.testing.assert_array_equal(hist[c], _ref_histogram256(ii[..., c]))
        np.testing.assert_array_equal(diffs[c], hist[c])


def test_skewed_case_really_has_step_zero():
    ii = np.asarray(A._to_int(_draw("skewed_step_zero", 1)[0]))
    for c in range(3):
        assert (ii[..., c] == ii[..., c].max()).sum() > 769


# ---------------------------------------------------------------------------
# structure: no data-dependent addressing under either operation
# ---------------------------------------------------------------------------

# `jnp.searchsorted`'s binary search is a `scan` in the jaxpr (a `while` in
# older lowerings): both are the loop of gathers the TPU pays for
_FORBIDDEN = ("sort", "gather", "scatter", "while", "scan")


def _equations(jaxpr):
    """Every equation in `jaxpr` and in the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _primitive_names(jaxpr):
    return (eqn.primitive.name for eqn in _equations(jaxpr))


def _vmapped_equations(fn, imgs):
    closed = jax.make_jaxpr(jax.vmap(fn, in_axes=(0, None, None)))(imgs, V, KEY)
    return list(_equations(closed.jaxpr))


def _forbidden_in(fn, imgs):
    return sorted({eqn.primitive.name for eqn in _vmapped_equations(fn, imgs)
                   if any(word in eqn.primitive.name for word in _FORBIDDEN)})


@pytest.mark.parametrize("op", OPS)
def test_no_sort_gather_scatter_or_loop(op):
    assert _forbidden_in(OPS[op][0], _draw("random0", 4)) == []


def _largest_output(fn, imgs):
    """(primitive names, elements of the largest output) of vmapped `fn`."""
    eqns = _vmapped_equations(fn, imgs)
    return ({eqn.primitive.name for eqn in eqns},
            max(int(np.prod(var.aval.shape)) for eqn in eqns for var in eqn.outvars))


def test_equalize_is_two_products_and_nothing_256_wide_over_the_pixels():
    imgs = _draw("random0", 4)
    all_levels = 256 * imgs.shape[0] * imgs.shape[1] * imgs.shape[2]
    names, largest = _largest_output(A.equalize, imgs)
    assert "dot_general" in names
    assert largest < all_levels  # two 16-wide one-hots a channel: 48 a pixel
    # the same walk over the form it replaced finds what it is there to keep out
    names, largest = _largest_output(_compare_select_equalize, imgs)
    assert "dot_general" not in names and largest >= all_levels


def test_the_walk_sees_what_the_reference_holds():
    # the same walk over the reference finds what it is there to keep out
    found = _forbidden_in(_ref_equalize, _draw("random0", 4))
    assert {"sort", "gather"} <= set(found)
    assert "scan" in found or "while" in found
    assert "gather" in _forbidden_in(_ref_auto_contrast, _draw("random0", 4))


# ---------------------------------------------------------------------------
# the random crop: static slices and selects against the dynamic_slice it was
# ---------------------------------------------------------------------------


def _ref_random_crop_with_pad(img, key, pad=4):
    h, w, c = img.shape
    padded = jnp.pad(img, ((pad, pad), (pad, pad), (0, 0)))
    ky, kx = jax.random.split(key)
    oy = jax.random.randint(ky, (), 0, 2 * pad + 1)
    ox = jax.random.randint(kx, (), 0, 2 * pad + 1)
    return jax.lax.dynamic_slice(padded, (oy, ox, 0), (h, w, c))


@pytest.mark.parametrize("size,pad", [(32, 4), (8, 4), (17, 2), (224, 4)])
def test_crop_equals_dynamic_slice(size, pad):
    # 128 keys: every one of the (2 * pad + 1) ** 2 offsets is likely drawn
    imgs = jnp.asarray(np.random.default_rng(size).uniform(
        0, 255, (128, size, size, 3)).astype(np.float32))
    keys = jax.random.split(jax.random.PRNGKey(size), 128)
    batched = lambda fn: jax.jit(jax.vmap(lambda im, k: fn(im, k, pad)))
    _assert_identical(batched(preprocess.random_crop_with_pad)(imgs, keys),
                      batched(_ref_random_crop_with_pad)(imgs, keys))


def test_crop_holds_no_gather_or_loop():
    imgs, keys = _draw("random0", 4), jax.random.split(KEY, 4)
    names = lambda fn: set(_primitive_names(
        jax.make_jaxpr(jax.vmap(fn))(imgs, keys).jaxpr))
    dense = names(preprocess.random_crop_with_pad)
    assert not any(w in n for n in dense for w in _FORBIDDEN + ("dynamic_slice",))
    assert "gather" in names(_ref_random_crop_with_pad)
