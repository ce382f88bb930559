"""Equalize and AutoContrast in their dense forms against the plain reference.

``ops/augment.py`` computes both with compares and reduces over the 256
levels (no sort, search, gather or scatter: those are what the TPU runs
slowest).  The reference below is the sort + searchsorted + gather
construction they replaced, kept here verbatim; the dense forms must give
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
}


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
    batched = lambda fn: jax.jit(jax.vmap(fn, in_axes=(0, None, None)))
    _assert_identical(batched(new)(imgs, V, KEY), batched(ref)(imgs, V, KEY))


@pytest.mark.parametrize("case", CASES)
def test_count_below_differences_are_the_histogram(case):
    ii = A._to_int(_draw(case, 1)[0])
    below = np.asarray(A._count_below(ii))  # [256, C]
    assert below.dtype == np.int32
    pixels = ii.shape[0] * ii.shape[1]
    hist = np.diff(below, axis=0, append=np.full((1, 3), pixels, np.int32))
    for c in range(3):
        np.testing.assert_array_equal(hist[:, c], _ref_histogram256(ii[..., c]))


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


def _primitive_names(jaxpr):
    """Every primitive name in `jaxpr` and the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _primitive_names(sub)


def _forbidden_in(fn, imgs):
    closed = jax.make_jaxpr(jax.vmap(fn, in_axes=(0, None, None)))(imgs, V, KEY)
    return sorted({name for name in _primitive_names(closed.jaxpr)
                   if any(word in name for word in _FORBIDDEN)})


@pytest.mark.parametrize("op", OPS)
def test_no_sort_gather_scatter_or_loop(op):
    assert _forbidden_in(OPS[op][0], _draw("random0", 4)) == []


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
