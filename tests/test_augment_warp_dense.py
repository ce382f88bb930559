"""The affine warp of an op slot (``ops/augment.py``): one warp branch
for the seven affine operations, addressed without a gather (a small
image as one tile, a larger one tile by tile in bounded windows), bit
for bit the pixels of the form it replaces.

The reference here is a copy of that form, kept in this file: the seven
operations as seven functions, each with its own gather, behind a
19-branch switch.  Everything is compared with ``np.array_equal``.
"""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_autoaugment_tpu.core import scopes, telemetry
from fast_autoaugment_tpu.ops import augment as A

AFFINE = ("ShearX", "ShearY", "TranslateX", "TranslateY", "Rotate",
          "TranslateXAbs", "TranslateYAbs")
OTHERS = tuple(n for n in A.OP_NAMES if n not in AFFINE)
#: a tile's window: an image of fewer pixels is one tile and its own window
WINDOW = A._warp_window_side(A._WARP_TILE)
#: the largest 64-wide image that is one tile, and the smallest that is tiled
ONE_TILE_SHAPE = ((WINDOW * WINDOW - 1) // 64, 64)
TILED_SHAPE = (ONE_TILE_SHAPE[0] + 1, 64)
SHAPES = {"32px": (32, 32), "odd": (17, 17), "nonsquare": (24, 40),
          "largest_one_tile": ONE_TILE_SHAPE, "smallest_tiled": TILED_SHAPE,
          "224px": (224, 224), "no_tile_divides": (101, 101),
          "odd_nonsquare": (225, 223), "nonsquare_tiled": (160, 224),
          "narrower_than_a_window": (40, 200)}
TILED = tuple(k for k, hw in SHAPES.items() if A._warp_tiling(*hw))


# ------------------------------------------- the form this PR replaced


def _gather_warp(img, mat):
    h, w = img.shape[0], img.shape[1]
    ys, xs = jnp.mgrid[0:h, 0:w]
    xsf, ysf = xs.astype(jnp.float32) + 0.5, ys.astype(jnp.float32) + 0.5
    sx = jnp.floor(mat[0, 0] * xsf + mat[0, 1] * ysf + mat[0, 2]).astype(jnp.int32)
    sy = jnp.floor(mat[1, 0] * xsf + mat[1, 1] * ysf + mat[1, 2]).astype(jnp.int32)
    valid = (sx >= 0) & (sx < w) & (sy >= 0) & (sy < h)
    gathered = img[jnp.clip(sy, 0, h - 1), jnp.clip(sx, 0, w - 1)]
    return jnp.where(valid[..., None], gathered, 0.0)


def _eye():
    return jnp.array([[1.0, 0.0, 0.0], [0.0, 1.0, 0.0]])


def _old_rotate(img, v, key):
    h, w = img.shape[0], img.shape[1]
    cx, cy = w / 2.0, h / 2.0
    rad = v * (np.pi / 180.0)
    ca, sa = jnp.cos(rad), jnp.sin(rad)
    mat = _eye()
    mat = mat.at[0, 0].set(ca).at[0, 1].set(-sa).at[0, 2].set(cx - ca * cx + sa * cy)
    mat = mat.at[1, 0].set(sa).at[1, 1].set(ca).at[1, 2].set(cy - sa * cx - ca * cy)
    return _gather_warp(img, mat)


OLD_AFFINE = {
    "ShearX": lambda img, v, key: _gather_warp(img, _eye().at[0, 1].set(v)),
    "ShearY": lambda img, v, key: _gather_warp(img, _eye().at[1, 0].set(v)),
    "TranslateX": lambda img, v, key: _gather_warp(
        img, _eye().at[0, 2].set(v * img.shape[1])),
    "TranslateY": lambda img, v, key: _gather_warp(
        img, _eye().at[1, 2].set(v * img.shape[0])),
    "Rotate": _old_rotate,
    "TranslateXAbs": lambda img, v, key: _gather_warp(img, _eye().at[0, 2].set(v)),
    "TranslateYAbs": lambda img, v, key: _gather_warp(img, _eye().at[1, 2].set(v)),
}


def _old_apply_op(img, op_idx, level, key):
    """``apply_op`` as it was: 19 branches, seven of them warps."""
    key_mirror, key_op = jax.random.split(key)
    low = jnp.asarray(A._OP_LOW)[op_idx]
    high = jnp.asarray(A._OP_HIGH)[op_idx]
    value = level * (high - low) + low
    sign = jnp.where(jnp.asarray(A._OP_MIRROR)[op_idx]
                     & (jax.random.uniform(key_mirror) > 0.5), -1.0, 1.0)
    value = value * sign
    branches = [OLD_AFFINE.get(name, fn) for fn, name in zip(A._OP_FNS, A.OP_NAMES)]
    return jax.lax.switch(op_idx, branches, img, value, key_op)


def _image(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 256, shape + (3,)).astype(np.float32))


def _end(name):
    """The sign flip reaches both ends of an operation's range."""
    i = A.op_index(name)
    return max(abs(float(A._OP_LOW[i])), abs(float(A._OP_HIGH[i])))


def _values(name, kind, both_signs=False):
    """Signed values of one operation: what ``apply_op`` can hand it.
    `both_signs`: one seeded value under both mirror signs, not six."""
    end = _end(name)
    if kind == "zero":
        return [0.0]
    if kind == "ends":
        return [-end, end]
    rng = np.random.default_rng(zlib.crc32(f"{name}/{kind}".encode()))
    values = [float(v) for v in rng.uniform(-end, end, 6)]
    return [values[0], -values[0]] if both_signs else values


_warp = jax.jit(A._warp_affine_nearest)


@functools.lru_cache(maxsize=None)
def _pair(name, shape):
    """(new, old) of one operation over the value, jitted where it is
    cheap.  A tiled shape runs the reference operation by operation:
    under one ``jit`` the CPU compiler may fuse the coordinates' multiply
    and add into one rounding, which moves a pixel that sits on a tie, a
    few in a 224-px rotation.  A large shape compiles one warp for the
    seven operations: an operation is its matrix into that warp, which
    the smaller shapes hold it to."""
    new = A._OP_FNS[A.op_index(name)]
    key = jax.random.PRNGKey(0)
    if A._warp_tiling(*shape) is None:
        return (jax.jit(lambda img, v: new(img, v, key)),
                jax.jit(lambda img, v: OLD_AFFINE[name](img, v, key)))
    if shape[0] * shape[1] > 128 * 128:
        def new(img, v, key):
            return _warp(img, A._AFFINE_MATRIX_FNS[name](v, *shape))
    else:
        new = jax.jit(new)
    return (lambda img, v: new(img, v, key),
            lambda img, v: OLD_AFFINE[name](img, v, key))


# ------------------------------------------------ the seven operations


@pytest.mark.parametrize("kind", ["zero", "ends", "random_a", "random_b"])
@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
@pytest.mark.parametrize("name", AFFINE)
def test_affine_op_equals_the_gather_form(name, shape, kind):
    new, old = _pair(name, SHAPES[shape])
    img = _image(SHAPES[shape], seed=len(name))
    for v in _values(name, kind, both_signs=shape in TILED):
        got, want = new(img, jnp.float32(v)), old(img, jnp.float32(v))
        assert np.array_equal(np.asarray(got), np.asarray(want)), (name, v)
        # the ends of a range move pixels: equality is not of two identities
        assert kind != "ends" or not np.array_equal(np.asarray(got),
                                                    np.asarray(img)), (name, v)


def test_the_shapes_sit_on_both_sides_of_the_choice():
    h, w = TILED_SHAPE
    assert h * w >= WINDOW * WINDOW > (h - 1) * w == np.prod(ONE_TILE_SHAPE)
    assert A._warp_tiling(*ONE_TILE_SHAPE) is None
    assert A._warp_tiling(WINDOW - 1, WINDOW) is None
    # 100 x 64: two tiles of 50 down and two of 32 across, which look their
    # columns up in the whole side: it is shorter than a window
    assert A._warp_tiling(*TILED_SHAPE) == ((50, 32), A._warp_window_side(50), 64)
    assert set(TILED) == {"smallest_tiled", "224px", "no_tile_divides",
                          "odd_nonsquare", "nonsquare_tiled", "narrower_than_a_window"}
    # CIFAR is one tile, the same program as before; every ImageNet conf
    # (224 to 380 px) and whatever is larger is tiled: no size gathers
    assert A._warp_tiling(32, 32) is None
    assert A._warp_tiling(WINDOW, WINDOW) is not None
    for side, tile in ((224, 56), (240, 48), (260, 52), (300, 50), (380, 55),
                       (448, 56), (600, 55)):
        window = A._warp_window_side(tile)
        assert A._warp_tiling(side, side) == ((tile, tile), window, window)
        assert tile <= A._WARP_TILE and -(-side // tile) == -(-side // A._WARP_TILE)
    # a side shorter than the window: the window is the whole side
    assert A._warp_tiling(20, 500) == ((20, 56), 20, A._warp_window_side(56))
    assert A._warp_tiling(40, 200) == ((40, 50), 40, A._warp_window_side(50))
    # no tile divides these: the last tiles hang over the edge
    assert A._warp_tiling(101, 101)[0] == (51, 51) and A._warp_tiling(225, 223)[0] == (45, 56)


@pytest.mark.parametrize("tile", [8, 16, 28, 32, 56])
def test_window_side_comes_from_the_op_table(tile):
    """Shear 0.3 gives 1.3 source pixels an output pixel, Rotate 30
    degrees cos + sin = 1.366: the larger, over a tile, and two more."""
    gain = max(1 + _end("ShearX"), 1 + _end("ShearY"),
               np.cos(np.deg2rad(_end("Rotate"))) + np.sin(np.deg2rad(_end("Rotate"))))
    need = int(np.floor((tile - 1) * gain)) + 2
    side = A._warp_window_side(tile)
    assert need <= side < need + 8 and side % 8 == 0


@pytest.mark.parametrize("name", OTHERS)
def test_identity_matrix_for_an_operation_that_resamples_nothing(name):
    idx = jnp.int32(A.op_index(name))
    mat = jax.jit(lambda i, v: A.op_affine_matrix(i, v, 32, 32))(idx, jnp.float32(0.7))
    assert np.array_equal(np.asarray(mat), [[1, 0, 0], [0, 1, 0]])
    img = _image((32, 32), seed=3)
    assert np.array_equal(np.asarray(A._warp_affine_nearest(img, mat)),
                          np.asarray(img))


@pytest.mark.parametrize("name", AFFINE)
def test_matrix_of_an_affine_operation_is_its_builder(name):
    idx = jnp.int32(A.op_index(name))
    v = jnp.float32(_values(name, "random_a")[0])
    mat = jax.jit(lambda i, v: A.op_affine_matrix(i, v, 24, 40))(idx, v)
    want = jax.jit(lambda v: A._AFFINE_MATRIX_FNS[name](v, 24, 40))(v)
    assert np.array_equal(np.asarray(mat), np.asarray(want))
    assert not np.array_equal(np.asarray(mat), [[1, 0, 0], [0, 1, 0]])


@pytest.mark.parametrize("shape", [(32, 32), (100, 100)], ids=["32px", "tiled"])
@pytest.mark.parametrize("name", AFFINE)
def test_same_pixels_under_highest_matmul_precision(name, shape):
    new, old = _pair(name, shape)
    img = _image(shape, seed=5)
    v = jnp.float32(_values(name, "random_b")[1])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda i, v: A._OP_FNS[A.op_index(name)](
            i, v, jax.random.PRNGKey(0)))(img, v)
    assert np.array_equal(np.asarray(got), np.asarray(old(img, v)))
    assert np.array_equal(np.asarray(got), np.asarray(new(img, v)))


# ----------------------------- the contract the exactness rests on


@pytest.mark.parametrize("level", [0.0, 0.37, 1.0])
@pytest.mark.parametrize("name", A.OP_NAMES)
def test_every_operation_returns_integers_in_0_255(name, level):
    imgs = jnp.asarray(np.random.default_rng(11).integers(
        0, 256, (6, 32, 32, 3)).astype(np.float32))
    keys = jax.random.split(jax.random.PRNGKey(2), 6)
    out = np.asarray(jax.jit(jax.vmap(
        lambda i, k: A.apply_op(i, jnp.int32(A.op_index(name)),
                                jnp.float32(level), k)))(imgs, keys))
    assert out.dtype == np.float32
    assert np.array_equal(out, np.round(out))
    assert out.min() >= 0 and out.max() <= 255


# ------------------------------------------------- whole dispatches


def _policy(seed, num_sub=12):
    """Every operation in both slots, a gate that fires and one that may not."""
    rng = np.random.default_rng(seed)
    ops = np.concatenate([rng.permutation(A.NUM_OPS), rng.permutation(A.NUM_OPS),
                          [A.op_index(n) for n in AFFINE]])[:2 * num_sub]
    pol = np.stack([ops.astype(np.float32),
                    rng.choice([1.0, 0.6], 2 * num_sub),
                    rng.uniform(0, 1, 2 * num_sub)], axis=-1)
    return jnp.asarray(pol.reshape(num_sub, 2, 3).astype(np.float32))


def _dispatch(which, imgs, policy, key):
    if which == "exact":
        return A.apply_policy_batch(imgs, policy, key)
    if which == "grouped":
        return A.apply_policy_batch_grouped(imgs, policy, key, groups=4)
    keys = jax.random.split(key, imgs.shape[0])
    return jnp.stack([
        jax.vmap(A.apply_policy_scalar_single, in_axes=(0, None, 0))(
            imgs, policy[i:i + 1], keys) for i in range(0, policy.shape[0], 3)])


@pytest.mark.parametrize("size", [32, 224])
@pytest.mark.parametrize("which", ["exact", "grouped", "scalar_single"])
def test_dispatch_gives_the_pixels_of_the_19_branch_gather_form(
        monkeypatch, which, size):
    batch = 16 if size == 32 else 4
    imgs = jnp.asarray(np.random.default_rng(size).integers(
        0, 256, (batch, size, size, 3)).astype(np.float32))
    policy, key = _policy(size), jax.random.PRNGKey(size + 1)
    got = np.asarray(jax.jit(functools.partial(_dispatch, which))(imgs, policy, key))
    monkeypatch.setattr(A, "apply_op", _old_apply_op)
    want = np.asarray(jax.jit(functools.partial(_dispatch, which))(imgs, policy, key))
    assert np.array_equal(got, want)
    assert not np.array_equal(got.reshape((-1,) + imgs.shape)[0], np.asarray(imgs))


# ------------------------------------- what a scalar index executes


def _name_stacks(jaxpr):
    """Every name stack under `jaxpr`, sub-jaxprs of its equations included."""
    out = set()
    for eqn in jaxpr.eqns:
        out.add(str(eqn.source_info.name_stack))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out |= _name_stacks(sub)
    return out


def _switches(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _switches(sub, found)
    return found


def _op_switch(fn, *args):
    """The 13-branch switch of ``apply_op`` in the program `fn` traces to."""
    conds = [e for e in _switches(jax.make_jaxpr(fn)(*args).jaxpr)
             if len(e.params["branches"]) == len(A._BRANCHES)]
    assert len(conds) == 1
    return [_name_stacks(b.jaxpr) for b in conds[0].params["branches"]]


def _holds(stacks, scope):
    return any(scope in s for s in stacks)


def test_scalar_index_switch_has_one_warp_branch_and_twelve_without():
    img, key = _image((32, 32)), jax.random.PRNGKey(0)
    branches = _op_switch(A.apply_op, img, jnp.int32(6), jnp.float32(0.5), key)
    assert len(branches) == 13
    warp = [i for i, stacks in enumerate(branches) if _holds(stacks, scopes.AUG_WARP)]
    assert warp == [len(A._BRANCHES) - 1]
    assert sorted(set(A._BRANCH_OF.tolist())) == list(range(13))
    for name in A.OP_NAMES:
        stacks = branches[A._BRANCH_OF[A.op_index(name)]]
        assert _holds(stacks, scopes.AUG_WARP) == (name in AFFINE), name
        # an operation's own scope: its branch, or its matrix in the warp's
        assert _holds(stacks, scopes.aug_op(name)), name


@pytest.mark.parametrize("name", OTHERS)
def test_scalar_index_under_a_batch_vmap_keeps_the_switch(name):
    """``apply_policy_scalar_single``'s shape: images batched, op index
    not, so the switch stays a switch and `name`'s branch holds no warp."""
    imgs = jnp.stack([_image((32, 32), seed=s) for s in range(3)])
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    idx = jnp.int32(A.op_index(name))
    branches = _op_switch(
        jax.vmap(A.apply_op, in_axes=(0, None, None, 0)), imgs, idx,
        jnp.float32(0.5), keys)
    assert not _holds(branches[A._BRANCH_OF[A.op_index(name)]], scopes.AUG_WARP)
    assert _holds(branches[-1], scopes.AUG_WARP)


def _pixel_gathers(text):
    """Gathers of one pixel a row: what ``img[sy, sx]`` lowers to."""
    return [line for line in text.splitlines()
            if "stablehlo.gather" in line and "slice_sizes = array<i64: 1, 1, 1, 3>" in line]


def test_batched_index_runs_the_warp_once_an_op_slot(monkeypatch):
    """Select-all: no switch is left, and the lowered program holds one
    resampling (two products) an op slot where it held seven gathers."""
    imgs = jnp.stack([_image((32, 32), seed=s) for s in range(4)])
    policy, key = _policy(1), jax.random.PRNGKey(0)
    jaxpr = jax.make_jaxpr(A.apply_policy_batch)(imgs, policy, key).jaxpr
    assert not [e for e in _switches(jaxpr)
                if len(e.params["branches"]) == len(A._BRANCHES)]
    text = jax.jit(A.apply_policy_batch).lower(imgs, policy, key).as_text()
    products = [line for line in text.splitlines() if "stablehlo.dot_general" in line]
    # Equalize's two (its histogram and its table, [C, 16, 16] each) are not the warp's
    warps = [line for line in products if "x16x16x" not in line]
    assert len(warps) == 2 * policy.shape[1]
    assert len(products) - len(warps) == 2 * policy.shape[1]
    assert not _pixel_gathers(text)
    monkeypatch.setattr(A, "apply_op", _old_apply_op)
    old = jax.jit(lambda *a: A.apply_policy_batch(*a)).lower(imgs, policy, key).as_text()
    assert len(_pixel_gathers(old)) == 7 * policy.shape[1]
    assert scopes.AUG_WARP in jax.jit(A.apply_policy_batch).lower(
        imgs, policy, key).as_text(debug_info=True)


# --------------------------------------- the batch the dense form sees


def _warp_batch(imgs, mats):
    return jax.jit(jax.vmap(A._warp_affine_nearest))(imgs, mats)


def _matrices(n, h, w, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.stack([
        A._AFFINE_MATRIX_FNS[AFFINE[i % 7]](
            jnp.float32(_values(AFFINE[i % 7], "ends")[1] * rng.uniform(-1, 1)), h, w)
        for i in range(n)])


@pytest.mark.parametrize("size", [32, 90], ids=["one_tile", "tiled"])
@pytest.mark.parametrize("images_a_chunk", [1, 4, 5, 13, 14])
def test_chunks_of_the_batch_give_the_same_pixels(monkeypatch, images_a_chunk, size):
    imgs = jnp.stack([_image((size, size), seed=s) for s in range(13)])
    mats = _matrices(13, size, size)
    want = np.asarray(jax.vmap(_gather_warp)(imgs, mats))
    monkeypatch.setattr(A, "_DENSE_WARP_BUDGET_BYTES",
                        images_a_chunk * A._warp_bytes_an_image(size, size, 3))
    text = jax.jit(jax.vmap(A._warp_affine_nearest)).lower(imgs, mats).as_text()
    assert ("stablehlo.while" in text) == (images_a_chunk < 13)
    assert np.array_equal(np.asarray(_warp_batch(imgs, mats)), want)


def test_a_chunk_is_never_one_image(monkeypatch):
    """A budget under one image's rows still runs two at a time: alone on
    the chip a chunk of one runs at 0.4 of the rate (PERF.md section 6)."""
    imgs = jnp.stack([_image((90, 90), seed=s) for s in range(6)])
    mats = _matrices(6, 90, 90)
    monkeypatch.setattr(A, "_DENSE_WARP_BUDGET_BYTES", 1)
    text = jax.jit(jax.vmap(A._warp_affine_nearest)).lower(imgs, mats).as_text()
    (th, tw), sh, _ = A._warp_tiling(90, 90)
    assert "stablehlo.while" in text
    assert f"tensor<2x4x{th * tw}x{sh}xbf16>" in text  # [chunk, tiles, T*T, Sh]
    assert f"tensor<1x4x{th * tw}x{sh}xbf16>" not in text
    want = np.asarray(jax.vmap(_gather_warp)(imgs, mats))
    assert np.array_equal(np.asarray(_warp_batch(imgs, mats)), want)


def test_bytes_an_image_are_the_rows_picked():
    assert A._warp_bytes_an_image(32, 32, 3) == 32 * 32 * 32 * 3 * 2
    (th, tw), sh, sw = A._warp_tiling(224, 224)
    tiles = (224 // th) * (224 // tw)
    assert A._warp_bytes_an_image(224, 224, 3) == tiles * (
        th * tw * sw * 3 + sh * 224 * 3) * 2
    # what tiling is for: a 224-px image keeps 2/5 of what one tile would
    assert A._warp_bytes_an_image(224, 224, 3) * 2.5 < 224 * 224 * 224 * 3 * 2


@pytest.mark.parametrize("size", [17, 90], ids=["one_tile", "tiled"])
def test_nested_vmaps_fold_into_one_batch(monkeypatch, size):
    """Draws x images (the TTA program's shape), matrices shared by the
    draws: one resampling over 3 x 5 images, chunked as one batch."""
    imgs = jnp.stack([_image((size, size), seed=s) for s in range(15)]).reshape(
        3, 5, size, size, 3)
    mats = _matrices(5, size, size)
    fn = jax.vmap(jax.vmap(A._warp_affine_nearest), in_axes=(0, None))
    want = np.asarray(jax.vmap(jax.vmap(_gather_warp), in_axes=(0, None))(imgs, mats))
    assert np.array_equal(np.asarray(jax.jit(fn)(imgs, mats)), want)
    text = jax.jit(fn).lower(imgs, mats).as_text()
    if size == 17:
        assert text.count("stablehlo.dot_general") == 2
        assert "15x289x17" in text  # [N, H*W, H] one-hot of the whole batch
    else:  # two products fetch the windows, two resample inside them
        assert text.count("stablehlo.dot_general") == 4
        (th, tw), sh, sw = A._warp_tiling(size, size)
        assert f"15x4x{th * tw}x{sh}x" in text  # [N, tiles, T*T, Sh] one-hot
    monkeypatch.setattr(A, "_DENSE_WARP_BUDGET_BYTES",
                        4 * A._warp_bytes_an_image(size, size, 3))
    assert np.array_equal(np.asarray(jax.jit(fn)(imgs, mats)), want)


# ------------------------------------------- a window holds its tile


def _np_matrix(name, v, h, w):
    """The seven matrices in NumPy float32, as ``ops/augment.py`` builds them."""
    v = np.float32(v)
    mat = np.array([[1, 0, 0], [0, 1, 0]], np.float32)
    if name == "Rotate":
        cx, cy = np.float32(w / 2.0), np.float32(h / 2.0)
        rad = v * np.float32(np.pi / 180.0)
        ca, sa = np.cos(rad), np.sin(rad)
        return np.array([[ca, -sa, cx - ca * cx + sa * cy],
                         [sa, ca, cy - sa * cx - ca * cy]], np.float32)
    row, col, scale = {"ShearX": (0, 1, 1), "ShearY": (1, 0, 1),
                       "TranslateX": (0, 2, w), "TranslateY": (1, 2, h),
                       "TranslateXAbs": (0, 2, 1), "TranslateYAbs": (1, 2, 1)}[name]
    mat[row, col] = v * np.float32(scale)
    return mat


def _np_indices(mat, h, w):
    ys, xs = np.mgrid[0:h, 0:w]
    xsf, ysf = xs.astype(np.float32) + np.float32(0.5), ys.astype(np.float32) + np.float32(0.5)
    sx = np.floor(mat[0, 0] * xsf + mat[0, 1] * ysf + mat[0, 2]).astype(np.int32)
    sy = np.floor(mat[1, 0] * xsf + mat[1, 1] * ysf + mat[1, 2]).astype(np.int32)
    return sy, sx


def _np_tiles(idx, th, tw):
    """``[H, W]`` -> ``[tiles, th * tw]``, the edge repeated where no tile divides."""
    h, w = idx.shape
    rows, cols = -(-h // th), -(-w // tw)
    idx = np.pad(idx, ((0, rows * th - h), (0, cols * tw - w)), mode="edge")
    return idx.reshape(rows, th, cols, tw).transpose(0, 2, 1, 3).reshape(-1, th * tw)


@pytest.mark.parametrize("shape", [(224, 224), (260, 260), (380, 380), (160, 224),
                                   (225, 223), (40, 200)],
                         ids=lambda hw: f"{hw[0]}x{hw[1]}")
@pytest.mark.parametrize("name", AFFINE)
def test_window_holds_every_source_index_of_its_tile(name, shape):
    """Pure NumPy: at both ends of the range, in every tile of the grid,
    each source index inside the image falls inside the tile's clamped
    window, and each outside the image falls outside the window."""
    (th, tw), sh, sw = A._warp_tiling(*shape)
    for v in (-_end(name), _end(name)):
        sy, sx = _np_indices(_np_matrix(name, v, *shape), *shape)
        for idx, size, side in ((sy, shape[0], sh), (sx, shape[1], sw)):
            tiles = _np_tiles(idx, th, tw)
            origin = np.clip(tiles.min(axis=1), 0, size - side)[:, None]
            inside = (tiles >= 0) & (tiles < size)
            in_window = (tiles >= origin) & (tiles < origin + side)
            assert np.array_equal(inside, in_window), (name, v)
            if side < size:  # a whole side holds whatever lies inside the image
                assert (tiles.max(axis=1) - tiles.min(axis=1)).max() < side
        assert _fits(_np_matrix(name, v, *shape), shape)


def test_np_matrices_are_the_builders():
    for name in AFFINE:
        for v in (-_end(name), 0.37 * _end(name)):
            want = np.asarray(A._AFFINE_MATRIX_FNS[name](jnp.float32(v), 224, 160))
            np.testing.assert_allclose(_np_matrix(name, v, 224, 160), want,
                                       rtol=1e-6, atol=1e-6)


def _fits(mat, shape):
    sy, sx = _np_indices(np.asarray(mat), *shape)
    return bool(A._fits_windows(jnp.asarray(sy)[None], jnp.asarray(sx)[None],
                                *A._warp_tiling(*shape)))


@pytest.mark.parametrize("name,v,fits", [
    ("ShearX", 0.3, True), ("ShearY", -0.3, True), ("Rotate", 30.0, True),
    ("TranslateX", 7.5, True),  # a translation of any length moves the window
    ("ShearX", 1.0, False), ("ShearY", -2.0, False), ("ShearX", 9 * 0.6 - 0.3, False),
    ("Rotate", 9 * 60.0 - 30.0, True)])  # a rotation of any angle spans under 1.42
def test_matrix_beyond_the_bound_is_gathered_and_right(name, v, fits):
    """The named functions take any value, and a policy tensor is an
    input whose levels nothing checks (``autoaug_policy`` holds levels up
    to 9, on operations that ignore them): where a tile's indices leave
    its window the batch takes the gather, and the pixels are the
    reference's either way."""
    shape = (100, 120)
    mat = A._AFFINE_MATRIX_FNS[name](jnp.float32(v), *shape)
    assert _fits(mat, shape) == fits
    img = _image(shape, seed=9)
    got = _warp(img, mat)
    assert np.array_equal(np.asarray(got), np.asarray(_gather_warp(img, mat)))
    assert not np.array_equal(np.asarray(got), np.asarray(img))


def test_one_matrix_beyond_the_bound_gathers_its_batch():
    shape = (90, 90)
    imgs = jnp.stack([_image(shape, seed=s) for s in range(4)])
    mats = _matrices(4, *shape).at[2].set(
        A.shear_x_matrix(jnp.float32(1.5), *shape))
    want = np.asarray(jax.vmap(_gather_warp)(imgs, mats))
    assert np.array_equal(np.asarray(_warp_batch(imgs, mats)), want)
    text = jax.jit(jax.vmap(A._warp_affine_nearest)).lower(imgs, mats).as_text()
    assert "stablehlo.case" in text or "stablehlo.if" in text
    assert len(_pixel_gathers(text)) == 1  # the other branch of the choice


# ------------------------------------- the 32-px program did not change


def _parent_dense_one(img, sy, sx):
    """The dense form as it was before tiles: the image is the window."""
    h, w, c = img.shape
    contract = (((1,), (0,)), ((), ()))
    rows = jax.lax.dot_general(
        (sy.reshape(-1, 1) == jnp.arange(h)).astype(jnp.bfloat16),
        img.reshape(h, w * c).astype(jnp.bfloat16),
        contract, preferred_element_type=jnp.bfloat16)
    lane = jnp.arange(w * c)
    masked = jnp.where(sx.reshape(-1, 1) == lane // c, rows, 0)
    channel_of = (lane[:, None] % c == jnp.arange(c)).astype(jnp.bfloat16)
    out = jax.lax.dot_general(masked, channel_of, contract,
                              preferred_element_type=jnp.float32)
    return out.reshape(h, w, c)


def _parent_warp(img, mat):
    h, w = img.shape[0], img.shape[1]
    ys, xs = jnp.mgrid[0:h, 0:w]
    xsf, ysf = xs.astype(jnp.float32) + 0.5, ys.astype(jnp.float32) + 0.5
    sx = jnp.floor(mat[0, 0] * xsf + mat[0, 1] * ysf + mat[0, 2]).astype(jnp.int32)
    sy = jnp.floor(mat[1, 0] * xsf + mat[1, 1] * ysf + mat[1, 2]).astype(jnp.int32)
    return _parent_dense_one(img, sy, sx)


def test_the_32_px_program_is_the_one_it_was():
    """A CIFAR step's batch: the two products of a warp (twice in the
    text: the chunks of a `lax.map` and what is left over), no gather,
    no choice at run time, no window fetched; the parent's pixels."""
    imgs = jnp.asarray(np.random.default_rng(4).integers(
        0, 256, (2048, 32, 32, 3)).astype(np.float32))
    mats = jnp.tile(_matrices(16, 32, 32, seed=4), (128, 1, 1))
    text = jax.jit(jax.vmap(A._warp_affine_nearest)).lower(imgs, mats).as_text()
    assert text.count("stablehlo.dot_general") == 4
    assert "gather" not in text and "stablehlo.case" not in text
    assert "stablehlo.if" not in text and "stablehlo.transpose" not in text
    for shape in ("1365x1024x32", "1365x1024x96", "683x1024x32", "683x1024x96"):
        assert f"tensor<{shape}xbf16>" in text  # [N, H*W, H] and [N, H*W, W*C]
    unchunked = jax.jit(jax.vmap(A._warp_affine_nearest)).lower(
        imgs[:64], mats[:64]).as_text()
    assert unchunked.count("stablehlo.dot_general") == 2
    assert "stablehlo.while" in text and "stablehlo.while" not in unchunked
    got = np.asarray(_warp_batch(imgs[:256], mats[:256]))
    want = np.asarray(jax.jit(jax.vmap(_parent_warp))(imgs[:256], mats[:256]))
    assert np.array_equal(got, want)


# -------------------------------------------------------- the counter


@pytest.mark.parametrize("shape,form,tile", [
    ((30, 30), "dense", "30x30"), ((18, 22), "dense", "18x22"),
    ((79, 80), "dense", "79x80"), ((82, 82), "tiled", "41x56"),
    ((448, 448), "tiled", "56x80"), ((240, 240), "tiled", "48x72"),
    ((20, 500), "tiled", "20x56x20x80")])
def test_counter_says_which_addressing_a_program_got(shape, form, tile):
    """Shapes no other test traces: JAX keeps a switch's traced branches,
    so the counter counts a shape's first trace in a process, not each
    program."""
    def count(f):
        return telemetry.registry().counter(
            "faa_aug_warp_traces_total", form=f,
            image=f"{shape[0]}x{shape[1]}", tile=tile).value

    other = "tiled" if form == "dense" else "dense"
    before, before_other = count(form), count(other)
    imgs = jnp.zeros((2,) + shape + (3,), jnp.float32)
    jax.make_jaxpr(A.apply_policy_batch)(imgs, _policy(0, num_sub=2),
                                         jax.random.PRNGKey(0))
    # one warp branch, whatever the 19 operations of the switch
    assert 1 <= count(form) - before <= 2
    assert count(other) == before_other
