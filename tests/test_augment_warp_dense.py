"""The affine warp of an op slot (``ops/augment.py``): one warp branch
for the seven affine operations, addressed without a gather at CIFAR
size, bit for bit the pixels of the form it replaces.

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
#: the smallest 64-wide image that keeps the gather
GATHER_SHAPE = (A._DENSE_WARP_MAX_PIXELS // 64 + 1, 64)
SHAPES = {"32px": (32, 32), "odd": (17, 17), "nonsquare": (24, 40),
          "smallest_gather": GATHER_SHAPE}


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


def _values(name, kind):
    """Signed values of one operation: what ``apply_op`` can hand it."""
    i = A.op_index(name)
    lo, hi = float(A._OP_LOW[i]), float(A._OP_HIGH[i])
    end = max(abs(lo), abs(hi))  # the sign flip reaches both ends
    if kind == "zero":
        return [0.0]
    if kind == "ends":
        return [-end, end]
    rng = np.random.default_rng(zlib.crc32(f"{name}/{kind}".encode()))
    return [float(v) for v in rng.uniform(-end, end, 6)]


@functools.lru_cache(maxsize=None)
def _pair(name, shape):
    """(new, old) of one operation, jitted over the value."""
    new = A._OP_FNS[A.op_index(name)]
    key = jax.random.PRNGKey(0)
    return (jax.jit(lambda img, v: new(img, v, key)),
            jax.jit(lambda img, v: OLD_AFFINE[name](img, v, key)))


# ------------------------------------------------ the seven operations


@pytest.mark.parametrize("kind", ["zero", "ends", "random_a", "random_b"])
@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
@pytest.mark.parametrize("name", AFFINE)
def test_affine_op_equals_the_gather_form(name, shape, kind):
    new, old = _pair(name, SHAPES[shape])
    img = _image(SHAPES[shape], seed=len(name))
    for v in _values(name, kind):
        got, want = new(img, jnp.float32(v)), old(img, jnp.float32(v))
        assert np.array_equal(np.asarray(got), np.asarray(want)), (name, v)
        # the ends of a range move pixels: equality is not of two identities
        assert kind != "ends" or not np.array_equal(np.asarray(got),
                                                    np.asarray(img)), (name, v)


def test_the_shapes_sit_on_both_sides_of_the_choice():
    h, w = GATHER_SHAPE
    assert h * w > A._DENSE_WARP_MAX_PIXELS >= (h - 1) * w
    for key, (h, w) in SHAPES.items():
        assert (h * w > A._DENSE_WARP_MAX_PIXELS) == (key == "smallest_gather")
    # CIFAR and every ImageNet conf (224 to 380 px) resample densely
    assert 448 * 448 > A._DENSE_WARP_MAX_PIXELS >= 380 * 380


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


@pytest.mark.parametrize("name", AFFINE)
def test_same_pixels_under_highest_matmul_precision(name):
    new, old = _pair(name, (32, 32))
    img = _image((32, 32), seed=5)
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


@pytest.mark.parametrize("images_a_chunk", [1, 4, 5, 13, 14])
def test_chunks_of_the_batch_give_the_same_pixels(monkeypatch, images_a_chunk):
    imgs = jnp.stack([_image((32, 32), seed=s) for s in range(13)])
    mats = _matrices(13, 32, 32)
    want = np.asarray(jax.jit(jax.vmap(_gather_warp))(imgs, mats))
    monkeypatch.setattr(A, "_DENSE_WARP_BUDGET_BYTES",
                        images_a_chunk * 32 * 32 * 32 * 3 * 4)
    assert np.array_equal(np.asarray(_warp_batch(imgs, mats)), want)


def test_nested_vmaps_fold_into_one_batch(monkeypatch):
    """Draws x images (the TTA program's shape), matrices shared by the
    draws: one resampling over 3 x 5 images, chunked as one batch."""
    imgs = jnp.stack([_image((17, 17), seed=s) for s in range(15)]).reshape(
        3, 5, 17, 17, 3)
    mats = _matrices(5, 17, 17)
    fn = jax.vmap(jax.vmap(A._warp_affine_nearest), in_axes=(0, None))
    want = np.asarray(jax.vmap(jax.vmap(_gather_warp), in_axes=(0, None))(imgs, mats))
    assert np.array_equal(np.asarray(jax.jit(fn)(imgs, mats)), want)
    text = jax.jit(fn).lower(imgs, mats).as_text()
    assert text.count("stablehlo.dot_general") == 2
    assert "15x289x17" in text  # [N, H*W, H] one-hot of the whole batch
    monkeypatch.setattr(A, "_DENSE_WARP_BUDGET_BYTES", 4 * 17 * 17 * 17 * 3 * 4)
    assert np.array_equal(np.asarray(jax.jit(fn)(imgs, mats)), want)


# -------------------------------------------------------- the counter


@pytest.mark.parametrize("shape,form", [
    ((30, 30), "dense"), ((18, 22), "dense"),
    ((GATHER_SHAPE[0] + 1, 64), "gather"), ((448, 448), "gather")])
def test_counter_says_which_addressing_a_program_got(shape, form):
    """Shapes no other test traces: JAX keeps a switch's traced branches,
    so the counter counts a shape's first trace in a process, not each
    program."""
    def count(f):
        return telemetry.registry().counter(
            "faa_aug_warp_traces_total", form=f,
            image=f"{shape[0]}x{shape[1]}").value

    other = "gather" if form == "dense" else "dense"
    before, before_other = count(form), count(other)
    imgs = jnp.zeros((2,) + shape + (3,), jnp.float32)
    jax.make_jaxpr(A.apply_policy_batch)(imgs, _policy(0, num_sub=2),
                                         jax.random.PRNGKey(0))
    # one warp branch, whatever the 19 operations of the switch
    assert 1 <= count(form) - before <= 2
    assert count(other) == before_other
