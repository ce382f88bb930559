"""On-device batched augmentation kernels.

The reference applies its 19 registered augmentation ops per-image with
PIL on CPU DataLoader workers (reference ``augmentations.py:13-194``,
``data.py:253-264``).  Here every op is a pure ``jnp`` function on a
``[H, W, C]`` float32 image holding integral uint8 values in [0, 255],
with explicit PRNG keys, vmapped over the batch and jit-compiled — the
augmentation runs on the TPU, fused into the input side of the train
step, and the *policy is a tensor input* rather than Python structure.
That last property is what makes TTA policy search fast: one compiled
evaluation step serves every candidate policy (SURVEY.md section 7).

Semantics were pinned against PIL empirically and are exact (see
``tests/test_augment_golden.py``):

- affine/rotate: nearest-neighbor, ``src = floor(A @ (x, y) + t + 0.5)``,
  fill 0, rotate about ``(W/2, H/2)``  (PIL ``Image.transform``
  with ``AFFINE`` / ``Image.rotate``, reference ``augmentations.py:17-62``);
  the seven operations are seven 2x3 matrices into ONE warp, which
  addresses its source pixels by one-hot products, no gather: over the
  whole image at CIFAR size, over a bounded source window per output
  tile at ImageNet size
- L (grayscale): ``(r*19595 + g*38470 + b*7471 + 0x8000) >> 16``
- enhance ops: ``clip(trunc(deg + (img - deg) * factor), 0, 255)`` in
  float32 (PIL ``ImageEnhance`` via ``Image.blend``)
- equalize / autocontrast: PIL's exact integer LUT constructions, with no
  sort, search, gather or scatter (what the TPU runs slowest).  Equalize
  addresses the 256 levels as 16 x 16: a pixel ``p = 16a + b`` is two
  16-wide one-hots, whose product over the pixels is the histogram and
  through which the table is applied, both on the MXU.  The one-hots and
  the table (integers in [0, 255]) are bfloat16 operands, which is exact;
  a count is never one (it passes 256 at 32 px), so the sums are float32
  and the table is built in int32.  AutoContrast's table is arithmetic
  on each pixel
- SMOOTH filter (sharpness degenerate): 3x3 kernel [[1,1,1],[1,5,1],
  [1,1,1]]/13, ``trunc(acc + 0.5)``, 1-pixel border copied unfiltered

Op registry (19 ops) mirrors the reference's ``augment_list(True)``
(``augmentations.py:156-182``): indices 0-14 are the searchable ops
(``augment_list(False)``), 15-18 the AutoAugment-compat extras.  ``Flip``
exists in the reference source but is never registered (SURVEY.md
errata 1) — provided here as a standalone function only.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fast_autoaugment_tpu.core import scopes, telemetry

__all__ = [
    "OP_NAMES",
    "SEARCH_OP_NAMES",
    "AUG_DISPATCH_MODES",
    "op_index",
    "augment_list",
    "apply_augment",
    "apply_op",
    "apply_subpolicy",
    "apply_subpolicy_batch",
    "apply_policy",
    "apply_policy_scalar_single",
    "apply_policy_batch",
    "apply_policy_batch_grouped",
    "check_aug_dispatch",
    "CUTOUT_COLOR",
]

# (name, low, high, mirrored): value = level * (high - low) + low, then the
# sign is flipped with prob 0.5 when `mirrored` (reference `random_mirror`,
# augmentations.py:10-16; TranslateX/YAbs always mirror, :44-56).
_OP_TABLE = (
    ("ShearX", -0.3, 0.3, True),
    ("ShearY", -0.3, 0.3, True),
    ("TranslateX", -0.45, 0.45, True),
    ("TranslateY", -0.45, 0.45, True),
    ("Rotate", -30.0, 30.0, True),
    ("AutoContrast", 0.0, 1.0, False),
    ("Invert", 0.0, 1.0, False),
    ("Equalize", 0.0, 1.0, False),
    ("Solarize", 0.0, 256.0, False),
    ("Posterize", 4.0, 8.0, False),
    ("Contrast", 0.1, 1.9, False),
    ("Color", 0.1, 1.9, False),
    ("Brightness", 0.1, 1.9, False),
    ("Sharpness", 0.1, 1.9, False),
    ("Cutout", 0.0, 0.2, False),
    ("CutoutAbs", 0.0, 20.0, False),  # no sign flip (augmentations.py:127-131)
    ("Posterize2", 0.0, 4.0, False),
    ("TranslateXAbs", 0.0, 10.0, True),
    ("TranslateYAbs", 0.0, 10.0, True),
)

OP_NAMES: tuple[str, ...] = tuple(t[0] for t in _OP_TABLE)
NUM_OPS = len(OP_NAMES)
SEARCH_OP_NAMES: tuple[str, ...] = OP_NAMES[:15]  # augment_list(False)
_OP_LOW = np.array([t[1] for t in _OP_TABLE], np.float32)
_OP_HIGH = np.array([t[2] for t in _OP_TABLE], np.float32)
_OP_MIRROR = np.array([t[3] for t in _OP_TABLE], np.bool_)

CUTOUT_COLOR = (125.0, 123.0, 114.0)  # reference augmentations.py:140

# dispatch modes for batched policy application: "exact" is the i.i.d.
# per-image sub-policy draw (vmapped lax.switch — XLA lowers the batched
# op index to executing ALL 13 branches per image and selecting one);
# "grouped" keeps the switch index SCALAR inside the compiled program
# (stratified per-chunk sub-policy draws; one branch executes).
AUG_DISPATCH_MODES = ("exact", "grouped")


def check_aug_dispatch(mode: str) -> str:
    if mode not in AUG_DISPATCH_MODES:
        raise ValueError(
            f"aug_dispatch must be one of {AUG_DISPATCH_MODES}, got {mode!r}")
    return mode


def op_index(name: str) -> int:
    return OP_NAMES.index(name)


def augment_list(for_autoaug: bool = True) -> list[tuple[str, float, float]]:
    """Name/range table, same contract as reference ``augment_list``."""
    rows = _OP_TABLE if for_autoaug else _OP_TABLE[:15]
    return [(name, low, high) for name, low, high, _ in rows]


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def _to_int(img: jax.Array) -> jax.Array:
    return jnp.clip(img, 0.0, 255.0).astype(jnp.int32)


def _grayscale_u8(img: jax.Array) -> jax.Array:
    """PIL 'L' conversion on integral-valued float input -> int32 [H, W]."""
    ii = _to_int(img)
    r, g, b = ii[..., 0], ii[..., 1], ii[..., 2]
    return (r * 19595 + g * 38470 + b * 7471 + 0x8000) >> 16


def _blend(degenerate: jax.Array, img: jax.Array, factor: jax.Array) -> jax.Array:
    """PIL Image.blend + uint8 store: float32 lerp, trunc, clip."""
    out = degenerate + (img - degenerate) * factor
    return jnp.clip(jnp.trunc(out), 0.0, 255.0)


# The warp addresses its source pixels by one-hot products, nothing
# addressed by data (a gather is the slowest thing the TPU does: 9 ns a
# row of three floats, whatever the image).  An output pixel pays for the
# window its source pixel is looked up in.  An image of fewer pixels than
# the window of a `_WARP_TILE` tile is one tile and its own window: 3.1
# MMAC an image at 32 px and nothing, where 224 px would be 7.55 GMAC.
# A larger one is cut into tiles of at most `_WARP_TILE` output pixels a
# side, and a tile looks its pixels up in a window that the seven
# operations' matrices cannot leave while their values stay inside
# `_OP_TABLE`'s ranges (:func:`_warp_window_side`): 1.26 GMAC an image at
# 224 px, the windows' fetch included, and linear in the pixels but for
# the rows picked for the windows (H*W*H*W*C*S/T**2: a sixth of the work
# at 224 px, most of it at 600, where the tiled form still takes a
# quarter of the gather's time).  The intermediates (the rows picked for
# every output pixel) run in chunks of the batch that fit
# `_DENSE_WARP_BUDGET_BYTES`, which costs no time.  Every form timed
# alone on a v5e at 32 to 600 px, and why 56: PERF.md section 6, PR 33.
_WARP_TILE = 56
_DENSE_WARP_BUDGET_BYTES = 256 << 20


def _warp_window_side(tile: int) -> int:
    """Source pixels a side that a `tile` x `tile` block of output pixels
    can reach under any of the seven operations inside `_OP_TABLE`'s
    ranges, rounded up to whole sublanes.

    A source coordinate is ``floor(a*x + b*y + t)``: over the block it
    takes at most ``floor((tile - 1) * (|a| + |b|)) + 2`` values, and
    ``|a| + |b|`` is at most ``1 + |shear|`` or ``cos + sin`` of the
    largest rotation (a translation moves the window, not its size).
    The hundredth covers float32 rounding of coordinates under 2**13."""
    def reach(name):  # the sign flip reaches both ends of a range
        return float(max(abs(_OP_LOW[op_index(name)]), abs(_OP_HIGH[op_index(name)])))

    turn = np.deg2rad(min(reach("Rotate"), 45.0))
    gain = max(1.0 + reach("ShearX"), 1.0 + reach("ShearY"),
               float(np.cos(turn) + np.sin(turn)))
    return -(-(int((tile - 1) * gain + 0.01) + 2) // 8) * 8


def _warp_tiling(h: int, w: int):
    """``((tile rows, tile columns), window rows, window columns)`` of an
    ``h x w`` image, or None where the image is one tile and its own
    window.

    A side is cut into as few tiles as `_WARP_TILE` allows, of equal size:
    240 px is 5 x 48, not 5 x 56 with a third of the last tile empty.  A
    side shorter than the window is the window."""
    if h * w < _warp_window_side(_WARP_TILE) ** 2:
        return None
    th, tw = (-(-n // -(-n // _WARP_TILE)) for n in (h, w))
    side = _warp_window_side(max(th, tw))
    return (th, tw), min(side, h), min(side, w)


def _warp_bytes_an_image(h: int, w: int, c: int) -> int:
    """What the resampling of one image keeps at a time, in bfloat16: the
    rows picked for every output pixel and, where it is tiled, those
    picked for every window."""
    tiling = _warp_tiling(h, w)
    if tiling is None:
        return h * w * w * c * 2
    (th, tw), sh, sw = tiling
    tiles = -(-h // th) * -(-w // tw)
    return tiles * (th * tw * sw + sh * w) * c * 2


def _resample_dense_one(img, sy, sx, planar=False):
    """``img[sy, sx]`` with zero fill, as two one-hot selections through
    the MXU and nothing addressed by data.

    Rows: ``[P, H] x [H, W*C]`` picks the source row of every output
    pixel.  Columns: that row masked down to the source column's C
    lanes, then ``[P, W*C] x [W*C, C]`` against a constant selector
    sums the lanes of each channel.  Exact, whatever the ambient matmul
    precision: every sum is of one pixel value and zeros, and pixel
    values are integers in [0, 255], which bfloat16 holds.  An index out
    of range matches no row or lane, which is the zero fill.

    `img` is ``[H, W, C]``, or ``[H, C, W]`` where `planar` (a window of
    :func:`_fetch_windows`); the result has `sy`'s shape and then C."""
    h, w, c = (img.shape[0], img.shape[2], img.shape[1]) if planar else img.shape
    contract = (((1,), (0,)), ((), ()))
    rows = jax.lax.dot_general(
        (sy.reshape(-1, 1) == jnp.arange(h)).astype(jnp.bfloat16),
        img.reshape(h, w * c).astype(jnp.bfloat16),
        contract, preferred_element_type=jnp.bfloat16)
    # jnp.arange, not np: the batching of a switch would batch a constant
    # this function closed over, which a custom_vmap rule refuses
    lane = jnp.arange(w * c)
    masked = jnp.where(sx.reshape(-1, 1) == (lane % w if planar else lane // c), rows, 0)
    lane = lane[:, None]
    channel_of = ((lane // w if planar else lane % c) == jnp.arange(c)).astype(jnp.bfloat16)
    out = jax.lax.dot_general(masked, channel_of, contract,
                              preferred_element_type=jnp.float32)
    return out.reshape(sy.shape + (c,))


def _in_chunks(one, bytes_an_image, *args):
    """``vmap(one)`` over the leading axis, in chunks of the batch whose
    intermediates fit `_DENSE_WARP_BUDGET_BYTES`; never of one image,
    which runs at 0.4 of a larger chunk's rate (from 528 px a side:
    PERF.md section 6, PR 33)."""
    chunk = max(2, _DENSE_WARP_BUDGET_BYTES // bytes_an_image)
    if args[0].shape[0] <= chunk:
        return jax.vmap(one)(*args)
    return jax.lax.map(lambda a: one(*a), args, batch_size=chunk)


def _fetch_windows(img, oy, ox, sh, sw):
    """The ``sh x sw`` windows of `img` ``[H, W, C]`` at the origins
    `oy`, `ox` (``[tiles]`` each, inside the image), as ``[tiles, sh, C,
    sw]``: the one-hot of a window's rows picks them out of the image,
    channels apart (one product for every tile), and the one-hot of its
    columns picks those out of the rows (a product a tile)."""
    h, w, c = img.shape
    tiles = oy.shape[0]
    pick_rows = (oy[:, None] + jnp.arange(sh)).reshape(-1, 1) == jnp.arange(h)
    planes = img.astype(jnp.bfloat16).transpose(0, 2, 1).reshape(h, c * w)
    rows = jax.lax.dot_general(
        pick_rows.astype(jnp.bfloat16), planes, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.bfloat16)  # [tiles * sh, C * W]
    pick_cols = jnp.arange(w)[:, None] == ox[:, None, None] + jnp.arange(sw)
    wins = jax.lax.dot_general(
        rows.reshape(tiles, sh * c, w), pick_cols.astype(jnp.bfloat16),
        (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.bfloat16)
    return wins.reshape(tiles, sh, c, sw)


def _tiles_of(grid, th, tw):
    """``[H, W]`` -> ``[tiles, th * tw]``; the last tiles of a side no
    tile divides repeat its last row or column."""
    h, w = grid.shape
    rows, cols = -(-h // th), -(-w // tw)
    grid = jnp.pad(grid, ((0, rows * th - h), (0, cols * tw - w)), mode="edge")
    return grid.reshape(rows, th, cols, tw).transpose(0, 2, 1, 3).reshape(
        rows * cols, th * tw)


def _window_origins(tiles, size, side):
    """Where the windows of `tiles` (``[..., tiles, pixels]`` source
    indices along an axis of `size` pixels) start: at the smallest index
    of a tile, clamped so that the `side` pixels lie inside the image."""
    return jnp.clip(tiles.min(axis=-1), 0, size - side)


def _resample_tiled_one(img, sy, sx):
    """:func:`_resample_dense_one` of an image of many tiles: every tile
    looks its pixels up in a window of its own (:func:`_warp_tiling`).

    Every index inside the image that a tile needs lies in its window if
    :func:`_fits_windows` says so, and one outside the image lies outside
    the window too (the window is inside the image), which is the zero
    fill."""
    h, w, c = img.shape
    (th, tw), sh, sw = _warp_tiling(h, w)
    ty, tx = _tiles_of(sy, th, tw), _tiles_of(sx, th, tw)
    oy, ox = _window_origins(ty, h, sh), _window_origins(tx, w, sw)
    out = jax.vmap(functools.partial(_resample_dense_one, planar=True))(
        _fetch_windows(img, oy, ox, sh, sw), ty - oy[:, None], tx - ox[:, None])
    rows, cols = -(-h // th), -(-w // tw)
    out = out.reshape(rows, cols, th, tw, c).transpose(0, 2, 1, 3, 4)
    return out.reshape(rows * th, cols * tw, c)[:h, :w]


def _fits_windows(sy, sx, tile, sh, sw):
    """Whether, in every tile of every image, the largest source row and
    column inside the image lie in the tile's window (``[N, H, W]`` each
    -> a scalar).  The smallest do by where a window starts."""
    def fits(grid, size, side):
        tiles = jax.vmap(lambda g: _tiles_of(g, *tile))(grid)
        last = _window_origins(tiles, size, side) + side
        return jnp.all(jnp.minimum(tiles.max(axis=-1), size - 1) < last)
    return fits(sy, sy.shape[1], sh) & fits(sx, sx.shape[2], sw)


def _resample_gather_one(img, sy, sx):
    """``img[sy, sx]`` with zero fill as a gather: slow, and right for
    any indices."""
    h, w = img.shape[0], img.shape[1]
    valid = (sx >= 0) & (sx < w) & (sy >= 0) & (sy < h)
    gathered = img[jnp.clip(sy, 0, h - 1), jnp.clip(sx, 0, w - 1)]
    return jnp.where(valid[..., None], gathered, 0.0)


def _tile_label(tile, sh, sw) -> str:
    """``TxS``: output pixels and window pixels a side; all four where
    the sides differ."""
    th, tw = tile
    return f"{th}x{sh}" if (th, sh) == (tw, sw) else f"{th}x{tw}x{sh}x{sw}"


@jax.custom_batching.custom_vmap
def _resample_dense(imgs, sy, sx):
    """``imgs[n, sy[n], sx[n]]`` with zero fill over a leading axis of N
    images, by the form :func:`_warp_tiling` gives their shape.

    Its ``vmap`` rule folds every batch axis a caller adds into N, so this
    body is traced with the whole batch in sight, which a per-image
    function under ``vmap`` never is: it can bound what it allocates,
    and it can ask of the whole batch at once, at run time, whether the
    indices stay inside the tiles' windows.  They do for every matrix of
    the seven operations at a level in [0, 1]; where one does not (the
    named functions take any value, a policy tensor any level), the
    batch is gathered: never a wrong pixel, and no value is clamped."""
    h, w, c = imgs.shape[1:]
    tiling = _warp_tiling(h, w)
    bytes_an_image = _warp_bytes_an_image(h, w, c)
    if tiling is None:
        return _in_chunks(_resample_dense_one, bytes_an_image, imgs, sy, sx)
    return jax.lax.cond(
        _fits_windows(sy, sx, *tiling),
        functools.partial(_in_chunks, _resample_tiled_one, bytes_an_image),
        jax.vmap(_resample_gather_one), imgs, sy, sx)


@_resample_dense.def_vmap
def _resample_dense_fold(axis_size, in_batched, *args):
    folded = []
    for x, batched in zip(args, in_batched):  # [B, N, ...] or [N, ...]
        if not batched:
            x = jnp.broadcast_to(x, (axis_size,) + x.shape)
        folded.append(x.reshape((-1,) + x.shape[2:]))
    out = _resample_dense(*folded)
    return out.reshape((axis_size, -1) + out.shape[1:]), True


def _warp_affine_nearest(img: jax.Array, mat: jax.Array) -> jax.Array:
    """PIL-exact nearest affine warp with zero fill.

    `mat` is the 2x3 PIL-convention inverse map [[a, b, c], [d, e, f]]
    from output to source coords.  PIL samples at pixel centers with a
    plain floor: ``src = floor(A @ (x+0.5, y+0.5) + t)`` (pinned
    empirically; the center offset matters for tie-breaking at .5).
    Any `mat` gives the right pixels; one of the seven operations inside
    `_OP_TABLE`'s ranges gives them fast (:func:`_resample_dense`).
    """
    h, w = img.shape[0], img.shape[1]
    tiling = _warp_tiling(h, w)
    # trace time: which addressing each program that holds a warp got
    telemetry.registry().counter(
        "faa_aug_warp_traces_total", "affine warps traced into a program, "
        "by how they address their source pixels",
        form="dense" if tiling is None else "tiled", image=f"{h}x{w}",
        tile=f"{h}x{w}" if tiling is None else _tile_label(*tiling)).inc()
    with jax.named_scope(scopes.AUG_WARP):
        ys, xs = jnp.mgrid[0:h, 0:w]
        xsf, ysf = xs.astype(jnp.float32) + 0.5, ys.astype(jnp.float32) + 0.5
        sx = jnp.floor(mat[0, 0] * xsf + mat[0, 1] * ysf + mat[0, 2]).astype(jnp.int32)
        sy = jnp.floor(mat[1, 0] * xsf + mat[1, 1] * ysf + mat[1, 2]).astype(jnp.int32)
        return _resample_dense(img[None], sy[None], sx[None])[0]


# Equalize addresses the 256 levels as 16 x 16: level ``16a + b`` is entry
# ``[a, b]`` of a ``[16, 16]`` table
_LEVELS = np.arange(256, dtype=np.int32)
_NIBBLES = np.arange(16, dtype=np.int32)
_NIBBLE_BELOW = _NIBBLES[:, None] < _NIBBLES[None, :]  # [u, v]: u < v


def _nibble_one_hots(ii: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``p = 16a + b``: the one-hots of ``a`` and of ``b``, bool ``[C, 16, P]``
    each, for an int32 ``[H, W, C]`` image with values in [0, 255].

    The ``P = H * W`` pixels lie in the last (lane) axis, so both 256-way
    passes of :func:`equalize` are 16-row products over dense lanes."""
    flat = ii.reshape(-1, ii.shape[-1]).T[:, None, :]  # [C, 1, P]
    return ((flat >> 4) == _NIBBLES[:, None],
            (flat & 15) == _NIBBLES[:, None])


def _histogram256(high: jax.Array, low: jax.Array) -> jax.Array:
    """``[C, 16, 16]`` int32: the 256-bin histogram of every channel, as
    ``hist[a, b] = sum_p high[a, p] * low[b, p]`` on the MXU.

    The operands are 0/1, exact in bfloat16; the sums are counts, so they
    accumulate in float32, exact up to 2**24 pixels an image."""
    hist = jax.lax.dot_general(
        high.astype(jnp.bfloat16), low.astype(jnp.bfloat16),
        (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32)
    return hist.astype(jnp.int32)


def _count_below(hist: jax.Array) -> jax.Array:
    """``[C, 256]`` int32: per channel, how many pixels lie below each
    level: the exclusive running sum of the ``[C, 16, 16]`` int32
    histogram over its 256 bins, in two levels as the histogram is.

    Below level ``16a + b`` lie the rows above row ``a`` and the entries
    left of ``b`` in it.  Counts stay integers: two triangular
    select-and-sums, no ``cumsum`` (a loop of slices on the TPU) and no
    bfloat16."""
    rows = jnp.sum(hist, axis=2, dtype=jnp.int32)  # [C, 16]
    rows_above = jnp.sum(jnp.where(_NIBBLE_BELOW, rows[:, :, None], 0),
                         axis=1, dtype=jnp.int32)  # [C, 16a]
    left_of = jnp.sum(jnp.where(_NIBBLE_BELOW, hist[:, :, :, None], 0),
                      axis=2, dtype=jnp.int32)  # [C, 16a, 16b]
    return (rows_above[:, :, None] + left_of).reshape(-1, 256)


# ---------------------------------------------------------------------------
# the 19 ops — each is (img [H,W,C] f32 integral, value f32 scalar, key) -> img
# ---------------------------------------------------------------------------


def _identity_matrix() -> jax.Array:
    return jnp.array([[1.0, 0.0, 0.0], [0.0, 1.0, 0.0]])


def shear_x_matrix(v, h, w):
    return _identity_matrix().at[0, 1].set(v)


def shear_y_matrix(v, h, w):
    return _identity_matrix().at[1, 0].set(v)


def translate_x_matrix(v, h, w):
    # fractional of width (reference augmentations.py:28-33)
    return _identity_matrix().at[0, 2].set(v * w)


def translate_y_matrix(v, h, w):
    return _identity_matrix().at[1, 2].set(v * h)


def translate_x_abs_matrix(v, h, w):
    return _identity_matrix().at[0, 2].set(v)


def translate_y_abs_matrix(v, h, w):
    return _identity_matrix().at[1, 2].set(v)


def rotate_matrix(v, h, w):
    """PIL Image.rotate(v): CCW degrees about (W/2, H/2)."""
    cx, cy = w / 2.0, h / 2.0
    rad = v * (np.pi / 180.0)
    ca, sa = jnp.cos(rad), jnp.sin(rad)
    mat = _identity_matrix()
    mat = mat.at[0, 0].set(ca).at[0, 1].set(-sa).at[0, 2].set(cx - ca * cx + sa * cy)
    mat = mat.at[1, 0].set(sa).at[1, 1].set(ca).at[1, 2].set(cy - sa * cx - ca * cy)
    return mat


# the seven affine operations differ in their matrix alone: (value, H, W)
# -> the 2x3 inverse map :func:`_warp_affine_nearest` takes
_AFFINE_MATRIX_FNS = {
    "ShearX": shear_x_matrix,
    "ShearY": shear_y_matrix,
    "TranslateX": translate_x_matrix,
    "TranslateY": translate_y_matrix,
    "Rotate": rotate_matrix,
    "TranslateXAbs": translate_x_abs_matrix,
    "TranslateYAbs": translate_y_abs_matrix,
}


def shear_x(img, v, key):
    return _warp_affine_nearest(img, shear_x_matrix(v, *img.shape[:2]))


def shear_y(img, v, key):
    return _warp_affine_nearest(img, shear_y_matrix(v, *img.shape[:2]))


def translate_x(img, v, key):
    return _warp_affine_nearest(img, translate_x_matrix(v, *img.shape[:2]))


def translate_y(img, v, key):
    return _warp_affine_nearest(img, translate_y_matrix(v, *img.shape[:2]))


def translate_x_abs(img, v, key):
    return _warp_affine_nearest(img, translate_x_abs_matrix(v, *img.shape[:2]))


def translate_y_abs(img, v, key):
    return _warp_affine_nearest(img, translate_y_abs_matrix(v, *img.shape[:2]))


def rotate(img, v, key):
    return _warp_affine_nearest(img, rotate_matrix(v, *img.shape[:2]))


def auto_contrast(img, v, key):
    """PIL ImageOps.autocontrast(cutoff=0): per-channel min/max stretch.

    Computed as the exact rational ``(p - lo) * 255 // (hi - lo)`` on
    each pixel: every pixel lies in [lo, hi], so the numerator is never
    negative and this is the entry PIL's 256-entry LUT holds for it.
    PIL evaluates the same map in double precision with truncation,
    which lands 1 below the exact value on ~20% of images — so outputs
    may differ from PIL by at most 1 (deliberate deviation; the exact
    form is stable in float-free integer math on device).
    """
    ii = _to_int(img)
    lo = ii.min(axis=(0, 1))  # [C]
    hi = ii.max(axis=(0, 1))
    stretched = jnp.clip((ii - lo) * 255 // jnp.maximum(hi - lo, 1), 0, 255)
    return jnp.where(hi <= lo, ii, stretched).astype(jnp.float32)


def invert(img, v, key):
    return 255.0 - jnp.clip(img, 0.0, 255.0)


def equalize(img, v, key):
    """PIL ImageOps.equalize: per-channel integer histogram remap.

    PIL's table is ``lut[v] = (step // 2 + #(pixels < v)) // step`` with
    ``step = (pixels - h_last) // 255`` and ``h_last`` the count of the
    last nonzero bin; a channel with ``step == 0`` passes unchanged.

    The 256 levels are addressed as 16 x 16 (``p = 16a + b``) through
    the two one-hots of :func:`_nibble_one_hots`.  Their product over
    the pixels is the histogram (:func:`_histogram256`), from which the
    table is built in int32.  The table, as ``[C, 16, 16]``, is applied
    by one more product: ``table[:, a, :]`` against the one-hot of ``a``
    leaves every pixel the 16 entries of its high nibble, and the
    one-hot of ``b`` selects one.  Operands of that product are
    bfloat16, stated in the ``dot_general`` as the warp's are, exact
    whatever the ambient matmul precision: one is 0/1, the other an
    integer in [0, 255], and every sum is of one entry and zeros.  A
    count is never a bfloat16 operand: counts pass 256 at 32 px."""
    ii = _to_int(img)
    high, low = _nibble_one_hots(ii)  # [C, 16, P]
    below = _count_below(_histogram256(high, low))  # [C, 256]
    # the last nonzero bin is the channel maximum's
    h_last = jnp.sum(ii == ii.max(axis=(0, 1)), axis=(0, 1), dtype=jnp.int32)
    step = ((ii.shape[0] * ii.shape[1] - h_last) // 255)[:, None]  # [C, 1]
    lut = jnp.clip((step // 2 + below) // jnp.maximum(step, 1), 0, 255)
    # a single nonzero bin holds every pixel, so it gives step == 0 too
    lut = jnp.where(step == 0, _LEVELS, lut)
    # lut[p] without a gather: [C, 16a, 16b] x [C, 16a, P] -> [C, 16b, P]
    rows = jax.lax.dot_general(
        lut.reshape(-1, 16, 16).astype(jnp.bfloat16), high.astype(jnp.bfloat16),
        (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.bfloat16)
    out = jnp.sum(jnp.where(low, rows, 0), axis=1)  # [C, P]
    return out.T.reshape(ii.shape).astype(jnp.float32)


def solarize(img, v, key):
    ii = jnp.clip(img, 0.0, 255.0)
    return jnp.where(ii < v, ii, 255.0 - ii)


def _posterize_bits(img, bits):
    mask = jnp.left_shift(jnp.int32(0xFF), 8 - bits) & 0xFF
    return (_to_int(img) & mask).astype(jnp.float32)


def posterize(img, v, key):
    # int(v), v in [4, 8] (reference augmentations.py:85-88)
    return _posterize_bits(img, jnp.trunc(v).astype(jnp.int32))


def posterize2(img, v, key):
    # v in [0, 4] (reference augmentations.py:91-94)
    return _posterize_bits(img, jnp.trunc(v).astype(jnp.int32))


def contrast(img, v, key):
    gray = _grayscale_u8(img)
    mean = jnp.trunc(gray.astype(jnp.float32).mean() + 0.5)
    return _blend(jnp.full_like(img, mean), jnp.clip(img, 0.0, 255.0), v)


def color(img, v, key):
    deg = jnp.repeat(_grayscale_u8(img)[..., None].astype(jnp.float32), img.shape[-1], axis=-1)
    return _blend(deg, jnp.clip(img, 0.0, 255.0), v)


def brightness(img, v, key):
    return _blend(jnp.zeros_like(img), jnp.clip(img, 0.0, 255.0), v)


def _smooth_degenerate(img: jax.Array) -> jax.Array:
    """PIL ImageFilter.SMOOTH: 3x3 [[1,1,1],[1,5,1],[1,1,1]]/13, border copied."""
    h, w = img.shape[0], img.shape[1]
    kernel = np.array([[1, 1, 1], [1, 5, 1], [1, 1, 1]], np.float32) / 13.0
    padded = jnp.pad(img, ((1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros_like(img)
    for dy in range(3):
        for dx in range(3):
            acc = acc + kernel[dy, dx] * jax.lax.dynamic_slice(
                padded, (dy, dx, 0), (h, w, img.shape[2])
            )
    sm = jnp.clip(jnp.trunc(acc + 0.5), 0.0, 255.0)
    border = jnp.zeros((h, w, 1), bool).at[0, :].set(True).at[-1, :].set(True).at[:, 0].set(True).at[:, -1].set(True)
    return jnp.where(border, jnp.clip(img, 0.0, 255.0), sm)


def sharpness(img, v, key):
    return _blend(_smooth_degenerate(img), jnp.clip(img, 0.0, 255.0), v)


def _cutout_abs(img, v, key):
    """Gray rectangle at uniform center (reference CutoutAbs, augmentations.py:127-146).

    PIL's ImageDraw.rectangle fills the box *inclusive* of (x1, y1).
    """
    h, w = img.shape[0], img.shape[1]
    kx, ky = jax.random.split(key)
    x0f = jax.random.uniform(kx, (), minval=0.0, maxval=float(w))
    y0f = jax.random.uniform(ky, (), minval=0.0, maxval=float(h))
    x0 = jnp.trunc(jnp.maximum(0.0, x0f - v / 2.0))
    y0 = jnp.trunc(jnp.maximum(0.0, y0f - v / 2.0))
    x1 = jnp.minimum(float(w), x0 + v)
    y1 = jnp.minimum(float(h), y0 + v)
    ys, xs = jnp.mgrid[0:h, 0:w]
    inside = (
        (xs.astype(jnp.float32) >= x0)
        & (xs.astype(jnp.float32) <= x1)
        & (ys.astype(jnp.float32) >= y0)
        & (ys.astype(jnp.float32) <= y1)
    )
    fill = jnp.asarray(CUTOUT_COLOR, img.dtype)
    out = jnp.where(inside[..., None], fill, img)
    return jnp.where(v < 0.0, img, out)


def cutout(img, v, key):
    # fractional of width; <= 0 is identity (reference augmentations.py:118-124)
    out = _cutout_abs(img, v * img.shape[1], key)
    return jnp.where(v <= 0.0, img, out)


def cutout_abs(img, v, key):
    return _cutout_abs(img, v, key)


def flip(img, v, key):
    """PIL ImageOps.mirror — defined in the reference but never registered."""
    return img[:, ::-1]


_OP_FNS = (
    shear_x, shear_y, translate_x, translate_y, rotate,
    auto_contrast, invert, equalize, solarize, posterize,
    contrast, color, brightness, sharpness, cutout,
    cutout_abs, posterize2, translate_x_abs, translate_y_abs,
)
assert len(_OP_FNS) == NUM_OPS


# ---------------------------------------------------------------------------
# dispatch + policy application
# ---------------------------------------------------------------------------


def apply_augment(img: jax.Array, name: str, level, key: jax.Array) -> jax.Array:
    """Single named op at `level` in [0, 1] (reference ``apply_augment``,
    ``augmentations.py:192-194``) — includes the random mirror."""
    return apply_op(img, jnp.int32(op_index(name)), jnp.float32(level), key)


@functools.lru_cache(maxsize=None)
def _op_range_constants():
    """Device-resident (low, high, mirror, branch) op tables, built ONCE.

    ``apply_op`` used to call ``jnp.asarray(_OP_LOW)`` (and friends) per
    invocation, rebuilding the constants on every trace.  Lazy
    (not module-level) so importing this module never eagerly
    initializes a JAX backend — bench tools probe backend liveness
    before touching the device.  ``ensure_compile_time_eval`` keeps the
    cached values CONCRETE even when the first call lands inside a
    trace (a cached tracer would escape its trace scope)."""
    with jax.ensure_compile_time_eval():
        return (jnp.asarray(_OP_LOW), jnp.asarray(_OP_HIGH),
                jnp.asarray(_OP_MIRROR), jnp.asarray(_BRANCH_OF))


def op_affine_matrix(op_idx: jax.Array, value: jax.Array, h: int, w: int) -> jax.Array:
    """The 2x3 matrix of op `op_idx` (traced scalar) at `value` on an
    ``h x w`` image: its builder's for the seven affine operations, the
    identity for the twelve that resample nothing."""
    def branch(name):
        build = _AFFINE_MATRIX_FNS.get(name)
        if build is None:
            return lambda v: _identity_matrix()

        def scoped(v):
            with jax.named_scope(scopes.aug_op(name)):
                return build(v, h, w)
        return scoped

    return jax.lax.switch(op_idx, [branch(name) for name in OP_NAMES], value)


def _warp_op(img, value, key, op_idx):
    """The one branch of the seven affine operations: the matrix is
    chosen by `op_idx`, the resampling runs once."""
    mat = op_affine_matrix(op_idx, value, img.shape[0], img.shape[1])
    return _warp_affine_nearest(img, mat)


def _call_op(fn, scope, img, value, key, op_idx):
    # the one place every other branch passes: its device time is read by name
    with jax.named_scope(scope):
        return fn(img, value, key)


# the switch of :func:`apply_op`: a branch for each of the twelve other
# operations, then the warp; `_BRANCH_OF` maps an op index to its branch
_PLAIN_OPS = tuple(n for n in OP_NAMES if n not in _AFFINE_MATRIX_FNS)
_BRANCHES = tuple(
    functools.partial(_call_op, _OP_FNS[op_index(name)], scopes.aug_op(name))
    for name in _PLAIN_OPS) + (_warp_op,)
_BRANCH_OF = np.array(
    [_PLAIN_OPS.index(name) if name in _PLAIN_OPS else len(_PLAIN_OPS)
     for name in OP_NAMES], np.int32)


def apply_op(img: jax.Array, op_idx: jax.Array, level: jax.Array, key: jax.Array) -> jax.Array:
    """Apply op `op_idx` (traced scalar) at `level` in [0, 1].

    Maps level -> value = level*(high-low)+low and flips the sign with
    prob 0.5 for mirrored (geometric) ops, then dispatches via
    ``lax.switch`` so the op id can be a runtime tensor (policy-as-data).
    The switch has 13 branches, not 19: the seven affine operations
    share :func:`_warp_op`, so a batched index (which executes every
    branch) pays for one resampling, and a scalar index on any other
    operation for none.
    """
    key_mirror, key_op = jax.random.split(key)
    op_low, op_high, op_mirror, branch_of = _op_range_constants()
    low = op_low[op_idx]
    high = op_high[op_idx]
    value = level * (high - low) + low
    mirrored = op_mirror[op_idx]
    sign = jnp.where(
        mirrored & (jax.random.uniform(key_mirror) > 0.5), -1.0, 1.0
    )
    value = value * sign
    return jax.lax.switch(branch_of[op_idx], _BRANCHES, img, value, key_op, op_idx)


def apply_subpolicy(img: jax.Array, subpolicy: jax.Array, key: jax.Array) -> jax.Array:
    """Apply one sub-policy: rows of (op_idx, prob, level).

    Each op fires independently with its probability (reference
    ``Augmentation.__call__``, ``data.py:257-263``).
    """
    num_op = subpolicy.shape[0]

    def body(i, carry):
        img, key = carry
        key, key_gate, key_op = jax.random.split(key, 3)
        op_idx = subpolicy[i, 0].astype(jnp.int32)
        prob = subpolicy[i, 1]
        level = subpolicy[i, 2]
        out = apply_op(img, op_idx, level, key_op)
        img = jnp.where(jax.random.uniform(key_gate) < prob, out, img)
        return img, key

    # num_op is tiny (2); unrolled python loop keeps XLA free to fuse
    carry = (img, key)
    for i in range(num_op):
        carry = body(i, carry)
    return carry[0]


def apply_policy(img: jax.Array, policy: jax.Array, key: jax.Array) -> jax.Array:
    """Pick one random sub-policy from `policy` [num_sub, num_op, 3] and
    apply it (reference ``Augmentation``, ``data.py:253-264``)."""
    key_choice, key_sub = jax.random.split(key)
    idx = jax.random.randint(key_choice, (), 0, policy.shape[0])
    return apply_subpolicy(img, policy[idx], key_sub)


def apply_policy_batch(images: jax.Array, policy: jax.Array, key: jax.Array) -> jax.Array:
    """vmapped :func:`apply_policy` over a [B, H, W, C] batch.

    This is the EXACT dispatch path: every image draws its sub-policy
    i.i.d., which makes the ``lax.switch`` op index a batched tensor —
    XLA lowers that to executing all 13 branches of :func:`apply_op`
    for every image per op slot and selecting one (the twelve other
    operations and one warp, where one operation would do).
    :func:`apply_policy_batch_grouped` is the scalar-dispatch
    alternative."""
    keys = jax.random.split(key, images.shape[0])
    return jax.vmap(apply_policy, in_axes=(0, None, 0))(images, policy, keys)


# ---------------------------------------------------------------------------
# grouped scalar dispatch
# ---------------------------------------------------------------------------


def apply_policy_scalar_single(img: jax.Array, policy: jax.Array, key: jax.Array) -> jax.Array:
    """:func:`apply_policy` specialized to a SINGLE-sub-policy tensor.

    Consumes the key stream identically (the sub-policy-choice key is
    split off and discarded — with one sub-policy the draw is
    vacuous), but indexes ``policy[0]`` statically instead of through a
    traced ``randint``: under an outer per-image vmap the op indices
    stay UNBATCHED, so ``lax.switch`` keeps its scalar index and
    executes exactly one branch.  Output is bitwise identical to
    :func:`apply_policy` on the same ``[1, num_op, 3]`` policy."""
    _key_choice, key_sub = jax.random.split(key)
    return apply_subpolicy(img, policy[0], key_sub)


def apply_subpolicy_batch(images: jax.Array, subpolicy: jax.Array, key: jax.Array) -> jax.Array:
    """Apply ONE sub-policy to a whole [B, H, W, C] batch with scalar
    op dispatch: `subpolicy` is unbatched under the image vmap, so each
    ``lax.switch`` executes exactly one branch for the whole batch.
    Per-image randomness (the `prob` gates, mirror signs, Cutout
    centers) stays per-image through the vmapped keys."""
    keys = jax.random.split(key, images.shape[0])
    return jax.vmap(apply_subpolicy, in_axes=(0, None, 0))(images, subpolicy, keys)


def grouped_permutation(key: jax.Array, batch: int):
    """Shared helper: a PRNG-derived batch permutation and its inverse.

    ``out[inv]`` undoes ``x[perm]`` — the grouped kernels shuffle with
    `perm`, process contiguous chunks, and restore original order with
    `inv`."""
    perm = jax.random.permutation(key, batch)
    inv = jnp.argsort(perm)
    return perm, inv


def apply_policy_batch_grouped(images: jax.Array, policy: jax.Array,
                               key: jax.Array, *, groups: int) -> jax.Array:
    """Grouped scalar-dispatch :func:`apply_policy_batch`.

    Permutes the batch with a PRNG-derived permutation, splits it into
    `groups` contiguous chunks, draws ONE sub-policy per chunk and
    applies each chunk through :func:`apply_subpolicy` with a SCALAR op
    index (the chunk loop is a ``lax.scan``, so the compiled program
    contains one switch per op slot and each invocation executes
    exactly one branch), then inverse-permutes.  Per-image `prob`
    gating, mirror signs and op randomness remain exactly per-image.

    Distributional deviation vs the exact path (documented in
    docs/PARITY.md "Augmentation dispatch"): sub-policy selection
    is STRATIFIED — each batch sees fixed per-chunk counts instead of
    i.i.d. per-image draws.  The per-image marginal is unchanged (the
    uniform permutation makes every image's chunk — hence its
    sub-policy — uniform); only within-batch selection counts and the
    joint (images of one chunk share a sub-policy) differ.

    A single-sub-policy tensor short-circuits to the bitwise-exact
    scalar path (:func:`apply_policy_scalar_single`): with one
    sub-policy there is no selection to stratify, so grouped == exact
    bit-for-bit — the case the TTA sub-policy audit runs per lane.
    """
    b = images.shape[0]
    g = int(groups)
    if g < 1:
        raise ValueError(f"groups must be >= 1, got {groups}")
    if int(policy.shape[0]) == 1:
        keys = jax.random.split(key, b)
        return jax.vmap(apply_policy_scalar_single, in_axes=(0, None, 0))(
            images, policy, keys)
    g = min(g, b)
    key_perm, key_groups = jax.random.split(key)
    perm, inv = grouped_permutation(key_perm, b)
    shuffled = jnp.take(images, perm, axis=0)
    chunk = -(-b // g)  # ceil: uneven batches pad up to g full chunks
    pad = g * chunk - b
    if pad:
        shuffled = jnp.concatenate([shuffled, shuffled[:pad]], axis=0)
    grouped = shuffled.reshape((g, chunk) + images.shape[1:])
    group_keys = jax.random.split(key_groups, g)

    def one_group(_, xs):
        imgs, k = xs
        key_choice, key_apply = jax.random.split(k)
        idx = jax.random.randint(key_choice, (), 0, policy.shape[0])
        return None, apply_subpolicy_batch(imgs, policy[idx], key_apply)

    _, out = jax.lax.scan(one_group, None, (grouped, group_keys))
    out = out.reshape((g * chunk,) + images.shape[1:])[:b]
    return jnp.take(out, inv, axis=0)
