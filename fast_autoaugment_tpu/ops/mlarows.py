"""Latent attention's operands laid on the projections' own rows.

The fused attention kernels (``ops/attention.py``) read ``[B, T, H * D]``,
the heads side by side as a projection of that width writes them, and one
product a tile serves both parts of latent attention's score where every
head's last ``Ds`` lanes hold the part against the shared key: the query's
own there in `q`, the one shared key part — the same for all heads — in
`k`.  Cut into ``[B, T, H, D]`` to put it there (a slice, a rotation as two
strided halves, a concatenate, a broadcast to every head, a pad) every
operand is moved between two tilings by XLA, whole, several times a pass
(PERF.md section 6, PR 52).  Here the rows stay rows, and each pass touches
what it must:

- :func:`turn`, the queries, forward: one pass over ``[B, T, H * D]`` that
  turns every head's last ``Ds`` lanes by position and writes all of it *as
  the kernels' products take it* (bfloat16 unless the products are float32
  ones) — the rounding is this pass's store and no pass of its own, and it
  comes after the rotation, as it did.
- :func:`lay`, the keys, forward, in place on a head's last 128-lane piece:
  `k` comes with zeros in the shared part's lanes (zero weight columns wrote
  them), so XLA rounds it where the product writes it, and the pass adds the
  shared part (turned) into those lanes of every head — a sum with zero,
  rounded once.
- :func:`unlay`, backward, in place on the same pieces: the rotation's
  transpose on ``dq`` (the rest of ``dq`` is the projection's cotangent as
  it lies), and the shared part's gradient, ``dk``'s lanes there summed
  over the heads and turned back once.

The rotation is DeepSeek's interleaved one, pair ``(2i, 2i + 1)`` of the
``Ds`` lanes turned by ``t * theta ** (-2i / Ds)``, *in place*: a lane's
partner is its neighbour, one lane roll up and one down (``pltpu.roll``)
against tables that are zero where the neighbour is the wrong one.  (The
mixer's cut path de-interleaves; a score sums over the lanes, so any order
that `q` and `k` share is the same score.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fast_autoaugment_tpu.ops import kda

__all__ = ["turn", "lay", "unlay", "admits"]

LANES = kda.LANES
#: tokens a grid step of :func:`turn`: a block is ``[ROWS, H * D]`` float32 in
#: (5.2 MB at 20 heads of 256) and the operands' dtype out
ROWS = 256
#: tokens a grid step of the passes whose block is one 128-lane piece
PIECE_ROWS = 1024
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def admits(dim: int, shared: int) -> bool:
    """Whether heads of `dim` lanes whose last `shared` are the part against
    the shared key can be laid here: whole 128-lane pieces, the part inside
    the last one, its pairs whole."""
    return dim % LANES == 0 and 0 < shared <= LANES and shared % 2 == 0


def _tables(angle, shared: int, sign: float = 1.0):
    """``[T, shared / 2]`` angles -> ``(cos, up, down)``, ``[T, 128]`` each,
    for a head's last piece: ``y = n * cos + roll(n, -1) * up + roll(n, 1) *
    down`` turns pair ``(2i, 2i + 1)`` of its last `shared` lanes by angle
    ``i`` (times `sign`: -1 is the transpose) and leaves the other lanes."""
    cos, sin = jnp.cos(angle), sign * jnp.sin(angle)
    zero = jnp.zeros_like(sin)
    pairs = lambda even, odd: jnp.stack([even, odd], -1).reshape(angle.shape[0], shared)
    own = ((0, 0), (LANES - shared, 0))
    return (jnp.pad(pairs(cos, cos), own, constant_values=1.0),
            jnp.pad(pairs(-sin, zero), own), jnp.pad(pairs(zero, sin), own))


def _turn(n, tables):
    cos_ref, up_ref, down_ref = tables
    return (n * cos_ref[...] + pltpu.roll(n, LANES - 1, 1) * up_ref[...]
            + pltpu.roll(n, 1, 1) * down_ref[...])


def _turn_kernel(x_ref, *refs, pieces: int, each: int):
    """`x_ref` ``[1, rows, pieces * 128]`` -> `y_ref`, every `each`-th piece
    (a head's last) turned, all of it rounded as `y_ref` holds it."""
    *tables, y_ref = refs
    for piece in range(pieces):
        lanes = slice(piece * LANES, (piece + 1) * LANES)
        n = x_ref[0, :, lanes]
        if piece % each == each - 1:
            n = _turn(n, tables)
        y_ref[0, :, lanes] = n.astype(y_ref.dtype)


def _lay_kernel(x_ref, s_ref, *refs):
    """One head's last piece of `x`, already as the products take it, in
    place: plus `s_ref` ``[1, rows, 128]`` (turned where there are tables),
    whose lanes are zeros in `x`, so that the sum is rounded once."""
    *tables, y_ref = refs
    laid = _turn(s_ref[0], tables) if tables else s_ref[0]
    y_ref[0] = (x_ref[0].astype(jnp.float32) + laid).astype(y_ref.dtype)


def _unlay_kernel(*refs, heads: int, rotate: bool):
    """One head's last piece of ``dq`` (turned back, in place) and of ``dk``
    (summed over the grid's heads into `ds_ref`, turned back with the last);
    `tables` are the transpose's."""
    if not rotate:
        dk_ref, ds_ref = refs
    else:
        dq_ref, dk_ref, *tables, dq_out_ref, ds_ref = refs
        dq_out_ref[0] = _turn(dq_ref[0], tables)
    head = pl.program_id(2)

    @pl.when(head == 0)
    def _():
        ds_ref[0] = dk_ref[0]

    @pl.when(head > 0)
    def _():
        ds_ref[0] += dk_ref[0]

    if rotate:
        @pl.when(head == heads - 1)
        def _():
            ds_ref[0] = _turn(ds_ref[0], tables)


def _rows(length: int, most: int) -> int:
    """The tokens a block: `most` halved until it divides `length`, else all."""
    rows = most
    while rows >= 16 and length % rows:
        rows //= 2
    return rows if rows >= 16 else length


class _Pieces:
    """The blocks of the passes that touch a head's last 128-lane piece
    alone, on a grid of (batch, tokens, head)."""

    def __init__(self, shape, heads: int):
        self.batch, self.length, width = shape
        each = width // heads // LANES
        self.rows = _rows(self.length, PIECE_ROWS)
        self.grid = (self.batch, self.length // self.rows, heads)
        self.piece = pl.BlockSpec((1, self.rows, LANES),
                                  lambda b, t, h: (b, t, h * each + each - 1))
        self.one = pl.BlockSpec((1, self.rows, LANES), lambda b, t, h: (b, t, 0))
        self.table = pl.BlockSpec((self.rows, LANES), lambda b, t, h: (t, 0))

    def params(self, heads_semantics: str):
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", heads_semantics),
            vmem_limit_bytes=VMEM_LIMIT_BYTES)


@functools.partial(jax.jit, static_argnames=("heads", "exact", "interpret"))
def turn(x, heads: int, angle, *, exact: bool = False, interpret: bool = False):
    """The queries' rows `x` ``[B, T, heads * D]`` float32 -> the same shape
    as a product takes it (``kda._operand``: bfloat16 unless `exact`), the
    last ``Ds`` lanes of every head turned by `angle` ``[T, Ds / 2]`` (None:
    the rounding alone, XLA's to fuse into what wrote `x`)."""
    batch, length, width = x.shape
    if angle is None:
        return kda._operand(x, exact)
    rows = _rows(length, ROWS)
    block = pl.BlockSpec((1, rows, width), lambda b, t: (b, t, 0))
    table = pl.BlockSpec((rows, LANES), lambda b, t: (t, 0))
    return pl.pallas_call(
        functools.partial(_turn_kernel, pieces=width // LANES, each=width // heads // LANES),
        grid=(batch, length // rows), in_specs=[block] + [table] * 3, out_specs=block,
        out_shape=jax.eval_shape(functools.partial(kda._operand, exact=exact), x),
        interpret=interpret, name="mla_rows_turn",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(x.astype(jnp.float32), *_tables(angle.astype(jnp.float32), 2 * angle.shape[-1]))


@functools.partial(jax.jit, static_argnames=("heads", "exact", "interpret"))
def lay(x, heads: int, shared, angle, *, exact: bool = False, interpret: bool = False):
    """The keys' rows `x` ``[B, T, heads * D]`` float32, zeros in the last
    ``Ds`` lanes of every head -> the same shape as a product takes it, with
    `shared` ``[B, T, Ds]`` in those lanes, turned by `angle` ``[T, Ds / 2]``
    first where one is given."""
    part = shared.shape[-1]
    # rounded where the product writes it (XLA fuses that in); then in place
    x = kda._operand(x.astype(jnp.float32), exact)
    tables = () if angle is None else _tables(angle.astype(jnp.float32), part)
    blocks = _Pieces(x.shape, heads)
    return pl.pallas_call(
        _lay_kernel, grid=blocks.grid,
        in_specs=[blocks.piece, blocks.one] + [blocks.table] * len(tables),
        out_specs=blocks.piece, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0}, interpret=interpret, name="mla_rows_lay",
        compiler_params=blocks.params("parallel"),
    )(x, jnp.pad(shared.astype(jnp.float32), ((0, 0), (0, 0), (LANES - part, 0))), *tables)


@functools.partial(jax.jit, static_argnames=("heads", "shared", "interpret"))
def unlay(dq, dk, heads: int, shared: int, angle, *, interpret: bool = False):
    """:func:`turn`'s and :func:`lay`'s transpose on the kernels' gradients
    ``[B, T, heads * D]`` float32: ``(dq, d_shared)`` — `dq` with every
    head's last `shared` lanes turned back (as it came where `angle` is
    None), and the shared part's gradient ``[B, T, shared]``, `dk`'s same
    lanes summed over the heads and turned back.  `dk` itself is its
    projection's cotangent as it lies (the lanes the shared part was added
    into meet zero weight columns)."""
    blocks = _Pieces(dq.shape, heads)
    summed = jax.ShapeDtypeStruct((blocks.batch, blocks.length, LANES), jnp.float32)
    rotate = angle is not None
    if rotate:
        tables = _tables(angle.astype(jnp.float32), shared, sign=-1.0)
        operands, in_specs = [dq, dk, *tables], [blocks.piece] * 2 + [blocks.table] * 3
        out_shape = [jax.ShapeDtypeStruct(dq.shape, dq.dtype), summed]
        out_specs = [blocks.piece, blocks.one]
    else:
        operands, in_specs, out_shape, out_specs = [dk], [blocks.piece], [summed], [blocks.one]
    out = pl.pallas_call(
        functools.partial(_unlay_kernel, heads=heads, rotate=rotate),
        grid=blocks.grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        input_output_aliases={0: 0} if rotate else {}, interpret=interpret,
        name="mla_rows_unlay", compiler_params=blocks.params("arbitrary"))(*operands)
    return out[0] if rotate else dq, out[-1][..., LANES - shared:]
