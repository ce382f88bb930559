"""A norm a head and a rotation by position in one pass over the rows.

``y = rotate(rms_norm(x) * weight)`` on ``[B, T, H * 128]``, the heads
side by side as a projection writes them: a head is 128 lanes of a row,
its RMS norm a sum along those lanes, and the rotation's partner of
channel ``i`` — channel ``i + 64`` of the same head — a lane roll by half
a row (``pltpu.roll``), which XLA has no operation for: written as two
halves and a ``concatenate`` it costs two half-width arrays padded to
whole tiles between fusions, and its backward pass is fused into the
operand of the projection's transposed product (PERF.md section 6, PR
50).  Two Pallas kernels under a ``jax.custom_vjp`` (interpreted where
the backend is no TPU): forward reads ``x`` and writes ``y``; backward
reads ``x`` and ``dy``, computes the norm's factor again, writes ``dx``
and gathers the weight's gradient along the grid.  Either part is left
out where it is not given (`weight` None: no norm; `angle` None: no
rotation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fast_autoaugment_tpu.ops import kda

__all__ = ["head_norm_rotate"]

LANES = kda.LANES
#: tokens a grid step: a block is ``[ROWS, H * 128]`` float32 (4 MB at 32 heads)
ROWS = 256
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _tables(angle):
    """``[T, 64]`` angles -> the cosine twice and the signed sine, ``[T, 128]``
    each: ``y = n * cos + roll(n, 64) * sin``."""
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([cos, cos], -1), jnp.concatenate([-sin, sin], -1)


def _factor(x, eps: float):
    return jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _forward_kernel(*refs, heads: int, eps: float | None, rotate: bool):
    x_ref, refs = refs[0], refs[1:]
    if eps is not None:
        w_ref, refs = refs[0], refs[1:]
    if rotate:
        cos_ref, sin_ref, refs = refs[0], refs[1], refs[2:]
    (y_ref,) = refs
    for h in range(heads):
        lanes = slice(h * LANES, (h + 1) * LANES)
        n = x_ref[0, :, lanes]
        if eps is not None:
            n = n * _factor(n, eps) * w_ref[...]
        if rotate:
            n = n * cos_ref[...] + pltpu.roll(n, LANES // 2, 1) * sin_ref[...]
        y_ref[0, :, lanes] = n


def _backward_kernel(*refs, heads: int, eps: float | None, rotate: bool):
    x_ref, dy_ref, refs = refs[0], refs[1], refs[2:]
    if eps is not None:
        w_ref, refs = refs[0], refs[1:]
    if rotate:
        cos_ref, sin_ref, refs = refs[0], refs[1], refs[2:]
    dx_ref, refs = refs[0], refs[1:]
    if eps is not None:
        (dw_ref,) = refs

        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)

        dw = jnp.zeros((1, LANES), jnp.float32)
    for h in range(heads):
        lanes = slice(h * LANES, (h + 1) * LANES)
        d = dy_ref[0, :, lanes]
        if rotate:          # the rotation's transpose: by the angle's negative
            d = d * cos_ref[...] - pltpu.roll(d, LANES // 2, 1) * sin_ref[...]
        if eps is not None:
            x = x_ref[0, :, lanes]
            r = _factor(x, eps)
            dw = dw + jnp.sum(d * x * r, 0, keepdims=True)
            g = d * w_ref[...]
            d = r * (g - x * (r * r) * jnp.mean(g * x, -1, keepdims=True))
        dx_ref[0, :, lanes] = d
    if eps is not None:
        dw_ref[...] += dw


def _call(kernel, arrays, weight, tables, heads, eps, interpret, outputs):
    batch, length, width = arrays[0].shape
    rows = ROWS if length % ROWS == 0 else length
    block = pl.BlockSpec((1, rows, width), lambda b, t: (b, t, 0))
    in_specs, operands = [block] * len(arrays), list(arrays)
    if weight is not None:
        in_specs.append(pl.BlockSpec((1, LANES), lambda b, t: (0, 0)))
        operands.append(weight.reshape(1, LANES).astype(jnp.float32))
    if tables is not None:
        in_specs += [pl.BlockSpec((rows, LANES), lambda b, t: (t, 0))] * 2
        operands += list(tables)
    out_shape = [jax.ShapeDtypeStruct(arrays[0].shape, jnp.float32)]
    out_specs = [block]
    if outputs == 2:
        out_shape.append(jax.ShapeDtypeStruct((1, LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((1, LANES), lambda b, t: (0, 0)))
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, eps=eps if weight is not None else None,
                          rotate=tables is not None),
        grid=(batch, length // rows), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
        name="head_norm_rotate" + kernel.__name__.removesuffix("_kernel"),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES))(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused(x, weight, tables, heads: int, eps: float, interpret: bool):
    return _call(_forward_kernel, [x], weight, tables, heads, eps, interpret, 1)[0]


def _fused_fwd(x, weight, tables, heads, eps, interpret):
    return _fused(x, weight, tables, heads, eps, interpret), (x, weight, tables)


def _fused_bwd(heads, eps, interpret, residuals, dy):
    x, weight, tables = residuals
    out = _call(_backward_kernel, [x, dy], weight, tables, heads, eps, interpret,
                2 if weight is not None else 1)
    dw = None if weight is None else out[1].reshape(weight.shape).astype(weight.dtype)
    return out[0], dw, None if tables is None else jax.tree.map(jnp.zeros_like, tables)


_fused.defvjp(_fused_fwd, _fused_bwd)


def head_norm_rotate(x, heads: int, *, weight=None, eps: float = 1e-5, angle=None):
    """`x` ``[B, T, heads * 128]`` float32 -> the same shape: every head's
    128 channels RMS-normed and scaled by `weight` ``[128]`` (None: as they
    are), then pair ``(i, i + 64)`` turned by `angle` ``[T, 64]`` (None: not
    turned)."""
    if x.shape[-1] != heads * LANES:
        raise ValueError(f"{x.shape} is not {heads} heads of {LANES} lanes")
    tables = None if angle is None else _tables(angle.astype(jnp.float32))
    return _fused(x.astype(jnp.float32), weight, tables, heads, eps, not kda._on_tpu())
