"""Mamba-2's state-space scan (SSD, arXiv:2405.21060), computed in chunks.

Per head ``h``, with a ``[P, N]`` state (``P`` the head's width, ``N`` the
state size), a step ``Δ_t > 0`` a head, one negative scalar ``A`` a head,
and ``B_t``, ``C_t`` ``[N]`` shared by the heads of a *group* (head ``h``
reads group ``h // (heads / groups)``)::

    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t,        S_0 = 0
    y_t = S_t C_t + D x_t

What parts it from ``ops/kda.py``'s recurrence: the decay is one scalar a
head and a token (not one a channel), nothing is solved (no delta rule),
``B`` and ``C`` are shared across a group's heads, and a ``D`` skip joins
the output.

:func:`recurrent_ssd` is that recurrence token by token (a ``lax.scan``
over T steps: the definition, for tests).  :func:`chunk_ssd` is the form
the model runs, T/chunk sequential steps in place of T.  With ``a_t = Δ_t
A <= 0`` and ``G_t`` the running sum of ``a`` from the chunk's start,
inside a chunk that starts from the state ``S``::

    y_t = sum_{s<=t} (C_t . B_s) exp(G_t - G_s) Δ_s x_s     (the chunk's own tokens)
        + exp(G_t) S C_t                                    (what came before)
        + D x_t
    S'  = exp(G_last) S + sum_s exp(G_last - G_s) Δ_s x_s ⊗ B_s

so a chunk costs one ``[chunk, chunk]`` Gram matrix a *group* (``C . B``),
its masked, decayed product with ``Δ x`` a head, and two products with the
state a head.

**Every exponent taken is a sum of ``Δ A <= 0`` over a span of one
chunk**: ``G_t - G_s`` with ``s <= t`` (the difference of two running
sums *inside the chunk*, taken before the exponential, masked above the
diagonal before it too), ``G_t`` and ``G_last - G_s``.  No exponential of
a running sum is ever divided by another, so nothing overflows however
fast a head forgets, and no step is clamped.

**Two forms, chosen by shape.**  With chunks of 128, a state of 128,
heads whose widths fill whole tiles of 128 lanes group by group (64 heads
of 64 in 8 groups: every published Mamba-2 layer of the family) and a
sequence of whole chunks, :func:`chunk_ssd` runs a pair of fused TPU
kernels under a ``jax.custom_vjp`` (:func:`_fused_ssd`; interpreted where
the backend is no TPU, so a CPU test runs the code the chip runs).  A grid
step takes one chunk of one group's heads; the chunk axis is sequential
and the group's state ``[heads * P, N]`` float32 is carried in VMEM along
it.  What is local to a chunk lives in VMEM from the operation that makes
it to the ones that use it: the running sums ``G``, the group's Gram
matrix, a head's decay ``exp(G_t - G_s)`` and its product with the Gram
matrix (``[128, 128]`` each), the decays to the chunk's two ends.  HBM
sees ``x``, ``Δ``, ``B``, ``C`` in and ``y`` out, in the layout the mixer
has them in (``x`` and ``y`` as rows ``[B, T, H * P]``, a group's heads
adjacent lanes; ``B`` and ``C`` as ``[B, T, G * N]``), and one start state
a chunk kept for the backward pass (``[B, T / 128, H * P, N]``: 134 MB a
layer at 8,192 tokens, alive inside that layer's backward pass alone
under ``nn.remat``).  The ``D`` skip is added inside the kernel.

**The backward pass is written by hand** (:func:`_backward_kernel`): the
chunks run in reverse carrying ``dS``; a chunk's local quantities are
computed again from its inputs and its kept start state; every product's
cotangent is a product of the same shapes.  The decay's gradient needs no
``[128, 128]`` pass of its own.  An entry of ``within = gram * decay``
depends on the running sums through ``G_t - G_s`` alone, so ``dG_t`` is a
row sum less a column sum of ``within * d_within``, and with ``d_within =
dy (Δx)^T`` those are row-wise products of what the pass has anyway::

    dG_t = dy_t . y_t  -  (Δ x)_t . d(Δ x)_t

where ``y`` is the scan's output without the skip (the state's read,
decayed by ``exp G_t``, is in it) and ``d(Δ x)`` the whole cotangent of
``Δ x`` (what the tokens add to the state at the chunk's end, decayed by
``exp(G_last - G_s)``, is in it); ``G_last`` gets what the kept state and
those tokens owe through the chunk's total.  ``d(Δ A)`` is the running sum
of ``dG`` from each token on, ``dΔ`` follows from it and from ``d(Δ x)``,
and ``dA``, ``dD`` are summed over the chunks in accumulators a group.
``dB`` and ``dC`` sum over a group's heads inside the kernel.

Any other shape (the tests' tiny sizes, another chunk, a sequence shorter
than a chunk) takes :func:`_chunk_ssd_xla`: the same chunked computation in
``jnp``, all chunks' local parts at once (batched products over the chunk
axis) and a ``lax.scan`` over chunks for the states, ``T / chunk`` steps
of one multiply-add each, differentiated by ``jax.grad`` as it stands.

The products take the ambient matmul precision, as an ``einsum`` does
(``ops/kda.py``'s rule and its helpers): bfloat16 operands and float32
sums on the chip by default, float32 throughout under
``jax.default_matmul_precision("highest")``; decays, running sums and the
state are float32, and the running sum is a float32 product whatever the
ambient precision (a sum with a triangle of ones).

``faa_ssd_scan_traces_total{form}`` counts, at trace time, which form a
program got: ``fused`` or ``chunked_xla`` (:func:`chunk_ssd`), or
``recurrent`` (:func:`recurrent_ssd`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.ops import kda
from fast_autoaugment_tpu.ops.kda import _NN, _NT, _TN, LANES, _dot, _operand

__all__ = ["recurrent_ssd", "chunk_ssd", "DEFAULT_CHUNK"]

DEFAULT_CHUNK = 128


def _count_trace(form: str) -> None:
    # trace time: which form each program that holds a scan got
    telemetry.registry().counter(
        "faa_ssd_scan_traces_total", "Mamba-2 state-space scans traced into a "
        "program, by the form that computes them", form=form).inc()


def _float32(*arrays):
    return tuple(a.astype(jnp.float32) for a in arrays)


def recurrent_ssd(x, dt, a, b, c, d):
    """The recurrence, token by token, from the state ``S_0 = 0``.

    `x` ``[B, T, H, P]``; `dt` ``[B, T, H]`` (the step ``Δ``, positive:
    after its softplus); `a` ``[H]`` (negative); `b`, `c` ``[B, T, G, N]``
    with ``H % G == 0``; `d` ``[H]``.  Returns ``y [B, T, H, P]``,
    float32."""
    _count_trace("recurrent")
    x, dt, a, b, c, d = _float32(x, dt, a, b, c, d)
    batch, _, heads, width = x.shape
    per_group = heads // b.shape[2]

    def step(state, token):
        x_t, dt_t, b_t, c_t = token               # [B, H, P], [B, H], [B, G, N] x 2
        b_t, c_t = (jnp.repeat(g, per_group, axis=1) for g in (b_t, c_t))
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y_t = jnp.sum(state * c_t[:, :, None, :], -1) + d[:, None] * x_t
        return state, y_t

    start = jnp.zeros((batch, heads, width, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, start, tuple(
        jnp.moveaxis(arr, 1, 0) for arr in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def chunk_ssd(x, dt, a, b, c, d, *, chunk: int = DEFAULT_CHUNK):
    """:func:`recurrent_ssd`'s result in chunks of `chunk` tokens (module
    docstring); the same arguments, and a sequence of whole chunks (one
    shorter than a chunk is one chunk)."""
    x, dt, a, b, c, d = _float32(x, dt, a, b, c, d)
    _, length, heads, width = x.shape
    groups, size = b.shape[2], b.shape[3]
    if length % min(chunk, length) or heads % groups:
        raise ValueError(f"a sequence of {length} tokens is no whole number of "
                         f"chunks of {min(chunk, length)}, or {heads} heads no "
                         f"whole number of {groups} groups")
    # the shapes the kernels are written for: whole chunks of 128, a state
    # of 128, heads that fill whole tiles of 128 lanes, group by group
    fused = (chunk == DEFAULT_CHUNK and length % chunk == 0 and size == LANES
             and LANES % width == 0 and (heads // groups * width) % LANES == 0)
    _count_trace("fused" if fused else "chunked_xla")
    if not fused:
        return _chunk_ssd_xla(x, dt, a, b, c, d, min(chunk, length))
    return _fused_ssd(x, dt, a, b, c, d, kda._float32_products(), not kda._on_tpu())


# ------------------------------------------------------- the fused kernels
#
# Both kernels take ``x`` (and write ``y``) as rows ``[B, T, H * P]`` and
# ``b``, ``c`` as ``[B, T, G * N]``: the layout the mixer's slices of its
# ``xBC`` have, seen as rows.  A grid step's block is one chunk of one
# group: its heads' ``J * P`` adjacent lanes of ``x``, its 128 lanes of
# ``b`` and ``c``.  Inside, the group's lanes are taken a *tile* of 128 at
# a time (``128 / P`` heads side by side: every load, store and product is
# on whole tiles, no head is ever shifted along the lanes); a head's own
# ``[chunk, chunk]`` product takes the tile with the other heads' lanes
# zeroed.  The state of a tile's heads is stacked the same way, ``[128,
# N]``: head ``k`` of the tile is rows ``k * P`` onwards.  The step ``Δ``
# comes, and its gradient leaves, group by group, ``[B, G, T, J]`` (a
# chunk of a group: ``[chunk, J]``): 2 MB a layer where ``x`` is 134,
# turned outside the kernels.


def _per_head(columns, first: int, width: int, axis: int):
    """Column ``first + k`` of `columns` over the tile's `width` places from
    ``k * width``, the tile's heads side by side: ``[R, J]`` -> ``[R, 128]``
    along the lanes (`axis` 1), or ``[1, J]`` -> ``[128, 1]`` down the rows
    of a tile's stacked states (`axis` 0)."""
    shape = (columns.shape[0], LANES) if axis else (LANES, 1)
    place = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    out = jnp.broadcast_to(columns[:, first:first + 1], shape)
    for k in range(1, LANES // width):
        out = jnp.where(place >= k * width, columns[:, first + k:first + k + 1], out)
    return out


def _sum_all(a):
    return jnp.sum(jnp.sum(a, -1, keepdims=True), 0, keepdims=True)


class _Chunk:
    """One chunk of one group's heads inside a kernel: the running sums of
    ``Δ A`` (float32 products whatever the ambient precision: a sum with a
    triangle of ones, never rounded to bfloat16), the decays to and from
    the chunk's ends, and the group's Gram matrix."""

    def __init__(self, dt_ref, a_ref, b_ref, c_ref, width: int, exact: bool):
        self.width = width
        self.dt = dt_ref[0, 0]                                # [L, J]
        size = self.dt.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
        self.seen = row >= col                                # [t, s]: s <= t
        self.upto = jnp.where(self.seen, 1.0, 0.0)
        #: G_t, the running sum of Δ A <= 0, down the rows ``[L, J]`` and,
        #: the same numbers, along the lanes ``[J, L]``
        self.running = _dot(self.upto, self.dt * a_ref[0], _NN, True)
        self.running_rows = _dot(self.running, jnp.where(row == col, 1.0, 0.0), _TN, True)
        last = self.running[size - 1:size]                    # [1, J]
        self.from_start = jnp.exp(self.running)               # exp G_t
        self.to_end = jnp.exp(last - self.running)            # exp(G_last - G_s)
        self.keep = jnp.exp(last)                             # [1, J]
        self.b, self.c = b_ref[0], c_ref[0]                   # [L, N]
        self.gram = _dot(self.c, self.b, _NT, exact)          # C_t . B_s

    def decay(self, head: int):
        """``exp(G_t - G_s)``, ``s <= t``, zero above: ``[L, L]``.  The
        span is taken before the exponential and held to what it is, a sum
        of ``Δ A <= 0`` (above the diagonal too, where the mask drops it)."""
        span = self.running[:, head:head + 1] - self.running_rows[head:head + 1]
        return jnp.where(self.seen, jnp.exp(jnp.minimum(span, 0.0)), 0.0)

    def heads_of(self, tile: int):
        """``(head, its lanes of the tile [1, 128])`` of `tile`'s heads."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        per_tile = LANES // self.width
        return [(tile * per_tile + k,
                 (lane >= k * self.width) & (lane < (k + 1) * self.width))
                for k in range(per_tile)]

    def per_lane(self, columns, tile: int):
        return _per_head(columns, tile * (LANES // self.width), self.width, 1)

    def per_row(self, columns, tile: int):
        return _per_head(columns, tile * (LANES // self.width), self.width, 0)


def _forward_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, *rest,
                    width: int, exact: bool):
    """One chunk of one group.  `s_ref` ``[J * P, N]`` is the group's
    state, a tile's heads stacked; it stays in VMEM along the chunk axis.
    `rest`: the kept start states' block (where a backward pass will want
    them), then the state."""
    *kept_ref, s_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    for ref in kept_ref:
        ref[0, 0] = s_ref[...]
    part = _Chunk(dt_ref, a_ref, b_ref, c_ref, width, exact)
    for tile in range(x_ref.shape[2] // LANES):
        lanes = pl.ds(tile * LANES, LANES)
        x, state = x_ref[0, :, lanes], s_ref[lanes, :]
        stepped = x * part.per_lane(part.dt, tile)            # Δ_s x_s
        # what came before the chunk, read through C and decayed to the token
        y = (part.per_lane(part.from_start, tile) * _dot(part.c, state, _NT, exact)
             + part.per_lane(d_ref[0], tile) * x)
        for head, mine in part.heads_of(tile):
            # the chunk's own tokens: (C_t . B_s) exp(G_t - G_s) Δ_s x_s
            y += _dot(part.decay(head) * part.gram, jnp.where(mine, stepped, 0.0),
                      _NN, exact)
        y_ref[0, :, lanes] = y
        s_ref[lanes, :] = part.per_row(part.keep, tile) * state + _dot(
            stepped * part.per_lane(part.to_end, tile), part.b, _TN, exact)


def _backward_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, kept_ref,
                     dy_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref, ds_ref,
                     *, width: int, exact: bool):
    """The chunk the forward kernel's grid step took, in reverse order;
    `ds_ref` ``[J * P, N]`` carries the state's cotangent, `da_ref` and
    `dd_ref` ``[1, 1, 1, J]`` sum ``dA`` and ``dD`` over the chunks."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    part = _Chunk(dt_ref, a_ref, b_ref, c_ref, width, exact)
    size, per_group = part.dt.shape
    head_of = jax.lax.broadcasted_iota(jnp.int32, (1, per_group), 1)
    d_gram = jnp.zeros((size, size), jnp.float32)
    d_b, d_c = jnp.zeros_like(part.b), jnp.zeros_like(part.c)
    d_running, d_dt = jnp.zeros_like(part.dt), jnp.zeros_like(part.dt)
    d_last, d_skip = jnp.zeros_like(part.keep), jnp.zeros_like(part.keep)
    for tile in range(x_ref.shape[2] // LANES):
        lanes = pl.ds(tile * LANES, LANES)
        x, d_y = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        state, d_state = kept_ref[0, 0, lanes, :], ds_ref[lanes, :]
        dt, to_end = part.per_lane(part.dt, tile), part.per_lane(part.to_end, tile)
        from_start = part.per_lane(part.from_start, tile)
        stepped = x * dt
        ended = stepped * to_end                              # what joins the state
        read = d_y * from_start                               # dy as the state met it
        d_ended = _dot(part.b, d_state, _NT, exact)           # [L, 128]
        y = from_start * _dot(part.c, state, _NT, exact)
        d_stepped = to_end * d_ended
        for head, mine in part.heads_of(tile):
            decay = part.decay(head)
            within = _operand(decay * part.gram, exact)
            d_mine = jnp.where(mine, d_y, 0.0)
            y += _dot(within, jnp.where(mine, stepped, 0.0), _NN, exact)
            d_stepped += _dot(within, d_mine, _TN, exact)
            d_gram += decay * _dot(d_mine, stepped, _NT, exact)
        # the decay's gradient: an entry of `within` depends on G_t - G_s
        # alone, so its row sums less its column sums of within * d_within
        # are dy_t . y_t - (Δx)_s . d(Δx)_s, token by token; the state's
        # terms (the read decayed by exp G_t, the tokens taken to the
        # chunk's end) are in the same two products
        d_span = d_y * y - stepped * d_stepped
        through_end = ended * d_ended
        from_dt, skipped, held = d_stepped * x, d_y * x, state * d_state
        keep_rows = part.per_row(part.keep, tile)
        row = jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0)
        for k, (head, mine) in enumerate(part.heads_of(tile)):
            only = lambda a: jnp.sum(jnp.where(mine, a, 0.0), -1, keepdims=True)
            here = head_of == head
            d_running = jnp.where(here, only(d_span), d_running)
            d_dt = jnp.where(here, only(from_dt), d_dt)
            d_skip = jnp.where(here, jnp.sum(only(skipped), 0, keepdims=True), d_skip)
            rows = (row >= k * width) & (row < (k + 1) * width)
            # what every token of the chunk owes through the total G_last
            d_last = jnp.where(
                here, jnp.sum(only(through_end), 0, keepdims=True)
                + _sum_all(jnp.where(rows, keep_rows * held, 0.0)), d_last)
        dx_ref[0, :, lanes] = d_stepped * dt + part.per_lane(d_ref[0], tile) * d_y
        d_c += _dot(read, state, _NN, exact)
        d_b += _dot(ended, d_state, _NN, exact)
        ds_ref[lanes, :] = keep_rows * d_state + _dot(read, part.c, _TN, exact)
    dc_ref[0] = d_c + _dot(d_gram, part.b, _NN, exact)
    db_ref[0] = d_b + _dot(d_gram, part.c, _TN, exact)
    # G_t sums Δ A over the tokens up to t: its gradient is dG's sum from t on
    d_log_decay = _dot(part.upto, d_running, _TN, True) + d_last
    ddt_ref[0, 0] = d_log_decay * a_ref[0] + d_dt
    da_ref[0, 0] += jnp.sum(d_log_decay * part.dt, 0, keepdims=True)
    dd_ref[0, 0] += d_skip


class _Blocks:
    """What both kernels' ``pallas_call``s share: the grid (batch, group,
    then the chunks in order) and a grid step's blocks; `at` maps the step
    along the chunk axis to the chunk it takes."""

    def __init__(self, x, b, at, interpret: bool):
        self.batch, length, heads, self.width = x.shape
        self.groups, self.size = b.shape[2], b.shape[3]
        self.per_group = heads // self.groups
        self.lanes = self.per_group * self.width
        self.count = length // DEFAULT_CHUNK
        self.at = at
        self.state = pltpu.VMEM((self.lanes, self.size), jnp.float32)
        self.options = dict(
            grid=(self.batch, self.groups, self.count), interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=64 * 1024 * 1024))

    def tokens(self, width):    # of [B, T, G * width]
        return pl.BlockSpec((1, DEFAULT_CHUNK, width), lambda i, g, n: (i, self.at(n), g))

    @property
    def steps(self):            # of Δ as [B, G, T, J]
        return pl.BlockSpec((1, 1, DEFAULT_CHUNK, self.per_group),
                            lambda i, g, n: (i, g, self.at(n), 0))

    @property
    def a_head(self):           # of [G, 1, J]
        return pl.BlockSpec((1, 1, self.per_group), lambda i, g, n: (g, 0, 0))

    @property
    def summed(self):           # of [B, G, 1, J], a sum over the chunks
        return pl.BlockSpec((1, 1, 1, self.per_group), lambda i, g, n: (i, g, 0, 0))

    @property
    def kept(self):             # of [B, n, H * P, N]
        return pl.BlockSpec((1, 1, self.lanes, self.size),
                            lambda i, g, n: (i, self.at(n), g, 0))

    def operands(self, x, dt, a, b, c, d):
        """The six arguments as the kernels take them, and their specs."""
        batch, length = x.shape[:2]
        per_head = lambda v: v.reshape(self.groups, 1, self.per_group)
        steps = jnp.swapaxes(dt.reshape(batch, length, self.groups, self.per_group), 1, 2)
        return ((x.reshape(batch, length, -1), steps, per_head(a),
                 b.reshape(batch, length, -1), c.reshape(batch, length, -1), per_head(d)),
                [self.tokens(self.lanes), self.steps, self.a_head,
                 self.tokens(self.size), self.tokens(self.size), self.a_head])


# Both are jitted by themselves, as ``ops/kda.py``'s are and for its
# reason: a model's layers call them at the same shapes, and a ``jit``
# inside a trace is traced once and lowered once a program, where a bare
# ``pallas_call`` is lowered to Mosaic again at every call (four layers,
# three calls a layer).


@functools.partial(jax.jit, static_argnames=("exact", "interpret", "keep"))
def _forward(x, dt, a, b, c, d, exact: bool, interpret: bool, keep: bool):
    """``y`` and, with `keep`, every chunk's start state ``[B, n, H * P,
    N]``."""
    blocks = _Blocks(x, b, lambda n: n, interpret)
    operands, in_specs = blocks.operands(x, dt, a, b, c, d)
    out_shape = [jax.ShapeDtypeStruct(operands[0].shape, jnp.float32)]
    out_specs = [blocks.tokens(blocks.lanes)]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (blocks.batch, blocks.count, blocks.groups * blocks.lanes, blocks.size),
            jnp.float32))
        out_specs.append(blocks.kept)
    y, *states = pl.pallas_call(
        functools.partial(_forward_kernel, width=blocks.width, exact=exact),
        out_shape=out_shape, in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=[blocks.state], name="ssd_forward", **blocks.options,
    )(*operands)
    return (y.reshape(x.shape), *states)


@functools.partial(jax.jit, static_argnames=("exact", "interpret"))
def _backward(x, dt, a, b, c, d, kept_states, d_y, exact: bool, interpret: bool):
    count = x.shape[1] // DEFAULT_CHUNK
    blocks = _Blocks(x, b, lambda n: count - 1 - n, interpret)
    operands, in_specs = blocks.operands(x, dt, a, b, c, d)
    rows, steps, _, b_rows, c_rows, _ = operands
    like = lambda v: jax.ShapeDtypeStruct(v.shape, jnp.float32)
    summed = jax.ShapeDtypeStruct((blocks.batch, blocks.groups, 1, blocks.per_group),
                                  jnp.float32)
    dx, ddt, da, db, dc, dd = pl.pallas_call(
        functools.partial(_backward_kernel, width=blocks.width, exact=exact),
        out_shape=[like(rows), like(steps), summed, like(b_rows), like(c_rows), summed],
        in_specs=[*in_specs, blocks.kept, blocks.tokens(blocks.lanes)],
        out_specs=[blocks.tokens(blocks.lanes), blocks.steps, blocks.summed,
                   blocks.tokens(blocks.size), blocks.tokens(blocks.size), blocks.summed],
        scratch_shapes=[blocks.state], name="ssd_backward", **blocks.options,
    )(*operands, kept_states, d_y.reshape(rows.shape))
    return (dx.reshape(x.shape), jnp.swapaxes(ddt, 1, 2).reshape(dt.shape),
            jnp.sum(da, 0).reshape(a.shape), db.reshape(b.shape), dc.reshape(c.shape),
            jnp.sum(dd, 0).reshape(d.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _fused_ssd(x, dt, a, b, c, d, exact: bool, interpret: bool):
    """The scan through the kernels; all float32.  `exact`: float32
    products; `interpret`: no TPU to compile them for."""
    return _forward(x, dt, a, b, c, d, exact, interpret, keep=False)[0]


def _fused_ssd_fwd(x, dt, a, b, c, d, exact: bool, interpret: bool):
    y, kept = _forward(x, dt, a, b, c, d, exact, interpret, keep=True)
    return y, (x, dt, a, b, c, d, kept)


def _fused_ssd_bwd(exact: bool, interpret: bool, residuals, d_y):
    return _backward(*residuals, d_y, exact, interpret)


_fused_ssd.defvjp(_fused_ssd_fwd, _fused_ssd_bwd)

# ------------------------------------------------- the form in jnp and XLA

def _chunk_ssd_xla(x, dt, a, b, c, d, chunk: int):
    """:func:`chunk_ssd` for the shapes the kernels do not take (all
    float32, `chunk` dividing T)."""
    batch, length, heads, width = x.shape
    groups, size = b.shape[2], b.shape[3]
    per_group = heads // groups
    count = length // chunk

    def in_chunks(arr, *per_head):
        """``[B, T, G * J, ...]`` -> ``[B, n, G, J, L, ...]`` (`per_head`:
        ``(J,)``), or ``[B, T, G, N]`` -> ``[B, n, G, L, N]``: chunk n,
        group G, head J of it, token L of the chunk."""
        arr = arr.reshape((batch, count, chunk, groups) + per_head + arr.shape[3:])
        return jnp.moveaxis(arr, 2, 3 + len(per_head))

    x_c = in_chunks(x, per_group)                              # [B, n, G, J, L, P]
    dt_c = in_chunks(dt, per_group)                            # [B, n, G, J, L]
    b_c, c_c = in_chunks(b), in_chunks(c)                      # [B, n, G, L, N]
    log_decay = dt_c * a.reshape(groups, per_group, 1)         # Δ A <= 0
    running = jnp.cumsum(log_decay, axis=-1)                   # G_t, inside the chunk
    last = running[..., -1:]                                   # [B, n, G, J, 1]

    # the chunk's own tokens: (C_t . B_s) exp(G_t - G_s) Δ_s, s <= t
    gram = jnp.einsum("bngtk,bngsk->bngts", c_c, b_c)          # a group
    span = running[..., :, None] - running[..., None, :]       # [B, n, G, J, t, s]
    seen = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    within = (jnp.exp(jnp.where(seen, span, -jnp.inf)) * dt_c[..., None, :]
              * gram[:, :, :, None])
    y = jnp.einsum("bngjts,bngjsp->bngjtp", within, x_c)

    # what each chunk adds to the state it hands on, and the states' pass
    to_end = jnp.exp(last - running) * dt_c                    # [B, n, G, J, L]
    added = jnp.einsum("bngjlp,bnglk->bngjpk", x_c * to_end[..., None], b_c)
    keep = jnp.exp(last)[..., None]                            # [B, n, G, J, 1, 1]

    def carry(state, chunk_of):
        keep_n, added_n = chunk_of
        return keep_n * state + added_n, state

    start = jnp.zeros((batch, groups, per_group, width, size), jnp.float32)
    _, starts = jax.lax.scan(carry, start, (
        jnp.moveaxis(keep, 1, 0), jnp.moveaxis(added, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                        # [B, n, G, J, P, N]

    # what came before the chunk, read through C and decayed to the token
    before = jnp.einsum("bnglk,bngjpk->bngjlp", c_c, starts)
    y = y + before * jnp.exp(running)[..., None]
    y = jnp.moveaxis(y, 4, 2).reshape(batch, length, heads, width)
    return y + d[:, None] * x
