"""Kimi Delta Attention's recurrence, computed in chunks.

Per head, with a ``[K, V]`` state (Kimi Linear, arXiv:2510.26692; fla's
``chunk_kda``)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is a log-decay per *channel* of the key, which is what parts
KDA from the gated delta rule (one decay a head).  :func:`recurrent_kda`
is that recurrence token by token (a ``lax.scan`` over T steps: the form
tests and the benchmark's reference compare with);
:func:`chunk_kda` is the form the model runs: T/C sequential steps in
place of T.

Inside a chunk of C tokens, with ``G_t`` the running sum of ``g`` from
the chunk's start and ``S_0`` the state it starts from, write ``u_t`` for
the corrected value ``beta_t (v_t - k_t^T Diag(exp g_t) S_{t-1})``.  Then
``S_t = Diag(exp G_t) S_0 + sum_{s<=t} Diag(exp(G_t - G_s)) k_s u_s^T``
and the ``u`` solve one unit lower-triangular system a chunk,

    (I + A) U = beta * (V - (K * exp G) S_0),
    A[t, s] = beta_t sum_c k_tc k_sc exp(G_tc - G_sc)   (s < t),

so a chunk costs two decayed Gram matrices (``A`` and the same sum with
``q_t`` for ``k_t``, ``s <= t``), the system's inverse, and five products
with the state.

**The decay is applied without overflow.**  ``exp(G_t - G_s)`` is at
most 1, but written as a product ``(k_t exp G_t)(k_s exp -G_s)`` for the
MXU its second factor overflows once a channel decays by ``e^88`` inside
a chunk.  So a pair of tokens always meets through a reference row that
lies *between* them: both exponents are then non-positive.  No gate is
clamped, and no exponent taken is positive.  The two forms below differ
in where the reference rows lie (see each).

**Two forms, chosen by shape.**  With ``K = V = 128`` and chunks of 64
— every configuration's shapes — :func:`chunk_kda` runs a pair of fused
TPU kernels under a ``jax.custom_vjp`` (:func:`_fused_kda`; interpreted
where the backend is no TPU, so a CPU test runs the code the chip runs).
A grid step takes one chunk of every head; what is local to a chunk lives
in VMEM from the operation that makes it to the ones that use it, and the
state is carried in VMEM along the sequential chunk axis.  HBM sees ``q,
k, v, g, beta`` in, ``o`` out and one state a chunk kept for the backward
pass — ``q, k, v, g, o`` and their gradients as ``[B, T, H * K]``, the
heads side by side as a projection of width ``H * K`` writes them, so
that nothing is moved between the projections' tiling and the kernels'
(:func:`chunk_kda` takes and gives that shape, or ``[B, T, H, K]``; with
`unit_scale` the kernels also bring a head's ``q`` and ``k`` rows to unit
length once loaded, a lane reduction of the window, and the backward
kernel hands back the gradients of the rows as they came).  In
a grid step's block ``[C, H * K]`` a token is a sublane and a head 128
lanes: a head's chunk is the dense window ``[:, h * 128:(h + 1) * 128]``.
There the reference rows are those of a binary tree over the
chunk: at level ``b`` (32, 16, ... 1) every token of an odd block of
``b`` tokens meets every token of the even block before it through the
row between the two blocks, one product on the MXU a level, and the
levels' masks partition the lower triangle.  The levels' sums of ``g``
run along the sublanes, a segmented scan by log steps: every token holds
the sum of its block through itself and the sum after itself, and a
level up takes the sibling block's whole sum from the token ``b``
sublanes away — sums of ``g`` itself at every level
(:func:`_decay_factors`).  The system's inverse
follows the same tree (the inverse of a block of ``2b`` from those of its
two halves: block forward substitution, two products a level), so
nothing is solved row by row.  Two heads' chunks are stacked for these
products (128 rows: the MXU's width), block-diagonal by head.

**The backward pass is written by hand** (:func:`_backward_kernel`), as
fla's is: the chunks run in reverse carrying ``dS``; a chunk's local
quantities are computed again from its inputs and its kept start state;
every product's cotangent is a product of the same shapes.  The decay's
gradient needs no pass of its own: a Gram's entry depends on ``G_t -
G_s`` alone, so ``dG_t = x_t * dx_t - k_t * dk_t`` row by row from the
Gram's own input gradients, and ``dg`` is the reverse running sum of
``dG`` over the chunk, again by log steps along the sublanes.

Any other shape (the tests' ``K`` of 16, chunks of 16 or 32, a sequence
shorter than a chunk) takes :func:`_chunk_kda_xla`: the same chunked
computation in ``jnp``, everything but ``S_0`` for all chunks at once and
a ``lax.scan`` over chunks for the state, differentiated by ``jax.grad``
as it stands.  Its reference rows are those between sub-blocks of 16
(:func:`_decayed_gram`); a pair inside a sub-block is summed channel by
channel with its own exponent.

The products take the ambient matmul precision, as an ``einsum`` does:
bfloat16 operands and float32 sums on the chip by default, float32
throughout under ``jax.default_matmul_precision("highest")``.

``faa_kda_scan_traces_total{form}`` counts, at trace time, which form a
program got: ``fused`` or ``chunked_xla``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fast_autoaugment_tpu.core import telemetry

__all__ = ["chunk_kda", "recurrent_kda", "unit_factor", "by_tile", "DEFAULT_CHUNK",
           "SUB_BLOCK"]

DEFAULT_CHUNK = 64
SUB_BLOCK = 16
LANES = 128
#: tokens in a tile of a float32 ``[T, H * K]`` array on the chip
SUBLANES = 8
#: heads whose chunks the kernels stack for one product: two chunks of 64
#: rows fill the MXU's 128
GROUP = LANES // DEFAULT_CHUNK


def recurrent_kda(q, k, v, g, beta, initial_state=None):
    """The recurrence as written, one token a step.

    `q`, `k`, `g`: ``[B, T, H, K]``; `v`: ``[B, T, H, V]``; `beta`:
    ``[B, T, H]``.  Returns ``(o [B, T, H, V], S [B, H, K, V])``."""
    batch, _, heads, kdim = q.shape
    vdim = v.shape[-1]
    state = (jnp.zeros((batch, heads, kdim, vdim), jnp.float32)
             if initial_state is None else initial_state)

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs            # [B, H, K] ... [B, H]
        state = state * jnp.exp(g_t)[..., None]
        read = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        u_t = b_t[..., None] * (v_t - read)
        state = state + k_t[..., None] * u_t[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state, out = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(out, 0, 1), state


def chunk_kda(q, k, v, g, beta, initial_state=None, *, chunk: int = DEFAULT_CHUNK,
              unit_scale: float | None = None):
    """:func:`recurrent_kda` in chunks of `chunk` tokens (T a multiple of
    it, or shorter than it): same arguments, same results.  `q, k, v, g`
    may also come as a projection leaves them, ``[B, T, H * K]`` with the
    heads side by side (H is `beta`'s), and `o` then comes back so: the
    layout the kernels read, which a model keeps from end to end.  With
    `unit_scale`, `q` and `k` are first brought to unit length over a
    head's K channels (:func:`unit_factor`) and `q` times `unit_scale`:
    the kernels do it to the tile they have loaded, so the normalised
    arrays never exist in HBM."""
    batch, length, heads = beta.shape
    by_head = lambda a: a.reshape(batch, length, heads, -1)
    side_by_side = lambda a: a.reshape(batch, length, -1)
    given = side_by_side if q.ndim == 3 else by_head
    kdim, vdim = (a.size // beta.size for a in (q, v))
    chunk = min(chunk, length)
    if length % chunk:
        raise ValueError(f"sequence length {length} is no multiple of the "
                         f"chunk {chunk}")
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    state = (jnp.zeros((batch, heads, kdim, vdim), jnp.float32)
             if initial_state is None else initial_state.astype(jnp.float32))
    fused = chunk == DEFAULT_CHUNK and kdim == LANES and vdim == LANES
    # trace time: which form each program that holds a scan got
    telemetry.registry().counter(
        "faa_kda_scan_traces_total", "KDA recurrences traced into a program, "
        "by the form that computes them",
        form="fused" if fused else "chunked_xla").inc()
    if fused:
        out, state = _fused_kda(*map(side_by_side, (q, k, v, g)), beta, state,
                                _float32_products(), not _on_tpu(), unit_scale)
    else:
        q, k, v, g = map(by_head, (q, k, v, g))
        if unit_scale is not None:
            q, k = q * unit_factor(q, unit_scale), k * unit_factor(k)
        out, state = _chunk_kda_xla(q, k, v, g, beta, state, chunk)
    return given(out), state


# ------------------------------------------------------- the fused kernels
#
# Both kernels take ``q, k, v, g`` and give ``o`` and the gradients as ``[B,
# T, H * K]``: what a projection of width ``H * K`` writes, a tile of it
# eight tokens of one head's 128 lanes.  A grid step's block is one chunk
# of every head, ``[C, H * K]``; token ``t`` of it is sublane ``t``, head
# ``h`` the lane-aligned window ``[:, h * 128:(h + 1) * 128]`` (a dense
# ``[64, 128]`` load at a lane offset the loop over groups computes), and a
# sum over tokens runs along the sublanes (:func:`_decay_factors`).

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def by_tile(x, heads: int):
    """``[B, T, H * K]`` cut as the chip tiles it, ``[B, T/8, 8, H, K]``: a
    tile is eight tokens of one head's lanes.  Cut so, XLA reads and
    writes a head's lanes where the array lies; cut to ``[B, T, H, K]`` it
    first moves the whole array to tiles of ``(H, K)``, and back after."""
    batch, length, width = x.shape
    rows = math.gcd(length, SUBLANES)
    return x.reshape(batch, length // rows, rows, heads, width // heads)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _float32_products() -> bool:
    """Whether the kernels' products are float32 throughout: what the
    ambient precision asks of an ``einsum`` on this backend."""
    ambient = jax.config.jax_default_matmul_precision
    return not _on_tpu() or ambient not in (None, "default", "fastest", "bfloat16")


def _operand(x, exact: bool):
    """`x` as a product takes it: rounded to bfloat16 unless `exact`
    (XLA's default for a float32 product on the chip, which Mosaic does
    not apply by itself)."""
    return x if exact else x.astype(jnp.bfloat16)


def _dot(a, b, dims, exact: bool):
    """``a . b`` contracted as `dims`, summed in float32."""
    return jax.lax.dot_general(
        _operand(a, exact), _operand(b, exact), dims,
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def unit_factor(x, scale: float = 1.0):
    """``scale / sqrt(sum x^2 + 1e-6)`` over the last axis, kept: `x`
    times it has rows of length `scale`."""
    return jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6) * scale


def _unit_cotangent(y, factor, scale: float, d_y):
    """The cotangent of `x` where ``y = x * factor`` and ``factor =
    unit_factor(x, scale)``."""
    unit = y * (1.0 / scale)
    return factor * (d_y - unit * jnp.sum(unit * d_y, -1, keepdims=True))


def _decay_factors(g, f_ref, chunk: int):
    """Every decay a group's products need.

    `g`: the group's chunks stacked, ``[R, K]``, so a token is one
    sublane and a running sum over tokens runs along the sublanes: a
    segmented scan by log steps, a level a step.  Level ``l`` (blocks of
    ``b = 2^l`` tokens) gets ``f_ref[l]``: for a token of an odd block
    ``exp`` of the sum of ``g`` from its block's start through itself,
    for one of an even block ``exp`` of the sum after it to its block's
    end — what takes either to the row between the two blocks.  The two
    sums of the whole chunk are ``f_ref[L] = exp G`` and ``f_ref[L + 1] =
    exp(total - G)``.  Each is summed from `g` itself, never as a
    difference of long sums: every token holds both sums of its block
    (`through` itself and `after` itself: together the whole block's),
    and going up a level an odd block's tokens take the whole of the even
    block in front from the token ``b`` sublanes before them, the even
    block's the whole of the odd block behind from the one ``b`` after
    (``pltpu.roll``; a stacked head's rows are never reached: the masks
    are the token's bits)."""
    rows = g.shape[0]
    levels = chunk.bit_length() - 1
    token = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    through, after = g, jnp.zeros_like(g)
    for level in range(levels):
        size = 1 << level
        odd = (token & size) != 0
        f_ref[level] = jnp.exp(jnp.where(odd, through, after))
        block = through + after
        through = through + jnp.where(odd, pltpu.roll(block, size, 0), 0.0)
        after = after + jnp.where(odd, 0.0, pltpu.roll(block, rows - size, 0))
    f_ref[levels] = jnp.exp(through)
    f_ref[levels + 1] = jnp.exp(after)


def _sum_from_each_token(x, chunk: int):
    """``[R, K]`` -> the sum over a chunk's tokens from each token
    onwards, along the sublanes by log steps."""
    rows = x.shape[0]
    token = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) & (chunk - 1)
    for level in range(chunk.bit_length() - 1):
        size = 1 << level
        x = x + jnp.where(token + size < chunk, pltpu.roll(x, rows - size, 0), 0.0)
    return x


def _column(rows, index):
    """Column `index` of `rows` ``[C, n]`` as ``[C, 1]``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    return jnp.sum(jnp.where(lane == index, rows, 0.0), -1, keepdims=True)


class _Group:
    """The chunks of a group of heads inside a kernel, stacked: ``R = n *
    C`` rows, a head's tokens together.  One product then serves the
    group (the MXU is 128 rows wide and a chunk has 64), and what it
    computes between two heads is masked away with what lies above the
    diagonal.  Holds the group's inputs and what is local to its chunks:
    both Gram matrices and the system's inverse, block-diagonal by head."""

    def __init__(self, index, heads: int, chunk: int, q_ref, k_ref, g_ref, beta_ref,
                 f_ref, exact: bool, unit_scale: float | None):
        count = _group_size(heads)
        self.chunk, self.exact, self.unit_scale = chunk, exact, unit_scale
        self.heads = [index * count + j for j in range(count)]
        self._lanes = [pl.ds(pl.multiple_of(h * LANES, LANES), LANES)
                       for h in self.heads]
        self._f_ref = f_ref
        self.q, self.k = self.load(q_ref), self.load(k_ref)
        if unit_scale is not None:
            self._q_factor = unit_factor(self.q, unit_scale)
            self._k_factor = unit_factor(self.k)
            self.q, self.k = self.q * self._q_factor, self.k * self._k_factor
        _decay_factors(self.load(g_ref), f_ref, chunk)
        self.beta = jnp.concatenate([_column(beta_ref[0], h) for h in self.heads], 0)
        self.levels = chunk.bit_length() - 1
        self.size = size = count * chunk
        row = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
        self.diagonal = row == col
        # a later and an earlier token of one head: their indices differ
        # below the chunk's bit alone, and the highest bit in which they
        # differ names the level at which their blocks are siblings (0
        # elsewhere, so no level's mask holds there)
        self.later = (row > col) & ((row ^ col) < chunk)
        self._apart = jnp.where(self.later, row ^ col, 0)
        self.eg, self.ed = self.factor(self.levels), self.factor(self.levels + 1)

        q_k = jnp.where(self.diagonal, _dot(self.q, self.k, _NT, exact), 0.0)
        k_k = jnp.zeros_like(q_k)
        for level in range(self.levels):
            both, k_f = self.level_operands(level)
            product = _dot(both, k_f, _NT, exact)            # [2R, R]
            pair = self.pairs(level)
            q_k = jnp.where(pair, product[:size], q_k)
            k_k = jnp.where(pair, product[size:], k_k)
        self.q_k, self.k_k = q_k, k_k
        system = self.beta * k_k
        inverse = (jnp.where(self.diagonal, 1.0, 0.0)
                   - jnp.where(self.pairs(0), system, 0.0))
        for level in range(1, self.levels):
            below = jnp.where(self.pairs(level), system, 0.0)
            inverse = inverse - _dot(_dot(inverse, below, _NN, exact),
                                     inverse, _NN, exact)
        self.inverse = inverse

    def load(self, ref):
        """The group's heads of a ``[1, C, H * D]`` block, stacked:
        ``[R, D]``."""
        return jnp.concatenate([ref[0, :, lanes] for lanes in self._lanes], 0)

    def store(self, ref, value):
        for lanes, part in zip(self._lanes, self.by_head(value)):
            ref[0, :, lanes] = part

    def store_gradients(self, dq_ref, dk_ref, d_q, d_k):
        """`d_q`, `d_k`: of `self.q`, `self.k`; stored: of what was loaded."""
        if self.unit_scale is not None:
            d_q = _unit_cotangent(self.q, self._q_factor, self.unit_scale, d_q)
            d_k = _unit_cotangent(self.k, self._k_factor, 1.0, d_k)
        self.store(dq_ref, d_q)
        self.store(dk_ref, d_k)

    def by_head(self, stacked):
        """``[R, D]`` -> a ``[C, D]`` a head."""
        return [stacked[j * self.chunk:(j + 1) * self.chunk]
                for j in range(len(self.heads))]

    def factor(self, level):
        return self._f_ref[level]

    def pairs(self, level):
        """``[R, R]``: the token pairs that meet at `level`."""
        return (self._apart >> level) == 1

    def level_operands(self, level):
        """``([q * f; k * f] [2R, K], k * f [R, K])`` of `level`, as the
        products take them."""
        f = self.factor(level)
        k_f = _operand(self.k * f, self.exact)
        return jnp.concatenate([_operand(self.q * f, self.exact), k_f], 0), k_f

    def through_state(self, states, *stacked):
        """``x S_0`` (``[R, V]``) for each `x` ``[R, K]`` of `stacked`,
        every head with its own state ``[V, K]``: a head's rows of all of
        them in one product."""
        reads = [_dot(jnp.concatenate(parts, 0), state, _NT, self.exact)
                 for state, *parts in zip(states, *map(self.by_head, stacked))]
        return [jnp.concatenate([r[i * self.chunk:(i + 1) * self.chunk] for r in reads], 0)
                for i in range(len(stacked))]

    def total_decay(self, j):
        """``exp`` of head `j`'s whole chunk's decay, ``[1, K]``."""
        last = (j + 1) * self.chunk - 1
        return self.eg[last:last + 1]


def _group_size(heads: int) -> int:
    return GROUP if heads % GROUP == 0 else 1


def _for_each_group(heads: int, group):
    """``group(i)`` for every group of heads, in a loop."""
    def body(i, carry):
        group(i)
        return carry

    jax.lax.fori_loop(0, heads // _group_size(heads), body, 0)


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref,
                    o_ref, s_ref, *rest, exact: bool, unit_scale: float | None):
    """One chunk of every head.  `s_ref` ``[1, H, V, K]`` is the state,
    transposed so that a channel's decay scales a column; it stays in
    VMEM along the chunk axis.  `rest`: the kept start states' block
    (where a backward pass will want them), then the scratch of a
    group's factors."""
    *kept_ref, f_ref = rest
    heads, chunk = s_ref.shape[1], q_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    for ref in kept_ref:
        ref[0, :, 0] = s_ref[0]

    def group(i):
        part = _Group(i, heads, chunk, q_ref, k_ref, g_ref, beta_ref, f_ref, exact,
                      unit_scale)
        states = [s_ref[0, h] for h in part.heads]           # [V, K]
        k_s, q_s = part.through_state(states, part.k * part.eg, part.q * part.eg)
        u = _dot(part.inverse, part.beta * (part.load(v_ref) - k_s), _NN, exact)
        part.store(o_ref, q_s + _dot(part.q_k, u, _NN, exact))
        for j, (h, state, u_h, k_d) in enumerate(zip(
                part.heads, states, part.by_head(u), part.by_head(part.k * part.ed))):
            s_ref[0, h] = part.total_decay(j) * state + _dot(u_h, k_d, _TN, exact)

    _for_each_group(heads, group)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, kept_ref, do_ref, ds1_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_ref,
                     f_ref, *, exact: bool, unit_scale: float | None):
    """The chunk the forward kernel's grid step took, in reverse order;
    `ds_ref` ``[1, H, V, K]`` carries the state's cotangent."""
    heads, chunk = ds_ref.shape[1], q_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = ds1_ref[...]

    def group(i):
        part = _Group(i, heads, chunk, q_ref, k_ref, g_ref, beta_ref, f_ref, exact,
                      unit_scale)
        q, k, beta, size = part.q, part.k, part.beta, part.size
        d_o = part.load(do_ref)
        states = [kept_ref[0, h, 0] for h in part.heads]     # [V, K]
        d_states = [ds_ref[0, h] for h in part.heads]
        k_g, q_g, k_d = k * part.eg, q * part.eg, k * part.ed
        k_s, = part.through_state(states, k_g)
        corrected = part.load(v_ref) - k_s
        u = _dot(part.inverse, beta * corrected, _NN, exact)

        d_u = _dot(part.q_k, d_o, _TN, exact) + jnp.concatenate(
            [_dot(k_d_h, d_state, _NT, exact)
             for k_d_h, d_state in zip(part.by_head(k_d), d_states)], 0)
        d_qk = jnp.where(part.later | part.diagonal, _dot(d_o, u, _NT, exact), 0.0)
        d_r = _dot(part.inverse, d_u, _TN, exact)
        d_system = -jnp.where(part.later, _dot(d_r, u, _NT, exact), 0.0)
        d_beta = (jnp.sum(d_system * part.k_k, -1, keepdims=True)
                  + jnp.sum(d_r * corrected, -1, keepdims=True))
        d_v = beta * d_r

        # what touches a head's own state, head by head
        d_qg, d_kg, d_kd, d_total = [], [], [], []
        lane = jax.lax.broadcasted_iota(jnp.int32, dbeta_ref.shape[1:], 1)
        for j, (h, state, d_state) in enumerate(zip(part.heads, states, d_states)):
            cut = lambda a: part.by_head(a)[j]
            both = _operand(jnp.concatenate([cut(d_o), -cut(d_v)], 0), exact)  # [2C, V]
            from_state = _dot(both, state, _NN, exact)       # [2C, K]
            d_qg.append(from_state[:chunk])
            d_kg.append(from_state[chunk:])
            d_kd.append(_dot(cut(u), d_state, _NN, exact))
            ds_ref[0, h] = part.total_decay(j) * d_state + _dot(
                both, jnp.concatenate([cut(q_g), cut(k_g)], 0), _TN, exact)
            # what every token of the chunk owes through the total
            d_total.append(jnp.broadcast_to(
                part.total_decay(j) * jnp.sum(state * d_state, 0, keepdims=True)
                + jnp.sum(cut(k_d) * d_kd[-1], 0, keepdims=True), cut(k_d).shape))
            dbeta_ref[0] = jnp.where(lane == h, cut(d_beta), dbeta_ref[0])
        d_qg, d_kg, d_kd, d_total = (jnp.concatenate(a, 0)
                                     for a in (d_qg, d_kg, d_kd, d_total))

        # the two Grams: as the left operand (q or k of the later token)
        # and as the right one (k of the earlier token)
        d_pairs = jnp.concatenate([d_qk, beta * d_system], 0)  # [2R, R]
        on_diagonal = jnp.sum(jnp.where(part.diagonal, d_qk, 0.0), -1, keepdims=True)
        dq_left, dk_right = on_diagonal * k, on_diagonal * q
        dk_left = jnp.zeros_like(k)
        for level in range(part.levels):
            both_f, k_f = part.level_operands(level)
            pair = part.pairs(level)
            met = _operand(jnp.where(jnp.concatenate([pair, pair], 0), d_pairs, 0.0),
                           exact)
            f = part.factor(level)
            left = _dot(met, k_f, _NN, exact)                # [2R, K]
            dq_left += f * left[:size]
            dk_left += f * left[size:]
            dk_right += f * _dot(met, both_f, _TN, exact)
        part.store_gradients(dq_ref, dk_ref, d_qg * part.eg + dq_left,
                             d_kg * part.eg + d_kd * part.ed + dk_left + dk_right)
        part.store(dv_ref, d_v)
        # G_t sums g over the tokens up to t: dg is dG's sum from t onwards
        d_sum = (q_g * d_qg + k_g * d_kg - k_d * d_kd
                 + q * dq_left + k * (dk_left - dk_right))
        part.store(dg_ref, d_total + _sum_from_each_token(d_sum, chunk))

    _for_each_group(heads, group)


class _Blocks:
    """What both kernels' ``pallas_call``s share: the grid (batch, then
    the chunks in order), and a grid step's blocks; `at` maps the step
    along the chunk axis to the chunk it takes."""

    def __init__(self, beta, at, interpret: bool):
        self.batch, length, self.heads = beta.shape
        self.count = length // DEFAULT_CHUNK
        self.at = at
        levels = DEFAULT_CHUNK.bit_length() - 1
        #: a group's decay factors of every level and of the whole chunk
        self.factors = pltpu.VMEM(
            (levels + 2, _group_size(self.heads) * DEFAULT_CHUNK, LANES), jnp.float32)
        self.options = dict(
            grid=(self.batch, self.count), interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=100 * 1024 * 1024))

    @property
    def tokens(self):           # of [B, T, H * 128]
        return pl.BlockSpec((1, DEFAULT_CHUNK, self.heads * LANES),
                            lambda b, n: (b, self.at(n), 0))

    @property
    def gates(self):            # of beta [B, T, H]
        return pl.BlockSpec((1, DEFAULT_CHUNK, self.heads),
                            lambda b, n: (b, self.at(n), 0))

    @property
    def kept(self):             # of [B, H, N, V, K]
        return pl.BlockSpec((1, self.heads, 1, LANES, LANES),
                            lambda b, n: (b, 0, self.at(n), 0, 0))

    @property
    def state(self):            # of [B, H, V, K]
        return pl.BlockSpec((1, self.heads, LANES, LANES), lambda b, n: (b, 0, 0, 0))

    def state_shape(self, *chunks):
        return jax.ShapeDtypeStruct(
            (self.batch, self.heads, *chunks, LANES, LANES), jnp.float32)


# Both are jitted by themselves: a model's layers call them at the same
# shapes, and a ``jit`` inside a trace is traced once and lowered once a
# program, where a bare ``pallas_call`` is traced and lowered to Mosaic
# again at every call (6 s more in front of every run of the five-layer
# cell, warm: twelve calls a step).


@functools.partial(jax.jit, static_argnames=("exact", "interpret", "unit_scale", "keep"))
def _forward(q, k, v, g, beta, state, exact: bool, interpret: bool,
             unit_scale: float | None, keep: bool):
    """``(o, final state [B, H, V, K])`` and, with `keep`, every chunk's
    start state ``[B, H, N, V, K]``."""
    blocks = _Blocks(beta, lambda n: n, interpret)
    out_shape = [jax.ShapeDtypeStruct(v.shape, jnp.float32), blocks.state_shape()]
    out_specs = [blocks.tokens, blocks.state]
    if keep:
        out_shape.append(blocks.state_shape(blocks.count))
        out_specs.append(blocks.kept)
    return pl.pallas_call(
        functools.partial(_forward_kernel, exact=exact, unit_scale=unit_scale),
        out_shape=out_shape,
        in_specs=[blocks.tokens] * 4 + [blocks.gates, blocks.state],
        out_specs=out_specs, scratch_shapes=[blocks.factors],
        name="kda_forward", **blocks.options,
    )(q, k, v, g, beta, jnp.swapaxes(state, -1, -2))


@functools.partial(jax.jit, static_argnames=("exact", "interpret", "unit_scale"))
def _backward(q, k, v, g, beta, kept_states, d_out, d_state, exact: bool,
              interpret: bool, unit_scale: float | None):
    count = q.shape[1] // DEFAULT_CHUNK
    blocks = _Blocks(beta, lambda n: count - 1 - n, interpret)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32)
    *gradients, d_state = pl.pallas_call(
        functools.partial(_backward_kernel, exact=exact, unit_scale=unit_scale),
        out_shape=[*map(like, (q, k, v, g, beta)), blocks.state_shape()],
        in_specs=[blocks.tokens] * 4 + [blocks.gates, blocks.kept, blocks.tokens,
                                        blocks.state],
        out_specs=[blocks.tokens] * 4 + [blocks.gates, blocks.state],
        scratch_shapes=[blocks.factors],
        name="kda_backward", **blocks.options,
    )(q, k, v, g, beta, kept_states, d_out, jnp.swapaxes(d_state, -1, -2))
    return (*gradients, jnp.swapaxes(d_state, -1, -2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _fused_kda(q, k, v, g, beta, state, exact: bool, interpret: bool,
               unit_scale: float | None):
    """The recurrence through the kernels; all float32, `state` given,
    `q, k, v, g` and the output ``[B, T, H * 128]``.
    `exact`: float32 products; `interpret`: no TPU to compile them for."""
    out, final = _forward(q, k, v, g, beta, state, exact, interpret, unit_scale,
                          keep=False)
    return out, jnp.swapaxes(final, -1, -2)


def _fused_kda_fwd(q, k, v, g, beta, state, exact: bool, interpret: bool,
                   unit_scale: float | None):
    out, final, kept = _forward(q, k, v, g, beta, state, exact, interpret, unit_scale,
                                keep=True)
    return (out, jnp.swapaxes(final, -1, -2)), (q, k, v, g, beta, kept)


def _fused_kda_bwd(exact: bool, interpret: bool, unit_scale: float | None, residuals,
                   cotangents):
    return _backward(*residuals, *cotangents, exact, interpret, unit_scale)


_fused_kda.defvjp(_fused_kda_fwd, _fused_kda_bwd)


# ------------------------------------------------- the form in jnp and XLA

def _decayed_gram(x, k, decay, *, strict: bool):
    """``M[t, s] = sum_c x[t, c] k[s, c] exp(decay[t, c] - decay[s, c])``
    for ``s < t`` (`strict`) or ``s <= t``, zero above; `x`, `k`, `decay`:
    ``[..., C, K]`` with `decay` non-increasing along C.  A chunk is cut
    into sub-blocks of 16: a pair of tokens in different sub-blocks meets
    through the row between them, a pair inside one is summed channel by
    channel with its own exponent.  No exponent taken is positive."""
    size = x.shape[-2]
    sub = SUB_BLOCK if size % SUB_BLOCK == 0 else size
    count = size // sub

    def cut(a):
        return a.reshape(a.shape[:-2] + (count, sub, a.shape[-1]))

    xs, ks, ds = cut(x), cut(k), cut(decay)
    # inside a sub-block: every pair with its own exponent
    diff = ds[..., :, None, :] - ds[..., None, :, :]      # [.., n, t, s, K]
    lower = jnp.tril(jnp.ones((sub, sub), bool), -1 if strict else 0)[..., None]
    weight = jnp.exp(jnp.where(lower, diff, 0.0)) * lower
    diagonal = jnp.sum(xs[..., :, None, :] * ks[..., None, :, :] * weight, -1)
    rows = []
    for i in range(count):
        parts = []
        if i:
            # an earlier sub-block's tokens, through the row between them
            ref = decay[..., i * sub - 1, :][..., None, :]
            x_i = xs[..., i, :, :] * jnp.exp(ds[..., i, :, :] - ref)
            k_before = k[..., :i * sub, :] * jnp.exp(ref - decay[..., :i * sub, :])
            parts.append(jnp.einsum("...tc,...sc->...ts", x_i, k_before))
        parts.append(diagonal[..., i, :, :])
        after = size - (i + 1) * sub
        if after:
            parts.append(jnp.zeros(x.shape[:-2] + (sub, after), x.dtype))
        rows.append(jnp.concatenate(parts, -1))
    return jnp.concatenate(rows, -2)


def _chunk_kda_xla(q, k, v, g, beta, state, chunk: int):
    """:func:`chunk_kda` for the shapes the kernels do not take (all
    float32, `state` given, `chunk` dividing T)."""
    batch, length, heads, kdim = q.shape
    vdim = v.shape[-1]
    count = length // chunk

    def cut(a):  # [B, T, H, D] -> [B, H, N, C, D]
        return a.reshape(batch, count, chunk, heads, -1).transpose(0, 3, 1, 2, 4)

    q, k, v, g, beta = cut(q), cut(k), cut(v), cut(g), cut(beta[..., None])
    decay = jnp.cumsum(g, axis=-2)
    total = decay[..., -1:, :]                              # [B, H, N, 1, K]
    system = jnp.eye(chunk) + beta * _decayed_gram(k, k, decay, strict=True)
    solved = jax.scipy.linalg.solve_triangular(
        system, jnp.concatenate([beta * k * jnp.exp(decay), beta * v], -1),
        lower=True, unit_diagonal=True)
    w, u = solved[..., :kdim], solved[..., kdim:]
    q_k = _decayed_gram(q, k, decay, strict=False)
    q_in = q * jnp.exp(decay)
    k_out = k * jnp.exp(total - decay)

    def step(state, xs):
        w_n, u_n, q_in_n, q_k_n, k_out_n, total_n = xs
        u_n = u_n - jnp.einsum("bhck,bhkv->bhcv", w_n, state)
        out = (jnp.einsum("bhck,bhkv->bhcv", q_in_n, state)
               + jnp.einsum("bhcs,bhsv->bhcv", q_k_n, u_n))
        state = (state * jnp.exp(total_n)[..., None]
                 + jnp.einsum("bhck,bhcv->bhkv", k_out_n, u_n))
        return state, out

    xs = tuple(jnp.moveaxis(a, 2, 0)
               for a in (w, u, q_in, q_k, k_out, total[..., 0, :]))
    state, out = jax.lax.scan(step, state, xs)              # [N, B, H, C, V]
    out = out.transpose(1, 0, 3, 2, 4).reshape(batch, length, heads, vdim)
    return out, state
