"""A sigmoid-routed expert layer that holds a share of its experts.

The router scores every token against *all* experts (its published
width), chooses the ``top_k`` largest of ``score + bias``, and weighs the
chosen by their scores renormalised to one and scaled (DeepSeek-V3's
routing, which Kimi Linear keeps; ``num_expert_group`` 1, so the grouped
top-k is the plain one).  This chip holds the experts ``[first, first +
held)`` — in a deployment the others live on other chips — and computes
what *its* experts add for the tokens routed to them,

    y = sum over e chosen and held of  w_e * E_e(x),
    E(x) = W_down (silu(W_gate x) * W_up x)        (``form="swiglu"``)
    E(x) = W_down relu(W_up x)^2                   (``form="relu2"``).

What the absent experts would add is left out here, as it is in the
benchmark's reference; nothing stands in for the other chips or for the
exchange with them.  With every expert held the result is the whole
layer's, and the shares of a partition add up to it (tests).

**The bias is what balances the router, between steps and outside the
gradient** (the auxiliary-loss-free rule of arXiv:2408.15664, which
DeepSeek-V3 and Kimi Linear train with): after a step every expert's
bias moves by ``rate`` towards the mean load, ``b_e += rate * sign(mean
load - load_e)`` (:func:`balance_bias`).  Without it a model trained from
its initial weights sends nearly every token of a step to the same
``top_k`` experts (PERF.md section 6, PR 35).

**The held experts' part is grouped**: the assignments that fell to held
experts are sorted by expert, and an expert's tokens go through its
products a block of rows at a time (:func:`held_experts`), in a loop
whose trip count is the blocks the routing filled.  So the products done
follow the routing — a balanced router's ``tokens * top_k / experts``
rows an expert, rounded up to whole blocks, and every token through every
held expert only if the router sends them there — while every shape is
static and **no token is dropped** whatever the router does.  A loop of
a length only the device knows cannot be differentiated by JAX, so the
backward pass is written here too (``jax.custom_vjp``): the same loop,
each block's products differentiated by ``jax.vjp``.

**A block's rows reach the sum through a kernel** (:func:`_combine`, the
Mosaic kernel ``moe_combine``; interpreted where the backend is no TPU):
both loops carry their ``[tokens, hidden]`` sum (the result; ``d_x``) as
``[tokens, 1, hidden]``, in place, and a block's rows of it are copied
into VMEM all at once, added to and copied back — the float32 adds
``sum.at[token].add(rows, mode="drop")`` does, in its order, so the same
bits.  Rows of whole 128-lane tiles of 32-bit words go that way, which
every configuration's are; any other width or type keeps XLA's scatter
(:func:`_sum_of_rows`: the choice is ``x.shape`` and ``x.dtype`` alone).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fast_autoaugment_tpu.core import scopes
from fast_autoaugment_tpu.ops import kda
from fast_autoaugment_tpu.ops.kda import LANES

__all__ = ["route", "held_experts", "assignment_counts", "balance_bias", "FORMS"]

#: rows of one block of an expert's tokens (:func:`held_experts`)
BLOCK_ROWS = 512


def route(x, router, bias, *, top_k: int, scale: float, renormalize: bool = True,
          eps: float = 1e-20):
    """``(chosen [N, top_k] int32, weights [N, top_k] float32)`` for the
    tokens `x` ``[N, D]``: sigmoid scores over all ``router.shape[1]``
    experts, the `top_k` largest of ``score + bias`` (`bias` moves the
    choice and never the weight), weights renormalised — the chosen scores
    over their sum plus `eps`, a family's own (lfm2_moe's 1e-6) — and
    scaled."""
    scores = jax.nn.sigmoid(jnp.dot(x, router).astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + eps)
    return chosen.astype(jnp.int32), weights * scale


def assignment_counts(chosen, first: int, held: int):
    """Assignments each of the experts ``[first, first + held)``
    received: ``[held]`` int32."""
    local = chosen.reshape(-1) - first
    return jnp.sum(local[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)


def balance_bias(bias, load, rate: float):
    """The router's correction bias after a step in which expert ``e``
    received ``load[e]`` assignments: every bias moves by `rate` towards
    the mean load (up where the expert had fewer than the mean, down
    where more)."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def _block_of(index, counts, rows: int):
    """Where block `index` of the sorted assignments lies: ``(expert,
    first position, mine [rows] bool)`` — an expert's assignments fill
    ``ceil(count / rows)`` blocks, the last one in part."""
    blocks = (counts + rows - 1) // rows
    ends = jnp.cumsum(blocks)
    expert = jnp.sum(index >= ends).astype(jnp.int32)
    offset = (index - (ends[expert] - blocks[expert])) * rows
    start = (jnp.cumsum(counts) - counts)[expert] + offset
    return expert, start, offset + jnp.arange(rows) < counts[expert]


def _products(taken, weight, gate, up, down):
    out = jnp.dot(jax.nn.silu(jnp.dot(taken, gate)) * jnp.dot(taken, up), down)
    return out * weight[:, None].astype(out.dtype)


def _relu2_products(taken, weight, up, down):
    out = jnp.dot(jnp.square(jax.nn.relu(jnp.dot(taken, up))), down)
    return out * weight[:, None].astype(out.dtype)


#: an expert's form by name: the names of its matrices, in the order
#: :func:`held_experts` takes them, and ``(rows, weight, *the expert's
#: matrices) -> weighted rows``
FORMS = {"swiglu": (("gate", "up", "down"), _products),
         "relu2": (("up", "down"), _relu2_products)}


def _block_inputs(index, x, weight_of, token_of, counts, rows: int):
    """``(expert, start, token [rows], taken [rows, D], weight [rows])``
    of a block; rows past the expert's last assignment get weight zero
    and token indices past the last token, which a scatter drops."""
    expert, start, mine = _block_of(index, counts, rows)
    token = jnp.where(mine, jax.lax.dynamic_slice(token_of, (start,), (rows,)),
                      x.shape[0] + jnp.arange(rows))
    weight = jnp.where(mine, jax.lax.dynamic_slice(weight_of, (start,), (rows,)), 0)
    taken = jnp.take(x, token, axis=0, mode="fill", fill_value=0,
                     indices_are_sorted=True, unique_indices=True)
    return expert, start, mine, token, taken, weight


# ------------------------------------------------- a block's rows into the sum
#
# XLA's scatter updates an ``(8, 128)``-tiled ``[tokens, hidden]`` sum a row
# after another, a row being one sublane of ``hidden / 128`` tiles: 0.43 to
# 0.93 ms a block of 512 rows whose bytes owe 0.02 (PERF.md section 6,
# PR 44).  The loops carry the sum as ``[tokens, 1, hidden]`` instead, where
# a row is whole tiles and one stretch of HBM, and the kernel moves rows
# with the chip's copy engine, all of a block's in flight at once.


def _combine_kernel(token_ref, _, rows_ref, total_ref, taken, mine, landed, brought):
    """``total[token[r]] += rows[r]`` for the block's rows whose token
    lies inside `total_ref` (the second operand's own buffer, in HBM):
    their rows of the sum copied into `taken`, one add, and copied back.
    `rows_ref` ``[R, D]`` is in HBM too, tiled ``(8, 128)`` as the
    products leave it; the copy into `mine` lays it out as `taken` is."""
    tokens = total_ref.shape[0]
    bring = pltpu.make_async_copy(rows_ref, mine.at[:, 0], brought)
    bring.start()

    def each_row(act):
        def row(r, carry):
            t = token_ref[r]

            @pl.when(t < tokens)
            def _():
                act(total_ref.at[t], taken.at[r])
            return carry

        jax.lax.fori_loop(0, taken.shape[0], row, 0)

    # one semaphore counts every row's copy: they are of one size, so as
    # many waits as starts have seen them all land
    each_row(lambda kept, held: pltpu.make_async_copy(kept, held, landed).start())
    each_row(lambda kept, held: pltpu.make_async_copy(kept, held, landed).wait())
    bring.wait()
    taken[...] += mine[...]
    each_row(lambda kept, held: pltpu.make_async_copy(held, kept, landed).start())
    each_row(lambda kept, held: pltpu.make_async_copy(held, kept, landed).wait())


def _combine(total, token, rows):
    """`rows` ``[R, D]`` added into `total` ``[N, 1, D]`` at the rows
    `token` ``[R]`` names, in place.  A token past the last row adds
    nothing, and no two of a call's tokens inside `total` may be the same:
    the float32 adds of ``total.at[token, 0].add(rows, mode="drop")``, as
    one Mosaic kernel (interpreted where the backend is no TPU)."""
    block = pltpu.VMEM((rows.shape[0], 1, rows.shape[1]), rows.dtype)
    with jax.named_scope(scopes.MOE_COMBINE):
        return pl.pallas_call(
            _combine_kernel,
            out_shape=jax.ShapeDtypeStruct(total.shape, total.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[block, block, pltpu.SemaphoreType.DMA(()),
                                pltpu.SemaphoreType.DMA(())]),
            input_output_aliases={1: 0}, name="moe_combine",
            interpret=not kda._on_tpu(),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=64 * 1024 * 1024),
        )(token, total, rows)


def _scatter(total, token, rows):
    """The same into `total` ``[N, D]``, by XLA."""
    return total.at[token].add(rows, mode="drop", indices_are_sorted=True,
                               unique_indices=True)


def _sum_of_rows(x):
    """``(zero, add, whole)`` for a sum of rows like `x`'s ``[N, D]``: the
    sum as the loops carry it, ``add(total, token, rows [R, D])`` for a
    block, and ``whole(total)`` as ``[N, D]``.  Mosaic copies a row of
    32-bit words in whole tiles of 128 lanes; an `x` of any other width or
    type keeps XLA's scatter."""
    tokens, hidden = x.shape
    if x.dtype.itemsize == 4 and hidden % LANES == 0:
        return (jnp.zeros((tokens, 1, hidden), x.dtype), _combine,
                lambda total: total[:, 0])
    return jnp.zeros_like(x), _scatter, lambda total: total


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped(x, weight_of, matrices, token_of, counts, rows: int, form: str):
    """The sum over the sorted assignments, a block of `rows` at a time;
    `matrices`: the held experts' weights, as ``FORMS[form]`` names them."""
    _, products = FORMS[form]
    zero, add, whole = _sum_of_rows(x)

    def block(index, total):
        expert, _, _, token, taken, weight = _block_inputs(
            index, x, weight_of, token_of, counts, rows)
        return add(total, token,
                   products(taken, weight, *(w[expert] for w in matrices)))

    blocks = jnp.sum((counts + rows - 1) // rows)
    return whole(jax.lax.fori_loop(0, blocks, block, zero))


def _grouped_fwd(x, weight_of, matrices, token_of, counts, rows, form):
    return (_grouped(x, weight_of, matrices, token_of, counts, rows, form),
            (x, weight_of, matrices, token_of, counts))


def _grouped_bwd(rows, form, kept, d_total):
    x, weight_of, matrices, token_of, counts = kept
    _, products = FORMS[form]
    zero, add, whole = _sum_of_rows(x)

    def block(index, grads):
        d_x, d_weight_of, d_matrices = grads
        expert, start, mine, token, taken, weight = _block_inputs(
            index, x, weight_of, token_of, counts, rows)
        _, vjp = jax.vjp(products, taken, weight,
                         *(w[expert] for w in matrices))
        d_taken, d_weight, *d_expert = vjp(jnp.take(
            d_total, token, axis=0, mode="fill", fill_value=0,
            indices_are_sorted=True, unique_indices=True))
        d_x = add(d_x, token, d_taken)
        # the block's window may reach into the next expert's assignments
        d_weight = jnp.where(mine, d_weight, 0) + jax.lax.dynamic_slice(
            d_weight_of, (start,), (rows,))
        d_weight_of = jax.lax.dynamic_update_slice(d_weight_of, d_weight, (start,))
        return (d_x, d_weight_of, tuple(
            d_w.at[expert].add(d_e) for d_w, d_e in zip(d_matrices, d_expert)))

    blocks = jnp.sum((counts + rows - 1) // rows)
    d_x, *grads = jax.lax.fori_loop(0, blocks, block, (
        zero, jnp.zeros_like(weight_of),
        tuple(jnp.zeros_like(w) for w in matrices)))
    return (whole(d_x), *grads, None, None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def held_experts(x, chosen, weights, *matrices, first: int,
                 block_rows: int = BLOCK_ROWS, form: str = "swiglu"):
    """What the held experts add: `x` ``[N, D]``, `chosen` and `weights`
    from :func:`route`, `matrices` the held experts' weights as their
    `form` takes them (:data:`FORMS` — ``swiglu``: gate and up ``[held, D,
    F]``, down ``[held, F, D]``; ``relu2``: up and down), `first` the
    index of the first held expert among all.  Returns ``[N, D]``.

    The assignments are sorted by expert (those to experts not held
    last; within an expert by token).  An expert's assignments fill
    ``ceil(count / block_rows)`` blocks; a block gathers its tokens' rows,
    takes them through the expert's products and adds the weighted result
    to its tokens' rows of the sum, in place: by the kernel ``moe_combine``
    where `x` is whole tiles of 32-bit words (interpreted where the
    backend is no TPU), else by XLA's scatter, the same adds either way.
    A block's ``[rows, F]`` activations are computed again in the backward
    pass and kept for none."""
    if form not in FORMS:
        raise ValueError(f"unknown expert form {form!r} (have {sorted(FORMS)})")
    tokens, top_k = chosen.shape
    held = matrices[0].shape[0]
    rows = min(block_rows, tokens)
    local = (chosen - first).reshape(-1)
    local = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(local, stable=True)
    # a block that starts inside the assignments may read past their end
    token_of = jnp.pad((order // top_k).astype(jnp.int32), (0, rows))
    weight_of = jnp.pad(weights.reshape(-1)[order], (0, rows))
    return _grouped(x, weight_of, matrices, token_of,
                    assignment_counts(chosen, first, held), rows, form)
