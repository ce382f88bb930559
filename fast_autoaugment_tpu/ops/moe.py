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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["route", "held_experts", "assignment_counts", "balance_bias", "FORMS"]

#: rows of one block of an expert's tokens (:func:`held_experts`)
BLOCK_ROWS = 512


def route(x, router, bias, *, top_k: int, scale: float, renormalize: bool = True):
    """``(chosen [N, top_k] int32, weights [N, top_k] float32)`` for the
    tokens `x` ``[N, D]``: sigmoid scores over all ``router.shape[1]``
    experts, the `top_k` largest of ``score + bias`` (`bias` moves the
    choice and never the weight), weights renormalised and scaled."""
    scores = jax.nn.sigmoid(jnp.dot(x, router).astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped(x, weight_of, matrices, token_of, counts, rows: int, form: str):
    """The sum over the sorted assignments, a block of `rows` at a time;
    `matrices`: the held experts' weights, as ``FORMS[form]`` names them."""
    _, products = FORMS[form]

    def block(index, total):
        expert, _, _, token, taken, weight = _block_inputs(
            index, x, weight_of, token_of, counts, rows)
        out = products(taken, weight, *(w[expert] for w in matrices))
        return total.at[token].add(out, mode="drop", indices_are_sorted=True,
                                   unique_indices=True)

    blocks = jnp.sum((counts + rows - 1) // rows)
    return jax.lax.fori_loop(0, blocks, block, jnp.zeros_like(x))


def _grouped_fwd(x, weight_of, matrices, token_of, counts, rows, form):
    return (_grouped(x, weight_of, matrices, token_of, counts, rows, form),
            (x, weight_of, matrices, token_of, counts))


def _grouped_bwd(rows, form, kept, d_total):
    x, weight_of, matrices, token_of, counts = kept
    _, products = FORMS[form]

    def block(index, grads):
        d_x, d_weight_of, d_matrices = grads
        expert, start, mine, token, taken, weight = _block_inputs(
            index, x, weight_of, token_of, counts, rows)
        _, vjp = jax.vjp(products, taken, weight,
                         *(w[expert] for w in matrices))
        d_taken, d_weight, *d_expert = vjp(jnp.take(
            d_total, token, axis=0, mode="fill", fill_value=0,
            indices_are_sorted=True, unique_indices=True))
        d_x = d_x.at[token].add(d_taken, mode="drop", indices_are_sorted=True,
                                unique_indices=True)
        # the block's window may reach into the next expert's assignments
        d_weight = jnp.where(mine, d_weight, 0) + jax.lax.dynamic_slice(
            d_weight_of, (start,), (rows,))
        d_weight_of = jax.lax.dynamic_update_slice(d_weight_of, d_weight, (start,))
        return (d_x, d_weight_of, tuple(
            d_w.at[expert].add(d_e) for d_w, d_e in zip(d_matrices, d_expert)))

    blocks = jnp.sum((counts + rows - 1) // rows)
    grads = jax.lax.fori_loop(0, blocks, block, (
        jnp.zeros_like(x), jnp.zeros_like(weight_of),
        tuple(jnp.zeros_like(w) for w in matrices)))
    return (*grads, None, None)


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
    to its tokens.  A block's ``[rows, F]`` activations are computed
    again in the backward pass and kept for none."""
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
