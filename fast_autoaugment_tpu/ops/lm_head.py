"""The output head's product and the next-token cross-entropy, a block of
positions at a time.

Whole, the float32 logits of 8,192 positions over 19,360 ids are 634 MB,
and a step keeps them and their gradient; with a second head over the
same vocabulary (a multi-token-prediction module) that is 2.5 GB beside
the state.  :func:`blocked_next_token_sums` walks the positions in
blocks inside a ``lax.scan`` whose body is wrapped in ``jax.checkpoint``:
one block's ``[block, ids]`` logits are live at a time, in the forward
pass and in the backward pass alike, and the backward pass computes a
block's logits again from its rows of `x` (how the program computes, not
what: ``ops/attention.py`` treats its scores the same way).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fast_autoaugment_tpu.core import scopes

__all__ = ["blocked_next_token_sums", "DEFAULT_POSITION_BLOCK"]

DEFAULT_POSITION_BLOCK = 1024


def _block_sums(x, kernel, targets, weight):
    """``(nll [B], hits [B])`` of one block: `x` ``[B, n, D]``, `targets`
    and `weight` ``[B, n]``."""
    with jax.named_scope(scopes.LM_HEAD):
        logits = jnp.dot(x, kernel.astype(x.dtype)).astype(jnp.float32)
    with jax.named_scope(scopes.LOSS):
        picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        hit = (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)
        return (nll * weight).sum(axis=-1), (hit * weight).sum(axis=-1)


def blocked_next_token_sums(x, kernel, targets, weight=None, *,
                            block: int = DEFAULT_POSITION_BLOCK):
    """``(nll [B], hits [B])``: over a sequence's positions the sums of
    ``weight * (logsumexp(x W) - (x W)[target])`` and of ``weight *
    [argmax(x W) == target]``.  `x` ``[B, T, D]``, `kernel` ``[D, V]``,
    `targets` ``[B, T]`` int, `weight` ``[B, T]`` (absent: ones)."""
    batch, length = x.shape[:2]
    if weight is None:
        weight = jnp.ones((batch, length), jnp.float32)
    block = min(block, length)
    if length % block:
        raise ValueError(f"sequence length {length} is no multiple of the "
                         f"position block {block}")

    def in_blocks(a):  # [B, T, ...] -> [n, B, block, ...]
        return jnp.moveaxis(
            a.reshape((batch, length // block, block) + a.shape[2:]), 1, 0)

    sums = jax.checkpoint(_block_sums)

    def step(total, xs):
        nll, hits = sums(xs[0], kernel, xs[1], xs[2])
        return (total[0] + nll, total[1] + hits), None

    zero = jnp.zeros((batch,), jnp.float32)
    total, _ = jax.lax.scan(step, (zero, zero), (
        in_blocks(x), in_blocks(targets), in_blocks(weight.astype(jnp.float32))))
    return total
