"""Causal softmax attention computed a block of queries at a time.

Whole, the scores of 32 heads over 8,192 tokens are 8.6 GB in float32.
:func:`blocked_causal_attention` walks the queries in blocks, one whole
softmax a block, inside a ``lax.scan`` — a loop, so that one block's
scores are live at a time in the forward pass and in the backward pass
alike (as independent blocks the compiler scheduled all of them at once:
9.3 GB of temporaries in the step of Kimi Linear's cut, and an
``optimization_barrier`` chain ordered the forward pass alone;
compile-time analysis for the v5e, PR 35).  A block's body is wrapped in
``jax.checkpoint``: the backward pass computes its scores again from
``q``, ``k`` and ``v`` rather than keeping every block's probabilities.

A loop has one shape for all its steps, so a block cannot meet just the
keys before it.  The sequence is therefore cut into `spans` static
spans, each with a loop of its own over keys that end where the span
ends: with four spans 62.5% of the full square is computed (the causal
half is 50%, whole blocks of 512 would need 53%).

The score of latent attention (DeepSeek-V2's MLA, as Kimi Linear runs it
without rotary) has two parts: a head's own ``q_nope . k_nope`` and
``q_pe . k_pe`` against a key part that all heads share, so `k_shared`
is taken apart and never copied to every head.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["blocked_causal_attention", "DEFAULT_QUERY_BLOCK", "DEFAULT_SPANS"]

DEFAULT_QUERY_BLOCK = 512
DEFAULT_SPANS = 4


def _attend(q, q_shared, k, k_shared, v, first, scale: float):
    """One block of queries, whose first token is token `first`, against
    the keys ``[0, k.shape[1])``."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if q_shared is not None:
        scores = scores + jnp.einsum("bqhd,bkd->bhqk", q_shared, k_shared)
    rows = first + jnp.arange(q.shape[1])[:, None]
    seen = rows >= jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(seen, scores.astype(jnp.float32) * scale, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def blocked_causal_attention(q, k, v, *, scale: float, q_shared=None,
                             k_shared=None, block: int = DEFAULT_QUERY_BLOCK,
                             spans: int = DEFAULT_SPANS):
    """``softmax(causal(q k^T + q_shared k_shared^T) * scale) v``.

    `q`, `k`: ``[B, T, H, D]``; `v`: ``[B, T, H, Dv]``; `q_shared`
    ``[B, T, H, Ds]`` with `k_shared` ``[B, T, Ds]`` (one key part for all
    heads), or neither.  Returns ``[B, T, H, Dv]``."""
    batch, length = q.shape[:2]
    block = min(block, length)
    if length % block:
        raise ValueError(f"sequence length {length} is no multiple of the "
                         f"query block {block}")
    blocks = length // block
    while blocks % spans:  # a short sequence has fewer blocks than spans
        spans -= 1
    per_span = blocks // spans
    attend = jax.checkpoint(_attend, static_argnums=(6,))

    def in_blocks(a, start, stop):  # [B, T, ...] -> [n, B, block, ...]
        a = a[:, start:stop]
        return jnp.moveaxis(a.reshape((batch, per_span, block) + a.shape[2:]), 1, 0)

    out = []
    for span in range(spans):
        start, stop = span * per_span * block, (span + 1) * per_span * block
        keys = (k[:, :stop], None if k_shared is None else k_shared[:, :stop],
                v[:, :stop])

        def step(first, xs, keys=keys):
            q_block, q_shared_block = xs
            return first + block, attend(q_block, q_shared_block, keys[0],
                                         keys[1], keys[2], first, scale)

        xs = (in_blocks(q, start, stop),
              None if q_shared is None else in_blocks(q_shared, start, stop))
        _, done = jax.lax.scan(step, jnp.int32(start), xs)  # [n, B, block, H, Dv]
        out.append(jnp.moveaxis(done, 0, 1).reshape(
            (batch, per_span * block) + done.shape[3:]))
    return jnp.concatenate(out, axis=1)
