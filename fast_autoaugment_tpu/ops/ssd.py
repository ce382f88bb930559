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
its masked, decayed product with ``x`` a head, and two products with the
state a head.  All chunks' local parts are computed at once (batched
products over the chunk axis); only the states' pass from chunk to chunk
is a loop, ``T / chunk`` steps of one multiply-add each.

**Every exponent taken is a sum of ``Δ A <= 0`` over a span of one
chunk**: ``G_t - G_s`` with ``s <= t`` (the difference of two running
sums *inside the chunk*, taken before the exponential, masked to ``-inf``
above the diagonal before it too), ``G_t`` and ``G_last - G_s``.  No
exponential of a running sum is ever divided by another, so nothing
overflows however fast a head forgets, and no step is clamped.

Plain ``jax.numpy``: the backward pass is JAX's own, through the batched
products and the loop over chunk states, and equals the recurrence's
gradient (tests).  The products take the ambient matmul precision, as an
``einsum`` does (``ops/kda.py``'s rule): bfloat16 operands and float32
sums on the chip by default, float32 throughout under
``jax.default_matmul_precision("highest")``; decays, running sums and the
state are float32.

``faa_ssd_scan_traces_total{form}`` counts, at trace time, which form a
program got: ``chunked_xla`` (:func:`chunk_ssd`) or ``recurrent``
(:func:`recurrent_ssd`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fast_autoaugment_tpu.core import telemetry

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
    _count_trace("chunked_xla")
    x, dt, a, b, c, d = _float32(x, dt, a, b, c, d)
    batch, length, heads, width = x.shape
    groups, size = b.shape[2], b.shape[3]
    per_group = heads // groups
    chunk = min(chunk, length)
    if length % chunk or heads % groups:
        raise ValueError(f"a sequence of {length} tokens is no whole number of "
                         f"chunks of {chunk}, or {heads} heads no whole number "
                         f"of {groups} groups")
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
