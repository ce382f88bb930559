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

    (I + A) U = beta * V - (beta * K * exp G) S_0,
    A[t, s] = beta_t sum_c k_tc k_sc exp(G_tc - G_sc)   (s < t),

so everything but ``S_0`` is computed for all chunks at once, and the
scan over chunks carries the state alone: three products a step.

**The decay is applied without overflow.**  ``exp(G_t - G_s)`` is at
most 1, but written as a product ``(k_t exp G_t)(k_s exp -G_s)`` for the
MXU its second factor overflows once a channel decays by ``e^88`` inside
a chunk (sixteen tokens at the decay's initial maximum of 1.6 a token do
not; a trained gate may).  So a chunk is cut into sub-blocks of 16: a
pair of tokens in different sub-blocks meets through a reference row
between them (both exponents then non-positive), and a pair inside one
sub-block is summed channel by channel with its own exponent
(:func:`_decayed_gram`).  No gate is clamped.

The backward pass is the chunked computation differentiated as it
stands (``jax.grad`` through the products, the triangular solve and the
scan over chunks), so it has the forward pass's shape: T/C sequential
steps, one ``[K, V]`` state a chunk kept for it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["chunk_kda", "recurrent_kda", "DEFAULT_CHUNK", "SUB_BLOCK"]

DEFAULT_CHUNK = 64
SUB_BLOCK = 16


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


def _decayed_gram(x, k, decay, *, strict: bool):
    """``M[t, s] = sum_c x[t, c] k[s, c] exp(decay[t, c] - decay[s, c])``
    for ``s < t`` (`strict`) or ``s <= t``, zero above; `x`, `k`, `decay`:
    ``[..., C, K]`` with `decay` non-increasing along C.  No exponent
    taken is positive (module docstring)."""
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


def chunk_kda(q, k, v, g, beta, initial_state=None, *, chunk: int = DEFAULT_CHUNK):
    """:func:`recurrent_kda` in chunks of `chunk` tokens (T a multiple of
    it, or shorter than it): same arguments, same results."""
    batch, length, heads, kdim = q.shape
    vdim = v.shape[-1]
    chunk = min(chunk, length)
    if length % chunk:
        raise ValueError(f"sequence length {length} is no multiple of the "
                         f"chunk {chunk}")
    count = length // chunk

    def cut(a):  # [B, T, H, D] -> [B, H, N, C, D]
        return a.reshape(batch, count, chunk, heads, -1).transpose(0, 3, 1, 2, 4)

    q, k, v, g = (cut(a.astype(jnp.float32)) for a in (q, k, v, g))
    beta = cut(beta.astype(jnp.float32)[..., None])
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

    state = (jnp.zeros((batch, heads, kdim, vdim), jnp.float32)
             if initial_state is None else initial_state)

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
