"""What the token models share: the blocks of a pre-norm decoder whose
FFN may be a sigmoid-routed expert layer this chip holds a share of
(``models/kimi_linear.py``, ``models/glm4_moe_lite.py``,
``models/nemotron_h.py``, ``models/afmoe.py``, ``models/lfm2_moe.py``).

- :class:`RMSNorm`, the bias-free :func:`dense` and :func:`proj` (a mixer's: under
  ``faa_mixer_proj``), a bare :class:`Kernel`, :class:`SwiGLU`, :class:`SquaredReLU`;
- :class:`MLAMixer`, latent attention (DeepSeek-V2's MLA): the key-value
  latent always, the query whole or through a low-rank pair with a norm
  between (``q_rank``), the shared key part and every head's matching
  query part rotated by position or left as they are (``rope_theta``); at
  heads of whole lanes (``nope + pe`` and the values multiples of 128:
  GLM-4.7-Flash) on the projections' own rows from ``q_b_proj`` to
  ``o_proj`` — ``kv_b_proj``'s kernel cut by columns
  (:func:`key_value_columns`, :func:`proj_columns`), the shared part laid,
  the rotation and the rounding passes over the rows inside the core
  (``ops/mlarows.py``) — and cut into heads at any other shape;
- :class:`GQAMixer`, grouped-query attention, bare (Nemotron-H's) or with
  a norm a head on queries and keys, rotary, a key span and a gate on the
  output (afmoe's), each by an argument; at a head of 128 on the
  projections' own rows from ``q_proj`` to ``o_proj``, the norm and the
  rotation one pass over them (:class:`HeadNormRotate`, ``ops/headnorm.py``);
- :func:`causal_conv`, a depthwise causal convolution over a few taps, and
  :class:`ShortConvMixer`, lfm2_moe's mixer: such a convolution gated on
  both sides between two projections, no activation;
- :class:`ExpertLayer`, the routed experts this chip holds beside the
  shared expert where the family has one (``ops/moe.py``), both of one
  form (``ops/moe.py::FORMS``), the shared one at ``width * shared`` or at
  a width of its own;
- :class:`Sizes`, the hashable view of a model's sizes ``nn.remat`` takes;
- :func:`remat_block`, the ``nn.remat`` every family wraps its block in
  (``model.remat``; false: no ``nn.remat`` at all): a block's input is kept
  and the block computed again in the backward pass, but for the fused
  attention cores' output and log-sum-exp, which are kept too
  (``ops/attention.py`` names them), so that the forward kernel runs once
  a step;
- :data:`CUT_KEYS`, the three top-level conf keys that say what *this
  chip* holds of a deployment — absent, the whole model: ``layers_held``
  (the first n layers), ``experts_held`` with ``expert_share`` (experts
  ``[share * held, (share + 1) * held)`` of every expert layer; the
  router keeps its published width and ``top_k``), and ``ids_held`` (ids
  ``[0, n)``: embedding, head, logits and loss are over the slice).  No
  width changes with them;
- the router's rule between steps.  An expert layer ``sow``s the
  assignments each of *all* its experts received into the
  :data:`STEP_STATS` collection; :func:`balance_routers` turns that into
  every router's next correction bias (``ops/moe.py::balance_bias``,
  outside the gradient) and into the counts :func:`publish_router_counts`
  publishes.  A model's ``after_step`` / ``publish_counts`` — the names the
  step body and the trainer know a model by
  (``train/steps.py::make_token_step_body``) — call the two.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from fast_autoaugment_tpu.core import scopes, telemetry
from fast_autoaugment_tpu.ops import mlarows, moe
from fast_autoaugment_tpu.ops.attention import (
    LSE_NAME,
    OUT_NAME,
    blocked_causal_attention,
)
from fast_autoaugment_tpu.ops.headnorm import head_norm_rotate
from fast_autoaugment_tpu.ops.kda import LANES

__all__ = ["RMSNorm", "SwiGLU", "SquaredReLU", "Kernel", "MLAMixer", "GQAMixer", "HeadNormRotate",
           "ShortConvMixer", "ExpertLayer", "causal_conv",
           "FEED_FORWARDS", "Sizes", "remat_block", "dense", "proj", "proj_columns",
           "key_value_columns", "step_bias_init",
           "rotate_by_position", "expert_share_of", "refuse_unwritten_routing",
           "balance_routers", "publish_router_counts", "INIT", "STEP_STATS",
           "ROUTING", "CUT_KEYS"]

#: the collection the expert layers ``sow`` a step's loads into
STEP_STATS = "step_stats"
#: the collection they ``sow`` every token's chosen experts into
ROUTING = "routing"
#: top-level conf keys that say what this chip holds (absent: everything)
CUT_KEYS = ("layers_held", "experts_held", "ids_held")

INIT = nn.initializers.normal(0.02)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + self.eps)
        return (x32 * weight).astype(x.dtype)


def step_bias_init(low: float, high: float, floor: float):
    """An initializer for the bias under a softplus that makes a step (a
    recurrent mixer's ``dt_bias``): the inverse softplus of a step drawn
    log-uniformly in ``[low, high]`` and floored (fla's and Mamba's
    initialisation)."""

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                     * (math.log(high) - math.log(low)) + math.log(low))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


def dense(features: int, name: str, dtype, kernel_init=INIT) -> nn.Dense:
    return nn.Dense(features, use_bias=False, kernel_init=kernel_init, name=name,
                    dtype=dtype)


def rotate_by_position(x, theta: float, pairs: str = "interleaved"):
    """Rotary position embedding over the whole last axis of `x` ``[B, T,
    ..., D]``, token ``t`` at position ``t``: pair ``i`` turned by ``t *
    theta ** (-2i / D)``.  `pairs` says which two channels pair ``i`` is:
    ``interleaved``, ``(x[2i], x[2i + 1])`` (DeepSeek-V3's layout) — comes
    back with the pairs' first members in the first half and the second
    members in the second, for queries and keys alike, so their products
    are the interleaved layout's; ``halves``, ``(x[i], x[i + D/2])`` (the
    ``rotate_half`` layout), every channel back in its own place."""
    length, dim = x.shape[1], x.shape[-1]
    inverse = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inverse[None, :]
    angle = angle.reshape((1, length) + (1,) * (x.ndim - 3) + (dim // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if pairs == "interleaved":
        first, second = x[..., 0::2], x[..., 1::2]
    elif pairs == "halves":
        first, second = x[..., :dim // 2], x[..., dim // 2:]
    else:
        raise ValueError(f"pairs={pairs!r}: 'interleaved' or 'halves'")
    first, second = first.astype(jnp.float32), second.astype(jnp.float32)
    return jnp.concatenate([first * cos - second * sin,
                            first * sin + second * cos], -1).astype(x.dtype)


class MLAMixer(nn.Module):
    """Latent attention.  `q_rank` None: the query is one product
    (``q_lora_rank: null``); `rope_theta` None: no rotary.

    **Whole lanes a head: the projections' rows all the way.**  Where a
    head's key width ``nope_dim + pe_dim`` and `v_dim` are whole multiples
    of 128 lanes (GLM-4.7-Flash: 192 + 64 and 256; `pe_dim` at most 128, so
    that the rotary part lies in a head's last 128-lane piece), no array is
    cut into heads between the projections and ``o_proj``:
    ``q_b_proj`` | ``q_proj`` writes the kernels' `q` rows ``[B, T, heads *
    (nope + pe)]`` as they are; ``kv_b_proj``'s *kernel* is cut by columns
    (:func:`key_value_columns`: 18 MB, where cutting its output moves 300 MB)
    into one matrix whose product is the `v` rows and one — every head's
    `nope_dim` columns and `pe_dim` zero columns — whose product is the `k`
    rows with zeros where the shared key part belongs;
    ``ops/attention.py`` (``heads=``, ``k_shared=``, ``theta=``) then lays
    that part into every head and turns it and the queries' by position
    there, a pass over the rows each whose store (or the product's own) is
    the operands' rounding (``ops/mlarows.py``), and ``o_proj`` reads the
    kernels' output rows.  The rotary pairs are turned
    in place (a lane and its neighbour), not de-interleaved: a score is a
    sum over the lanes and `q` and `k` share the order.  Any other shape
    (Kimi Linear's 128 + 64 on values of 128, the tests' heads of 8) keeps
    ``[B, T, H, D]`` and the core's own concatenate, broadcast and pad.  The
    parameter tree is one and the same."""

    heads: int
    nope_dim: int
    pe_dim: int
    v_dim: int
    kv_rank: int
    eps: float
    dtype: Any = jnp.float32
    q_rank: int | None = None
    rope_theta: float | None = None

    @nn.compact
    def __call__(self, x):
        batch, length, hidden = x.shape
        heads, width = self.heads, self.nope_dim + self.pe_dim
        # whole lanes a head: rows as the projections write them, all the way
        rows = mlarows.admits(width, self.pe_dim) and self.v_dim % LANES == 0
        if self.q_rank is None:
            q = proj(x, heads * width, "q_proj", self.dtype)
        else:
            q = proj(RMSNorm(self.eps, name="q_a_norm")(
                proj(x, self.q_rank, "q_a_proj", self.dtype)),
                heads * width, "q_b_proj", self.dtype)
        latent = proj(x, self.kv_rank + self.pe_dim, "kv_a_proj", self.dtype)
        k_pe = latent[..., self.kv_rank:]
        normed = RMSNorm(self.eps, name="kv_a_norm")(latent[..., :self.kv_rank])
        if rows:
            kernel = Kernel((self.kv_rank, heads * (self.nope_dim + self.v_dim)),
                            name="kv_b_proj")()
            k, v = proj_columns(normed, key_value_columns(
                kernel, heads, self.nope_dim, self.pe_dim), self.dtype)
            with jax.named_scope(scopes.MLA_ATTENTION):
                out = blocked_causal_attention(q, k, v, k_shared=k_pe, scale=width ** -0.5,
                                               heads=heads, theta=self.rope_theta)
            return proj(out, hidden, "o_proj", self.dtype)
        q = q.reshape(batch, length, heads, width)
        kv = proj(normed, heads * (self.nope_dim + self.v_dim), "kv_b_proj", self.dtype
                  ).reshape(batch, length, heads, self.nope_dim + self.v_dim)
        q_nope, k_nope, v = (q[..., :self.nope_dim], kv[..., :self.nope_dim],
                             kv[..., self.nope_dim:])
        q_pe = q[..., self.nope_dim:]
        if self.rope_theta is not None:
            q_pe = rotate_by_position(q_pe, self.rope_theta)
            k_pe = rotate_by_position(k_pe, self.rope_theta)
        with jax.named_scope(scopes.MLA_ATTENTION):
            out = blocked_causal_attention(
                q_nope, k_nope, v, q_shared=q_pe, k_shared=k_pe, scale=width ** -0.5)
        return proj(out.reshape(batch, length, heads * self.v_dim), hidden,
                    "o_proj", self.dtype)


def key_value_columns(kernel, heads: int, nope: int, pe: int):
    """``kv_b_proj``'s kernel ``[rank, heads * (nope + v)]``, a head's key
    columns in front of its value columns, cut into ``(keys [rank, heads *
    (nope + pe)], values [rank, heads * v])``: the keys' matrix has `pe` zero
    columns behind every head's `nope`, so that its product is the attention
    kernels' `k` rows with room for the shared key part."""
    rank = kernel.shape[0]
    by_head = kernel.reshape(rank, heads, -1)
    keys = jnp.pad(by_head[..., :nope], ((0, 0), (0, 0), (0, pe)))
    return keys.reshape(rank, -1), by_head[..., nope:].reshape(rank, -1)


class HeadNormRotate(nn.Module):
    """A norm a head and a rotation by position on ``[B, T, heads * 128]``
    rows, one pass of ``ops/headnorm.py``'s kernels, under ``RMSNorm``'s
    parameter (``weight``, one for all heads); `eps` None: no norm, `theta`
    None: no rotation, neither: the rows as they came."""

    heads: int
    eps: float | None
    theta: float | None

    @nn.compact
    def __call__(self, x):
        if self.eps is None and self.theta is None:
            return x
        dim = x.shape[-1] // self.heads
        weight = None if self.eps is None else self.param(
            "weight", nn.initializers.ones, (dim,))
        angle = None
        if self.theta is not None:
            inverse = self.theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
            angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inverse
        return head_norm_rotate(x, self.heads, weight=weight, eps=self.eps or 0.0,
                                angle=angle).astype(x.dtype)


class GQAMixer(nn.Module):
    """Grouped-query attention: `heads` query heads of `head_dim` on
    `kv_heads` key-value heads, key-value head ``g`` serving the query
    heads ``[g * n, (g + 1) * n)``, one causal softmax at ``head_dim **
    -0.5``, no bias.  Each of the rest is left out where it is not given:
    `qk_norm_eps`, an RMS norm over every head's ``head_dim`` channels of
    the queries and of the keys (one weight each, ``q_norm`` and
    ``k_norm``); `rope_theta`, queries and keys rotated by position over
    all of ``head_dim``, pairs ``(i, i + head_dim / 2)``; `window`, the key
    span (``ops/attention.py``); `gated`, the output times ``sigmoid(gate_proj
    x)`` in front of ``o_proj``.

    Every array between a projection and ``o_proj`` stays ``[B, T, heads *
    head_dim]``, the heads side by side as the projection writes them and
    as ``ops/attention.py``'s kernels read and write them (``heads=``): the
    norm a head and the rotation are one pass over those rows
    (:class:`HeadNormRotate`: a head's sum along its 128 lanes, the
    rotation's partner a lane roll), and the key-value heads are
    handed over as they are, `kv_heads` of them — the kernels' index maps
    give a group its head, and their backward pass sums a group's
    gradient.  No array is cut into ``(heads, head_dim)`` tiles on the way
    and none is repeated in HBM.  That is for heads of one row of lanes
    (128).  A narrower head (lfm2_moe's 64, the tests' 8) is no whole
    tile's lanes, and cut as the chip tiles it XLA moves every array for
    real: those (and a wider one) keep ``[B, T, heads, head_dim]`` from the projections to
    the core, which repeats a group's key-value heads itself where its
    kernels take two heads of 64 to a block (``ops/attention.py``) and in
    front of its XLA form."""

    heads: int
    kv_heads: int
    head_dim: int
    dtype: Any = jnp.float32
    qk_norm_eps: float | None = None
    rope_theta: float | None = None
    window: int | None = None
    gated: bool = False

    @nn.compact
    def __call__(self, x):
        batch, length, hidden = x.shape
        width = self.heads * self.head_dim
        # a head of one row of lanes: rows as the projections write them, all the way
        rows = self.head_dim == LANES
        q = proj(x, width, "q_proj", self.dtype)
        k, v = (proj(x, self.kv_heads * self.head_dim, f"{name}_proj", self.dtype)
                for name in "kv")

        def by_head(a, heads):
            return a.reshape(batch, length, heads, self.head_dim)

        def a_head(a, heads, name):
            """The norm and the rotation, a head at a time where it lies."""
            if rows:
                return HeadNormRotate(heads, self.qk_norm_eps, self.rope_theta, name=name)(a)
            cut = by_head(a, heads)
            if self.qk_norm_eps is not None:
                cut = RMSNorm(self.qk_norm_eps, name=name)(cut)
            if self.rope_theta is not None:
                cut = rotate_by_position(cut, self.rope_theta, "halves")
            return cut

        q, k = a_head(q, self.heads, "q_norm"), a_head(k, self.kv_heads, "k_norm")
        v = v if rows else by_head(v, self.kv_heads)
        with jax.named_scope(scopes.GQA_ATTENTION):
            out = blocked_causal_attention(q, k, v, scale=self.head_dim ** -0.5,
                                           window=self.window,
                                           heads=self.heads if rows else None)
        out = out.astype(self.dtype).reshape(batch, length, width)
        if self.gated:
            out = out * jax.nn.sigmoid(proj(x, width, "gate_proj", self.dtype))
        return proj(out, hidden, "o_proj", self.dtype)


def causal_conv(x, kernel, bias=None):
    """Depthwise causal convolution over time: `x` ``[B, T, C]``, `kernel`
    ``[taps, C]`` (its last tap meets the token itself), `bias` ``[C]`` or
    none; zeros before the sequence."""
    taps, length = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(padded[:, i:i + length] * kernel[i].astype(x.dtype)
              for i in range(taps))
    return out if bias is None else out + bias.astype(x.dtype)


class ShortConvMixer(nn.Module):
    """lfm2_moe's mixer, a doubly gated short convolution: ``(B, C, z) =
    split3(in_proj u)`` (hidden -> 3 hidden, in that order), ``s = B * z``,
    ``c = causal_conv(s)`` over `taps` taps (depthwise, no bias, no
    activation), ``out_proj(C * c)``.  What lies between the two
    projections — both gates and the taps — is under ``faa_short_conv_gate``;
    the family puts the mixer whole under ``faa_short_conv``."""

    taps: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        telemetry.registry().counter(
            "faa_short_conv_traces_total", "short-convolution mixers traced into a "
            "program, by their taps", taps=str(self.taps)).inc()
        projected = proj(x, 3 * hidden, "in_proj", self.dtype)
        kernel = self.param("conv_kernel", nn.initializers.normal(
            1.0 / math.sqrt(self.taps)), (self.taps, hidden))
        with jax.named_scope(scopes.SHORT_CONV_GATE):
            before, after, z = jnp.split(projected, 3, axis=-1)
            gated = after * causal_conv(before * z, kernel)
        return proj(gated, hidden, "out_proj", self.dtype)


class Kernel(nn.Module):
    """A matrix under ``nn.Dense``'s name for it, handed out whole (an
    output head whose product a blocked loss takes a block at a time)."""

    shape: tuple[int, int]

    @nn.compact
    def __call__(self):
        return self.param("kernel", INIT, self.shape)


class SwiGLU(nn.Module):
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        gate = dense(self.width, "gate_proj", self.dtype)(x)
        up = dense(self.width, "up_proj", self.dtype)(x)
        return dense(hidden, "down_proj", self.dtype)(jax.nn.silu(gate) * up)


class SquaredReLU(nn.Module):
    """``W_down relu(W_up x)^2``: two matrices, no gate (``mlp_hidden_act:
    relu2``)."""

    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        up = dense(self.width, "up_proj", self.dtype)(x)
        return dense(x.shape[-1], "down_proj", self.dtype)(
            jnp.square(jax.nn.relu(up)))


#: the shared expert: the feed-forward module of each of the experts' forms
#: (``ops/moe.py::FORMS``, which names a routed expert's matrices)
FEED_FORWARDS = {"swiglu": SwiGLU, "relu2": SquaredReLU}
assert set(FEED_FORWARDS) == set(moe.FORMS)


class ExpertLayer(nn.Module):
    """The routed experts this chip holds, beside the shared expert
    (`shared` 0: the family has none).  `form`: what an expert computes,
    routed and shared alike (``ops/moe.py::FORMS``); `shared_width`: the
    shared expert's width where it has one of its own (absent: ``width *
    shared``); `renorm_eps`: what the renormalisation adds to the chosen
    scores' sum (``ops/moe.py::route``)."""

    experts: int
    held: int
    share: int
    top_k: int
    width: int
    shared: int
    scale: float
    renormalize: bool
    dtype: Any = jnp.float32
    form: str = "swiglu"
    shared_width: int | None = None
    renorm_eps: float = 1e-20

    @nn.compact
    def __call__(self, x):
        batch, length, hidden = x.shape
        flat = x.reshape(batch * length, hidden)
        first = self.share * self.held
        with jax.named_scope(scopes.MOE_ROUTER):
            router = self.param("router", INIT, (hidden, self.experts))
            # moves the choice, never the weight; no gradient reaches it
            bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                              (self.experts,))
            chosen, weights = moe.route(
                flat, router.astype(self.dtype), bias, top_k=self.top_k,
                scale=self.scale, renormalize=self.renormalize, eps=self.renorm_eps)
            self.sow(STEP_STATS, "load",
                     moe.assignment_counts(chosen, 0, self.experts))
            # for a caller that asks (``mutable=[ROUTING]``): which experts
            # each token chose, to hold a second computation to the same
            self.sow(ROUTING, "chosen", chosen.reshape(batch, length, self.top_k))
        names, _ = moe.FORMS[self.form]
        matrices = [self.param(
            f"experts_{name}", INIT,
            (self.held, self.width, hidden) if name == "down"
            else (self.held, hidden, self.width)) for name in names]
        with jax.named_scope(scopes.MOE_EXPERTS):
            out = moe.held_experts(
                flat, chosen, weights.astype(self.dtype),
                *(w.astype(self.dtype) for w in matrices), first=first,
                form=self.form)
        out = out.reshape(batch, length, hidden)
        if self.shared:
            out = out + FEED_FORWARDS[self.form](
                self.shared_width or self.width * self.shared, self.dtype,
                name="shared_experts")(x)
        return out


class Sizes:
    """The sizes a block needs, hashable so that ``nn.remat`` takes them."""

    def __init__(self, **sizes):
        self.__dict__.update(sizes)
        self._key = tuple(sorted((k, v) for k, v in sizes.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Sizes) and self._key == other._key

    def __repr__(self):
        return f"Sizes{self._key}"


def remat_block(block):
    """`block`, a module class, under ``nn.remat``: its input is kept and
    the block computed again in the backward pass — but for the fused
    attention cores' output and log-sum-exp (``ops/attention.py`` names
    them), which are kept beside it, so that the forward kernel runs once
    a step: the backward kernel needs nothing else of it."""
    return nn.remat(block, policy=jax.checkpoint_policies.save_only_these_names(
        OUT_NAME, LSE_NAME))


def proj(x, features: int, name: str, dtype, kernel_init=INIT):
    """A mixer's projection of `x`: :func:`dense`'s layer called under
    ``faa_mixer_proj``, where the product is traced; a norm, a rotation, a
    gate, the taps, a cast or a reshape round the product stays outside it.

    Down here and not beside :func:`dense`: a Mosaic kernel's payload in the
    lowered step holds the line numbers of the frames that called it, the
    mixers' and the blocks' among them.  With no such line moved the step
    lowers to the text it lowered to before the scope, byte for byte."""
    with jax.named_scope(scopes.MIXER_PROJ):
        return dense(features, name, dtype, kernel_init)(x)


def proj_columns(x, matrices, dtype):
    """A mixer's projection wanted in other columns than its kernel has
    (:class:`Kernel`: :func:`dense`'s parameter, handed out whole): `x`
    times each of `matrices`, the kernel's columns arranged — a few MB of
    weights, never the activation — under ``faa_mixer_proj`` as
    :func:`proj`'s product is."""
    # behind a barrier: fused into the products, the arrangement (and its
    # transpose into the weight gradients) has XLA lay the products out by it
    # and move the activations to match — 84 MB a product where this is 18
    matrices = jax.lax.optimization_barrier(tuple(matrices))
    with jax.named_scope(scopes.MIXER_PROJ):
        return [jnp.dot(x.astype(dtype), w.astype(dtype)) for w in matrices]


def expert_share_of(conf: Any, experts: int) -> tuple[int, int]:
    """``(experts_held, expert_share)`` of a conf ``model`` mapping whose
    expert layers have `experts` experts; refuses what is no share."""
    held = int(conf.get("experts_held") or experts)
    share = int(conf.get("expert_share") or 0)
    if experts % held or not 0 <= share < experts // held:
        raise ValueError(f"experts_held={held}, expert_share={share}: not a "
                         f"share of {experts} experts")
    return held, share


def refuse_unwritten_routing(groups: int, chosen_groups: int, activation: str,
                             layer_freq: int = 1) -> None:
    """What no token model here has written down of a router: a top-k
    taken group by group, scores other than a sigmoid's (a softmax over
    the experts), and expert layers at a fixed stride among dense ones.
    (Which layers hold experts by a pattern string, experts of two
    matrices and a shared expert of a width of its own are written:
    ``models/nemotron_h.py``.)"""
    if groups != 1 or chosen_groups != 1:
        raise ValueError("grouped top-k over more than one group is not "
                         "written down")
    if activation != "sigmoid":
        raise ValueError("only the sigmoid router is written down")
    if layer_freq != 1:
        raise ValueError("moe_layer_freq other than 1 is not written down")


def balance_routers(sizes: Sizes, params, stats):
    """``(params, counts)`` after an optimizer step whose forward pass
    sowed `stats` (``{layer name: {"moe": {"load": (loads,)}}}``): every
    expert layer's correction bias moved by the balancing rule, and per
    layer the assignments the held experts received, in all and the most
    loaded one's (float32 scalars, for the step's count sums)."""
    first = sizes.expert_share * sizes.experts_held
    params, counts = dict(params), {}
    for layer, entry in sorted(stats.items()):
        load = sum(entry["moe"]["load"])
        held = load[first:first + sizes.experts_held].astype(jnp.float32)
        counts[f"moe_assigned/{layer}"] = jnp.sum(held)
        counts[f"moe_largest/{layer}"] = jnp.max(held)
        if sizes.bias_update_rate:
            layer_params = dict(params[layer])
            layer_params["moe"] = dict(
                layer_params["moe"], e_score_correction_bias=moe.balance_bias(
                    layer_params["moe"]["e_score_correction_bias"], load,
                    sizes.bias_update_rate))
            params[layer] = layer_params
    return params, counts


def publish_router_counts(sizes: Sizes, rise: dict, registry) -> None:
    """What :func:`balance_routers` counted, where the trainer has synced
    a stretch of steps' sums anyway (`rise`: the sums over the steps since
    it last published): the counter
    ``faa_moe_assignments_total{held,layer}`` and the gauge
    ``faa_moe_held_load_max_over_mean{layer}``, the most loaded held
    expert's assignments over the held experts' mean, over those steps."""
    for key, assigned in rise.items():
        kind, _, layer = key.partition("/")
        if kind != "moe_assigned":
            continue
        registry.counter(
            "faa_moe_assignments_total",
            "token-to-expert assignments that fell to experts this chip "
            "holds", held="true", layer=layer).inc(assigned)
        largest = rise.get(f"moe_largest/{layer}")
        if largest is not None and assigned > 0:
            registry.gauge(
                "faa_moe_held_load_max_over_mean",
                "assignments of the most loaded held expert over the held "
                "experts' mean, a step at a time, over the steps last "
                "published", layer=layer).set(
                    largest * sizes.experts_held / assigned)
