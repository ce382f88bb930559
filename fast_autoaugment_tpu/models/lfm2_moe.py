"""LiquidAI's lfm2_moe (``model_type: lfm2_moe``; ``config.json`` of
LiquidAI/LFM2-8B-A1B) as a Flax module: token ids in, next-token logits out.

The embedding, then blocks of a mixer and a feed-forward under two
RMSNorms on the sub-layers' inputs (``operator_norm``, ``ffn_norm``)::

    h = x + mixer(N1(x))          y = h + ffn(N2(h))

a final RMSNorm and **the head the embedding's transpose**: the logits are
``norm(x) E^T`` through the one table, whose gradient is the sum of the
embedding's and the head's.

``layer_types`` says which of two kinds a block's mixer is.  ``conv``: a
doubly gated short causal convolution (``models/token_blocks.py::
ShortConvMixer``: ``(B, C, z) = split3(in_proj u)``, ``c = taps(B * z)``
over ``conv_L_cache`` taps, depthwise, no bias (``conv_bias: false``), no
activation, ``out_proj(C * c)``).  ``full_attention``: grouped-query
attention (``models/token_blocks.py::GQAMixer``), ``num_attention_heads``
query heads of ``hidden_size / num_attention_heads`` on
``num_key_value_heads`` key-value heads, an RMS norm a head on queries and
on keys, both rotated by position (``rope_theta``, pairs ``(i, i + head_dim
/ 2)``), the whole causal past — at the published head of 64 two heads to a
128-lane block of ``ops/attention.py``'s fused kernels.

The feed-forward is a dense SwiGLU at ``intermediate_size`` in the leading
``num_dense_layers`` blocks and after them ``num_experts`` sigmoid-routed
SwiGLU experts of ``moe_intermediate_size`` with **no shared expert**
(``models/token_blocks.py::ExpertLayer`` with ``shared=0``, ``ops/moe.py``):
the ``num_experts_per_tok`` largest of ``score + expert_bias``
(``use_expert_bias``), the chosen scores over their sum + 1e-6
(``norm_topk_prob``) times ``routed_scaling_factor``.  The bias moves
between steps, outside the gradient (``ops/moe.py::balance_bias``), by the
conf's ``router_bias_update_rate``.

What a step body calls (``train/steps.py::make_token_step_body``):
:meth:`Lfm2Moe.loss_terms`, the head's product and cross-entropy a block of
positions at a time (``ops/lm_head.py``), and :meth:`Lfm2Moe.after_step` /
:meth:`Lfm2Moe.publish_counts`, the routers' rule between steps.

The sizes are the published ``config.json``'s keys, handed over as the
conf's ``model`` mapping (:func:`lfm2_moe_from_conf`) with the three keys of
``token_blocks.CUT_KEYS``; ``layers_held`` counts blocks, the leading dense
ones among them.  The parameter tree: ``embed_tokens``, ``layer1`` ..
``layer<n>`` (``operator_norm``, ``conv`` or ``attn``, ``ffn_norm``, ``mlp``
or ``moe``), ``norm``; no ``lm_head``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from fast_autoaugment_tpu.core import scopes
from fast_autoaugment_tpu.models.token_blocks import (
    INIT,
    ROUTING,
    STEP_STATS,
    ExpertLayer,
    GQAMixer,
    RMSNorm,
    ShortConvMixer,
    Sizes,
    SwiGLU,
    balance_routers,
    expert_share_of,
    publish_router_counts,
    refuse_unwritten_routing,
    remat_block,
)
from fast_autoaugment_tpu.ops.lm_head import blocked_next_token_sums

__all__ = ["Lfm2Moe", "lfm2_moe_from_conf", "STEP_STATS", "ROUTING", "CONV", "FULL",
           "RENORM_EPS"]

#: the two values of ``layer_types``
CONV, FULL = "conv", "full_attention"
#: what the family's renormalisation adds to the chosen scores' sum
RENORM_EPS = 1e-6


class Block(nn.Module):
    conf: Any            # the hashable view Lfm2Moe makes of its sizes
    conv: bool           # a short-convolution mixer or an attention one
    dense_ffn: bool      # a dense SwiGLU (a leading block) or the experts
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.conf
        normed = RMSNorm(c.eps, name="operator_norm")(x)
        if self.conv:
            with jax.named_scope(scopes.SHORT_CONV):
                mixed = ShortConvMixer(c.taps, self.dtype, name="conv")(normed)
        else:
            with jax.named_scope(scopes.GQA):
                mixed = GQAMixer(c.heads, c.kv_heads, c.head_dim, self.dtype,
                                 qk_norm_eps=c.eps, rope_theta=c.rope_theta,
                                 name="attn")(normed)
        h = x + mixed
        normed = RMSNorm(c.eps, name="ffn_norm")(h)
        if self.dense_ffn:
            return h + SwiGLU(c.dense_width, self.dtype, name="mlp")(normed)
        with jax.named_scope(scopes.MOE):
            return h + ExpertLayer(
                c.experts, c.experts_held, c.expert_share, c.top_k, c.expert_width,
                0, c.routed_scale, c.renormalize, self.dtype,
                renorm_eps=RENORM_EPS, name="moe")(normed)


class Lfm2Moe(nn.Module):
    sizes: Sizes
    remat: bool = True
    dtype: Any = jnp.float32

    #: what a step body makes mutable and hands to :meth:`after_step`
    step_collection = STEP_STATS

    def after_step(self, params, stats):
        """``token_blocks.balance_routers`` over this model's sizes."""
        return balance_routers(self.sizes, params, stats)

    def publish_counts(self, rise: dict, registry) -> None:
        """``token_blocks.publish_router_counts`` over this model's sizes."""
        publish_router_counts(self.sizes, rise, registry)

    @nn.compact
    def _hidden(self, ids):
        """``(x, head kernel)``: the final norm's output ``[B, T, D]`` and
        the embedding's transpose ``[D, ids_held]``."""
        c = self.sizes
        if ids.dtype not in (jnp.int32, jnp.uint32, jnp.int64):
            ids = ids.astype(jnp.int32)  # an init sample may come as floats
        table = self.param("embed_tokens", INIT, (c.ids_held, c.hidden))
        x = jnp.take(table, ids, axis=0).astype(self.dtype)
        block = remat_block(Block) if self.remat else Block
        for index, kind in enumerate(c.layer_types[:c.layers_held], start=1):
            x = block(c, kind == CONV, index <= c.dense_layers, self.dtype,
                      name=f"layer{index}")(x)
        return RMSNorm(c.eps, name="norm")(x), table.T

    def __call__(self, ids, train: bool = False):
        """`ids` ``[B, T]`` int32 (below ``ids_held``) -> logits ``[B, T,
        ids_held]`` float32.  `train` changes nothing: the model has no
        dropout and no running statistics."""
        del train
        x, head = self._hidden(ids)
        with jax.named_scope(scopes.LM_HEAD):
            return jnp.dot(x, head.astype(self.dtype)).astype(jnp.float32)

    def loss_terms(self, ids, targets):
        """``(nll [B], top1 [B], {})`` for inputs `ids` and `targets` ``[B,
        T]``: the mean next-token cross-entropy and accuracy a sequence,
        the head taken a block of positions at a time."""
        x, head = self._hidden(ids)
        nll, hits = blocked_next_token_sums(x, head, targets)
        return nll / ids.shape[1], hits / ids.shape[1], {}


def lfm2_moe_from_conf(conf: Any, dtype=jnp.float32) -> Lfm2Moe:
    """The module for a conf ``model`` mapping that holds the published
    ``config.json``'s keys (and, merged in by the caller, the three keys
    of ``token_blocks.CUT_KEYS``)."""
    layers = int(conf["num_hidden_layers"])
    experts = int(conf["num_experts"])
    ids = int(conf["vocab_size"])
    kinds = tuple(str(kind) for kind in conf["layer_types"])
    if len(kinds) != layers:
        raise ValueError(f"layer_types has {len(kinds)} layers, "
                         f"num_hidden_layers says {layers}")
    unknown = set(kinds) - {CONV, FULL}
    if unknown:
        raise ValueError(f"layer_types: unknown kinds {sorted(unknown)} "
                         f"(have {CONV}, {FULL})")
    held, share = expert_share_of(conf, experts)
    refuse_unwritten_routing(int(conf.get("n_group", 1)), int(conf.get("topk_group", 1)),
                             str(conf.get("score_func", "sigmoid")))
    if conf.get("rope_scaling"):
        raise ValueError("a scaled rotary (rope_scaling) is not written down")
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {conf.get('hidden_act')!r} is not written "
                         "down: the feed-forwards are SwiGLU")
    if conf.get("conv_bias", False) or not conf.get("use_expert_bias", True):
        raise ValueError("conv_bias: true or use_expert_bias: false is not "
                         "written down")
    if not conf.get("tie_word_embeddings", True):
        raise ValueError("tie_word_embeddings: false is not written down: the "
                         "head is the embedding's transpose")
    hidden = int(conf["hidden_size"])
    heads, kv_heads = int(conf["num_attention_heads"]), int(conf["num_key_value_heads"])
    head_dim = int(conf.get("head_dim") or hidden // heads)
    if heads % kv_heads or head_dim % 2:
        raise ValueError(f"{heads} query heads over {kv_heads} key-value heads of "
                         f"{head_dim}: no whole number a group, or rotary "
                         "has no pairs to turn")
    sizes = Sizes(
        hidden=hidden, eps=float(conf["norm_eps"]), layer_types=kinds,
        layers_held=int(conf.get("layers_held") or layers),
        ids_held=int(conf.get("ids_held") or ids),
        heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        rope_theta=float(conf["rope_theta"]), taps=int(conf["conv_L_cache"]),
        dense_layers=int(conf["num_dense_layers"]),
        dense_width=int(conf["intermediate_size"]),
        experts=experts, experts_held=held, expert_share=share,
        top_k=int(conf["num_experts_per_tok"]),
        expert_width=int(conf["moe_intermediate_size"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        renormalize=bool(conf.get("norm_topk_prob", True)),
        bias_update_rate=float(conf.get("router_bias_update_rate") or 0.0))
    if not 1 <= sizes.layers_held <= layers or not 1 <= sizes.ids_held <= ids:
        raise ValueError(f"layers_held={sizes.layers_held}, ids_held="
                         f"{sizes.ids_held}: more than the model has")
    return Lfm2Moe(sizes, remat=bool(conf.get("remat", True)), dtype=dtype)
