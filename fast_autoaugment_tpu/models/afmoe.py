"""Arcee's afmoe (``model_type: afmoe``; ``config.json`` of
arcee-ai/Trinity-Mini) as a Flax module: token ids in, next-token logits out.

The embedding's output times ``sqrt(hidden_size)`` (``mup_enabled``), then
blocks of a mixer and a feed-forward under **four** RMSNorms, two on the
sub-layers' inputs and two on their outputs::

    h = x + N2(mixer(N1(x)))          y = h + N4(ffn(N3(h)))

a final RMSNorm and an untied head.

The mixer of every block is grouped-query attention
(``models/token_blocks.py::GQAMixer``): ``num_attention_heads`` query heads
of ``head_dim`` on ``num_key_value_heads`` key-value heads, an RMS norm a
head on queries and on keys, the output times ``sigmoid(gate_proj x)`` in
front of ``o_proj``.  ``layer_types`` says which of two kinds a block's
mixer is: ``sliding_attention`` rotates queries and keys by position
(``rope_theta``, pairs ``(i, i + head_dim / 2)``) and sees the
``sliding_window`` nearest keys, its own among them (``0 <= i - j <
sliding_window``: a key span in ``ops/attention.py``'s kernels' loops);
``full_attention`` rotates nothing and sees the whole causal past.

The feed-forward is a dense SwiGLU at ``intermediate_size`` in the leading
``num_dense_layers`` blocks and after them ``num_experts`` sigmoid-routed
SwiGLU experts of ``moe_intermediate_size`` beside ``num_shared_experts``
of the same form (``models/token_blocks.py::ExpertLayer``, ``ops/moe.py``):
the ``num_experts_per_tok`` largest of ``score + expert_bias``, the chosen
scores over their sum (``route_norm``) times ``route_scale``.  The bias
moves between steps, outside the gradient (``ops/moe.py::balance_bias``),
by ``load_balance_coeff`` a step, the published key, unless the conf sets
this repo's ``router_bias_update_rate`` as the other token models' confs
do.

What a step body calls (``train/steps.py::make_token_step_body``):
:meth:`Afmoe.loss_terms`, the head's product and cross-entropy a block of
positions at a time (``ops/lm_head.py``), and :meth:`Afmoe.after_step` /
:meth:`Afmoe.publish_counts`, the routers' rule between steps.

The sizes are the published ``config.json``'s keys, handed over as the
conf's ``model`` mapping (:func:`afmoe_from_conf`) with the three keys of
``token_blocks.CUT_KEYS``; ``layers_held`` counts blocks, the leading dense
ones among them.  The parameter tree: ``embed_tokens``, ``layer1`` ..
``layer<n>`` (``input_norm``, ``attn``, ``post_attn_norm``,
``pre_mlp_norm``, ``mlp`` or ``moe``, ``post_mlp_norm``), ``norm``,
``lm_head/kernel``.
"""

from __future__ import annotations

import contextlib
import math
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
    Kernel,
    RMSNorm,
    Sizes,
    SwiGLU,
    balance_routers,
    expert_share_of,
    publish_router_counts,
    refuse_unwritten_routing,
    remat_block,
)
from fast_autoaugment_tpu.ops.lm_head import blocked_next_token_sums

__all__ = ["Afmoe", "afmoe_from_conf", "STEP_STATS", "ROUTING", "WINDOW", "FULL"]

#: the two values of ``layer_types``
WINDOW, FULL = "sliding_attention", "full_attention"


class Block(nn.Module):
    conf: Any            # the hashable view Afmoe makes of its sizes
    window: bool         # a window layer (rotary, a key span) or a full one
    dense_ffn: bool      # a dense SwiGLU (a leading block) or the experts
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.conf
        mixer = GQAMixer(
            c.heads, c.kv_heads, c.head_dim, self.dtype, qk_norm_eps=c.eps,
            rope_theta=c.rope_theta if self.window else None,
            window=c.window if self.window else None, gated=True, name="attn")
        normed = RMSNorm(c.eps, name="input_norm")(x)
        # every mixer under faa_gqa, a window layer's under faa_swa too
        with jax.named_scope(scopes.GQA), (
                jax.named_scope(scopes.SWA) if self.window else contextlib.nullcontext()):
            mixed = mixer(normed)
        h = x + RMSNorm(c.eps, name="post_attn_norm")(mixed)
        normed = RMSNorm(c.eps, name="pre_mlp_norm")(h)
        if self.dense_ffn:
            ffn = SwiGLU(c.dense_width, self.dtype, name="mlp")(normed)
        else:
            with jax.named_scope(scopes.MOE):
                ffn = ExpertLayer(c.experts, c.experts_held, c.expert_share,
                                  c.top_k, c.expert_width, c.shared_experts,
                                  c.routed_scale, c.renormalize, self.dtype,
                                  name="moe")(normed)
        return h + RMSNorm(c.eps, name="post_mlp_norm")(ffn)


class Afmoe(nn.Module):
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
        """``(x, head kernel)``: the final norm's output ``[B, T, D]``."""
        c = self.sizes
        if ids.dtype not in (jnp.int32, jnp.uint32, jnp.int64):
            ids = ids.astype(jnp.int32)  # an init sample may come as floats
        table = self.param("embed_tokens", INIT, (c.ids_held, c.hidden))
        x = jnp.take(table, ids, axis=0).astype(self.dtype)
        if c.embed_scale != 1.0:
            x = x * jnp.asarray(c.embed_scale, self.dtype)
        block = remat_block(Block) if self.remat else Block
        for index, kind in enumerate(c.layer_types[:c.layers_held], start=1):
            x = block(c, kind == WINDOW, index <= c.dense_layers, self.dtype,
                      name=f"layer{index}")(x)
        head = Kernel((c.hidden, c.ids_held), name="lm_head")()
        return RMSNorm(c.eps, name="norm")(x), head

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


def afmoe_from_conf(conf: Any, dtype=jnp.float32) -> Afmoe:
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
    unknown = set(kinds) - {WINDOW, FULL}
    if unknown:
        raise ValueError(f"layer_types: unknown kinds {sorted(unknown)} "
                         f"(have {WINDOW}, {FULL})")
    held, share = expert_share_of(conf, experts)
    refuse_unwritten_routing(
        max(int(conf.get("n_group", 1)), int(conf.get("num_expert_groups", 1))),
        max(int(conf.get("topk_group", 1)), int(conf.get("num_limited_groups", 1))),
        str(conf.get("score_func", "sigmoid")))
    if conf.get("rope_scaling"):
        raise ValueError("a scaled rotary (rope_scaling) is not written down")
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {conf.get('hidden_act')!r} is not written "
                         "down: the feed-forwards are SwiGLU")
    if conf.get("attention_bias", False) or conf.get("tie_word_embeddings", False):
        raise ValueError("attention_bias or tie_word_embeddings: true is not "
                         "written down")
    heads, kv_heads = int(conf["num_attention_heads"]), int(conf["num_key_value_heads"])
    if heads % kv_heads or int(conf["head_dim"]) % 2:
        raise ValueError(f"{heads} query heads over {kv_heads} key-value heads of "
                         f"{conf['head_dim']}: no whole number a group, or rotary "
                         "has no pairs to turn")
    hidden = int(conf["hidden_size"])
    sizes = Sizes(
        hidden=hidden, eps=float(conf["rms_norm_eps"]),
        embed_scale=math.sqrt(hidden) if conf.get("mup_enabled", False) else 1.0,
        layer_types=kinds,
        layers_held=int(conf.get("layers_held") or layers),
        ids_held=int(conf.get("ids_held") or ids),
        heads=heads, kv_heads=kv_heads, head_dim=int(conf["head_dim"]),
        rope_theta=float(conf["rope_theta"]), window=int(conf["sliding_window"]),
        dense_layers=int(conf["num_dense_layers"]),
        dense_width=int(conf["intermediate_size"]),
        experts=experts, experts_held=held, expert_share=share,
        top_k=int(conf["num_experts_per_tok"]),
        expert_width=int(conf["moe_intermediate_size"]),
        shared_experts=int(conf["num_shared_experts"]),
        routed_scale=float(conf["route_scale"]),
        renormalize=bool(conf.get("route_norm", True)),
        bias_update_rate=float(conf.get("router_bias_update_rate",
                                        conf.get("load_balance_coeff")) or 0.0))
    if not 1 <= sizes.layers_held <= layers or not 1 <= sizes.ids_held <= ids:
        raise ValueError(f"layers_held={sizes.layers_held}, ids_held="
                         f"{sizes.ids_held}: more than the model has")
    return Afmoe(sizes, remat=bool(conf.get("remat", True)), dtype=dtype)
