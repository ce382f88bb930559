"""GLM-4.7-Flash (``model_type: glm4_moe_lite``; ``config.json`` of
zai-org/GLM-4.7-Flash) as a Flax module: token ids in, next-token logits
out, and in training a second loss term from a multi-token-prediction
module.

Pre-norm blocks, ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``,
a final RMSNorm and an untied head.  The mixer of *every* layer is latent
attention with a low-rank query (``q_a`` -> RMSNorm -> ``q_b``) and rotary
on each head's ``qk_rope_head_dim`` query dimensions and on the one key
part all heads share (``models/token_blocks.py::MLAMixer``); the FFN is a
dense SwiGLU in the leading ``first_k_dense_replace`` layers and after
them a sigmoid-routed expert layer beside one shared expert
(``ops/moe.py``).

**Multi-token prediction, depth 1** (DeepSeek-V3, arXiv:2412.19437
section 2.2, which ``num_nextn_predict_layers`` refers to).  With ``h_i``
the output of the last held layer at position ``i`` *before* the final
norm and ``t_{i+1}`` the next token,

    h'_i  = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]
    h''_i = Block(h'_i)
    logits^mtp_i = Head(RMSNorm_s(h''_i))      predicts t_{i+2}

``Emb`` and ``Head`` are the main model's own arrays, so their gradient
has two sources; ``Block`` is one more block of the kind the expert
layers are, with its own router, bias and held experts.  The training
loss is ``CE(logits, t_{i+1}) + mtp_weight * CE(logits^mtp, t_{i+2})``,
the second mean over the ``T - 1`` positions that have a target two
ahead (the block runs over all ``T`` so that the attention's blocks stay
whole; the last position's term is weighed by zero).  In evaluation and
under ``train=False`` the module is not computed.

What a step body calls (``train/steps.py::make_token_step_body``):
:meth:`Glm4MoeLite.loss_terms` — inputs *and* targets in, the main
head's per-sequence loss and accuracy and the further terms by name
(``mtp_loss`` at ``mtp_weight``, ``mtp_top1`` reported only) out — with
both heads' product and cross-entropy taken a block of positions at a
time (``ops/lm_head.py``), and :meth:`Glm4MoeLite.after_step` /
:meth:`Glm4MoeLite.publish_counts`, the routers' rule between steps with
the module's own counts beside it.

The sizes are the published ``config.json``'s keys, handed over as the
conf's ``model`` mapping (:func:`glm4_moe_lite_from_conf`) with the three
keys of ``token_blocks.CUT_KEYS``.  The parameter tree: ``embed_tokens``,
``layer1`` .. ``layer<n>`` (``input_norm``, ``mla``, ``post_norm``, ``mlp``
or ``moe``), ``norm``, ``lm_head/kernel``, and for the module ``mtp_enorm``,
``mtp_hnorm``, ``mtp_eh_proj/kernel`` (the embedding's half first),
``mtp`` (its block, a layer of its own to the routers' rule and counters)
and ``mtp_norm``.
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
    Kernel,
    MLAMixer,
    RMSNorm,
    Sizes,
    SwiGLU,
    balance_routers,
    dense,
    expert_share_of,
    publish_router_counts,
    refuse_unwritten_routing,
    remat_block,
)
from fast_autoaugment_tpu.ops.lm_head import blocked_next_token_sums

__all__ = ["Glm4MoeLite", "glm4_moe_lite_from_conf", "STEP_STATS", "ROUTING",
           "MTP_LAYER"]

#: the module's block among the layers (parameter tree, routing, counters)
MTP_LAYER = "mtp"


class Block(nn.Module):
    conf: Any            # the hashable view Glm4MoeLite makes of its sizes
    dense_ffn: bool      # a dense SwiGLU (a leading layer) or the experts
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.conf
        normed = RMSNorm(c.eps, name="input_norm")(x)
        with jax.named_scope(scopes.MLA):
            mixed = MLAMixer(c.heads, c.nope_dim, c.pe_dim, c.v_dim, c.kv_rank,
                             c.eps, self.dtype, q_rank=c.q_rank,
                             rope_theta=c.rope_theta, name="mla")(normed)
        h = x + mixed
        normed = RMSNorm(c.eps, name="post_norm")(h)
        if self.dense_ffn:
            ffn = SwiGLU(c.dense_width, self.dtype, name="mlp")(normed)
        else:
            with jax.named_scope(scopes.MOE):
                ffn = ExpertLayer(c.experts, c.experts_held, c.expert_share,
                                  c.top_k, c.expert_width, c.shared_experts,
                                  c.routed_scale, c.renormalize, self.dtype,
                                  name="moe")(normed)
        return h + ffn


class Glm4MoeLite(nn.Module):
    sizes: Sizes
    remat: bool = True
    dtype: Any = jnp.float32

    #: what a step body makes mutable and hands to :meth:`after_step`
    step_collection = STEP_STATS

    def after_step(self, params, stats):
        """``token_blocks.balance_routers`` over the expert layers and the
        module's block, and what :meth:`loss_terms` sowed of the module:
        the positions its term was taken over and their summed loss."""
        stats = dict(stats)
        own = {name: sum(stats.pop(name)) for name in ("mtp_targets", "mtp_nll")
               if name in stats}
        params, counts = balance_routers(self.sizes, params, stats)
        return params, dict(counts, **own)

    def publish_counts(self, rise: dict, registry) -> None:
        """The routers' counters (``token_blocks.publish_router_counts``),
        the counter ``faa_mtp_targets_total`` and the gauge
        ``faa_mtp_loss``, the module's mean loss a position over the steps
        last published."""
        publish_router_counts(self.sizes, rise, registry)
        targets = rise.get("mtp_targets", 0.0)
        if targets > 0:
            registry.counter(
                "faa_mtp_targets_total",
                "positions the multi-token-prediction loss term was taken "
                "over").inc(targets)
            registry.gauge(
                "faa_mtp_loss",
                "the multi-token-prediction head's cross-entropy a position, "
                "over the steps last published").set(rise["mtp_nll"] / targets)

    @nn.compact
    def _run(self, ids, next_ids, want: str):
        """The one body behind the three entry points; `want` is
        ``logits`` (main head alone, `next_ids` not read), ``both``
        (``(logits, mtp_logits)``) or ``hidden`` (``(x, x_mtp, head
        kernel)``: both heads' normed inputs, for a blocked loss)."""
        c = self.sizes
        if ids.dtype not in (jnp.int32, jnp.uint32, jnp.int64):
            ids = ids.astype(jnp.int32)  # an init sample may come as floats
        table = self.param("embed_tokens", INIT, (c.ids_held, c.hidden))
        x = jnp.take(table, ids, axis=0).astype(self.dtype)
        block = remat_block(Block) if self.remat else Block
        for layer in range(1, c.layers_held + 1):
            x = block(c, layer <= c.dense_layers, self.dtype,
                      name=f"layer{layer}")(x)
        head = Kernel((c.hidden, c.ids_held), name="lm_head")()
        normed = RMSNorm(c.eps, name="norm")(x)
        mtp = None
        if c.mtp_modules and (want != "logits" or self.is_initializing()):
            if next_ids is None:   # init: shapes alone matter
                next_ids = ids
            with jax.named_scope(scopes.MTP):
                ahead = jnp.take(table, next_ids.astype(jnp.int32), axis=0)
                joined = jnp.concatenate(
                    [RMSNorm(c.eps, name="mtp_enorm")(ahead.astype(self.dtype)),
                     RMSNorm(c.eps, name="mtp_hnorm")(x)], -1)
                mtp = block(c, False, self.dtype, name=MTP_LAYER)(
                    dense(c.hidden, "mtp_eh_proj", self.dtype)(joined))
                mtp = RMSNorm(c.eps, name="mtp_norm")(mtp)
        if want == "hidden":
            return normed, mtp, head

        def logits_of(h):
            with jax.named_scope(scopes.LM_HEAD):
                return jnp.dot(h, head.astype(self.dtype)).astype(jnp.float32)

        if want == "logits":
            return logits_of(normed)
        return logits_of(normed), None if mtp is None else logits_of(mtp)

    def __call__(self, ids, train: bool = False):
        """`ids` ``[B, T]`` int32 (below ``ids_held``) -> the main head's
        logits ``[B, T, ids_held]`` float32.  `train` changes nothing here:
        the second loss term is :meth:`loss_terms`'s."""
        del train
        return self._run(ids, None, "logits")

    def logits_and_mtp_logits(self, ids, next_ids):
        """``(logits, mtp_logits)``, both ``[B, T, ids_held]`` float32:
        position ``i`` of the second predicts the token after
        ``next_ids[:, i]`` (None where the model has no module)."""
        return self._run(ids, next_ids, "both")

    def loss_terms(self, ids, targets):
        """``(nll [B], top1 [B], further)`` for inputs `ids` and `targets`
        ``[B, T]`` (``targets[:, i]`` the token after ``ids[:, i]``): the
        main head's mean next-token cross-entropy and accuracy a sequence,
        and ``further = {name: (value [B], weight)}`` — terms the loss adds
        at ``weight * mean(value)`` (weight zero: reported only)."""
        c = self.sizes
        x, x_mtp, head = self._run(ids, targets, "hidden")
        length = ids.shape[1]
        nll, hits = blocked_next_token_sums(x, head, targets)
        further = {}
        if x_mtp is not None:
            # position i embedded targets[i] and predicts targets[i + 1]
            two_ahead = jnp.concatenate([targets[:, 1:], targets[:, :1]], 1)
            has_target = jnp.broadcast_to(
                jnp.arange(length) < length - 1, targets.shape)
            mtp_nll, mtp_hits = blocked_next_token_sums(
                x_mtp, head, two_ahead, has_target)
            count = max(length - 1, 1)
            self.sow(STEP_STATS, "mtp_targets",
                     jnp.float32(ids.shape[0] * (length - 1)))
            self.sow(STEP_STATS, "mtp_nll",
                     jax.lax.stop_gradient(mtp_nll.sum()))
            further = {"mtp_loss": (mtp_nll / count, c.mtp_weight),
                       "mtp_top1": (mtp_hits / count, 0.0)}
        return nll / length, hits / length, further


def glm4_moe_lite_from_conf(conf: Any, dtype=jnp.float32) -> Glm4MoeLite:
    """The module for a conf ``model`` mapping that holds the published
    ``config.json``'s keys (and, merged in by the caller, the three keys
    of ``token_blocks.CUT_KEYS``)."""
    layers = int(conf["num_hidden_layers"])
    experts = int(conf["n_routed_experts"])
    ids = int(conf["vocab_size"])
    held, share = expert_share_of(conf, experts)
    refuse_unwritten_routing(
        int(conf.get("n_group", 1)), int(conf.get("topk_group", 1)),
        {"noaux_tc": "sigmoid"}.get(conf.get("topk_method", "noaux_tc"),
                                    str(conf.get("topk_method"))),
        int(conf.get("moe_layer_freq", 1)))
    if conf.get("q_lora_rank") is None:
        raise ValueError("this family's query is a low-rank pair: q_lora_rank "
                         "is not given")
    if float(conf.get("partial_rotary_factor", 1)) != 1 or conf.get("rope_scaling"):
        raise ValueError("rotary over part of qk_rope_head_dim, or scaled, is "
                         "not written down")
    if conf.get("attention_bias", False):
        raise ValueError("attention_bias: true is not written down")
    modules = int(conf.get("num_nextn_predict_layers", 0))
    if modules > 1:
        raise ValueError("multi-token prediction at a depth greater than one "
                         "is not written down")
    sizes = Sizes(
        hidden=int(conf["hidden_size"]), eps=float(conf["rms_norm_eps"]),
        layers_held=int(conf.get("layers_held") or layers),
        ids_held=int(conf.get("ids_held") or ids),
        heads=int(conf["num_attention_heads"]),
        nope_dim=int(conf["qk_nope_head_dim"]), pe_dim=int(conf["qk_rope_head_dim"]),
        v_dim=int(conf["v_head_dim"]), kv_rank=int(conf["kv_lora_rank"]),
        q_rank=int(conf["q_lora_rank"]), rope_theta=float(conf["rope_theta"]),
        dense_layers=int(conf["first_k_dense_replace"]),
        dense_width=int(conf["intermediate_size"]),
        experts=experts, experts_held=held, expert_share=share,
        top_k=int(conf["num_experts_per_tok"]),
        expert_width=int(conf["moe_intermediate_size"]),
        shared_experts=int(conf["n_shared_experts"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        renormalize=bool(conf.get("norm_topk_prob", True)),
        mtp_modules=modules,
        mtp_weight=float(conf.get("mtp_loss_weight", 0.3)),
        bias_update_rate=float(conf.get("router_bias_update_rate") or 0.0))
    if not 1 <= sizes.layers_held <= layers or not 1 <= sizes.ids_held <= ids:
        raise ValueError(f"layers_held={sizes.layers_held}, ids_held="
                         f"{sizes.ids_held}: more than the model has")
    if sizes.pe_dim % 2:
        raise ValueError(f"qk_rope_head_dim={sizes.pe_dim}: rotary turns pairs")
    return Glm4MoeLite(sizes, remat=bool(conf.get("remat", True)), dtype=dtype)
