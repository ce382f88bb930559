"""Kimi Linear (``model_type: kimi_linear``, arXiv:2510.26692) as a Flax
module: token ids in, next-token logits out.

Pre-norm blocks, ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``,
a final RMSNorm and an untied head.  The mixer of a layer is KDA (gated
delta-rule linear attention, ``ops/kda.py``) or latent attention without
rotary (MLA, ``mla_use_nope``; ``ops/attention.py``), by the published
lists of layers; the FFN is a dense SwiGLU in the leading
``first_k_dense_replace`` layers and after them a sigmoid-routed expert
layer beside one shared expert (``ops/moe.py``).

The sizes are the published ``config.json``'s keys, handed over as the conf's
``model`` mapping (:func:`kimi_linear_from_conf`), with the three keys that say
what *this chip* holds of a deployment in which 32 chips share each layer and
further chips hold further layers (``models/token_blocks.py::CUT_KEYS``).  The
blocks every token model here is made of, and the router's rule between steps
behind :meth:`KimiLinear.after_step` and :meth:`KimiLinear.publish_counts`, are
that module's.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from fast_autoaugment_tpu.core import scopes
from fast_autoaugment_tpu.models.token_blocks import (
    CUT_KEYS,
    INIT,
    ROUTING,
    STEP_STATS,
    ExpertLayer,
    MLAMixer,
    RMSNorm,
    Sizes as _Sizes,
    SwiGLU,
    balance_routers,
    causal_conv,
    dense as _dense,
    expert_share_of,
    proj as _proj,
    publish_router_counts,
    refuse_unwritten_routing,
    remat_block,
    step_bias_init,
)
from fast_autoaugment_tpu.ops.kda import by_tile as _by_tile, chunk_kda

__all__ = ["KimiLinear", "kimi_linear_from_conf", "STEP_STATS", "ROUTING",
           "CUT_KEYS", "ExpertLayer", "SwiGLU"]


class ShortConv(nn.Module):
    """Depthwise causal convolution over time, then SiLU."""

    taps: int

    @nn.compact
    def __call__(self, x):                                   # [B, T, C]
        kernel = self.param("kernel", nn.initializers.normal(
            1.0 / math.sqrt(self.taps)), (self.taps, x.shape[-1]))
        return jax.nn.silu(causal_conv(x, kernel))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


#: fla's initialisation: a step drawn log-uniformly in [0.001, 0.1]
_dt_bias_init = step_bias_init(0.001, 0.1, 1e-4)


class KDAMixer(nn.Module):
    """Every array between a projection and ``o_proj`` stays ``[B, T, H *
    K]``, the heads side by side as the projection writes them and as
    ``ops/kda.py``'s kernels read them.  What is per head: the unit
    lengths of ``q`` and ``k`` are the kernels' (``unit_scale``), ``A_log``
    is a vector ``[H * K]``, and the output norm runs over the last axis
    of the array cut as it is tiled (``ops/kda.py::by_tile``); no array is cut
    into ``(H, K)`` tiles on the way."""

    heads: int
    head_dim: int
    taps: int
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):                                   # [B, T, D]
        heads, dim = self.heads, self.head_dim
        width = heads * dim

        def branch(name):
            y = _proj(x, width, f"{name}_proj", self.dtype)
            return ShortConv(self.taps, name=f"{name}_conv")(y)

        q, k, v = branch("q"), branch("k"), branch("v")
        a_log = self.param("A_log", _a_log_init, (heads,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (width,))
        gate = _proj(_proj(x, dim, "f_a_proj", self.dtype), width, "f_b_proj",
                     self.dtype)
        gate = jax.nn.softplus(gate.astype(jnp.float32) + dt_bias)
        g = jnp.repeat(-jnp.exp(a_log), dim) * gate
        beta = jax.nn.sigmoid(
            _proj(x, heads, "b_proj", self.dtype).astype(jnp.float32))
        with jax.named_scope(scopes.KDA_SCAN):
            # q and k to unit length a head, q by dim ** -0.5: in the kernels
            out, _ = chunk_kda(q, k, v, g, beta, unit_scale=dim ** -0.5)
        out = RMSNorm(self.eps, name="o_norm")(
            _by_tile(out.astype(self.dtype), heads)).reshape(out.shape)
        out_gate = _proj(_proj(x, dim, "g_a_proj", self.dtype), width, "g_b_proj",
                         self.dtype)
        return _proj(out * jax.nn.sigmoid(out_gate), x.shape[-1], "o_proj",
                     self.dtype)


class Block(nn.Module):
    conf: Any            # the hashable view KimiLinear makes of its sizes
    layer: int           # 1-based, as the published lists count
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.conf
        normed = RMSNorm(c.eps, name="input_norm")(x)
        if self.layer in c.kda_layers:
            with jax.named_scope(scopes.KDA):
                mixed = KDAMixer(c.kda_heads, c.kda_head_dim, c.conv_taps, c.eps,
                                 self.dtype, name="kda")(normed)
        else:
            with jax.named_scope(scopes.MLA):
                mixed = MLAMixer(c.heads, c.nope_dim, c.pe_dim, c.v_dim, c.kv_rank,
                                 c.eps, self.dtype, name="mla")(normed)
        h = x + mixed
        normed = RMSNorm(c.eps, name="post_norm")(h)
        if self.layer <= c.dense_layers:
            ffn = SwiGLU(c.dense_width, self.dtype, name="mlp")(normed)
        else:
            with jax.named_scope(scopes.MOE):
                ffn = ExpertLayer(c.experts, c.experts_held, c.expert_share,
                                  c.top_k, c.expert_width, c.shared_experts,
                                  c.routed_scale, c.renormalize, self.dtype,
                                  name="moe")(normed)
        return h + ffn


class KimiLinear(nn.Module):
    sizes: _Sizes
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
    def __call__(self, ids, train: bool = False):
        """`ids` ``[B, T]`` int32 (below ``ids_held``) -> logits ``[B, T,
        ids_held]`` float32.  `train` changes nothing: the model has no
        dropout and no running statistics."""
        del train
        c = self.sizes
        if ids.dtype not in (jnp.int32, jnp.uint32, jnp.int64):
            ids = ids.astype(jnp.int32)  # an init sample may come as floats
        table = self.param("embed_tokens", INIT, (c.ids_held, c.hidden))
        x = jnp.take(table, ids, axis=0).astype(self.dtype)
        block = remat_block(Block) if self.remat else Block
        for layer in range(1, c.layers_held + 1):
            x = block(c, layer, self.dtype, name=f"layer{layer}")(x)
        x = RMSNorm(c.eps, name="norm")(x)
        with jax.named_scope(scopes.LM_HEAD):
            logits = _dense(c.ids_held, "lm_head", self.dtype)(x)
        return logits.astype(jnp.float32)


def kimi_linear_from_conf(conf: Any, dtype=jnp.float32) -> KimiLinear:
    """The module for a conf ``model`` mapping that holds the published
    ``config.json``'s keys (and, merged in by the caller, the three keys
    of :data:`CUT_KEYS`)."""
    linear = conf["linear_attn_config"]
    layers = int(conf["num_hidden_layers"])
    experts = int(conf["num_experts"])
    ids = int(conf["vocab_size"])
    held, share = expert_share_of(conf, experts)
    if conf.get("q_lora_rank") is not None or not conf.get("mla_use_nope", False):
        raise ValueError("this family's latent attention has q_lora_rank: null "
                         "and mla_use_nope: true; a low-rank query and rotary "
                         "are models/glm4_moe_lite.py's")
    refuse_unwritten_routing(
        int(conf.get("num_expert_group", 1)), int(conf.get("topk_group", 1)),
        conf.get("moe_router_activation_func", "sigmoid"),
        int(conf.get("moe_layer_freq", 1)))
    sizes = _Sizes(
        hidden=int(conf["hidden_size"]), eps=float(conf["rms_norm_eps"]),
        layers_held=int(conf.get("layers_held") or layers),
        ids_held=int(conf.get("ids_held") or ids),
        kda_layers=tuple(int(i) for i in linear["kda_layers"]),
        kda_heads=int(linear["num_heads"]), kda_head_dim=int(linear["head_dim"]),
        conv_taps=int(linear["short_conv_kernel_size"]),
        heads=int(conf["num_attention_heads"]),
        nope_dim=int(conf["qk_nope_head_dim"]), pe_dim=int(conf["qk_rope_head_dim"]),
        v_dim=int(conf["v_head_dim"]), kv_rank=int(conf["kv_lora_rank"]),
        dense_layers=int(conf["first_k_dense_replace"]),
        dense_width=int(conf["intermediate_size"]),
        experts=experts, experts_held=held, expert_share=share,
        top_k=int(conf["num_experts_per_token"]),
        expert_width=int(conf["moe_intermediate_size"]),
        shared_experts=int(conf["num_shared_experts"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        renormalize=bool(conf.get("moe_renormalize", True)),
        bias_update_rate=float(conf.get("router_bias_update_rate") or 0.0))
    if not 1 <= sizes.layers_held <= layers or not 1 <= sizes.ids_held <= ids:
        raise ValueError(f"layers_held={sizes.layers_held}, ids_held="
                         f"{sizes.ids_held}: more than the model has")
    return KimiLinear(sizes, remat=bool(conf.get("remat", True)), dtype=dtype)
