"""Kimi Linear (``model_type: kimi_linear``, arXiv:2510.26692) as a Flax
module: token ids in, next-token logits out.

Pre-norm blocks, ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``,
a final RMSNorm and an untied head.  The mixer of a layer is KDA (gated
delta-rule linear attention, ``ops/kda.py``) or latent attention without
rotary (MLA, ``mla_use_nope``; ``ops/attention.py``), by the published
lists of layers; the FFN is a dense SwiGLU in the leading
``first_k_dense_replace`` layers and after them a sigmoid-routed expert
layer beside one shared expert (``ops/moe.py``).

The sizes are the published ``config.json``'s keys, handed over as the
conf's ``model`` mapping (:func:`kimi_linear_from_conf`).  Three more
keys say what *this chip* holds of a deployment in which 32 chips share
each layer and further chips hold further layers — absent, the whole
model: ``layers_held`` (the first n layers), ``experts_held`` with
``expert_share`` (experts ``[share * held, (share + 1) * held)`` of every
expert layer; the router keeps its published width and ``top_k``), and
``ids_held`` (ids ``[0, n)``: embedding, head, logits and loss are over
the slice).  No width changes with them.

What a step has to know of the routing an expert layer ``sow``s into
the :data:`STEP_STATS` collection: the assignments each of *all* its
experts received.  :meth:`KimiLinear.after_step` turns that into the
router's next correction bias (``ops/moe.py::balance_bias``: the
balancing rule between steps, outside the gradient) and into the counts
the trainer publishes (:meth:`KimiLinear.publish_counts`).  The step body
and the trainer know of a model only these three names
(``train/steps.py::make_token_step_body``).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from fast_autoaugment_tpu.core import scopes
from fast_autoaugment_tpu.ops import moe
from fast_autoaugment_tpu.ops.attention import blocked_causal_attention
from fast_autoaugment_tpu.ops.kda import chunk_kda

__all__ = ["KimiLinear", "kimi_linear_from_conf", "STEP_STATS", "ROUTING",
           "CUT_KEYS"]

#: the collection the expert layers ``sow`` a step's loads into
STEP_STATS = "step_stats"
#: the collection they ``sow`` every token's chosen experts into
ROUTING = "routing"
#: top-level conf keys that say what this chip holds (absent: everything)
CUT_KEYS = ("layers_held", "experts_held", "ids_held")

_INIT = nn.initializers.normal(0.02)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + self.eps)
        return (x32 * weight).astype(x.dtype)


def _dense(features: int, name: str, dtype) -> nn.Dense:
    return nn.Dense(features, use_bias=False, kernel_init=_INIT, name=name,
                    dtype=dtype)


class ShortConv(nn.Module):
    """Depthwise causal convolution over time, then SiLU."""

    taps: int

    @nn.compact
    def __call__(self, x):                                   # [B, T, C]
        kernel = self.param("kernel", nn.initializers.normal(
            1.0 / math.sqrt(self.taps)), (self.taps, x.shape[-1]))
        padded = jnp.pad(x, ((0, 0), (self.taps - 1, 0), (0, 0)))
        length = x.shape[1]
        out = sum(padded[:, i:i + length] * kernel[i].astype(x.dtype)
                  for i in range(self.taps))
        return jax.nn.silu(out)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniformly in
    [0.001, 0.1] (fla's initialisation)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype) *
                 (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class KDAMixer(nn.Module):
    heads: int
    head_dim: int
    taps: int
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):                                   # [B, T, D]
        batch, length, hidden = x.shape
        heads, dim = self.heads, self.head_dim
        width = heads * dim

        def branch(name):
            y = _dense(width, f"{name}_proj", self.dtype)(x)
            return ShortConv(self.taps, name=f"{name}_conv")(y).reshape(
                batch, length, heads, dim)

        q, k, v = branch("q"), branch("k"), branch("v")

        def unit(a):
            a32 = a.astype(jnp.float32)
            return a32 * jax.lax.rsqrt(jnp.sum(a32 * a32, -1, keepdims=True) + 1e-6)

        q, k = unit(q) * dim ** -0.5, unit(k)
        a_log = self.param("A_log", _a_log_init, (heads,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (width,))
        gate = _dense(width, "f_b_proj", self.dtype)(
            _dense(dim, "f_a_proj", self.dtype)(x))
        gate = jax.nn.softplus(gate.astype(jnp.float32) + dt_bias).reshape(
            batch, length, heads, dim)
        g = -jnp.exp(a_log)[:, None] * gate
        beta = jax.nn.sigmoid(
            _dense(heads, "b_proj", self.dtype)(x).astype(jnp.float32))
        with jax.named_scope(scopes.KDA_SCAN):
            out, _ = chunk_kda(q, k, v, g, beta)
        out = RMSNorm(self.eps, name="o_norm")(out.astype(self.dtype))
        out_gate = _dense(width, "g_b_proj", self.dtype)(
            _dense(dim, "g_a_proj", self.dtype)(x))
        out = out * jax.nn.sigmoid(out_gate).reshape(batch, length, heads, dim)
        return _dense(hidden, "o_proj", self.dtype)(
            out.reshape(batch, length, width))


class MLAMixer(nn.Module):
    """Latent attention with ``q_lora_rank: null`` and no rotary."""

    heads: int
    nope_dim: int
    pe_dim: int
    v_dim: int
    kv_rank: int
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        batch, length, hidden = x.shape
        heads = self.heads
        q = _dense(heads * (self.nope_dim + self.pe_dim), "q_proj", self.dtype)(
            x).reshape(batch, length, heads, self.nope_dim + self.pe_dim)
        latent = _dense(self.kv_rank + self.pe_dim, "kv_a_proj", self.dtype)(x)
        k_pe = latent[..., self.kv_rank:]
        kv = _dense(heads * (self.nope_dim + self.v_dim), "kv_b_proj", self.dtype)(
            RMSNorm(self.eps, name="kv_a_norm")(latent[..., :self.kv_rank])
        ).reshape(batch, length, heads, self.nope_dim + self.v_dim)
        out = blocked_causal_attention(
            q[..., :self.nope_dim], kv[..., :self.nope_dim], kv[..., self.nope_dim:],
            q_shared=q[..., self.nope_dim:], k_shared=k_pe,
            scale=(self.nope_dim + self.pe_dim) ** -0.5)
        return _dense(hidden, "o_proj", self.dtype)(
            out.reshape(batch, length, heads * self.v_dim))


class SwiGLU(nn.Module):
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        gate = _dense(self.width, "gate_proj", self.dtype)(x)
        up = _dense(self.width, "up_proj", self.dtype)(x)
        return _dense(hidden, "down_proj", self.dtype)(jax.nn.silu(gate) * up)


class ExpertLayer(nn.Module):
    """The routed experts this chip holds, beside the shared expert."""

    experts: int
    held: int
    share: int
    top_k: int
    width: int
    shared: int
    scale: float
    renormalize: bool
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        batch, length, hidden = x.shape
        flat = x.reshape(batch * length, hidden)
        first = self.share * self.held
        with jax.named_scope(scopes.MOE_ROUTER):
            router = self.param("router", _INIT, (hidden, self.experts))
            # moves the choice, never the weight; no gradient reaches it
            bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                              (self.experts,))
            chosen, weights = moe.route(
                flat, router.astype(self.dtype), bias, top_k=self.top_k,
                scale=self.scale, renormalize=self.renormalize)
            self.sow(STEP_STATS, "load",
                     moe.assignment_counts(chosen, 0, self.experts))
            # for a caller that asks (``mutable=[ROUTING]``): which experts
            # each token chose, to hold a second computation to the same
            self.sow(ROUTING, "chosen", chosen.reshape(batch, length, self.top_k))
        shape = (self.held, hidden, self.width)
        w_gate = self.param("experts_gate", _INIT, shape)
        w_up = self.param("experts_up", _INIT, shape)
        w_down = self.param("experts_down", _INIT, (self.held, self.width, hidden))
        with jax.named_scope(scopes.MOE_EXPERTS):
            out = moe.held_experts(
                flat, chosen, weights.astype(self.dtype),
                w_gate.astype(self.dtype), w_up.astype(self.dtype),
                w_down.astype(self.dtype), first=first)
        out = out.reshape(batch, length, hidden)
        if self.shared:
            out = out + SwiGLU(self.width * self.shared, self.dtype,
                               name="shared_experts")(x)
        return out


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


class _Sizes:
    """The sizes a block needs, hashable so that ``nn.remat`` takes them."""

    def __init__(self, **sizes):
        self.__dict__.update(sizes)
        self._key = tuple(sorted((k, v) for k, v in sizes.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Sizes) and self._key == other._key

    def __repr__(self):
        return f"_Sizes{self._key}"


class KimiLinear(nn.Module):
    sizes: _Sizes
    remat: bool = True
    dtype: Any = jnp.float32

    #: what a step body makes mutable and hands to :meth:`after_step`
    step_collection = STEP_STATS

    def after_step(self, params, stats):
        """``(params, counts)`` after an optimizer step whose forward pass
        sowed `stats`: every expert layer's correction bias moved by the
        balancing rule, and per layer the assignments the held experts
        received, in all and the most loaded one's (float32 scalars, for
        the step's count sums)."""
        c = self.sizes
        first = c.expert_share * c.experts_held
        params, counts = dict(params), {}
        for layer, entry in sorted(stats.items()):
            load = sum(entry["moe"]["load"])
            held = load[first:first + c.experts_held].astype(jnp.float32)
            counts[f"moe_assigned/{layer}"] = jnp.sum(held)
            counts[f"moe_largest/{layer}"] = jnp.max(held)
            if c.bias_update_rate:
                layer_params = dict(params[layer])
                layer_params["moe"] = dict(
                    layer_params["moe"], e_score_correction_bias=moe.balance_bias(
                        layer_params["moe"]["e_score_correction_bias"], load,
                        c.bias_update_rate))
                params[layer] = layer_params
        return params, counts

    def publish_counts(self, rise: dict, registry) -> None:
        """What :meth:`after_step` counted, where the trainer has synced a
        stretch of steps' sums anyway (`rise`: the sums over the steps
        since it last published): the counter
        ``faa_moe_assignments_total{held,layer}`` and the gauge
        ``faa_moe_held_load_max_over_mean{layer}``, the most loaded held
        expert's assignments over the held experts' mean, over those
        steps."""
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
                        largest * self.sizes.experts_held / assigned)

    @nn.compact
    def __call__(self, ids, train: bool = False):
        """`ids` ``[B, T]`` int32 (below ``ids_held``) -> logits ``[B, T,
        ids_held]`` float32.  `train` changes nothing: the model has no
        dropout and no running statistics."""
        del train
        c = self.sizes
        if ids.dtype not in (jnp.int32, jnp.uint32, jnp.int64):
            ids = ids.astype(jnp.int32)  # an init sample may come as floats
        table = self.param("embed_tokens", _INIT, (c.ids_held, c.hidden))
        x = jnp.take(table, ids, axis=0).astype(self.dtype)
        block = nn.remat(Block) if self.remat else Block
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
    held = int(conf.get("experts_held") or experts)
    share = int(conf.get("expert_share") or 0)
    if experts % held or not 0 <= share < experts // held:
        raise ValueError(f"experts_held={held}, expert_share={share}: not a "
                         f"share of {experts} experts")
    if conf.get("q_lora_rank") is not None or not conf.get("mla_use_nope", False):
        raise ValueError("only q_lora_rank: null with mla_use_nope: true "
                         "(latent attention without rotary) is written down")
    if int(conf.get("num_expert_group", 1)) != 1 or int(conf.get("topk_group", 1)) != 1:
        raise ValueError("grouped top-k over more than one group is not "
                         "written down")
    if conf.get("moe_router_activation_func", "sigmoid") != "sigmoid":
        raise ValueError("only the sigmoid router is written down")
    if int(conf.get("moe_layer_freq", 1)) != 1:
        raise ValueError("moe_layer_freq other than 1 is not written down")
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
