"""Nemotron-H (``model_type: nemotron_h``; ``config.json`` of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16) as a Flax module: token ids in,
next-token logits out.

A *layer* is one norm and one mixer, ``x <- x + mixer(RMSNorm(x))``, with
no second sub-layer; which mixer, a character of ``hybrid_override_pattern``
says, layer by layer:

``M``  Mamba-2 (arXiv:2405.21060).  ``in_proj: H -> [z | xBC | dt]``
       (``heads * head_dim``, that plus ``2 * n_groups * ssm_state_size``,
       ``heads``); a causal depthwise convolution of ``conv_kernel`` taps
       with a bias over ``xBC`` (zeros before the sequence), then SiLU;
       ``xBC`` split into ``x`` (a head's ``head_dim`` channels), ``B`` and
       ``C`` (a group's ``ssm_state_size``; head ``h`` reads group ``h //
       (heads / n_groups)``); ``Δ = softplus(dt + dt_bias)`` and ``A =
       -exp(A_log)`` a head; the state-space scan with its ``D`` skip
       (``ops/ssd.py``, chunks of ``chunk_size``: a pair of fused kernels
       at the published shapes, which read ``x``, ``B`` and ``C`` as the
       rows sliced here, the ``jnp`` form at any other); the gated,
       grouped norm ``w * g(y * silu(z))``, ``g`` an RMS normalisation
       over each of the ``n_groups`` groups of channels on their own (gate
       first, then norm); ``out_proj``.
``*``  grouped-query attention: ``q_proj`` to ``num_attention_heads`` heads
       of ``head_dim``, ``k_proj`` and ``v_proj`` to ``num_key_value_heads``,
       key-value head ``g`` serving the query heads ``[g * n, (g + 1) * n)``,
       one causal softmax at ``head_dim ** -0.5``, ``o_proj``.  **No
       position encoding**: the family's layers take their order from the
       Mamba-2 layers around them.  ``models/token_blocks.py::GQAMixer``
       with none of its optional parts; the core is ``ops/attention.py``'s
       (the fused kernels at the configuration's shapes), given the
       key-value heads repeated to every query head: the repeat's transpose
       sums a group's gradient.
``E``  a sigmoid-routed expert layer beside one shared expert
       (``models/token_blocks.py::ExpertLayer``, ``ops/moe.py``) whose
       experts are two matrices and a squared ReLU (``mlp_hidden_act:
       relu2``), the shared one at a width of its own
       (``moe_shared_expert_intermediate_size``).
``-``  a dense feed-forward: the family allows it, this file does not
       write it down and refuses it by name.

Then a final RMSNorm and an untied head.  What a step body calls
(``train/steps.py::make_token_step_body``): :meth:`NemotronH.loss_terms`,
the head's product and cross-entropy a block of positions at a time
(``ops/lm_head.py``), and :meth:`NemotronH.after_step` /
:meth:`NemotronH.publish_counts`, the routers' rule between steps.

The sizes are the published ``config.json``'s keys, handed over as the
conf's ``model`` mapping (:func:`nemotron_h_from_conf`) with the three keys
of ``token_blocks.CUT_KEYS``; ``layers_held`` counts layers of the pattern
(the first n characters).  The parameter tree: ``embed_tokens``,
``layer1`` .. ``layer<n>`` (``norm`` and one of ``mamba``, ``attn``,
``moe``), ``norm``, ``lm_head/kernel``.
"""

from __future__ import annotations

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
    balance_routers,
    causal_conv,
    expert_share_of,
    proj,
    publish_router_counts,
    refuse_unwritten_routing,
    remat_block,
    step_bias_init,
)
from fast_autoaugment_tpu.ops.lm_head import blocked_next_token_sums
from fast_autoaugment_tpu.ops.ssd import chunk_ssd

__all__ = ["NemotronH", "nemotron_h_from_conf", "causal_conv", "gated_group_norm",
           "STEP_STATS", "ROUTING", "MAMBA", "ATTENTION", "EXPERTS"]

#: the pattern's characters
MAMBA, ATTENTION, EXPERTS, DENSE = "M", "*", "E", "-"


def gated_group_norm(y, z, weight, groups: int, eps: float):
    """``weight * g(y * silu(z))``: the gate first, then an RMS
    normalisation over each of `groups` groups of the last axis' channels
    on their own.  Group by group on slices of the rows as they lie (a
    group's channels are adjacent lanes): the factor's way back over its
    group is then a broadcast along the lanes inside the fusion, where a
    ``[..., groups, channels]`` view of rows is another tiling and XLA
    wrote the broadcast factor and the view back out whole (PERF.md
    section 6, PR 43)."""
    gated = (y * jax.nn.silu(z)).astype(jnp.float32)
    normed = [part * jax.lax.rsqrt(jnp.mean(part * part, -1, keepdims=True) + eps)
              for part in jnp.split(gated, groups, axis=-1)]
    return (jnp.concatenate(normed, -1) * weight).astype(y.dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    del key
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


class Mamba2Mixer(nn.Module):
    conf: Any            # the hashable view NemotronH makes of its sizes
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):                                   # [B, T, D]
        c = self.conf
        batch, length, hidden = x.shape
        inner = c.mamba_heads * c.mamba_head_dim
        state = c.mamba_groups * c.state_size
        joined = proj(x, 2 * inner + 2 * state + c.mamba_heads, "in_proj",
                      self.dtype)
        z, xbc, dt = (joined[..., :inner], joined[..., inner:2 * inner + 2 * state],
                      joined[..., 2 * inner + 2 * state:])
        taps = self.param("conv_kernel", nn.initializers.normal(
            1.0 / math.sqrt(c.conv_taps)), (c.conv_taps, inner + 2 * state))
        bias = self.param("conv_bias", nn.initializers.zeros, (inner + 2 * state,))
        xbc = jax.nn.silu(causal_conv(xbc, taps, bias))
        a_log = self.param("A_log", _a_log_init, (c.mamba_heads,))
        skip = self.param("D", nn.initializers.ones, (c.mamba_heads,))
        dt_bias = self.param("dt_bias", step_bias_init(
            c.dt_min, c.dt_max, c.dt_floor), (c.mamba_heads,))
        step = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        groups = (batch, length, c.mamba_groups, c.state_size)
        with jax.named_scope(scopes.SSD_SCAN):
            y = chunk_ssd(
                xbc[..., :inner].reshape(batch, length, c.mamba_heads,
                                         c.mamba_head_dim),
                step, -jnp.exp(a_log), xbc[..., inner:inner + state].reshape(groups),
                xbc[..., inner + state:].reshape(groups), skip, chunk=c.chunk)
        y = y.reshape(batch, length, inner).astype(self.dtype)
        weight = self.param("norm_weight", nn.initializers.ones, (inner,))
        y = gated_group_norm(y, z, weight, c.mamba_groups, c.eps)
        # rescale_prenorm_residual: the residual stream's writers start small
        out_init = nn.initializers.normal(0.02 / math.sqrt(c.layers))
        return proj(y, hidden, "out_proj", self.dtype,
                    kernel_init=out_init)


class Layer(nn.Module):
    conf: Any
    kind: str            # a character of the pattern
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.conf
        normed = RMSNorm(c.eps, name="norm")(x)
        if self.kind == MAMBA:
            with jax.named_scope(scopes.MAMBA2):
                return x + Mamba2Mixer(c, self.dtype, name="mamba")(normed)
        if self.kind == ATTENTION:
            with jax.named_scope(scopes.GQA):
                return x + GQAMixer(c.heads, c.kv_heads, c.head_dim, self.dtype,
                                    name="attn")(normed)
        with jax.named_scope(scopes.MOE):
            return x + ExpertLayer(
                c.experts, c.experts_held, c.expert_share, c.top_k, c.expert_width,
                c.shared_experts, c.routed_scale, c.renormalize, self.dtype,
                form="relu2", shared_width=c.shared_width, name="moe")(normed)


class NemotronH(nn.Module):
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
        layer = remat_block(Layer) if self.remat else Layer
        for index, kind in enumerate(c.pattern[:c.layers_held], start=1):
            x = layer(c, kind, self.dtype, name=f"layer{index}")(x)
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


def nemotron_h_from_conf(conf: Any, dtype=jnp.float32) -> NemotronH:
    """The module for a conf ``model`` mapping that holds the published
    ``config.json``'s keys (and, merged in by the caller, the three keys
    of ``token_blocks.CUT_KEYS``)."""
    pattern = str(conf["hybrid_override_pattern"])
    layers = int(conf["num_hidden_layers"])
    experts = int(conf["n_routed_experts"])
    ids = int(conf["vocab_size"])
    if len(pattern) != layers:
        raise ValueError(f"hybrid_override_pattern has {len(pattern)} layers, "
                         f"num_hidden_layers says {layers}")
    if DENSE in pattern:
        raise ValueError("a dense feed-forward layer ('-' in "
                         "hybrid_override_pattern) is not written down")
    unknown = set(pattern) - {MAMBA, ATTENTION, EXPERTS}
    if unknown:
        raise ValueError(f"hybrid_override_pattern: unknown layer kinds "
                         f"{sorted(unknown)} (have M, E, *)")
    held, share = expert_share_of(conf, experts)
    refuse_unwritten_routing(int(conf.get("n_group", 1)),
                             int(conf.get("topk_group", 1)), "sigmoid")
    if conf.get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError("this family's experts are two matrices and a squared "
                         f"ReLU: mlp_hidden_act {conf.get('mlp_hidden_act')!r} "
                         "is not written down")
    for key in ("mamba_proj_bias", "use_bias", "attention_bias", "mlp_bias"):
        if conf.get(key, False):
            raise ValueError(f"{key}: true is not written down")
    if not conf.get("use_conv_bias", True):
        raise ValueError("use_conv_bias: false is not written down")
    if conf.get("sliding_window") is not None:
        raise ValueError("a sliding_window is not written down: the attention "
                         "layers see the whole sequence")
    low, high = conf.get("time_step_limit") or (0.0, float("inf"))
    if float(low) > 0.0 or float(high) != float("inf"):
        raise ValueError("a clamp on the step (time_step_limit other than "
                         "(0, inf)) is not written down")
    if conf.get("tie_word_embeddings", False):
        raise ValueError("tie_word_embeddings: true is not written down")
    heads, kv_heads = int(conf["num_attention_heads"]), int(conf["num_key_value_heads"])
    mamba_heads, groups = int(conf["mamba_num_heads"]), int(conf["n_groups"])
    if heads % kv_heads or mamba_heads % groups:
        raise ValueError(f"{heads} query heads over {kv_heads} key-value heads, "
                         f"or {mamba_heads} Mamba-2 heads over {groups} groups: "
                         "no whole number a group")
    sizes = Sizes(
        hidden=int(conf["hidden_size"]), eps=float(conf["layer_norm_epsilon"]),
        pattern=pattern, layers=layers,
        layers_held=int(conf.get("layers_held") or layers),
        ids_held=int(conf.get("ids_held") or ids),
        mamba_heads=mamba_heads, mamba_head_dim=int(conf["mamba_head_dim"]),
        mamba_groups=groups, state_size=int(conf["ssm_state_size"]),
        conv_taps=int(conf["conv_kernel"]), chunk=int(conf["chunk_size"]),
        dt_min=float(conf.get("time_step_min", 0.001)),
        dt_max=float(conf.get("time_step_max", 0.1)),
        dt_floor=float(conf.get("time_step_floor", 1e-4)),
        heads=heads, kv_heads=kv_heads, head_dim=int(conf["head_dim"]),
        experts=experts, experts_held=held, expert_share=share,
        top_k=int(conf["num_experts_per_tok"]),
        expert_width=int(conf["moe_intermediate_size"]),
        shared_experts=int(conf["n_shared_experts"]),
        shared_width=int(conf["moe_shared_expert_intermediate_size"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        renormalize=bool(conf.get("norm_topk_prob", True)),
        bias_update_rate=float(conf.get("router_bias_update_rate") or 0.0))
    if not 1 <= sizes.layers_held <= layers or not 1 <= sizes.ids_held <= ids:
        raise ValueError(f"layers_held={sizes.layers_held}, ids_held="
                         f"{sizes.ids_held}: more than the model has")
    return NemotronH(sizes, remat=bool(conf.get("remat", True)), dtype=dtype)
