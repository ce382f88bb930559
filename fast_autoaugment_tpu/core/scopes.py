"""The names the step program gives its work on the device.

``jax.named_scope`` writes a name into the ``op_name`` metadata of every
HLO instruction traced under it; the metadata survives XLA's
optimisation (on fusion instructions too) and comes back out of
``compiled.as_text()``, where ``core/compilecache.py::scope_map`` reads
it.  A scope is compile-time metadata: it costs nothing per step,
changes no numerics and no compile-cache key.  This module is the one
table of those names, and the three functions that find them and the
pass they belong to again in an ``op_name`` such as::

    jit(multi_fn)/vmap(faa_aug_policy)/faa_aug_op_Equalize/sort
    jit(multi_fn)/transpose(jvp(faa_model))/layer3_1/conv1/conv_general_dilated

The ``faa_`` prefix keeps them apart from the flax module paths
(``layer3_1/conv1``) in the same string.  Which metric reads which scope
is in docs/OBSERVABILITY.md ("Named scopes on the device").
"""

from __future__ import annotations

import re

__all__ = [
    "PREFIX",
    "BATCH_GATHER",
    "AUG_POLICY",
    "AUG_OP_PREFIX",
    "AUG_WARP",
    "AUG_FIXED",
    "AUG_JITTER",
    "AUG_LIGHTING",
    "MODEL",
    "RESNET_STEM",
    "SHAKE_MIX",
    "SHAKE_SHORTCUT",
    "KDA",
    "KDA_SCAN",
    "MLA",
    "MLA_ATTENTION",
    "MOE",
    "MOE_ROUTER",
    "MOE_EXPERTS",
    "MOE_COMBINE",
    "LM_HEAD",
    "MTP",
    "MAMBA2",
    "SSD_SCAN",
    "GQA",
    "SWA",
    "GQA_ATTENTION",
    "SHORT_CONV",
    "SHORT_CONV_GATE",
    "MIXER_PROJ",
    "LOSS",
    "OPTIMIZER",
    "EMA",
    "METRICS",
    "aug_op",
    "scope_of",
    "is_backward",
    "pass_of",
    "PASSES",
]

PREFIX = "faa_"

#: ``train/steps.py``: a batch taken from the device cache's stored rows
#: (``data.pipeline.StoredRows.take``) and its labels
BATCH_GATHER = "faa_batch_gather"
#: ``ops/preprocess.py``: sub-policy draw, gates, the switch and its select
AUG_POLICY = "faa_aug_policy"
#: ``ops/augment.py::_call_op``: one operation's branch, ``faa_aug_op_<Name>``
AUG_OP_PREFIX = "faa_aug_op_"
#: ``ops/augment.py::_warp_affine_nearest``, nested in ``faa_aug_policy``:
#: the resampling of the seven affine operations, once an op slot; their
#: own ``faa_aug_op_<Name>`` scopes hold a 2x3 matrix each
AUG_WARP = "faa_aug_warp"
#: ``ops/preprocess.py``: crop, flip, normalize, cutout: what every recipe pays;
#: ``ops/preprocess_imagenet.py``: the ImageNet per-image stack outside the
#: policy (flip, ColorJitter, Lighting, normalize, cutout)
AUG_FIXED = "faa_aug_fixed"
#: ``ops/preprocess_imagenet.py``, both nested under ``faa_aug_fixed``:
#: ``_color_jitter`` (brightness, contrast and saturation in a drawn
#: order) and ``_lighting`` (the PCA noise)
AUG_JITTER = "faa_aug_jitter"
AUG_LIGHTING = "faa_aug_lighting"
#: ``train/steps.py::loss_fn``: forward under ``jvp(...)``, backward
#: under ``transpose(jvp(...))``
MODEL = "faa_model"
#: ``models/shake_resnet.py``, both nested under ``faa_model``: the noise
#: draw and the mix of a block's two branches (``_ShakeMix``; its backward
#: instructions come out of ``ops/shake.py``'s ``custom_vjp`` rule, not out
#: of a transpose), and the two-path strided ``Shortcut``
SHAKE_MIX = "faa_shake_mix"
SHAKE_SHORTCUT = "faa_shake_shortcut"
#: ``models/resnet.py``, nested under ``faa_model``: the ImageNet stem
#: (7x7 stride-2 convolution, BatchNorm, ReLU, 3x3 stride-2 max-pool)
RESNET_STEM = "faa_resnet_stem"
#: the token models (``models/kimi_linear.py``, ``models/glm4_moe_lite.py``,
#: ``models/nemotron_h.py``, ``models/afmoe.py``, ``models/lfm2_moe.py``,
#: ``models/token_blocks.py``), all nested under
#: ``faa_model``: the KDA mixer
#: (projections, short convolutions, gates, output norm and gate) with the
#: chunked delta-rule recurrence alone inside it (``ops/kda.py``, forward
#: and backward); the latent-attention mixer, with its attention core alone
#: inside it (``ops/attention.py::blocked_causal_attention``: at the
#: configurations' shapes the fused kernels ``mla_attention_forward`` and,
#: under ``transpose(jvp(...))``, ``mla_attention_backward`` with the XLA
#: operations that lay their operands out, else scores, softmax and
#: weighted sum a block of queries at a time; forward, backward and what
#: ``nn.remat`` computes again); an
#: expert layer (its shared expert included) with the router (scores, top-k,
#: every expert's load) and the held experts' part (the assignments sorted
#: by expert, a loop over the blocks of rows the routing filled: gather,
#: its products, the weighted rows into the sum; forward and backward) inside it;
#: the output head's product; a multi-token-prediction module (its two
#: norms, ``eh_proj`` and its block, whose own ``faa_mla`` and ``faa_moe``
#: nest inside it; its head and loss stay ``faa_lm_head`` and ``faa_loss``)
KDA = "faa_kda"
KDA_SCAN = "faa_kda_scan"
MLA = "faa_mla"
MLA_ATTENTION = "faa_mla_attention"
MOE = "faa_moe"
MOE_ROUTER = "faa_moe_router"
MOE_EXPERTS = "faa_moe_experts"
#: ``ops/moe.py::_combine``, nested under ``faa_moe_experts``: the kernel
#: ``moe_combine`` alone, a block's rows added into the ``[tokens, 1,
#: hidden]`` sum (forward, what ``nn.remat`` computes again, and ``d_x``)
MOE_COMBINE = "faa_moe_combine"
LM_HEAD = "faa_lm_head"
MTP = "faa_mtp"
#: ``models/nemotron_h.py``, nested under ``faa_model``: a Mamba-2 mixer
#: (``in_proj``, the causal convolution, the step's softplus, the gated
#: grouped norm, ``out_proj``) with the chunked state-space scan alone
#: inside it (``ops/ssd.py::chunk_ssd``, forward and backward); and a
#: grouped-query attention mixer whole (its projections and the causal
#: softmax: the fused kernels of ``ops/attention.py`` with the key-value
#: heads repeated in front of them; ``models/afmoe.py``'s mixers too, with
#: their norms a head, rotary and gate; ``faa_mla_attention`` stays the
#: latent-attention cores', a grouped-query core's scope is
#: ``faa_gqa_attention`` below)
MAMBA2 = "faa_mamba2"
SSD_SCAN = "faa_ssd_scan"
GQA = "faa_gqa"
#: ``models/token_blocks.py::GQAMixer`` (``models/afmoe.py``'s mixers and
#: ``models/nemotron_h.py``'s), nested under ``faa_gqa``: a mixer whose key
#: span is shorter than the whole past (a window layer), whole; and, in
#: every grouped-query mixer, the attention core alone
#: (``ops/attention.py::blocked_causal_attention``'s call: the fused kernels
#: with the XLA operations that repeat the key-value heads and round the
#: operands in front of them; forward, backward and what ``nn.remat``
#: computes again)
SWA = "faa_swa"
GQA_ATTENTION = "faa_gqa_attention"
#: ``models/lfm2_moe.py`` round ``models/token_blocks.py::ShortConvMixer``,
#: nested under ``faa_model``: a short-convolution mixer whole (``in_proj``,
#: the two gates, the taps, ``out_proj``; forward, backward and what
#: ``nn.remat`` computes again) and, inside it, what lies between its two
#: projections alone (the split, ``B * z``, the depthwise causal taps, ``C *
#: c`` and their cotangents: memory-bound elementwise work over ``[T, 3
#: hidden]``)
SHORT_CONV = "faa_short_conv"
SHORT_CONV_GATE = "faa_short_conv_gate"
#: ``models/token_blocks.py::proj``, always nested in a mixer's scope
#: (``faa_mla``, ``faa_gqa``, ``faa_short_conv``, ``faa_kda``,
#: ``faa_mamba2``): the mixers' products with a weight matrix alone (the
#: ``nn.Dense`` calls ``q_proj`` .. ``o_proj``, ``in_proj`` / ``out_proj``,
#: the low-rank pairs; forward, backward and what ``nn.remat`` computes
#: again), so that a mixer's scope less its core's less this one is what is
#: neither kernel nor product: norms, rotary, gates, taps, casts, reshapes
MIXER_PROJ = "faa_mixer_proj"
LOSS = "faa_loss"
#: ``train/steps.py::step_fn``: update and parameter add; EMA; top-k and sums
OPTIMIZER = "faa_optimizer"
EMA = "faa_ema"
METRICS = "faa_metrics"

#: the three passes :func:`pass_of` tells apart
PASSES = ("forward", "recompute", "backward")

_SCOPE = re.compile(r"faa_\w+")
# the path component jax.checkpoint (``nn.remat``) puts over what its
# backward pass computes again of the forward one
_REMATTED = re.compile(r"(?:^|/)rematted_computation(?:/|$)")
# a scope that jax.grad transposed: transpose(jvp(faa_model)), and
# transpose(jvp(vmap(faa_...))) where a batching rule sits between
_TRANSPOSED = re.compile(r"transpose\((?:\w+\()*faa_")


def aug_op(name: str) -> str:
    """The scope of one augmentation operation (`name` from
    ``ops.augment.OP_NAMES``)."""
    return AUG_OP_PREFIX + name


def _scoped_path(op_name: str) -> str:
    """XLA joins the names of instructions it merged with ``;``: the
    first of them that carries a scope speaks for the instruction."""
    for path in op_name.split(";"):
        if PREFIX in path:
            return path
    return ""


def scope_of(op_name: str) -> tuple[str, ...]:
    """The chain of ``faa_`` scopes in an ``op_name``, outermost first;
    empty where the instruction was traced under none."""
    return tuple(_SCOPE.findall(_scoped_path(op_name)))


def is_backward(op_name: str) -> bool:
    """True where the instruction's scope sits under ``transpose(``: the
    backward pass of what ``jvp(<scope>)`` names in the forward one."""
    return bool(_TRANSPOSED.search(_scoped_path(op_name)))


def pass_of(op_name: str) -> str:
    """The pass an instruction belongs to, one of :data:`PASSES`:
    ``recompute`` where its scoped path holds JAX's own
    ``rematted_computation`` component (the forward pass that ``nn.remat``
    runs a second time, which sits under ``transpose(`` and so is
    :func:`is_backward` too), else ``backward`` where :func:`is_backward`,
    else ``forward``.
    The three shapes, one each from the recorded step of
    ``lfm2_8b_a1b_train`` (``benchmarks/testdata/v5e_lfm2_moe_step_scopes.json``),
    ``<hidden>`` standing for ``Lfm2Moe.loss_terms/Lfm2Moe._hidden``::

        forward    jit(multi_fn)/jvp(faa_model)/<hidden>/layer6/faa_short_conv/conv/in_proj/dot_general
        recompute  jit(multi_fn)/transpose(jvp(faa_model))/<hidden>/jvp(faa_model)/<hidden>/checkpoint/rematted_computation/layer2/faa_short_conv/conv/in_proj/dot_general
        backward   jit(multi_fn)/transpose(jvp(faa_model))/<hidden>/jvp(faa_model)/<hidden>/checkpoint/layer2/faa_short_conv/conv/out_proj/dot_general
    """
    path = _scoped_path(op_name)
    if _REMATTED.search(path):
        return "recompute"
    return "backward" if _TRANSPOSED.search(path) else "forward"
