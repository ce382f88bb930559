"""Nemotron-H's forward pass, its loss and gradients in plain ``jax.numpy``.

Written from the published description (``config.json`` of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type: nemotron_h``;
Mamba-2, arXiv:2405.21060, for the state-space layer its keys size;
DeepSeek-V3, arXiv:2412.19437, for the router its keys name), none of the
program's code: float32, every product at ``highest``, jitted as it
stands.  It reads the parameter tree the program checkpoints (the names
of ``models/nemotron_h.py``) and the configuration file's ``model`` block
(``flops/nemotron_h.py::model_from_conf`` plus what this chip holds:
``layers_held``, ``experts_held``, ``expert_share``, ``ids_held``).

A layer is one norm and one mixer, ``x <- x + mixer(RMSNorm(x))``, the
mixer by the layer's character of ``pattern``; a final RMSNorm, an untied
head.

``M``, Mamba-2: ``[z | xBC | dt] = x W_in``; ``xBC <- silu(conv(xBC) +
bias)``, the convolution causal and depthwise over ``conv_taps`` tokens
(zeros before the sequence); ``xBC`` split into ``x`` (a head's
``mamba_head_dim`` channels), ``B`` and ``C`` (a group's ``state_size``);
``Δ_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)`` a head; then the
recurrence itself, **token by token** (the program computes it in chunks:
``ops/ssd.py``), head ``h`` reading group ``h // (heads / groups)``::

    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t,   y_t = S_t C_t + D x_t,   S_0 = 0

the gated, grouped norm ``w * g(y * silu(z))``, ``g`` an RMS normalisation
over each group's channels on their own; ``W_out``.

``*``, attention: ``q = x W_q`` (``heads`` of ``head_dim``), ``k = x W_k``,
``v = x W_v`` (``kv_heads``), key-value head ``g`` serving the query heads
``[g n, (g + 1) n)``; one whole causal softmax of ``q . k / sqrt(head_dim)``;
``W_o``.  No position encoding, no biases.

``E``, experts: ``s = sigmoid(W_r x)`` over all experts, the ``top_k``
largest of ``s + bias`` chosen, weights ``s_e / sum_chosen s * scale``;
``y = sum over e chosen and held of w_e E_e(x) + E_shared(x)``, ``E(x) =
W_down relu(W_up x)^2``, a loop over the held experts.

Departures from the published description, each because the program
under test departs the same way or because it changes no number:

- the router's correction bias is a parameter that no gradient reaches
  (the program moves it by a load-balancing rule between steps:
  ``ops/moe.py::balance_bias``); the forward pass reads whatever value the
  checkpoint holds;
- what the experts this chip does not hold would add is left out
  (``model-configs`` guide, section 4), as the program leaves it out;
- to fit a chip's memory at 8,192 tokens, and only for that: the softmax is
  taken a block of queries at a time (each row still one whole softmax), the
  recurrence runs in segments of tokens, and :func:`loss_and_grads`
  recomputes a layer, a block of queries and a segment in its backward pass
  (``jax.checkpoint``), which changes no value.

**A comparison of logits has to survive a top-k** (``references/
kimi_linear.py`` says why): :func:`forward_given_routing` takes the
*system's* choices (``[B, T, top_k]`` an expert layer) in place of its own
top-k, computes weights, experts and everything else itself, and says how
far those choices are from its own: the largest amount by which a chosen
expert's ``score + bias`` lies under the reference's own ``top_k``-th
largest.  `kept` (``[held]`` zeros and ones a layer) drops held experts
without another compilation.

**Controls by the `model` dict**, each another function that the
benchmark's comparison must refuse: ``layers_held`` smaller stops short;
``control`` names one of ``no_d_skip`` (``y_t = S_t C_t``), ``no_gate``
(the grouped norm of ``y`` alone), ``no_conv_bias``, ``rotary`` (queries
and keys turned by position, theta ``rope_theta``: what the family does
*not* do), ``one_kv_head`` (key-value head 0 serving every query head).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 256   # queries a softmax block
_SEGMENT = 256       # tokens of the recurrence a checkpointed segment
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _recurrence(x, step, a, b, c, skip):
    """`x` ``[T, H, P]``, `step` ``[T, H]``, `a`, `skip` ``[H]``, `b`, `c`
    ``[T, H, N]`` (a group's, already a head's) -> ``y [T, H, P]``."""
    length = x.shape[0]
    size = _SEGMENT if length % _SEGMENT == 0 else length

    def token(state, at):
        x_t, step_t, b_t, c_t = at
        state = (jnp.exp(step_t * a)[:, None, None] * state
                 + (step_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], -1) + skip[:, None] * x_t

    @jax.checkpoint
    def segment(state, tokens):
        return jax.lax.scan(token, state, tokens)

    state = jnp.zeros(x.shape[1:] + (b.shape[-1],), jnp.float32)
    _, y = jax.lax.scan(segment, state, tuple(
        arr.reshape((length // size, size) + arr.shape[1:]) for arr in (x, step, b, c)))
    return y.reshape(x.shape)


def _mamba(x, p, model):
    heads, width = int(model["mamba_heads"]), int(model["mamba_head_dim"])
    groups, size = int(model["mamba_groups"]), int(model["state_size"])
    taps, control = int(model["conv_taps"]), model.get("control")
    inner, length = heads * width, x.shape[0]
    joined = x @ p["in_proj"]["kernel"]
    z = joined[:, :inner]
    xbc = joined[:, inner:2 * inner + 2 * groups * size]
    dt = joined[:, 2 * inner + 2 * groups * size:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc])
    # the last tap meets the token itself
    conv = sum(padded[i:i + length] * p["conv_kernel"][i] for i in range(taps))
    if control != "no_conv_bias":
        conv = conv + p["conv_bias"]
    xbc = jax.nn.silu(conv)
    per_group = heads // groups
    b, c = (jnp.repeat(part.reshape(length, groups, size), per_group, axis=1)
            for part in (xbc[:, inner:inner + groups * size],
                         xbc[:, inner + groups * size:]))
    skip = jnp.zeros_like(p["D"]) if control == "no_d_skip" else p["D"]
    y = _recurrence(xbc[:, :inner].reshape(length, heads, width),
                    jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                    b, c, skip).reshape(length, inner)
    if control != "no_gate":
        y = y * jax.nn.silu(z)
    y = y.reshape(length, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + float(model["eps"]))
    return (y.reshape(length, inner) * p["norm_weight"]) @ p["out_proj"]["kernel"]


def _rotate(x, theta):
    """A control: `x` ``[T, ..., d]``, the pair ``(x[2i], x[2i + 1])`` of
    token ``t`` turned by ``t * theta^(-2i / d)``."""
    length, dim = x.shape[0], x.shape[-1]
    angle = (jnp.arange(length, dtype=jnp.float32)[:, None]
             * theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)[None, :])
    angle = angle.reshape((length,) + (1,) * (x.ndim - 2) + (dim // 2,))
    pairs = x.reshape(x.shape[:-1] + (dim // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                      even * jnp.sin(angle) + odd * jnp.cos(angle)], -1).reshape(x.shape)


def _attention(x, p, model):
    heads, kv_heads, dim = (int(model[k]) for k in ("heads", "kv_heads", "head_dim"))
    length, control = x.shape[0], model.get("control")
    q = (x @ p["q_proj"]["kernel"]).reshape(length, kv_heads, heads // kv_heads, dim)
    k = (x @ p["k_proj"]["kernel"]).reshape(length, kv_heads, dim)
    v = (x @ p["v_proj"]["kernel"]).reshape(length, kv_heads, dim)
    if control == "rotary":
        q, k = (_rotate(a, float(model.get("rope_theta", 10000.0))) for a in (q, k))
    if control == "one_kv_head":
        k, v = (jnp.broadcast_to(a[:, :1], a.shape) for a in (k, v))
    size = _QUERY_BLOCK if length % _QUERY_BLOCK == 0 else length

    @jax.checkpoint
    def block(args):
        q_block, first = args
        scores = jnp.einsum("qgnd,kgd->gnqk", q_block, k) * dim ** -0.5
        rows = first + jnp.arange(size)[:, None]
        scores = jnp.where(rows >= jnp.arange(length)[None, :], scores, -jnp.inf)
        return jnp.einsum("gnqk,kgd->qgnd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(block, (q.reshape((length // size, size) + q.shape[1:]),
                              jnp.arange(0, length, size)))
    return out.reshape(length, heads * dim) @ p["o_proj"]["kernel"]


def _relu2(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


def _experts(x, p, model, given=None, kept=None):
    """``(output, margin)``; `given` ``[T, top_k]`` replaces the layer's
    own choice of experts (module docstring), `kept` ``[held]`` weighs the
    held experts by zero or one."""
    held = p["experts_up"].shape[0]
    first = int(model.get("expert_share") or 0) * held
    top_k = int(model["top_k"])
    scores = jax.nn.sigmoid(x @ p["router"])
    biased = scores + p["e_score_correction_bias"]
    kth, chosen = jax.lax.top_k(biased, top_k)
    margin = jnp.float32(0.0)
    if given is not None:
        chosen = given
        margin = jnp.max(kth[:, -1] - jnp.min(
            jnp.take_along_axis(biased, chosen, -1), -1))
    weights = jnp.take_along_axis(scores, chosen, -1)
    if model.get("renormalize", True):
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    weights = weights * float(model["routed_scale"])
    keep = jnp.ones(held, x.dtype) if kept is None else kept

    def one(out, expert):   # one held expert a step: one body to compile
        up, down, j, keep = expert
        weight = keep * jnp.sum(jnp.where(chosen == first + j, weights, 0.0), -1)
        return out + weight[:, None] * _relu2(x, up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_up"], p["experts_down"], jnp.arange(held), keep))
    if "shared_experts" in p:
        shared = p["shared_experts"]
        out = out + _relu2(x, shared["up_proj"]["kernel"], shared["down_proj"]["kernel"])
    return out, margin


def _layer(x, p, kind, model, given=None, kept=None):
    normed = _rms(x, p["norm"]["weight"], float(model["eps"]))
    if kind == MAMBA:
        return x + _mamba(normed, p["mamba"], model), jnp.float32(0.0)
    if kind == ATTENTION:
        return x + _attention(normed, p["attn"], model), jnp.float32(0.0)
    if kind != EXPERTS:
        raise ValueError(f"unknown layer kind {kind!r} in the pattern")
    out, margin = _experts(normed, p["moe"], model, given, kept)
    return x + out, margin


def _held_kinds(model) -> str:
    return str(model["pattern"])[:int(model["layers_held"])]


def _sequence_logits(params, ids, model, remat: bool, routing=None, kept=None):
    """``(logits [T, ids_held], margin)``; `routing` and `kept` by layer
    name, for the expert layers (:func:`_experts`)."""
    routing, kept = routing or {}, kept or {}
    x = params["embed_tokens"][ids]
    margins = [jnp.float32(0.0)]
    for index, kind in enumerate(_held_kinds(model), start=1):
        name = f"layer{index}"
        fn = functools.partial(_layer, kind=kind, model=model)
        x, margin = (jax.checkpoint(fn) if remat else fn)(
            x, params[name], given=routing.get(name), kept=kept.get(name))
        margins.append(margin)
    logits = (_rms(x, params["norm"]["weight"], float(model["eps"]))
              @ params["lm_head"]["kernel"])
    return logits, jnp.max(jnp.stack(margins))


class _Frozen(dict):
    """The `model` block as a hashable static argument of ``jit``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


@functools.partial(jax.jit, static_argnames=("model",))
def _logits(params, ids, model):
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda row: _sequence_logits(params, row, model, remat=False)[0], ids)


@functools.partial(jax.jit, static_argnames=("model",))
def _logits_given_routing(params, ids, routing, kept, model):
    with jax.default_matmul_precision("highest"):
        logits, margins = jax.lax.map(
            lambda args: _sequence_logits(params, args[0], model, remat=False,
                                          routing=args[1], kept=kept),
            (ids, routing))
    return logits, jnp.max(margins)


@functools.partial(jax.jit, static_argnames=("model",))
def _loss_and_grads(params, ids, model):
    def loss(params):
        def one(row):
            logits, _ = _sequence_logits(params, row[:-1], model, remat=True)
            picked = jnp.take_along_axis(logits, row[1:, None], -1)[:, 0]
            return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)

        return jnp.mean(jax.lax.map(one, ids))

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params)


def _float32(tree):
    return jax.tree.map(lambda a: jnp.asarray(np.asarray(a), jnp.float32), tree)


def _ids(ids):
    return jnp.asarray(np.asarray(ids), jnp.int32)


def forward(params, batch_stats, ids, model):
    """Logits ``[B, T, ids_held]`` for `ids` ``[B, T]`` (a sequence at a
    time), each layer's own choice of experts.  `batch_stats` is empty: the
    model has none."""
    del batch_stats
    return np.asarray(_logits(_float32(params), _ids(ids), _Frozen(model)))


def expert_layers(model) -> list[str]:
    """The names of the expert layers this chip holds."""
    return [f"layer{index}" for index, kind in enumerate(_held_kinds(model), start=1)
            if kind == EXPERTS]


def _whole(routing, kept, held: int):
    """`routing` as int32 and `kept` (every held expert where None) as
    float32 ``[held]`` arrays by layer name."""
    routing = {k: jnp.asarray(np.asarray(v), jnp.int32) for k, v in routing.items()}
    kept = kept or {}
    return routing, {k: jnp.broadcast_to(jnp.asarray(
        np.asarray(kept.get(k, 1.0)), jnp.float32), (held,)) for k in routing}


def forward_given_routing(params, ids, model, routing, kept=None):
    """``(logits [B, T, ids_held], margin)`` with every expert layer's
    choice of experts given (`routing`: ``{layer name: [B, T, top_k]}``, the
    system's) and, for a control, `kept` (``{layer name: [held]}`` zeros and
    ones; absent: all held experts).  Module docstring."""
    held = int(model.get("experts_held") or model["experts"])
    routing, kept = _whole(routing, kept, held)
    logits, margin = _logits_given_routing(
        _float32(params), _ids(ids), routing, kept, _Frozen(model))
    return np.asarray(logits), float(margin)


def compile_forward_given_routing(params, ids, model):
    """:func:`forward_given_routing` compiled ahead from shapes (`params`
    and `ids` arrays or ``jax.ShapeDtypeStruct`` s): ``(params, ids,
    routing, kept=None) -> (logits, margin)``."""
    held = int(model.get("experts_held") or model["experts"])
    layers = expert_layers(model)
    chosen = jax.ShapeDtypeStruct(tuple(ids.shape) + (int(model["top_k"]),), jnp.int32)
    compiled = _logits_given_routing.lower(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params),
        jax.ShapeDtypeStruct(ids.shape, jnp.int32), {k: chosen for k in layers},
        {k: jax.ShapeDtypeStruct((held,), jnp.float32) for k in layers},
        _Frozen(model)).compile()

    def run(params, ids, routing, kept=None):
        routing, kept = _whole(routing, kept, held)
        logits, margin = compiled(_float32(params), _ids(ids), routing, kept)
        return np.asarray(logits), float(margin)

    return run


def loss_and_grads(params, ids, model):
    """``(loss, grads)``: the mean next-token cross-entropy of `ids` ``[B,
    T + 1]`` (inputs ``ids[:, :-1]``, targets ``ids[:, 1:]``) and its
    gradient in the parameters' own tree."""
    loss, grads = _loss_and_grads(_float32(params), _ids(ids), _Frozen(model))
    return float(loss), jax.device_get(grads)
