"""lfm2_moe's forward pass, its loss and gradients in plain ``jax.numpy``.

Written from the published description (``config.json`` of
LiquidAI/LFM2-8B-A1B, ``model_type: lfm2_moe``; the family's public
modelling code as recalled, for the points of a block's form that are no
key of the config: the configuration file's ``assumed``), none of the
program's code: float32, every product at ``highest``, jitted as it stands.
It reads the parameter tree the program checkpoints (the names of
``models/lfm2_moe.py``) and the configuration file's ``model`` block
(``flops/lfm2_moe.py::model_from_conf`` plus what this chip holds:
``layers_held``, ``experts_held``, ``expert_share``, ``ids_held``).

With ``RMSNorm(x) = w x / sqrt(mean(x^2) + eps)``: the embedding's rows; a
block is

    h = x + mixer(N1(x))          y = h + ffn(N2(h))

then a final RMSNorm and the logits through the embedding's own table,
``norm(x) E^T``.

mixer, ``layer_types[l] == "conv"``: ``(B, C, z) = split3(u W_in)`` (hidden
-> 3 hidden, in that order), ``s = B * z``, ``c_t = k_2 s_t + k_1 s_(t-1) +
k_0 s_(t-2)`` (depthwise, ``k`` ``[3, hidden]``, zeros before the sequence:
three shifted copies, no bias, no activation), ``(C * c) W_out``.

mixer, ``"full_attention"``: ``q = u W_q`` (``heads`` of ``head_dim``), ``k
= u W_k``, ``v = u W_v`` (``kv_heads``); ``q`` and ``k`` through an RMSNorm
over each head's ``head_dim`` channels (one weight for queries, one for
keys); both of token ``t`` turned by ``t * theta^(-2i / head_dim)`` in the
pairs ``(i, i + head_dim / 2)``; one whole softmax of ``q . k /
sqrt(head_dim)`` a row over an explicit ``[block, T]`` score matrix, the
whole causal past; key-value head ``c`` serves the query heads ``[c n, (c +
1) n)``; ``a W_o``.  No biases.

ffn: ``W_down (silu(W_gate u) * W_up u)`` at ``dense_width`` in the leading
``dense_layers`` blocks; after them ``s = sigmoid(W_r u)`` over all experts,
the ``top_k`` largest of ``s + bias`` chosen, weights ``s_e / (sum_chosen s +
renorm_eps) * routed_scale``; ``y = sum over e chosen and held of w_e
E_e(u)``, each ``E`` a SwiGLU at ``expert_width``, a loop over the held
experts; no shared expert.

Departures from the published description, each because the program
under test departs the same way or because it changes no number:

- the router's correction bias is a parameter that no gradient reaches
  (the program moves it by a load-balancing rule between steps:
  ``ops/moe.py::balance_bias``); the forward pass reads whatever value the
  checkpoint holds;
- what the experts this chip does not hold would add is left out
  (``model-configs`` guide, section 4), as the program leaves it out;
- to fit a chip's memory at 16,384 tokens, and only for that: the softmax
  is taken a block of queries at a time (each row still one whole softmax
  over all ``T`` keys, masked), and :func:`loss_and_grads` recomputes a
  block and a block of queries in its backward pass (``jax.checkpoint``),
  which changes no value.

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
``control`` names one of ``untied_head`` (the logits through
``lm_head/kernel``, a matrix of its own the caller adds to the tree),
``no_qk_norm``, ``no_rotary``, ``interleaved_pairs`` (the pairs ``(2i, 2i +
1)``), ``split_cbz`` (the projection read as ``(C, B, z)``: the two gates'
places exchanged), ``silu_after_taps``, ``taps_shifted`` (the last tap on
the token before: ``c_t = k_2 s_(t-1) + k_1 s_(t-2) + k_0 s_(t-3)``),
``no_final_norm``, ``neighbours_queries`` (every odd query head asks with
the even head's query before it: what a kernel that takes two heads to a
block would give had it read the pair's first half twice — the pair's keys
and values are the same head's wherever a key-value head serves an even
number of query heads, so their exchange is no control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 256   # queries a softmax block
CONV, FULL = "conv", "full_attention"


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rotate(x, theta, interleaved: bool):
    """`x` ``[T, ..., d]``, token ``t``'s pair ``i`` turned by ``t *
    theta^(-2i / d)``; a pair is ``(x[i], x[i + d/2])``, or ``(x[2i], x[2i +
    1])`` where `interleaved` (a control)."""
    length, dim = x.shape[0], x.shape[-1]
    angle = (jnp.arange(length, dtype=jnp.float32)[:, None]
             * theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)[None, :])
    angle = angle.reshape((length,) + (1,) * (x.ndim - 2) + (dim // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if interleaved:
        pairs = x.reshape(x.shape[:-1] + (dim // 2, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _earlier(s, by: int):
    """``s[t - by]`` at token ``t``, zeros before the sequence."""
    return s if by == 0 else jnp.pad(s, ((by, 0), (0, 0)))[:-by]


def _conv_mixer(x, p, model):
    control = model.get("control")
    first, second, z = jnp.split(x @ p["in_proj"]["kernel"], 3, axis=-1)
    before, after = (second, first) if control == "split_cbz" else (first, second)
    s = before * z
    taps = p["conv_kernel"]
    late = 1 if control == "taps_shifted" else 0
    c = sum(taps[taps.shape[0] - 1 - back] * _earlier(s, back + late)
            for back in range(taps.shape[0]))
    if control == "silu_after_taps":
        c = jax.nn.silu(c)
    return (after * c) @ p["out_proj"]["kernel"]


def _attention_mixer(x, p, model):
    heads, kv_heads, dim = (int(model[k]) for k in ("heads", "kv_heads", "head_dim"))
    length, control, eps = x.shape[0], model.get("control"), float(model["eps"])
    q = (x @ p["q_proj"]["kernel"]).reshape(length, kv_heads, heads // kv_heads, dim)
    k = (x @ p["k_proj"]["kernel"]).reshape(length, kv_heads, dim)
    v = (x @ p["v_proj"]["kernel"]).reshape(length, kv_heads, dim)
    if control != "no_qk_norm":
        q = _rms(q, p["q_norm"]["weight"], eps)
        k = _rms(k, p["k_norm"]["weight"], eps)
    if control != "no_rotary":
        q, k = (_rotate(a, float(model["rope_theta"]), control == "interleaved_pairs")
                for a in (q, k))
    if control == "neighbours_queries":
        flat = q.reshape(length, heads, dim)
        q = jnp.repeat(flat[:, 0::2], 2, axis=1).reshape(q.shape)
    size = _QUERY_BLOCK if length % _QUERY_BLOCK == 0 else length

    @jax.checkpoint
    def block(args):
        q_block, first = args
        scores = jnp.einsum("qgnd,kgd->gnqk", q_block, k) * dim ** -0.5
        ahead = first + jnp.arange(size)[:, None] - jnp.arange(length)[None, :]
        scores = jnp.where(ahead >= 0, scores, -jnp.inf)
        return jnp.einsum("gnqk,kgd->qgnd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(block, (q.reshape((length // size, size) + q.shape[1:]),
                              jnp.arange(0, length, size)))
    return out.reshape(length, heads * dim) @ p["o_proj"]["kernel"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _experts(x, p, model, given=None, kept=None):
    """``(output, margin)``; `given` ``[T, top_k]`` replaces the layer's
    own choice of experts (module docstring), `kept` ``[held]`` weighs the
    held experts by zero or one."""
    held = p["experts_gate"].shape[0]
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
        weights = weights / (weights.sum(-1, keepdims=True)
                             + float(model.get("renorm_eps", 1e-6)))
    weights = weights * float(model["routed_scale"])
    keep = jnp.ones(held, x.dtype) if kept is None else kept

    def one(out, expert):   # one held expert a step: one body to compile
        gate, up, down, j, keep = expert
        weight = keep * jnp.sum(jnp.where(chosen == first + j, weights, 0.0), -1)
        return out + weight[:, None] * _swiglu(x, gate, up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"], p["experts_up"], p["experts_down"], jnp.arange(held), keep))
    return out, margin


def _block(x, p, kind, model, given=None, kept=None):
    eps = float(model["eps"])
    normed = _rms(x, p["operator_norm"]["weight"], eps)
    h = x + (_conv_mixer(normed, p["conv"], model) if kind == CONV
             else _attention_mixer(normed, p["attn"], model))
    normed = _rms(h, p["ffn_norm"]["weight"], eps)
    if "mlp" in p:
        ffn, margin = _swiglu(normed, *(p["mlp"][f"{name}_proj"]["kernel"] for name in (
            "gate", "up", "down"))), jnp.float32(0.0)
    else:
        ffn, margin = _experts(normed, p["moe"], model, given, kept)
    return h + ffn, margin


def _held_kinds(model) -> tuple:
    return tuple(model["layer_types"])[:int(model["layers_held"])]


def _sequence_logits(params, ids, model, remat: bool, routing=None, kept=None):
    """``(logits [T, ids_held], margin)``; `routing` and `kept` by layer
    name, for the expert layers (:func:`_experts`)."""
    routing, kept = routing or {}, kept or {}
    x = params["embed_tokens"][ids]
    margins = [jnp.float32(0.0)]
    for index, kind in enumerate(_held_kinds(model), start=1):
        name = f"layer{index}"
        fn = functools.partial(_block, kind=kind, model=model)
        x, margin = (jax.checkpoint(fn) if remat else fn)(
            x, params[name], given=routing.get(name), kept=kept.get(name))
        margins.append(margin)
    control = model.get("control")
    if control != "no_final_norm":
        x = _rms(x, params["norm"]["weight"], float(model["eps"]))
    head = (params["lm_head"]["kernel"] if control == "untied_head"
            else params["embed_tokens"].T)
    return x @ head, jnp.max(jnp.stack(margins))


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


def _frozen(model) -> _Frozen:
    return _Frozen(model, layer_types=tuple(model["layer_types"]))


def forward(params, batch_stats, ids, model):
    """Logits ``[B, T, ids_held]`` for `ids` ``[B, T]`` (a sequence at a
    time), each layer's own choice of experts.  `batch_stats` is empty: the
    model has none."""
    del batch_stats
    return np.asarray(_logits(_float32(params), _ids(ids), _frozen(model)))


def expert_layers(model) -> list[str]:
    """The names of the expert layers this chip holds."""
    return [f"layer{index}" for index in range(
        int(model["dense_layers"]) + 1, int(model["layers_held"]) + 1)]


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
        _float32(params), _ids(ids), routing, kept, _frozen(model))
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
        _frozen(model)).compile()

    def run(params, ids, routing, kept=None):
        routing, kept = _whole(routing, kept, held)
        logits, margin = compiled(_float32(params), _ids(ids), routing, kept)
        return np.asarray(logits), float(margin)

    return run


def loss_and_grads(params, ids, model):
    """``(loss, grads)``: the mean next-token cross-entropy of `ids` ``[B,
    T + 1]`` (inputs ``ids[:, :-1]``, targets ``ids[:, 1:]``) and its
    gradient in the parameters' own tree."""
    loss, grads = _loss_and_grads(_float32(params), _ids(ids), _frozen(model))
    return float(loss), jax.device_get(grads)
