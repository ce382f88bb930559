"""GLM-4.7-Flash's forward pass, both heads, its two-term loss and gradients
in plain ``jax.numpy``.

Written from the published description (``config.json`` of
zai-org/GLM-4.7-Flash, ``model_type: glm4_moe_lite``; DeepSeek-V2 and
DeepSeek-V3, arXiv:2405.04434 and arXiv:2412.19437, for latent attention,
the router and the multi-token-prediction module the config's keys refer
to), none of the program's code: float32, every product at ``highest``,
jitted as it stands.  It reads the parameter tree the program checkpoints
(the names of ``models/glm4_moe_lite.py``) and the configuration file's
``model`` block (``flops/glm4_moe_lite.py::model_from_conf`` plus what this
chip holds: ``layers_held``, ``experts_held``, ``expert_share``,
``ids_held``).

Pre-norm blocks, ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``,
a final RMSNorm, an untied head.

Latent attention, every layer: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb``
(a head ``[q_nope | q_r]``); ``[c_kv | k_r] = x W_kva``, ``[k_nope | v]`` a
head ``= RMSNorm(c_kv) W_kvb``; ``q_r`` of every head and the one ``k_r``
all heads share are rotated by position — the pair ``(x[2i], x[2i + 1])``
of token ``t`` turned by ``t * theta^(-2i / d)``; one whole causal softmax
over ``(q_nope . k_nope + q_r . k_r) / sqrt(nope + rope)``; ``o = sum p v``
through ``W_o``.  No biases.

Experts: ``s = sigmoid(W_r x)`` over all experts, the ``top_k`` largest of
``s + bias`` chosen, weights ``s_e / sum_chosen s * scale``; ``y = sum over
e chosen and held of w_e E_e(x) + E_shared(x)``, ``E(x) = W_down(silu(W_gate
x) * W_up x)``, a loop over the held experts.

Multi-token prediction, depth 1: with ``h_i`` the last held layer's output
before the final norm, ``h'_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]
W_eh``, ``h''_i = Block(h'_i)`` (a block like the expert layers', its own
router), ``logits^mtp_i = RMSNorm_s(h''_i) W_head``, which predicts
``t_{i+2}``; ``Emb`` and ``W_head`` the main model's.  Loss ``CE(logits,
t_{i+1}) + weight * CE(logits^mtp, t_{i+2})``, the second mean over the
``T - 1`` positions with a target two ahead.

Departures from the published description, each because the program
under test departs the same way or because it changes no number:

- which of the rotary dimensions pair up is not in ``config.json``: the
  interleaved pairs of DeepSeek-V3's public code are taken;
- ``eh_proj`` takes the embedding's half first (DeepSeek-V3's public
  checkpoints; the config does not say); the loss's ``weight`` is the
  configuration's ``mtp_weight`` (0.3, DeepSeek-V3's; the config gives
  none);
- the router's correction bias is a parameter that no gradient reaches
  (the published model moves it by a load-balancing rule between steps, and
  so does the program: ``ops/moe.py::balance_bias``); the forward pass reads
  whatever value the checkpoint holds;
- what the experts this chip does not hold would add is left out
  (``model-configs`` guide, section 4), as the program leaves it out;
- to fit a chip's memory at 8,192 tokens, and only for that: the softmax is
  taken a block of queries at a time (each row still one whole softmax),
  and :func:`loss_and_grads` recomputes a layer and a block of queries in
  its backward pass (``jax.checkpoint``), which changes no value.

**A comparison of logits has to survive a top-k** (``references/
kimi_linear.py`` says why): :func:`forward_given_routing` takes the
*system's* choices (``[B, T, top_k]`` a layer, the module's block under
``mtp``) in place of its own top-k, computes weights, experts and
everything else itself, and says how far those choices are from its own:
the largest amount by which a chosen expert's ``score + bias`` lies under
the reference's own ``top_k``-th largest, over the main layers and for the
module's block apart.  `kept` (``[held]`` zeros and ones a layer) drops
held experts without another compilation; a `model` whose ``rope_theta``
is None leaves the rotation out and one whose ``layers_held`` is smaller
stops short: controls that the benchmark's comparison must refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 256   # queries a softmax block
MTP = "mtp"          # the module's block among the layers


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rotate(x, theta):
    """`x` ``[T, ..., d]``: the pair ``(x[2i], x[2i + 1])`` of token ``t``
    turned by ``t * theta^(-2i / d)``, in place."""
    length, dim = x.shape[0], x.shape[-1]
    angle = (jnp.arange(length, dtype=jnp.float32)[:, None]
             * theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)[None, :])
    angle = angle.reshape((length,) + (1,) * (x.ndim - 2) + (dim // 2,))
    pairs = x.reshape(x.shape[:-1] + (dim // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        even * jnp.sin(angle) + odd * jnp.cos(angle)], -1)
    return turned.reshape(x.shape)


def _mla(x, p, model):
    heads = int(model["heads"])
    nope, rope, vdim = (int(model[k]) for k in ("nope_dim", "pe_dim", "v_dim"))
    rank, eps = int(model["kv_rank"]), float(model["eps"])
    length = x.shape[0]
    q = (_rms(x @ p["q_a_proj"]["kernel"], p["q_a_norm"]["weight"], eps)
         @ p["q_b_proj"]["kernel"]).reshape(length, heads, nope + rope)
    latent = x @ p["kv_a_proj"]["kernel"]
    kv = (_rms(latent[:, :rank], p["kv_a_norm"]["weight"], eps)
          @ p["kv_b_proj"]["kernel"]).reshape(length, heads, nope + vdim)
    q_rope, k_rope = q[:, :, nope:], latent[:, rank:]
    if model.get("rope_theta") is not None:
        q_rope = _rotate(q_rope, float(model["rope_theta"]))
        k_rope = _rotate(k_rope, float(model["rope_theta"]))
    queries = jnp.concatenate([q[:, :, :nope], q_rope], -1)
    keys = jnp.concatenate(
        [kv[:, :, :nope],
         jnp.broadcast_to(k_rope[:, None, :], (length, heads, rope))], -1)
    values = kv[:, :, nope:]
    size = _QUERY_BLOCK if length % _QUERY_BLOCK == 0 else length

    @jax.checkpoint
    def block(args):
        q_block, first = args
        scores = jnp.einsum("qhd,khd->hqk", q_block, keys) * (nope + rope) ** -0.5
        rows = first + jnp.arange(size)[:, None]
        scores = jnp.where(rows >= jnp.arange(length)[None, :], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), values)

    out = jax.lax.map(block, (queries.reshape(length // size, size, heads, nope + rope),
                              jnp.arange(0, length, size)))
    return out.reshape(length, heads * vdim) @ p["o_proj"]["kernel"]


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["gate_proj"]["kernel"])
            * (x @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


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
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    weights = weights * float(model["routed_scale"])
    keep = jnp.ones(held, x.dtype) if kept is None else kept

    def one(out, expert):   # one held expert a step: one body to compile
        gate, up, down, j, keep = expert
        weight = keep * jnp.sum(jnp.where(chosen == first + j, weights, 0.0), -1)
        return out + weight[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["experts_gate"], p["experts_up"], p["experts_down"], jnp.arange(held), keep))
    if "shared_experts" in p:
        out = out + _swiglu(x, p["shared_experts"])
    return out, margin


def _layer(x, p, model, given=None, kept=None):
    eps = float(model["eps"])
    h = x + _mla(_rms(x, p["input_norm"]["weight"], eps), p["mla"], model)
    normed = _rms(h, p["post_norm"]["weight"], eps)
    if "mlp" in p:
        return h + _swiglu(normed, p["mlp"]), jnp.float32(0.0)
    ffn, margin = _experts(normed, p["moe"], model, given, kept)
    return h + ffn, margin


def _sequence_logits(params, ids, next_ids, model, remat: bool, routing=None,
                     kept=None):
    """``(logits [T, ids_held], mtp_logits or None, margin, mtp_margin)``;
    `routing` and `kept` by layer name, for the expert layers and the
    module's block (:func:`_experts`); `next_ids` None: the main head
    alone."""
    routing, kept = routing or {}, kept or {}
    eps = float(model["eps"])

    def layer_fn(x, p, name):
        fn = functools.partial(_layer, model=model)
        return (jax.checkpoint(fn) if remat else fn)(
            x, p, given=routing.get(name), kept=kept.get(name))

    x = params["embed_tokens"][ids]
    margins = [jnp.float32(0.0)]
    for layer in range(1, int(model["layers_held"]) + 1):
        x, margin = layer_fn(x, params[f"layer{layer}"], f"layer{layer}")
        margins.append(margin)
    head = params["lm_head"]["kernel"]
    logits = _rms(x, params["norm"]["weight"], eps) @ head
    mtp_logits, mtp_margin = None, jnp.float32(0.0)
    if next_ids is not None:
        joined = jnp.concatenate(
            [_rms(params["embed_tokens"][next_ids], params["mtp_enorm"]["weight"], eps),
             _rms(x, params["mtp_hnorm"]["weight"], eps)], -1)
        y, mtp_margin = layer_fn(joined @ params["mtp_eh_proj"]["kernel"],
                                 params[MTP], MTP)
        mtp_logits = _rms(y, params["mtp_norm"]["weight"], eps) @ head
    return logits, mtp_logits, jnp.max(jnp.stack(margins)), mtp_margin


class _Frozen(dict):
    """The `model` block as a hashable static argument of ``jit``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


@functools.partial(jax.jit, static_argnames=("model",))
def _logits(params, ids, next_ids, model):
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda rows: _sequence_logits(params, rows[0], rows[1], model,
                                          remat=False)[:2], (ids, next_ids))


@functools.partial(jax.jit, static_argnames=("model",))
def _logits_given_routing(params, ids, next_ids, routing, kept, model):
    with jax.default_matmul_precision("highest"):
        logits, mtp_logits, margins, mtp_margins = jax.lax.map(
            lambda args: _sequence_logits(params, args[0], args[1], model,
                                          remat=False, routing=args[2], kept=kept),
            (ids, next_ids, routing))
    return logits, mtp_logits, jnp.max(margins), jnp.max(mtp_margins)


def _cross_entropy(logits, targets):
    picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jax.nn.logsumexp(logits, -1) - picked


@functools.partial(jax.jit, static_argnames=("model",))
def _loss_and_grads(params, ids, model):
    weight = float(model.get("mtp_weight", 0.0)) if model.get("mtp_modules") else 0.0

    def loss(params):
        def one(row):
            inputs, targets = row[:-1], row[1:]
            logits, mtp_logits, _, _ = _sequence_logits(
                params, inputs, targets if weight else None, model, remat=True)
            main = jnp.mean(_cross_entropy(logits, targets))
            if not weight:
                return main, jnp.float32(0.0)
            # position i predicts targets[i + 1]; the last has no such target
            return main, jnp.mean(_cross_entropy(mtp_logits[:-1], targets[1:]))

        main, mtp = jax.lax.map(one, ids)
        main, mtp = jnp.mean(main), jnp.mean(mtp)
        return main + weight * mtp, (main, mtp)

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(params)


def _float32(tree):
    return jax.tree.map(lambda a: jnp.asarray(np.asarray(a), jnp.float32), tree)


def _ids(ids):
    return None if ids is None else jnp.asarray(np.asarray(ids), jnp.int32)


def forward(params, batch_stats, ids, model, next_ids=None):
    """The main head's logits ``[B, T, ids_held]`` for `ids` ``[B, T]`` (the
    inputs; a sequence at a time), each layer's own choice of experts; with
    `next_ids` (the token after each input) ``(logits, mtp_logits)``.
    `batch_stats` is empty: the model has none."""
    del batch_stats
    out = _logits(_float32(params), _ids(ids), _ids(next_ids), _Frozen(model))
    return np.asarray(out[0]) if next_ids is None else tuple(map(np.asarray, out))


def _layers_given(model, mtp: bool) -> list[str]:
    layers = [f"layer{i}" for i in range(int(model["dense_layers"]) + 1,
                                         int(model["layers_held"]) + 1)]
    return layers + [MTP] if mtp else layers


def _whole(routing, kept, held: int):
    """`routing` as int32 and `kept` (every held expert where None) as
    float32 ``[held]`` arrays by layer name."""
    routing = {k: jnp.asarray(np.asarray(v), jnp.int32) for k, v in routing.items()}
    kept = kept or {}
    return routing, {k: jnp.broadcast_to(jnp.asarray(
        np.asarray(kept.get(k, 1.0)), jnp.float32), (held,)) for k in routing}


def _result(out, mtp: bool):
    logits, mtp_logits, margin, mtp_margin = out
    if not mtp:
        return np.asarray(logits), float(margin)
    return ((np.asarray(logits), np.asarray(mtp_logits)),
            (float(margin), float(mtp_margin)))


def forward_given_routing(params, ids, model, routing, kept=None, next_ids=None):
    """``(logits [B, T, ids_held], margin)`` with every expert layer's
    choice of experts given (`routing`: ``{layer name: [B, T, top_k]}``, the
    system's) and, for a control, `kept` (``{layer name: [held]}`` zeros and
    ones; absent: all held experts).  With `next_ids`, and the module's
    block's choice under ``mtp`` in `routing`: ``((logits, mtp_logits),
    (margin, mtp_margin))``.  Module docstring."""
    held = int(model.get("experts_held") or model["experts"])
    routing, kept = _whole(routing, kept, held)
    return _result(_logits_given_routing(
        _float32(params), _ids(ids), _ids(next_ids), routing, kept,
        _Frozen(model)), next_ids is not None)


def compile_forward_given_routing(params, ids, model, mtp: bool = False):
    """:func:`forward_given_routing` compiled ahead from shapes (`params`
    and `ids` arrays or ``jax.ShapeDtypeStruct`` s): ``(params, ids,
    routing, kept=None) -> (logits, margin)``, or with `mtp` ``(params, ids,
    routing, kept=None, next_ids=...)`` -> both heads'."""
    held = int(model.get("experts_held") or model["experts"])
    layers = _layers_given(model, mtp)
    some_ids = jax.ShapeDtypeStruct(ids.shape, jnp.int32)
    chosen = jax.ShapeDtypeStruct(tuple(ids.shape) + (int(model["top_k"]),), jnp.int32)
    compiled = _logits_given_routing.lower(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params),
        some_ids, some_ids if mtp else None, {k: chosen for k in layers},
        {k: jax.ShapeDtypeStruct((held,), jnp.float32) for k in layers},
        _Frozen(model)).compile()

    def run(params, ids, routing, kept=None, next_ids=None):
        routing, kept = _whole(routing, kept, held)
        return _result(compiled(_float32(params), _ids(ids),
                                _ids(next_ids) if mtp else None, routing, kept), mtp)

    return run


def loss_and_grads(params, ids, model):
    """``(loss, grads, terms)``: the two-term loss of `ids` ``[B, T + 1]``
    (inputs ``ids[:, :-1]``, targets ``ids[:, 1:]``), its gradient in the
    parameters' own tree, and ``terms = {"main": ..., "mtp": ...}``, the two
    cross-entropies the loss adds at 1 and at ``mtp_weight``."""
    (loss, (main, mtp)), grads = _loss_and_grads(
        _float32(params), _ids(ids), _Frozen(model))
    return float(loss), jax.device_get(grads), {"main": float(main), "mtp": float(mtp)}
