"""Kimi Linear's forward pass, loss and gradients in plain ``jax.numpy``.

Written from the published description (Kimi Linear, arXiv:2510.26692;
the model's ``config.json``; fla's ``KimiDeltaAttention``), none of the
program's code: float32, every product at ``highest``, jitted as it
stands.  It reads the parameter tree the program checkpoints (the names
of ``models/kimi_linear.py``) and the configuration file's ``model``
block (``flops/kimi_linear.py::model_from_conf`` plus what this chip
holds: ``layers_held``, ``experts_held``, ``expert_share``, ``ids_held``).

Pre-norm blocks, ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``,
a final RMSNorm, an untied head.

KDA, **as the recurrence, one token a step** (no chunks): ``q, k, v`` are
projections through a depthwise causal convolution of 4 taps and SiLU;
``q``, ``k`` L2-normalised per head, ``q`` scaled by ``d^-1/2``;
``g_t = -exp(A_log) softplus(W_fb W_fa x + dt_bias)`` per channel,
``beta_t = sigmoid(W_b x)`` per head; ``S_t = (I - beta_t k_t k_t^T)
Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``; output
``W_o [RMSNorm_head(o_t) * sigmoid(W_gb W_ga x)]``.

Latent attention without rotary and without query compression: ``q = W_q
x`` (nope + pe a head), ``c = W_a x``, ``c_kv = RMSNorm(c[:rank])``,
``k_pe = c[rank:]`` shared by the heads, ``[k_nope; v] = W_b c_kv``; one
whole causal softmax over ``q . [k_nope; k_pe] / sqrt(nope + pe)``.

Experts: ``s = sigmoid(W_r x)`` over all experts, the ``top_k`` largest of
``s + bias`` chosen, weights ``s_e / sum_chosen s * scale``; ``y = sum over
e chosen and held of w_e E_e(x) + E_shared(x)``, ``E(x) = W_down(silu(W_gate
x) * W_up x)``, a loop over the held experts.

Departures from the published description, each because the program
under test departs the same way or because it changes no number:

- the inner widths of ``W_f`` and ``W_g`` (one head's size) are not in
  ``config.json``; fla's default is taken; ``W_g`` has no bias;
- the router's correction bias is a parameter that no gradient reaches
  (the published model moves it by a load-balancing rule between steps,
  not by the loss, and so does the program: ``ops/moe.py::balance_bias``,
  held by the CPU tests); the forward pass reads whatever value the
  checkpoint holds;
- what the experts this chip does not hold would add is left out
  (``model-configs`` guide, section 4), as the program leaves it out;
- to fit a chip's memory at 8,192 tokens, and only for that: the softmax
  is taken a block of queries at a time (each row still one whole
  softmax), and :func:`loss_and_grads` recomputes a layer, a block of
  queries and a segment of 64 recurrence steps in its backward pass
  (``jax.checkpoint``), which changes no value.

**A comparison of logits has to survive a top-k.**  Under a balanced
router hundreds of a sequence's tokens have their eighth and ninth expert
within rounding of each other, and a token whose choice falls the other
way in the other computation moves its logits by a whole expert's output:
two sound computations then differ by 5-15% of the largest logit (PERF.md
section 6, PR 35).  So :func:`forward_given_routing` takes the *system's*
choices (``[B, T, top_k]`` a layer) in place of its own top-k, computes
weights, experts and everything else itself, and says how far those
choices are from its own: the largest amount by which a chosen expert's
``score + bias`` lies under the reference's own ``top_k``-th largest,
which is zero for the same choice, rounding for a tie that fell the other
way, and the bias's or the scores' size for a router that chose wrongly.
`kept` (``[held]`` zeros and ones a layer) drops held experts without
another compilation: the control that the benchmark's comparison must
refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_SEGMENT = 64        # recurrence steps recomputed together in a backward pass
_QUERY_BLOCK = 256   # queries a softmax block


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _short_conv(x, kernel):
    """``y_t = silu(sum_i kernel[i] x_{t - (taps - 1) + i})``, zeros before
    the first token; `x` ``[T, C]``, `kernel` ``[taps, C]``."""
    taps = kernel.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    out = sum(padded[i:i + x.shape[0]] * kernel[i] for i in range(taps))
    return jax.nn.silu(out)


def _delta_rule(q, k, v, g, beta):
    """The recurrence over tokens; `q`, `k`, `g` ``[T, H, K]``, `v`
    ``[T, H, V]``, `beta` ``[T, H]`` -> ``[T, H, V]``."""
    length, heads, kdim = q.shape

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[:, :, None]
        u_t = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, state))
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    size = _SEGMENT if length % _SEGMENT == 0 else length

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(a.reshape((length // size, size) + a.shape[1:])
               for a in (q, k, v, g, beta))
    state = jnp.zeros((heads, kdim, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(segment, state, xs)
    return out.reshape((length,) + out.shape[2:])


def _kda(x, p, model):
    heads, dim = int(model["kda_heads"]), int(model["kda_head_dim"])
    length = x.shape[0]

    def branch(name):
        return _short_conv(x @ p[f"{name}_proj"]["kernel"],
                           p[f"{name}_conv"]["kernel"]).reshape(length, heads, dim)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q, k, v = unit(branch("q")) * dim ** -0.5, unit(branch("k")), branch("v")
    raw = (x @ p["f_a_proj"]["kernel"]) @ p["f_b_proj"]["kernel"] + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(raw).reshape(
        length, heads, dim)
    beta = jax.nn.sigmoid(x @ p["b_proj"]["kernel"])
    out = _rms(_delta_rule(q, k, v, g, beta), p["o_norm"]["weight"],
               float(model["eps"]))
    gate = jax.nn.sigmoid((x @ p["g_a_proj"]["kernel"]) @ p["g_b_proj"]["kernel"])
    return (out * gate.reshape(length, heads, dim)).reshape(
        length, heads * dim) @ p["o_proj"]["kernel"]


def _mla(x, p, model):
    heads = int(model["heads"])
    nope, pe, vdim = (int(model[k]) for k in ("nope_dim", "pe_dim", "v_dim"))
    rank = int(model["kv_rank"])
    length = x.shape[0]
    q = (x @ p["q_proj"]["kernel"]).reshape(length, heads, nope + pe)
    latent = x @ p["kv_a_proj"]["kernel"]
    kv = (_rms(latent[:, :rank], p["kv_a_norm"]["weight"], float(model["eps"]))
          @ p["kv_b_proj"]["kernel"]).reshape(length, heads, nope + vdim)
    keys = jnp.concatenate(
        [kv[:, :, :nope],
         jnp.broadcast_to(latent[:, None, rank:], (length, heads, pe))], -1)
    values = kv[:, :, nope:]
    size = _QUERY_BLOCK if length % _QUERY_BLOCK == 0 else length

    @jax.checkpoint
    def block(args):
        q_block, first = args
        scores = jnp.einsum("qhd,khd->hqk", q_block, keys) * (nope + pe) ** -0.5
        rows = first + jnp.arange(size)[:, None]
        scores = jnp.where(rows >= jnp.arange(length)[None, :], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), values)

    out = jax.lax.map(block, (q.reshape(length // size, size, heads, nope + pe),
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
    normed = _rms(x, p["input_norm"]["weight"], eps)
    h = x + (_kda(normed, p["kda"], model) if "kda" in p
             else _mla(normed, p["mla"], model))
    normed = _rms(h, p["post_norm"]["weight"], eps)
    if "mlp" in p:
        return h + _swiglu(normed, p["mlp"]), jnp.float32(0.0)
    ffn, margin = _experts(normed, p["moe"], model, given, kept)
    return h + ffn, margin


def _sequence_logits(params, ids, model, remat: bool, routing=None, kept=None):
    """``(logits [T, ids_held], margin)``; `routing` and `kept` by layer
    name, for the expert layers (:func:`_experts`)."""
    x = params["embed_tokens"][ids]
    margins = [jnp.float32(0.0)]
    for layer in range(1, int(model["layers_held"]) + 1):
        name = f"layer{layer}"
        fn = functools.partial(_layer, model=model)
        x, margin = (jax.checkpoint(fn) if remat else fn)(
            x, params[name], given=(routing or {}).get(name),
            kept=(kept or {}).get(name))
        margins.append(margin)
    x = _rms(x, params["norm"]["weight"], float(model["eps"]))
    return x @ params["lm_head"]["kernel"], jnp.max(jnp.stack(margins))


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


def forward(params, batch_stats, ids, model) -> np.ndarray:
    """Logits ``[B, T, ids_held]`` for `ids` ``[B, T]`` (the inputs; a
    sequence at a time).  `batch_stats` is empty: the model has none."""
    del batch_stats
    return np.asarray(_logits(_float32(params), jnp.asarray(ids, jnp.int32),
                              _Frozen(model)))


def _whole(routing, kept):
    """`routing` as int32 and `kept` (every held expert where None) as
    float32 arrays by layer name."""
    routing = {k: jnp.asarray(np.asarray(v), jnp.int32) for k, v in routing.items()}
    if kept is None:
        kept = {}
    return routing, {k: jnp.asarray(np.asarray(kept.get(k, 1.0)), jnp.float32)
                     for k in routing}


def forward_given_routing(params, ids, model, routing, kept=None):
    """``(logits [B, T, ids_held], margin)`` with every expert layer's
    choice of experts given (`routing`: ``{layer name: [B, T, top_k]}``,
    the system's) and, for a control, `kept` (``{layer name: [held]}``
    zeros and ones; absent: all held experts).  Module docstring."""
    held = int(model.get("experts_held") or model["experts"])
    routing, kept = _whole(routing, kept)
    kept = {k: jnp.broadcast_to(v, (held,)) for k, v in kept.items()}
    logits, margin = _logits_given_routing(
        _float32(params), jnp.asarray(ids, jnp.int32), routing, kept, _Frozen(model))
    return np.asarray(logits), float(margin)


def compile_forward_given_routing(params, ids, model):
    """:func:`forward_given_routing` compiled ahead from shapes (`params`
    and `ids` arrays or ``jax.ShapeDtypeStruct`` s): ``(params, ids,
    routing, kept=None) -> (logits, margin)``."""
    held = int(model.get("experts_held") or model["experts"])
    layers = [f"layer{i}" for i in range(int(model["dense_layers"]) + 1,
                                         int(model["layers_held"]) + 1)]
    chosen = jax.ShapeDtypeStruct(tuple(ids.shape) + (int(model["top_k"]),), jnp.int32)
    compiled = _logits_given_routing.lower(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params),
        jax.ShapeDtypeStruct(ids.shape, jnp.int32), {k: chosen for k in layers},
        {k: jax.ShapeDtypeStruct((held,), jnp.float32) for k in layers},
        _Frozen(model)).compile()

    def run(params, ids, routing, kept=None):
        routing, kept = _whole(routing, kept)
        kept = {k: jnp.broadcast_to(v, (held,)) for k, v in kept.items()}
        logits, margin = compiled(_float32(params), jnp.asarray(ids, jnp.int32),
                                  routing, kept)
        return np.asarray(logits), float(margin)

    return run


def loss_and_grads(params, ids, model):
    """``(loss, grads)``: the mean next-token cross-entropy of `ids`
    ``[B, T + 1]`` (inputs ``ids[:, :-1]``, targets ``ids[:, 1:]``) and
    its gradient in the parameters' own tree."""
    loss, grads = _loss_and_grads(_float32(params), jnp.asarray(ids, jnp.int32),
                                  _Frozen(model))
    return float(loss), jax.device_get(grads)
