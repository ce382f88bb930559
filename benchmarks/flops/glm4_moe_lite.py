"""Operations of GLM-4.7-Flash from its shapes alone, an example being one
sequence; and what the mathematics of two of its kernels needs.

GLM-4.7-Flash (``config.json`` of zai-org/GLM-4.7-Flash, ``model_type:
glm4_moe_lite``) as this system runs it (``models/glm4_moe_lite.py``): per
layer latent attention with a low-rank query and rotary, and an FFN — dense
SwiGLU in the leading layers, then routed experts beside a shared one — a
final norm and an untied head; in training one multi-token-prediction
module (two norms, ``eh_proj``, one more block of the expert layers' kind,
a norm, and the head a second time), over the layers, experts and ids this
chip holds.

Counted, two operations a multiply-accumulate: every projection; the
attention's scores and weighted values over the causal half; the router
over all its experts; the routed experts at the *expected* ``top_k * held /
experts`` assignments a token; the shared expert; the head, once in a
forward pass (evaluation computes no module) and twice, with the module,
in a training step.  Not counted: norms, the rotation, gates' activations,
the softmax, the embedding's gathers, the loss.  A backward pass is taken
as twice the forward pass; what ``nn.remat`` and the blocked loss compute
again is not counted.

The kernels' functions count what the *mathematics* needs, not what an
implementation does (``<kernel>_operations``, ``<kernel>_bytes``), so a
later kernel is read against the same work and a share of the roofline
cannot pass 100% by a cheaper form.
"""

from __future__ import annotations


def model_from_conf(conf_model: dict) -> dict:
    """The sizes the functions below and the reference need, from the
    conf's ``model`` mapping (the published ``config.json``'s keys)."""
    if conf_model.get("type") != "glm4_moe_lite":
        raise ValueError(f"not a glm4_moe_lite model: {conf_model.get('type')!r}")
    return {
        "hidden": int(conf_model["hidden_size"]),
        "eps": float(conf_model["rms_norm_eps"]),
        "layers": int(conf_model["num_hidden_layers"]),
        "vocab": int(conf_model["vocab_size"]),
        "heads": int(conf_model["num_attention_heads"]),
        "nope_dim": int(conf_model["qk_nope_head_dim"]),
        "pe_dim": int(conf_model["qk_rope_head_dim"]),
        "v_dim": int(conf_model["v_head_dim"]),
        "kv_rank": int(conf_model["kv_lora_rank"]),
        "q_rank": int(conf_model["q_lora_rank"]),
        "rope_theta": float(conf_model["rope_theta"]),
        "dense_layers": int(conf_model["first_k_dense_replace"]),
        "dense_width": int(conf_model["intermediate_size"]),
        "experts": int(conf_model["n_routed_experts"]),
        "top_k": int(conf_model["num_experts_per_tok"]),
        "expert_width": int(conf_model["moe_intermediate_size"]),
        "shared_experts": int(conf_model["n_shared_experts"]),
        "routed_scale": float(conf_model["routed_scaling_factor"]),
        "renormalize": bool(conf_model["norm_topk_prob"]),
        "mtp_modules": int(conf_model["num_nextn_predict_layers"]),
        "mtp_weight": float(conf_model.get("mtp_loss_weight", 0.3)),
    }


def _held(model: dict) -> tuple[int, int, int]:
    return (int(model.get("layers_held") or model["layers"]),
            int(model.get("experts_held") or model["experts"]),
            int(model.get("ids_held") or model["vocab"]))


def mla_mixer_matrices(model: dict) -> int:
    """The five products' parameters (no norms)."""
    hidden, heads = model["hidden"], model["heads"]
    return (hidden * model["q_rank"]
            + model["q_rank"] * heads * (model["nope_dim"] + model["pe_dim"])
            + hidden * (model["kv_rank"] + model["pe_dim"])
            + model["kv_rank"] * heads * (model["nope_dim"] + model["v_dim"])
            + heads * model["v_dim"] * hidden)


def mla_mixer_params(model: dict) -> int:
    return mla_mixer_matrices(model) + model["q_rank"] + model["kv_rank"]


def expert_params(model: dict) -> int:
    return 3 * model["hidden"] * model["expert_width"]


def expert_block_params(model: dict, experts: int) -> int:
    """A block of the expert layers' kind with `experts` routed experts
    held: two norms, the mixer, router and bias, the routed and the shared
    experts."""
    hidden = model["hidden"]
    return (2 * hidden + mla_mixer_params(model)
            + hidden * model["experts"] + model["experts"]
            + (experts + model["shared_experts"]) * expert_params(model))


def mtp_params(model: dict, experts: int) -> int:
    """One module: three norms, ``eh_proj`` and its block (embedding and
    head are the main model's)."""
    hidden = model["hidden"]
    return 3 * hidden + 2 * hidden * hidden + expert_block_params(model, experts)


def num_params(model: dict) -> int:
    """Trainable parameters of what this chip holds, the module with its
    held experts included."""
    layers, experts, ids = _held(model)
    hidden = model["hidden"]
    total = 2 * ids * hidden + hidden             # embedding, head, final norm
    for layer in range(1, layers + 1):
        if layer <= model["dense_layers"]:
            total += (2 * hidden + mla_mixer_params(model)
                      + 3 * hidden * model["dense_width"])
        else:
            total += expert_block_params(model, experts)
    return total + model["mtp_modules"] * mtp_params(model, experts)


def mla_attention_operations(model: dict, tokens: int, *, backward: bool) -> float:
    """The attention core of one layer over a sequence of `tokens`: the
    causal half of the two products, ``tokens^2 / 2 * heads * (qk + v)``
    multiply-accumulates forward (scores over ``qk = nope + rope``, the
    weighted sum over ``v``); backward four products against two; nothing
    for what is computed again."""
    width = model["nope_dim"] + model["pe_dim"] + model["v_dim"]
    forward = 2.0 * model["heads"] * width * tokens * tokens / 2
    return (2.0 if backward else 1.0) * forward


def mla_attention_bytes(model: dict, tokens: int, *, backward: bool) -> float:
    """float32 ``q`` (nope + rope a head), ``k`` (nope a head and the one
    shared rope part), ``v`` and the output read or written once forward;
    backward reads them and the output's gradient and writes the three
    gradients."""
    heads = model["heads"]
    q = heads * (model["nope_dim"] + model["pe_dim"])
    k = heads * model["nope_dim"] + model["pe_dim"]
    v = out = heads * model["v_dim"]
    floats = (2 * (q + k + v) + 2 * out) if backward else (q + k + v + out)
    return 4.0 * floats * tokens


def moe_experts_operations(model: dict, assignments: float, *, backward: bool) -> float:
    """The three products of the experts `assignments` token-to-expert
    assignments reached (the program's counter), one expert layer."""
    return (2.0 if backward else 1.0) * 2.0 * expert_params(model) * assignments


def moe_experts_bytes(model: dict, assignments: float, *, backward: bool) -> float:
    """The held experts' float32 weights once a pass (backward: read, and
    their gradient written), and a row in and a row out an assignment."""
    _, experts, _ = _held(model)
    weights = 4.0 * experts * expert_params(model)
    rows = 4.0 * 2 * model["hidden"] * assignments
    return (2.0 if backward else 1.0) * (weights + rows)


def _block_flops(model: dict, tokens: int, experts: int, dense: bool) -> float:
    """One block over a sequence: its projections, the attention core and
    its FFN."""
    hidden = model["hidden"]
    per_token = 2.0 * mla_mixer_matrices(model)
    if dense:
        per_token += 2.0 * 3 * hidden * model["dense_width"]
    else:
        routed = model["top_k"] * experts / model["experts"]
        per_token += 2.0 * (hidden * model["experts"]
                            + (routed + model["shared_experts"]) * expert_params(model))
    return per_token * tokens + mla_attention_operations(model, tokens, backward=False)


def forward_flops_per_image(model: dict, *, training: bool = False) -> float:
    """One sequence of ``seq_len`` tokens through the forward pass: the
    main model and its head; with `training` the module and the head once
    more."""
    layers, experts, ids = _held(model)
    tokens = int(model["seq_len"])
    hidden = model["hidden"]
    head = 2.0 * hidden * ids * tokens
    total = head + sum(_block_flops(model, tokens, experts, layer <= model["dense_layers"])
                       for layer in range(1, layers + 1))
    if training:
        total += model["mtp_modules"] * (
            2.0 * 2 * hidden * hidden * tokens
            + _block_flops(model, tokens, experts, False) + head)
    return total


def train_flops_per_image(model: dict) -> float:
    """Forward plus backward of a training step, the module and the second
    head counted: three such forward passes' worth."""
    return 3.0 * forward_flops_per_image(model, training=True)
