"""Operations of Kimi Linear from its shapes alone, an example being one
sequence; and what the mathematics of its two kernels needs.

Kimi Linear (arXiv:2510.26692; ``config.json`` of
moonshotai/Kimi-Linear-48B-A3B-Instruct) as this system runs it
(``models/kimi_linear.py``): per layer a mixer — KDA (projections, short
convolutions, the gated delta-rule recurrence, output gate) or latent
attention without rotary — and an FFN — dense SwiGLU in the leading
layers, then routed experts beside a shared one — a final norm and an
untied head, over the layers, experts and ids this chip holds.

Counted, two operations a multiply-accumulate: every projection; the
recurrence as the recurrence (7 K V operations a token a head: decay,
read, correction, output); the attention's scores and weighted values
over the causal half; the router over all its experts; the routed
experts at the *expected* ``top_k * held / experts`` assignments a token;
the shared expert; the head.  Not counted: norms, convolutions' 4 taps,
gates' activations, the softmax, the embedding's gather, the loss.  A
backward pass is taken as twice the forward pass; what ``nn.remat``
computes again is not counted.

The kernels' functions count what the *mathematics* needs, not what an
implementation does (``<kernel>_operations``, ``<kernel>_bytes``), so a
later kernel is read against the same work and a share of the roofline
cannot pass 100% by a cheaper form.
"""

from __future__ import annotations


def model_from_conf(conf_model: dict) -> dict:
    """The sizes the functions below and the reference need, from the
    conf's ``model`` mapping (the published ``config.json``'s keys)."""
    if conf_model.get("type") != "kimi_linear":
        raise ValueError(f"not a Kimi Linear model: {conf_model.get('type')!r}")
    linear = conf_model["linear_attn_config"]
    return {
        "hidden": int(conf_model["hidden_size"]),
        "eps": float(conf_model["rms_norm_eps"]),
        "layers": int(conf_model["num_hidden_layers"]),
        "vocab": int(conf_model["vocab_size"]),
        "kda_layers": [int(i) for i in linear["kda_layers"]],
        "kda_heads": int(linear["num_heads"]),
        "kda_head_dim": int(linear["head_dim"]),
        "conv_taps": int(linear["short_conv_kernel_size"]),
        "heads": int(conf_model["num_attention_heads"]),
        "nope_dim": int(conf_model["qk_nope_head_dim"]),
        "pe_dim": int(conf_model["qk_rope_head_dim"]),
        "v_dim": int(conf_model["v_head_dim"]),
        "kv_rank": int(conf_model["kv_lora_rank"]),
        "dense_layers": int(conf_model["first_k_dense_replace"]),
        "dense_width": int(conf_model["intermediate_size"]),
        "experts": int(conf_model["num_experts"]),
        "top_k": int(conf_model["num_experts_per_token"]),
        "expert_width": int(conf_model["moe_intermediate_size"]),
        "shared_experts": int(conf_model["num_shared_experts"]),
        "routed_scale": float(conf_model["routed_scaling_factor"]),
        "renormalize": bool(conf_model["moe_renormalize"]),
    }


def _held(model: dict) -> tuple[int, int, int]:
    return (int(model.get("layers_held") or model["layers"]),
            int(model.get("experts_held") or model["experts"]),
            int(model.get("ids_held") or model["vocab"]))


def _is_kda(model: dict, layer: int) -> bool:
    return layer in model["kda_layers"]


def kda_mixer_params(model: dict) -> int:
    hidden, width, dim = (model["hidden"], model["kda_heads"] * model["kda_head_dim"],
                          model["kda_head_dim"])
    matrices = (4 * hidden * width                # q, k, v, o
                + 2 * (hidden * dim + dim * width)  # the decay's and the gate's pairs
                + hidden * model["kda_heads"])      # beta
    return (matrices + 3 * model["conv_taps"] * width + model["kda_heads"]
            + width + dim)                        # taps, A_log, dt_bias, o_norm


def mla_mixer_params(model: dict) -> int:
    hidden, heads = model["hidden"], model["heads"]
    return (hidden * heads * (model["nope_dim"] + model["pe_dim"])
            + hidden * (model["kv_rank"] + model["pe_dim"])
            + model["kv_rank"] * heads * (model["nope_dim"] + model["v_dim"])
            + heads * model["v_dim"] * hidden + model["kv_rank"])


def expert_params(model: dict) -> int:
    return 3 * model["hidden"] * model["expert_width"]


def num_params(model: dict) -> int:
    """Trainable parameters of what this chip holds."""
    layers, experts, ids = _held(model)
    hidden = model["hidden"]
    total = 2 * ids * hidden + hidden             # embedding, head, final norm
    for layer in range(1, layers + 1):
        total += 2 * hidden                       # the block's two norms
        total += (kda_mixer_params(model) if _is_kda(model, layer)
                  else mla_mixer_params(model))
        if layer <= model["dense_layers"]:
            total += 3 * hidden * model["dense_width"]
        else:
            total += (hidden * model["experts"] + model["experts"]   # router, bias
                      + (experts + model["shared_experts"]) * expert_params(model))
    return total


def kda_scan_operations(model: dict, tokens: int, *, backward: bool) -> float:
    """The recurrence of one KDA layer over `tokens` tokens: ``7 K V`` a
    token a head forward (decay K V, read 2 K V, write 2 K V, output 2 K V),
    twice that backward."""
    dim = model["kda_head_dim"]
    return (2.0 if backward else 1.0) * 7.0 * dim * dim * model["kda_heads"] * tokens


def kda_scan_bytes(model: dict, tokens: int, *, backward: bool) -> float:
    """float32 ``q, k, g`` (K a head), ``v, o`` (V) and ``beta`` (1) read or
    written once forward; backward reads the five inputs and ``do`` and
    writes five gradients."""
    dim = model["kda_head_dim"]
    inputs, out = 4 * dim + 1, dim
    floats = (2 * inputs + out) if backward else (inputs + out)
    return 4.0 * floats * model["kda_heads"] * tokens


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


def forward_flops_per_image(model: dict) -> float:
    """One sequence of ``seq_len`` tokens through the forward pass."""
    layers, experts, ids = _held(model)
    tokens = int(model["seq_len"])
    hidden = model["hidden"]
    per_token = 2.0 * hidden * ids                # the head
    total = 0.0
    for layer in range(1, layers + 1):
        if _is_kda(model, layer):
            width, dim = model["kda_heads"] * model["kda_head_dim"], model["kda_head_dim"]
            per_token += 2.0 * (4 * hidden * width + 2 * (hidden * dim + dim * width)
                                + hidden * model["kda_heads"])
            total += kda_scan_operations(model, tokens, backward=False)
        else:
            heads = model["heads"]
            per_token += 2.0 * (mla_mixer_params(model) - model["kv_rank"])
            # token t meets t + 1 keys: scores and weighted values
            total += (2.0 * heads * (model["nope_dim"] + model["pe_dim"] + model["v_dim"])
                      * tokens * (tokens + 1) / 2)
        if layer <= model["dense_layers"]:
            per_token += 2.0 * 3 * hidden * model["dense_width"]
        else:
            routed = model["top_k"] * experts / model["experts"]
            per_token += 2.0 * (hidden * model["experts"]
                                + (routed + model["shared_experts"]) * expert_params(model))
    return total + per_token * tokens


def train_flops_per_image(model: dict) -> float:
    """Forward plus backward: three forward passes' worth."""
    return 3.0 * forward_flops_per_image(model)
