"""Operations of Nemotron-H from its shapes alone, an example being one
sequence; and what the mathematics of two of its kernels needs.

Nemotron 3 Nano 30B-A3B (``config.json`` of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type: nemotron_h``) as
this system runs it (``models/nemotron_h.py``): layers of one norm and one
mixer by a pattern string — Mamba-2 (``M``), grouped-query attention
(``*``), routed experts of two matrices and a squared ReLU beside a shared
expert (``E``) — a final norm and an untied head, over the layers, experts
and ids this chip holds.

Counted, two operations a multiply-accumulate: every projection; the
state-space scan (:func:`ssd_scan_operations`); the attention's scores and
weighted values over the causal half; the router over all its experts; the
routed experts at the *expected* ``top_k * held / experts`` assignments a
token; the shared expert; the head.  Not counted: norms, the convolution's
four taps, gates' activations, the softmax, the embedding's gathers, the
loss.  A backward pass is taken as twice the forward pass; what
``nn.remat`` and the blocked loss compute again is not counted.

The kernels' functions count what the *mathematics* needs, not what an
implementation does (``<kernel>_operations``, ``<kernel>_bytes``), so a
later kernel is read against the same work and a share of the roofline
cannot pass 100% by a cheaper form.
"""

from __future__ import annotations

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def model_from_conf(conf_model: dict) -> dict:
    """The sizes the functions below and the reference need, from the
    conf's ``model`` mapping (the published ``config.json``'s keys)."""
    if conf_model.get("type") != "nemotron_h":
        raise ValueError(f"not a nemotron_h model: {conf_model.get('type')!r}")
    return {
        "hidden": int(conf_model["hidden_size"]),
        "eps": float(conf_model["layer_norm_epsilon"]),
        "layers": int(conf_model["num_hidden_layers"]),
        "pattern": str(conf_model["hybrid_override_pattern"]),
        "vocab": int(conf_model["vocab_size"]),
        "mamba_heads": int(conf_model["mamba_num_heads"]),
        "mamba_head_dim": int(conf_model["mamba_head_dim"]),
        "mamba_groups": int(conf_model["n_groups"]),
        "state_size": int(conf_model["ssm_state_size"]),
        "conv_taps": int(conf_model["conv_kernel"]),
        "chunk": int(conf_model["chunk_size"]),
        "heads": int(conf_model["num_attention_heads"]),
        "kv_heads": int(conf_model["num_key_value_heads"]),
        "head_dim": int(conf_model["head_dim"]),
        "experts": int(conf_model["n_routed_experts"]),
        "top_k": int(conf_model["num_experts_per_tok"]),
        "expert_width": int(conf_model["moe_intermediate_size"]),
        "shared_experts": int(conf_model["n_shared_experts"]),
        "shared_width": int(conf_model["moe_shared_expert_intermediate_size"]),
        "routed_scale": float(conf_model["routed_scaling_factor"]),
        "renormalize": bool(conf_model["norm_topk_prob"]),
    }


def _held(model: dict) -> tuple[str, int, int]:
    """``(the held layers' kinds, experts held, ids held)``."""
    layers = int(model.get("layers_held") or model["layers"])
    return (model["pattern"][:layers],
            int(model.get("experts_held") or model["experts"]),
            int(model.get("ids_held") or model["vocab"]))


def held_layers(model: dict, kind: str) -> int:
    """How many layers of `kind` (a character of the pattern) this chip
    holds."""
    return _held(model)[0].count(kind)


def _mamba_widths(model: dict) -> tuple[int, int]:
    """``(inner width, the convolution's channels)``."""
    inner = model["mamba_heads"] * model["mamba_head_dim"]
    return inner, inner + 2 * model["mamba_groups"] * model["state_size"]


def mamba_matrices(model: dict) -> int:
    """``in_proj`` (``z``, ``xBC``, ``dt``) and ``out_proj``."""
    inner, channels = _mamba_widths(model)
    return model["hidden"] * (inner + channels + model["mamba_heads"] + inner)


def mamba_params(model: dict) -> int:
    """The mixer whole: its two matrices, the convolution's taps and bias,
    ``dt_bias``, ``A_log`` and ``D`` a head, the gated norm's weight."""
    inner, channels = _mamba_widths(model)
    return (mamba_matrices(model) + (model["conv_taps"] + 1) * channels
            + 3 * model["mamba_heads"] + inner)


def attention_matrices(model: dict) -> int:
    width = model["head_dim"]
    return model["hidden"] * width * 2 * (model["heads"] + model["kv_heads"])


def expert_params(model: dict) -> int:
    """One routed expert: two matrices."""
    return 2 * model["hidden"] * model["expert_width"]


def shared_expert_params(model: dict) -> int:
    return model["shared_experts"] * 2 * model["hidden"] * model["shared_width"]


def expert_layer_params(model: dict, experts: int) -> int:
    """An expert layer's mixer with `experts` routed experts held: router
    and correction bias, the routed and the shared experts."""
    return (model["hidden"] * model["experts"] + model["experts"]
            + experts * expert_params(model) + shared_expert_params(model))


def num_params(model: dict) -> int:
    """Trainable parameters of what this chip holds (the routers'
    correction biases, which no gradient reaches, included)."""
    kinds, experts, ids = _held(model)
    hidden = model["hidden"]
    mixer = {MAMBA: mamba_params(model), ATTENTION: attention_matrices(model),
             EXPERTS: expert_layer_params(model, experts)}
    return (2 * ids * hidden + hidden             # embedding, head, final norm
            + sum(hidden + mixer[kind] for kind in kinds))


def ssd_scan_operations(model: dict, tokens: int, *, backward: bool) -> float:
    """The state-space scan of one Mamba-2 layer over `tokens` tokens in
    its chunked form at the published chunk ``L`` (whatever implements
    it), two operations a multiply-accumulate.  Forward, a token: the
    chunk's Gram row ``C_t . B_s`` a *group* (``L N`` multiply-accumulates
    over the whole square: the mask is applied to it, not taken out of
    it), its product with the chunk's ``x`` a head (``L P``), the token's
    share of the chunk's state (``x_t (x) B_t``, ``P N`` a head) and the
    read of the chunk's start state through ``C_t`` (``P N`` a head).
    Backward twice that: every product's two cotangents.  Decays, running
    sums and the ``D`` skip are not counted."""
    chunk = min(model["chunk"], tokens)
    width, size = model["mamba_head_dim"], model["state_size"]
    per_token = (model["mamba_groups"] * chunk * size
                 + model["mamba_heads"] * (chunk * width + 2 * width * size))
    return (2.0 if backward else 1.0) * 2.0 * per_token * tokens


def ssd_scan_bytes(model: dict, tokens: int, *, backward: bool) -> float:
    """float32 ``x`` and the output (a head's width each), ``B`` and ``C``
    (a group's state size each) and the step (one a head), read or written
    once forward; backward reads them and the output's gradient and writes
    the four gradients.  The chunk states are the form's own and not
    counted."""
    inner, channels = _mamba_widths(model)
    operands = channels + model["mamba_heads"]            # x, B, C, dt
    floats = (2 * operands + inner) if backward else (operands + inner)
    return 4.0 * floats * tokens


def attention_operations(model: dict, tokens: int) -> float:
    """The causal half of the two products, forward."""
    return 2.0 * model["heads"] * 2 * model["head_dim"] * tokens * tokens / 2


def moe_experts_operations(model: dict, assignments: float, *, backward: bool) -> float:
    """The two products of the experts `assignments` token-to-expert
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
    kinds, experts, ids = _held(model)
    tokens = int(model["seq_len"])
    hidden = model["hidden"]
    routed = model["top_k"] * experts / model["experts"]
    per_token = {
        MAMBA: 2.0 * mamba_matrices(model),
        ATTENTION: 2.0 * attention_matrices(model),
        EXPERTS: 2.0 * (hidden * model["experts"] + routed * expert_params(model)
                        + shared_expert_params(model))}
    whole = {MAMBA: ssd_scan_operations(model, tokens, backward=False),
             ATTENTION: attention_operations(model, tokens), EXPERTS: 0.0}
    return (2.0 * hidden * ids * tokens
            + sum(per_token[kind] * tokens + whole[kind] for kind in kinds))


def train_flops_per_image(model: dict) -> float:
    """Forward plus backward of a training step: three forward passes'
    worth."""
    return 3.0 * forward_flops_per_image(model)
