"""Operations of lfm2_moe from its shapes alone, an example being one
sequence; and what the mathematics of its attention cores, of its
short-convolution mixers' gates and taps and of its held experts needs.

LFM2-8B-A1B (``config.json`` of LiquidAI/LFM2-8B-A1B, ``model_type:
lfm2_moe``) as this system runs it (``models/lfm2_moe.py``): the embedding,
blocks of a mixer (a doubly gated short convolution or rotary grouped-query
attention, by ``layer_types``) and a feed-forward (a dense SwiGLU in the
leading blocks, routed SwiGLU experts with no shared one after them) under
two norms, a final norm and the head the embedding's transpose (one table,
counted once), over the blocks, experts and ids this chip holds.

Counted, two operations a multiply-accumulate: a convolution mixer's two
projections and, a channel a token, its two gates and three taps (8
operations: :func:`short_conv_gate_operations`); an attention mixer's four
projections and its scores and weighted values over the triangle ``T (T +
1) / 2`` (:func:`visible_pairs`); the dense feed-forward; the router over
all its experts; the routed experts at the *expected* ``top_k * held /
experts`` assignments a token; the head.  Not counted: norms, rotary, the
feed-forwards' activations, the softmax, the embedding's gathers, the loss.
A backward pass is taken as twice the forward pass; what ``nn.remat`` and
the blocked loss compute again is not counted.

The cores', the gates' and the experts' functions count what the
*mathematics* needs, not what an implementation does, so a later kernel is
read against the same work and a share of the roofline cannot pass 100% by
a cheaper form: the fused attention kernels' half-empty 128-wide products
on a head of 64 do more than is counted here and read lower, never higher.
"""

from __future__ import annotations

CONV, FULL = "conv", "full_attention"


def model_from_conf(conf_model: dict) -> dict:
    """The sizes the functions below and the reference need, from the
    conf's ``model`` mapping (the published ``config.json``'s keys)."""
    if conf_model.get("type") != "lfm2_moe":
        raise ValueError(f"not an lfm2_moe model: {conf_model.get('type')!r}")
    hidden, heads = int(conf_model["hidden_size"]), int(conf_model["num_attention_heads"])
    return {
        "hidden": hidden,
        "eps": float(conf_model["norm_eps"]),
        "layers": int(conf_model["num_hidden_layers"]),
        "layer_types": list(conf_model["layer_types"]),
        "vocab": int(conf_model["vocab_size"]),
        "heads": heads,
        "kv_heads": int(conf_model["num_key_value_heads"]),
        "head_dim": int(conf_model.get("head_dim") or hidden // heads),
        "rope_theta": float(conf_model["rope_theta"]),
        "taps": int(conf_model["conv_L_cache"]),
        "dense_layers": int(conf_model["num_dense_layers"]),
        "dense_width": int(conf_model["intermediate_size"]),
        "experts": int(conf_model["num_experts"]),
        "top_k": int(conf_model["num_experts_per_tok"]),
        "expert_width": int(conf_model["moe_intermediate_size"]),
        "routed_scale": float(conf_model["routed_scaling_factor"]),
        "renormalize": bool(conf_model["norm_topk_prob"]),
        "renorm_eps": 1e-6,
        "tied_head": bool(conf_model.get("tie_word_embeddings", True)),
    }


def _held(model: dict) -> tuple[list, int, int]:
    """``(the held blocks' kinds, experts held, ids held)``."""
    layers = int(model.get("layers_held") or model["layers"])
    return (list(model["layer_types"])[:layers],
            int(model.get("experts_held") or model["experts"]),
            int(model.get("ids_held") or model["vocab"]))


def held_layers(model: dict, kind: str) -> int:
    """How many blocks whose mixer is of `kind` (a value of
    ``layer_types``) this chip holds."""
    return _held(model)[0].count(kind)


def held_expert_layers(model: dict) -> int:
    return max(len(_held(model)[0]) - model["dense_layers"], 0)


def conv_mixer_matrices(model: dict) -> int:
    """``in_proj`` hidden -> 3 hidden and ``out_proj`` hidden -> hidden."""
    return 4 * model["hidden"] ** 2


def attention_mixer_matrices(model: dict) -> int:
    """``q_proj`` and ``o_proj`` at ``heads * head_dim``, ``k_proj`` and
    ``v_proj`` at ``kv_heads * head_dim``."""
    return model["hidden"] * model["head_dim"] * 2 * (model["heads"] + model["kv_heads"])


def mixer_matrices(model: dict, kind: str) -> int:
    return conv_mixer_matrices(model) if kind == CONV else attention_mixer_matrices(model)


def mixer_params(model: dict, kind: str) -> int:
    """A convolution mixer's two matrices and its taps; an attention
    mixer's four matrices and the two norms a head."""
    if kind == CONV:
        return conv_mixer_matrices(model) + model["taps"] * model["hidden"]
    return attention_mixer_matrices(model) + 2 * model["head_dim"]


def dense_ffn_params(model: dict) -> int:
    return 3 * model["hidden"] * model["dense_width"]


def expert_params(model: dict) -> int:
    """One routed expert: three matrices."""
    return 3 * model["hidden"] * model["expert_width"]


def expert_layer_params(model: dict, experts: int) -> int:
    """An expert layer's feed-forward with `experts` routed experts held:
    router, correction bias and the routed experts; no shared expert."""
    return (model["hidden"] * model["experts"] + model["experts"]
            + experts * expert_params(model))


def num_params(model: dict) -> int:
    """Trainable parameters of what this chip holds (the routers'
    correction biases, which no gradient reaches, included); the head is
    the embedding's table again unless ``tied_head`` is false."""
    kinds, experts, ids = _held(model)
    hidden = model["hidden"]
    dense = min(model["dense_layers"], len(kinds))
    tables = 1 if model.get("tied_head", True) else 2
    return (tables * ids * hidden + hidden        # the table(s), final norm
            + sum(2 * hidden + mixer_params(model, kind) for kind in kinds)
            + dense * dense_ffn_params(model)
            + (len(kinds) - dense) * expert_layer_params(model, experts))


def visible_pairs(model: dict, kind: str, tokens: int) -> int:
    """Pairs of a query and a key it sees, over `tokens` tokens of one
    head: the triangle with its diagonal in an attention block, none in a
    convolution block."""
    del model
    return tokens * (tokens + 1) // 2 if kind == FULL else 0


def gqa_attention_operations(model: dict, kind: str, tokens: int, *,
                             backward: bool) -> float:
    """The core of one mixer of `kind` over `tokens` tokens: the two
    products (scores, weighted values) over the pairs a query sees, two
    operations a multiply-accumulate at a head's own width (64 + 64: the
    mathematics pads no head to a row of lanes); backward twice that,
    nothing for what is computed again.  A convolution block has none."""
    per_pair = model["heads"] * 2 * model["head_dim"]
    return (2.0 if backward else 1.0) * 2.0 * per_pair * visible_pairs(
        model, kind, tokens)


def gqa_attention_bytes(model: dict, kind: str, tokens: int, *,
                        backward: bool) -> float:
    """float32 ``q`` and the output (``heads`` of ``head_dim``), ``k`` and
    ``v`` (``kv_heads``: the mathematics repeats no head), read or written
    once forward; backward reads them and the output's gradient and writes
    the three gradients.  A convolution block has none."""
    if kind != FULL:
        return 0.0
    query, key_value = (model[name] * model["head_dim"] for name in ("heads", "kv_heads"))
    operands = query + 2 * key_value                       # q, k, v
    floats = (2 * operands + 2 * query) if backward else (operands + query)
    return 4.0 * floats * tokens


def short_conv_gate_operations(model: dict, tokens: int, *, backward: bool) -> float:
    """What lies between one convolution mixer's two projections, a
    channel a token: ``B * z``, ``taps`` multiply-accumulates, ``C * c`` —
    8 operations at three taps; backward twice that."""
    per_channel = 2 + 2 * model["taps"]
    return (2.0 if backward else 1.0) * per_channel * model["hidden"] * tokens


def short_conv_gate_bytes(model: dict, tokens: int, *, backward: bool) -> float:
    """float32 words of ``hidden`` a token: forward ``B``, ``C``, ``z``
    read and the gated product written (4); backward the cotangent and
    ``B``, ``C``, ``z`` read and their three gradients written (7); nothing
    for what is computed again, nor for the taps themselves."""
    return 4.0 * (7 if backward else 4) * model["hidden"] * tokens


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
    kinds, experts, ids = _held(model)
    tokens = int(model["seq_len"])
    hidden = model["hidden"]
    dense = min(model["dense_layers"], len(kinds))
    routed = model["top_k"] * experts / model["experts"]
    per_token = (2.0 * hidden * ids
                 + sum(2.0 * mixer_matrices(model, kind) for kind in kinds)
                 + dense * 2.0 * dense_ffn_params(model)
                 + (len(kinds) - dense) * 2.0 * (
                     hidden * model["experts"] + routed * expert_params(model)))
    return per_token * tokens + sum(
        gqa_attention_operations(model, kind, tokens, backward=False)
        if kind == FULL else short_conv_gate_operations(model, tokens, backward=False)
        for kind in kinds)


def train_flops_per_image(model: dict) -> float:
    """Forward plus backward of a training step: three forward passes'
    worth."""
    return 3.0 * forward_flops_per_image(model)
