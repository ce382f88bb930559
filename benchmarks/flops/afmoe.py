"""Operations of afmoe from its shapes alone, an example being one sequence;
and what the mathematics of its attention cores needs.

Trinity-Mini (``config.json`` of arcee-ai/Trinity-Mini, ``model_type:
afmoe``) as this system runs it (``models/afmoe.py``): the embedding, blocks
of a grouped-query attention mixer (a window layer or a full one, by
``layer_types``) and a feed-forward (a dense SwiGLU in the leading blocks,
routed SwiGLU experts beside a shared one after them) under four norms, a
final norm and an untied head, over the blocks, experts and ids this chip
holds.

Counted, two operations a multiply-accumulate: a mixer's five projections;
the attention's scores and weighted values over the pairs a query *sees* —
the band ``T W - W (W - 1) / 2`` of a window layer, the triangle ``T (T + 1)
/ 2`` of a full one (:func:`visible_pairs`); the dense feed-forward; the
router over all its experts; the routed experts at the *expected* ``top_k *
held / experts`` assignments a token; the shared expert; the head.  Not
counted: norms, rotary, gates' activations, the softmax, the embedding's
gathers and its multiplier, the loss.  A backward pass is taken as twice
the forward pass; what ``nn.remat`` and the blocked loss compute again is
not counted.

The cores' functions count what the *mathematics* needs, not what an
implementation does (``gqa_attention_operations``, ``gqa_attention_bytes``),
so a later kernel is read against the same work and a share of the roofline
cannot pass 100% by a cheaper form: a kernel that masks the band and does
not skip it does more than is counted here and reads lower, never higher.
"""

from __future__ import annotations

WINDOW, FULL = "sliding_attention", "full_attention"


def model_from_conf(conf_model: dict) -> dict:
    """The sizes the functions below and the reference need, from the
    conf's ``model`` mapping (the published ``config.json``'s keys)."""
    if conf_model.get("type") != "afmoe":
        raise ValueError(f"not an afmoe model: {conf_model.get('type')!r}")
    hidden = int(conf_model["hidden_size"])
    return {
        "hidden": hidden,
        "eps": float(conf_model["rms_norm_eps"]),
        "embed_scale": float(hidden) ** 0.5 if conf_model["mup_enabled"] else 1.0,
        "layers": int(conf_model["num_hidden_layers"]),
        "layer_types": list(conf_model["layer_types"]),
        "vocab": int(conf_model["vocab_size"]),
        "heads": int(conf_model["num_attention_heads"]),
        "kv_heads": int(conf_model["num_key_value_heads"]),
        "head_dim": int(conf_model["head_dim"]),
        "rope_theta": float(conf_model["rope_theta"]),
        "window": int(conf_model["sliding_window"]),
        "dense_layers": int(conf_model["num_dense_layers"]),
        "dense_width": int(conf_model["intermediate_size"]),
        "experts": int(conf_model["num_experts"]),
        "top_k": int(conf_model["num_experts_per_tok"]),
        "expert_width": int(conf_model["moe_intermediate_size"]),
        "shared_experts": int(conf_model["num_shared_experts"]),
        "routed_scale": float(conf_model["route_scale"]),
        "renormalize": bool(conf_model["route_norm"]),
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


def mixer_matrices(model: dict) -> int:
    """``q_proj``, ``gate_proj`` and ``o_proj`` at ``heads * head_dim``,
    ``k_proj`` and ``v_proj`` at ``kv_heads * head_dim``."""
    return model["hidden"] * model["head_dim"] * (3 * model["heads"]
                                                   + 2 * model["kv_heads"])


def mixer_params(model: dict) -> int:
    """The five matrices and the two norms a head."""
    return mixer_matrices(model) + 2 * model["head_dim"]


def dense_ffn_params(model: dict) -> int:
    return 3 * model["hidden"] * model["dense_width"]


def expert_params(model: dict) -> int:
    """One routed expert: three matrices."""
    return 3 * model["hidden"] * model["expert_width"]


def shared_expert_params(model: dict) -> int:
    return model["shared_experts"] * expert_params(model)


def expert_layer_params(model: dict, experts: int) -> int:
    """An expert layer's feed-forward with `experts` routed experts held:
    router and correction bias, the routed and the shared experts."""
    return (model["hidden"] * model["experts"] + model["experts"]
            + experts * expert_params(model) + shared_expert_params(model))


def num_params(model: dict) -> int:
    """Trainable parameters of what this chip holds (the routers'
    correction biases, which no gradient reaches, included)."""
    kinds, experts, ids = _held(model)
    hidden = model["hidden"]
    dense = min(model["dense_layers"], len(kinds))
    return (2 * ids * hidden + hidden             # embedding, head, final norm
            + len(kinds) * (4 * hidden + mixer_params(model))
            + dense * dense_ffn_params(model)
            + (len(kinds) - dense) * expert_layer_params(model, experts))


def visible_pairs(model: dict, kind: str, tokens: int) -> int:
    """Pairs of a query and a key it sees, over `tokens` tokens of one
    head: the triangle with its diagonal in a full layer; the band, query
    ``i`` seeing keys ``(i - window, i]``, in a window layer."""
    span = min(model["window"], tokens) if kind == WINDOW else tokens
    return tokens * span - span * (span - 1) // 2


def gqa_attention_operations(model: dict, kind: str, tokens: int, *,
                             backward: bool) -> float:
    """The cores of one mixer of `kind` over `tokens` tokens: the two
    products (scores, weighted values) over the pairs a query sees, two
    operations a multiply-accumulate; backward twice that (every product's
    two cotangents), nothing for what is computed again."""
    per_pair = model["heads"] * 2 * model["head_dim"]
    return (2.0 if backward else 1.0) * 2.0 * per_pair * visible_pairs(
        model, kind, tokens)


def gqa_attention_bytes(model: dict, kind: str, tokens: int, *,
                        backward: bool) -> float:
    """float32 ``q`` and the output (``heads`` of ``head_dim``), ``k`` and
    ``v`` (``kv_heads``: the mathematics repeats no head), read or written
    once forward; backward reads them and the output's gradient and writes
    the three gradients.  The same for either `kind`."""
    del kind
    query, key_value = (model[name] * model["head_dim"] for name in ("heads", "kv_heads"))
    operands = query + 2 * key_value                       # q, k, v
    floats = (2 * operands + 2 * query) if backward else (operands + query)
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


def forward_flops_per_image(model: dict) -> float:
    """One sequence of ``seq_len`` tokens through the forward pass."""
    kinds, experts, ids = _held(model)
    tokens = int(model["seq_len"])
    hidden = model["hidden"]
    dense = min(model["dense_layers"], len(kinds))
    routed = model["top_k"] * experts / model["experts"]
    per_token = (2.0 * hidden * ids
                 + len(kinds) * 2.0 * mixer_matrices(model)
                 + dense * 2.0 * dense_ffn_params(model)
                 + (len(kinds) - dense) * 2.0 * (
                     hidden * model["experts"] + routed * expert_params(model)
                     + shared_expert_params(model)))
    return per_token * tokens + sum(
        gqa_attention_operations(model, kind, tokens, backward=False) for kind in kinds)


def train_flops_per_image(model: dict) -> float:
    """Forward plus backward of a training step: three forward passes'
    worth."""
    return 3.0 * forward_flops_per_image(model)
