"""Operations and bytes of the mixers' projections, by the mathematics.

The program puts a mixer's products with a weight matrix under one scope,
``faa_mixer_proj`` (``models/token_blocks.py::proj``).  This module counts
what those products need in one train step from a configuration's ``model``
block of sizes and the tokens a step, over the layers this chip holds: a
product of ``T`` tokens from ``n`` to ``m`` channels is ``2 T n m``
operations forward and twice that backward (the input's and the matrix's
gradients); forward it reads ``x`` and ``W`` and writes ``y``, backward it
reads the cotangent, ``x`` and ``W`` and writes ``dx`` and ``dW``, every
array once in float32, the precision the configurations state.  Nothing
for what ``nn.remat`` computes again, as the other ``*_roofline`` readers
count.  The count is of the mathematics whatever implements it.

The tables are by the family a configuration names as its operations file
(``"flops"``) and the kind a layer has in its ``model`` block
(``layer_types``, or a character of ``pattern``): grouped-query attention
with and without the gate on its output, the doubly gated short
convolution, Mamba-2.  A family without a table here (latent attention,
KDA) gives None.
"""

from __future__ import annotations

BYTES = 4  # float32


def gqa_products(model: dict, gated: bool) -> list[tuple[int, int]]:
    """``q_proj``, ``k_proj``, ``v_proj``, ``gate_proj`` where the output is
    gated, ``o_proj``, each as ``(in, out)`` channels."""
    hidden = int(model["hidden"])
    queries = int(model["heads"]) * int(model["head_dim"])
    keys = int(model["kv_heads"]) * int(model["head_dim"])
    return ([(hidden, queries), (hidden, keys), (hidden, keys)]
            + ([(hidden, queries)] if gated else []) + [(queries, hidden)])


def short_conv_products(model: dict) -> list[tuple[int, int]]:
    """``in_proj`` (hidden to ``B``, ``C`` and ``z``) and ``out_proj``."""
    hidden = int(model["hidden"])
    return [(hidden, 3 * hidden), (hidden, hidden)]


def mamba2_products(model: dict) -> list[tuple[int, int]]:
    """``in_proj`` (hidden to ``z``, ``x``, ``B``, ``C`` and a step a head)
    and ``out_proj``."""
    hidden, heads = int(model["hidden"]), int(model["mamba_heads"])
    inner = heads * int(model["mamba_head_dim"])
    state = int(model["mamba_groups"]) * int(model["state_size"])
    return [(hidden, 2 * inner + 2 * state + heads), (inner, hidden)]


#: family -> (the key of ``model`` that lists the layers' kinds, {kind: the
#: products of that kind's mixer}); a kind that is not named has no mixer
#: (Nemotron-H's ``E``, an expert layer)
FAMILIES = {
    "afmoe": ("layer_types", {
        "sliding_attention": lambda model: gqa_products(model, gated=True),
        "full_attention": lambda model: gqa_products(model, gated=True)}),
    "lfm2_moe": ("layer_types", {
        "conv": short_conv_products,
        "full_attention": lambda model: gqa_products(model, gated=False)}),
    "nemotron_h": ("pattern", {
        "M": mamba2_products,
        "*": lambda model: gqa_products(model, gated=False)}),
}


def held_products(family: str, model: dict) -> list[tuple[int, int]] | None:
    """``(in, out)`` of every product under ``faa_mixer_proj`` in the layers
    this chip holds (the first ``layers_held``); None for a family without
    a table."""
    if family not in FAMILIES:
        return None
    key, mixers = FAMILIES[family]
    kinds = list(model[key])[:int(model["layers_held"])]
    return [product for kind in kinds if kind in mixers
            for product in mixers[kind](model)]


def operations(products: list[tuple[int, int]], tokens: float, *,
               backward: bool) -> float:
    """Operations of `products` over `tokens` tokens in one pass."""
    return (2.0 if backward else 1.0) * sum(
        2.0 * tokens * n * m for n, m in products)


def moved_bytes(products: list[tuple[int, int]], tokens: float, *,
                backward: bool) -> float:
    """Bytes `products` read and write over `tokens` tokens in one pass."""
    if backward:  # the cotangent, x and W read; dx and dW written
        return BYTES * sum(tokens * m + 2 * tokens * n + 2 * n * m
                           for n, m in products)
    return BYTES * sum(tokens * n + n * m + tokens * m for n, m in products)
