"""The grouped-query attention cores' share of their roofline: the least time the chip could
take for what the *mathematics* of the cores needs in one train step — the larger of their
operations over the chip's bfloat16 peak and their bytes over the memory's peak
(``gqa_attention_operations`` / ``gqa_attention_bytes`` of the configuration's operations
file: the two products over the pairs a query *sees*, the band ``T W - W (W - 1) / 2`` of a
window layer and the triangle ``T (T + 1) / 2`` of a full one, ``heads * (qk + v)``
multiply-accumulates a pair forward and twice that backward, nothing for what is computed
again; ``q``, ``k``, ``v``, the output and their gradients read or written once, the
key-value heads not repeated), summed over the blocks this chip holds by each one's kind —
over the median device time of a step under ``faa_gqa_attention`` (``models/token_blocks.py::
GQAMixer`` round ``ops/attention.py::blocked_causal_attention``: the repeat of the key-value
heads, the kernels, forward, backward and what ``nn.remat`` computes again).  The count is
of the mathematics whatever implements it: a kernel that masks the band and does not skip
it does more than is counted and reads lower.  The operations bound it at these sizes.  A
program from before the scope (``core/scopes.py::GQA_ATTENTION``, PR 46), or a
configuration whose operations file has no such functions, has nothing to read."""

from benchmarks.harness.device import peaks_for
from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "%", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "GQA_ATTENTION", None)
    tokens = obs.work.get("tokens_a_step")
    flops = obs.cell.module("flops", obs.cell.config["flops"])
    if scope is None or not tokens or not hasattr(flops, "gqa_attention_operations"):
        return None
    measured_ms = scope_ms(obs, scope)
    if not measured_ms:
        return None
    model = obs.cell.config["model"]
    kinds = list(model["layer_types"])[:int(model["layers_held"])]
    # an example is one sequence: the cores see a sequence's length at a time
    length, sequences = int(model["seq_len"]), tokens / int(model["seq_len"])
    operations = sequences * sum(
        flops.gqa_attention_operations(model, kind, length, backward=b)
        for kind in kinds for b in (False, True))
    moved = sequences * sum(
        flops.gqa_attention_bytes(model, kind, length, backward=b)
        for kind in kinds for b in (False, True))
    peaks = peaks_for(obs.devices[0].device_kind)
    least_s = max(operations / peaks["bf16_flops_per_s"],
                  moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ms / 1e3)
