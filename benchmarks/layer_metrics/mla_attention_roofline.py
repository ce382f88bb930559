"""The latent attention cores' share of their roofline: the least time the chip could take
for what the *mathematics* of the attention cores needs in one train step — the larger of
their operations over the chip's bfloat16 peak and their bytes over the memory's peak
(``mla_attention_operations`` / ``mla_attention_bytes`` of the configuration's operations
file: the causal half of the two products, ``T^2 / 2 * heads * (qk + v)``
multiply-accumulates forward and twice that backward, nothing for what is computed again;
``q``, ``k``, ``v``, the output and their gradients read or written once), over every
latent-attention block this chip holds, the multi-token-prediction module's included — over
the median device time of a step under ``faa_mla_attention`` (``ops/attention.py::
blocked_causal_attention``: scores, softmax, weighted sum; forward, backward and what
``jax.checkpoint`` and ``nn.remat`` compute again).  The program computes 62.5% of the
square where the mathematics needs half and computes scores again in the backward pass, so
a sound reading is far under 100%.  The operations bound it at these sizes.  A program from
before the scope (``core/scopes.py::MLA_ATTENTION``, PR 40), or a configuration whose
operations file has no such functions, has nothing to read."""

from benchmarks.harness.device import peaks_for
from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "%", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "MLA_ATTENTION", None)
    tokens = obs.work.get("tokens_a_step")
    flops = obs.cell.module("flops", obs.cell.config["flops"])
    if scope is None or not tokens or not hasattr(flops, "mla_attention_operations"):
        return None
    measured_ms = scope_ms(obs, scope)
    if not measured_ms:
        return None
    model = obs.cell.config["model"]
    blocks = int(model["layers_held"]) + int(model.get("mtp_modules", 0))
    # an example is one sequence: the cores see a sequence's length at a time
    length, sequences = int(model["seq_len"]), tokens / int(model["seq_len"])
    operations = blocks * sequences * sum(
        flops.mla_attention_operations(model, length, backward=b) for b in (False, True))
    moved = blocks * sequences * sum(
        flops.mla_attention_bytes(model, length, backward=b) for b in (False, True))
    peaks = peaks_for(obs.devices[0].device_kind)
    least_s = max(operations / peaks["bf16_flops_per_s"],
                  moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ms / 1e3)
