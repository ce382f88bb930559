"""The short-convolution mixers' gates and taps' share of their roofline: the least time the
chip could take for what lies between the mixers' two projections in one train step — the
larger of its operations over the chip's bfloat16 peak and its bytes over the memory's peak
(``short_conv_gate_operations`` / ``short_conv_gate_bytes`` of the configuration's
operations file: a channel a token two gates and three multiply-accumulates forward, twice
that backward; float32 ``B``, ``C``, ``z`` read and the gated product written forward, the
cotangent and ``B``, ``C``, ``z`` read and their three gradients written backward, nothing
for what is computed again), summed over the convolution blocks this chip holds — over the
median device time of a step under ``faa_short_conv_gate`` (``models/token_blocks.py::
ShortConvMixer``: the split, ``B * z``, the shifted reads of the taps, ``C * c``, forward,
backward and what ``nn.remat`` computes again).  The bytes bound it: 1.48 GB a layer a step
at 16,384 tokens of 2,048 channels, 1.8 ms at the chip's 819 GB/s.  It says how far XLA's
fusions of the shifted reads are from one pass over the operands.  A program from before the
scope (``core/scopes.py::SHORT_CONV_GATE``, PR 49), or a configuration whose operations file
has no such functions, has nothing to read."""

from benchmarks.harness.device import peaks_for
from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "%", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "SHORT_CONV_GATE", None)
    tokens = obs.work.get("tokens_a_step")
    flops = obs.cell.module("flops", obs.cell.config["flops"])
    if scope is None or not tokens or not hasattr(flops, "short_conv_gate_operations"):
        return None
    measured_ms = scope_ms(obs, scope)
    if not measured_ms:
        return None
    model = obs.cell.config["model"]
    layers = list(model["layer_types"])[:int(model["layers_held"])].count("conv")
    operations = layers * sum(flops.short_conv_gate_operations(model, tokens, backward=b)
                              for b in (False, True))
    moved = layers * sum(flops.short_conv_gate_bytes(model, tokens, backward=b)
                         for b in (False, True))
    peaks = peaks_for(obs.devices[0].device_kind)
    least_s = max(operations / peaks["bf16_flops_per_s"],
                  moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ms / 1e3)
