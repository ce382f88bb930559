"""The KDA recurrence's share of its roofline: the least time the chip could take for
what the *mathematics* of the recurrence needs in one train step — the larger of its
operations over the chip's bfloat16 peak and its bytes over the memory's peak
(``flops/kimi_linear.py::kda_scan_operations`` / ``kda_scan_bytes``: ``7 K V`` operations
a token a head forward and twice that backward; ``q, k, v, g, beta, o`` and their
gradients read or written once), over every KDA layer this chip holds — over the median
device time of a step under ``faa_kda_scan`` (forward, backward and what ``nn.remat``
computes again, which the mathematics does not count).  The bytes bound it at these
sizes.  A program from before the scope (``core/scopes.py::KDA_SCAN``, PR 35) has
nothing to read."""

from benchmarks.harness.device import peaks_for
from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "%", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "KDA_SCAN", None)
    tokens = obs.work.get("tokens_a_step")
    measured_ms = None if scope is None or not tokens else scope_ms(obs, scope)
    if not measured_ms:
        return None
    flops = obs.cell.module("flops", obs.cell.config["flops"])
    model = obs.cell.config["model"]
    layers = sum(layer in model["kda_layers"]
                 for layer in range(1, int(model["layers_held"]) + 1))
    operations = layers * sum(flops.kda_scan_operations(model, tokens, backward=b)
                              for b in (False, True))
    moved = layers * sum(flops.kda_scan_bytes(model, tokens, backward=b)
                         for b in (False, True))
    peaks = peaks_for(obs.devices[0].device_kind)
    least_s = max(operations / peaks["bf16_flops_per_s"],
                  moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ms / 1e3)
