"""The state-space scan's share of its roofline: the least time the chip could take for
what the *mathematics* of Mamba-2's scan in its chunked form needs in one train step — the
larger of its operations over the chip's bfloat16 peak and its bytes over the memory's
peak (``flops/nemotron_h.py::ssd_scan_operations`` / ``ssd_scan_bytes``: a chunk's Gram
matrix a group, its product with ``x`` and the two products with the state a head,
forward and twice that backward; ``x``, ``B``, ``C``, the step, the output and their
gradients read or written once), over every Mamba-2 layer this chip holds — over the
median device time of a step under ``faa_ssd_scan`` (``ops/ssd.py::chunk_ssd``: forward,
backward and what ``nn.remat`` computes again, which the mathematics does not count).  The
bytes bound it at these sizes.  A program from before the scope (``core/scopes.py::
SSD_SCAN``, PR 42), or a configuration whose operations file has no such functions, has
nothing to read."""

from benchmarks.harness.device import peaks_for
from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "%", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "SSD_SCAN", None)
    tokens = obs.work.get("tokens_a_step")
    flops = obs.cell.module("flops", obs.cell.config["flops"])
    if scope is None or not tokens or not hasattr(flops, "ssd_scan_operations"):
        return None
    measured_ms = scope_ms(obs, scope)
    if not measured_ms:
        return None
    model = obs.cell.config["model"]
    layers = flops.held_layers(model, flops.MAMBA)
    # an example is one sequence: the scan sees a sequence's length at a time
    length, sequences = int(model["seq_len"]), tokens / int(model["seq_len"])
    operations = layers * sequences * sum(
        flops.ssd_scan_operations(model, length, backward=b) for b in (False, True))
    moved = layers * sequences * sum(
        flops.ssd_scan_bytes(model, length, backward=b) for b in (False, True))
    peaks = peaks_for(obs.devices[0].device_kind)
    least_s = max(operations / peaks["bf16_flops_per_s"],
                  moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ms / 1e3)
