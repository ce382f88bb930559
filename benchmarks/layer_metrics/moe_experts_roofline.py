"""The held experts' share of their roofline: the least time the chip could take for
what the held experts' three products need in one train step — the larger of their
operations over the chip's bfloat16 peak and their bytes over the memory's peak
(``flops/kimi_linear.py::moe_experts_operations`` / ``moe_experts_bytes``: the assignments
the *program's counter* counted, ``faa_moe_assignments_total{held}``, a layer a step;
the held experts' float32 weights once a pass, a row in and a row out an assignment),
summed over the expert layers — over the median device time of a step under
``faa_moe_experts`` (dispatch, the products, combine; forward, backward and what is
computed again).  The program groups the assignments by expert and does the products a
block of rows at a time (``ops/moe.py``), so its work follows the routing as the counted
work does; the share says how far the loop, its gathers and scatters and the part-filled
blocks are from the weights' bytes.  A program from before the scope and the counter
(PR 35) has nothing to read."""

from benchmarks.harness.device import peaks_for
from benchmarks.harness.scopes import program_scopes, scope_ms

META = {"layer": "models", "unit": "%", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "MOE_EXPERTS", None)
    by_layer = obs.work.get("moe_assignments_a_step_by_layer")
    measured_ms = None if scope is None or not by_layer else scope_ms(obs, scope)
    if not measured_ms:
        return None
    flops = obs.cell.module("flops", obs.cell.config["flops"])
    model = obs.cell.config["model"]
    operations = sum(flops.moe_experts_operations(model, n, backward=b)
                     for n in by_layer.values() for b in (False, True))
    moved = sum(flops.moe_experts_bytes(model, n, backward=b)
                for n in by_layer.values() for b in (False, True))
    peaks = peaks_for(obs.devices[0].device_kind)
    least_s = max(operations / peaks["bf16_flops_per_s"],
                  moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ms / 1e3)
