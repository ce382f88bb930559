"""The arithmetic the per-layer readers share.  A reader file under
``layer_metrics/`` names its layer, unit, source and arrow and calls one
of these; a cell's program says which step program and which passes."""

from __future__ import annotations

from benchmarks.harness import trace as tr
from benchmarks.harness.device import peaks_for


def step_device_ms(obs) -> float | None:
    """Median device time of one execution of the step program.  The
    first and the last execution in a trace may be cut by its edges (a
    search trace starts and stops mid-dispatch), so they are left out."""
    view = obs.trace
    if view is None or not obs.step_program:
        return None
    return tr.median([e.dur_ns / 1e6 for p in view.planes
                      for e in tr.program_runs(p, obs.step_program)[1:-1]])


def dispatch_gap_ms(obs) -> float | None:
    """Median device wait between two executions of the step program."""
    view = obs.trace
    if view is None or not obs.step_program:
        return None
    return tr.median([g * 1e3 for p in view.planes for g in tr.gaps_between(
        tr.program_runs(p, obs.step_program))])


def device_idle_share(obs) -> float | None:
    """Percent of the traced window in which no operation ran, averaged
    over the chips the cell uses."""
    view = obs.trace
    if view is None or view.window_s <= 0:
        return None
    return 100.0 * (1.0 - view.busy_s / view.window_s)


def model_flops_utilization(obs) -> float | None:
    """Percent of the chip's published bfloat16 peak that the model
    operations of the images completed in the window amount to."""
    if not obs.work:
        return None
    flops = obs.cell.module("flops", obs.cell.config["flops"])
    per_image = {"train": flops.train_flops_per_image,
                 "forward": flops.forward_flops_per_image}[obs.work["passes"]]
    peak = peaks_for(obs.devices[0].device_kind)["bf16_flops_per_s"]
    return (100.0 * per_image(obs.cell.config["model"])
            * obs.work["images_per_s_per_chip"] / peak)
