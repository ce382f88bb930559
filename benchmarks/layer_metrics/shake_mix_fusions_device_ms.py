"""Median device time of one train step in the operations that *hold* an instruction
of ``faa_shake_mix`` (the per-image noise draw and the mix of a Shake-Shake block's
two branches, forward, and the ``custom_vjp`` rule's products, backward), whatever
they are rooted in.  XLA gives the mix no pass of its own: it rides in the BatchNorm
and residual-add fusions before and after it and in the backward fusions of each
branch, and a fusion's time goes to its root's scope, so the time *rooted* in the mix
is the noise draw alone (0.06 of a 214 ms step).  This is the other reading, by
membership (``core/compilecache.py::scope_members``): an upper bound on what the mix
costs — the neighbours' own work in the same fusions is in it and cannot be taken
out — that moves when the mix is fused differently.  A program from before the scope
or from before ``scope_members`` has nothing to read."""

import bisect

from benchmarks.harness import trace as tr
from benchmarks.harness.scopes import (instruction_name, module_name,
                                       program_scopes, step_split)

META = {"layer": "models", "unit": "ms", "source": "device_trace",
        "moves": "train_images_per_s"}


def read(obs):
    scope = getattr(program_scopes(), "SHAKE_MIX", None)
    if scope is None or step_split(obs) is None:  # no scope, no trace, no step
        return None
    try:
        from fast_autoaugment_tpu.core.compilecache import scope_members
    except ImportError:
        return None
    modules = scope_members(obs.cell.traffic["dispatch_label"])
    if not any(scope in held for table in modules.values()
               for held in table.values()):
        return None
    per_execution = []
    for plane in obs.trace.planes:
        line = plane.line(tr.OPS_LINE)
        ops = line.events if line else []
        starts = [e.start_ns for e in ops]
        for run in tr.program_runs(plane, obs.step_program)[1:-1]:
            table = modules.get(module_name(run.name), {})
            inside = ops[bisect.bisect_left(starts, run.start_ns):
                         bisect.bisect_left(starts, run.end_ns)]
            per_execution.append(sum(
                own for event, own in zip(inside, tr.self_times(inside))
                if scope in table.get(instruction_name(event.name), ())) / 1e6)
    return tr.median(per_execution)
