"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  The last line of standard output is one JSON object with the
keys ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
when traced ``breakdown``, and last ``compared``: every number a check
compared, beside its limit (the same, a line each, are the run's last
lines on standard error); the line before it, ``{"observed": ...}``,
carries the rest of the evidence behind ``correct`` and is for people.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, the exit code
is 3 and nothing is printed on standard output: JAX falls back to the
CPU by itself, this benchmark does not.  Everything a run writes (the
fixture, checkpoints, the journal, the trace) goes under ``bench_work/``
in the checkout; the compile cache is where ``JAX_COMPILATION_CACHE_DIR``
says, else the program's fixed ``.jax_cache/`` in the checkout.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import trace as tr  # noqa: E402
from benchmarks.harness.device import (  # noqa: E402
    NoAcceleratorError,
    device_stamp,
    require_devices,
)
from benchmarks.harness.observed import Observed  # noqa: E402
from benchmarks.harness.spec import Cell, SpecError, resolve_cell  # noqa: E402
from benchmarks.harness.window import process_start_wall  # noqa: E402

_READER_KEYS = ("unit", "source", "layer", "moves")


def reader_for(cell: Cell, entry: dict):
    """The reader file of one ``per_layer`` entry the cell lists;
    :class:`SpecError` where there is none or its ``META`` contradicts
    the entry."""
    reader = cell.module("layer_metrics", entry["name"])
    for key in _READER_KEYS:
        if reader.META[key] != entry[key]:
            raise SpecError(
                f"per-layer metric {entry['name']!r}: its reader says "
                f"{key}={reader.META[key]!r}, BENCHMARK.json says "
                f"{entry[key]!r}")
    return reader


def read_layer_metrics(obs: Observed) -> dict:
    """Every per-layer metric of the cell whose reader finds something
    to read, as ``{name: {"value", "unit"}}``."""
    reported = {m["name"] for m in obs.cell.end_to_end
                if m["name"] in obs.end_to_end}
    out = {}
    for entry in obs.cell.per_layer:
        reader = reader_for(obs.cell, entry)
        if entry["moves"] not in reported:
            continue
        value = reader.read(obs)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def breakdown(obs: Observed) -> dict | None:
    view = obs.trace
    if view is None:
        return None
    gaps = [g for p in view.planes for g in tr.idle_gaps(p, view.window_ns)]
    by_host = tr.attribute_gaps(gaps, view.host_spans_ns(obs.host_spans))
    return {"device_ops": tr.top_operations(view.planes),
            "idle_gaps": [[name, s / len(view.planes)] for name, s in by_host]}


def compared_numbers(obs: Observed) -> dict:
    """``{check: {"value", "must", "limit"}}`` for every check that compared
    a number (``harness/window.py::compared``), as JSON takes it: a reading
    that is not a number, as a loss that diverged, goes by its name."""
    def plain(x):
        if isinstance(x, (list, tuple)):
            return [plain(v) for v in x]
        return x if x is None or math.isfinite(x) else repr(float(x))
    return {name: {"value": plain(check["compared"]["value"]),
                   "must": check["compared"]["must"],
                   "limit": plain(check["compared"]["limit"])}
            for name, check in obs.checks.items() if "compared" in check}


def result_line(obs: Observed) -> dict:
    """The contract's last line: its keys, and ``compared`` last."""
    cell = obs.cell
    device = dict(device_stamp(obs.devices),
                  memory_peak_bytes=obs.memory_peak_bytes)
    line = {"correct": obs.correct, "attempted": obs.attempted,
            "failed": obs.failed}
    if not cell.trace:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        line["metrics"] = {name: {"value": float(value), "unit": units[name]}
                           for name, value in obs.end_to_end.items()
                           if name in units}
        line["device"] = device
    else:
        line["metrics"] = read_layer_metrics(obs)
        view = obs.trace
        if view is not None:
            device.update(busy_s=view.busy_s, window_s=view.window_s)
        line["device"] = device
        parts = breakdown(obs)
        if parts is not None:
            line["breakdown"] = parts
    line["compared"] = compared_numbers(obs)
    return line


def run_cell(cell: Cell, devices: list, start_wall: float) -> Observed:
    program = cell.module("programs", cell.traffic["program"])
    os.makedirs(cell.work, exist_ok=True)
    return program.run(cell, devices, start_wall)


def main(argv: list[str] | None = None) -> int:
    start_wall = process_start_wall()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    cell = resolve_cell(args.workload, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace))
    try:
        devices = require_devices(cell.chips)
    except NoAcceleratorError as e:
        print(f"benchmarks/run.py: {e}; no result", file=sys.stderr)
        return 3
    obs = run_cell(cell, devices, start_wall)
    line = result_line(obs)
    print(json.dumps({"observed": {
        "workload": cell.name, "seed": cell.seed, "window_s": obs.window_s,
        "end_to_end": obs.end_to_end, "checks": obs.checks,
        "compile_cache": obs.compile_stats,
        "memory_stats": devices[0].memory_stats()}}, default=str))
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']} must be {c['must']} {c['limit']}",
              file=sys.stderr)
    failed = sorted(k for k, c in obs.checks.items() if not c.get("ok"))
    print(f"correct: {obs.correct}" + (f" (failed: {', '.join(failed)})"
                                       if failed else ""),
          file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
