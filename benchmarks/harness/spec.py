"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Nothing here knows a configuration, a model family, a traffic mix, a
program or a metric by name: a cell names its configuration and traffic,
the traffic file names its program and fixture, the configuration file
names its family's operations file and plain reference, and a per-layer
metric is the reader file that carries its name.  Adding any of them is
adding files and entries; no list in this directory has to be edited.

What a configuration of a new model family brings, and who calls it
(``configs/<config>.json`` names both files, as ``"flops"`` and
``"reference"``, and hands them its ``"model"`` block of sizes):

``flops/<family>.py``, operations from shapes alone
    ``model_from_conf(conf_model) -> dict``: the sizes the functions
    below need, derived from the conf's ``model`` mapping; the
    configuration self-test (``tests/benchmarks/test_bench_spec.py::
    check_config``) holds the file's ``model`` block to it on those keys.
    ``train_flops_per_image(model) -> float`` and
    ``forward_flops_per_image(model) -> float``: model operations of one
    image through forward and backward, and through forward alone;
    ``harness/readers.py::model_flops_utilization`` calls the one the
    cell's program names in ``Observed.work["passes"]``.

``references/<family>.py``, the plain float32 forward pass
    ``forward(params, batch_stats, images_u8, model) -> logits``: no
    code of the program, every product at ``highest``;
    ``harness/window.py::reference_check`` calls it on the parameter
    trees the program checkpoints, and ``correct`` rests on it.

A family's own tests (its operations against a hand count, its reference
against the program on seeded weights) come with it as a new file under
``tests/benchmarks/``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import zlib
from dataclasses import dataclass, field

#: the benchmark's own tree and the checkout it sits in
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
#: run-time products (fixture, checkpoints, journal, trace) go under
#: this directory of the checkout, listed in .gitignore, one
#: subdirectory per cell
WORK_NAME = "bench_work"


class SpecError(Exception):
    """The benchmark's own files contradict each other or are missing."""


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """Import ``<bench_dir>/<kind>/<name>.py`` by path (`kind` is one of
    programs, layer_metrics, flops, references)."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} file for {name!r}: {path} is missing")
    # keyed by the path too: a test loads the same name from a copy
    mod_name = f"_bench_{kind}_{name}_{zlib.crc32(path.encode()):08x}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return module


@dataclass
class Cell:
    """One entry of ``workloads`` with everything its run needs."""

    name: str
    chips: int
    config: dict
    traffic: dict
    fixture: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    seed: int = 0
    seconds: float = 0.0
    trace: bool = False
    root: str = ROOT
    bench_dir: str = BENCH_DIR
    work: str = field(default="")

    def module(self, kind: str, name: str):
        return load_module(kind, name, self.bench_dir)

    def conf_dict(self) -> dict:
        """The configuration as this cell runs it: the file's ``conf``
        with the traffic file's ``conf_overrides`` (dotted keys) on top."""
        conf = json.loads(json.dumps(self.config["conf"]))
        for dotted, value in (self.traffic.get("conf_overrides") or {}).items():
            node = conf
            parts = dotted.split(".")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = value
        return conf


def _metrics_for(entries: list[dict], cell_name: str) -> list[dict]:
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def resolve_cell(workload: str, *, seed: int = 0, seconds: float = 0.0,
                 trace: bool = False, root: str = ROOT,
                 bench_dir: str | None = None) -> Cell:
    """The cell named `workload`, with its files loaded."""
    bench_dir = bench_dir or os.path.join(root, "benchmarks")
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise SpecError(f"workload {workload!r} names config "
                        f"{entry['config']!r}, which BENCHMARK.json lacks")
    config = load_json(os.path.join(root, configs[entry["config"]]["file"]))
    traffic = load_json(
        os.path.join(bench_dir, "traffic", f"{entry['traffic']}.json"))
    fixture = load_json(
        os.path.join(bench_dir, "fixtures", f"{traffic['fixture']}.json"))
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=config, traffic=traffic, fixture=fixture,
        end_to_end=_metrics_for(bench["end_to_end"], workload),
        per_layer=_metrics_for(bench["per_layer"], workload),
        seed=int(seed), seconds=float(seconds), trace=bool(trace),
        root=root, bench_dir=bench_dir,
        work=os.path.join(root, WORK_NAME, workload),
    )
