"""Shared pieces of the benchmark's self-tests: a copy of the benchmark
in a temporary checkout, with a tiny configuration, fixture and traffic
dropped in as NEW files and entries — nothing that was there is edited."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _write(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh)


def build_tiny_checkout(root: str, *, chips_train: int = 1,
                        chips_search: int = 1) -> str:
    """Copy ``benchmarks/`` and ``BENCHMARK.json`` into `root` and add a
    WRN-10-1 configuration on a 400-image fixture with a train and a
    search cell.  Returns `root`."""
    bench_dir = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _read(os.path.join(REPO, "BENCHMARK.json"))

    config = _read(os.path.join(bench_dir, "configs", "wrn40x2_cifar10.json"))
    config["conf"]["model"]["type"] = "wresnet10_1"
    config["conf"]["batch"] = 8
    config["model"] = {"depth": 10, "widen_factor": 1, "num_classes": 10,
                       "image": 32}
    _write(os.path.join(bench_dir, "configs", "tiny.json"), config)

    fixture = _read(os.path.join(bench_dir, "fixtures",
                                 "cifar10_templates.json"))
    fixture.update(train=400, test=100)
    _write(os.path.join(bench_dir, "fixtures", "tiny.json"), fixture)

    train = _read(os.path.join(bench_dir, "traffic", "train_epochs.json"))
    train.update(fixture="tiny", trace_seconds=1.5, reference_images=16,
                 accuracy_margin=-1.0)  # a few steps teach nothing
    _write(os.path.join(bench_dir, "traffic", "tiny_train.json"), train)

    # no cell of BENCHMARK.json runs the search program yet (PERF.md, Open
    # questions): its traffic, its end-to-end metric and its reader come
    # with the tiny cell, as they will with the real one
    _write(os.path.join(bench_dir, "traffic", "tiny_search.json"), {
        "describes": "search_policies phase 2 on one fold of the tiny fixture",
        "program": "search", "fixture": "tiny",
        "entry_args": {
            "cv_num": 5, "cv_ratio": 0.4, "folds": [0], "until": 2,
            "num_policy": 5, "num_op": 2, "num_search": 64, "num_top": 10,
            "fold_quality_floor": None, "phase1_epochs": 1, "trial_batch": 2},
        # reduced_cifar10 needs the full 50,000; folds of the 400-image
        # fixture are cut from `cifar10` itself
        "conf_overrides": {"dataset": "cifar10", "batch": 4},
        # the tests ask for a window shorter than any round (0.05 s), which
        # `take_window` runs to the end of the round it cut into: a fixed
        # number of rounds however slow the machine, so the watcher may wait
        # an hour (0.05 s x 72,000) for that round before it gives up
        "warmup_trials": 2, "close_margin_seconds": 0.5,
        "max_window_factor": 72000, "trace_seconds": 1.5, "trace_min_trials": 4,
        "dispatch_labels": ["tta", "tta_batched"],
        "step_program": "^jit_(tta_step_batched|one_candidate)",
        "reward_tolerance": 0.002, "reference_images": 16})
    bench["end_to_end"].append({
        "name": "search_trials_per_s", "unit": "trials/s", "better": "higher",
        "bound": 0.01, "source": "host_clock", "workloads": ["tiny_search"]})
    bench["per_layer"].append({
        "name": "trial_host_gap_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "search_scheduler",
        "moves": "search_trials_per_s", "workloads": ["tiny_search"]})

    bench["configs"].append({
        "name": "tiny", "source": "test", "reduced": ["model", "batch"],
        "file": "benchmarks/configs/tiny.json", "why": "test"})
    bench["workloads"] += [
        {"name": "tiny_train", "config": "tiny", "traffic": "tiny_train",
         "chips": chips_train, "why": "test"},
        {"name": "tiny_search", "config": "tiny", "traffic": "tiny_search",
         "chips": chips_search, "why": "test"}]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        cells = metric.get("workloads")
        if cells and "wrn40x2_train" in cells:
            cells.append("tiny_train")
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture()
def make_tiny_checkout(tmp_path):
    """``make(chips_train=1, chips_search=1) -> root`` of a fresh copy."""
    made = []

    def make(**chips):
        made.append(build_tiny_checkout(
            str(tmp_path / f"checkout{len(made)}"), **chips))
        return made[-1]

    return make
