"""``BENCHMARK.json`` against the contract it is written to, the files it
names, and the proof that a configuration, a traffic mix and a per-layer
metric are added as new files and entries alone."""

import json
import os
import re
import types

import pytest
import yaml

from benchmarks import run as runner
from benchmarks.harness import spec
from benchmarks.harness.observed import Observed

REPO = spec.ROOT
BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
#: a layer is a plain name too, and may start with an underscore (the
#: driver refused "compile seam")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
READER_FILES = sorted(
    f[:-3] for f in os.listdir(os.path.join(spec.BENCH_DIR, "layer_metrics"))
    if f.endswith(".py"))
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: what a model family's two files must have (``harness/spec.py`` says
#: who calls each)
FAMILY_CONTRACT = {
    "flops": ("model_from_conf", "train_flops_per_image",
              "forward_flops_per_image"),
    "references": ("forward",)}


def _family_files(kind):
    return sorted(f[:-3] for f in os.listdir(os.path.join(spec.BENCH_DIR, kind))
                  if f.endswith(".py"))


def check_config(root: str, config: dict) -> None:
    """One entry of ``configs`` against its file under `root`: the file
    holds the configuration as it is run, the only keys that differ from
    the program's own conf are the ones under `reduced`, and its
    ``model`` block is what its family's ``model_from_conf`` makes of
    the conf's ``model`` mapping."""
    held = spec.load_json(os.path.join(root, config["file"]))
    with open(os.path.join(root, held["repo_conf"])) as fh:
        conf = yaml.safe_load(fh)
    assert held["reduced"] == config["reduced"]
    assert held["source"] == config["source"]
    differing = {k for k in set(conf) | set(held["conf"])
                 if conf.get(k) != held["conf"].get(k)}
    assert differing == set(config["reduced"])
    family = spec.load_module("flops", held["flops"],
                              os.path.join(root, "benchmarks"))
    sizes = family.model_from_conf(held["conf"]["model"])
    assert sizes and {k: held["model"].get(k) for k in sizes} == sizes


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    assert len(BENCH["command"]) <= 32
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    # the command names a file under `paths` and nothing outside them
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])
    assert os.path.isfile(os.path.join(REPO, script))


def test_names_are_plain_and_used_once():
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in BENCH[key]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert all(LAYER.match(m["layer"]) for m in BENCH["per_layer"])
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert len(entry["why"]) <= 200


def test_cells_configs_and_chip_share():
    configs = {c["name"] for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == configs  # every configuration keeps a cell
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert not any(re.search(r"(_dim|_rank|width|widen|hidden)", key)
                       for key in c["reduced"])


def test_metrics_bounds_sources_and_arrows():
    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    assert end_to_end["setup_s"]["bound"] == 0.1
    assert "workloads" not in end_to_end["setup_s"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    for m in BENCH["per_layer"]:
        assert "bound" not in m
        assert m["source"] in SOURCES
        assert m["moves"] in end_to_end
    cells = [w["name"] for w in BENCH["workloads"]]
    for cell in cells:
        e2e = {m["name"] for m in spec._metrics_for(BENCH["end_to_end"], cell)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = spec._metrics_for(BENCH["per_layer"], cell)
        assert layers
        # a per-layer metric is reported only where the metric it moves is
        assert all(m["moves"] in e2e for m in layers)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_reader_file_agrees_with_its_entry(metric):
    reader = spec.load_module("layer_metrics", metric["name"])
    for key in ("unit", "source", "layer", "moves"):
        assert reader.META[key] == metric[key]
    assert callable(reader.read)


@pytest.mark.parametrize("name", READER_FILES)
def test_every_reader_on_disk_names_a_plain_layer_in_perf_md(name):
    """Readers kept for cells that are not shipped yet included: the entry
    a later PR writes for one copies its META."""
    meta = spec.load_module("layer_metrics", name).META
    assert NAME.match(name) and LAYER.match(meta["layer"])
    assert meta["source"] in SOURCES
    with open(os.path.join(REPO, "PERF.md")) as fh:
        assert meta["layer"] in fh.read()  # the layer's name in PERF.md


@pytest.mark.parametrize("cell_name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_files_that_exist(cell_name):
    cell = spec.resolve_cell(cell_name, seed=3, seconds=1, trace=True)
    program = cell.module("programs", cell.traffic["program"])
    assert callable(program.run)
    for kind, named in (("flops", cell.config["flops"]),
                        ("references", cell.config["reference"])):
        for function in FAMILY_CONTRACT[kind]:
            assert callable(getattr(cell.module(kind, named), function))
    assert cell.work == os.path.join(REPO, "bench_work", cell_name)
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert "/bench_work/" in fh.read().split()


@pytest.mark.parametrize("kind, name", [
    (kind, name) for kind in FAMILY_CONTRACT for name in _family_files(kind)])
def test_a_family_file_keeps_the_contract_spec_py_states(kind, name):
    module = spec.load_module(kind, name)
    for function in FAMILY_CONTRACT[kind]:
        assert callable(getattr(module, function)), function
        assert f"``{function}(" in spec.__doc__


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_is_the_repo_conf_but_for_what_it_lists(config):
    check_config(REPO, config)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_model_from_conf_refuses_a_conf_of_another_family(config):
    held = spec.load_json(os.path.join(REPO, config["file"]))
    family = spec.load_module("flops", held["flops"])
    assert family.model_from_conf(held["conf"]["model"])
    with pytest.raises((ValueError, KeyError)):
        family.model_from_conf({"type": "no_such_family"})


@pytest.mark.parametrize("cell_name", ["wrn40x2_train", "wrn28x10_train"])
def test_train_cells_run_the_conf_at_the_entry_points_defaults(cell_name):
    """A cell measures what a user gets: the traffic overrides no key of
    the conf and passes no argument to the entry point, so a later change
    of a default shows in the cell."""
    cell = spec.resolve_cell(cell_name)
    assert cell.traffic["program"] == "train"
    assert cell.traffic["conf_overrides"] == {} and cell.traffic["entry_args"] == {}
    assert cell.conf_dict() == cell.config["conf"]


def assert_every_original_file_is_in_the_copy(bench_dir):
    """Byte for byte: what dropped in edited nothing that was there."""
    for folder, _, files in os.walk(os.path.join(REPO, "benchmarks")):
        if "__pycache__" in folder:
            continue
        for name in files:
            original = os.path.join(folder, name)
            copy = os.path.join(bench_dir, os.path.relpath(
                original, os.path.join(REPO, "benchmarks")))
            with open(original, "rb") as a, open(copy, "rb") as b:
                assert a.read() == b.read(), original


def test_a_config_a_traffic_and_a_metric_drop_in_as_new_files(tmp_path,
                                                               make_tiny_checkout):
    """The drop-in proof: a copy of the benchmark gains a configuration,
    a fixture, two traffic files, an end-to-end metric with its reader
    (already: the tiny cells) and a per-layer metric; discovery finds them,
    and no file that was there changed."""
    root = make_tiny_checkout()
    bench_dir = os.path.join(root, "benchmarks")
    with open(os.path.join(bench_dir, "layer_metrics", "dummy_steps.py"), "w") as fh:
        fh.write('META = {"layer": "test", "unit": "count", "source": '
                 '"program_counter", "moves": "train_images_per_s"}\n\n\n'
                 'def read(obs):\n    return obs.attempted\n')
    with open(os.path.join(bench_dir, "layer_metrics", "dummy_absent.py"), "w") as fh:
        fh.write('META = {"layer": "test", "unit": "count", "source": '
                 '"program_counter", "moves": "train_images_per_s"}\n\n\n'
                 'def read(obs):\n    return None\n')
    bench = spec.load_benchmark(root)
    for name in ("dummy_steps", "dummy_absent"):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "test",
            "moves": "train_images_per_s", "workloads": ["tiny_train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)

    cell = spec.resolve_cell("tiny_train", seed=1, seconds=1, trace=True,
                             root=root)
    assert cell.conf_dict()["model"]["type"] == "wresnet10_1"
    assert cell.fixture["train"] == 400
    assert {m["name"] for m in cell.per_layer} >= {"dummy_steps", "dummy_absent"}
    # the other readers of a train cell want a device and a trace
    cell.per_layer = [m for m in cell.per_layer if m["name"].startswith("dummy")]
    obs = Observed(cell=cell, devices=[], window_s=1.0, attempted=7, failed=0,
                   end_to_end={"train_images_per_s": 1.0, "setup_s": 1.0},
                   checks={"x": {"ok": True}}, compile_stats={},
                   memory_peak_bytes=0)
    metrics = runner.read_layer_metrics(obs)
    assert metrics["dummy_steps"] == {"value": 7.0, "unit": "count"}
    assert "dummy_absent" not in metrics  # nothing to read: left out

    assert_every_original_file_is_in_the_copy(bench_dir)
    # and the real cells still resolve from the copy
    assert spec.resolve_cell("wrn28x10_train", root=root).chips == 1


#: stubs that keep the contract; the real ones are the ``model_config`` PR's
_PYRAMID_FLOPS = '''
def model_from_conf(conf_model):
    if conf_model.get("type") != "pyramid":
        raise ValueError(f"not a PyramidNet: {conf_model!r}")
    return {key: conf_model[key] for key in ("depth", "alpha", "bottleneck")}


def forward_flops_per_image(model):
    return 2.0 * model["depth"] * model["alpha"] * model["image"] ** 2


def train_flops_per_image(model):
    return 3.0 * forward_flops_per_image(model)
'''
_PYRAMID_REFERENCE = '''
def forward(params, batch_stats, images_u8, model):
    raise NotImplementedError("the model_config PR writes it")
'''


def test_a_configuration_of_another_model_family_drops_in_as_new_files(
        make_tiny_checkout):
    """What the next ``model_config`` PR does, in a copy: a PyramidNet
    conf, its operations file and reference, its configuration file and a
    cell on traffic that is there — new files and entries alone; the
    configuration self-test, discovery and the readers take them."""
    root = make_tiny_checkout()
    bench_dir = os.path.join(root, "benchmarks")
    model = {"type": "pyramid", "depth": 20, "alpha": 48, "bottleneck": True}
    conf = {"model": model, "dataset": "cifar10", "aug": "fa_reduced_cifar10",
            "cutout": 16, "batch": 64, "epoch": 1800, "lr": 0.05,
            "optimizer": {"type": "sgd", "nesterov": True, "decay": 5e-5}}
    os.makedirs(os.path.join(root, "confs"))
    with open(os.path.join(root, "confs", "pyramid20_cifar.yaml"), "w") as fh:
        yaml.safe_dump(conf, fh)
    for kind, text in (("flops", _PYRAMID_FLOPS), ("references", _PYRAMID_REFERENCE)):
        with open(os.path.join(bench_dir, kind, "pyramidnet.py"), "w") as fh:
            fh.write(text)
    entry = {"name": "pyramid20_cifar10", "source": "arXiv:1610.02915",
             "file": "benchmarks/configs/pyramid20_cifar10.json",
             "reduced": ["batch"], "why": "test"}
    sizes = dict(model, num_classes=10, image=32)
    del sizes["type"]
    with open(os.path.join(root, entry["file"]), "w") as fh:
        json.dump({"name": entry["name"], "source": entry["source"],
                   "repo_conf": "confs/pyramid20_cifar.yaml",
                   "conf": dict(conf, batch=8), "model": sizes,
                   "reduced": ["batch"], "flops": "pyramidnet",
                   "reference": "pyramidnet", "logit_tolerance": 0.02}, fh)
    bench = spec.load_benchmark(root)
    bench["configs"].append(entry)
    bench["workloads"].append({
        "name": "pyramid20_train", "config": entry["name"],
        "traffic": "tiny_train", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "tiny_train" in metric.get("workloads", ()):
            metric["workloads"].append("pyramid20_train")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)

    check_config(root, entry)
    # and the check holds the model block to the conf: another alpha is refused
    held = spec.load_json(os.path.join(root, entry["file"]))
    held["model"]["alpha"] = 64
    wrong = dict(entry, file="benchmarks/configs/pyramid20_wrong.json")
    with open(os.path.join(root, wrong["file"]), "w") as fh:
        json.dump(held, fh)
    with pytest.raises(AssertionError):
        check_config(root, wrong)

    cell = spec.resolve_cell("pyramid20_train", seed=2, seconds=1, trace=True,
                             root=root)
    assert cell.conf_dict()["model"] == model and cell.fixture["train"] == 400
    flops = cell.module("flops", cell.config["flops"])
    assert flops.__file__.startswith(bench_dir)
    assert callable(cell.module("references", cell.config["reference"]).forward)
    # every reader of a train cell, on a run that was not traced
    assert len(cell.per_layer) == len(
        spec.resolve_cell("tiny_train", root=root).per_layer) >= 16
    obs = Observed(cell=cell, window_s=1.0, attempted=7, failed=0,
                   devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
                   end_to_end={"train_images_per_s": 56.0, "setup_s": 1.0},
                   checks={"x": {"ok": True}}, memory_peak_bytes=5 << 30,
                   compile_stats={"hits": 1, "misses": 0, "labels": {
                       "train_dispatch": {"sec": 2.5}}},
                   work={"images_per_s_per_chip": 56.0, "passes": "train"})
    metrics = runner.read_layer_metrics(obs)
    assert set(metrics) == {"compile_first_call_s", "compile_cache_misses",
                            "peak_hbm_bytes", "model_flops_utilization"}
    assert metrics["model_flops_utilization"]["value"] == pytest.approx(
        100.0 * flops.train_flops_per_image(sizes) * 56.0 / 197e12)

    assert_every_original_file_is_in_the_copy(bench_dir)
    assert spec.resolve_cell("wrn40x2_train", root=root).chips == 1


def test_a_reader_that_contradicts_its_entry_is_refused(make_tiny_checkout):
    root = make_tiny_checkout()
    bench = spec.load_benchmark(root)
    next(m for m in bench["per_layer"]
         if m["name"] == "compile_cache_misses")["unit"] = "s"
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    cell = spec.resolve_cell("tiny_train", trace=True, root=root)
    obs = Observed(cell=cell, devices=[], window_s=1.0, attempted=1, failed=0,
                   end_to_end={"setup_s": 1.0}, checks={}, compile_stats={},
                   memory_peak_bytes=0)
    with pytest.raises(spec.SpecError, match="compile_cache_misses"):
        runner.read_layer_metrics(obs)


def test_unknown_workload_is_an_error():
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.resolve_cell("no_such_cell")
