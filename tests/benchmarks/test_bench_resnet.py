"""What ``resnet50_imagenet`` brings: its operations against a hand count,
its plain reference against the program's ResNet-50 at the published widths
on seeded weights (evaluation logits; training loss, every gradient and the
new running statistics through ``train/steps.py``'s own step), the
configuration file, the JPEG fixture, the cell, what its program says of
the feed, the heartbeat that refuses a trainer beating once an epoch, and
the cell's program rehearsed on the CPU at a small size.
"""

import json
import os
import re
import types

import flax
import jax
import jax.numpy as jnp
import numpy as np
import optax
import PIL.Image
import pytest
from flax import serialization

from benchmarks import run as runner
from benchmarks.harness import fixture_jpeg, spec, window
from benchmarks.harness import scopes as hs
from benchmarks.harness import trace as tr
from benchmarks.harness.observed import Observed, TraceView
from fast_autoaugment_tpu.core import compilecache, scopes, telemetry
from fast_autoaugment_tpu.data.datasets import load_dataset
from fast_autoaugment_tpu.models import get_model
from fast_autoaugment_tpu.ops.preprocess_imagenet import imagenet_eval_batch
from fast_autoaugment_tpu.train.steps import TrainState, _make_train_step_body

reference = spec.load_module("references", "resnet")
flops = spec.load_module("flops", "resnet")
program = spec.load_module("programs", "train_hostfed")

CONFIG = "resnet50_imagenet"
CELL = "resnet50_imagenet_train"
PX, BATCH = 64, 4         # the small size: published widths, 64 px, batch 4
SIZES = {"depth": 50, "blocks": [3, 4, 6, 3], "widths": [64, 128, 256, 512],
         "expansion": 4, "num_classes": 1000, "image": PX}

# Tolerances, as the largest difference over the largest reference entry of
# the same array; both sides float32 on the CPU at full precision.  Measured
# at this size: evaluation logits 0 (the same operations in the same order),
# loss 0, new statistics 9e-7.  Gradients: 1e-5 of a leaf's largest entry
# wherever no ReLU sits on the other side of zero in the two computations,
# and 0.1-0.9% in the leaves upstream of one that does (one element of
# layer1_2/bn1's two million pre-activations here: flax takes a variance as
# E[x^2] - E[x]^2 where the reference takes E[(x - E[x])^2], and with some
# two million ReLU inputs at these widths one lying within rounding of zero
# is expected, not bad luck).  The limit lets a flipped element through and
# no more: with the paper's stride placement (on the first 1x1 convolution)
# in place of torchvision's the gradients are off by 1.9 of the largest entry.
LOGIT_TOL, LOSS_TOL, GRAD_TOL, STATS_TOL = 1e-5, 1e-5, 5e-2, 1e-5
#: the configuration's limits (their readings are in the file)
DEPLOYED_LIMIT, FLOAT32_LIMIT = 0.05, 1e-5
#: the per-layer metrics that list this cell alone (PR 34; PERF.md section 3)
OWN_READERS = ("feed_wait_ms", "host_decode_images_per_s",
               "aug_jitter_device_ms", "resnet_stem_device_ms")


def _gap(system, plain) -> float:
    system, plain = np.asarray(system, np.float64), np.asarray(plain, np.float64)
    return float(np.max(np.abs(system - plain)) / np.max(np.abs(plain)))


def _largest_gap(system_tree, plain_tree) -> float:
    return max(jax.tree.leaves(jax.tree.map(
        _gap, jax.device_get(system_tree), plain_tree)))


def _structured_images(rng, count, px):
    """uint8 images that differ from one another in their coarse content
    (pure noise averages out to the same features by layer 4, and a batch
    whose members agree has no variance for a BatchNorm to divide by)."""
    coarse = rng.integers(0, 256, (count, 4, 4, 3)).astype(np.float32)
    fine = np.repeat(np.repeat(coarse, px // 4, 1), px // 4, 2)
    return np.clip(fine + rng.integers(-20, 21, fine.shape), 0, 255).astype(np.uint8)


@pytest.fixture(scope="module")
def seeded():
    """The program's ResNet-50 at its published widths, 64 px: seeded
    weights with every BatchNorm's scale and bias moved off 1 and 0 (the
    last of a block to a fifth, so that 16 blocks of random weights do not
    amplify rounding past telling), running statistics taken from a batch
    of other images, four images and labels."""
    model = get_model({"type": "resnet50", "dataset": "imagenet"}, 1000)
    variables = model.init(jax.random.PRNGKey(3), jnp.zeros((2, PX, PX, 3)),
                           train=False)
    flat = flax.traverse_util.flatten_dict(variables["params"])
    keys = iter(jax.random.split(jax.random.PRNGKey(11), len(flat)))
    for path, leaf in flat.items():
        key = next(keys)
        if path[-1] == "scale":
            flat[path] = (0.2 if "bn3" in path else 1.0) * (
                1 + 0.2 * jax.random.uniform(key, leaf.shape, minval=-1, maxval=1))
        elif path[-1] == "bias":
            flat[path] = 0.1 * jax.random.normal(key, leaf.shape)
    params = flax.traverse_util.unflatten_dict(flat)
    rng = np.random.default_rng(0)
    images = _structured_images(rng, BATCH, PX)
    floats = imagenet_eval_batch(jnp.asarray(images))
    _, mutated = model.apply(
        {"params": params, "batch_stats": variables["batch_stats"]},
        imagenet_eval_batch(jnp.asarray(_structured_images(rng, BATCH, PX))),
        train=True, mutable=["batch_stats"])
    stats = jax.tree.map(lambda new, old: (new - 0.9 * old) / 0.1,
                         mutated["batch_stats"], variables["batch_stats"])
    return types.SimpleNamespace(
        model=model, params=params, stats=stats, images=images, floats=floats,
        labels=jnp.asarray([1, 5, 3, 999]))


#: an "optimizer" that hands the gradients out as its state and moves nothing
_CARRY_GRADS = optax.GradientTransformation(
    lambda params: jax.tree.map(jnp.zeros_like, params),
    lambda grads, state, params=None: (jax.tree.map(jnp.zeros_like, grads), grads))


@pytest.fixture(scope="module")
def system_step(seeded):
    """One step of ``train/steps.py``'s own body (its ``loss_fn``, the
    ``faa_model`` and ``faa_loss`` scopes, the metric sums) on images that
    pass the augmentation unchanged: ``(loss, grads, new_stats)``."""
    body = _make_train_step_body(
        seeded.model, _CARRY_GRADS, num_classes=1000, cutout_length=0,
        use_policy=False, augment_fn=lambda images, policy, key: images)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=seeded.params,
                       batch_stats=seeded.stats,
                       opt_state=_CARRY_GRADS.init(seeded.params), ema=None)
    new, metrics = jax.jit(body)(state, seeded.floats, seeded.labels,
                                 jnp.zeros((1, 1, 3)), jax.random.PRNGKey(0))
    assert _largest_gap(new.params, jax.device_get(seeded.params)) == 0.0
    return (float(metrics["loss"]) / BATCH, jax.device_get(new.opt_state),
            jax.device_get(new.batch_stats))


@pytest.fixture(scope="module")
def reference_step(seeded):
    return reference.loss_and_grads(
        jax.device_get(seeded.params), jax.device_get(seeded.stats),
        np.asarray(seeded.floats), np.asarray(seeded.labels), SIZES)


# ------------------------------------------------------- operations, shapes


def _hand_count(image=224, classes=1000):
    """Multiply-accumulates and parameters of ResNet-50, stage by stage: the
    stem at half the image, the stages at a quarter and down; a stage's first
    block takes the previous stage's width in, strides in its 3x3 (so its
    first 1x1 still runs at the larger size) and projects its shortcut."""
    px = image // 2
    macs = 49 * 3 * 64 * px * px
    params = 49 * 3 * 64 + 2 * 64
    px //= 2
    c_in = 64
    for stage, (w, n) in enumerate(zip((64, 128, 256, 512), (3, 4, 6, 3))):
        out = px if stage == 0 else px // 2
        first = c_in * w * px * px + (9 * w * w + w * 4 * w + c_in * 4 * w) * out * out
        rest = (n - 1) * (4 * w * w + 9 * w * w + w * 4 * w) * out * out
        macs += first + rest
        params += (c_in * w + 9 * w * w + 4 * w * w + c_in * 4 * w
                   + 2 * (w + w + 4 * w + 4 * w))
        params += (n - 1) * (4 * w * w + 9 * w * w + 4 * w * w + 2 * (w + w + 4 * w))
        c_in, px = 4 * w, out
    return macs + 2048 * classes, params + 2048 * classes + classes


def test_operations_and_parameters_match_a_hand_count():
    model = dict(SIZES, image=224)
    assert flops.forward_macs_per_image(model) == 4_089_184_256   # 4.09 GMAC
    assert flops.num_params(model) == 25_557_032                 # torchvision's
    assert _hand_count() == (4_089_184_256, 25_557_032)
    assert flops.forward_flops_per_image(model) == 2 * 4_089_184_256
    assert flops.train_flops_per_image(model) == 6 * 4_089_184_256
    assert sum(1 for _ in flops._convs(model)) == 53
    assert flops.forward_macs_per_image(SIZES) == _hand_count(PX)[0]


def test_parameter_count_matches_the_programs_model(seeded):
    counted = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(seeded.params))
    assert flops.num_params(SIZES) == counted == 25_557_032
    stats = jax.tree.leaves(seeded.stats)
    assert len(stats) == 2 * 53   # a mean and a variance a BatchNorm


@pytest.mark.parametrize("conf_model, sizes", [
    ({"type": "resnet50"}, {"depth": 50, "blocks": [3, 4, 6, 3]}),
    ({"type": "resnet200"}, {"depth": 200, "blocks": [3, 24, 36, 3]}),
    ({"type": "resnet18"}, None),
    ({"type": "wresnet28_10"}, None),
    ({}, None)])
def test_model_from_conf_takes_this_family_alone(conf_model, sizes):
    if sizes is None:
        with pytest.raises(ValueError, match="not a bottleneck ImageNet ResNet"):
            flops.model_from_conf(conf_model)
    else:
        assert flops.model_from_conf(conf_model) == dict(
            sizes, widths=[64, 128, 256, 512], expansion=4)


# ------------------------------------------------------------ the reference


def test_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as fh:
        source = fh.read()
    assert not re.search(r"^\s*(from|import)\s+(fast_autoaugment_tpu|flax|benchmarks)",
                         source, re.M)
    assert "Precision.HIGHEST" in source and "default_matmul_precision" in source


def test_evaluation_logits_agree_with_the_programs_model(seeded):
    system = seeded.model.apply(
        {"params": seeded.params, "batch_stats": seeded.stats},
        seeded.floats, train=False)
    plain = reference.forward(jax.device_get(seeded.params),
                              jax.device_get(seeded.stats), seeded.images, SIZES)
    assert plain.shape == (BATCH, 1000) and np.abs(plain).max() > 1.0
    verdict = window.logits_agreement(np.asarray(system), plain, LOGIT_TOL)
    assert verdict["ok"], verdict
    # and the comparison can fail: a wrong BatchNorm epsilon is caught
    reference._BN_EPS, kept = 1e-2, reference._BN_EPS
    try:
        wrong = reference.forward(jax.device_get(seeded.params),
                                  jax.device_get(seeded.stats), seeded.images, SIZES)
    finally:
        reference._BN_EPS = kept
    assert not window.logits_agreement(np.asarray(system), wrong, LOGIT_TOL)["ok"]


def test_the_reference_takes_its_images_a_block_at_a_time(seeded, monkeypatch):
    whole = reference.forward(jax.device_get(seeded.params),
                              jax.device_get(seeded.stats), seeded.images, SIZES)
    monkeypatch.setattr(reference, "_FORWARD_BLOCK", 3)
    blocks = reference.forward(jax.device_get(seeded.params),
                               jax.device_get(seeded.stats), seeded.images, SIZES)
    assert blocks.shape == whole.shape and _gap(blocks, whole) < 1e-5


def test_training_loss_gradients_and_statistics_agree(system_step, reference_step):
    loss, grads, new_stats = system_step
    plain_loss, plain_grads, plain_stats = reference_step
    assert abs(loss - plain_loss) <= LOSS_TOL * abs(plain_loss)
    assert jax.tree.structure(grads) == jax.tree.structure(plain_grads)
    assert _largest_gap(grads, plain_grads) <= GRAD_TOL
    assert _largest_gap(new_stats, plain_stats) <= STATS_TOL
    # downstream of every ReLU that could flip (the last stage and the head)
    # the two agree to rounding
    for name in ("fc", "layer4_2", "layer4_1"):
        assert _largest_gap(grads[name], plain_grads[name]) <= 1e-4, name


def test_the_comparison_fails_on_the_papers_stride_placement(
        seeded, system_step, monkeypatch):
    """torchvision strides a stage's first block in its 3x3; the paper in
    the first 1x1.  Same shapes, another function: the comparison says so."""
    conv = reference._conv

    def stride_moved(x, p, stride=1):
        if p["kernel"].shape[0] == 3 and stride == 2:
            return conv(x[:, ::2, ::2, :], p, 1)   # as if conv1 had strided
        return conv(x, p, stride)

    monkeypatch.setattr(reference, "_conv", stride_moved)
    loss, grads, _ = reference.loss_and_grads(
        jax.device_get(seeded.params), jax.device_get(seeded.stats),
        np.asarray(seeded.floats), np.asarray(seeded.labels), SIZES)
    assert _largest_gap(system_step[1], grads) > 10 * GRAD_TOL


# ------------------------------------------ the configuration and the cell


def test_the_configuration_file_states_no_cut():
    from test_bench_spec import check_config

    entry = next(c for c in spec.load_benchmark()["configs"] if c["name"] == CONFIG)
    check_config(spec.ROOT, entry)
    held = spec.load_json(os.path.join(spec.ROOT, entry["file"]))
    assert held["model"] == dict(SIZES, image=224)
    assert held["model"] == dict(
        flops.model_from_conf(held["conf"]["model"]), num_classes=1000, image=224)
    assert held["reduced"] == entry["reduced"] == []
    conf = held["conf"]
    assert (conf["batch"], conf["epoch"], conf["lr"], conf["cutout"]) == (128, 270, 0.05, 0)
    assert conf["aug"] == "fa_reduced_imagenet" and conf["dataset"] == "imagenet"
    assert conf["lr_schedule"]["type"] == "resnet"
    assert conf["optimizer"] == {"type": "sgd", "nesterov": True, "decay": 1e-4,
                                 "clip": 0, "ema": 0}
    assert set(held["assumed"]) == {"batch", "epoch", "precision", "data"}
    assert held["flops"] == held["reference"] == "resnet"
    assert held["logit_tolerance"] == DEPLOYED_LIMIT
    assert held["logit_tolerance_float32"] == FLOAT32_LIMIT
    assert "1512.03385" in held["architecture"] and held["deployment"]


def test_the_policy_the_conf_names_has_498_rows():
    from fast_autoaugment_tpu.train.trainer import resolve_policy_tensor

    assert resolve_policy_tensor("fa_reduced_imagenet").shape == (498, 2, 3)


def test_the_cell_runs_the_conf_at_the_entry_points_defaults():
    cell = spec.resolve_cell(CELL)
    assert cell.chips == 1 and cell.traffic["program"] == "train_hostfed"
    assert cell.traffic["conf_overrides"] == {} and cell.traffic["entry_args"] == {}
    assert cell.conf_dict() == cell.config["conf"]
    traffic = cell.traffic
    assert traffic["dispatch_label"] == "train_step"
    assert re.match(traffic["step_program"], "jit_step_fn(123)")
    assert (traffic["warmup_dispatches_after_boundary"],
            traffic["max_dispatches_in_flight"], traffic["trace_seconds"],
            traffic["reference_images"], traffic["accuracy_margin"]) == (
                2, 3, 6, 256, 0.02)
    fixture = cell.fixture
    assert fixture["train_entries"] == 15360 == 120 * cell.conf_dict()["batch"]
    assert fixture["train_files"] * fixture["listed_times"] == 15360
    assert (fixture["val_files"], fixture["width"], fixture["height"],
            fixture["quality"], fixture["classes"], fixture["of_classes"]) == (
                1000, 500, 375, 90, 10, 1000)
    # ISSUE 32's parameters stand: no trim was needed for the 300 s cold run
    assert "trims" not in traffic and "trims" not in fixture
    assert {m["name"] for m in cell.end_to_end} == {"train_images_per_s", "setup_s"}
    named = {m["name"] for m in cell.per_layer}
    wrn = {m["name"] for m in spec.resolve_cell("wrn28x10_train").per_layer}
    assert wrn - named == {"batch_gather_device_ms"}   # nothing is gathered
    # what only the host-fed 224-px program has to read (PR 34)
    assert named - wrn == set(OWN_READERS) and len(named) == 19
    assert traffic["host_tracer_level"] == 0 and traffic["host_tracer_level_because"]


def test_every_metric_lists_cells_that_exist_and_move_what_it_moves():
    """By rule and for every cell, so that a later cell or metric needs no
    edit here: a list is not empty, names cells that exist, and each of
    them reports the end-to-end metric the per-layer metric moves."""
    bench = spec.load_benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    reports = {e["name"]: set(e.get("workloads", cells)) for e in bench["end_to_end"]}
    for metric in bench["per_layer"]:
        listed = metric["workloads"]
        assert listed and len(set(listed)) == len(listed), metric["name"]
        assert set(listed) <= cells & reports[metric["moves"]], metric["name"]
        spec.load_module("layer_metrics", metric["name"])   # a reader is there


@pytest.mark.parametrize("cell_name", [
    w["name"] for w in spec.load_benchmark()["workloads"]])
def test_every_metric_a_cell_lists_has_a_reader_that_agrees_with_its_entry(cell_name):
    """The converse, cell by cell: what ``run.py::read_layer_metrics`` would
    raise on in a traced run on the chip is caught here.  A metric lists
    the cells whose programs have what it reads; no test names a cell that
    every metric must list."""
    cell = spec.resolve_cell(cell_name, trace=True)
    assert cell.per_layer
    for entry in cell.per_layer:
        reader = runner.reader_for(cell, entry)
        assert callable(reader.read) and reader.__doc__, entry["name"]
        assert cell_name in entry["workloads"]


# --------------------------------------------------------- the JPEG fixture

TINY_FIXTURE = {
    "train_entries": 48, "train_files": 24, "listed_times": 2, "val_files": 8,
    "width": 40, "height": 30, "upright_every": 4, "quality": 90, "classes": 3,
    "of_classes": 1000, "wnid_first": 1440764, "wnid_step": 1000,
    "template_seed": 1512, "template_grid": 2, "template_amplitude": 40.0,
    "gain_low": 0.4, "gain_high": 1.2, "offset": 30.0, "noise_amplitude": 44,
    "mean_file_bytes_must_lie_in": [1, 10**9]}


def _file_bytes(root):
    return {os.path.relpath(os.path.join(folder, name), root):
            open(os.path.join(folder, name), "rb").read()
            for folder, _, names in os.walk(root) for name in names}


def test_the_fixture_is_the_same_bytes_for_the_same_seed(tmp_path):
    a, b, c = (str(tmp_path / name) for name in "abc")
    seed = 2**31 + 7   # the driver's seeds are larger than 32 signed bits hold
    wrote = fixture_jpeg.write_fixture(a, TINY_FIXTURE, seed)
    fixture_jpeg.write_fixture(b, TINY_FIXTURE, seed)
    fixture_jpeg.write_fixture(c, TINY_FIXTURE, seed + 1)
    assert _file_bytes(a) == _file_bytes(b)
    differing = [k for k, v in _file_bytes(a).items()
                 if k.endswith(".JPEG") and _file_bytes(c)[k] != v]
    assert len(differing) == 24 + 8
    assert wrote["files"] == 24 and wrote["mean_file_bytes"] == wrote["bytes"] / 24
    # a fixture already there for the seed is left alone, another's replaced
    stamp = os.path.getmtime(os.path.join(a, "train_cls.txt"))
    assert fixture_jpeg.write_fixture(a, TINY_FIXTURE, seed) == wrote
    assert os.path.getmtime(os.path.join(a, "train_cls.txt")) == stamp
    fixture_jpeg.write_fixture(a, TINY_FIXTURE, seed + 1)
    assert _file_bytes(a) == {k.replace(c, a): v for k, v in _file_bytes(c).items()}


def test_the_programs_loader_reads_the_fixture(tmp_path):
    root = str(tmp_path / "data")
    fixture_jpeg.write_fixture(root, TINY_FIXTURE, 5)
    train, val = load_dataset("imagenet", root)
    assert train.lazy and val.lazy
    assert len(train) == 48 and len(set(train.images)) == 24   # each listed twice
    assert len(val) == 8
    assert set(train.labels) == set(val.labels) == {0, 1, 2}
    sizes = []
    for path, label in zip(train.images[:24], train.labels[:24]):
        with PIL.Image.open(path) as img:
            assert img.format == "JPEG" and img.mode == "RGB"
            assert not img.info.get("progressive")           # baseline
            sizes.append(img.size)
        wnid = os.path.basename(os.path.dirname(path))
        assert fixture_jpeg.wnids(TINY_FIXTURE).index(wnid) == label
    assert sizes.count((30, 40)) == 6 and sizes.count((40, 30)) == 18
    with open(os.path.join(root, "train_cls.txt")) as fh:
        first = fh.readline().split()
    assert re.fullmatch(r"n\d{8}/n\d{8}_0", first[0]) and first[1] == "1"


def test_the_shipped_fixture_makes_files_of_imagenets_size():
    held = spec.resolve_cell(CELL).fixture
    templates = fixture_jpeg.class_templates(held)
    assert templates.shape == (10, 4, 4, 3)
    import io

    sizes, shapes = [], []
    for index in range(8):
        image = fixture_jpeg.make_image(held, templates, 2**31 + 3, "train", index)
        buffer = io.BytesIO()
        PIL.Image.fromarray(image).save(buffer, format="JPEG", quality=90)
        sizes.append(buffer.tell())
        shapes.append(image.shape)
    low, high = held["mean_file_bytes_must_lie_in"]
    assert (low, high) == (90000, 130000) and low <= np.mean(sizes) <= high
    assert shapes.count((500, 375, 3)) == 2 and shapes.count((375, 500, 3)) == 6


# ------------------------------------- the scopes in a trace, and the feed

JIT = "jit(step_fn)/"
MODULES = {"jit_step_fn": {
    "fusion.1": JIT + "vmap(faa_aug_policy)/faa_aug_op_Equalize/dot_general",
    "fusion.2": JIT + "vmap(faa_aug_fixed)/select_n",
    "fusion.3": JIT + "vmap(faa_aug_fixed)/faa_aug_jitter/switch/mul",
    "fusion.4": JIT + "vmap(faa_aug_fixed)/faa_aug_lighting/add",
    "fusion.5": JIT + "jvp(faa_model)/ResNet/faa_resnet_stem/conv1/conv_general_dilated",
    "fusion.6": JIT + "jvp(faa_model)/ResNet/layer1_0/conv1/conv_general_dilated",
    "fusion.7": JIT + "transpose(jvp(faa_model))/ResNet/faa_resnet_stem/bn1/BatchNorm_0/reduce_sum",
    "fusion.8": JIT + "transpose(jvp(faa_model))/ResNet/layer1_0/conv1/conv_general_dilated",
    "fusion.9": JIT + "faa_optimizer/add"}}
DURATIONS = {"fusion.1": 300, "fusion.2": 10, "fusion.3": 40, "fusion.4": 5,
             "fusion.5": 30, "fusion.6": 170, "fusion.7": 60, "fusion.8": 340,
             "fusion.9": 20}
COUNTERS = {
    "open": {"faa_feed_batches_total": 122.0, "faa_feed_wait_seconds_total": 1.5,
             "faa_decode_images_total": 16000.0,
             'faa_decode_seconds_total{decoder="native"}': 9.0},
    "closed": {"faa_feed_batches_total": 202.0, "faa_feed_wait_seconds_total": 1.9,
               "faa_decode_images_total": 26240.0,
               'faa_decode_seconds_total{decoder="native"}': 12.0,
               'faa_decode_seconds_total{decoder="pil"}': 1.0}}


def _plane(executions=4):
    ops, runs, t0 = [], [], 0.0
    for _ in range(executions):
        at = t0
        for name, dur in DURATIONS.items():
            ops.append(tr.Event(f"%{name} = f32[8]{{0}} fusion(f32[8] %a), kind=kLoop",
                                at, float(dur)))
            at += dur
        runs.append(tr.Event("jit_step_fn(1)", t0, 1000.0))
        t0 += 1010.0
    return tr.Plane("/device:TPU:0", [tr.Line(tr.OPS_LINE, ops),
                                      tr.Line(tr.MODULES_LINE, runs)])


@pytest.fixture()
def traced(monkeypatch, tmp_path):
    cell = spec.resolve_cell(CELL, trace=True)
    obs = Observed(cell=cell, end_to_end={}, window_s=1.0,
                   devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
                   attempted=0, failed=0, checks={}, compile_stats={},
                   memory_peak_bytes=0, step_program=cell.traffic["step_program"],
                   trace_dir=str(tmp_path),
                   work={"images_per_s_per_chip": 1000.0, "passes": "train"})
    plane = _plane()
    obs.__dict__["trace"] = TraceView([plane], tr.traced_window([plane]), None)
    monkeypatch.setattr(compilecache, "scope_map", lambda label: MODULES)
    return obs


def _read(obs, name):
    return spec.load_module("layer_metrics", name).read(obs)


def test_the_nested_scopes_stay_in_their_parents_time(traced):
    # what the table printer shows of the ImageNet stack and the stem
    assert hs.scope_ms(traced, scopes.AUG_JITTER) == pytest.approx(40e-6)
    assert hs.scope_ms(traced, scopes.AUG_LIGHTING) == pytest.approx(5e-6)
    assert hs.scope_ms(traced, scopes.RESNET_STEM) == pytest.approx(90e-6)
    assert hs.scope_ms(traced, scopes.RESNET_STEM, backward=True) == pytest.approx(60e-6)
    # and the standing readers take them with their parents
    assert _read(traced, "aug_fixed_device_ms") == pytest.approx(55e-6)
    assert _read(traced, "aug_policy_device_ms") == pytest.approx(300e-6)
    assert _read(traced, "model_forward_device_ms") == pytest.approx(200e-6)
    assert _read(traced, "model_backward_device_ms") == pytest.approx(400e-6)
    assert _read(traced, "step_unscoped_share") == pytest.approx(2.5)
    # every per-layer metric the cell lists that needs no device comes out
    traced.end_to_end = {"train_images_per_s": 1.0, "setup_s": 1.0}
    reported = set(runner.read_layer_metrics(traced))
    assert {"step_device_ms", "step_unscoped_share", "aug_policy_device_ms",
            "aug_fixed_device_ms", "model_flops_utilization"} <= reported
    # 24.5 GFLOP an image forward and backward, at 1,000 images/s, of 197 TFLOP/s
    assert _read(traced, "model_flops_utilization") == pytest.approx(
        100 * 6 * 4_089_184_256 * 1000.0 / 197e12)


def _guard_with(counters):
    guarded = program.OneBeatADispatch(
        types.SimpleNamespace(first_count=0), steps_per_dispatch=1)
    guarded.counters = counters
    return guarded


def test_what_the_counters_say_of_the_feed_over_the_window():
    # 0.4 s more waited over 80 batches; 10,240 images over 3 + 1 seconds
    assert _guard_with(COUNTERS).feed_over_the_window() == {
        "wait_ms_a_step": pytest.approx(5.0),
        "decode_images_per_s": pytest.approx(2560.0)}


@pytest.mark.parametrize("counters", [
    {}, {"open": {}, "closed": {}}, {"open": COUNTERS["open"]},
    {"open": {"faa_feed_batches_total": 5.0, "faa_decode_images_total": 9.0},
     "closed": {"faa_feed_batches_total": 5.0, "faa_decode_images_total": 9.0}}],
    ids=["no_ends", "no_counters", "never_closed", "nothing_moved"])
def test_a_window_without_two_ends_or_counters_says_nothing_of_the_feed(counters):
    """A program from before the counters, or a run that never closed."""
    assert _guard_with(counters).feed_over_the_window() == {}


@pytest.mark.parametrize("metric, value", [
    ("feed_wait_ms", 5.0), ("host_decode_images_per_s", 2560.0),
    ("aug_jitter_device_ms", 45e-6), ("resnet_stem_device_ms", 90e-6)])
def test_a_reader_of_this_cells_own_reads_its_counter_or_scope(traced, metric, value):
    """On the synthetic run: the counters' two quotients as the program
    hands them over in ``Observed.work``, jitter 40 + lighting 5 ns, and
    the stem's 30 forward + 60 backward."""
    traced.work.update(_guard_with(COUNTERS).feed_over_the_window())
    assert _read(traced, metric) == pytest.approx(value)
    traced.end_to_end = {"train_images_per_s": 1.0, "setup_s": 1.0}
    assert runner.read_layer_metrics(traced)[metric]["value"] == pytest.approx(value)


@pytest.mark.parametrize("metric, absent", [
    ("feed_wait_ms", None), ("host_decode_images_per_s", None),
    ("aug_jitter_device_ms", "AUG_LIGHTING"), ("resnet_stem_device_ms", "RESNET_STEM")])
def test_a_program_from_before_pr_32_leaves_the_readers_own_out(
        traced, monkeypatch, metric, absent):
    """The benchmark's files laid over a parent whose registry has no feed
    counters (``feed_over_the_window`` then hands over nothing) and whose
    table of scopes lacks the name: nothing is read, nothing fails."""
    reader = spec.load_module("layer_metrics", metric)
    if absent is not None:
        before = types.SimpleNamespace(**{
            name: getattr(scopes, name) for name in dir(scopes)
            if not name.startswith("_") and name != absent})
        monkeypatch.setattr(reader, "program_scopes", lambda: before)
    assert reader.read(traced) is None
    traced.end_to_end = {"train_images_per_s": 1.0, "setup_s": 1.0}
    assert metric not in runner.read_layer_metrics(traced)


def test_the_references_nesterov_step_is_the_programs_optimizer(seeded):
    """``ops/optim.py``'s chain (masked decay, trace, the learning rate)
    from an empty momentum buffer against the reference's plain formula:
    BatchNorm leaves are not decayed, every other leaf is."""
    from fast_autoaugment_tpu.ops.optim import build_optimizer

    params = {name: seeded.params[name] for name in ("conv1", "bn1", "layer1_0", "fc")}
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))
    grads = jax.tree.map(lambda p: jax.random.normal(next(keys), p.shape), params)
    optimizer = build_optimizer(
        {"type": "sgd", "nesterov": True, "decay": 1e-2, "clip": 0}, 0.05)
    moved, _ = optimizer.update(grads, optimizer.init(params), params)
    plain = reference.sgd_nesterov_first_step(
        jax.device_get(params), jax.device_get(grads), 0.05, 1e-2)
    assert jax.tree.structure(moved) == jax.tree.structure(plain)
    assert _largest_gap(moved, plain) < 1e-6
    undecayed = reference.sgd_nesterov_first_step(
        jax.device_get(params), jax.device_get(grads), 0.05, 0.0)
    assert _largest_gap(moved["bn1"], undecayed["bn1"]) < 1e-6
    assert _largest_gap(moved["fc"], undecayed["fc"]) > 1e-4


# ------------------------------------------- the heartbeat, and the program


class _FakeBeat:
    """``programs/train.py::_Beat`` as far as the guard looks at it."""

    def __init__(self, counter):
        self.counter, self.first_count, self.state, self.calls = counter, 0, "warmup", 0

    def __call__(self):
        self.calls += 1
        self.state = {3: "open", 5: "closed"}.get(self.calls, self.state)


def test_the_heartbeat_raises_on_a_trainer_that_beats_once_an_epoch():
    counter = types.SimpleNamespace(value=0.0)
    beat = _FakeBeat(counter)
    guarded = program.OneBeatADispatch(beat, steps_per_dispatch=1)
    for _ in range(4):          # a beat a dispatch
        counter.value += 1
        guarded()
    guarded()                   # an epoch boundary: no dispatch since
    assert beat.calls == 5 and set(guarded.counters) == {"open", "closed"}
    counter.value += 120        # the parent: an epoch, then its one beat
    with pytest.raises(program.BeatsTooRarely, match="120 dispatches"):
        guarded()
    assert beat.calls == 5      # the window's own heartbeat was not reached


def test_the_exception_comes_out_of_train_and_eval(tmp_path):
    """As ``programs/train.py``'s docstring says a heartbeat's does."""
    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.parallel.mesh import make_mesh
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    def heartbeat():
        raise program.BeatsTooRarely("as on the parent")

    conf = Config({"model": {"type": "wresnet10_1"}, "dataset": "synthetic",
                   "aug": "default", "cutout": 0, "batch": 8, "epoch": 1,
                   "lr": 0.05, "lr_schedule": {"type": "cosine"},
                   "optimizer": {"type": "sgd", "nesterov": True,
                                 "decay": 1e-4, "clip": 0, "ema": 0}})
    with pytest.raises(program.BeatsTooRarely):
        train_and_eval(conf, str(tmp_path), mesh=make_mesh(jax.devices()[:1]),
                       device_cache="off", heartbeat=heartbeat)


def test_train_window_on_a_small_resnet50(make_tiny_checkout):
    """The cell's program on ResNet-50 at 32 px over a 96-entry JPEG
    fixture, without the policy (498 rows compile for minutes on a CPU):
    the window opens after the first epoch and closes inside the second,
    the mid-epoch checkpoint restores through ``only_eval``, both
    comparisons run through ``imagenet_eval_batch`` against this family's
    reference, and the second fails a model with bfloat16 activations that
    the first lets through.  Counts, never times."""
    root = make_tiny_checkout()
    bench_dir = os.path.join(root, "benchmarks")
    held = spec.load_json(os.path.join(bench_dir, "configs", f"{CONFIG}.json"))
    held["conf"].update(batch=4, imgsize=32, aug="default")
    held["model"] = dict(SIZES, image=32)
    with open(os.path.join(bench_dir, "configs", "tiny_resnet.json"), "w") as fh:
        json.dump(held, fh)
    with open(os.path.join(bench_dir, "fixtures", "tiny_jpeg.json"), "w") as fh:
        json.dump(dict(TINY_FIXTURE, train_entries=96, listed_times=4), fh)
    traffic = spec.load_json(os.path.join(bench_dir, "traffic",
                                          "train_epochs_hostfed.json"))
    traffic.update(fixture="tiny_jpeg", trace_seconds=1.5, reference_images=8,
                   accuracy_margin=-1.0)
    with open(os.path.join(bench_dir, "traffic", "tiny_hostfed.json"), "w") as fh:
        json.dump(traffic, fh)
    bench = spec.load_benchmark(root)
    bench["configs"].append({
        "name": "tiny_resnet", "source": "test", "reduced": ["batch"],
        "file": "benchmarks/configs/tiny_resnet.json", "why": "test"})
    bench["workloads"].append({
        "name": "tiny_hostfed", "config": "tiny_resnet",
        "traffic": "tiny_hostfed", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny_hostfed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)

    cell = spec.resolve_cell("tiny_hostfed", seed=2147483659, seconds=0.5,
                             trace=False, root=root)
    obs = runner.run_cell(cell, jax.devices()[:1], runner.process_start_wall())
    assert obs.correct, {k: v for k, v in obs.checks.items() if not v["ok"]}
    line = runner.result_line(obs)
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    steps_per_epoch = 96 // 4
    counted = obs.checks["step_counter"]
    assert counted["checkpoint_step"] == counted["steps_counted"]
    assert counted["steps_counted"] == steps_per_epoch + 2 + obs.attempted
    assert obs.checks["no_compile_in_window"]["compile_requests"] == 0
    learned = obs.checks["learned"]
    assert learned["restored_steps"] == counted["steps_counted"]
    assert learned["num_test"] == 8 and learned["top1_must_reach"] == 0.001 - 1.0
    feed = obs.checks["feed"]
    assert feed["ok"] and feed["decoder"] in ("native", "pil")
    assert feed["fixture"]["files"] == 24
    # float32 on the CPU on both sides: the two comparisons read alike
    deployed = obs.checks["reference_logits"]
    exact = obs.checks["reference_logits_float32"]
    assert deployed["images"] == exact["images"] == 8
    assert (deployed["tolerance"], exact["tolerance"]) == (DEPLOYED_LIMIT, FLOAT32_LIMIT)
    assert exact["ok"] and exact["relative_gap"] < FLOAT32_LIMIT
    # what the feed's counters said of the window
    assert feed["wait_ms_a_step"] >= 0 and feed["decode_images_per_s"] > 0
    # and hands them to the two readers
    assert (obs.work["wait_ms_a_step"], obs.work["decode_images_per_s"]) == (
        feed["wait_ms_a_step"], feed["decode_images_per_s"])
    # the control: the nearest precision below the configuration's, from the
    # weights the window ended on: 2% lets it through, the float32 limit not
    loaded = spec.load_module("programs", "train_hostfed",
                              os.path.join(root, "benchmarks"))
    with open(os.path.join(cell.work, "ckpt", "model.msgpack"), "rb") as fh:
        saved = serialization.msgpack_restore(fh.read())
    conf = cell.conf_dict()
    images = loaded.validation_images(conf, os.path.join(cell.work, "data"), 8)
    assert images.shape == (8, 32, 32, 3) and images.dtype == np.uint8
    control = window.reference_check(
        cell, dict(conf, precision="bf16"), saved["params"],
        saved["batch_stats"], images, preprocess=imagenet_eval_batch)
    assert control["reference_logits"]["ok"]
    lower = control["reference_logits_float32"]
    assert not lower["ok"] and lower["relative_gap"] > 10 * FLOAT32_LIMIT
    assert telemetry.registry().gauge(
        "faa_model_parameters", model="resnet50").value == 25_557_032
