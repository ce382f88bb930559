"""What ``shake26_2x96d_cifar10`` brings: its operations against a hand
count, its plain reference against the program's ShakeResNet on seeded
weights (evaluation logits; training loss, every gradient and the new
running statistics under the same noise), the proof that the comparison
sees the decoupled backward pass, the configuration file, the cell, and
the reader of ``faa_shake_mix``.

How the reference gets the noise the system drew.  Each ``_ShakeMix``
asks flax for ``make_rng("shake")``: a key that is a function of the
stream's key (``rngs={"shake": key}``) and of the module's path
(``s<stage>_<i>_mix``), nothing else.  The draw itself is
``ops.shake.sample_shake_shake_noise(key, batch)``, which the model
module calls by that name: the tests wrap that name, run the model
outside ``jit`` (the draws depend on no parameter, so they are concrete
under ``jax.grad``) and hand what it returned, in block order, to the
reference as two ``[blocks, batch]`` arrays.
"""

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import serialization

from benchmarks import run as runner
from benchmarks.harness import scopes as hs
from benchmarks.harness import spec, window
from benchmarks.harness import trace as tr
from benchmarks.harness.observed import Observed, TraceView
from fast_autoaugment_tpu.core import compilecache, scopes
from fast_autoaugment_tpu.core.metrics import smooth_cross_entropy
from fast_autoaugment_tpu.data.datasets import load_dataset
from fast_autoaugment_tpu.models import get_model, shake_resnet
from fast_autoaugment_tpu.ops.preprocess import cifar_eval_batch

reference = spec.load_module("references", "shake_resnet")
flops = spec.load_module("flops", "shake_resnet")

CONFIG = "shake26_2x96d_cifar10"
CELL = "shake26_2x96d_train"
W, BATCH = 8, 4          # the small size: w_base 8, batch 4, 32 px
SIZES = {"depth": 26, "w_base": W, "num_classes": 10, "image": 32}

# Tolerances, as the largest difference over the largest reference entry of
# the same array.  Both sides are float32 on the CPU at full precision, so
# what differs is rounding order: flax's BatchNorm takes the variance as
# E[x^2] - E[x]^2 where the reference takes E[(x - E[x])^2], and XLA is free
# to reassociate sums.  Measured at this size: evaluation logits 0 (the same
# operations in the same order), loss 3e-7, gradients 1.1e-5 through 26
# layers and 51 batch-statistics BatchNorms, new statistics 7e-7.  With alpha
# in place of beta the gradients are off by 3.1 of the largest entry.
LOGIT_TOL, LOSS_TOL, GRAD_TOL, STATS_TOL = 1e-5, 1e-5, 2e-4, 1e-5
#: the configuration's ``logit_tolerance_float32`` (its readings are in the file)
FLOAT32_LIMIT = 1e-5


def _gap(system, plain) -> float:
    system, plain = np.asarray(system, np.float64), np.asarray(plain, np.float64)
    return float(np.max(np.abs(system - plain)) / np.max(np.abs(plain)))


def _largest_gap(system_tree, plain_tree) -> float:
    return max(jax.tree.leaves(jax.tree.map(
        _gap, jax.device_get(system_tree), plain_tree)))


@pytest.fixture(scope="module")
def seeded():
    """The program's model at the small size, seeded weights, running
    statistics away from their initial 0 and 1, four images and labels."""
    model = get_model({"type": f"shakeshake26_2x{W}d", "dataset": "cifar10"}, 10)
    variables = model.init(
        {"params": jax.random.PRNGKey(3), "shake": jax.random.PRNGKey(4)},
        jnp.zeros((2, 32, 32, 3)), train=False)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 1000))
    stats = jax.tree.map(
        lambda x: x + 0.5 * jax.random.uniform(next(keys), x.shape),
        variables["batch_stats"])
    images = np.random.default_rng(0).integers(
        0, 256, (BATCH, 32, 32, 3), dtype=np.uint8)
    return types.SimpleNamespace(
        model=model, params=variables["params"], stats=stats, images=images,
        floats=cifar_eval_batch(jnp.asarray(images)),
        labels=jnp.asarray([1, 5, 3, 9]))


def _system_step(seeded, monkeypatch, stream_key):
    """One training-mode pass of the system outside ``jit``:
    ``(loss, grads, new_stats, alpha, beta)``, the noise as the wrapped
    draw returned it, ``[blocks, batch]`` each."""
    drawn = []
    draw = shake_resnet.sample_shake_shake_noise

    def recording(key, batch, dtype=jnp.float32):
        drawn.append(draw(key, batch, dtype))
        return drawn[-1]

    monkeypatch.setattr(shake_resnet, "sample_shake_shake_noise", recording)

    def loss_fn(params):
        logits, mutated = seeded.model.apply(
            {"params": params, "batch_stats": seeded.stats}, seeded.floats,
            train=True, mutable=["batch_stats"], rngs={"shake": stream_key})
        return smooth_cross_entropy(logits, seeded.labels, 0.0), mutated["batch_stats"]

    (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(seeded.params)
    alpha, beta = (np.stack([np.asarray(pair[i]).reshape(-1) for pair in drawn])
                   for i in (0, 1))
    return float(loss), grads, new_stats, alpha, beta


def _reference_step(seeded, alpha, beta):
    return reference.loss_and_grads(
        jax.device_get(seeded.params), jax.device_get(seeded.stats),
        np.asarray(seeded.floats), np.asarray(seeded.labels), (alpha, beta), SIZES)


# ------------------------------------------------------- operations, shapes


def _hand_count(w, n=4, classes=10):
    """Multiply-accumulates and parameters of Shake-Shake-26 2x{w}d on a
    32-px image, stage by stage: the first block of a stage takes the
    previous width in, strides in its first convolutions, and has the
    two-path shortcut (two 1x1 convolutions to half the width each)."""
    macs = 9 * 3 * 16 * 32 * 32
    params = 9 * 3 * 16 + 16
    c_in = 16
    for width, px in ((w, 32), (2 * w, 16), (4 * w, 8)):
        first = 2 * (9 * c_in * width + 9 * width * width) + 2 * c_in * (width // 2)
        rest = (n - 1) * 2 * 2 * 9 * width * width
        macs += (first + rest) * px * px
        params += first + rest
        params += n * 2 * 2 * 2 * width + 2 * width   # 4 BatchNorms a block + 1
        c_in = width
    return macs + 4 * w * classes, params + 4 * w * classes + classes


@pytest.mark.parametrize("w_base, macs, params", [
    (96, 3_776_892_672, 26_192_906), (32, 426_689_792, 2_923_146)])
def test_operations_and_parameters_match_a_hand_count(w_base, macs, params):
    model = dict(SIZES, w_base=w_base)
    assert flops.forward_macs_per_image(model) == macs
    assert flops.num_params(model) == params
    assert _hand_count(w_base) == (macs, params)
    assert flops.forward_flops_per_image(model) == 2 * macs
    assert flops.train_flops_per_image(model) == 6 * macs
    assert flops.num_mixes(model) == 12


def test_parameter_count_matches_the_programs_model(seeded):
    counted = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(seeded.params))
    assert flops.num_params(SIZES) == counted


@pytest.mark.parametrize("conf_model, sizes", [
    ({"type": "shakeshake26_2x96d"}, {"depth": 26, "w_base": 96}),
    ({"type": "shakeshake26_2x32d"}, {"depth": 26, "w_base": 32}),
    ({"type": "shakeshake26_2x96d_next"}, None),
    ({"type": "wresnet28_10"}, None),
    ({}, None)])
def test_model_from_conf_takes_this_family_alone(conf_model, sizes):
    if sizes is None:
        with pytest.raises(ValueError, match="not a Shake-Shake ResNet"):
            flops.model_from_conf(conf_model)
    else:
        assert flops.model_from_conf(conf_model) == sizes


def test_depth_must_be_6n_plus_2():
    with pytest.raises(ValueError, match="6n\\+2"):
        flops.forward_macs_per_image(dict(SIZES, depth=28))


# ------------------------------------------------ the reference, evaluation


def test_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as fh:
        source = fh.read()
    assert not re.search(r"^\s*(from|import)\s+(fast_autoaugment_tpu|flax|benchmarks)",
                         source, re.M)
    assert "custom_vjp(" not in source and "stop_gradient" in source


def test_evaluation_logits_agree_with_the_programs_model(seeded):
    system = seeded.model.apply(
        {"params": seeded.params, "batch_stats": seeded.stats},
        seeded.floats, train=False)
    plain = reference.forward(jax.device_get(seeded.params),
                              jax.device_get(seeded.stats), seeded.images, SIZES)
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


def test_evaluation_is_the_half_mix_and_draws_nothing(seeded):
    """No ``shake`` stream is given: evaluation must not ask for one, and
    two passes give the same logits."""
    apply = lambda: seeded.model.apply(  # noqa: E731
        {"params": seeded.params, "batch_stats": seeded.stats},
        seeded.floats, train=False)
    np.testing.assert_array_equal(np.asarray(apply()), np.asarray(apply()))


# -------------------------------------------------- the reference, training


def test_training_loss_gradients_and_statistics_agree_under_the_same_noise(
        seeded, monkeypatch):
    loss, grads, new_stats, alpha, beta = _system_step(
        seeded, monkeypatch, jax.random.PRNGKey(11))
    assert alpha.shape == beta.shape == (flops.num_mixes(SIZES), BATCH)
    plain_loss, plain_grads, plain_stats = _reference_step(seeded, alpha, beta)
    assert abs(loss - plain_loss) <= LOSS_TOL * abs(plain_loss)
    assert jax.tree.structure(jax.device_get(grads)) == jax.tree.structure(plain_grads)
    assert _largest_gap(grads, plain_grads) <= GRAD_TOL
    assert jax.tree.structure(jax.device_get(new_stats)) == jax.tree.structure(plain_stats)
    assert _largest_gap(new_stats, plain_stats) <= STATS_TOL


def test_the_comparison_fails_when_beta_is_replaced_by_alpha(seeded, monkeypatch):
    """The mechanism under test: the forward value does not see beta, the
    gradients do.  Given alpha for beta (what autodiff through the forward
    mix would compute) the reference's loss still agrees and its
    gradients do not."""
    loss, grads, _, alpha, beta = _system_step(
        seeded, monkeypatch, jax.random.PRNGKey(11))
    assert np.max(np.abs(alpha - beta)) > 0.1
    coupled_loss, coupled_grads, _ = _reference_step(seeded, alpha, alpha)
    assert abs(loss - coupled_loss) <= LOSS_TOL * abs(coupled_loss)
    gaps = jax.tree.map(_gap, jax.device_get(grads), coupled_grads)
    assert max(jax.tree.leaves(gaps)) > 1000 * GRAD_TOL
    # what no mix stands behind is untouched by the swap: the linear layer
    assert max(jax.tree.leaves(gaps["fc_out"])) <= GRAD_TOL
    # every block in front of a mix is touched
    assert min(jax.tree.leaves(gaps["s0_0_branch1"])) > GRAD_TOL


def test_the_noise_is_a_function_of_the_stream_key_and_the_module_path(
        seeded, monkeypatch):
    _, _, _, alpha, beta = _system_step(seeded, monkeypatch, jax.random.PRNGKey(11))
    _, _, _, again_a, again_b = _system_step(seeded, monkeypatch, jax.random.PRNGKey(11))
    _, _, _, other_a, _ = _system_step(seeded, monkeypatch, jax.random.PRNGKey(12))
    np.testing.assert_array_equal(alpha, again_a)
    np.testing.assert_array_equal(beta, again_b)
    assert not np.array_equal(alpha, other_a)
    # one stream, twelve paths: no two blocks draw the same coefficients
    assert len({row.tobytes() for row in alpha}) == len(alpha)
    assert len({row.tobytes() for row in beta}) == len(beta)
    assert ((0 <= alpha) & (alpha < 1) & (0 <= beta) & (beta < 1)).all()


# ------------------------------------------ the configuration and the cell


def test_the_configuration_file_states_its_cut():
    entry = next(c for c in spec.load_benchmark()["configs"] if c["name"] == CONFIG)
    held = spec.load_json(os.path.join(spec.ROOT, entry["file"]))
    assert held["model"] == {"depth": 26, "w_base": 96, "num_classes": 10, "image": 32}
    assert held["model"] == dict(
        flops.model_from_conf(held["conf"]["model"]), num_classes=10, image=32)
    assert held["reduced"] == entry["reduced"] == ["batch"]
    assert held["conf"]["batch"] == 512 and held["conf"]["lr"] == 0.01
    assert set(held["assumed"]) == {"batch", "epoch", "precision", "data"}
    assert held["flops"] == held["reference"] == "shake_resnet"
    # as deployed (bfloat16 operands), and with the program's own
    # arithmetic alone: the second limit lies between a sound run's gap and
    # that of bfloat16 activations (the file gives both readings)
    assert held["logit_tolerance"] == 0.02
    assert held["logit_tolerance_float32"] == FLOAT32_LIMIT
    assert "1705.07485" in held["architecture"] and held["deployment"]
    assert flops.forward_macs_per_image(held["model"]) == 3_776_892_672


def test_the_cell_runs_the_conf_at_the_entry_points_defaults():
    cell = spec.resolve_cell(CELL)
    assert cell.chips == 1 and cell.traffic["program"] == "train"
    assert cell.traffic["conf_overrides"] == {} and cell.traffic["entry_args"] == {}
    assert cell.conf_dict() == cell.config["conf"]
    assert cell.conf_dict()["model"] == {"type": "shakeshake26_2x96d"}
    assert 50_000 // cell.conf_dict()["batch"] == 97   # steps an epoch
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"train_images_per_s", "setup_s"}


def test_the_cells_traffic_is_train_epochs_under_the_name_the_ledger_knows():
    """Two files, one traffic and one program: every parameter the harness
    reads is equal, so the cell's window is ``wrn28x10_train``'s and the two
    cannot drift.  The second comparison the file was added for is
    ``reference_check``'s since PR 34, for every configuration that states
    its limit; the program that made it is gone."""
    ours = spec.load_json(os.path.join(
        spec.BENCH_DIR, "traffic", "train_epochs_float32_check.json"))
    theirs = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", "train_epochs.json"))
    words = {"describes", "entry_defaults_in_force", "reduced",
             "accuracy_margin_because"}
    assert set(ours) == set(theirs)
    assert {k: v for k, v in ours.items() if k not in words} == \
        {k: v for k, v in theirs.items() if k not in words}
    assert set(ours["reduced"]) == set(theirs["reduced"])
    assert ours["program"] == theirs["program"] == "train"
    assert not os.path.exists(os.path.join(
        spec.BENCH_DIR, "programs", "train_float32_check.py"))
    entry = next(w for w in spec.load_benchmark()["workloads"] if w["name"] == CELL)
    assert (entry["traffic"], entry["config"]) == ("train_epochs_float32_check", CONFIG)


def test_the_cell_reports_what_wrn28x10_train_reports_and_its_two_own():
    """The set, by rule: the same policy at the same batch from the same
    device cache, so every metric ``wrn28x10_train`` lists this cell lists
    too, and the two that read what only a Shake-Shake program has.  A later
    configuration's own metrics list neither cell and change nothing here;
    that every list is sound is ``test_bench_resnet.py::
    test_every_metric_lists_cells_that_exist_and_move_what_it_moves``."""
    named = {m["name"] for m in spec.resolve_cell(CELL).per_layer}
    wrn = {m["name"] for m in spec.resolve_cell("wrn28x10_train").per_layer}
    assert named == wrn | {"shake_mix_fusions_device_ms", "shake_shortcut_device_ms"}
    assert len(named) == len(wrn) + 2 == 18


# ------------------------------------------------- the reader of the scope

JIT = "jit(multi_fn)/"
#: ``op_name`` strings as the lowered step carries them
#: (tests/test_shake_train_step.py holds the program to these forms): the
#: mix's backward instructions come out of the ``custom_vjp`` rule
MODULES = {"jit_multi_fn": {
    "fusion.1": JIT + "vmap(faa_aug_policy)/faa_aug_op_Rotate/gather",
    "fusion.2": JIT + "jvp(faa_model)/ShakeResNet/s1_0_branch1/conv1/conv_general_dilated",
    "fusion.3": JIT + "jvp(faa_model)/ShakeResNet/s1_0_mix/faa_shake_mix/jit(_uniform)/sub",
    "fusion.4": JIT + "jvp(faa_model)/ShakeResNet/s1_0_mix/faa_shake_mix/add",
    "fusion.5": JIT + "jvp(faa_model)/ShakeResNet/s1_0_shortcut/faa_shake_shortcut/conv1/conv_general_dilated",
    "fusion.6": JIT + "transpose(jvp(faa_model))/ShakeResNet/s1_0_mix/faa_shake_mix/mul",
    "fusion.7": JIT + "transpose(jvp(faa_model))/ShakeResNet/s1_0_shortcut/faa_shake_shortcut/bn/BatchNorm_0/reduce_sum",
    "fusion.8": JIT + "transpose(jvp(faa_model))/ShakeResNet/s1_0_branch1/conv1/conv_general_dilated",
    "fusion.9": JIT + "faa_optimizer/add"}}
#: by membership (``compilecache.scope_members``): the two fusions of the
#: branch's convolutions are rooted in ``faa_model`` and hold the mix's
#: products too, as XLA fuses them on the chip
MEMBERS = {"jit_multi_fn": {
    name: tuple(sorted(set(scopes.scope_of(op_name)) | (
        {scopes.SHAKE_MIX} if name in ("fusion.2", "fusion.8") else set())))
    for name, op_name in MODULES["jit_multi_fn"].items()}}
#: nanoseconds of each instruction in one execution of 1,000
DURATIONS = {"fusion.1": 300, "fusion.2": 200, "fusion.3": 5, "fusion.4": 15,
             "fusion.5": 30, "fusion.6": 25, "fusion.7": 45, "fusion.8": 330,
             "fusion.9": 20}


def _plane(executions=4):
    ops, runs, t0 = [], [], 0.0
    for _ in range(executions):
        at = t0
        for name, dur in DURATIONS.items():
            ops.append(tr.Event(f"%{name} = f32[8]{{0}} fusion(f32[8] %a), kind=kLoop",
                                at, float(dur)))
            at += dur
        runs.append(tr.Event("jit_multi_fn(1)", t0, 1000.0))
        t0 += 1010.0
    return tr.Plane("/device:TPU:0", [tr.Line(tr.OPS_LINE, ops),
                                      tr.Line(tr.MODULES_LINE, runs)])


@pytest.fixture()
def traced(monkeypatch, tmp_path):
    cell = spec.resolve_cell(CELL, trace=True)
    obs = Observed(cell=cell, devices=[], end_to_end={}, window_s=1.0,
                   attempted=0, failed=0, checks={}, compile_stats={},
                   memory_peak_bytes=0, step_program=cell.traffic["step_program"],
                   trace_dir=str(tmp_path))
    plane = _plane()
    obs.__dict__["trace"] = TraceView([plane], tr.traced_window([plane]), None)
    monkeypatch.setattr(compilecache, "scope_map", lambda label: MODULES)
    monkeypatch.setattr(compilecache, "scope_members", lambda label: MEMBERS)
    return obs


def test_the_mix_reads_forward_and_backward_and_stays_in_the_models_time(traced):
    read = lambda name: spec.load_module("layer_metrics", name).read(traced)  # noqa: E731
    # rooted in the mix: the draw and the two products; held by membership:
    # those and the two convolution fusions the products ride in
    assert hs.scope_ms(traced, scopes.SHAKE_MIX) == pytest.approx((5 + 15 + 25) * 1e-6)
    assert hs.scope_ms(traced, scopes.SHAKE_MIX, backward=True) == pytest.approx(25e-6)
    assert read("shake_mix_fusions_device_ms") == pytest.approx(
        (5 + 15 + 25 + 200 + 330) * 1e-6)
    assert read("shake_shortcut_device_ms") == pytest.approx(75e-6)
    assert read("model_forward_device_ms") == pytest.approx(250e-6)
    assert read("model_backward_device_ms") == pytest.approx(400e-6)
    step_ms = read("step_device_ms")
    six = sum(read(name) for name in (
        "aug_policy_device_ms", "aug_fixed_device_ms", "model_forward_device_ms",
        "model_backward_device_ms", "optimizer_device_ms", "batch_gather_device_ms"))
    assert six + read("step_unscoped_share") / 100 * step_ms == pytest.approx(step_ms)
    table = "\n".join(hs.format_table(
        "train_dispatch", hs.step_split(traced), scopes))
    # nested rows: a scope under its parent, backward beside forward
    rows = [line.split()[0] for line in table.splitlines()]
    for scope in (scopes.SHAKE_MIX, scopes.SHAKE_SHORTCUT):
        assert scope in rows and scope + hs.BACKWARD in rows


@pytest.mark.parametrize("metric, scope", [
    ("shake_mix_fusions_device_ms", "SHAKE_MIX"),
    ("shake_shortcut_device_ms", "SHAKE_SHORTCUT")])
def test_a_program_from_before_the_scope_leaves_the_metric_out(
        traced, monkeypatch, metric, scope):
    """The parent of the PR that added the cell: the benchmark's files
    laid over a program whose table lacks the scope."""
    before = types.SimpleNamespace(**{
        name: getattr(scopes, name) for name in dir(scopes)
        if not name.startswith("_") and name != scope})
    reader = spec.load_module("layer_metrics", metric)
    monkeypatch.setattr(reader, "program_scopes", lambda: before)
    assert reader.read(traced) is None
    traced.end_to_end = {"train_images_per_s": 1.0, "setup_s": 1.0}
    traced.cell.per_layer = [m for m in traced.cell.per_layer
                             if m["name"] in (metric, "step_device_ms")]
    assert set(runner.read_layer_metrics(traced)) == {"step_device_ms"}


def test_membership_reads_nothing_where_the_program_cannot_say(traced, monkeypatch):
    reader = spec.load_module("layer_metrics", "shake_mix_fusions_device_ms")
    # an executable whose text names no instruction of the mix
    monkeypatch.setattr(compilecache, "scope_members", lambda label: {
        "jit_multi_fn": {"fusion.2": (scopes.MODEL,)}})
    assert reader.read(traced) is None
    # a program with the scope and without ``scope_members``
    monkeypatch.delattr(compilecache, "scope_members")
    assert reader.read(traced) is None
    # an untraced run
    traced.__dict__["trace"] = None
    vars(traced).pop("_scope_split", None)
    assert reader.read(traced) is None


# ------------------------------------- the cell's program, rehearsed (CPU)


def test_train_window_on_a_tiny_shake_shake(make_tiny_checkout):
    """The cell's program (``programs/train.py``) on Shake-Shake-26 2x8d
    over the 400-image fixture: the window opens and closes, the checkpoint
    restores through ``only_eval``, ``reference_check`` compares the
    system's evaluation path with this family's reference twice, and the
    second comparison fails a model with bfloat16 activations that the
    first lets through.  Counts, never times — a CPU run measures nothing."""
    root = make_tiny_checkout()
    bench_dir = os.path.join(root, "benchmarks")
    held = spec.load_json(os.path.join(bench_dir, "configs", f"{CONFIG}.json"))
    held["conf"]["model"]["type"] = f"shakeshake26_2x{W}d"
    held["conf"]["batch"] = 8
    held["model"] = SIZES
    with open(os.path.join(bench_dir, "configs", "tiny_shake.json"), "w") as fh:
        json.dump(held, fh)
    tiny = spec.load_json(os.path.join(bench_dir, "traffic", "tiny_train.json"))
    assert tiny["program"] == "train"
    with open(os.path.join(bench_dir, "traffic", "tiny_shake_train.json"), "w") as fh:
        json.dump(tiny, fh)
    bench = spec.load_benchmark(root)
    bench["configs"].append({
        "name": "tiny_shake", "source": "test", "reduced": ["model", "batch"],
        "file": "benchmarks/configs/tiny_shake.json", "why": "test"})
    bench["workloads"].append({
        "name": "tiny_shake_train", "config": "tiny_shake",
        "traffic": "tiny_shake_train", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny_shake_train")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)

    cell = spec.resolve_cell("tiny_shake_train", seed=2147483659, seconds=1.0,
                             trace=False, root=root)
    obs = runner.run_cell(cell, jax.devices()[:1], runner.process_start_wall())
    assert obs.correct, obs.checks
    line = runner.result_line(obs)
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    counted = obs.checks["step_counter"]
    assert counted["checkpoint_step"] == counted["steps_counted"]
    assert counted["steps_counted"] == 400 // 8 + 2 + obs.attempted
    assert obs.checks["no_compile_in_window"]["compile_requests"] == 0
    assert obs.checks["learned"]["restored_steps"] == counted["steps_counted"]
    logits = obs.checks["reference_logits"]
    assert logits["ok"] and logits["images"] == 16
    # float32 on the CPU on both sides, after some 60 steps of training
    assert logits["relative_gap"] < 1e-4
    # the second comparison: the same gap here (a CPU rounds no operand),
    # judged by the configuration's own limit for the chip
    exact = obs.checks["reference_logits_float32"]
    assert exact["ok"] and exact["tolerance"] == FLOAT32_LIMIT
    assert exact["relative_gap"] == pytest.approx(logits["relative_gap"], rel=0.5, abs=1e-7)
    # the control: the nearest precision below the configuration's, from
    # the weights the window ended on.  2% lets it through; the float32
    # limit does not, and ``correct`` is the conjunction
    with open(os.path.join(cell.work, "ckpt", "model.msgpack"), "rb") as fh:
        saved = serialization.msgpack_restore(fh.read())
    images = load_dataset("cifar10", os.path.join(cell.work, "data"))[1].images[:16]
    lower = dict(cell.conf_dict(), precision="bf16")
    args = (cell, lower, saved["params"], saved["batch_stats"], images)
    control = window.reference_check(*args)
    assert control["reference_logits"]["ok"]
    lower = control["reference_logits_float32"]
    assert not lower["ok"] and lower["relative_gap"] > 100 * FLOAT32_LIMIT
    assert lower["compared"] == {"value": lower["relative_gap"], "must": "<=",
                                 "limit": FLOAT32_LIMIT}
    obs.checks.update(control)
    assert not obs.correct
    assert runner.result_line(obs)["compared"]["reference_logits_float32"][
        "value"] == lower["relative_gap"]
    # the gauge says which family ran at what size
    from fast_autoaugment_tpu.core import telemetry
    assert telemetry.registry().gauge(
        "faa_model_parameters", model=f"shakeshake26_2x{W}d").value == \
        flops.num_params(SIZES)
