"""The yardstick's own arithmetic: the plain float32 reference against
the program's WideResNet, the operations function against a hand count,
the peaks table, the logits comparison."""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import device, spec, window
from benchmarks.harness.spec import load_module
from fast_autoaugment_tpu.models import get_model
from fast_autoaugment_tpu.ops.preprocess import cifar_eval_batch

reference = load_module("references", "wideresnet")
flops = load_module("flops", "wideresnet")


@pytest.mark.parametrize("depth,widen", [(10, 1), (16, 2)])
def test_reference_agrees_with_the_programs_wideresnet(depth, widen):
    model = get_model({"type": f"wresnet{depth}_{widen}", "dataset": "cifar10"}, 10)
    variables = model.init(jax.random.PRNGKey(depth), jnp.zeros((2, 32, 32, 3)),
                           train=False)
    # running statistics away from their initial 0 and 1, as after training
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 1000))
    stats = jax.tree.map(
        lambda x: x + 0.5 * jax.random.uniform(next(keys), x.shape),
        variables["batch_stats"])
    images = np.random.default_rng(0).integers(0, 256, (6, 32, 32, 3), dtype=np.uint8)
    system = model.apply({"params": variables["params"], "batch_stats": stats},
                         cifar_eval_batch(jnp.asarray(images)), train=False)
    plain = reference.forward(jax.device_get(variables["params"]),
                              jax.device_get(stats), images,
                              {"depth": depth, "widen_factor": widen})
    # float32 on the CPU on both sides: rounding order is all that differs
    verdict = window.logits_agreement(np.asarray(system), plain, 1e-5)
    assert verdict["ok"], verdict
    # and the comparison can fail: a wrong BatchNorm epsilon is caught
    reference._BN_EPS, kept = 1e-2, reference._BN_EPS
    try:
        wrong = reference.forward(jax.device_get(variables["params"]),
                                  jax.device_get(stats), images,
                                  {"depth": depth, "widen_factor": widen})
    finally:
        reference._BN_EPS = kept
    assert not window.logits_agreement(np.asarray(system), wrong, 1e-5)["ok"]


def _hand_count(n, k):
    """Multiply-accumulates of WRN-(6n+4)-k on a 32-px image, stage by
    stage: the first block of a stage takes the previous width at the
    previous size into conv1, strides in conv2, and has a 1x1 shortcut."""
    stem = 9 * 3 * 16 * 32 * 32
    total, c_in, size = stem, 16, 32
    for width, stride in ((16 * k, 1), (32 * k, 2), (64 * k, 2)):
        out = size // stride
        first = (9 * c_in * width * size * size + 9 * width * width * out * out
                 + c_in * width * out * out)
        rest = (n - 1) * 2 * 9 * width * width * out * out
        total += first + rest
        c_in, size = width, out
    return total + 64 * k * 10


@pytest.mark.parametrize("depth,widen,gmac,mparams", [
    (40, 2, 0.3559, 2.246), (28, 10, 5.951, 36.49)])
def test_operations_match_a_hand_count(depth, widen, gmac, mparams):
    model = {"depth": depth, "widen_factor": widen, "num_classes": 10, "image": 32}
    macs = flops.forward_macs_per_image(model)
    assert macs == _hand_count((depth - 4) // 6, widen)
    assert macs / 1e9 == pytest.approx(gmac, rel=1e-3)
    assert flops.forward_flops_per_image(model) == 2 * macs
    assert flops.train_flops_per_image(model) == 6 * macs
    assert flops.num_params(model) / 1e6 == pytest.approx(mparams, rel=1e-3)
    assert flops.gradient_bytes(model) == 4 * flops.num_params(model)


def test_parameter_count_matches_the_programs_model():
    model = get_model({"type": "wresnet16_2", "dataset": "cifar10"}, 10)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 32, 32, 3)), train=False))
    counted = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes["params"]))
    assert flops.num_params({"depth": 16, "widen_factor": 2, "num_classes": 10,
                             "image": 32}) == counted


def test_depth_must_be_6n_plus_4():
    with pytest.raises(ValueError, match="6n\\+4"):
        flops.forward_macs_per_image({"depth": 30, "widen_factor": 2,
                                      "num_classes": 10, "image": 32})


def test_peaks_are_keyed_by_the_exact_device_kind():
    v5e = device.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    for unknown in ("TPU v5", "cpu", "TPU v5 lite "):
        with pytest.raises(KeyError, match="no peaks on record"):
            device.peaks_for(unknown)


def test_logits_comparison():
    ref = np.array([[4.0, -2.0], [1.0, 0.5]])
    assert window.logits_agreement(ref + 0.01, ref, 0.01)["relative_gap"] == pytest.approx(0.0025)
    assert window.logits_agreement(ref + 0.01, ref, 0.01)["ok"]
    assert not window.logits_agreement(ref + 0.1, ref, 0.01)["ok"]
    assert not window.logits_agreement(ref[:1], ref, 0.01)["ok"]
    assert not window.logits_agreement(ref * np.nan, ref, 0.01)["ok"]


@pytest.mark.parametrize("config, tiny_type, tiny_sizes", [
    ("wrn40x2_cifar10", "wresnet10_2", {"depth": 10, "widen_factor": 2}),
    ("wrn28x10_cifar10", "wresnet10_3", {"depth": 10, "widen_factor": 3}),
    ("shake26_2x96d_cifar10", "shakeshake26_2x8d", {"depth": 26, "w_base": 8})])
def test_a_cached_configuration_states_both_limits_and_each_catches_its_fault(
        config, tiny_type, tiny_sizes, monkeypatch):
    """``reference_check`` under the file's own two limits, its family's
    model at a tiny size on the CPU (float32 on both sides, so a sound
    system reads rounding order alone): a reference whose classifier kernel
    is half as large again fails both comparisons; the nearest precision below the
    configuration's (``precision: bf16`` on the program's own path, and the
    system's logits alone rounded to bfloat16) fails the float32 one, and
    the rounded logits fail it alone.  The two readings that
    justify the second limit on the chip are in the file."""
    entry = next(c for c in spec.load_benchmark()["configs"] if c["name"] == config)
    held = spec.load_json(os.path.join(spec.ROOT, entry["file"]))
    assert held["logit_tolerance"] == 0.02 and held["logit_tolerance_float32"] == 1e-5
    assert "reference_logits_float32" in held["logit_gap_measured"]
    assert "`precision: bf16`" in held["logit_gap_measured"]
    assert "reference_logits_float32" in held["assumed"]["precision"]

    conf = dict(held["conf"], model={"type": tiny_type}, batch=8)
    sizes = dict(held["model"], **tiny_sizes)
    family = load_module("references", held["reference"])
    cell = types.SimpleNamespace(
        config=dict(held, model=sizes),
        module=lambda kind, name: {"references": family}[kind])
    model = get_model({"type": tiny_type, "dataset": "cifar10"}, 10)
    variables = model.init(
        {"params": jax.random.PRNGKey(1), "shake": jax.random.PRNGKey(2)},
        jnp.zeros((2, 32, 32, 3)), train=False)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 1000))
    stats = jax.tree.map(
        lambda x: x + 0.5 * jax.random.uniform(next(keys), x.shape),
        variables["batch_stats"])
    images = np.random.default_rng(3).integers(0, 256, (8, 32, 32, 3), dtype=np.uint8)
    args = (variables["params"], stats, images)

    sound = window.reference_check(cell, conf, *args)
    assert list(sound) == ["reference_logits", "reference_logits_float32"]
    assert sound["reference_logits"]["ok"] and sound["reference_logits_float32"]["ok"]
    assert [c["tolerance"] for c in sound.values()] == [0.02, 1e-5]
    assert sound["reference_logits_float32"]["relative_gap"] < 1e-6

    # the program's own lower path: bfloat16 activations through every layer
    lower = window.reference_check(cell, dict(conf, precision="bf16"), *args)
    assert not lower["reference_logits_float32"]["ok"]
    assert 30 * 1e-5 < lower["reference_logits_float32"]["relative_gap"] < 0.05
    # and the least of it, the system's logits alone rounded to bfloat16 (a
    # part in 256 at most): the deployed limit lets it through, the other not
    agreement = window.logits_agreement
    with monkeypatch.context() as patch:
        patch.setattr(window, "logits_agreement", lambda system, plain, limit: agreement(
            np.asarray(jnp.asarray(system, jnp.bfloat16), np.float32), plain, limit))
        rounded = window.reference_check(cell, conf, *args)
    assert rounded["reference_logits"]["ok"]
    assert not rounded["reference_logits_float32"]["ok"]

    forward = family.forward

    def one_weight_off(params, batch_stats, images_u8, model_sizes):
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        (path,) = (p for p, leaf in flat if np.ndim(leaf) == 2)   # the classifier's
        wrong = jax.tree_util.tree_map_with_path(
            lambda p, leaf: leaf * 1.5 if p == path else leaf, params)
        return forward(wrong, batch_stats, images_u8, model_sizes)

    monkeypatch.setattr(family, "forward", one_weight_off)
    wrong = window.reference_check(cell, conf, *args)
    assert not wrong["reference_logits"]["ok"]
    assert not wrong["reference_logits_float32"]["ok"]
    # a configuration without the second limit makes the first comparison alone
    del cell.config["logit_tolerance_float32"]
    assert list(window.reference_check(cell, conf, *args)) == ["reference_logits"]


def test_device_gate_refuses_the_cpu():
    with pytest.raises(device.NoAcceleratorError, match="platform 'cpu'"):
        device.require_devices(1)
    assert len(device.require_devices(2, platform="cpu")) == 2
    with pytest.raises(device.NoAcceleratorError, match="needs 4096"):
        device.require_devices(4096, platform="cpu")
