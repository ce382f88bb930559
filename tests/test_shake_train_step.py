"""Shake-Shake through the train step, on the CPU at small widths: what
the benchmark cell ``shake26_2x96d_train`` leans on in the program.

The ``shake`` RNG stream (``train/steps.py::loss_fn`` splits a key for it
and hands it to every family; only this one draws from it) gives fresh
per-image coefficients every step and the same ones for the same seed and
step; the backward pass uses its own draw (``ops/shake.py``'s
``custom_vjp``), which autodiff through the forward mix would not; the
evaluation step draws nothing; the named scopes of ``core/scopes.py``
reach the mix's forward and backward instructions; and a preempted run's
checkpoint restores through ``only_eval``.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fast_autoaugment_tpu.core import scopes
from fast_autoaugment_tpu.core.config import Config
from fast_autoaugment_tpu.models import get_model, shake_resnet
from fast_autoaugment_tpu.train.steps import (
    create_train_state,
    make_eval_step,
    make_train_step,
    make_train_step_body,
)

W, BATCH, BLOCKS = 8, 4, 12


@pytest.fixture(scope="module")
def built():
    model = get_model({"type": f"shakeshake26_2x{W}d", "dataset": "cifar10"}, 10)
    optimizer = optax.sgd(0.05)
    state = create_train_state(model, optimizer, jax.random.PRNGKey(0),
                               jnp.zeros((2, 32, 32, 3), jnp.float32), use_ema=False)
    images = jnp.asarray(np.random.default_rng(1).integers(
        0, 256, (BATCH, 32, 32, 3), dtype=np.uint8))
    labels = jnp.asarray([2, 7, 7, 0])
    return model, optimizer, state, images, labels


def _kw():
    return dict(num_classes=10, cutout_length=0, use_policy=False)


def _eager_step(built, monkeypatch, key, step=0, mix=None):
    """The unjitted step body with the model's noise draw recorded:
    ``(new_state, alpha, beta)``, the noise ``[blocks, batch]`` each.
    `mix` replaces the model's ``shake_shake`` (for the autodiff control)."""
    model, optimizer, state, images, labels = built
    drawn = []
    draw = shake_resnet.sample_shake_shake_noise

    def recording(key, batch, dtype=jnp.float32):
        drawn.append(draw(key, batch, dtype))
        return drawn[-1]

    monkeypatch.setattr(shake_resnet, "sample_shake_shake_noise", recording)
    if mix is not None:
        monkeypatch.setattr(shake_resnet, "shake_shake", mix)
    body = make_train_step_body(model, optimizer, **_kw())
    new_state, _ = body(state.replace(step=jnp.int32(step)), images, labels, None, key)
    alpha, beta = (np.stack([np.asarray(pair[i]).reshape(-1) for pair in drawn])
                   for i in (0, 1))
    return new_state, alpha, beta


def test_noise_is_fresh_every_step_and_repeats_for_the_same_seed_and_step(
        built, monkeypatch):
    key = jax.random.PRNGKey(5)
    _, a0, b0 = _eager_step(built, monkeypatch, key, step=0)
    _, a1, b1 = _eager_step(built, monkeypatch, key, step=1)
    _, again_a, again_b = _eager_step(built, monkeypatch, key, step=0)
    _, other_a, _ = _eager_step(built, monkeypatch, jax.random.PRNGKey(6), step=0)
    assert a0.shape == b0.shape == (BLOCKS, BATCH)
    np.testing.assert_array_equal(a0, again_a)
    np.testing.assert_array_equal(b0, again_b)
    assert not np.array_equal(a0, a1) and not np.array_equal(b0, b1)
    assert not np.array_equal(a0, other_a)
    # the backward draw is its own: nowhere near the forward one
    assert np.max(np.abs(a0 - b0)) > 0.1
    assert abs(np.corrcoef(a0.ravel(), b0.ravel())[0, 1]) < 0.5


def test_the_jitted_step_is_the_body_the_noise_was_read_from(built, monkeypatch):
    model, optimizer, state, images, labels = built
    key = jax.random.PRNGKey(5)
    eager, _, _ = _eager_step(built, monkeypatch, key)
    monkeypatch.undo()
    step = make_train_step(model, optimizer, **_kw())
    copy = jax.tree.map(jnp.copy, state)   # the step donates its state
    jitted, _ = step(copy, images, labels, None, key)
    gaps = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                        eager.params, jitted.params)
    # one SGD step of 0.05 on gradients that agree to float32 rounding
    assert max(jax.tree.leaves(gaps)) < 1e-5
    assert int(jitted.step) == 1


def test_the_backward_pass_does_not_use_the_forward_coefficients(built, monkeypatch):
    """Autodiff through the forward mix (alpha both ways) moves the
    parameters elsewhere; what no mix stands behind moves alike."""
    key = jax.random.PRNGKey(5)
    decoupled, alpha, _ = _eager_step(built, monkeypatch, key)
    monkeypatch.undo()
    coupled, same_alpha, _ = _eager_step(
        built, monkeypatch, key,
        mix=lambda x1, x2, a, b: a * x1 + (1.0 - a) * x2)
    np.testing.assert_array_equal(alpha, same_alpha)
    gaps = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                        decoupled.params, coupled.params)
    assert max(jax.tree.leaves(gaps["s0_0_branch1"])) > 1e-4
    assert max(jax.tree.leaves(gaps["fc_out"])) < 1e-7
    # the forward value is alpha's in both: the same new running statistics
    stats = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         decoupled.batch_stats, coupled.batch_stats)
    assert max(jax.tree.leaves(stats)) < 1e-6


def test_evaluation_is_deterministic_and_draws_nothing(built):
    model, _, state, images, labels = built
    eval_step = make_eval_step(model, num_classes=10)
    mask = jnp.ones((BATCH,), jnp.float32)
    first = eval_step(state.params, state.batch_stats, images, labels, mask)
    second = eval_step(state.params, state.batch_stats, images, labels, mask)
    for name in first:
        np.testing.assert_array_equal(np.asarray(first[name]),
                                      np.asarray(second[name]))
    text = eval_step.lower(state.params, state.batch_stats, images, labels,
                           mask).as_text()
    assert "threefry" not in text and "rng" not in text.lower()


def test_the_scopes_reach_the_mix_forward_and_backward(built):
    """The lowered step's location names carry ``faa_model/.../faa_shake_mix``
    under ``jvp(`` and under ``transpose(``: the ``custom_vjp`` rule's
    instructions are filed with the backward pass by the same two functions
    that file a transposed instruction."""
    model, optimizer, state, images, labels = built
    step = make_train_step(model, optimizer, **_kw())
    text = step._jitted.lower(state, images, labels, None,
                              jax.random.PRNGKey(1)).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*faa_shake_[^"]*)"', text))
    chain = {scopes.SHAKE_MIX: (scopes.MODEL, scopes.SHAKE_MIX),
             scopes.SHAKE_SHORTCUT: (scopes.MODEL, scopes.SHAKE_SHORTCUT)}
    for scope, expected in chain.items():
        mine = {n for n in names if scope in n}
        assert mine and all(scopes.scope_of(n) == expected for n in mine)
        forward = {n for n in mine if not scopes.is_backward(n)}
        backward = {n for n in mine if scopes.is_backward(n)}
        assert all("/jvp(faa_model)/" in n for n in forward) and forward
        assert all("/transpose(jvp(faa_model))/" in n for n in backward) and backward
    # every block's mix, both ways; the rule's own two products
    for stage in range(3):
        for i in range(4):
            mix = f"ShakeResNet/s{stage}_{i}_mix/faa_shake_mix/"
            assert any(n.endswith(mix + "custom_vjp_call") for n in names)
            assert any(n.endswith(mix + "mul") and scopes.is_backward(n)
                       for n in names)
            assert any(n.endswith(mix + "sub") and scopes.is_backward(n)
                       for n in names)
    # three blocks change width or stride: three shortcuts
    assert len({n.split("/faa_shake_shortcut")[0] for n in names
                if scopes.SHAKE_SHORTCUT in n and not scopes.is_backward(n)}) == 3


#: ``op_name`` strings of the step compiled for a v5e at published widths
#: (the cell's ``scope_map``), as XLA:TPU writes them
@pytest.mark.parametrize("op_name, chain, backward", [
    ("jit(multi_fn)/transpose(jvp(faa_model))/ShakeResNet/s1_2_mix/faa_shake_mix/mul",
     ("faa_model", "faa_shake_mix"), True),
    ("jit(multi_fn)/transpose(jvp(faa_model))/ShakeResNet/s0_0_mix/faa_shake_mix/sub",
     ("faa_model", "faa_shake_mix"), True),
    ("jit(multi_fn)/jvp(faa_model)/ShakeResNet/s2_3_mix/faa_shake_mix/jit(_uniform)/"
     "shift_right_logical", ("faa_model", "faa_shake_mix"), False),
    ("jit(multi_fn)/jvp(faa_model)/ShakeResNet/s0_1_mix/faa_shake_mix/"
     "jit(_threefry_split)/_make_train_step_body.<locals>.step_fn/xor",
     ("faa_model", "faa_shake_mix"), False),
    ("jit(multi_fn)/jvp(faa_model)/ShakeResNet/s2_0_shortcut/faa_shake_shortcut/"
     "conv2/conv_general_dilated", ("faa_model", "faa_shake_shortcut"), False),
    ("jit(multi_fn)/transpose(jvp(faa_model))/ShakeResNet/s1_0_shortcut/"
     "faa_shake_shortcut/bn/BatchNorm_0/reduce_sum",
     ("faa_model", "faa_shake_shortcut"), True),
    # two instructions XLA merged: the first that carries a scope speaks
    ("jit(multi_fn)/jit(_threefry_split)/threefry2x32;"
     "jit(multi_fn)/transpose(jvp(faa_model))/ShakeResNet/s0_3_mix/faa_shake_mix/mul",
     ("faa_model", "faa_shake_mix"), True),
    ("jit(multi_fn)/jvp(faa_model)/ShakeResNet/s0_0_branch1/conv1/conv_general_dilated",
     ("faa_model",), False)])
def test_scope_functions_read_the_compiled_steps_op_names(op_name, chain, backward):
    assert scopes.scope_of(op_name) == chain
    assert scopes.is_backward(op_name) is backward


def test_the_two_scopes_are_in_the_one_table():
    assert scopes.SHAKE_MIX == "faa_shake_mix"
    assert scopes.SHAKE_SHORTCUT == "faa_shake_shortcut"
    assert {"SHAKE_MIX", "SHAKE_SHORTCUT"} <= set(scopes.__all__)
    assert all(name.startswith(scopes.PREFIX)
               for name in (scopes.SHAKE_MIX, scopes.SHAKE_SHORTCUT))


def test_a_preempted_runs_checkpoint_restores_and_only_eval_reads_it(tmp_path):
    """What the cell's check does after its window: the trainer is
    stopped at a dispatch boundary, writes its checkpoint, and a second
    call with ``only_eval`` restores it and evaluates."""
    from fast_autoaugment_tpu.core import resilience, telemetry
    from fast_autoaugment_tpu.core.checkpoint import read_metadata
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    conf = Config({
        "model": {"type": f"shakeshake26_2x{W}d"}, "dataset": "synthetic",
        "aug": "default", "cutout": 0, "batch": 8, "epoch": 3, "lr": 0.01,
        "lr_schedule": {"type": "cosine"},
        "optimizer": {"type": "sgd", "decay": 1e-3, "nesterov": True}})
    save = str(tmp_path / "shake.msgpack")
    beats = []

    def heartbeat():
        beats.append(1)
        if len(beats) == 3:
            resilience.request_preemption()

    resilience.clear_preemption()
    try:
        with pytest.raises(resilience.PreemptedError):
            train_and_eval(conf, str(tmp_path), save_path=save, seed=3,
                           test_ratio=0.4, evaluation_interval=1000,
                           heartbeat=heartbeat)
    finally:
        resilience.clear_preemption()
    meta = read_metadata(save)
    assert meta["preempted"] is True and meta["step"] == 3
    evaluated = train_and_eval(conf, str(tmp_path), save_path=save, seed=3,
                               test_ratio=0.4, only_eval=True)
    assert evaluated["steps"] == 3
    assert np.isfinite(evaluated["loss_test"]) and 0.0 <= evaluated["top1_test"] <= 1.0
    # and the trainer said what it built
    params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(jax.eval_shape(
        lambda: get_model({"type": f"shakeshake26_2x{W}d", "dataset": "synthetic"},
                          10).init(jax.random.PRNGKey(0),
                                   jnp.zeros((2, 32, 32, 3)), train=False))["params"]))
    assert telemetry.registry().gauge(
        "faa_model_parameters", model=f"shakeshake26_2x{W}d").value == params


def test_the_trainer_journals_the_model_it_built(tmp_path, monkeypatch):
    from fast_autoaugment_tpu.core import telemetry
    from fast_autoaugment_tpu.train import trainer

    events = []
    monkeypatch.setattr(telemetry, "emit",
                        lambda etype, label=None, **f: events.append((etype, label, f)))
    conf = Config({
        "model": {"type": "wresnet10_1"}, "dataset": "synthetic", "aug": "default",
        "cutout": 0, "batch": 8, "epoch": 1, "lr": 0.05,
        "lr_schedule": {"type": "cosine"},
        "optimizer": {"type": "sgd", "decay": 1e-4, "nesterov": True}})
    trainer.train_and_eval(conf, str(tmp_path), test_ratio=0.4, seed=0)
    built = [e for e in events if e[0] == "model"]
    assert len(built) == 1 and "model" in telemetry.EVENT_TYPES
    _, label, fields = built[0]
    assert label == "wresnet10_1"
    assert fields["batch_per_device"] == 8 and fields["steps_per_epoch"] >= 1
    assert fields["parameters"] == telemetry.registry().gauge(
        "faa_model_parameters", model="wresnet10_1").value > 0
