"""Search engine tests: TPE convergence on a toy problem, TTA-step
reduction semantics, and the end-to-end smoke search (the analog of the
reference's --smoke-test, search.py:153)."""

import json
import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fast_autoaugment_tpu.search.tpe import TPE, choice, uniform


def test_tpe_beats_random_on_quadratic():
    space = [uniform("x", 0, 1), uniform("y", 0, 1), choice("c", 4)]

    def objective(s):
        return -((s["x"] - 0.7) ** 2) - (s["y"] - 0.2) ** 2 + (0.5 if s["c"] == 2 else 0.0)

    tpe = TPE(space, seed=0)
    for _ in range(120):
        s = tpe.suggest()
        tpe.tell(s, objective(s))

    rng = np.random.default_rng(0)
    random_best = max(
        objective({"x": rng.uniform(), "y": rng.uniform(), "c": int(rng.integers(4))})
        for _ in range(120)
    )
    best_x, best_r = tpe.best
    assert best_r >= random_best - 0.02
    assert best_x["c"] == 2
    assert abs(best_x["x"] - 0.7) < 0.25


def test_tpe_deterministic():
    space = [uniform("x"), choice("c", 3)]
    a, b = TPE(space, seed=5), TPE(space, seed=5)
    for _ in range(30):
        sa, sb = a.suggest(), b.suggest()
        assert sa == sb
        a.tell(sa, sa["x"])
        b.tell(sb, sb["x"])


def test_tta_step_reductions():
    """Identity-policy TTA on a fixed linear model: minus_loss must be the
    batch-global min; correct must be the per-sample any() across draws."""
    from flax import linen as nn

    from fast_autoaugment_tpu.parallel.mesh import make_mesh, shard_transform
    from fast_autoaugment_tpu.search.tta import eval_tta, make_tta_step

    class Probe(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            # logits depend deterministically on mean pixel: samples with
            # high mean get class 1
            m = x.mean(axis=(1, 2, 3), keepdims=False)
            return jnp.stack([jnp.zeros_like(m), m * 10.0], axis=-1)

    model = Probe()
    tta = make_tta_step(model, num_policy=3, cutout_length=0,
                        augment_fn=lambda im, pol, k: im / 255.0 - 0.5)
    mesh = make_mesh(jax.devices()[:1])

    images = np.zeros((4, 8, 8, 3), np.uint8)
    images[2:] = 255  # samples 2,3 -> mean 0.5 -> logit 5 -> class 1
    labels = np.array([1, 1, 1, 1], np.int32)
    to_device = shard_transform(mesh, ("x", "y", "m"))
    out = eval_tta(tta, {}, {},
                   [to_device((images, labels, np.ones(4, np.float32)))],
                   jnp.zeros((1, 1, 3)), jax.random.PRNGKey(0))
    # samples 0,1 predict class 0 (wrong), 2,3 predict 1 (right)
    assert out["top1_valid"] == pytest.approx(0.5)
    # min nll over all = nll of a correct confident sample
    assert out["minus_loss"] < 0.0
    assert out["cnt"] == 4


@pytest.mark.slow
def test_smoke_search_on_imagenet_family(tmp_path):
    """Regression: phase 2 must decode lazy variable-size images through
    the boxed crop path and use the ImageNet TTA stack (was: np.stack
    shape crash + CIFAR stack silently applied)."""
    from tests.test_imagenet_pipeline import _write_fake_imagenet

    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.search.driver import search_policies

    _write_fake_imagenet(str(tmp_path))
    conf = Config({
        "model": {"type": "wresnet10_1"},
        "dataset": "imagenet",
        "aug": "default",
        "cutout": 0,
        "batch": 1,
        "epoch": 1,
        "lr": 0.01,
        "lr_schedule": {"type": "cosine"},
        "optimizer": {"type": "sgd", "decay": 1e-4, "clip": 5.0,
                      "momentum": 0.9, "nesterov": True},
    })
    result = search_policies(
        conf, dataroot=str(tmp_path), save_dir=str(tmp_path / "s"),
        cv_num=1, cv_ratio=0.4, num_policy=2, num_op=2,
        num_search=2, num_top=1, smoke_test=False,
    )
    assert 1 <= len(result["final_policy_set"]) <= 2


@pytest.mark.slow
def test_smoke_search_end_to_end():
    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.search.driver import search_policies

    conf = Config({
        "model": {"type": "wresnet10_1"},
        "dataset": "synthetic",
        "aug": "default",
        "cutout": 8,
        "batch": 8,
        "epoch": 1,
        "lr": 0.05,
        "lr_schedule": {"type": "cosine"},
        "optimizer": {"type": "sgd", "decay": 1e-4, "clip": 5.0,
                      "momentum": 0.9, "nesterov": True},
    })
    with tempfile.TemporaryDirectory() as tmp:
        result = search_policies(
            conf, dataroot=tmp, save_dir=os.path.join(tmp, "search"),
            cv_num=2, cv_ratio=0.4, num_policy=2, num_op=2,
            num_search=4, num_top=2, smoke_test=True,
        )
        pols = result["final_policy_set"]
        assert 1 <= len(pols) <= 2 * 2 * 2
        for sub in pols:
            assert len(sub) == 2
            for op, prob, level in sub:
                assert 0 <= prob <= 1 and 0 <= level <= 1
        # artifacts written
        assert os.path.exists(os.path.join(tmp, "search", "final_policy.json"))
        trials = json.load(open(os.path.join(tmp, "search", "search_trials.json")))
        assert set(trials) == {"0", "1"}
        assert result["tpu_secs_phase2"] > 0


@pytest.mark.slow
def test_audit_drops_destructive_keeps_benign(tmp_path):
    """Round-2 regression gate (docs/search_postmortem_r2.md): the
    sub-policy audit must drop policies that standalone-destroy fold
    accuracy (Invert/Solarize-to-0 on a bright-glyph task) and keep
    label-preserving ones (translate/near-identity brightness).  This is
    the exact mechanism whose absence let the round-2 e2e search ship a
    policy set that trained to random accuracy.

    Horizon note (PR-6 root-cause, docs/PARITY.md "audit-gate oracle"):
    at 20 epochs the seeded oracle converges to 0.344 in THIS
    container's jax build (bit-identical across PR 3..6 — the training
    stream never changed; the original authoring environment's kernels
    escaped the early plateau faster).  The cosine horizon is the
    lever: 35 epochs reaches 0.979 (vs 0.267-0.354 for 2x LR at any
    horizon).  The longer train pushes the test past the tier-1 wall
    budget, so it is slow-marked per the ROADMAP standing constraint —
    the audit-gate wiring stays covered in tier-1 by the cheaper
    agreement tests that defer semantics to this one."""
    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.search.driver import (
        _FoldEval,
        _fold_ckpt_path,
        audit_sub_policies,
    )
    from fast_autoaugment_tpu.train.trainer import train_and_eval

    conf = Config({
        "model": {"type": "wresnet10_1"},
        "dataset": "synthetic_shapes",
        "aug": "default",
        "cutout": 0,
        "batch": 2,  # global 16 on the 8-device mesh
        "epoch": 35,
        # conf lr is scaled by mesh.size (reference lr x world_size,
        # train.py:117): 0.00625 x 8 = effective 0.05
        "lr": 0.00625,
        "lr_schedule": {"type": "cosine", "warmup": {"multiplier": 1, "epoch": 2}},
        "optimizer": {"type": "sgd", "decay": 2e-4, "momentum": 0.9,
                      "nesterov": True},
    })
    from fast_autoaugment_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    path = _fold_ckpt_path(str(tmp_path), conf, 0, 0.4)
    train_and_eval(conf.replace(aug="default"), str(tmp_path), test_ratio=0.4,
                   cv_fold=0, save_path=path, metric="last", seed=0)

    ev = _FoldEval(conf, str(tmp_path), mesh,
                   num_policy=5, num_op=2, cv_ratio=0.4, seed=0)
    base = ev.baseline(0, path)
    assert base > 0.5, f"fold oracle too weak to audit against ({base:.3f})"

    benign = [
        [("TranslateX", 0.5, 0.5), ("TranslateY", 0.5, 0.5)],
        [("Brightness", 0.5, 0.55), ("Cutout", 0.3, 0.3)],
        # 5 candidates total: forces the CHUNKED batched audit step
        # (make_audit_step), not the small-n fallback
        [("ShearX", 0.3, 0.5), ("Sharpness", 0.3, 0.5)],
    ]
    destructive = [
        # net polarity flips (NOT mutually-cancelling pairs: Invert+
        # Solarize(0) would compose back to identity)
        [("Invert", 1.0, 1.0), ("Cutout", 0.1, 0.1)],
        # Solarize level 0 -> threshold 0 -> every pixel inverted;
        # Brightness level 0.55 ~ factor 1.0 (identity)
        [("Solarize", 1.0, 0.0), ("Brightness", 1.0, 0.55)],
    ]
    kept, audit = audit_sub_policies(
        ev, benign + destructive, [path],
        fold_baselines={0: base}, candidate_folds=[0], audit_floor=0.7,
    )
    scores = {i: s["score"] for i, s in enumerate(audit["scores"])}
    assert all(b in kept for b in benign), scores
    assert not any(d in kept for d in destructive), scores


def test_tpe_beats_random_at_small_budget():
    """The 30-D mixed space benchmark at the 60-trial budget the e2e
    validation actually runs (VERDICT round 2, weak 4): with clean
    rewards TPE must clearly beat random, and under heavy observation
    noise it must at worst match it.  Metric is the TRUE reward of the
    best-by-observed incumbent (what top-N selection consumes).  Fully
    deterministic given the seeds; the full budget x noise grid is in
    docs/SEARCH_QUALITY.md."""
    import planted_policy

    clean = planted_policy.run_cell(trials=60, noise=0.02, runs=10)
    assert clean["wins"] >= 5, clean
    assert clean["gain"] > 0.01, clean

    # the regime the fold-quality gate exists to avoid: reward noise at
    # the weak-oracle spread — TPE may lose its edge but not its floor
    noisy = planted_policy.run_cell(trials=60, noise=0.1, runs=10)
    assert noisy["gain"] > -0.02, noisy


def test_cli_defaults_are_the_validated_guards():
    """Round-3 regression (VERDICT r3, weak 1): the CLI's DEFAULT guard
    settings must be the validated recipe, not the settings that
    reproduced the round-2 destructive selection (audit floor 0.7, gate
    off — committed evidence search_e2e_r3/search_result_floor0.70.json)."""
    from fast_autoaugment_tpu.launch.search_cli import build_parser

    args = build_parser().parse_args(["-c", "conf.yaml"])
    assert args.audit_floor == 0.95
    assert args.fold_quality_floor == "auto"
    assert args.num_search == 200 and args.num_fold == 5  # reference scale


def test_resolve_quality_floor():
    from fast_autoaugment_tpu.search.driver import resolve_quality_floor

    # auto = chance-relative: close >=35% of the chance-to-perfect gap
    assert resolve_quality_floor("auto", 10) == pytest.approx(0.415)
    assert resolve_quality_floor("auto", 2) == pytest.approx(0.675)
    assert resolve_quality_floor("auto", 120) == pytest.approx(
        1 / 120 + 0.35 * (1 - 1 / 120))
    assert resolve_quality_floor("off", 10) is None
    assert resolve_quality_floor(None, 10) is None
    assert resolve_quality_floor(0.45, 10) == 0.45
    assert resolve_quality_floor("0.6", 10) == 0.6
    assert resolve_quality_floor(-1.0, 10) is None


@pytest.mark.slow
def test_phase2_crash_loses_at_most_inflight_trial(tmp_path, monkeypatch):
    """Per-trial persistence (VERDICT r3, weak 4): kill the search mid-
    fold and the trial log must already hold every COMPLETED trial; the
    resumed run finishes the budget without re-evaluating them."""
    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.search import driver
    from fast_autoaugment_tpu.search.driver import search_policies

    conf = Config({
        "model": {"type": "wresnet10_1"},
        "dataset": "synthetic",
        "aug": "default",
        "cutout": 8,
        "batch": 8,
        "epoch": 1,
        "lr": 0.05,
        "lr_schedule": {"type": "cosine"},
        "optimizer": {"type": "sgd", "decay": 1e-4, "clip": 5.0,
                      "momentum": 0.9, "nesterov": True},
    })
    save = str(tmp_path / "search")
    kwargs = dict(
        dataroot=str(tmp_path), save_dir=save, cv_num=1, cv_ratio=0.4,
        num_policy=2, num_op=2, num_search=6, num_top=2,
    )

    orig = driver._FoldEval.evaluate
    calls = {"n": 0}

    def crashing(self, *a, **kw):
        calls["n"] += 1
        if calls["n"] == 4:  # simulated kill mid-fold, 3 trials done
            raise KeyboardInterrupt("simulated kill")
        return orig(self, *a, **kw)

    monkeypatch.setattr(driver._FoldEval, "evaluate", crashing)
    with pytest.raises(KeyboardInterrupt):
        search_policies(conf, **kwargs)
    trials = json.load(open(os.path.join(save, "search_trials.json")))
    assert len(trials["0"]) == 3  # every completed trial persisted

    monkeypatch.setattr(driver._FoldEval, "evaluate", orig)
    result = search_policies(conf, **kwargs)  # resume=True default
    trials = json.load(open(os.path.join(save, "search_trials.json")))
    assert len(trials["0"]) == 6
    assert result["final_policy_set"]
    # one executable served every TTA evaluation (no recompiles)
    assert result["tta_executables"] in (None, 1)


def test_audit_batched_matches_sequential():
    """The chunked audit step (make_audit_step, sub-policy axis vmapped)
    must agree with per-sub-policy TTA evaluation up to augmentation
    sampling noise — same model, same batches, same reduction."""
    from flax import linen as nn

    from fast_autoaugment_tpu.parallel.mesh import make_mesh, shard_transform
    from fast_autoaugment_tpu.policies.archive import policy_to_tensor
    from fast_autoaugment_tpu.search.tta import (
        eval_tta,
        make_audit_step,
        make_tta_step,
    )

    class Probe(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            # class 1 iff mean pixel (post-normalize) above threshold:
            # sensitive to Brightness/Invert but ignores geometry
            m = x.mean(axis=(1, 2, 3))
            return jnp.stack([jnp.zeros_like(m), m * 8.0], axis=-1)

    model = Probe()
    tta = make_tta_step(model, num_policy=4, cutout_length=0)
    audit = make_audit_step(model, num_policy=4, cutout_length=0)
    mesh = make_mesh(jax.devices()[:1])
    to_device = shard_transform(mesh, ("x", "y", "m"))

    rng = np.random.default_rng(0)
    images = rng.integers(100, 180, (32, 8, 8, 3)).astype(np.uint8)
    labels = (images.mean(axis=(1, 2, 3)) > 140).astype(np.int32)
    batch = to_device((images, labels, np.ones(32, np.float32)))

    subs = [
        [("Brightness", 1.0, 0.9), ("Cutout", 0.0, 0.0)],
        [("Invert", 1.0, 1.0), ("Cutout", 0.0, 0.0)],
        [("TranslateX", 0.5, 0.5), ("Cutout", 0.0, 0.0)],
    ]
    subs_t = jnp.asarray(policy_to_tensor(subs))
    out = audit({}, {}, batch["x"], batch["y"], batch["m"], subs_t,
                jax.random.PRNGKey(5))
    batched = np.asarray(out["correct_mean_sum"]) / float(out["cnt"])

    for i, s in enumerate(subs):
        seq = eval_tta(tta, {}, {}, [batch],
                       jnp.asarray(policy_to_tensor([s])),
                       jax.random.PRNGKey(50 + i))["top1_mean"]
        # different draws -> sampling noise only (destructive-vs-benign
        # SEMANTICS are covered by test_audit_drops_destructive_keeps_benign
        # with a real trained model)
        assert abs(float(seq) - batched[i]) < 0.15, (i, float(seq), batched[i])


def test_draw_random_policy_set():
    """The phase-3 control arm (VERDICT r4 next-4): equal-size uniform
    draws from the same (op, prob, level) space, deduplicated and
    deterministic under a fixed seed."""
    from fast_autoaugment_tpu.ops.augment import SEARCH_OP_NAMES
    from fast_autoaugment_tpu.search.driver import draw_random_policy_set

    s1 = draw_random_policy_set(23, 5, 2, seed=42)
    s2 = draw_random_policy_set(23, 5, 2, seed=42)
    assert s1 == s2
    assert len(s1) == 23
    assert len({json.dumps(sub) for sub in s1}) == 23  # deduplicated
    for sub in s1:
        assert len(sub) == 2
        for op, prob, level in sub:
            assert op in SEARCH_OP_NAMES
            assert 0.0 <= prob <= 1.0 and 0.0 <= level <= 1.0
    assert draw_random_policy_set(7, 5, 2, seed=1) != \
        draw_random_policy_set(7, 5, 2, seed=2)


def test_quality_gate_retry_seed_reaches_hook():
    """ADVICE r4 (medium): the retry seed must reach a train_fold_fn
    override explicitly — a thin three-arg wrapper around
    train_and_eval used to retrain with the identical seed, silently
    voiding the quality gate's fresh-seed retry."""
    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.search.driver import _call_train_fold_fn

    conf = Config({"model": {"type": "wresnet10_1"}, "dataset": "synthetic",
                   "aug": "default", "batch": 2, "epoch": 1, "lr": 0.1,
                   "lr_schedule": {"type": "cosine"},
                   "optimizer": {"type": "sgd"}})
    calls = {}

    def legacy(conf, fold, path):
        calls["legacy"] = conf["seed"]

    def modern(conf, fold, path, *, seed):
        calls["modern"] = seed
        calls["modern_conf"] = conf["seed"]

    _call_train_fold_fn(legacy, conf, 0, "p", 123)
    _call_train_fold_fn(modern, conf, 0, "p", 456)
    assert calls == {"legacy": 123, "modern": 456, "modern_conf": 456}


def test_search_random_control_arm(tmp_path):
    """random_control=True draws, persists and resumes the control
    policy set, and the artifact records backend provenance
    (VERDICT r4 weak 5 + next-4)."""
    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.search.driver import search_policies

    conf = Config({
        "model": {"type": "wresnet10_1"},
        "dataset": "synthetic",
        "aug": "default",
        "cutout": 8,
        "batch": 8,
        "epoch": 1,
        "lr": 0.05,
        "lr_schedule": {"type": "cosine"},
        "optimizer": {"type": "sgd", "decay": 1e-4, "clip": 5.0,
                      "momentum": 0.9, "nesterov": True},
    })
    save = str(tmp_path / "search")
    kwargs = dict(
        cv_num=1, cv_ratio=0.4, num_policy=2, num_op=2,
        num_search=2, num_top=1, random_control=True,
    )
    result = search_policies(conf, dataroot=str(tmp_path), save_dir=save,
                             **kwargs)
    # ledger provenance: a CPU run must say so next to its device-secs
    assert result["platform"] == "cpu"
    assert result["device_count"] >= 1
    assert result["device_secs_phase2"] == result["tpu_secs_phase2"]
    rand = result["random_policy_set"]
    assert len(rand) == result["num_sub_policies_selected"]
    assert os.path.exists(os.path.join(save, "random_policy.json"))
    assert os.path.exists(os.path.join(save, "random_final_policy.json"))
    # resume must reuse the persisted draw, not redraw
    result2 = search_policies(conf, dataroot=str(tmp_path), save_dir=save,
                              **kwargs)
    assert result2["random_policy_set"] == rand


def test_fold_quality_floor_cli_validation(capsys):
    """ADVICE r4: malformed --fold-quality-floor fails at parse time as
    a CLI usage error, not a float() traceback inside the search."""
    from fast_autoaugment_tpu.launch.search_cli import build_parser

    p = build_parser()
    with pytest.raises(SystemExit):
        p.parse_args(["-c", "x.yaml", "--fold-quality-floor", "0,45"])
    assert "expected 'auto', 'off' or a float" in capsys.readouterr().err
    assert p.parse_args(
        ["-c", "x.yaml", "--fold-quality-floor", "0.45"]
    ).fold_quality_floor == "0.45"
    assert p.parse_args(
        ["-c", "x.yaml", "--fold-quality-floor", "OFF"]
    ).fold_quality_floor == "off"


def test_draw_random_policy_set_exhausted_space_raises():
    """num_op=1 leaves only 15 distinct op sequences; asking for 20
    must raise, not spin forever (round-5 review finding)."""
    from fast_autoaugment_tpu.search.driver import draw_random_policy_set

    with pytest.raises(ValueError, match="distinct sub-policies"):
        draw_random_policy_set(20, 5, 1, seed=0)


def test_fold_quality_floor_cli_rejects_non_finite():
    """float('nan') parses but nan > 0 is False — it would silently
    disable the gate; the validator must reject it (round-5 review)."""
    from fast_autoaugment_tpu.launch.search_cli import build_parser

    p = build_parser()
    for bad in ("nan", "inf", "-inf"):
        with pytest.raises(SystemExit):
            p.parse_args(["-c", "x.yaml", "--fold-quality-floor", bad])
