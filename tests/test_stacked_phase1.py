"""Fold-stacked phase-1 training: vmapped K-model train step, the
multiplexed per-fold data feed, the fold mesh, seeded stacked-vs-
sequential equivalence, driver wiring (--fold-stack), device-seconds
attribution, and the prefetch failure paths the pipeline relies on."""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fast_autoaugment_tpu.core.config import Config


def _conf(**over):
    base = {
        "model": {"type": "wresnet10_1"},
        "dataset": "synthetic",
        "aug": "default",
        "cutout": 8,
        "batch": 8,
        "epoch": 1,
        "lr": 0.05,
        "lr_schedule": {"type": "cosine", "warmup": {"multiplier": 2, "epoch": 1}},
        "optimizer": {"type": "sgd", "decay": 2e-4, "clip": 5.0,
                      "momentum": 0.9, "nesterov": True},
    }
    base.update(over)
    return Config(base)


# --------------------------------------------------- stacked data feed

def test_stacked_train_batches_match_sequential_streams():
    """Fold k's stream out of the multiplexed iterator must equal
    train_batches' for (indices[k], seeds[k]) EXACTLY — the property
    that makes stacked training consume bit-identical per-fold data."""
    from fast_autoaugment_tpu.data.datasets import ArrayDataset
    from fast_autoaugment_tpu.data.pipeline import (
        stacked_train_batches,
        train_batches,
    )

    rng = np.random.default_rng(0)
    ds = ArrayDataset(rng.integers(0, 256, (64, 4, 4, 3), dtype=np.uint8),
                      rng.integers(0, 10, (64,), np.int32), 10)
    folds = [np.arange(0, 40), np.arange(20, 60)]
    seeds = [0, 7]
    stacked = list(stacked_train_batches(ds, folds, 8, epoch=3, seeds=seeds))
    assert len(stacked) == 5  # 40 // 8
    for k in range(2):
        seq = list(train_batches(ds, folds[k], 8, epoch=3, seed=seeds[k]))
        assert len(seq) == len(stacked)
        for (sx, sy, sa), (qx, qy) in zip(stacked, seq):
            assert sa[k] == 1.0
            np.testing.assert_array_equal(sx[k], qx)
            np.testing.assert_array_equal(sy[k], qy)


def test_stacked_train_batches_uneven_folds_mask_out():
    """A fold with fewer steps goes active=0 on its exhausted lanes —
    the stacked shape never changes, the mask carries correctness."""
    from fast_autoaugment_tpu.data.datasets import ArrayDataset
    from fast_autoaugment_tpu.data.pipeline import stacked_train_batches

    rng = np.random.default_rng(1)
    ds = ArrayDataset(rng.integers(0, 256, (64, 4, 4, 3), dtype=np.uint8),
                      rng.integers(0, 10, (64,), np.int32), 10)
    folds = [np.arange(32), np.arange(16)]  # 4 vs 2 steps at batch 8
    out = list(stacked_train_batches(ds, folds, 8, epoch=1, seeds=[0, 0]))
    assert len(out) == 4
    actives = np.stack([a for _, _, a in out])
    np.testing.assert_array_equal(actives[:, 0], [1, 1, 1, 1])
    np.testing.assert_array_equal(actives[:, 1], [1, 1, 0, 0])
    assert all(x.shape == (2, 8, 4, 4, 3) for x, _, _ in out)


# ----------------------------------------------------------- fold mesh

def test_make_fold_mesh_sharding_rule(devices8):
    """The fold->mesh mapping rule: gcd(K, n_devices) fold shards, the
    rest on the data axis — devices >= K shard folds instead of
    replicating when the counts divide."""
    from fast_autoaugment_tpu.parallel.mesh import make_fold_mesh

    m = make_fold_mesh(4, devices8)  # 8 devices, K=4 -> (4, 2)
    assert m.shape["fold"] == 4 and m.shape["data"] == 2
    m = make_fold_mesh(5, devices8)  # coprime -> pure vmap stacking
    assert m.shape["fold"] == 1 and m.shape["data"] == 8
    m = make_fold_mesh(2, devices8, fold_shards=1)  # explicit override
    assert m.shape["fold"] == 1 and m.shape["data"] == 8
    m = make_fold_mesh(3, devices8[:1])  # single device
    assert m.shape["fold"] == 1 and m.shape["data"] == 1
    with pytest.raises(ValueError, match="does not divide"):
        make_fold_mesh(4, devices8, fold_shards=3)


def test_stacked_step_matches_sequential_per_step(devices8):
    """One stacked step from identical states equals K sequential steps
    to within the documented ~1 f32 ULP batched-kernel bound, and
    inactive lanes pass state through bit-for-bit unchanged."""
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.train.steps import (
        create_train_state,
        make_stacked_train_step,
        make_train_step,
        slice_state,
        stack_states,
    )

    model = get_model({"type": "wresnet10_1"}, 10)
    opt_conf = {"type": "sgd", "decay": 2e-4, "clip": 5.0, "momentum": 0.9,
                "nesterov": True}
    sample = jnp.zeros((2, 32, 32, 3), jnp.float32)
    kw = dict(num_classes=10, cutout_length=8, use_policy=False)
    K = 3

    def states():
        opt = build_optimizer(opt_conf, lambda s: 0.05)
        return [create_train_state(model, opt, jax.random.PRNGKey(k), sample,
                                   use_ema=False) for k in range(K)]

    opt = build_optimizer(opt_conf, lambda s: 0.05)
    seq_step = make_train_step(model, opt, **kw)
    st_step = make_stacked_train_step(model, opt, **kw)

    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (K, 8, 32, 32, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, (K, 8), np.int32)
    pol = jnp.zeros((1, 1, 3), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(100 + k) for k in range(K)])

    seq = states()
    seq_out = [seq_step(seq[k], jnp.asarray(images[k]),
                        jnp.asarray(labels[k]), pol, keys[k])
               for k in range(K)]
    stacked, metrics = st_step(stack_states(states()), jnp.asarray(images),
                               jnp.asarray(labels), pol, keys,
                               jnp.ones((K,), jnp.float32))
    for k in range(K):
        want_state, want_metrics = seq_out[k]
        got = slice_state(stacked, k)
        for a, b in zip(jax.tree.leaves(want_state.params),
                        jax.tree.leaves(got.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree.leaves(want_state.batch_stats),
                        jax.tree.leaves(got.batch_stats)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        assert float(metrics["num"][k]) == float(want_metrics["num"])
        assert float(metrics["top1"][k]) == float(want_metrics["top1"])

    # inactive lanes: state passes through UNTOUCHED (bitwise), metrics
    # zeroed — a masked lane is indistinguishable from not stepping
    base = stack_states(states())
    frozen, m0 = st_step(base, jnp.asarray(images), jnp.asarray(labels), pol,
                         keys, jnp.asarray([1.0, 0.0, 1.0], jnp.float32))
    ref = states()[1]
    for a, b in zip(jax.tree.leaves(ref.params),
                    jax.tree.leaves(slice_state(frozen, 1).params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(slice_state(frozen, 1).step) == 0
    assert float(m0["num"][1]) == 0.0
    assert int(slice_state(frozen, 0).step) == 1


# ------------------------------------------- trainer-level equivalence

def test_train_folds_stacked_matches_sequential(tmp_path, devices8):
    """Seeded equivalence at matched data-axis device count: per-fold
    params/batch_stats from the stacked trainer match sequential
    train_and_eval within the documented bound (ULP-level per-step
    kernel reduction-order differences, amplified over the run — the
    same deviation class as the committed 1-vs-8-device tolerance),
    checkpoints land under the same layout, and eval metrics agree."""
    from fast_autoaugment_tpu.core.checkpoint import load_checkpoint, read_metadata
    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.parallel.mesh import make_fold_mesh, make_mesh
    from fast_autoaugment_tpu.train.steps import create_train_state
    from fast_autoaugment_tpu.train.trainer import train_and_eval, train_folds_stacked

    conf = _conf()
    tmp = str(tmp_path)
    seq_paths = [os.path.join(tmp, f"seq{f}.msgpack") for f in (0, 1)]
    st_paths = [os.path.join(tmp, f"st{f}.msgpack") for f in (0, 1)]
    for f in (0, 1):
        train_and_eval(conf, tmp, test_ratio=0.4, cv_fold=f,
                       save_path=seq_paths[f], metric="last", seed=0,
                       evaluation_interval=1, mesh=make_mesh(devices8))
    res = train_folds_stacked(
        conf, tmp, cv_ratio=0.4, folds=[0, 1], save_paths=st_paths, seed=0,
        evaluation_interval=1, mesh=make_fold_mesh(2, devices8, fold_shards=1),
    )

    model = get_model({"type": "wresnet10_1"}, 10)
    opt = build_optimizer(dict(conf["optimizer"]), lambda s: 0.0)
    tmpl = create_train_state(model, opt, jax.random.PRNGKey(0),
                              jnp.zeros((2, 32, 32, 3)), use_ema=False)
    for f in (0, 1):
        a = load_checkpoint(seq_paths[f], tmpl)
        b = load_checkpoint(st_paths[f], tmpl)
        assert int(a.step) == int(b.step)
        # The per-step difference is reduction order only (vmapped vs
        # sequential kernels; test_stacked_step_matches_sequential_per_
        # step pins it at rtol 1e-5), and one epoch of SGD amplifies it
        # by an amount that depends on the random crops/flips drawn.
        # Under the installed JAX's default PRNG stream (partitionable
        # threefry) the measured worst case after the epoch is 1.2e-3
        # absolute on 2 of the 432 elements of the stem conv kernel
        # (XLA:CPU, jax 0.9.0); under the old stream
        # (JAX_THREEFRY_PARTITIONABLE=0) the same code stays inside
        # 1e-3.  2e-3 bounds the measured drift with headroom and still
        # fails on a real divergence (a wrong batch or key is >1e-1).
        for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-3, atol=2e-3)
        for x, y in zip(jax.tree.leaves(a.batch_stats),
                        jax.tree.leaves(b.batch_stats)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=5e-2, atol=1e-2)
        ma, mb = read_metadata(seq_paths[f]), read_metadata(st_paths[f])
        assert ma["epoch"] == mb["epoch"] == 1
        assert res[f]["top1_valid"] == pytest.approx(
            ma["metrics"]["top1_valid"], abs=0.05)
        # the sidecar layout the gate/retrain promotion walks
        assert os.path.exists(st_paths[f] + ".meta.json")
        assert os.path.exists(st_paths[f] + "_train.jsonl")


def test_train_folds_stacked_resume_and_fold_sharded_mesh(tmp_path, devices8):
    """Resume: a second call with complete checkpoints trains nothing
    and preserves state; a fold-SHARDED mesh (K=2 over 8 devices ->
    (2, 4)) trains to completion with folds on disjoint device groups."""
    from fast_autoaugment_tpu.core.checkpoint import read_metadata
    from fast_autoaugment_tpu.parallel.mesh import make_fold_mesh
    from fast_autoaugment_tpu.train.trainer import train_folds_stacked

    conf = _conf()
    tmp = str(tmp_path)
    paths = [os.path.join(tmp, f"f{f}.msgpack") for f in (0, 1)]
    mesh = make_fold_mesh(2, devices8)  # (2, 4): folds sharded
    assert mesh.shape["fold"] == 2
    res = train_folds_stacked(conf, tmp, cv_ratio=0.4, folds=[0, 1],
                              save_paths=paths, seed=0, mesh=mesh,
                              evaluation_interval=1)
    for f in (0, 1):
        assert read_metadata(paths[f])["epoch"] == 1
        assert np.isfinite(res[f]["loss_train"])
    mtimes = [os.path.getmtime(p) for p in paths]
    res2 = train_folds_stacked(conf, tmp, cv_ratio=0.4, folds=[0, 1],
                               save_paths=paths, seed=0, mesh=mesh,
                               evaluation_interval=1)
    assert [os.path.getmtime(p) for p in paths] == mtimes  # nothing retrained
    assert res2[0]["epoch"] == 1


def test_train_folds_stacked_rejects_lazy_and_ragged(monkeypatch):
    from fast_autoaugment_tpu.data.datasets import ArrayDataset
    from fast_autoaugment_tpu.train import trainer

    lazy = ArrayDataset(np.asarray(["a.jpg"] * 64, object),
                        np.zeros(64, np.int32), 10, lazy=True)
    monkeypatch.setattr(trainer, "load_dataset", lambda name, root: (lazy, lazy))
    with pytest.raises(ValueError, match="in-memory"):
        trainer.train_folds_stacked(_conf(), "/tmp", cv_ratio=0.4,
                                    folds=[0, 1], save_paths=["a", "b"],
                                    seed=0)
    monkeypatch.undo()
    with pytest.raises(ValueError, match="folds but"):
        trainer.train_folds_stacked(_conf(), "/tmp", cv_ratio=0.4,
                                    folds=[0, 1], save_paths=["a"], seed=0)


# --------------------------------------------------- driver / CLI / e2e

def _search_kwargs(tmp, **over):
    kw = dict(
        dataroot=tmp, save_dir=os.path.join(tmp, "search"), cv_num=2,
        cv_ratio=0.4, num_policy=1, num_op=1, num_search=2, num_top=1,
    )
    kw.update(over)
    return kw


def test_search_fold_stack_e2e_matches_sequential(tmp_path):
    """--fold-stack auto end-to-end: phase 1 trains both folds in one
    stacked program, phase 2 runs unchanged, the final policy set
    matches a sequential (--fold-stack 0) run of the same seed (fold
    oracles differ only within the documented stacking bound, the TPE
    trial stream is driven by the same keys), and the device-seconds
    accounting identity holds in both modes."""
    from fast_autoaugment_tpu.search.driver import search_policies

    conf = _conf()
    seq_tmp = str(tmp_path / "seq")
    st_tmp = str(tmp_path / "st")
    for d in (seq_tmp, st_tmp):
        os.makedirs(d, exist_ok=True)
    r_seq = search_policies(conf, **_search_kwargs(seq_tmp), fold_stack=0)
    r_st = search_policies(conf, **_search_kwargs(st_tmp), fold_stack="auto")
    assert r_seq["fold_stack"] == 0
    assert r_st["fold_stack"] == 2
    assert r_st["final_policy_set"]
    trials_seq = json.load(open(os.path.join(seq_tmp, "search", "search_trials.json")))
    trials_st = json.load(open(os.path.join(st_tmp, "search", "search_trials.json")))
    assert sorted(trials_st) == sorted(trials_seq) == ["0", "1"]
    # the TPE proposal stream is fold-seeded and identical across
    # modes; rewards (fold-oracle evals on stacked-vs-sequential
    # checkpoints) may differ only within the stacking bound, so the
    # final set is drawn from the same proposal pool in either mode
    for fold in ("0", "1"):
        for (pa, ra), (pb, rb) in zip(trials_seq[fold], trials_st[fold]):
            assert pa == pb
            assert rb == pytest.approx(ra, abs=0.1)
    # device_secs_phase1 accounting under stacking (ISSUE satellite):
    # the per-fold attribution sums to (at most) the once-recorded
    # phase total in BOTH modes and covers the bulk of it (gate off —
    # the non-attributed remainder is setup only), and a stacked group
    # splits its ONE wall measurement evenly
    for r, stacked_mode in ((r_seq, False), (r_st, True)):
        attr = r["device_secs_phase1_per_fold"]
        assert sorted(attr) == ["0", "1"]
        total = r["device_secs_phase1"]
        s = sum(attr.values())
        assert 0 < s <= total + 1e-6
        assert s >= 0.5 * total, (stacked_mode, attr, total)
        if stacked_mode:
            assert attr["0"] == pytest.approx(attr["1"])
    # resume: a stacked rerun retrains nothing and replays the trials
    r_resume = search_policies(conf, **_search_kwargs(st_tmp), fold_stack="auto")
    assert r_resume["final_policy_set"] == r_st["final_policy_set"]
    assert r_resume["fold_stack"] == 0  # nothing pending -> sequential no-op


def test_fold_stack_gate_retrain_and_exclusion(tmp_path, monkeypatch):
    """The fold-oracle quality gate still works over stacked-trained
    checkpoints: an unreachable floor triggers the sequential per-fold
    retrain path and excludes still-weak folds.  The retrain itself is
    stubbed with a checkpoint copy (its full training path is covered
    by the equivalence tests above and the gate tests in
    test_search.py) — what this pins is the gate/retrain MECHANISM over
    a stacked phase 1: assessment, .retryN promotion paths, exclusion."""
    import shutil

    from fast_autoaugment_tpu.search import driver

    conf = _conf()
    tmp = str(tmp_path)
    retrained = []

    def stub_retrain(_conf_, _dataroot, *, save_path, cv_fold, **kw):
        retrained.append(save_path)
        src = save_path.rsplit(".retry", 1)[0]
        for suffix in ("", ".meta.json"):
            shutil.copy(src + suffix, save_path + suffix)
        return {}

    monkeypatch.setattr(driver, "train_and_eval", stub_retrain)
    r = driver.search_policies(
        conf, **_search_kwargs(tmp), until=1, fold_stack="auto",
        fold_quality_floor=0.99, fold_retrain_tries=1,
    )
    assert r["fold_stack"] == 2
    # stacked training bypassed train_and_eval; every spy call is a
    # quality-gate retrain of a single below-floor fold
    assert len(retrained) == 2
    assert all(p.endswith((".retry1",)) for p in retrained)
    assert sorted(r["excluded_folds"]) == [0, 1]  # 0.99 is unreachable
    assert set(r["fold_baselines"]) == {"0", "1"}


def test_cli_fold_stack_flag():
    from fast_autoaugment_tpu.launch.search_cli import build_parser

    p = build_parser()
    assert p.parse_args(["-c", "x.yaml"]).fold_stack == 0
    assert p.parse_args(["-c", "x.yaml", "--fold-stack", "auto"]).fold_stack == "auto"
    assert p.parse_args(["-c", "x.yaml", "--fold-stack", "5"]).fold_stack == 5
    with pytest.raises(SystemExit):
        p.parse_args(["-c", "x.yaml", "--fold-stack", "nope"])
    with pytest.raises(SystemExit):
        p.parse_args(["-c", "x.yaml", "--fold-stack", "-1"])


def test_resolve_fold_stack():
    from fast_autoaugment_tpu.search.driver import resolve_fold_stack

    assert resolve_fold_stack(0, 5) == 0
    assert resolve_fold_stack(None, 5) == 0
    assert resolve_fold_stack("auto", 5) == 5
    assert resolve_fold_stack("auto", 1) == 0  # 1-fold stack buys nothing
    assert resolve_fold_stack(3, 5) == 3
    assert resolve_fold_stack(8, 3) == 3  # capped at pending folds
    assert resolve_fold_stack(1, 5) == 0
    with pytest.raises(ValueError):
        resolve_fold_stack(-2, 5)


# ------------------------------------------------ prefetch failure paths

def test_prefetch_worker_exception_propagates():
    """A worker exception must surface in the consumer — no deadlock,
    no swallowed error — after the items yielded before it."""
    from fast_autoaugment_tpu.data.pipeline import prefetch

    def gen():
        yield 1
        yield 2
        raise RuntimeError("decode boom")

    out = []
    with pytest.raises(RuntimeError, match="decode boom"):
        for item in prefetch(gen(), depth=1):
            out.append(item)
    assert out == [1, 2]


def test_prefetch_transform_exception_propagates():
    from fast_autoaugment_tpu.data.pipeline import prefetch

    def bad_transform(item):
        raise ValueError("transform boom")

    with pytest.raises(ValueError, match="transform boom"):
        list(prefetch(iter([1, 2]), depth=1, transform=bad_transform))


def test_prefetch_early_break_stops_worker_and_closes_generator():
    """Abandoning the consumer (break) must stop the worker within the
    bounded-wait window and close the SOURCE generator (its finally
    runs), releasing whatever the feed held."""
    from fast_autoaugment_tpu.data.pipeline import prefetch

    closed = threading.Event()
    produced = []

    def gen():
        try:
            for i in range(10_000):
                produced.append(i)
                yield i
        finally:
            closed.set()

    n_before = threading.active_count()
    it = prefetch(gen(), depth=2)
    for item in it:
        assert item == 0
        break
    it.close()
    assert closed.wait(2.0), "source generator not closed after break"
    deadline = time.time() + 2.0
    while threading.active_count() > n_before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= n_before, "worker thread leaked"
    # bounded production: the worker stopped near the queue depth, it
    # did not run the 10k-item feed dry into a dead queue
    assert len(produced) <= 10
