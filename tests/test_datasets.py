"""Dataset reader tests against real on-disk formats (synthesized CIFAR
pickle batches, SVHN .mat, CIFAR-10.1 .npy), split parity, and a
learnability check that the full training loop actually learns."""

import os
import pickle

import numpy as np
import pytest

from fast_autoaugment_tpu.data.datasets import cv_split, load_dataset


def _write_cifar10(root, n_per_batch=20):
    base = os.path.join(root, "cifar-10-batches-py")
    os.makedirs(base, exist_ok=True)

    def batch(n, seed):
        r = np.random.default_rng(seed)
        return {
            b"data": r.integers(0, 256, (n, 3072), dtype=np.uint8).astype(np.uint8),
            b"labels": r.integers(0, 10, (n,)).tolist(),
        }

    for i in range(1, 6):
        with open(os.path.join(base, f"data_batch_{i}"), "wb") as fh:
            pickle.dump(batch(n_per_batch, i), fh)
    with open(os.path.join(base, "test_batch"), "wb") as fh:
        pickle.dump(batch(10, 99), fh)


def _write_svhn(root, n=30):
    import scipy.io

    rng = np.random.default_rng(1)
    for split, count in (("train", n), ("test", 10), ("extra", 15)):
        scipy.io.savemat(
            os.path.join(root, f"{split}_32x32.mat"),
            {
                "X": rng.integers(0, 256, (32, 32, 3, count), dtype=np.uint8),
                # SVHN labels are 1..10 with 10 meaning digit 0
                "y": rng.integers(1, 11, (count, 1)).astype(np.uint8),
            },
        )


def test_cifar10_pickle_reader(tmp_path):
    _write_cifar10(str(tmp_path))
    train, test = load_dataset("cifar10", str(tmp_path))
    assert train.images.shape == (100, 32, 32, 3) and train.images.dtype == np.uint8
    assert test.images.shape == (10, 32, 32, 3)
    assert train.num_classes == 10
    # HWC unpacking: channel planes must not be interleaved — rebuild one
    with open(tmp_path / "cifar-10-batches-py" / "data_batch_1", "rb") as fh:
        raw = pickle.load(fh, encoding="bytes")[b"data"][0]
    want = raw.reshape(3, 32, 32).transpose(1, 2, 0)
    np.testing.assert_array_equal(train.images[0], want)


def test_svhn_mat_reader(tmp_path):
    _write_svhn(str(tmp_path))
    train, test = load_dataset("svhn", str(tmp_path))
    # svhn = train + extra concatenated (reference data.py:130-134)
    assert len(train) == 45 and len(test) == 10
    assert train.images.shape[1:] == (32, 32, 3)
    # label 10 -> 0 like torchvision
    assert set(np.unique(train.labels)) <= set(range(10))


def test_cifar10_1_variant(tmp_path):
    _write_cifar10(str(tmp_path))
    rng = np.random.default_rng(3)
    np.save(tmp_path / "cifar10.1_v6_data.npy",
            rng.integers(0, 256, (7, 32, 32, 3), dtype=np.uint8))
    np.save(tmp_path / "cifar10.1_v6_labels.npy", rng.integers(0, 10, (7,)))
    train, test = load_dataset("cifar10.1", str(tmp_path))
    assert len(train) == 100 and len(test) == 7


def test_reduced_cifar10_requires_enough_examples(tmp_path):
    # reduced_cifar10 wants 46000 held out of 50000; synthetic 100-example
    # files must fail loudly, not silently produce an empty set
    _write_cifar10(str(tmp_path))
    with pytest.raises(ValueError):
        load_dataset("reduced_cifar10", str(tmp_path))


def test_cv_split_is_deterministic_and_overlapping():
    labels = np.repeat(np.arange(10), 50)
    a_train, a_valid = cv_split(labels, 0.4, 0)
    b_train, b_valid = cv_split(labels, 0.4, 0)
    np.testing.assert_array_equal(a_train, b_train)
    np.testing.assert_array_equal(a_valid, b_valid)
    # resamples overlap (NOT disjoint K-fold — SURVEY errata 3)
    _c_train, c_valid = cv_split(labels, 0.4, 1)
    assert len(np.intersect1d(a_valid, c_valid)) > 0
    assert len(a_train) == 300 and len(a_valid) == 200


def test_training_actually_learns():
    """Learnability: a tiny model on a linearly-separable synthetic task
    (class = which half of the image is brighter) must fit far above
    chance within a few epochs — the whole-loop sanity check the
    reference never had."""
    import jax

    from fast_autoaugment_tpu.core.config import Config
    from fast_autoaugment_tpu.data import datasets

    rng = np.random.default_rng(0)
    n = 512
    images = rng.integers(0, 100, (n, 32, 32, 3), dtype=np.uint8)
    labels = rng.integers(0, 2, (n,)).astype(np.int32)
    # paint the signal: class 1 -> bright top half
    images[labels == 1, :16] += 120

    ds = datasets.ArrayDataset(images, labels, 2)
    orig = datasets.load_dataset

    def fake_load(name, root):
        return ds, ds

    datasets.load_dataset = fake_load
    try:
        import fast_autoaugment_tpu.train.trainer as trainer_mod

        trainer_mod.load_dataset = fake_load
        conf = Config({
            "model": {"type": "wresnet10_1"},
            "dataset": "synthetic",  # only used for num_class -> override below
            "aug": "default",
            "cutout": 0,
            "batch": 16,
            "epoch": 3,
            "lr": 0.02,
            "lr_schedule": {"type": "cosine"},
            "optimizer": {"type": "sgd", "decay": 1e-4, "clip": 5.0,
                          "momentum": 0.9, "nesterov": True},
        })
        import fast_autoaugment_tpu.models as models_mod

        orig_nc = models_mod.num_class
        trainer_nc = trainer_mod.num_class
        models_mod.num_class = lambda d: 2
        trainer_mod.num_class = lambda d: 2
        try:
            result = trainer_mod.train_and_eval(
                conf, dataroot="/nonexistent", test_ratio=0.0,
                evaluation_interval=3, metric="last",
            )
        finally:
            models_mod.num_class = orig_nc
            trainer_mod.num_class = trainer_nc
    finally:
        datasets.load_dataset = orig
        import fast_autoaugment_tpu.train.trainer as trainer_mod

        trainer_mod.load_dataset = orig

    assert result["top1_train"] > 0.9, result["top1_train"]
    assert result["top1_test"] > 0.9, result["top1_test"]


def test_eval_batches_shards_across_processes():
    """Multi-host eval must partition work, not duplicate it: the union of
    per-process shards is the dataset exactly once, padding is masked out,
    and every shard is the same size (ADVICE round 1, medium)."""
    from fast_autoaugment_tpu.data.datasets import ArrayDataset
    from fast_autoaugment_tpu.data.pipeline import eval_batches

    n = 10  # deliberately not a multiple of batch or mesh size
    ds = ArrayDataset(
        np.arange(n, dtype=np.uint8).reshape(n, 1, 1, 1) * np.ones((1, 2, 2, 3), np.uint8),
        np.arange(n, dtype=np.int32), 10,
    )
    seen = []
    for pi in range(2):
        got = list(eval_batches(ds, None, 4, process_index=pi,
                                process_count=2, pad_multiple=4))
        sizes = {im.shape[0] for im, _, _ in got}
        assert sizes == {2}, "every global batch split evenly across 2 hosts"
        for im, lab, mask in got:
            assert im.shape[0] == len(lab) == len(mask)
            seen.extend(int(l) for l, m in zip(lab, mask) if m > 0)
    assert sorted(seen) == list(range(n)), "each sample exactly once globally"


def test_eval_batches_single_process_pads_to_multiple():
    from fast_autoaugment_tpu.data.datasets import ArrayDataset
    from fast_autoaugment_tpu.data.pipeline import eval_batches

    ds = ArrayDataset(np.zeros((5, 2, 2, 3), np.uint8),
                      np.arange(5, dtype=np.int32), 10)
    got = list(eval_batches(ds, None, 4, pad_multiple=4))
    assert [im.shape[0] for im, _, _ in got] == [4, 4]
    assert sum(int(m.sum()) for _, _, m in got) == 5


def test_prefetch_transform_runs_in_worker_and_propagates_errors(monkeypatch):
    """prefetch(transform=) applies the mapping off the consumer thread
    and re-raises worker exceptions (including strict-zip arity errors
    from shard_transform) at the consumer."""
    import pytest

    from fast_autoaugment_tpu.data.pipeline import prefetch

    monkeypatch.delenv("FAA_PREFETCH_SYNC", raising=False)  # async path

    items = [(np.ones((2, 2)), np.zeros(2)), (np.zeros((2, 2)), np.ones(2))]
    got = list(prefetch(iter(items), transform=lambda t: {"x": t[0], "y": t[1]}))
    assert [sorted(d) for d in got] == [["x", "y"], ["x", "y"]]

    def boom(_):
        raise ValueError("bad batch")

    with pytest.raises(ValueError, match="bad batch"):
        list(prefetch(iter(items), transform=boom))


def test_shard_transform_arity_is_strict():
    """shard_transform must fail loudly when the key tuple does not match
    the pipeline tuple (a silently dropped mask would surface later as a
    KeyError far from the call site)."""
    import jax
    import pytest

    from fast_autoaugment_tpu.parallel.mesh import make_mesh, shard_transform

    mesh = make_mesh(jax.devices()[:1])
    to_dev = shard_transform(mesh, ("x", "y"))
    out = to_dev((np.zeros((4, 2, 2, 3), np.uint8), np.zeros(4, np.int32)))
    assert set(out) == {"x", "y"} and out["x"].shape == (4, 2, 2, 3)

    with pytest.raises(ValueError):
        shard_transform(mesh, ("x", "y", "m"))(
            (np.zeros((4, 2, 2, 3), np.uint8), np.zeros(4, np.int32))
        )


def test_prefetch_early_abandon_releases_worker(monkeypatch):
    """Breaking out of a prefetch loop (bench/eval early exit) must stop
    the worker thread rather than leave it blocked on a full queue
    holding buffered (possibly device-resident) batches."""
    import threading
    import time

    from fast_autoaugment_tpu.data.pipeline import prefetch

    monkeypatch.delenv("FAA_PREFETCH_SYNC", raising=False)  # async path
    before = set(threading.enumerate())
    it = prefetch(iter(range(100)), depth=1)
    assert next(it) == 0
    spawned = [t for t in threading.enumerate() if t not in before]
    assert spawned, "prefetch did not spawn a worker thread"
    it.close()  # what an abandoned for-loop break does on GC
    for t in spawned:
        t.join(timeout=5.0)
    assert not any(t.is_alive() for t in spawned), "prefetch worker leaked"


_THREADED_FEED_CHILD = r"""
import json, os, threading
import jax, jax.numpy as jnp, numpy as np
from fast_autoaugment_tpu.data import pipeline
from fast_autoaugment_tpu.data.datasets import ArrayDataset
from fast_autoaugment_tpu.models import get_model
from fast_autoaugment_tpu.parallel.mesh import make_mesh
from fast_autoaugment_tpu.train.steps import make_eval_step
from fast_autoaugment_tpu.train.trainer import _run_eval

assert len(jax.devices()) == 1, jax.devices()
mesh = make_mesh()
model = get_model({"type": "wresnet10_1"}, 10)
variables = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8, 8, 3)),
                       train=False)
eval_step = make_eval_step(model, num_classes=10)
rng = np.random.default_rng(0)
ds = ArrayDataset(rng.integers(0, 256, (100, 8, 8, 3), dtype=np.uint8),
                  rng.integers(0, 10, (100,), dtype=np.int32), 10)

def run():
    return _run_eval(eval_step, variables["params"],
                     variables.get("batch_stats", {}),
                     pipeline.eval_batches(ds, None, 4, pad_multiple=1), mesh)

workers = []
real_thread = threading.Thread
class Spy(real_thread):
    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        workers.append(self)
pipeline.threading.Thread = Spy
threaded = run()
n_workers = len(workers)
os.environ["FAA_PREFETCH_SYNC"] = "1"
inline = run()
print(json.dumps({"threaded": threaded, "inline": inline,
                  "workers": n_workers,
                  "inline_workers": len(workers) - n_workers}))
"""


def test_threaded_prefetch_device_put_on_one_cpu_device():
    """The DEFAULT feed — prefetch's worker thread running the
    device_put transform while the consumer dispatches — on a single
    device, the case the chip has (the suite itself runs the sync feed:
    tests/conftest.py).  `_run_eval` through the threaded feed must
    match the inline feed exactly, over 25 batches, without aborting.
    Runs in a child so the 1-device backend is real and a crash of the
    client cannot take pytest with it."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    env.pop("FAA_PREFETCH_SYNC", None)
    env.pop("XLA_FLAGS", None)  # one CPU device, not the 8-device mesh
    r = subprocess.run([sys.executable, "-c", _THREADED_FEED_CHILD], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["workers"] == 1 and rec["inline_workers"] == 0
    assert rec["threaded"]["num"] == 100.0
    assert rec["threaded"] == rec["inline"]


def test_synthetic_shapes_difficulty_knobs():
    """The render knobs grade task difficulty: higher noise / lower glyph
    contrast measurably corrupts the clean image."""
    from fast_autoaugment_tpu.data.datasets import _synthetic_shapes

    clean_train, _ = _synthetic_shapes(n_train=32, n_test=1)
    noisy_train, _ = _synthetic_shapes(n_train=32, n_test=1, noise=60.0)
    faint_train, _ = _synthetic_shapes(n_train=32, n_test=1, fg_lo=5.0, fg_hi=10.0)
    assert clean_train.images.std() > faint_train.images.std(), \
        "lower fg contrast must flatten the image"
    diff = (noisy_train.images.astype(np.float32)
            - clean_train.images.astype(np.float32))
    assert np.abs(diff).mean() > 10.0, "higher noise floor must perturb pixels"


def test_synthetic_shapes_pose_variant():
    """The pose variant must actually vary pose: per-sample rotation and
    scale change the glyph footprint in ways the base render never does,
    and the registry name parametrizes train size."""
    from fast_autoaugment_tpu.data.datasets import _synthetic_shapes, load_dataset

    base_train, _ = _synthetic_shapes(n_train=64, n_test=1)
    pose_train, _ = _synthetic_shapes(n_train=64, n_test=1, max_rot=25.0,
                                      scale_lo=0.7, scale_hi=1.3)
    assert pose_train.images.shape == base_train.images.shape
    # same label stream (same seed), different rendered pixels
    np.testing.assert_array_equal(pose_train.labels, base_train.labels)
    diff = (pose_train.images.astype(np.int32)
            - base_train.images.astype(np.int32))
    assert np.abs(diff).mean() > 2.0, "pose knobs changed nothing"

    train, test = load_dataset("synthetic_shapes_pose300", dataroot="")
    assert len(train) == 300 and train.num_classes == 10 and len(test) == 2000
