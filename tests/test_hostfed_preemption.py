"""The host-fed branch of ``train_and_eval`` on a lazy JPEG dataset: it
beats once a dispatch, stops at the dispatch after a preemption request,
and a run resumed from that checkpoint ends the epoch with the unbroken
run's parameters; and the PIL decode pool gives the serial loop's bytes.

A 24-file ImageFolder of 40 x 30 JPEGs, WRN-10-1 with the 1,000-way head
at 16 px (``imgsize``), batch 4 on one CPU device: 6 steps an epoch.
"""

import os

import jax
import numpy as np
import PIL.Image
import pytest

from fast_autoaugment_tpu.core import resilience, telemetry
from fast_autoaugment_tpu.core.checkpoint import read_metadata
from fast_autoaugment_tpu.core.config import Config
from fast_autoaugment_tpu.data import native_loader, pipeline
from fast_autoaugment_tpu.ops.preprocess_imagenet import random_crop_box
from fast_autoaugment_tpu.parallel.mesh import make_mesh
from fast_autoaugment_tpu.train.trainer import train_and_eval

FILES, BATCH, STEPS = 24, 4, 6


@pytest.fixture(scope="module")
def dataroot(tmp_path_factory):
    """``train/<wnid>/*.JPEG`` with a Kaggle-form ``train_cls.txt`` and
    ``val/<wnid>/``: three classes, a quarter of the files upright."""
    root = tmp_path_factory.mktemp("imagefolder")
    rng = np.random.default_rng(5)
    for split, count in (("train", FILES), ("val", 6)):
        lines = []
        for i in range(count):
            wnid = f"n{i % 3:08d}"
            os.makedirs(root / split / wnid, exist_ok=True)
            shape = (40, 30, 3) if i % 4 == 0 else (30, 40, 3)
            pixels = rng.integers(0, 256, shape, dtype=np.uint8)
            PIL.Image.fromarray(pixels).save(
                root / split / wnid / f"{split}_{i}.JPEG", quality=90)
            lines.append(f"{wnid}/{split}_{i} {i + 1}")
        if split == "train":
            (root / "train_cls.txt").write_text("\n".join(lines) + "\n")
    return str(root)


def _conf(epochs=1):
    return Config({
        "model": {"type": "wresnet10_1"}, "dataset": "imagenet",
        "imgsize": 16, "aug": "default", "cutout": 0, "batch": BATCH,
        "epoch": epochs, "lr": 0.05,
        "lr_schedule": {"type": "cosine"},
        "optimizer": {"type": "sgd", "nesterov": True, "decay": 1e-4,
                      "clip": 0, "ema": 0}})


@pytest.fixture(scope="module")
def native_so(tmp_path_factory):
    """``native/faa_loader.cpp`` built into a directory of this module's
    own: the checkout's ``libfaa_loader.so`` is there or not by which
    test file ran first, and two workers must not write it at once."""
    import subprocess

    source = os.path.join(os.path.dirname(native_loader._SO_PATH),
                          "faa_loader.cpp")
    built = str(tmp_path_factory.mktemp("native") / "libfaa_loader.so")
    try:
        subprocess.run(["g++", "-O2", "-fPIC", "-std=c++17", "-pthread",
                        source, "-o", built, "-shared", "-ljpeg"],
                       check=True, capture_output=True)
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        pytest.skip(f"no compiler or libjpeg for the native loader: {e}")
    return built


@pytest.fixture(params=["pil", "native"])
def decoder(request, monkeypatch):
    """Both decoders the feed has: PIL threads where the shared library
    is not built (a fresh checkout), the native loader where it is."""
    monkeypatch.setattr(native_loader, "_lib", None)
    if request.param == "pil":
        monkeypatch.setenv("FAA_NATIVE_LOADER", "0")
    else:
        monkeypatch.setenv("FAA_NATIVE_LOADER", "1")
        monkeypatch.setattr(native_loader, "_SO_PATH",
                            request.getfixturevalue("native_so"))
        assert native_loader.available()
    return request.param


def _train(dataroot, save_path, **kw):
    return train_and_eval(
        _conf(), dataroot, save_path=save_path,
        mesh=make_mesh(jax.devices()[:1]), seed=3,
        evaluation_interval=1, **kw)


def _digest(path):
    meta = read_metadata(path)
    assert meta and "digest" in meta
    return meta["digest"]


def test_a_lazy_dataset_keeps_the_host_feed_and_beats_once_a_dispatch(
        dataroot, decoder, tmp_path):
    dispatches = telemetry.registry().counter(
        "faa_dispatches_total", label="train_step")
    decoded = telemetry.registry().counter("faa_decode_images_total")
    seconds = telemetry.registry().counter(
        "faa_decode_seconds_total", decoder=decoder)
    batches = telemetry.registry().counter("faa_feed_batches_total")
    before = (dispatches.value, decoded.value, seconds.value, batches.value)
    seen = []
    result = _train(dataroot, str(tmp_path / "m.msgpack"),
                    heartbeat=lambda: seen.append(int(dispatches.value)))
    assert result["steps"] == STEPS
    # one beat after every dispatch, and the epoch boundary's, which sees
    # no dispatch since the last
    assert seen == [before[0] + i for i in (1, 2, 3, 4, 5, 6, 6)]
    # 24 training images and 6 validation images went through the decoder
    assert decoded.value - before[1] == FILES + 6
    assert seconds.value > before[2]
    assert batches.value - before[3] == STEPS + 2  # + two evaluation batches


@pytest.mark.parametrize("k", [1, 4])
def test_preempted_at_step_k_and_resumed_equals_the_unbroken_run(
        dataroot, decoder, tmp_path, k):
    full = str(tmp_path / "full.msgpack")
    unbroken = _train(dataroot, full)

    part = str(tmp_path / "part.msgpack")
    beats = []

    def stop_at_k():
        beats.append(1)
        if len(beats) == k:
            resilience.request_preemption()

    resilience.clear_preemption()
    try:
        with pytest.raises(resilience.PreemptedError):
            _train(dataroot, part, heartbeat=stop_at_k)
    finally:
        resilience.clear_preemption()
    assert len(beats) == k  # it stopped at that dispatch, not an epoch late
    meta = read_metadata(part)
    assert meta["preempted"] is True and meta["step"] == k
    assert meta["epoch"] == 0
    assert (meta["in_epoch"]["epoch"], meta["in_epoch"]["pos"]) == (1, k)
    assert meta["in_epoch"]["sums"]["num"] == k * BATCH

    decoded = telemetry.registry().counter("faa_decode_images_total")
    before = decoded.value
    resumed = _train(dataroot, part)
    # the k batches already trained were not decoded again
    assert decoded.value - before == FILES - k * BATCH + 6
    assert resumed["steps"] == STEPS
    assert _digest(part) == _digest(full)
    for key in ("loss_train", "top1_train", "loss_test"):
        assert resumed[key] == unbroken[key], key


def test_an_only_eval_restore_takes_the_mid_epoch_checkpoint(dataroot,
                                                             tmp_path):
    part = str(tmp_path / "part.msgpack")
    resilience.clear_preemption()
    try:
        with pytest.raises(resilience.PreemptedError):
            _train(dataroot, part, heartbeat=resilience.request_preemption)
    finally:
        resilience.clear_preemption()
    evaluated = _train(dataroot, part, only_eval=True)
    assert evaluated["steps"] == 1 and np.isfinite(evaluated["loss_test"])


def test_threaded_pil_decode_equals_the_serial_loop_byte_for_byte(
        dataroot, monkeypatch):
    monkeypatch.setenv("FAA_NATIVE_LOADER", "0")
    monkeypatch.setattr(native_loader, "_lib", None)
    paths = np.asarray(sorted(
        os.path.join(folder, name)
        for folder, _, names in os.walk(os.path.join(dataroot, "train"))
        for name in names), object)
    box_fn = lambda rng, w, h: random_crop_box(rng, w, h, 16)  # noqa: E731
    threaded = pipeline._decode_boxed(
        paths, 16, box_fn, np.random.default_rng(9), pipeline.SizeCache())
    rng = np.random.default_rng(9)
    serial = []
    for path in paths:
        with PIL.Image.open(path) as img:
            box = np.asarray(box_fn(rng, *img.size), np.float32)
            serial.append(np.asarray(img.convert("RGB").crop(tuple(box)).resize(
                (16, 16), PIL.Image.BICUBIC), np.uint8))
    assert threaded.dtype == np.uint8 and threaded.shape == (FILES, 16, 16, 3)
    assert threaded.tobytes() == np.stack(serial).tobytes()


def test_a_skipped_batch_leaves_the_rest_of_the_epoch_as_it_was(dataroot):
    """``train_batches(skip=k)``: the crop boxes of the skipped batches
    are drawn, so batch k onwards is the unbroken epoch's."""
    from fast_autoaugment_tpu.data.datasets import load_dataset

    train, _ = load_dataset("imagenet", dataroot)
    feed = pipeline.BatchIterator(
        train, train_box_fn=lambda rng, w, h: random_crop_box(rng, w, h, 16),
        imgsize=16)
    whole = list(feed.train_epoch(BATCH, 2, seed=7))
    tail = list(feed.train_epoch(BATCH, 2, seed=7, skip=2))
    assert len(whole) == STEPS and len(tail) == STEPS - 2
    for (x0, y0), (x1, y1) in zip(whole[2:], tail):
        assert x0.tobytes() == x1.tobytes() and (y0 == y1).all()


def test_a_wait_on_the_feed_is_counted_and_leaves_a_span():
    import time

    reg = telemetry.registry()
    waited = reg.counter("faa_feed_wait_seconds_total")
    batches = reg.counter("faa_feed_batches_total")
    spans = reg.counter("faa_dispatches_total", label="feed_wait")
    before = (waited.value, batches.value, spans.value)

    def slow():
        for i in range(3):
            time.sleep(0.02)
            yield i

    for depth in (0, 2):  # the synchronous feed and the worker thread
        assert list(pipeline.prefetch(slow(), depth=depth)) == [0, 1, 2]
    assert batches.value - before[1] == 6
    assert waited.value - before[0] >= 6 * 0.015
    assert spans.value - before[2] == 6
    # a batch that is ready leaves no span
    assert list(pipeline.prefetch(iter([1, 2]), depth=0)) == [1, 2]
    assert spans.value - before[2] == 6
