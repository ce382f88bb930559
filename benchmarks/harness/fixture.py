"""A seeded data set in CIFAR-10's own on-disk layout.

The program reads it through ``data/datasets.py::_load_cifar`` exactly
as it reads the real files: ``<dataroot>/cifar-10-batches-py/
data_batch_1..5`` and ``test_batch``, each a pickled dict with
``b"data"`` (uint8, ``[n, 3072]``, channel-major rows) and ``b"labels"``.

The images are learnable: each class has one coarse template, fixed by
the fixture file's ``template_seed`` (the data set's identity, the same
for every run), and every image is its class's template under a random
gain and offset plus pixel noise, all drawn from ``--seed`` together
with the labels.  So a model trained on one seed's images classifies
another seed's (the search cell reuses a fold checkpoint across runs),
the training loss falls inside a window, and augmentation policies
change the held-out accuracy.  Random labels would leave every TPE
reward at chance.
"""

from __future__ import annotations

import json
import os
import pickle

import numpy as np

_TRAIN_FILES = 5


def class_templates(spec: dict) -> np.ndarray:
    """``[classes, grid, grid, 3]`` float32 in [-1, 1]: a coarse random
    grid per class (each cell covers ``size // grid`` pixels), so that
    crops, flips and cutout leave the class recognisable."""
    rng = np.random.default_rng(int(spec["template_seed"]))
    grid = int(spec["template_grid"])
    return rng.uniform(-1.0, 1.0, (int(spec["classes"]), grid, grid, 3)
                       ).astype(np.float32)


def make_split(spec: dict, count: int, rng: np.random.Generator):
    """``(images uint8 [count, size, size, 3], labels int32 [count])``:
    ``128 + amplitude * gain * template[label] + offset`` upsampled to
    the image, plus uniform pixel noise over ``noise_levels`` values
    (a power of two, centred on zero)."""
    size, grid = int(spec["size"]), int(spec["template_grid"])
    levels = int(spec["noise_levels"])
    if levels & (levels - 1) or not 2 <= levels <= 256 or size % grid:
        raise ValueError("noise_levels must be a power of two in [2, 256] "
                         "and template_grid must divide size")
    labels = rng.integers(0, int(spec["classes"]), count).astype(np.int32)
    gain = rng.uniform(float(spec["gain_low"]), float(spec["gain_high"]),
                       (count, 1, 1, 1)).astype(np.float32)
    offset = rng.uniform(-float(spec["offset"]), float(spec["offset"]),
                         (count, 1, 1, 1)).astype(np.float32)
    base = (128.0 - levels // 2 + offset
            + float(spec["template_amplitude"]) * gain
            * class_templates(spec)[labels])
    # room for the noise on top, so the sum below cannot wrap
    base = np.clip(base, 0, 256 - levels).astype(np.uint8)
    reps = size // grid
    images = np.repeat(np.repeat(base, reps, axis=1), reps, axis=2)
    words = rng.integers(0, 2**64, count * size * size * 3 // 8,
                         dtype=np.uint64)
    images += words.view(np.uint8).reshape(images.shape) & np.uint8(levels - 1)
    return images, labels


def write_fixture(dataroot: str, spec: dict, seed: int) -> str:
    """Write the fixture for `seed` under `dataroot` (overwriting another
    seed's) and return `dataroot`.  A fixture already there for the same
    seed and spec is left alone."""
    base = os.path.join(dataroot, "cifar-10-batches-py")
    stamp_path = os.path.join(base, "fixture.json")
    want = {"seed": int(seed), "spec": spec}
    try:
        with open(stamp_path) as fh:
            if json.load(fh) == want:
                return dataroot
    except (OSError, ValueError):
        pass
    os.makedirs(base, exist_ok=True)
    if os.path.exists(stamp_path):
        os.remove(stamp_path)  # a torn rewrite must not read as complete
    rng = np.random.default_rng(int(seed))
    n_train, n_test = int(spec["train"]), int(spec["test"])
    if n_train % _TRAIN_FILES:
        raise ValueError(f"train count {n_train} does not divide into "
                         f"{_TRAIN_FILES} batch files")
    images, labels = make_split(spec, n_train, rng)
    per_file = n_train // _TRAIN_FILES
    for i in range(_TRAIN_FILES):
        sl = slice(i * per_file, (i + 1) * per_file)
        _write_batch(os.path.join(base, f"data_batch_{i + 1}"),
                     images[sl], labels[sl])
    images, labels = make_split(spec, n_test, rng)
    _write_batch(os.path.join(base, "test_batch"), images, labels)
    with open(stamp_path, "w") as fh:
        json.dump(want, fh)
    return dataroot


def _write_batch(path: str, images: np.ndarray, labels: np.ndarray) -> None:
    rows = np.ascontiguousarray(images.transpose(0, 3, 1, 2)).reshape(
        len(images), -1)
    with open(path, "wb") as fh:
        pickle.dump({b"data": rows, b"labels": labels.tolist()}, fh,
                    protocol=pickle.HIGHEST_PROTOCOL)
