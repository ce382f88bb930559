"""A seeded data set of JPEG files in ImageNet's on-disk layout.

The program reads it through ``data/datasets.py::_load_imagenet_listing``
exactly as it reads ILSVRC2012: ``<dataroot>/train/<wnid>/<stem>.JPEG``
with a Kaggle-form ``<dataroot>/train_cls.txt`` (``<wnid>/<stem> <index>``
a line, the loader's fast path; the label is the rank of the ``wnid``
among those listed) and ``<dataroot>/val/<wnid>/*.JPEG`` found by walking
the folders.  Every training file is listed ``listed_times`` times: each
entry is decoded and cropped anew with its own box, so the host's work is
that of ``train_files * listed_times`` files while set-up writes
``train_files``.

The images are learnable, as ``fixture.py``'s are: each class has one
coarse template, fixed by the fixture file's ``template_seed``, and every
image is its class's template under a random gain and offset plus uniform
pixel noise, whose amplitude sets the file size.  An image's pixels come
from ``(--seed, split, index)`` alone, so the files are the same bytes
whatever the order the writer's threads take them in.
"""

from __future__ import annotations

import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_SPLITS = {"train": 0, "val": 1}


def class_templates(spec: dict) -> np.ndarray:
    """``[classes, grid, grid, 3]`` float32 in [-1, 1]."""
    rng = np.random.default_rng(int(spec["template_seed"]))
    grid = int(spec["template_grid"])
    return rng.uniform(-1.0, 1.0, (int(spec["classes"]), grid, grid, 3)
                       ).astype(np.float32)


def wnids(spec: dict) -> list[str]:
    """The class folders: ``wnid_step`` apart, sorted as the loader ranks
    them, so folder ``k`` is label ``k``."""
    return [f"n{int(spec['wnid_first']) + k * int(spec['wnid_step']):08d}"
            for k in range(int(spec["classes"]))]


def label_of(index: int, spec: dict) -> int:
    return index % int(spec["classes"])


def image_shape(index: int, spec: dict) -> tuple[int, int]:
    """``(height, width)``: one file in ``upright_every`` stands upright."""
    long, short = int(spec["width"]), int(spec["height"])
    return (long, short) if index % int(spec["upright_every"]) == 0 else (short, long)


def make_image(spec: dict, templates: np.ndarray, seed: int, split: str,
               index: int) -> np.ndarray:
    """uint8 ``[height, width, 3]``: ``128 + amplitude * gain *
    template[label] + offset`` stretched over the image (nearest), plus
    uniform noise in ``[-noise_amplitude, noise_amplitude]``."""
    rng = np.random.default_rng((int(seed), _SPLITS[split], int(index)))
    height, width = image_shape(index, spec)
    grid = int(spec["template_grid"])
    gain = rng.uniform(float(spec["gain_low"]), float(spec["gain_high"]))
    offset = rng.uniform(-float(spec["offset"]), float(spec["offset"]))
    coarse = (128.0 + offset + float(spec["template_amplitude"]) * gain
              * templates[label_of(index, spec)])
    rows = (np.arange(height) * grid) // height
    cols = (np.arange(width) * grid) // width
    amplitude = int(spec["noise_amplitude"])
    noise = rng.integers(-amplitude, amplitude + 1, (height, width, 3),
                         dtype=np.int16)
    return np.clip(coarse[rows][:, cols] + noise, 0, 255).astype(np.uint8)


def _write_one(job) -> int:
    import PIL.Image

    spec, templates, seed, split, index, path = job
    PIL.Image.fromarray(make_image(spec, templates, seed, split, index)).save(
        path, format="JPEG", quality=int(spec["quality"]))
    return os.path.getsize(path)


def write_fixture(dataroot: str, spec: dict, seed: int) -> dict:
    """Write the fixture for `seed` under `dataroot` (replacing another
    seed's) and return ``{"files", "bytes", "mean_file_bytes"}`` of the
    training files.  A fixture already there for the same seed and spec
    is left alone."""
    stamp_path = os.path.join(dataroot, "fixture.json")
    want = {"seed": int(seed), "spec": spec}
    try:
        with open(stamp_path) as fh:
            held = json.load(fh)
        if held["want"] == want:
            return held["wrote"]
    except (OSError, ValueError, KeyError):
        pass
    shutil.rmtree(dataroot, ignore_errors=True)
    names = wnids(spec)
    templates = class_templates(spec)
    jobs, listing = [], []
    for split, count in (("train", int(spec["train_files"])),
                         ("val", int(spec["val_files"]))):
        for name in names:
            os.makedirs(os.path.join(dataroot, split, name))
        for index in range(count):
            name = names[label_of(index, spec)]
            stem = f"{name}_{index}" if split == "train" else f"val_{index:08d}"
            jobs.append((spec, templates, seed, split, index,
                         os.path.join(dataroot, split, name, stem + ".JPEG")))
            if split == "train":
                listing.append(f"{name}/{stem}")
    with ThreadPoolExecutor(max_workers=min(16, os.cpu_count() or 1)) as pool:
        sizes = list(pool.map(_write_one, jobs))
    # the Kaggle listing: every file `listed_times` times over, an index a line
    times = int(spec["listed_times"])
    with open(os.path.join(dataroot, "train_cls.txt"), "w") as fh:
        for n, rel in enumerate(listing * times):
            fh.write(f"{rel} {n + 1}\n")
    train_bytes = int(sum(sizes[:len(listing)]))
    wrote = {"files": len(listing), "bytes": train_bytes,
             "mean_file_bytes": train_bytes / len(listing)}
    with open(stamp_path, "w") as fh:
        json.dump({"want": want, "wrote": wrote}, fh)
    return wrote
