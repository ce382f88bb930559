"""Native dataset readers and split machinery.

The reference leans on torchvision datasets + sklearn splits
(``data.py:114-225``).  Here the readers are in-tree (CIFAR pickle
batches, SVHN .mat, ImageNet folder listing) producing plain numpy
arrays — the host never decodes more than once, and the TPU input
pipeline feeds raw uint8 batches (augmentation happens on device).

Split parity: reduced variants and CV "folds" use sklearn
``StratifiedShuffleSplit`` with the reference's exact parameters and
``random_state=0`` (``data.py:119,137,192-196``), so fold membership
matches the reference bit-for-bit.  Note the reference's 5 "folds" are
5 independent overlapping train/valid resamples, NOT disjoint K-folds
(SURVEY.md errata 3).

A deterministic ``synthetic`` dataset backs tests and benchmarks on
machines without data on disk.
"""

from __future__ import annotations

import os
import pickle
from dataclasses import dataclass, replace

import numpy as np

__all__ = ["ArrayDataset", "load_dataset", "cv_split", "is_token_dataset",
           "IDX120"]

# reference data.py:154 — the fixed 120 ImageNet classes of reduced_imagenet
IDX120 = [
    16, 23, 52, 57, 76, 93, 95, 96, 99, 121, 122, 128, 148, 172, 181, 189,
    202, 210, 232, 238, 257, 258, 259, 277, 283, 289, 295, 304, 307, 318,
    322, 331, 337, 338, 345, 350, 361, 375, 376, 381, 388, 399, 401, 408,
    424, 431, 432, 440, 447, 462, 464, 472, 483, 497, 506, 512, 530, 541,
    553, 554, 557, 564, 570, 584, 612, 614, 619, 626, 631, 632, 650, 657,
    658, 660, 674, 675, 680, 682, 691, 695, 699, 711, 734, 736, 741, 754,
    757, 764, 769, 770, 780, 781, 787, 797, 799, 811, 822, 829, 830, 835,
    837, 842, 843, 845, 873, 883, 897, 900, 902, 905, 913, 920, 925, 937,
    938, 940, 941, 944, 949, 959,
]


@dataclass
class ArrayDataset:
    """In-memory image classification dataset: uint8 NHWC + int labels.

    For datasets too large for RAM (ImageNet), ``images`` may instead be
    an object array of file paths with ``lazy=True``; the pipeline then
    decodes per batch.

    A token data set (``tokens=True``) rides in the same two arrays, so
    that the batch iterators, the index matrices and the device cache
    take it as they stand: ``images`` is int32 ``[N, T + 1]`` ids (a
    sequence and the id that follows its last token), ``labels`` one
    unused zero a sequence (the targets are the ids shifted by one), and
    ``num_classes`` one more than the largest id present.
    """

    images: np.ndarray
    labels: np.ndarray
    num_classes: int
    lazy: bool = False
    tokens: bool = False

    def __len__(self):
        return len(self.labels)

    def subset(self, idx) -> "ArrayDataset":
        idx = np.asarray(idx)
        return replace(self, images=self.images[idx], labels=self.labels[idx])


def _stratified_split(labels, test_size: int | float, random_state: int = 0):
    """StratifiedShuffleSplit(n_splits=1).split equivalent, sklearn-exact."""
    from sklearn.model_selection import StratifiedShuffleSplit

    sss = StratifiedShuffleSplit(n_splits=1, test_size=test_size, random_state=random_state)
    return next(sss.split(np.zeros(len(labels)), labels))


def cv_split(labels, split: float, split_idx: int, random_state: int = 0):
    """The reference's CV machinery (``data.py:192-196``): 5 independent
    stratified shuffle resamples; take resample `split_idx`."""
    from sklearn.model_selection import StratifiedShuffleSplit

    sss = StratifiedShuffleSplit(n_splits=5, test_size=split, random_state=random_state)
    gen = sss.split(np.zeros(len(labels)), labels)
    for _ in range(split_idx + 1):
        train_idx, valid_idx = next(gen)
    return train_idx, valid_idx


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------


def _load_cifar(dataroot: str, kind: str):
    """CIFAR-10/100 python pickle batches -> uint8 NHWC arrays."""
    if kind == "cifar10":
        base = os.path.join(dataroot, "cifar-10-batches-py")
        train_files = [f"data_batch_{i}" for i in range(1, 6)]
        test_files = ["test_batch"]
        label_key = b"labels"
        num_classes = 10
    else:
        base = os.path.join(dataroot, "cifar-100-python")
        train_files = ["train"]
        test_files = ["test"]
        label_key = b"fine_labels"
        num_classes = 100

    def read(files):
        xs, ys = [], []
        for name in files:
            with open(os.path.join(base, name), "rb") as fh:
                d = pickle.load(fh, encoding="bytes")
            xs.append(d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1))
            ys.extend(d[label_key])
        return np.concatenate(xs).astype(np.uint8), np.asarray(ys, np.int32)

    train = read(train_files)
    test = read(test_files)
    return (
        ArrayDataset(train[0], train[1], num_classes),
        ArrayDataset(test[0], test[1], num_classes),
    )


def _load_svhn(dataroot: str, split: str) -> ArrayDataset:
    """SVHN .mat files; labels 10 -> 0 as torchvision does."""
    import scipy.io

    mat = scipy.io.loadmat(os.path.join(dataroot, f"{split}_32x32.mat"))
    images = np.transpose(mat["X"], (3, 0, 1, 2)).astype(np.uint8)
    labels = mat["y"].reshape(-1).astype(np.int32) % 10
    return ArrayDataset(images, labels, 10)


def _load_imagenet_listing(dataroot: str, split: str) -> ArrayDataset:
    """ImageNet as a folder of class dirs (ImageFolder layout); images stay
    on disk (lazy) and are decoded by the pipeline.  Uses a ``train_cls.txt``
    style listfile when present to skip the os.walk (the same fast path as
    reference ``imagenet.py:60-88``)."""
    root = os.path.join(dataroot, split)
    listfile = os.path.join(dataroot, f"{split}_cls.txt")
    paths, labels = [], []
    if os.path.exists(listfile):
        with open(listfile) as fh:
            lines = [ln.split() for ln in fh if ln.strip()]
        if lines and len(lines[0]) >= 3:
            # extended 3-token form: <relpath> <index> <label>
            for rel, _idx, lb in lines:
                paths.append(os.path.join(root, rel))
                labels.append(int(lb))
        else:
            # Kaggle CLS-LOC form (what the reference's train_cls.txt
            # is, imagenet.py:60-88): <wnid>/<stem> <index>; label is
            # the sorted-wnid rank, extensionless stems get .JPEG
            rels = [ln[0] for ln in lines]
            flat = [r for r in rels if "/" not in r]
            if flat:
                raise ValueError(
                    f"{listfile}: {len(flat)} entries lack a '<wnid>/' "
                    "directory prefix (e.g. a flat val listing) — labels "
                    "cannot be derived; reorganize with "
                    "tools/prepare_imagenet.py val-reorg and regenerate, "
                    "or use the 3-token '<relpath> <index> <label>' form"
                )
            class_to_idx = {
                w: i for i, w in enumerate(sorted({r.split("/")[0] for r in rels}))
            }
            for rel in rels:
                if not os.path.splitext(rel)[1]:
                    rel += ".JPEG"
                paths.append(os.path.join(root, rel))
                labels.append(class_to_idx[rel.split("/")[0]])
    else:
        classes = sorted(
            d for d in os.listdir(root) if os.path.isdir(os.path.join(root, d))
        )
        for lb, cls in enumerate(classes):
            cdir = os.path.join(root, cls)
            for name in sorted(os.listdir(cdir)):
                paths.append(os.path.join(cdir, name))
                labels.append(lb)
    return ArrayDataset(
        np.asarray(paths, object), np.asarray(labels, np.int32), 1000, lazy=True
    )


def _synthetic_shapes(n_train: int = 600, n_test: int = 2000, size: int = 32,
                      noise: float = 12.0, fg_lo: float = 60.0,
                      fg_hi: float = 130.0, max_rot: float = 0.0,
                      scale_lo: float = 1.0, scale_hi: float = 1.0):
    """Structured 10-class glyph dataset for end-to-end search validation.

    Each class is a fixed 12x12 binary glyph; every sample renders it at
    a random position with random foreground/background intensity,
    contrast and pixel noise.  Train is deliberately SMALL (60/class) so
    an unaugmented model overfits and label-preserving augmentation
    (translation, brightness/contrast, cutout — all in the search's op
    vocabulary) measurably improves test accuracy.  Deterministic; i.i.d.
    train/test, so any phase-3 gain is pure regularization, not a
    distribution-shift trick.

    The ``synthetic_shapes_hard`` registry variant shrinks train to 150
    samples (~15/class, binomial across classes) with render parameters
    unchanged — measured to leave ~5% test headroom for the searched
    policies, where a default-aug WRN-10-1 saturates the 600-sample
    variant at 100% test.  The `noise`/`fg_lo`/`fg_hi` knobs grade
    difficulty further (lower glyph contrast or a higher noise floor
    make the task unlearnably hard well before 15/class does).

    `max_rot` (degrees) / `scale_lo..scale_hi` add per-sample POSE
    variation (the ``synthetic_shapes_pose*`` variants): unlike
    position, pose is NOT covered by the default crop+flip transform
    stack, so a small train set undersamples it and the model can only
    recover the invariance through augmentation — the regime where the
    reference's searched policies (Rotate/Shear/Translate live in the
    op vocabulary) genuinely pay, and the round-3 e2e validation's
    fix for default-aug saturating the position-only task at
    convergence (docs/search_postmortem_r2.md).
    """
    glyph_rng = np.random.default_rng(7)
    glyphs = (glyph_rng.uniform(size=(10, 12, 12)) < 0.45).astype(np.float32)

    def render(n, seed):
        rng = np.random.default_rng(seed)
        labels = rng.integers(0, 10, n).astype(np.int32)
        images = np.empty((n, size, size, 3), np.uint8)
        for i, lb in enumerate(labels):
            bg = rng.uniform(30, 120)
            fg = bg + rng.uniform(fg_lo, fg_hi)
            contrast = rng.uniform(0.7, 1.3)
            glyph = glyphs[lb]
            if max_rot or scale_lo != 1.0 or scale_hi != 1.0:
                # inverse-map affine (nearest): rotate by theta, scale s
                theta = np.deg2rad(rng.uniform(-max_rot, max_rot))
                s = rng.uniform(scale_lo, scale_hi)
                g = 12
                co, si = np.cos(theta), np.sin(theta)
                # canvas sized to the rotated bounding box (+2 guard px):
                # a fixed round(g*s)+4 clips glyph corners at high
                # rotation x scale, eroding label signal (ADVICE r3)
                out_px = int(np.ceil(g * max(s, 1.0) * (abs(co) + abs(si)))) + 2
                yy, xx = np.mgrid[0:out_px, 0:out_px].astype(np.float32)
                cy = cx = (out_px - 1) / 2.0
                ys = (co * (yy - cy) + si * (xx - cx)) / s + (g - 1) / 2.0
                xs = (-si * (yy - cy) + co * (xx - cx)) / s + (g - 1) / 2.0
                yi = np.clip(np.round(ys).astype(int), 0, g - 1)
                xi = np.clip(np.round(xs).astype(int), 0, g - 1)
                inside = (ys >= -0.5) & (ys <= g - 0.5) & (xs >= -0.5) & (xs <= g - 0.5)
                glyph = np.where(inside, glyphs[lb][yi, xi], 0.0).astype(np.float32)
            gh, gw = glyph.shape
            canvas = np.full((size, size), bg, np.float32)
            y = rng.integers(0, max(size - gh, 1))
            x = rng.integers(0, max(size - gw, 1))
            canvas[y:y + gh, x:x + gw] += glyph * (fg - bg)
            canvas = (canvas - canvas.mean()) * contrast + canvas.mean()
            canvas = canvas + rng.normal(0, noise, (size, size))
            images[i] = np.clip(canvas, 0, 255)[..., None].astype(np.uint8)
        return ArrayDataset(images, labels, 10)

    return render(n_train, 1), render(n_test, 2)


def is_token_dataset(dataset: str) -> bool:
    """Token data sets are named ``tokens`` (``<dataroot>/tokens/*.npy``)
    or ``synthetic_tokens`` (seeded, for tests)."""
    return dataset in ("tokens", "synthetic_tokens")


def _token_dataset(ids: np.ndarray) -> ArrayDataset:
    ids = np.ascontiguousarray(ids, np.int32)
    if ids.ndim != 2 or ids.shape[1] < 2 or ids.min() < 0:
        raise ValueError(f"a token data set is non-negative ids [N, T + 1] "
                         f"with T >= 1; got {ids.shape}")
    return ArrayDataset(ids, np.zeros(len(ids), np.int32),
                        int(ids.max()) + 1, tokens=True)


def _load_tokens(dataroot: str):
    """``<dataroot>/tokens/train.npy`` and ``test.npy``: int32 ids
    ``[N, T + 1]``, as a tokenizer's packed output would be saved."""
    base = os.path.join(dataroot, "tokens")
    return tuple(_token_dataset(np.load(os.path.join(base, f"{split}.npy")))
                 for split in ("train", "test"))


def _synthetic_tokens(vocab: int = 64, length: int = 32, n_train: int = 32,
                      n_test: int = 8):
    """A first-order Markov chain in which an id is followed by one of
    four fixed ids: learnable, so a few steps bring the loss down."""
    rng = np.random.default_rng(0)
    successors = rng.integers(0, vocab, (vocab, 4))

    def draw(n):
        ids = np.empty((n, length + 1), np.int32)
        ids[:, 0] = rng.integers(0, vocab, n)
        for t in range(length):
            ids[:, t + 1] = successors[ids[:, t], rng.integers(0, 4, n)]
        return _token_dataset(ids)

    return draw(n_train), draw(n_test)


def _synthetic(num_classes: int, n_train: int = 512, n_test: int = 256, size: int = 32):
    rng = np.random.default_rng(0)
    mk = lambda n: ArrayDataset(
        rng.integers(0, 256, (n, size, size, 3), dtype=np.uint8),
        rng.integers(0, num_classes, (n,), dtype=np.int32),
        num_classes,
    )
    return mk(n_train), mk(n_test)


# ---------------------------------------------------------------------------
# dataset registry (reference data.py:114-185)
# ---------------------------------------------------------------------------


def load_dataset(dataset: str, dataroot: str):
    """Return (total_trainset, testset) for a dataset name, applying the
    reference's reduction rules."""
    if dataset == "tokens":
        return _load_tokens(dataroot)
    if dataset == "synthetic_tokens":
        return _synthetic_tokens()
    if dataset == "cifar10":
        return _load_cifar(dataroot, "cifar10")
    if dataset == "cifar100":
        return _load_cifar(dataroot, "cifar100")
    if dataset == "reduced_cifar10":
        train, test = _load_cifar(dataroot, "cifar10")
        train_idx, _ = _stratified_split(train.labels, test_size=46000)  # 4000 kept
        return train.subset(train_idx), test
    if dataset == "svhn":
        train = _load_svhn(dataroot, "train")
        extra = _load_svhn(dataroot, "extra")
        merged = ArrayDataset(
            np.concatenate([train.images, extra.images]),
            np.concatenate([train.labels, extra.labels]),
            10,
        )
        return merged, _load_svhn(dataroot, "test")
    if dataset == "reduced_svhn":
        train = _load_svhn(dataroot, "train")
        train_idx, _ = _stratified_split(train.labels, test_size=73257 - 1000)  # 1000 kept
        return train.subset(train_idx), _load_svhn(dataroot, "test")
    if dataset == "imagenet":
        return (
            _load_imagenet_listing(dataroot, "train"),
            _load_imagenet_listing(dataroot, "val"),
        )
    if dataset == "reduced_imagenet":
        train = _load_imagenet_listing(dataroot, "train")
        test = _load_imagenet_listing(dataroot, "val")
        train_idx, _ = _stratified_split(train.labels, test_size=len(train) - 50000)
        keep = np.isin(train.labels[train_idx], IDX120)
        train_idx = np.asarray(train_idx)[keep]
        remap = {cls: i for i, cls in enumerate(IDX120)}
        train = train.subset(train_idx)
        train = ArrayDataset(
            train.images,
            np.asarray([remap[int(l)] for l in train.labels], np.int32),
            120,
            lazy=True,
        )
        tkeep = np.isin(test.labels, IDX120)
        test = test.subset(np.nonzero(tkeep)[0])
        test = ArrayDataset(
            test.images,
            np.asarray([remap[int(l)] for l in test.labels], np.int32),
            120,
            lazy=True,
        )
        return train, test
    if dataset == "cifar10.1":
        # CIFAR-10.1 v6 (Recht et al.) distribution-shift TEST set paired
        # with the standard CIFAR-10 train set; numpy files from the
        # released dataset (cifar10.1_v6_{data,labels}.npy)
        train, _ = _load_cifar(dataroot, "cifar10")
        data = np.load(os.path.join(dataroot, "cifar10.1_v6_data.npy"))
        labels = np.load(os.path.join(dataroot, "cifar10.1_v6_labels.npy"))
        return train, ArrayDataset(data.astype(np.uint8), labels.astype(np.int32), 10)
    if dataset == "synthetic_shapes":
        # structured glyph task for end-to-end search validation
        return _synthetic_shapes()
    if dataset == "synthetic_shapes_hard":
        # 15 samples/class (render params unchanged) — leaves measured
        # test-accuracy headroom for searched policies
        return _synthetic_shapes(n_train=150)
    if dataset.startswith("synthetic_shapes_n"):
        # parametrized train-set size (synthetic_shapes_n120 -> 120
        # samples, render unchanged): the difficulty dial for grading
        # search-validation headroom (docs/search_postmortem_r2.md #4)
        return _synthetic_shapes(n_train=int(dataset.rsplit("n", 1)[1]))
    if dataset.startswith("synthetic_shapes_pose"):
        # pose-varying variant (rotation +-25deg, scale 0.7-1.3) with a
        # parametrized train size (synthetic_shapes_pose200 -> 200):
        # pose is the one variation default crop+flip cannot cover, so
        # augmentation (Rotate/Shear/Translate in the op vocabulary) is
        # the only route to the invariance at small n
        suffix = dataset[len("synthetic_shapes_pose"):]
        return _synthetic_shapes(
            n_train=int(suffix) if suffix else 200,
            max_rot=25.0, scale_lo=0.7, scale_hi=1.3,
        )
    if dataset.startswith("synthetic"):
        # synthetic / synthetic_cifar100-style names for tests and benches
        num_classes = 100 if dataset.endswith("100") else 10
        return _synthetic(num_classes)
    raise ValueError(f"invalid dataset name {dataset!r}")
