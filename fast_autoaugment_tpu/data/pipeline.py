"""Host-side batching and device feed.

Replaces the reference's torch DataLoader pool (8 worker processes per
GPU doing PIL augmentation, ``data.py:214-224``).  Because augmentation
runs on device here, the host work is only: shuffle indices, slice
uint8 arrays, (for lazy datasets) decode JPEGs, and hand batches to the
mesh.  JAX's async dispatch overlaps the next batch's host work with
the current step's device work; an optional background thread deepens
the pipeline to keep the TPU fed.

Semantics parity (``data.py:205-224``): the train iterator reshuffles
every epoch from a deterministic per-epoch seed (the analog of
``DistributedSampler.set_epoch``, ``train.py:251-252``), drops the last
partial batch (``drop_last=True``), and in multi-host mode each process
takes its own contiguous shard of every global batch.  Valid/test
iterate deterministically without dropping.
"""

from __future__ import annotations

import functools
import os
import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.data.datasets import ArrayDataset

__all__ = ["BatchIterator", "DeviceCache", "StoredRows", "train_batches",
           "stacked_train_batches", "eval_batches", "prefetch",
           "train_index_matrix", "stacked_index_matrix",
           "resolve_device_cache", "split_dispatch_chunks"]


def _decode(paths: np.ndarray, transform=None, size: int | None = None) -> np.ndarray:
    """Decode a batch of image files to uint8 NHWC (lazy datasets).

    `transform(pil_image) -> np.uint8 [S, S, 3]` handles per-image
    host-side geometry (e.g. the ImageNet random/center crop + bicubic
    resize); without one, a plain bicubic resize to `size` is applied.
    """
    import PIL.Image

    out = []
    for p in paths:
        img = PIL.Image.open(p).convert("RGB")
        if transform is not None:
            out.append(transform(img))
        else:
            if size is not None:
                img = img.resize((size, size), PIL.Image.BICUBIC)
            out.append(np.asarray(img, np.uint8))
    return np.stack(out)


class SizeCache:
    """Lazy per-path (width, height) cache for boxed decoding."""

    def __init__(self):
        self._sizes: dict = {}

    def get(self, path) -> tuple[int, int]:
        got = self._sizes.get(path)
        if got is None:
            from fast_autoaugment_tpu.data import native_loader

            got = native_loader.image_size(path)
            if got is None:
                import PIL.Image

                with PIL.Image.open(path) as img:  # header-only read
                    got = img.size
            self._sizes[path] = got
        return got


def _draw_boxes(paths, box_fn, rng, size_cache: SizeCache) -> np.ndarray:
    """One crop box a path, drawn in order from `rng` (float32 [N, 4]).
    Reads image headers (cached), never pixels: a resumed epoch replays
    the draws of the batches it skips at this cost alone."""
    boxes = np.empty((len(paths), 4), np.float32)
    for i, p in enumerate(paths):
        w, h = size_cache.get(p)
        boxes[i] = box_fn(rng, w, h)
    return boxes


@functools.cache
def _pil_threads() -> ThreadPoolExecutor:
    """The PIL decode pool, as wide as the native loader's (made once:
    a pool a batch would pay the thread starts 10,009 times an epoch)."""
    return ThreadPoolExecutor(max_workers=min(16, os.cpu_count() or 1),
                              thread_name_prefix="faa-pil-decode")


def _pil_decode_one(path, box, imgsize: int) -> np.ndarray:
    import PIL.Image

    img = PIL.Image.open(path).convert("RGB")
    img = img.crop(tuple(box)).resize((imgsize, imgsize), PIL.Image.BICUBIC)
    return np.asarray(img, np.uint8)


def _decode_boxes(paths, imgsize: int, boxes: np.ndarray) -> np.ndarray:
    """Decode + crop(boxes) + resize a batch.

    Uses the native C++ loader (one threaded pass: libjpeg decode, crop,
    triangle resample) when built; falls back to PIL (bicubic, the
    golden-parity path) on a thread pool — PIL releases the GIL inside
    decode and resize, and ``map`` keeps the order, so the batch is the
    serial loop's byte for byte.  The call's wall time and image count
    go to ``faa_decode_seconds_total{decoder}`` / ``faa_decode_images_total``.
    """
    from fast_autoaugment_tpu.data import native_loader

    t0 = telemetry.mono()
    if native_loader.available():
        decoder = "native"
        batch, failures = native_loader.decode_resize_batch(paths, imgsize, boxes)
        if failures:
            import logging

            logging.getLogger("faa_tpu.data").warning(
                "native loader: %d/%d images failed to decode (zero-filled)",
                failures, len(paths),
            )
    else:
        decoder = "pil"
        batch = np.stack(list(_pil_threads().map(
            _pil_decode_one, paths, boxes, [imgsize] * len(paths))))
    reg = telemetry.registry()
    reg.counter("faa_decode_seconds_total",
                "wall seconds the feed spent decoding, cropping and "
                "resizing batches", decoder=decoder).inc(
                    telemetry.mono() - t0)
    reg.counter("faa_decode_images_total",
                "images the feed decoded, cropped and resized").inc(len(paths))
    return batch


def _decode_boxed(paths, imgsize: int, box_fn, rng, size_cache: SizeCache) -> np.ndarray:
    """Draw a crop box a path (`box_fn(rng, width, height) -> (x0, y0,
    x1, y1)`, in order), then decode, crop and resize the batch."""
    return _decode_boxes(paths, imgsize,
                         _draw_boxes(paths, box_fn, rng, size_cache))


def train_index_matrix(
    indices: np.ndarray,
    global_batch: int,
    epoch: int,
    *,
    seed: int = 0,
    process_index: int = 0,
    process_count: int = 1,
    rng: np.random.Generator | None = None,
) -> np.ndarray:
    """The epoch's batch composition as an int64 ``[steps, shard]``
    matrix: the SAME ``default_rng((seed, epoch))`` permutation,
    drop-last step count and per-process contiguous shard that
    :func:`train_batches` walks — it IS what train_batches walks (the
    iterator consumes this matrix), so the device-cache path's in-program
    gathers are identical-by-construction to the host path's fancy
    indexing.  `rng` lets a caller that needs the post-permutation
    stream (lazy decode) hand in the generator to consume from.
    """
    if rng is None:
        rng = np.random.default_rng((seed, epoch))
    idx = rng.permutation(np.asarray(indices))
    steps = len(idx) // global_batch
    shard = global_batch // process_count
    mat = idx[:steps * global_batch].reshape(steps, global_batch)
    return mat[:, process_index * shard:(process_index + 1) * shard]


def train_batches(
    dataset: ArrayDataset,
    indices: np.ndarray | None,
    global_batch: int,
    epoch: int,
    *,
    seed: int = 0,
    process_index: int = 0,
    process_count: int = 1,
    decode_size: int | None = None,
    host_transform=None,
    box_fn=None,
    imgsize: int | None = None,
    size_cache: "SizeCache | None" = None,
    skip: int = 0,
) -> Iterator[tuple[np.ndarray, np.ndarray]]:
    """Shuffled, drop-last train batches for one epoch.

    `indices` restricts to a subset (CV fold); each process yields its
    [process_index] shard of every global batch, so all hosts stay in
    step for the pjit'd global-batch train step.  For lazy datasets,
    either `box_fn(rng, w, h) -> crop box` + `imgsize` (native-loader
    fast path) or `host_transform(pil_image, rng) -> uint8 array` runs
    per image.

    `skip` drops the epoch's first `skip` batches (a mid-epoch resume)
    and leaves the rest as an unbroken epoch yields them: the crop
    boxes of a skipped batch are still drawn, from headers alone, so
    the random stream stands where it stood; only a `host_transform`
    feed, whose draws need the pixels, decodes what it skips.
    """
    idx = np.arange(len(dataset)) if indices is None else np.asarray(indices)
    rng = np.random.default_rng((seed, epoch))
    mat = train_index_matrix(
        idx, global_batch, epoch, seed=seed, process_index=process_index,
        process_count=process_count, rng=rng,
    )
    transform = None
    if host_transform is not None:
        transform = lambda img: host_transform(img, rng)  # noqa: E731
    size_cache = size_cache or SizeCache()
    for bi, chunk in enumerate(mat):
        skipped = bi < skip
        if skipped and not dataset.lazy:
            continue
        images = dataset.images[chunk]
        if dataset.lazy:
            if box_fn is not None:
                boxes = _draw_boxes(images, box_fn, rng, size_cache)
                if skipped:
                    continue
                images = _decode_boxes(images, imgsize, boxes)
            else:
                images = _decode(images, transform, decode_size)
                if skipped:
                    continue
        yield images, dataset.labels[chunk]


def stacked_index_matrix(
    fold_indices: list,
    global_batch: int,
    epoch: int,
    *,
    seeds: list,
    process_index: int = 0,
    process_count: int = 1,
) -> tuple[np.ndarray, np.ndarray]:
    """The multiplexed per-fold batch composition for one epoch:
    ``(chunks [steps, K, shard] int64, active [steps, K] float32)``.

    Fold k's row stream is exactly :func:`train_index_matrix` for
    ``(fold_indices[k], seeds[k])``; exhausted lanes (shorter folds)
    carry wrapped filler indices with ``active=0`` so the stacked shape
    never changes.  :func:`stacked_train_batches` consumes this matrix,
    and the fold-stacked device-cache path ships it instead of images —
    one source of truth for batch composition on both feed paths.
    """
    num_folds = len(fold_indices)
    if len(seeds) != num_folds:
        raise ValueError(f"{len(seeds)} seeds for {num_folds} folds")
    perms, steps = [], []
    for k in range(num_folds):
        idx = np.asarray(fold_indices[k])
        perms.append(np.random.default_rng((seeds[k], epoch)).permutation(idx))
        steps.append(len(idx) // global_batch)
    shard = global_batch // process_count
    total = max(steps, default=0)
    all_chunks = np.empty((total, num_folds, shard), np.int64)
    all_active = np.empty((total, num_folds), np.float32)
    for s in range(total):
        for k in range(num_folds):
            if s < steps[k]:
                chunk = perms[k][s * global_batch:(s + 1) * global_batch]
            else:  # exhausted lane: wrapped filler, masked out by `active`
                chunk = np.resize(perms[k], global_batch)
            all_chunks[s, k] = chunk[process_index * shard:
                                     (process_index + 1) * shard]
            all_active[s, k] = 1.0 if s < steps[k] else 0.0
    return all_chunks, all_active


def stacked_train_batches(
    dataset: ArrayDataset,
    fold_indices: list,
    global_batch: int,
    epoch: int,
    *,
    seeds: list,
    process_index: int = 0,
    process_count: int = 1,
    decode_size: int | None = None,
    host_transform=None,
    box_fn=None,
    imgsize: int | None = None,
    size_cache: "SizeCache | None" = None,
) -> Iterator[tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Multiplexed per-fold train feed for the fold-stacked trainer.

    Yields ``(images [K, S, H, W, C], labels [K, S], active [K])`` per
    step, where fold k's index stream is EXACTLY what
    :func:`train_batches` would yield for ``(fold_indices[k],
    seeds[k])`` — the same ``default_rng((seed, epoch))`` permutation,
    the same drop-last step count, the same per-process contiguous
    shard — so stacked training consumes bit-identical per-fold batches.
    The K folds share the underlying dataset; only the cv-split index
    sets differ, so for in-memory datasets the whole multiplex is one
    fancy-gather per step and nothing is ever copied per fold on the
    host beyond the gathered batch itself.

    Folds whose epoch is exhausted (shorter index sets) go
    ``active=0``: their lane repeats wrapped filler indices so the
    stacked shape never changes (one executable per shape downstream),
    and the consumer masks the lane out.

    Lazy (on-disk) datasets decode the per-step UNION of the K fold
    chunks once — an image drawn by several folds in the same step
    decodes a single time.  Deliberate deviation from per-fold
    sequential decoding: the decode rng is a fresh per-epoch stream
    rather than fold k's private stream, so host-side random crop boxes
    are equally-distributed fresh draws, not bit-identical ones (the
    device-side augmentation keys are unaffected; they ride with the
    train step).  The stacked phase-1 driver path gates lazy datasets
    out for exactly this reason.
    """
    num_folds = len(fold_indices)
    if len(seeds) != num_folds:
        raise ValueError(f"{len(seeds)} seeds for {num_folds} folds")
    rng = np.random.default_rng((int(seeds[0]), epoch, 971))  # lazy decode only
    all_chunks, all_active = stacked_index_matrix(
        fold_indices, global_batch, epoch, seeds=seeds,
        process_index=process_index, process_count=process_count,
    )
    transform = None
    if host_transform is not None:
        transform = lambda img: host_transform(img, rng)  # noqa: E731
    for chunks, active in zip(all_chunks, all_active):
        if dataset.lazy:
            flat_paths = dataset.images[chunks.reshape(-1)]
            uniq, inverse = np.unique(flat_paths, return_inverse=True)
            if box_fn is not None:
                decoded = _decode_boxed(uniq, imgsize, box_fn, rng,
                                        size_cache or SizeCache())
            else:
                decoded = _decode(uniq, transform, decode_size)
            images = decoded[inverse].reshape(chunks.shape + decoded.shape[1:])
        else:
            images = dataset.images[chunks]
        yield images, dataset.labels[chunks], active


@jax.tree_util.register_pytree_node_class
class StoredRows:
    """A data set's examples as the device cache keeps them: one
    contiguous row an example, ``[N, row]``, and the shape a taken
    example is given back.

    The example axis stays first (and stays the sharded axis).  An array
    of rank above 2 is flattened behind it, because a batch gather reads
    whole rows: ``uint8 [N, 32, 32, 3]`` as the chip tiles it (the
    example axis minor-most, a last dimension of 3 being no lane's
    worth) cannot be gathered from, and XLA:TPU laid all ``N`` examples
    out again in front of every step's gather to hand ``B`` of them on
    (docs/PARITY.md "Step dispatch & device cache").  Rank 1 and 2
    (labels, token ids ``[N, T+1]``) are rows already and stay as they
    are.  A pytree whose one leaf is ``rows``: it crosses ``jit`` as the
    array does, and the program is keyed on the example shape.
    """

    def __init__(self, rows, example_shape: tuple[int, ...]):
        self.rows = rows
        self.example_shape = tuple(example_shape)

    @classmethod
    def of(cls, examples: np.ndarray) -> "StoredRows":
        """The stored form of a host array ``[N, ...]``: a view, no copy."""
        examples = np.ascontiguousarray(examples)
        rows = examples if examples.ndim <= 2 else examples.reshape(
            examples.shape[0], -1)
        return cls(rows, examples.shape[1:])

    shape = property(lambda self: self.rows.shape)
    dtype = property(lambda self: self.rows.dtype)

    def take(self, idx):
        """``examples[idx]`` exactly: ``idx [...]`` ->
        ``[..., *example_shape]``.  Only the rows taken are read, and
        only they are given a shape again."""
        taken = jnp.take(self.rows, idx, axis=0)
        return taken.reshape(idx.shape + self.example_shape)

    def tree_flatten(self):
        return (self.rows,), self.example_shape

    @classmethod
    def tree_unflatten(cls, example_shape, children):
        return cls(children[0], example_shape)


class DeviceCache:
    """Device-resident dataset: the whole uint8 image array (as
    :class:`StoredRows`, one row an image) plus labels uploaded ONCE,
    example axis sharded over the mesh's data axis
    (``parallel.mesh.place_dataset``).

    The training inner loop then never ships images: the per-epoch
    shuffled order is still computed on host with the identical
    ``default_rng((seed, epoch))`` permutation (:func:`train_index_matrix`
    / :func:`stacked_index_matrix` — the same matrices the host iterators
    walk), but only the int32 index matrix crosses to the device, and the
    compiled train program takes each batch from the resident copy
    (``images.take`` inside ``train.steps.make_multistep_train_step``).
    This is the training-side twin of the search path's
    upload-once/replay-batches discipline (``search/tta.py::eval_tta``).

    Eager (in-memory) datasets only: a lazy dataset has nothing resident
    to gather from — ``resolve_device_cache`` gates it off.  HBM cost is
    the raw uint8 array (CIFAR-10 train: 50000*32*32*3 = 146 MiB; see
    docs/PARITY.md "Step dispatch & device cache" for the budget
    math), divided across the data-axis shards.
    """

    def __init__(self, dataset: ArrayDataset, mesh, axis_name: str = "data"):
        if dataset.lazy:
            raise ValueError(
                "DeviceCache needs an in-memory dataset; lazy (on-disk) "
                "datasets keep the host prefetch path")
        from fast_autoaugment_tpu.parallel.mesh import place_dataset

        stored = StoredRows.of(dataset.images)
        labels = np.ascontiguousarray(dataset.labels)
        self.num_examples = len(dataset)
        self.nbytes = int(stored.rows.nbytes + labels.nbytes)
        rows, self.labels = place_dataset(mesh, stored.rows, labels, axis_name)
        self.images = StoredRows(rows, stored.example_shape)
        self.mesh = mesh


def resolve_device_cache(mode, dataset: ArrayDataset, *,
                         process_count: int = 1) -> bool:
    """Resolve the ``--device-cache {auto,on,off}`` knob to a bool.

    ``"auto"`` (default) enables the device-resident path exactly when
    it is a pure win with unchanged semantics: an eager (in-memory)
    dataset on a single-process runtime.  Lazy datasets force it off
    (nothing resident to gather from — the prefetch/decode path stays),
    as does multi-host (per-process index shards feed
    ``make_array_from_process_local_data``-placed batches today; the
    cache path does not reimplement that assembly).  ``"on"`` is an
    explicit ask and RAISES where auto would silently fall back, so a
    launch script cannot believe it cached what it streamed.
    """
    if mode in (False, None, 0, "off", "0"):
        return False
    if mode in (True, 1, "on"):
        if dataset.lazy:
            raise ValueError(
                "--device-cache on: dataset is lazy (on-disk) — the "
                "device cache only serves in-memory datasets; use "
                "--device-cache auto/off")
        if process_count > 1:
            raise ValueError(
                "--device-cache on: multi-host runs keep the per-process "
                "host feed; use --device-cache auto/off")
        return True
    if mode == "auto":
        return not dataset.lazy and process_count == 1
    raise ValueError(f"unknown device-cache mode {mode!r}: use auto/on/off")


def split_dispatch_chunks(total_steps: int, steps_per_dispatch: int) -> list[int]:
    """Split an epoch's step count into per-dispatch chunk sizes.

    Full chunks of ``steps_per_dispatch`` plus one clamped remainder
    chunk, so checkpoint cadence, per-epoch eval and resume always land
    on dispatch boundaries and an epoch compiles at most two program
    shapes (N and ``total % N``), each reused every epoch."""
    if steps_per_dispatch < 1:
        raise ValueError(
            f"steps_per_dispatch must be >= 1, got {steps_per_dispatch}")
    full, rem = divmod(total_steps, steps_per_dispatch)
    return [steps_per_dispatch] * full + ([rem] if rem else [])


def eval_batches(
    dataset: ArrayDataset,
    indices: np.ndarray | None,
    batch: int,
    *,
    process_index: int = 0,
    process_count: int = 1,
    pad_multiple: int = 1,
    decode_size: int | None = None,
    host_transform=None,
    box_fn=None,
    imgsize: int | None = None,
    size_cache: "SizeCache | None" = None,
) -> Iterator[tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Deterministic eval batches (SubsetSampler semantics,
    ``data.py:348-362``); final partial batch kept, padded by repeating
    its last sample so the global batch divides `pad_multiple` (pass the
    mesh size) and `process_count`; `mask` is 1.0 for real samples.

    Yields ``(images, labels, mask)``.  Multi-host: each process decodes
    and yields only its [process_index] contiguous shard of every global
    batch — eval work is sharded across hosts exactly like
    `train_batches`, not duplicated per host.
    """
    idx = np.arange(len(dataset)) if indices is None else np.asarray(indices)
    rng = np.random.default_rng(0)  # eval box_fns ignore the rng
    multiple = int(np.lcm(max(1, pad_multiple), max(1, process_count)))
    for s in range(0, len(idx), batch):
        chunk = idx[s:s + batch]
        n = len(chunk)
        pad = (-n) % multiple
        mask = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
        if pad:
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad)])
        shard = len(chunk) // process_count
        lo = process_index * shard
        chunk, mask = chunk[lo:lo + shard], mask[lo:lo + shard]
        images = dataset.images[chunk]
        if dataset.lazy:
            if box_fn is not None:
                images = _decode_boxed(images, imgsize, box_fn, rng,
                                       size_cache or SizeCache())
            else:
                images = _decode(images, host_transform, decode_size)
        yield images, dataset.labels[chunk], mask


def num_train_steps(n_examples: int, global_batch: int) -> int:
    return n_examples // global_batch


def default_prefetch_depth() -> int:
    """Measured-default queue depth: on a single-core host the worker
    and consumer fight over the one CPU, so any depth beyond 1 only
    adds queue contention (237 img/s at depth 1 vs ~180 at depth 2-8
    on a CPU host's decode); with >=2 cores, 2 buffers the decode
    burst while the consumer dispatches the previous batch."""
    return 1 if (os.cpu_count() or 1) < 2 else 2


#: a wait on the feed shorter than this leaves no ``feed_wait`` span
FEED_WAIT_SPAN_SEC = 1e-3


def _feed_meter():
    """``delivered(t0)`` for :func:`prefetch`'s consumer side: one batch
    handed over, after being blocked since ``t0`` (:func:`telemetry.mono`)
    — ``faa_feed_batches_total`` and ``faa_feed_wait_seconds_total``
    always, a ``feed_wait`` span where the wait passed a millisecond."""
    reg = telemetry.registry()
    batches = reg.counter("faa_feed_batches_total",
                          "batches the prefetch feed handed its consumer")
    waited = reg.counter("faa_feed_wait_seconds_total",
                         "seconds a consumer was blocked in next() on "
                         "the prefetch feed")

    def delivered(t0: float) -> None:
        t1 = telemetry.mono()
        batches.inc()
        waited.inc(t1 - t0)
        if t1 - t0 > FEED_WAIT_SPAN_SEC:
            telemetry.record_dispatch("feed_wait", t0, t1)

    return delivered


def prefetch(iterator, depth: int | None = None, transform=None):
    """Run `iterator` in a background thread with a bounded queue —
    double-buffered host -> device feed.  `depth=None` uses
    :func:`default_prefetch_depth` (cpu-count gated; its docstring has
    the measurement).

    ``depth=0`` (or ``FAA_PREFETCH_SYNC=1`` for default-depth callers —
    an explicit depth always wins) degrades to a synchronous inline
    loop — no worker thread.  The test suite sets the env var: on the
    virtual 8-device CPU mesh the worker's `jax.device_put` races the
    consumer's dispatch inside the CPU PJRT client and intermittently
    SIGABRTs the process (observed round 3, twice, same two-thread
    signature); on a single-core host the thread buys no overlap
    anyway.  The TPU production path keeps the async worker.

    `transform(item)` runs in the WORKER thread; passing the mesh's
    `shard_transform` here starts the host->device copy off the
    consumer's critical path, so the transfer overlaps the current
    step's device work instead of serializing with step dispatch (JAX
    dispatch is thread-safe; the copy lands on the same device stream
    either way).

    Abandoning the generator early (``break``, or an exception in the
    consumer) closes it and stops the worker: every queue put waits in
    bounded slices against a stop event, so the thread never blocks
    forever holding buffered batches — with a device-put transform
    those would be TPU HBM, not just host arrays.

    Every batch handed over counts in ``faa_feed_batches_total``, and
    the time the consumer was blocked in ``next()`` for it in
    ``faa_feed_wait_seconds_total`` (:func:`_feed_meter`): what a step
    waits on the host, where the device's idle time says only that it
    waited.
    """
    if depth is None:
        # env override applies only to default-depth callers — an
        # explicit depth is an explicit choice; "0"/"" mean unset
        if os.environ.get("FAA_PREFETCH_SYNC", "0") not in ("", "0"):
            depth = 0
        else:
            depth = default_prefetch_depth()
    delivered = _feed_meter()
    if depth == 0:
        # NOTE prefetch is a generator function (the async path below
        # yields): the sync path must yield inline, not return a
        # sub-generator
        source = iter(iterator)
        while True:
            t0 = telemetry.mono()
            try:
                item = next(source)
            except StopIteration:
                return
            if transform is not None:
                item = transform(item)
            delivered(t0)
            yield item
    q: queue.Queue = queue.Queue(maxsize=depth)
    _END = object()
    stop = threading.Event()
    err: list[BaseException] = []

    def put(obj) -> bool:
        while not stop.is_set():
            try:
                q.put(obj, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for item in iterator:
                if not put(item if transform is None else transform(item)):
                    return
        except BaseException as e:  # propagate into the consumer
            err.append(e)
        finally:
            # close the SOURCE generator from the worker (its owning
            # thread): an abandoned consumer otherwise leaves the
            # source suspended until GC, holding its resources open
            close = getattr(iterator, "close", None)
            if close is not None:
                try:
                    close()
                except BaseException as e:  # noqa: BLE001
                    if not err:
                        err.append(e)
            put(_END)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        while True:
            t0 = telemetry.mono()
            while True:
                try:
                    item = q.get(timeout=5.0)
                    break
                except queue.Empty:
                    # bounded wait (lint R9): if the worker died without
                    # delivering its _END sentinel (e.g. killed hard), an
                    # untimed get would park the consumer forever; drained
                    # items always win over the liveness verdict
                    if t.is_alive():
                        continue
                    if err:
                        raise err[0]
                    return
            if item is _END:
                if err:
                    raise err[0]
                return
            delivered(t0)
            yield item
    finally:
        stop.set()


class BatchIterator:
    """Convenience wrapper bundling a dataset + fold indices + host-side
    crop semantics (used only for lazy, on-disk datasets)."""

    def __init__(self, dataset: ArrayDataset, indices=None, decode_size=None,
                 train_transform=None, eval_transform=None,
                 train_box_fn=None, eval_box_fn=None, imgsize=None):
        self.dataset = dataset
        self.indices = indices
        self.decode_size = decode_size
        self.train_transform = train_transform
        self.eval_transform = eval_transform
        self.train_box_fn = train_box_fn
        self.eval_box_fn = eval_box_fn
        self.imgsize = imgsize
        self.size_cache = SizeCache()

    def __len__(self):
        return len(self.indices) if self.indices is not None else len(self.dataset)

    def train_epoch(self, global_batch, epoch, **kw):
        return train_batches(
            self.dataset, self.indices, global_batch, epoch,
            decode_size=self.decode_size, host_transform=self.train_transform,
            box_fn=self.train_box_fn, imgsize=self.imgsize,
            size_cache=self.size_cache, **kw,
        )

    def eval_epoch(self, batch, **kw):
        return eval_batches(
            self.dataset, self.indices, batch, decode_size=self.decode_size,
            host_transform=self.eval_transform,
            box_fn=self.eval_box_fn, imgsize=self.imgsize,
            size_cache=self.size_cache, **kw,
        )
