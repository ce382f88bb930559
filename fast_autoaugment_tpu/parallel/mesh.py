"""Device mesh, sharding helpers and the distributed backend.

The reference's distributed layer is NCCL through torch.distributed:
one process per GPU, DDP gradient allreduce inside ``loss.backward()``,
explicit broadcasts for init/EMA sync (``train.py:113-119,220-224``),
plus an SSH launcher (``train_dist.py``).  The TPU-native design
replaces ALL of that with the XLA SPMD model:

- one process per HOST (``jax.distributed.initialize`` for multi-host),
- a ``jax.sharding.Mesh`` over all devices with a ``'data'`` axis,
- the train step jitted with the global batch sharded over ``'data'``
  and parameters replicated: XLA inserts the gradient reductions as ICI
  collectives automatically — there is no DDP wrapper to write, and
  "broadcast params from rank 0" is simply device placement of the
  replicated sharding,
- BN statistics are computed over the global batch under jit, which is
  exactly the cross-replica sync-BN the reference approximates with
  ``nn.SyncBatchNorm`` / ``TpuBatchNormalization`` allreduces.

NCCL-op -> XLA mapping (SURVEY.md section 5): allreduce(grads) ->
implicit psum under jit / ``lax.psum`` under shard_map; broadcast ->
replicated NamedSharding placement; allreduce(BN stats) -> global-batch
statistics (or ``lax.pmean`` with an axis_name under shard_map).
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "make_mesh",
    "make_fold_mesh",
    "data_sharding",
    "replicated",
    "shard_batch",
    "shard_stacked_batch",
    "shard_transform",
    "stacked_shard_transform",
    "place_dataset",
    "place_index_matrix",
    "place_stacked_index_matrix",
    "distributed_init",
    "local_batch_to_global",
    "device_stamp",
]


def make_mesh(devices=None, axis_name: str = "data") -> Mesh:
    """1-D data-parallel mesh over all (or the given) devices.

    Model families here are all sub-100M-param CNNs, so data parallelism
    is the whole story (SURVEY.md section 2.3); the mesh keeps an
    explicit axis so wider layouts can be added without API change.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devices.reshape(-1), (axis_name,))


def make_fold_mesh(num_folds: int, devices=None, *,
                   fold_shards: int | None = None,
                   fold_axis: str = "fold", data_axis: str = "data") -> Mesh:
    """2-D ``(fold, data)`` mesh for the fold-stacked phase-1 trainer.

    The fold-to-mesh mapping rule: the fold axis takes
    ``gcd(num_folds, n_devices)`` shards by default, the data axis the
    rest.  With devices >= K (and K | n_devices) every fold owns a
    disjoint device group — folds are SHARDED across the machine
    instead of replicated onto every device; with one device (or
    coprime counts) the fold axis stays unsharded and stacking is pure
    program fusion.  Each fold's per-fold global batch is
    ``batch_per_device x (n_devices / fold_shards)`` — exactly the
    global batch a sequential run restricted to that fold's device
    group would use, which is what keeps the seeded stacked-vs-
    sequential equivalence well-defined at any layout (pass
    ``fold_shards=1`` to reproduce the all-devices-per-fold sequential
    semantics bit-for-bit).
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = devices.size
    if fold_shards is None:
        fold_shards = math.gcd(int(num_folds), n)
    if fold_shards < 1 or n % fold_shards:
        raise ValueError(
            f"fold_shards={fold_shards} does not divide {n} devices")
    return Mesh(devices.reshape(fold_shards, n // fold_shards),
                (fold_axis, data_axis))


def data_sharding(mesh: Mesh, axis_name: str = "data") -> NamedSharding:
    """Shard the leading (batch) dimension over the data axis."""
    return NamedSharding(mesh, P(axis_name))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, batch, axis_name: str = "data"):
    """Place a host batch onto the mesh, sharded along the batch dim.

    Single-process: a plain device_put of the global batch.  Multi-host:
    each process passes its LOCAL shard (the pipeline yields per-process
    shards) and the global array is assembled across processes — the
    jax analog of DistributedSampler feeding per-rank loaders
    (reference ``data.py:205-212``).
    """
    sharding = data_sharding(mesh, axis_name)
    if jax.process_count() == 1:
        return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)

    def put(x):
        global_shape = (x.shape[0] * jax.process_count(),) + x.shape[1:]
        return jax.make_array_from_process_local_data(sharding, x, global_shape)

    return jax.tree.map(put, batch)


def shard_stacked_batch(mesh: Mesh, batch, fold_axis: str = "fold",
                        data_axis: str = "data"):
    """Place a stacked ``{x: [K,B,...], y: [K,B], a: [K]}`` batch on a
    :func:`make_fold_mesh` mesh: the leading fold axis maps onto the
    mesh's fold axis, the per-fold batch dim onto the data axis, and
    rank-1 fold-aligned tensors (the active mask) ride the fold axis
    alone.  Multi-host: each process passes its per-fold LOCAL batch
    shard (dim 1), mirroring :func:`shard_batch`."""
    def spec(x):
        return P(fold_axis, data_axis) if x.ndim >= 2 else P(fold_axis)

    if jax.process_count() == 1:
        return jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, spec(x))), batch)

    def put(x):
        global_shape = x.shape
        if x.ndim >= 2:
            global_shape = (x.shape[0], x.shape[1] * jax.process_count(),
                            ) + x.shape[2:]
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, spec(x)), x, global_shape)

    return jax.tree.map(put, batch)


def stacked_shard_transform(mesh: Mesh, keys=("x", "y", "a"),
                            fold_axis: str = "fold",
                            data_axis: str = "data"):
    """`transform=` hook for ``prefetch`` over
    :func:`data.pipeline.stacked_train_batches` tuples — the stacked
    analog of :func:`shard_transform`."""
    def transform(item):
        return shard_stacked_batch(
            mesh, dict(zip(keys, item, strict=True)), fold_axis, data_axis)

    return transform


def shard_transform(mesh: Mesh, keys=("x", "y"), axis_name: str = "data"):
    """`transform=` hook for `data.pipeline.prefetch`: maps a pipeline
    tuple to a `shard_batch`-placed dict in the prefetch worker thread,
    so the H2D copy overlaps the in-flight step's device work."""
    def transform(item):
        return shard_batch(mesh, dict(zip(keys, item, strict=True)), axis_name)

    return transform


def place_dataset(mesh: Mesh, images: np.ndarray, labels: np.ndarray,
                  axis_name: str = "data"):
    """Upload a whole eager dataset ONCE, example axis sharded over the
    mesh's data axis — the storage placement behind
    ``data.pipeline.DeviceCache``, which hands `images` over in its
    stored form (``StoredRows``: one row an example, ``[N, row]``).

    The example count is padded up to a multiple of the data-axis shard
    count with zero rows so every device holds an equal slab; pad rows
    are never referenced (the index matrices only name real examples).
    Train steps then gather their batches from this resident copy by
    index INSIDE the compiled program — no per-step H2D image copy.
    Returns ``(images_dev, labels_dev)``.
    """
    shards = mesh.shape[axis_name]
    n = images.shape[0]
    if labels.shape[0] != n:
        raise ValueError(f"{n} images but {labels.shape[0]} labels")
    pad = (-n) % shards
    if pad:
        images = np.concatenate(
            [images, np.zeros((pad,) + images.shape[1:], images.dtype)])
        labels = np.concatenate(
            [labels, np.zeros((pad,) + labels.shape[1:], labels.dtype)])
    sharding = NamedSharding(mesh, P(axis_name))
    return jax.device_put(images, sharding), jax.device_put(labels, sharding)


def place_index_matrix(mesh: Mesh, idx: np.ndarray, axis_name: str = "data"):
    """Place a ``[N, B]`` per-dispatch batch-index matrix: the scan
    (step) axis replicated, the batch axis sharded over the data axis —
    the only per-step H2D traffic the device-cache path ships (int32,
    ~KBs instead of the uint8 image batch)."""
    spec = P(*([None] * (idx.ndim - 1) + [axis_name]))
    return jax.device_put(np.ascontiguousarray(idx, np.int32),
                          NamedSharding(mesh, spec))


def place_stacked_index_matrix(mesh: Mesh, idx: np.ndarray,
                               active: np.ndarray,
                               fold_axis: str = "fold",
                               data_axis: str = "data"):
    """Stacked counterpart of :func:`place_index_matrix` for a
    :func:`make_fold_mesh` mesh: ``idx [N, K, B]`` rides (scan, fold,
    data), ``active [N, K]`` rides (scan, fold)."""
    idx_dev = jax.device_put(
        np.ascontiguousarray(idx, np.int32),
        NamedSharding(mesh, P(None, fold_axis, data_axis)))
    act_dev = jax.device_put(
        np.ascontiguousarray(active, np.float32),
        NamedSharding(mesh, P(None, fold_axis)))
    return idx_dev, act_dev


def local_batch_to_global(batch_per_device: int, mesh: Mesh) -> int:
    return batch_per_device * mesh.size


def device_stamp() -> dict:
    """What JAX is running on, as JAX reports it — stamped into every
    trainer/search result and bench line so a number can never be read
    without the device that produced it (a CPU rehearsal says ``cpu``)."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def distributed_init(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None):
    """Multi-host rendezvous (replaces torch.distributed.launch env-var
    plumbing, reference ``train_dist.py:126-131``).  On TPU pods the
    arguments are auto-detected from the environment.

    Must run before anything touches the backend:
    ``jax.distributed.initialize`` refuses once a device has been
    queried, so nothing here may ask JAX for a process or device count
    first.  With a coordinator given, a failed rendezvous raises — a
    host that silently trained alone would report success for a job
    that never ran.  Without one, a failed auto-detection means
    single-process (tests, one chip)."""
    if jax.distributed.is_initialized():
        return
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except (ValueError, RuntimeError) as e:
        if coordinator_address is not None:
            raise RuntimeError(
                f"multi-host initialisation against coordinator "
                f"{coordinator_address!r} failed: {e}") from e
