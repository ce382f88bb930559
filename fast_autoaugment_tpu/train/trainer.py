"""Epoch driver: the ``train_and_eval`` equivalent.

Mirrors the reference driver's contract (``train.py:110-322``): builds
data/model/optimizer/schedule, restores checkpoints, runs the epoch
loop with periodic evaluation (master-only), tracks the best metric,
reports progress to a callback (the search engine's hook,
``train.py:289-303``) and saves checkpoints with cheap metadata.

Differences by design, not omission:
- the per-batch work is ONE jitted step on the global mesh batch (no
  DDP wrapper, no host-side EMA loop, no H2D copy per tensor);
- the LR schedule is a pure function of the step baked into the
  optimizer, not a stateful scheduler stepped per batch;
- checkpoint progress metadata is readable without deserializing
  weights (``core/checkpoint.py``), which the search driver polls.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from fast_autoaugment_tpu.core.checkpoint import (
    load_checkpoint_chain,
    read_metadata,
    save_checkpoint,
)
from fast_autoaugment_tpu.core.compilecache import (
    compile_cache_stats,
    configure_compile_cache,
)
from fast_autoaugment_tpu.core.metrics import Accumulator
from fast_autoaugment_tpu.core.resilience import (
    PREEMPTED_EXIT_CODE,
    PreemptedError,
    install_signal_handlers,
    preemption_requested,
)
from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.core.telemetry import wall
from fast_autoaugment_tpu.core.watchdog import (
    dispatch_enqueue_guard,
    resolve_watchdog,
)
from fast_autoaugment_tpu.data.datasets import (
    cv_split,
    is_token_dataset,
    load_dataset,
)
from fast_autoaugment_tpu.data.pipeline import (
    BatchIterator,
    DeviceCache,
    prefetch,
    resolve_device_cache,
    split_dispatch_chunks,
    stacked_index_matrix,
    stacked_train_batches,
    train_index_matrix,
)
from fast_autoaugment_tpu.models import get_model, model_conf_of, num_class
from fast_autoaugment_tpu.ops.optim import build_optimizer
from fast_autoaugment_tpu.ops.schedules import build_schedule
from fast_autoaugment_tpu.parallel.mesh import (
    device_stamp,
    make_fold_mesh,
    make_mesh,
    place_index_matrix,
    place_stacked_index_matrix,
    replicated,
    shard_transform,
    stacked_shard_transform,
)
from fast_autoaugment_tpu.policies.archive import load_policy, policy_to_tensor
from fast_autoaugment_tpu.train.steps import (
    COUNT_PREFIX,
    create_train_state,
    make_eval_step,
    make_multistep_train_step,
    make_replay_eval_step,
    make_stacked_step_body,
    make_stacked_train_step,
    make_token_step_body,
    make_token_train_step,
    make_train_step,
    make_train_step_body,
    slice_state,
    stack_states,
)
from fast_autoaugment_tpu.utils import faultinject
from fast_autoaugment_tpu.utils.logging import get_logger, make_writers

__all__ = ["train_and_eval", "train_folds_stacked", "resolve_policy_tensor"]

logger = get_logger("faa_tpu.train")


# conf-name -> archive-name mapping (reference data.py:91-106)
AUG_ALIASES = {
    "fa_reduced_imagenet": "fa_resnet50_rimagenet",
    "arsaug": "arsaug_policy",
    "autoaug_cifar10": "autoaug_paper_cifar10",
    "autoaug_extend": "autoaug_policy",
}


def resolve_policy_tensor(aug: Any):
    """conf['aug'] -> policy tensor or None ('default').

    Accepts an archive name (or its conf alias), an explicit policy
    list (the search's decoded candidates), or 'default'/None.
    """
    if aug in (None, "default"):
        return None
    if isinstance(aug, str):
        return jnp.asarray(policy_to_tensor(load_policy(AUG_ALIASES.get(aug, aug))))
    # explicit list of sub-policies
    return jnp.asarray(policy_to_tensor([list(map(tuple, sub)) for sub in aug]))


def _run_eval(eval_step, params, batch_stats, batches, mesh) -> dict:
    """`batches` yields per-process (images, labels, mask) shards —
    padding/sharding lives in `eval_batches` (one place, multi-host
    aware), not here.  Host slicing/decoding and the H2D copy run in
    the prefetch worker so they overlap the previous batch's device
    eval.  The device-cache path evaluates differently: splits are
    placed once and replayed in one fused dispatch per shape group
    (:func:`_stacked_eval_splits` + :func:`_run_replay_eval` — the
    ``search/tta.py::eval_tta`` upload-once discipline applied to
    training eval)."""
    acc = Accumulator()
    sharded = prefetch(batches, transform=shard_transform(mesh, ("x", "y", "m")))
    for batch in sharded:
        acc.add_dict(eval_step(params, batch_stats, batch["x"], batch["y"], batch["m"]))
    return acc.normalize()


def _stacked_eval_splits(it: BatchIterator, global_batch: int, mesh,
                         eval_kw: dict) -> list:
    """Materialize one eval epoch as device-resident SHAPE-GROUPED batch
    stacks (``{"x": [S, B, ...], "y": [S, B], "m": [S, B]}``) for
    one-dispatch replay through ``make_replay_eval_step`` (usually one
    group; a padded final partial batch of a different size forms a
    second).  Placed once per split, reused every evaluation epoch."""
    from jax.sharding import NamedSharding, PartitionSpec

    groups: dict = {}
    for x, y, m in it.eval_epoch(global_batch, **eval_kw):
        groups.setdefault(x.shape, []).append((x, y, m))
    sharding = NamedSharding(mesh, PartitionSpec(None, "data"))
    out = []
    for items in groups.values():
        out.append({
            "x": jax.device_put(np.stack([x for x, _, _ in items]), sharding),
            "y": jax.device_put(np.stack([y for _, y, _ in items]), sharding),
            "m": jax.device_put(np.stack([m for _, _, m in items]), sharding),
        })
    return out


def _run_replay_eval(replay_step, params, batch_stats, groups,
                     wd=None) -> dict:
    """One fused dispatch per shape group over a replayed split (each
    deadline-guarded when a watchdog is enabled — the PR-4 rendezvous
    deadlock was first observed exactly here, in eval)."""
    acc = Accumulator()
    for g in groups:
        with telemetry.span("replay_eval"):
            if wd is not None and wd.enabled:
                out = wd.run("replay_eval", replay_step, params, batch_stats,
                             g["x"], g["y"], g["m"])
            else:
                with dispatch_enqueue_guard():
                    out = replay_step(params, batch_stats, g["x"], g["y"],
                                      g["m"])
        acc.add_dict(out)
    return acc.normalize()


def _monitored_dispatch(wd, label: str, fi, step: int, fn, *args):
    """One device dispatch through the watchdog + telemetry span seam.

    With the watchdog off and no injected fault this is EXACTLY the
    historical direct call — async dispatch, no per-dispatch block (the
    span then times the ENQUEUE window, not device completion; the
    monitored path times the full blocking wall).
    With the watchdog on (or a ``hang``/``slow`` fault pinned at this
    step) the call runs deadline-guarded in a worker thread, blocking
    on completion; that serializes the dispatch pipeline (wall only —
    values are unchanged), which is why ``--watchdog`` defaults off.
    A fired deadline raises the typed ``DispatchHungError`` (exit-77
    recovery — core/watchdog.py).  Every path times the window inside
    :func:`~fast_autoaugment_tpu.core.telemetry.span` — the records of
    the seam the TTA/audit and serve dispatches use, and an annotation
    of the same name on a profiler trace's host line."""
    inject = fi.dispatch_delay(step) if fi is not None else None
    if inject is None and not wd.enabled:
        # enqueue-order serialization (async pipeline only; no-op
        # otherwise) — completion stays async, the historical path
        with telemetry.span(label, step=step, blocking=False):
            with dispatch_enqueue_guard():
                return fn(*args)
    delay = 0.0
    if inject is not None:
        kind, val = inject
        # slow = straggler at F x the label's observed EMA (F seconds
        # before any observation); hang = forever
        delay = val if kind == "hang" else val * (wd.ema(label) or 1.0)
    with telemetry.span(label, step=step, blocking=True):
        return wd.run(label, fn, *args, inject_delay=delay)


def _beat(heartbeat) -> None:
    """Lease/host heartbeat at a safe boundary.  LeaseLostError (the
    unit was reclaimed — launch/workqueue.py) propagates: this worker
    must abandon the unit, not finish and clobber the survivor."""
    if heartbeat is not None:
        heartbeat()


def _sum_metric_dicts(metric_dicts: list) -> dict:
    """Epoch-end host-side accumulation of per-dispatch metric sums.

    Sequential float32 adds over the synced values — the SAME chain the
    host path's on-device `Accumulator` adds compute, so the reported
    sums stay bit-identical.  Summing on host AFTER the epoch (the sums
    are read at epoch end regardless) instead of queueing one scalar-add
    program per metric per dispatch matters on the virtual CPU mesh:
    with a mesh-committed state those adds are all-participant
    collectives, and long unsynced chains of them deadlock the backend
    (``make_replay_eval_step`` docstring)."""
    sums: dict = {}
    for m in metric_dicts:
        for k, v in m.items():
            v32 = np.asarray(v, np.float32)
            sums[k] = v32 if k not in sums else np.float32(sums[k] + v32)
    return sums


def _split_counts(sums: dict) -> dict:
    """Take the count sums (``steps.COUNT_PREFIX``) out of `sums` and hand
    them back: they are published as counters and never divided."""
    return {k: sums.pop(k) for k in [k for k in sums
                                     if k.startswith(COUNT_PREFIX)]}


class _CountPublisher:
    """Publishes an epoch's count sums (``steps.make_token_step_body``)
    where the trainer has synced them anyway — the epoch boundary, a
    mid-epoch snapshot: ``tokens`` as ``faa_tokens_total``, and what the
    model counted through the model's own ``publish_counts(rise,
    registry)``, where it has one.  Counters rise by what is new since
    the last publication of the same epoch."""

    def __init__(self, model):
        self.publish_model = getattr(model, "publish_counts", None)
        self.seen: dict = {}

    def new_epoch(self, carried: dict | None = None) -> None:
        # a resumed epoch's saved sums were published by the run that saved them
        self.seen = dict(carried or {})

    def publish(self, counts: dict) -> None:
        reg = telemetry.registry()
        rise = {k[len(COUNT_PREFIX):]: float(v) - float(self.seen.get(k, 0.0))
                for k, v in counts.items()}
        self.seen = {k: float(v) for k, v in counts.items()}
        reg.counter("faa_tokens_total",
                    "tokens the trainer's steps trained on").inc(
                        rise.pop("tokens", 0.0))
        if self.publish_model is not None:
            self.publish_model(rise, reg)


def train_and_eval(
    conf,
    dataroot: str,
    *,
    test_ratio: float = 0.0,
    cv_fold: int = 0,
    reporter: Callable | None = None,
    metric: str = "last",
    save_path: str | None = None,
    only_eval: bool = False,
    evaluation_interval: int = 5,
    mesh=None,
    target_lb: int = -1,
    seed: int = 0,
    aug_dispatch: str = "exact",
    aug_groups: int = 8,
    device_cache: str = "auto",
    steps_per_dispatch: int = 1,
    divergence_retries: int = 0,
    ckpt_keep: int = 2,
    checkpoint_every_dispatch: int = 0,
    watchdog="off",
    heartbeat: Callable | None = None,
) -> dict:
    """Train (or just evaluate) one model under `conf`.

    Returns the reference-shaped result dict with per-split loss/top1/
    top5 plus 'epoch'.  `metric` in {'last', 'train', 'valid', 'test'}
    selects what "best" means (reference ``train.py:286-303``).
    ``aug_dispatch``/``aug_groups`` pick the policy-application kernel
    ("exact" default, bit-for-bit historical; "grouped" scalar
    dispatch — see ``ops/augment.py``).

    ``device_cache`` ("auto"/"on"/"off") selects the device-resident
    data path: the whole eager dataset is uploaded ONCE (sharded over
    the mesh data axis), each epoch ships only the int32 index matrix of
    the IDENTICAL host-side shuffle, and the compiled program gathers
    its batches in place (``data.pipeline.DeviceCache``); eval splits
    are likewise placed once and replayed every evaluation epoch.
    "auto" enables it exactly for eager single-process datasets — lazy
    (ImageNet) datasets keep the prefetch/decode path.
    ``steps_per_dispatch`` (N, needs the cache) fuses N train steps into
    one ``lax.scan`` dispatch (``make_multistep_train_step``): N=1
    (default) is bit-for-bit the host-fed path; N>1 deviates by the
    documented ~1 f32 ULP/step scan-kernel bound (the fold-stacking
    deviation class — docs/PARITY.md "Step dispatch & device
    cache").

    Resilience (docs/RESILIENCE.md; defaults preserve the historical
    behavior bit-for-bit): SIGTERM/SIGUSR1 requests a graceful stop —
    the loop checkpoints at the next dispatch boundary (either feed:
    a scan chunk on the cache path, a batch on the host-fed one) with
    ``preempted: true`` metadata and the position in the epoch, and raises
    :class:`PreemptedError` (exit-code contract 77 = "resume me").
    ``divergence_retries`` (R, default 0 = raise as before) rolls a
    non-finite epoch loss back to the newest intact epoch-boundary
    checkpoint up to R times, folding the retry counter into the PRNG
    and shuffle seeds so the replay draws fresh randomness.
    ``ckpt_keep`` bounds the rollback chain (``path``, ``path.prev``,
    …).  ``checkpoint_every_dispatch`` (M) adds a mid-epoch snapshot
    every M dispatches — resumable from the exact dispatch boundary,
    bit-identically (the host-fed resume skips the batches already
    trained without decoding them).

    ``watchdog`` ("off" default / "auto" / seconds, or a shared
    :class:`~fast_autoaugment_tpu.core.watchdog.DispatchWatchdog`)
    deadline-guards every train dispatch and eval replay; a wedged
    dispatch raises the typed ``DispatchHungError`` (exit-77 restart
    recovery) instead of blocking forever.  ``heartbeat`` (callable,
    e.g. a work-queue lease renewal) is invoked after every dispatch
    and at every epoch boundary — a raised
    ``LeaseLostError`` propagates and aborts the unit.

    The persistent compilation cache is always armed where
    ``JAX_COMPILATION_CACHE_DIR`` (or the fixed in-checkout default)
    places it (``core/compilecache.py``), so a fresh process — an
    exit-77 resume, a fleet retry, a reclaimed work unit — reaches its
    first step without re-paying the compile; the evidence rides under
    ``result['compile_cache']``.  The result also names the device that
    ran it (``platform``/``device_kind``/``device_count``) and the
    optimizer ``steps`` taken.

    The call is the root of a stage tree (``core/telemetry.py::stage``;
    docs/OBSERVABILITY.md "Stages" names the stages): where its seconds
    went, by dotted path, rides under ``result['stages']``.
    """
    with telemetry.stage("train_and_eval", only_eval=bool(only_eval)) as root:
        cache_dir_active = configure_compile_cache()
        if mesh is None:
            mesh = make_mesh()
        is_master = jax.process_index() == 0

        dataset_name = conf["dataset"]
        tokens = is_token_dataset(dataset_name)
        model_conf = model_conf_of(conf)
        with telemetry.stage("load_dataset"):
            total_train, testset = load_dataset(dataset_name, dataroot)
        if tokens:
            # ids in, next-token loss: the classes are the ids the model holds
            if conf.get("aug", "default") not in (None, "default"):
                raise ValueError(
                    f"dataset {dataset_name!r} is a token data set and conf aug="
                    f"{conf['aug']!r} names an augmentation policy: policies "
                    "are image operations; use aug: default")
            num_classes = int(model_conf.get("ids_held")
                              or model_conf.get("vocab_size") or 0)
            if not 0 < max(total_train.num_classes, testset.num_classes) <= num_classes:
                raise ValueError(
                    f"the data set holds ids up to "
                    f"{max(total_train.num_classes, testset.num_classes) - 1}, the "
                    f"model {num_classes} ids (conf ids_held, else model.vocab_size)")
        else:
            num_classes = num_class(dataset_name)

        with telemetry.stage("split"):
            if test_ratio > 0.0:
                train_idx, valid_idx = cv_split(total_train.labels, test_ratio, cv_fold)
                if target_lb >= 0:
                    # single-class restriction (reference data.py:199-201)
                    train_idx = train_idx[total_train.labels[train_idx] == target_lb]
                    valid_idx = valid_idx[total_train.labels[valid_idx] == target_lb]
            else:
                train_idx, valid_idx = np.arange(len(total_train)), np.array([], np.int64)

            is_imagenet = dataset_name.endswith("imagenet")
            from fast_autoaugment_tpu.models import input_image_size

            # conf['imgsize'] overrides the native resolution (the reference
            # evaluates ResNet-200 at 320px, README.md:44-46)
            image = None if tokens else int(conf.get("imgsize", 0) or 0) or input_image_size(
                dataset_name, conf["model"]["type"]
            )
            if is_imagenet:
                from fast_autoaugment_tpu.ops.preprocess_imagenet import (
                    center_crop_box,
                    imagenet_eval_batch,
                    imagenet_train_batch,
                    random_crop_box,
                )

                train_box = lambda rng, w, h: random_crop_box(rng, w, h, image)  # noqa: E731
                eval_box = lambda rng, w, h: center_crop_box(w, h, image)  # noqa: E731
            else:
                train_box = eval_box = None
            it_kw = dict(train_box_fn=train_box, eval_box_fn=eval_box, imgsize=image)
            train_it = BatchIterator(total_train, train_idx, **it_kw)
            valid_it = BatchIterator(total_train, valid_idx, **it_kw)
            test_it = BatchIterator(testset, **it_kw)

        use_cache = resolve_device_cache(device_cache, total_train,
                                         process_count=jax.process_count())
        steps_per_dispatch = int(steps_per_dispatch)
        if steps_per_dispatch > 1 and not use_cache:
            raise ValueError(
                f"steps_per_dispatch={steps_per_dispatch} needs the device "
                "cache (in-program batch gather); it is "
                f"{'off' if device_cache == 'off' else 'unavailable (lazy dataset or multi-host)'} "
                "here — use --device-cache auto/on with an eager dataset")

        batch_per_device = int(conf["batch"])
        global_batch = batch_per_device * mesh.size
        logger.info("mesh %s over %d %s device(s); global batch %d",
                    dict(mesh.shape), mesh.size,
                    mesh.devices.flat[0].platform, global_batch)
        if not only_eval and len(train_idx) < global_batch:
            raise ValueError(
                f"training set has {len(train_idx)} examples < global batch "
                f"{global_batch} ({batch_per_device}/device x {mesh.size} devices); "
                "every epoch would be empty (train batches drop the last partial "
                "batch, reference data.py:215)"
            )
        steps_per_epoch = max(1, len(train_idx) // global_batch)
        epochs = int(conf["epoch"])

        with telemetry.stage("build"):
            model = get_model(model_conf, num_classes)
            lr_fn = build_schedule(conf, steps_per_epoch, world_lr_scale=float(mesh.size))
            optimizer_conf = conf["optimizer"]
            ema_mu = float(optimizer_conf.get("ema", 0.0) or 0.0)

            if tokens:
                # parameter shapes do not depend on the length: a short sample,
                # of the shortest length at which every operation takes the form
                # a step's length takes (ops/attention.py: two tiles of 128)
                sample = jnp.zeros((1, min(total_train.images.shape[1] - 1, 256)), jnp.int32)
            else:
                sample = jnp.zeros((2, image, image, 3), jnp.float32)
            rng = jax.random.PRNGKey(seed)

            optimizer = build_optimizer(optimizer_conf, lr_fn)
        with telemetry.stage("state_init"):
            # one program for a token model's init; the image models' seeded
            # weights are pinned under the eager one (create_train_state)
            state = create_train_state(model, optimizer, rng, sample,
                                       use_ema=ema_mu > 0.0, jit_init=tokens)
        # which family ran at what size, for the journal and /metrics (sizes
        # from shapes: nothing waits for the device)
        num_params = sum(int(p.size) for p in jax.tree.leaves(state.params))
        model_type = str(model_conf["type"])
        telemetry.registry().gauge(
            "faa_model_parameters", "trainable parameters of the model a "
            "trainer built", model=model_type).set(num_params)
        telemetry.emit("model", model_type, parameters=num_params,
                       batch_per_device=batch_per_device,
                       steps_per_epoch=steps_per_epoch)

        with telemetry.stage("build"):
            policy = resolve_policy_tensor(conf.get("aug", "default"))
            use_policy = policy is not None
            if is_imagenet:
                cutout_len = int(conf.get("cutout", 0) or 0)
                augment_fn = lambda images, pol, key: imagenet_train_batch(  # noqa: E731
                    images, key, pol if use_policy else None, cutout_length=cutout_len,
                    aug_dispatch=aug_dispatch, aug_groups=aug_groups,
                )
                eval_preprocess = imagenet_eval_batch
            else:
                augment_fn = None
                eval_preprocess = None
            step_kw = dict(
                num_classes=num_classes,
                mixup_alpha=float(conf.get("mixup", 0.0) or 0.0),
                lb_smooth=float(conf.get("lb_smooth", 0.0) or 0.0),
                ema_mu=ema_mu,
                cutout_length=int(conf.get("cutout", 0) or 0),
                use_policy=use_policy,
                augment_fn=augment_fn,
                aug_dispatch=aug_dispatch,
                aug_groups=aug_groups,
            )
            token_counters = _CountPublisher(model) if tokens else None
            if use_cache:
                # device-resident path: the body is dispatched through the
                # multi-step gather program; at most two chunk shapes per epoch
                # (N and the clamped remainder), each compiled once and reused
                step_body = (make_token_step_body(model, optimizer, ema_mu=ema_mu)
                             if tokens else
                             make_train_step_body(model, optimizer, **step_kw))
                multi_fns: dict[int, Callable] = {}

                def get_multi_step(n: int) -> Callable:
                    if n not in multi_fns:
                        multi_fns[n] = make_multistep_train_step(
                            step_body, steps_per_dispatch=n)
                    return multi_fns[n]
            else:
                train_step = (make_token_train_step(model, optimizer, ema_mu=ema_mu)
                              if tokens else
                              make_train_step(model, optimizer, **step_kw))
            eval_step = make_eval_step(model, num_classes=num_classes,
                                       lb_smooth=float(conf.get("lb_smooth", 0.0) or 0.0),
                                       preprocess_fn=eval_preprocess, tokens=tokens)
            replay_eval = make_replay_eval_step(
                model, num_classes=num_classes,
                lb_smooth=float(conf.get("lb_smooth", 0.0) or 0.0),
                preprocess_fn=eval_preprocess, tokens=tokens) if use_cache else None

            writers = make_writers(
                os.path.dirname(save_path) if save_path else None,
                os.path.basename(save_path or "run"),
                is_master,
            )

            ckpt_keep = max(1, int(ckpt_keep))
            divergence_retries = max(0, int(divergence_retries))
            checkpoint_every_dispatch = max(0, int(checkpoint_every_dispatch))
            wd = resolve_watchdog(watchdog)
            # flag-setting SIGTERM/SIGUSR1 handlers (idempotent, main thread
            # only): the epoch/dispatch loops below poll the flag at safe
            # boundaries — see core/resilience.py and docs/RESILIENCE.md
            install_signal_handlers()

        epoch_start = 1
        resume_pos = 0          # mid-epoch fast-forward (preempted snapshot)
        resume_sums: dict | None = None
        retries_done = 0        # divergence-retry counter (folds the PRNG)
        restored = None
        with telemetry.stage("restore"):
            if save_path:
                # lenient when the file came from the torch importer (no opt_state)
                lenient = bool((read_metadata(save_path) or {}).get("imported_from"))
                # restore from the NEWEST intact chain link; a mid-epoch
                # (preempted) snapshot fast-forwards its epoch to the dispatch
                # position it names, on either feed
                restored = load_checkpoint_chain(
                    save_path, state, lenient=lenient, keep=ckpt_keep)
                if restored is not None and "in_epoch" in restored[1]:
                    rec = restored[1]["in_epoch"] or {}
                    if int(rec.get("epoch", -1)) != int(restored[1].get("epoch", 0)) + 1:
                        logger.warning(
                            "inconsistent mid-epoch record in %s — falling back "
                            "to an epoch-boundary chain link", restored[2])
                        restored = load_checkpoint_chain(
                            save_path, state, lenient=lenient, keep=ckpt_keep,
                            accept=lambda m: "in_epoch" not in m)
            if restored is not None:
                state, meta, used_path = restored
                lenient = bool(meta.get("imported_from"))
                epoch_start = int(meta.get("epoch", 0)) + 1
                in_epoch = meta.get("in_epoch")
                if in_epoch:
                    resume_pos = int(in_epoch["pos"])
                    resume_sums = {k: np.float32(v)
                                   for k, v in (in_epoch.get("sums") or {}).items()}
                    retries_done = int(in_epoch.get("retries", 0))
                    logger.info(
                        "resuming MID-EPOCH: epoch %d from dispatch position %d "
                        "(preempted snapshot %s)", epoch_start, resume_pos,
                        used_path)
                if lenient:
                    fixes = {}
                    # the schedule is a pure fn of step: place it at the resume
                    # epoch, not back at warmup
                    fixes["step"] = jnp.int32((epoch_start - 1) * steps_per_epoch)
                    if state.ema is not None and not meta.get("has_ema"):
                        # no EMA in the imported file: seed the shadow from the
                        # imported weights, never from random init
                        fixes["ema"] = jax.tree.map(
                            jnp.copy,
                            {"params": state.params, "batch_stats": state.batch_stats},
                        )
                    state = state.replace(**fixes)
                # resume-cost provenance: whether this resumed process will
                # deserialize its executables (warm cache) or re-pay the full
                # compile tax — the final compile_cache stamp carries the proof
                logger.info("resumed %s at epoch %d (compile cache: %s)",
                            used_path, epoch_start - 1,
                            cache_dir_active or "off — full recompile ahead")
                if epoch_start > epochs:
                    only_eval = True
            elif only_eval and save_path:
                raise FileNotFoundError(f"--only-eval requires a checkpoint at {save_path}")

        # commit the carried state to the mesh BEFORE the first dispatch or
        # eval, on either feed.  Cached: an uncommitted state compiled
        # against the mesh-committed cache knocks every later call off the
        # C++ fast dispatch path (make_multistep_train_step note), and an
        # --only-eval restore must lower the SAME replay_eval program the
        # training run cached, not an uncommitted variant of it.  Host-fed:
        # the batches arrive committed, so the first step returns a
        # committed state and the SECOND call no longer matches what the
        # first compiled — the step program was lowered and compiled (or
        # loaded) twice a process (ResNet-50 on one v5e: 49 s of a cold
        # run's first epoch, 7 s of a warm one's; my chip runs, PR 32)
        with telemetry.stage("place_state"):
            state = jax.device_put(state, replicated(mesh))

        result: dict = {"epoch": epoch_start - 1}
        best_metric = -1e9
        # device-cache eval replay: each split is placed once on first
        # evaluation and reused for every later one (and for the EMA pass,
        # which previously re-fed the split within the SAME evaluation)
        eval_replay: dict[str, list] = {}

        def evaluate(tag_prefix: str, epoch: int) -> dict:
            # empty splits are SKIPPED, not reported as zeros: with
            # test_ratio=0 (every phase-3 search retrain) a zero-row per
            # interval is pure noise, and `metric="valid"` would silently
            # track a best of 0.0 (the reference only ever evaluates real
            # splits, train.py:272-280)
            out = {}
            splits = [("valid", valid_it), ("test", test_it)]
            for split, it in splits:
                if len(it) == 0:
                    continue
                eval_kw = dict(
                    process_index=jax.process_index(),
                    process_count=jax.process_count(),
                    pad_multiple=mesh.size,
                )
                if use_cache:
                    if split not in eval_replay:
                        eval_replay[split] = _stacked_eval_splits(
                            it, global_batch, mesh, eval_kw)
                    norm = _run_replay_eval(
                        replay_eval, state.params, state.batch_stats,
                        eval_replay[split], wd=wd)
                else:
                    norm = _run_eval(
                        eval_step, state.params, state.batch_stats,
                        it.eval_epoch(global_batch, **eval_kw), mesh,
                    )
                out[split] = norm
                if state.ema is not None:
                    if use_cache:
                        norm_ema = _run_replay_eval(
                            replay_eval, state.ema["params"],
                            state.ema["batch_stats"], eval_replay[split], wd=wd)
                    else:
                        norm_ema = _run_eval(
                            eval_step, state.ema["params"],
                            state.ema["batch_stats"],
                            it.eval_epoch(global_batch, **eval_kw), mesh,
                        )
                    # with EMA on, the REPORTED valid/test numbers are the
                    # EMA model's (reference train.py:277-280 overwrites
                    # rs['valid']/rs['test']); raw weights kept under _raw
                    out[split + "_raw"] = norm
                    out[split + "_ema"] = norm_ema
                    out[split] = norm_ema
            return out

        if only_eval:
            with telemetry.stage("evaluate"):
                evals = evaluate("only_eval", epoch_start)
            for split, m in evals.items():
                for k, v in m.items():
                    result[f"{k}_{split}"] = v
            result["epoch"] = epoch_start - 1
            result.update(steps=int(state.step), **device_stamp())
            result["compile_cache"] = compile_cache_stats()
            result["stages"] = root.summary()
            return result

        # best-metric guards live AFTER the only_eval return (eval-only runs
        # never consult `metric`, including resumes that auto-flip only_eval)
        if metric not in ("last", "train", "valid", "test"):
            raise ValueError(f"unknown metric {metric!r}: use last/train/valid/test")
        if metric == "valid" and len(valid_it) == 0:
            raise ValueError(
                "metric='valid' with an empty validation split (test_ratio=0): "
                "the best-checkpoint tracker would silently follow a constant "
                "0.0 — pass metric='last'/'train'/'test' or a test_ratio > 0"
            )
        if metric == "test" and len(test_it) == 0:
            raise ValueError("metric='test' with an empty test split")

        with telemetry.stage("cache_upload"):
            train_cache = DeviceCache(total_train, mesh) if use_cache else None
            if train_cache is not None:
                logger.info(
                    "device cache: %d examples (%.1f MiB) resident as %s %s, "
                    "steps_per_dispatch=%d", train_cache.num_examples,
                    train_cache.nbytes / 2**20, train_cache.images.dtype,
                    list(train_cache.images.shape), steps_per_dispatch)
                # replicated inputs join the committed state on the mesh
                rng = jax.device_put(rng, replicated(mesh))

        t_start = wall()
        pol = policy if policy is not None else jnp.zeros((1, 1, 3), jnp.float32)
        if train_cache is not None:
            pol = jax.device_put(pol, replicated(mesh))
        # while (not for): divergence recovery rolls `epoch` BACK to the
        # last good checkpoint's successor and replays with fresh randomness
        epoch = epoch_start
        while epoch <= epochs:
            with telemetry.stage("epoch", epoch=epoch):
                fi = faultinject.active_plan()
                # divergence-retry randomness: after any rollback every epoch
                # draws retry-folded augmentation keys and shuffle seeds;
                # retries_done == 0 is bit-for-bit the historical stream
                if retries_done:
                    rng_epoch = jax.random.fold_in(rng, 1_000_003 * retries_done)
                    seed_epoch = seed + 1_000_003 * retries_done
                    if train_cache is not None:
                        rng_epoch = jax.device_put(rng_epoch, replicated(mesh))
                else:
                    rng_epoch, seed_epoch = rng, seed
                acc = Accumulator()
                # live per-batch progress (the reference's tqdm postfix,
                # train.py:79-88): FAA_PROGRESS=N prints a loss-EMA line every N
                # batches (dispatches on the cache path).  Off by default —
                # reading metrics per batch forces a device sync and stalls the
                # dispatch pipeline, which is why the epoch loop otherwise never
                # touches metric values mid-epoch.
                try:
                    progress_every = int(os.environ.get("FAA_PROGRESS", "0") or 0)
                except ValueError:  # cosmetic knob must never kill a run — but
                    # the misconfiguration must be VISIBLE, not silently eaten
                    logger.warning(
                        "FAA_PROGRESS=%r is not an integer — live progress "
                        "line disabled", os.environ.get("FAA_PROGRESS"))
                    progress_every = 0
                loss_ema = None

                def progress(bi: int, metrics, epoch=epoch):
                    nonlocal loss_ema
                    if is_master and progress_every and (bi + 1) % progress_every == 0:
                        cur = float(metrics["loss"]) / max(float(metrics["num"]), 1.0)
                        loss_ema = cur if loss_ema is None else 0.9 * loss_ema + 0.1 * cur
                        sys.stderr.write(
                            f"\r[epoch {epoch} batch {bi + 1}] loss_ema={loss_ema:.4f} ")
                        sys.stderr.flush()

                def snapshot_in_epoch(pos: int, sums: dict, epoch=epoch):
                    """Mid-epoch checkpoint at a dispatch boundary: the exact
                    position and the epoch's metric sums so far (either feed)."""
                    with telemetry.stage("checkpoint"):
                        save_checkpoint(
                            save_path, state,
                            {"epoch": epoch - 1,
                             "step": (epoch - 1) * steps_per_epoch + pos,
                             "preempted": preemption_requested(),
                             "in_epoch": {
                                 "epoch": epoch, "pos": pos,
                                 "sums": {k: float(v) for k, v in sums.items()},
                                 "retries": retries_done}},
                            keep=ckpt_keep)

                def preempted_in_epoch(pos: int, total: int, epoch=epoch):
                    logger.warning(
                        "preempted at epoch %d dispatch boundary (position %d/%d) "
                        "— checkpointed, exit %d means 'resume me'", epoch, pos,
                        total, PREEMPTED_EXIT_CODE)
                    return PreemptedError(
                        f"preempted mid-epoch {epoch} at dispatch position {pos}")

                if train_cache is not None:
                    # device-resident feed: the per-epoch shuffle is the
                    # IDENTICAL host permutation; only the index matrix is
                    # shipped, and each dispatch advances a whole scan chunk
                    with telemetry.stage("index_matrix"):
                        mat = train_index_matrix(
                            train_idx, global_batch, epoch, seed=seed_epoch,
                            process_index=jax.process_index(),
                            process_count=jax.process_count(),
                        )
                    with telemetry.stage("dispatch_loop"):
                        pos = 0
                        dispatch_metrics: list = []
                        if token_counters is not None:
                            token_counters.new_epoch()
                        if resume_pos and epoch == epoch_start:
                            # preempted mid-epoch: skip the dispatches already done
                            # and seed the metric chain with the saved partial sums
                            # — the host additions below continue the SAME
                            # sequential f32 chain, so the epoch's reported metrics
                            # are bit-identical to the uninterrupted run
                            pos = resume_pos
                            if resume_sums:
                                dispatch_metrics.append(dict(resume_sums))
                                if token_counters is not None:
                                    token_counters.new_epoch(
                                        _split_counts(dict(resume_sums)))
                        for di, n in enumerate(split_dispatch_chunks(
                                len(mat) - pos, steps_per_dispatch)):
                            idx_dev = place_index_matrix(mesh, mat[pos:pos + n])
                            state, metrics = _monitored_dispatch(
                                wd, "train_dispatch", fi,
                                (epoch - 1) * steps_per_epoch + pos + n,
                                get_multi_step(n),
                                state, train_cache.images, train_cache.labels,
                                idx_dev, pol, rng_epoch)
                            # per-dispatch sums are kept as ASYNC device handles and
                            # summed on host at epoch end (_sum_metric_dicts): with
                            # the committed state a per-dispatch jnp add would queue
                            # one tiny all-participant collective per metric, and
                            # long unsynced chains of those wedge the CPU backend
                            dispatch_metrics.append(metrics)
                            progress(di, metrics)
                            pos += n
                            _beat(heartbeat)
                            if fi is not None:
                                fi.maybe_signal((epoch - 1) * steps_per_epoch + pos)
                            # resilience boundary: the PR-4 dispatch boundaries are
                            # exact resume points — honor a preemption request (or
                            # the periodic snapshot knob) here, mid-epoch
                            periodic = (checkpoint_every_dispatch > 0
                                        and (di + 1) % checkpoint_every_dispatch == 0)
                            if pos < len(mat) and (preemption_requested() or periodic):
                                if save_path and is_master:
                                    sums = _sum_metric_dicts(dispatch_metrics)
                                    snapshot_in_epoch(pos, sums)
                                    if token_counters is not None:
                                        token_counters.publish(_split_counts(dict(sums)))
                                    # saved sums replace the pending handles — the
                                    # continued f32 chain is identical either way
                                    dispatch_metrics = [
                                        {k: np.float32(v) for k, v in sums.items()}]
                                if preemption_requested():
                                    raise preempted_in_epoch(pos, len(mat))
                else:
                    # host feed: the same resume points as the device-resident
                    # feed, one batch a dispatch.  A resumed epoch skips the
                    # batches already trained without decoding them (their crop
                    # boxes are still drawn, so the rest of the epoch is the
                    # unbroken run's) and continues the saved metric sums.
                    with telemetry.stage("dispatch_loop"):
                        pos = 0
                        if resume_pos and epoch == epoch_start:
                            pos = resume_pos
                            if resume_sums:
                                acc.add_dict(resume_sums)
                        batches = prefetch(
                            train_it.train_epoch(
                                global_batch, epoch, seed=seed_epoch,
                                process_index=jax.process_index(),
                                process_count=jax.process_count(),
                                skip=pos,
                            ),
                            transform=shard_transform(mesh),
                        )
                        for bi, batch in enumerate(batches, start=pos):
                            state, metrics = _monitored_dispatch(
                                wd, "train_step", fi,
                                (epoch - 1) * steps_per_epoch + bi + 1,
                                train_step, state, batch["x"], batch["y"],
                                pol, rng_epoch)
                            acc.add_dict(metrics)
                            progress(bi, metrics)
                            pos = bi + 1
                            _beat(heartbeat)
                            if fi is not None:
                                fi.maybe_signal((epoch - 1) * steps_per_epoch + pos)
                            periodic = (checkpoint_every_dispatch > 0
                                        and pos % checkpoint_every_dispatch == 0)
                            if pos < steps_per_epoch and (preemption_requested()
                                                          or periodic):
                                if save_path and is_master:
                                    snapshot_in_epoch(pos, dict(acc.items()))
                                if preemption_requested():
                                    raise preempted_in_epoch(pos, steps_per_epoch)
                with telemetry.stage("epoch_boundary"):
                    if train_cache is not None:
                        # the cached feed's sums: the host waits here for the
                        # epoch's last dispatches
                        with telemetry.stage("metric_sync"):
                            sums = _sum_metric_dicts(dispatch_metrics)
                            counts = _split_counts(sums)
                            if token_counters is not None:
                                token_counters.publish(counts)
                            acc.add_dict(sums)
                    with telemetry.stage("heartbeat"):
                        _beat(heartbeat)
                    resume_pos, resume_sums = 0, None  # consumed by the first epoch
                    if is_master and progress_every and loss_ema is not None:
                        sys.stderr.write("\n")
                    with telemetry.stage("metric_sync"):
                        if token_counters is not None and train_cache is None:
                            # host feed: the sums sat on the device until here
                            token_counters.new_epoch()
                            token_counters.publish(_split_counts(acc.metrics))
                        train_metrics = acc.normalize()
                    if not train_metrics:
                        raise RuntimeError(
                            f"epoch {epoch} produced zero train batches "
                            f"({len(train_idx)} examples, global batch {global_batch}) — "
                            "feed pipeline bug or dataset/batch mismatch"
                        )
                    if fi is not None and fi.nan_loss_in((epoch - 1) * steps_per_epoch,
                                                         epoch * steps_per_epoch):
                        train_metrics["loss"] = float("nan")  # injected at the seam
                    if not np.isfinite(train_metrics["loss"]):
                        # divergence recovery (--divergence-retries R, default 0 =
                        # the historical raise): roll back to the newest intact
                        # EPOCH-BOUNDARY chain link and replay with retry-folded
                        # randomness; re-raise only after R failed rollbacks
                        if retries_done < divergence_retries and save_path:
                            rolled = load_checkpoint_chain(
                                save_path, state, keep=ckpt_keep,
                                accept=lambda m: "in_epoch" not in m)
                            if rolled is not None:
                                retries_done += 1
                                state, meta_rb, used_rb = rolled
                                if train_cache is not None:
                                    state = jax.device_put(state, replicated(mesh))
                                rollback_epoch = int(meta_rb.get("epoch", 0)) + 1
                                logger.warning(
                                    "divergence: non-finite loss at epoch %d — rolled "
                                    "back to %s (replaying from epoch %d), retry %d/%d "
                                    "with retry-folded PRNG/shuffle streams",
                                    epoch, used_rb, rollback_epoch, retries_done,
                                    divergence_retries)
                                epoch = rollback_epoch
                                continue
                            logger.error(
                                "divergence: retries remain but NO intact rollback "
                                "checkpoint under %s — re-raising", save_path)
                        raise RuntimeError("loss is NaN — training diverged (reference train.py:259)")

                    # periodic EMA -> model weight restore (reference train.py:262-270)
                    ema_interval = int(optimizer_conf.get("ema_interval", -1) or -1)
                    if state.ema is not None and ema_interval > 0 and epoch % ema_interval == 0:
                        logger.info("ema synced into model at epoch %d", epoch)
                        # copy: params must not alias the EMA shadow (donated buffers)
                        state = state.replace(
                            params=jax.tree.map(jnp.copy, state.ema["params"]),
                            batch_stats=jax.tree.map(jnp.copy, state.ema["batch_stats"]),
                        )
                    with telemetry.stage("log"):
                        # a token model reports no top-5, and may report
                        # further sums of its own (a second loss term)
                        further = sorted(set(train_metrics)
                                         - {"loss", "top1", "top5", "num"})
                        for k in ("loss", "top1", "top5", *further):
                            if k in train_metrics:
                                writers[0].add_scalar(k, train_metrics[k], epoch)
                        logger.info(
                            "[%s %3d/%3d] loss=%.4f top1=%.4f%s lr=%.5f",
                            "train", epoch, epochs, train_metrics["loss"], train_metrics["top1"],
                            "".join(f" {k}={train_metrics[k]:.4f}" for k in further),
                            float(lr_fn(int(state.step) - 1)),
                        )

                        result.update({f"{k}_train": v for k, v in train_metrics.items() if k != "num"})
                        result["epoch"] = epoch

                    if epoch % evaluation_interval == 0 or epoch == epochs:
                        with telemetry.stage("evaluate"):
                            evals = evaluate("eval", epoch)
                            for split, m in evals.items():
                                widx = 1 if split.startswith("valid") else 2
                                if split.endswith("_ema"):
                                    tag_suffix = "_ema"
                                elif split.endswith("_raw"):
                                    tag_suffix = "_raw"
                                else:
                                    tag_suffix = ""
                                for k in ("loss", "top1", "top5"):
                                    writers[widx].add_scalar(f"{k}{tag_suffix}", m.get(k, 0.0), epoch)
                                for k, v in m.items():
                                    result[f"{k}_{split}"] = v
                                logger.info("[%s %3d/%3d] %s", split, epoch, epochs,
                                            {k: round(float(v), 4) for k, v in m.items()})

                        if metric == "last":
                            cur = float(epoch)
                        elif metric == "train":
                            cur = train_metrics["top1"]
                        else:
                            cur = evals.get(metric, {}).get("top1", 0.0)
                        if cur >= best_metric:
                            best_metric = cur
                            result["best_valid_top1"] = evals.get("valid", {}).get("top1", 0.0)
                            result["best_test_top1"] = evals.get("test", {}).get("top1", 0.0)
                            if save_path and is_master:
                                with telemetry.stage("checkpoint"):
                                    save_checkpoint(
                                        save_path,
                                        state,
                                        {
                                            "epoch": epoch,
                                            "step": int(state.step),
                                            "metrics": {k: float(v) for k, v in result.items()
                                                        if isinstance(v, (int, float))},
                                        },
                                        keep=ckpt_keep,
                                    )
                        if reporter is not None:
                            reporter(
                                loss_valid=evals.get("valid", {}).get("loss", 0.0),
                                top1_valid=evals.get("valid", {}).get("top1", 0.0),
                                loss_train=train_metrics["loss"],
                                top1_train=train_metrics["top1"],
                                epoch=epoch,
                            )

                    # graceful preemption at the epoch boundary (both feeds usually
                    # caught the flag at a dispatch boundary already; this is the
                    # request that arrived with the epoch's last dispatch or during
                    # its evaluation): checkpoint the COMPLETED epoch with preempted
                    # metadata and exit via the 77 contract
                    if preemption_requested():
                        if save_path and is_master:
                            with telemetry.stage("checkpoint"):
                                save_checkpoint(
                                    save_path, state,
                                    {"epoch": epoch, "step": int(state.step),
                                     "preempted": True,
                                     "metrics": {k: float(v) for k, v in result.items()
                                                 if isinstance(v, (int, float))}},
                                    keep=ckpt_keep)
                        logger.warning(
                            "preempted at epoch %d boundary — checkpointed, exit %d "
                            "means 'resume me'", epoch, PREEMPTED_EXIT_CODE)
                        raise PreemptedError(f"preempted after epoch {epoch}")
                    epoch += 1

        result["elapsed_sec"] = wall() - t_start
        result.update(steps=int(state.step), **device_stamp())
        # compile-tax evidence (hit/miss counts + per-label first-call
        # seconds through the seam): a resumed/warm process proves here
        # that it reached its first step in seconds, not minutes
        result["compile_cache"] = compile_cache_stats()
        for w in writers:
            w.close()
        result["stages"] = root.summary()
        return result


def train_folds_stacked(
    conf,
    dataroot: str,
    *,
    cv_ratio: float,
    folds: list[int],
    save_paths: list[str],
    seed: int = 0,
    seeds: list[int] | None = None,
    evaluation_interval: int = 5,
    mesh=None,
    resume: bool = True,
    aug_dispatch: str = "exact",
    aug_groups: int = 8,
    device_cache: str = "auto",
    steps_per_dispatch: int = 1,
    ckpt_keep: int = 2,
    watchdog="off",
    heartbeat: Callable | None = None,
) -> dict[int, dict]:
    """Train K phase-1 fold models as ONE vmapped program per step.

    The fold-stacked counterpart of calling :func:`train_and_eval` once
    per fold with ``test_ratio=cv_ratio, cv_fold=fold, metric='last'``:
    all K fold states (params, batch_stats, opt_state, per-fold PRNG)
    advance together through :func:`make_stacked_train_step`, fed by
    :func:`stacked_train_batches` gathering the K per-fold shuffled
    index streams out of the ONE shared dataset.  The fold axis is a
    pure vmap of the sequential step body and each fold's data and key
    streams are reproduced exactly, so the stacked computation is the
    sequential one per fold — up to a measured ~1 f32 ULP/step kernel
    reduction-order difference (vmap lowers to batched conv/matmul
    kernels), which training dynamics amplify over a run exactly as the
    repo's documented single-vs-multi-device drift is amplified
    (tests/test_train.py::test_train_step_single_vs_eight_devices).
    The seeded equivalence test pins the bound at short horizons and
    checks eval-metric agreement at run end
    (tests/test_stacked_phase1.py); docs/PARITY.md "Step dispatch &
    device cache" records the deviation class.

    `mesh` defaults to :func:`make_fold_mesh` over all devices — folds
    shard across device groups when the counts divide (the per-fold
    global batch is then ``conf['batch'] x data_axis_size``; see
    `make_fold_mesh`).  `seeds` gives per-fold seeds (default: `seed`
    for every fold, matching the sequential phase-1 loop).  Per-fold
    checkpoints save/restore through :func:`slice_state` under the
    caller-supplied paths — the same layout the sequential path writes,
    so resume, the fold-oracle gate, and single-fold retrains consume
    them unchanged.  Returns ``{fold: result_dict}`` with the
    :func:`train_and_eval`-shaped per-fold metrics.

    In-memory datasets only: lazy (on-disk) datasets fall back to the
    sequential path in the search driver (per-fold host decode streams
    cannot be multiplexed bit-for-bit; ``stacked_train_batches``
    docstring).

    ``device_cache``/``steps_per_dispatch`` compose with the stack: the
    shared dataset is uploaded once, the multiplexed ``[steps, K, B]``
    index matrix replaces the image feed, and one ``lax.scan`` dispatch
    advances K folds x N steps (the scan sits outside the fold vmap —
    ``make_multistep_train_step``).  The dataset here is always eager
    (checked above), so "auto" enables the cache on single-process runs.

    Resilience (docs/RESILIENCE.md): a SIGTERM/SIGUSR1 preemption
    request is honored at the next dispatch-chunk boundary (cache path
    — every active fold checkpoints its slice with ``preempted: true``
    + the mid-epoch position, resumable bit-identically) or epoch
    boundary (host path), then :class:`PreemptedError` carries the
    exit-77 contract up.  ``ckpt_keep`` bounds each fold's rollback
    chain; restore walks to the newest intact link.  ``watchdog`` /
    ``heartbeat`` follow the :func:`train_and_eval` contract
    (deadline-guarded dispatches; lease renewal per dispatch/epoch
    boundary).
    """
    configure_compile_cache()
    if len(folds) != len(save_paths):
        raise ValueError(f"{len(folds)} folds but {len(save_paths)} paths")
    num_folds = len(folds)
    if seeds is None:
        seeds = [seed] * num_folds
    if mesh is None:
        mesh = make_fold_mesh(num_folds)
    data_size = mesh.shape["data"]
    is_master = jax.process_index() == 0
    t_start = wall()

    dataset_name = conf["dataset"]
    num_classes = num_class(dataset_name)
    total_train, testset = load_dataset(dataset_name, dataroot)
    if total_train.lazy:
        raise ValueError(
            "train_folds_stacked supports in-memory datasets only; "
            f"{dataset_name!r} is lazy — use the sequential per-fold path")

    fold_train_idx, fold_valid_idx = [], []
    for fold in folds:
        tr, va = cv_split(total_train.labels, cv_ratio, fold)
        fold_train_idx.append(tr)
        fold_valid_idx.append(va)

    from fast_autoaugment_tpu.models import input_image_size

    image = int(conf.get("imgsize", 0) or 0) or input_image_size(
        dataset_name, conf["model"]["type"]
    )
    batch_per_device = int(conf["batch"])
    global_batch = batch_per_device * data_size
    logger.info("stacked: %d folds on mesh %s over %d %s device(s); "
                "per-fold global batch %d", num_folds, dict(mesh.shape),
                mesh.size, mesh.devices.flat[0].platform, global_batch)
    for fold, tr in zip(folds, fold_train_idx):
        if len(tr) < global_batch:
            raise ValueError(
                f"fold {fold} has {len(tr)} train examples < per-fold "
                f"global batch {global_batch} — every epoch would be empty")
    step_counts = {len(tr) // global_batch for tr in fold_train_idx}
    if len(step_counts) != 1:
        # the LR schedule is baked into the ONE shared optimizer as a
        # pure function of the step; folds with different step counts
        # need per-fold schedules the stack cannot represent
        raise ValueError(
            f"folds disagree on steps/epoch ({sorted(step_counts)}) — "
            "train them sequentially instead")
    steps_per_epoch = step_counts.pop()
    epochs = int(conf["epoch"])

    model_conf = dict(conf["model"], dataset=dataset_name)
    model_conf.setdefault("precision", conf.get("precision", "f32"))
    model = get_model(model_conf, num_classes)
    lr_fn = build_schedule(conf, steps_per_epoch, world_lr_scale=float(data_size))
    optimizer_conf = conf["optimizer"]
    ema_mu = float(optimizer_conf.get("ema", 0.0) or 0.0)
    optimizer = build_optimizer(optimizer_conf, lr_fn)

    sample = jnp.zeros((2, image, image, 3), jnp.float32)
    policy = resolve_policy_tensor(conf.get("aug", "default"))
    use_policy = policy is not None
    pol = policy if policy is not None else jnp.zeros((1, 1, 3), jnp.float32)

    use_cache = resolve_device_cache(device_cache, total_train,
                                     process_count=jax.process_count())
    steps_per_dispatch = int(steps_per_dispatch)
    if steps_per_dispatch > 1 and not use_cache:
        raise ValueError(
            f"steps_per_dispatch={steps_per_dispatch} needs the device "
            "cache (in-program batch gather) — use --device-cache auto/on")
    step_kw = dict(
        num_classes=num_classes,
        mixup_alpha=float(conf.get("mixup", 0.0) or 0.0),
        lb_smooth=float(conf.get("lb_smooth", 0.0) or 0.0),
        ema_mu=ema_mu,
        cutout_length=int(conf.get("cutout", 0) or 0),
        use_policy=use_policy,
        aug_dispatch=aug_dispatch,
        aug_groups=aug_groups,
    )
    if use_cache:
        stacked_body = make_stacked_step_body(model, optimizer, **step_kw)
        multi_fns: dict[int, Callable] = {}

        def get_multi_step(n: int) -> Callable:
            if n not in multi_fns:
                multi_fns[n] = make_multistep_train_step(
                    stacked_body, steps_per_dispatch=n, stacked=True)
            return multi_fns[n]
    else:
        stacked_step = make_stacked_train_step(model, optimizer, **step_kw)
    eval_step = make_eval_step(
        model, num_classes=num_classes,
        lb_smooth=float(conf.get("lb_smooth", 0.0) or 0.0),
    )
    replay_eval = make_replay_eval_step(
        model, num_classes=num_classes,
        lb_smooth=float(conf.get("lb_smooth", 0.0) or 0.0),
    ) if use_cache else None

    ckpt_keep = max(1, int(ckpt_keep))
    wd = resolve_watchdog(watchdog)
    install_signal_handlers()

    # per-fold init/restore (newest intact chain link), then one
    # stacked state
    states, epoch_starts, fold_metas = [], [], []
    for k, (fold, path) in enumerate(zip(folds, save_paths)):
        state = create_train_state(
            model, optimizer, jax.random.PRNGKey(seeds[k]), sample,
            use_ema=ema_mu > 0.0,
        )
        epoch_start, meta = 1, {}
        if resume and path:
            got = load_checkpoint_chain(path, state, keep=ckpt_keep)
            if got is not None:
                state, meta, used = got
                epoch_start = int(meta.get("epoch", 0)) + 1
                logger.info(
                    "stacked: resumed fold %d at epoch %d%s", fold,
                    epoch_start - 1,
                    " (mid-epoch snapshot)" if "in_epoch" in meta else "")
        states.append(state)
        epoch_starts.append(epoch_start)
        fold_metas.append(meta)

    # mid-epoch (preempted) snapshots fast-forward the stacked dispatch
    # loop only when EVERY restored record agrees on (epoch, pos) and
    # the device-cache index feed is active (positions can be skipped);
    # otherwise each mid-epoch fold falls back to its epoch-boundary
    # chain link — losing at most the interrupted epoch, never
    # silently double-training it
    in_epoch_recs = [m.get("in_epoch") for m in fold_metas]
    stk_resume_pos, stk_resume_epoch, stk_resume_sums = 0, -1, None
    if any(in_epoch_recs):
        ref = next(r for r in in_epoch_recs if r)
        agree = use_cache and all(
            (r is not None and r.get("epoch") == ref["epoch"]
             and r.get("pos") == ref["pos"])
            or (r is None and epoch_starts[k] > int(ref["epoch"]))
            for k, r in enumerate(in_epoch_recs))
        if agree:
            stk_resume_pos = int(ref["pos"])
            stk_resume_epoch = int(ref["epoch"])
            sum_keys = sorted({kk for r in in_epoch_recs if r
                               for kk in (r.get("sums") or {})})
            stk_resume_sums = {
                kk: np.asarray(
                    [(r.get("sums") or {}).get(kk, 0.0) if r else 0.0
                     for r in in_epoch_recs], np.float32)
                for kk in sum_keys}
            logger.info(
                "stacked: resuming MID-EPOCH at epoch %d, dispatch "
                "position %d", stk_resume_epoch, stk_resume_pos)
        else:
            for k, r in enumerate(in_epoch_recs):
                if r is None:
                    continue
                logger.warning(
                    "stacked: fold %d mid-epoch snapshot unusable here "
                    "(position disagreement or host feed) — falling back "
                    "to its epoch-boundary chain link", folds[k])
                got = load_checkpoint_chain(
                    save_paths[k], states[k], keep=ckpt_keep,
                    accept=lambda m: "in_epoch" not in m)
                if got is not None:
                    states[k], meta_k, _used = got
                    epoch_starts[k] = int(meta_k.get("epoch", 0)) + 1
                else:
                    states[k] = create_train_state(
                        model, optimizer, jax.random.PRNGKey(seeds[k]),
                        sample, use_ema=ema_mu > 0.0)
                    epoch_starts[k] = 1
    stacked = stack_states(states)
    del states
    # shard every state leaf's leading fold axis over the mesh fold
    # axis (a no-op layout on fold_shards=1 meshes): folds live on
    # their own device groups instead of replicating
    from jax.sharding import NamedSharding, PartitionSpec

    fold_placed = NamedSharding(mesh, PartitionSpec("fold"))
    stacked = jax.device_put(stacked, fold_placed)
    keys = jax.device_put(
        jnp.stack([jax.random.PRNGKey(s) for s in seeds]), fold_placed)

    valid_its = [BatchIterator(total_train, va) for va in fold_valid_idx]
    test_it = BatchIterator(testset)
    writers = [
        make_writers(os.path.dirname(p) if p else None,
                     os.path.basename(p or "run"), is_master)
        for p in save_paths
    ]
    results: dict[int, dict] = {
        fold: {"epoch": epoch_starts[k] - 1} for k, fold in enumerate(folds)
    }

    # device-cache eval replay: valid splits are per fold, the test
    # split is shared — each placed once, reused every eval epoch
    eval_replay: dict = {}

    def evaluate_fold(k: int, state_k) -> dict:
        out = {}
        eval_kw = dict(
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            pad_multiple=data_size,
        )
        for split, it in (("valid", valid_its[k]), ("test", test_it)):
            if len(it) == 0:
                continue
            if use_cache:
                ck = ("test",) if split == "test" else ("valid", k)
                if ck not in eval_replay:
                    eval_replay[ck] = _stacked_eval_splits(
                        it, global_batch, mesh, eval_kw)
                out[split] = _run_replay_eval(
                    replay_eval, state_k.params, state_k.batch_stats,
                    eval_replay[ck], wd=wd)
            else:
                out[split] = _run_eval(
                    eval_step, state_k.params, state_k.batch_stats,
                    it.eval_epoch(global_batch, **eval_kw), mesh,
                )
        return out

    train_cache = DeviceCache(total_train, mesh) if use_cache else None
    if train_cache is not None:
        logger.info(
            "stacked device cache: %d examples (%.1f MiB) resident as "
            "%s %s, steps_per_dispatch=%d", train_cache.num_examples,
            train_cache.nbytes / 2**20, train_cache.images.dtype,
            list(train_cache.images.shape), steps_per_dispatch)
        # the stacked state/keys are already mesh-committed (fold
        # placement above); the policy tensor must be too, or the first
        # compile pins a mixed-commitment signature that knocks later
        # dispatches off the C++ fast path (make_multistep_train_step)
        pol = jax.device_put(pol, replicated(mesh))
    first_epoch = min(epoch_starts)
    transform = stacked_shard_transform(mesh)
    for epoch in range(first_epoch, epochs + 1):
        fi = faultinject.active_plan()
        epoch_active = np.asarray(
            [1.0 if epoch >= epoch_starts[k] else 0.0
             for k in range(num_folds)], np.float32)
        ep_act_dev = jnp.asarray(epoch_active)

        def _save_fold_slices(meta_fn):
            """Checkpoint every active fold's slice (master only)."""
            if not is_master:
                return
            for k2 in range(num_folds):
                if not epoch_active[k2] or not save_paths[k2]:
                    continue
                save_checkpoint(save_paths[k2], slice_state(stacked, k2),
                                meta_fn(k2), keep=ckpt_keep)

        # per-fold sums stay DEVICE-side [K] vectors until epoch end —
        # reading them per batch would sync the dispatch pipeline (the
        # same discipline as the sequential epoch loop)
        epoch_sums: dict | None = None
        if train_cache is not None:
            chunks, act = stacked_index_matrix(
                fold_train_idx, global_batch, epoch, seeds=seeds,
                process_index=jax.process_index(),
                process_count=jax.process_count(),
            )
            act = act * epoch_active[None, :]
            pos = 0
            dispatch_metrics: list = []
            if stk_resume_pos and epoch == stk_resume_epoch:
                # preempted mid-epoch: skip the completed dispatches and
                # seed the per-fold f32 sum chain (bit-identical
                # continuation, as in the sequential trainer)
                pos = stk_resume_pos
                if stk_resume_sums:
                    dispatch_metrics.append(dict(stk_resume_sums))
            for n in split_dispatch_chunks(len(chunks) - pos,
                                           steps_per_dispatch):
                idx_dev, act_dev = place_stacked_index_matrix(
                    mesh, chunks[pos:pos + n], act[pos:pos + n])
                stacked, metrics = _monitored_dispatch(
                    wd, "stacked_dispatch", fi,
                    (epoch - 1) * steps_per_epoch + pos + n,
                    get_multi_step(n),
                    stacked, train_cache.images, train_cache.labels,
                    idx_dev, pol, keys, act_dev)
                # async device handles, host-summed at epoch end — a
                # per-dispatch device add of [K] committed vectors is an
                # all-participant collective; chains of those wedge the
                # CPU backend (_sum_metric_dicts / make_replay_eval_step)
                dispatch_metrics.append(metrics)
                pos += n
                _beat(heartbeat)
                if fi is not None:
                    fi.maybe_signal((epoch - 1) * steps_per_epoch + pos)
                if preemption_requested() and pos < len(chunks):
                    # dispatch-boundary preemption: every active fold
                    # checkpoints its slice with the shared mid-epoch
                    # position, then the 77 contract goes up
                    sums = _sum_metric_dicts(dispatch_metrics)
                    _save_fold_slices(lambda k2: {
                        "epoch": epoch - 1,
                        "step": (epoch - 1) * steps_per_epoch + pos,
                        "preempted": True,
                        "in_epoch": {
                            "epoch": epoch, "pos": pos,
                            "sums": {kk: float(np.asarray(v)[k2])
                                     for kk, v in sums.items()}}})
                    logger.warning(
                        "stacked: preempted at epoch %d dispatch boundary "
                        "(position %d/%d) — %d fold slice(s) checkpointed, "
                        "exit %d means 'resume me'", epoch, pos,
                        len(chunks), int(epoch_active.sum()),
                        PREEMPTED_EXIT_CODE)
                    raise PreemptedError(
                        f"stacked preempted mid-epoch {epoch} at dispatch "
                        f"position {pos}")
            if dispatch_metrics:
                epoch_sums = _sum_metric_dicts(dispatch_metrics)
        else:
            batches = prefetch(
                stacked_train_batches(
                    total_train, fold_train_idx, global_batch, epoch,
                    seeds=seeds,
                    process_index=jax.process_index(),
                    process_count=jax.process_count(),
                ),
                transform=transform,
            )
            for bi, batch in enumerate(batches):
                active = batch["a"] * ep_act_dev
                stacked, metrics = _monitored_dispatch(
                    wd, "stacked_step", fi,
                    (epoch - 1) * steps_per_epoch + bi + 1,
                    stacked_step,
                    stacked, batch["x"], batch["y"], pol, keys, active)
                epoch_sums = metrics if epoch_sums is None else {
                    kk: epoch_sums[kk] + metrics[kk] for kk in epoch_sums}
                _beat(heartbeat)
                if fi is not None:
                    fi.maybe_signal((epoch - 1) * steps_per_epoch + bi + 1)
        host_sums = {kk: np.asarray(v)
                     for kk, v in (epoch_sums or {}).items()}

        for k, fold in enumerate(folds):
            if not epoch_active[k]:
                continue
            num = float(host_sums["num"][k]) if host_sums else 0.0
            if num <= 0:
                raise RuntimeError(
                    f"stacked epoch {epoch} produced zero batches for fold "
                    f"{fold} — feed pipeline bug")
            train_metrics = {
                kk: float(host_sums[kk][k]) / num
                for kk in ("loss", "top1", "top5")}
            train_metrics["num"] = num
            if np.isnan(train_metrics["loss"]):
                raise RuntimeError(
                    f"fold {fold} loss is NaN — training diverged")
            for kk in ("loss", "top1", "top5"):
                writers[k][0].add_scalar(kk, train_metrics[kk], epoch)
            logger.info(
                "[stacked fold %d %3d/%3d] loss=%.4f top1=%.4f", fold,
                epoch, epochs, train_metrics["loss"], train_metrics["top1"],
            )
            results[fold].update(
                {f"{kk}_train": v for kk, v in train_metrics.items()
                 if kk != "num"})
            results[fold]["epoch"] = epoch

            if epoch % evaluation_interval == 0 or epoch == epochs:
                state_k = slice_state(stacked, k)
                evals = evaluate_fold(k, state_k)
                for split, m in evals.items():
                    widx = 1 if split.startswith("valid") else 2
                    for kk in ("loss", "top1", "top5"):
                        writers[k][widx].add_scalar(kk, m.get(kk, 0.0), epoch)
                    for kk, v in m.items():
                        results[fold][f"{kk}_{split}"] = v
                    logger.info(
                        "[stacked fold %d %s %3d/%3d] %s", fold, split,
                        epoch, epochs,
                        {kk: round(float(v), 4) for kk, v in m.items()})
                # metric='last' semantics (the phase-1 contract): every
                # eval epoch is the new best, checkpoint it
                results[fold]["best_valid_top1"] = evals.get(
                    "valid", {}).get("top1", 0.0)
                results[fold]["best_test_top1"] = evals.get(
                    "test", {}).get("top1", 0.0)
                if save_paths[k] and is_master:
                    save_checkpoint(
                        save_paths[k],
                        state_k,
                        {
                            "epoch": epoch,
                            "step": int(state_k.step),
                            "metrics": {kk: float(v)
                                        for kk, v in results[fold].items()
                                        if isinstance(v, (int, float))},
                        },
                        keep=ckpt_keep,
                    )

        # epoch-boundary preemption (the host path's only safe point):
        # checkpoint every active fold's COMPLETED epoch, exit via 77
        if preemption_requested():
            _save_fold_slices(lambda k2: {
                "epoch": epoch,
                "step": int(slice_state(stacked, k2).step),
                "preempted": True})
            logger.warning(
                "stacked: preempted at epoch %d boundary — checkpointed, "
                "exit %d means 'resume me'", epoch, PREEMPTED_EXIT_CODE)
            raise PreemptedError(f"stacked preempted after epoch {epoch}")

    elapsed = wall() - t_start
    cc = compile_cache_stats()
    logger.info("stacked: compile cache dir=%s hits=%d misses=%d "
                "first_step_secs=%.3f", cc["dir"], cc["hits"], cc["misses"],
                cc["first_step_secs"])
    for k, fold in enumerate(folds):
        results[fold]["elapsed_sec"] = elapsed
        results[fold]["compile_cache"] = cc
        for w in writers[k]:
            w.close()
    return results
