"""Epoch driver: the ``train_and_eval`` equivalent.

Mirrors the reference driver's contract (``train.py:110-322``): builds
data/model/optimizer/schedule, restores checkpoints, runs the epoch
loop with periodic evaluation (master-only), tracks the best metric,
reports progress to a callback (the search engine's hook,
``train.py:289-303``) and saves checkpoints with cheap metadata.

In the order a call runs: what a data set needs (:class:`_DataPlan`,
:func:`_build_programs`); evaluation and records; the feeds and THE dispatch
loop (:func:`_dispatch_loop`); restore; then the two entry points, each over a
class (:class:`_Run`, :class:`_Stack`: set-up, then the pieces of an epoch).

Differences by design, not omission:
- the per-batch work is ONE jitted step on the global mesh batch (no
  DDP wrapper, no host-side EMA loop, no H2D copy per tensor);
- the LR schedule is a pure function of the step baked into the
  optimizer, not a stateful scheduler stepped per batch;
- checkpoint progress metadata is readable without deserializing
  weights (``core/checkpoint.py``), which the search driver polls.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import types
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from fast_autoaugment_tpu.core.checkpoint import (
    load_checkpoint_chain,
    read_metadata,
    save_checkpoint,
)
from fast_autoaugment_tpu.core.compilecache import (
    compile_cache_stats,
    configure_compile_cache,
    roomy,
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
from fast_autoaugment_tpu.data.datasets import cv_split, is_token_dataset, load_dataset
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
from fast_autoaugment_tpu.models import (
    get_model,
    input_image_size,
    model_conf_of,
    num_class,
)
from fast_autoaugment_tpu.ops import preprocess_imagenet as imagenet
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
from fast_autoaugment_tpu.train import steps
from fast_autoaugment_tpu.train.steps import COUNT_PREFIX, slice_state
from fast_autoaugment_tpu.utils import faultinject
from fast_autoaugment_tpu.utils.logging import get_logger, make_writers

__all__ = ["train_and_eval", "train_folds_stacked", "resolve_policy_tensor"]

logger = get_logger("faa_tpu.train")


def _policy_applies(aug: Any) -> bool:
    """Whether conf['aug'] names an augmentation policy ('default'/None: no)."""
    return aug not in (None, "default")


@dataclasses.dataclass(frozen=True)
class _DataPlan:
    """One data set as the trainer sees it."""

    total_train: Any
    testset: Any
    num_classes: int
    sample: jax.ShapeDtypeStruct  # the zeros that initialise the model
    it_kw: dict              # what a BatchIterator of this data set takes
    step_body: Callable      # (model, optimizer, **step_kw(options)) -> unjitted body
    train_step: Callable     # (model, optimizer, **step_kw(options)) -> jitted step
    eval_kw: dict            # what the evaluation makers take for this data set
    step_kw: Callable = dict  # the step options (_build_programs) -> what the two makers take
    jit_init: bool = False   # the state's init as one program (steps.create_train_state)
    publishes_counts: bool = False  # the step keeps count sums (_CountPublisher)
    # one split of it (:meth:`split`)
    train_idx: Any = None
    train_it: Any = None
    valid_it: Any = None
    test_it: Any = None

    def split(self, test_ratio: float = 0.0, cv_fold: int = 0,
              target_lb: int = -1) -> "_DataPlan":
        """This plan with fold `cv_fold`'s train/valid indices and the
        three iterators (``test_ratio`` 0: everything trains)."""
        train = self.total_train
        if test_ratio > 0.0:
            train_idx, valid_idx = cv_split(train.labels, test_ratio, cv_fold)
            if target_lb >= 0:
                # single-class restriction (reference data.py:199-201)
                train_idx = train_idx[train.labels[train_idx] == target_lb]
                valid_idx = valid_idx[train.labels[valid_idx] == target_lb]
        else:
            train_idx, valid_idx = np.arange(len(train)), np.array([], np.int64)
        return dataclasses.replace(
            self, train_idx=train_idx,
            train_it=BatchIterator(train, train_idx, **self.it_kw),
            valid_it=BatchIterator(train, valid_idx, **self.it_kw),
            test_it=BatchIterator(self.testset, **self.it_kw))


def _eager_images(conf, total_train, testset) -> _DataPlan:
    """CIFAR, SVHN, the synthetic sets: arrays in memory, the default
    augmentation stack of ``train/steps.py``."""
    # conf['imgsize'] overrides the native resolution (the reference
    # evaluates ResNet-200 at 320px, README.md:44-46)
    image = int(conf.get("imgsize", 0) or 0) or input_image_size(
        conf["dataset"], conf["model"]["type"])
    return _DataPlan(
        total_train, testset, num_classes=num_class(conf["dataset"]),
        sample=jax.ShapeDtypeStruct((2, image, image, 3), jnp.float32),
        it_kw=dict(imgsize=image), step_body=steps.make_train_step_body,
        train_step=steps.make_train_step, eval_kw={})


def _imagenet_step_kw(options: dict) -> dict:
    """The step options with the ImageNet stack as the step's augmentation."""
    def augment_fn(images, pol, key):
        return imagenet.imagenet_train_batch(
            images, key, pol if options["use_policy"] else None,
            cutout_length=options["cutout_length"],
            aug_dispatch=options["aug_dispatch"], aug_groups=options["aug_groups"])
    return dict(options, augment_fn=augment_fn)


def _lazy_images(conf, total_train, testset) -> _DataPlan:
    """ImageNet: files decoded by the host inside a crop box, the ImageNet
    stack on the device."""
    plan = _eager_images(conf, total_train, testset)
    image = plan.it_kw["imgsize"]
    return dataclasses.replace(
        plan,
        it_kw=dict(
            train_box_fn=lambda rng, w, h: imagenet.random_crop_box(rng, w, h, image),
            eval_box_fn=lambda rng, w, h: imagenet.center_crop_box(w, h, image),
            imgsize=image),
        step_kw=_imagenet_step_kw,
        eval_kw=dict(preprocess_fn=imagenet.imagenet_eval_batch))


def _tokens(conf, total_train, testset) -> _DataPlan:
    """Ids in, next-token loss: the classes are the ids the model holds."""
    if _policy_applies(conf.get("aug", "default")):
        raise ValueError(
            f"dataset {conf['dataset']!r} is a token data set and conf aug="
            f"{conf['aug']!r} names an augmentation policy: policies "
            "are image operations; use aug: default")
    model_conf = model_conf_of(conf)
    num_classes = int(model_conf.get("ids_held")
                      or model_conf.get("vocab_size") or 0)
    ids = max(total_train.num_classes, testset.num_classes)
    if not 0 < ids <= num_classes:
        raise ValueError(
            f"the data set holds ids up to {ids - 1}, the model "
            f"{num_classes} ids (conf ids_held, else model.vocab_size)")
    # parameter shapes do not depend on the length: a short sample, of the
    # shortest length at which every operation takes the form a step's
    # length takes (ops/attention.py: two tiles of 128)
    length = min(total_train.images.shape[1] - 1, 256)
    return _DataPlan(
        total_train, testset, num_classes=num_classes,
        sample=jax.ShapeDtypeStruct((1, length), jnp.int32), it_kw={},
        step_body=steps.make_token_step_body,
        train_step=steps.make_token_train_step,
        # of the step options a token step takes the EMA rate alone
        step_kw=lambda options: {"ema_mu": options["ema_mu"]},
        eval_kw=dict(tokens=True), jit_init=True, publishes_counts=True)


def _load_plan(conf, dataroot: str, kind: Callable | None = None) -> _DataPlan:
    """Load conf['dataset'] (stage ``load_dataset``) and build its plan,
    unsplit.  `kind` fixes the constructor (the fold-stacked trainer takes
    :func:`_eager_images` only); by default the name chooses it."""
    name = conf["dataset"]
    with telemetry.stage("load_dataset"):
        total_train, testset = load_dataset(name, dataroot)
    if kind is None:
        kind = (_tokens if is_token_dataset(name) else
                _lazy_images if name.endswith("imagenet") else _eager_images)
    return kind(conf, total_train, testset)


@dataclasses.dataclass
class _Programs:
    """What a feed dispatches and evaluates with.  The cached feed: the
    step `body` behind :meth:`multi_step`, and `replay_eval`; the host
    feed: `train_step` and `eval_step`."""

    body: Callable | None = None
    stacked: bool = False
    replay_eval: Callable | None = None
    train_step: Callable | None = None
    eval_step: Callable | None = None
    _multi: dict = dataclasses.field(default_factory=dict)

    def multi_step(self, n: int) -> Callable:
        """The program that gathers and trains `n` steps in one dispatch:
        at most two chunk shapes an epoch (N and the clamped remainder),
        each compiled once and reused."""
        if n not in self._multi:
            self._multi[n] = steps.make_multistep_train_step(
                self.body, steps_per_dispatch=n, stacked=self.stacked)
        return self._multi[n]


def _build_programs(plan: _DataPlan, conf, model, optimizer, *, use_cache: bool,
                    aug_dispatch: str = "exact", aug_groups: int = 8,
                    stacked: bool = False) -> _Programs:
    """The programs of one feed (`use_cache`: the device-resident one) for
    `plan`'s data set, and THE place the step options are decided; `stacked`
    takes the fold-stacked step makers (eager images only) behind the table."""
    lb_smooth = float(conf.get("lb_smooth", 0.0) or 0.0)
    step_kw = plan.step_kw(dict(
        num_classes=plan.num_classes,
        mixup_alpha=float(conf.get("mixup", 0.0) or 0.0),
        lb_smooth=lb_smooth,
        ema_mu=float(conf["optimizer"].get("ema", 0.0) or 0.0),
        cutout_length=int(conf.get("cutout", 0) or 0),
        use_policy=_policy_applies(conf.get("aug", "default")),
        aug_dispatch=aug_dispatch,
        aug_groups=aug_groups,
    ))
    eval_kw = dict(num_classes=plan.num_classes, lb_smooth=lb_smooth,
                   **plan.eval_kw)
    if use_cache:
        make_body = steps.make_stacked_step_body if stacked else plan.step_body
        return _Programs(body=make_body(model, optimizer, **step_kw),
                         stacked=stacked,
                         replay_eval=steps.make_replay_eval_step(model, **eval_kw))
    make_step = steps.make_stacked_train_step if stacked else plan.train_step
    return _Programs(train_step=make_step(model, optimizer, **step_kw),
                     eval_step=steps.make_eval_step(model, **eval_kw))


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
    if not _policy_applies(aug):
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
    eval.  (The device cache evaluates by replay: :class:`_Evaluator`.)"""
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


class _Evaluator:
    """The evaluation of both entry points, through the feed's program.  On
    the device cache each split is placed once, on its first evaluation,
    and replayed in one fused dispatch per shape group by every later one
    and by the EMA pass (``search/tta.py::eval_tta``'s upload-once
    discipline applied to training eval)."""

    def __init__(self, programs: _Programs, mesh, global_batch: int,
                 pad_multiple: int, wd):
        self.programs, self.mesh, self.wd = programs, mesh, wd
        self.global_batch, self.pad_multiple = global_batch, pad_multiple
        self.placed: dict = {}

    def _split(self, params, batch_stats, key, it) -> dict:
        eval_kw = dict(process_index=jax.process_index(),
                       process_count=jax.process_count(),
                       pad_multiple=self.pad_multiple)
        if self.programs.replay_eval is None:
            return _run_eval(self.programs.eval_step, params, batch_stats,
                             it.eval_epoch(self.global_batch, **eval_kw),
                             self.mesh)
        if key not in self.placed:
            self.placed[key] = _stacked_eval_splits(
                it, self.global_batch, self.mesh, eval_kw)
        return _run_replay_eval(self.programs.replay_eval, params,
                                batch_stats, self.placed[key], wd=self.wd)

    def __call__(self, state, plan: _DataPlan, *, ema: bool = True,
                 fold=None) -> dict:
        """`plan`'s valid and test splits (the stack's valid splits are per
        `fold`, its test split is shared).  Empty splits are SKIPPED, not
        reported as zeros: with test_ratio=0 (every phase-3 search retrain)
        a zero-row per interval is pure noise, and ``metric="valid"`` would
        silently track a best of 0.0 (the reference only ever evaluates
        real splits, train.py:272-280)."""
        out = {}
        for split, key, it in (("valid", ("valid", fold), plan.valid_it),
                               ("test", "test", plan.test_it)):
            if len(it) == 0:
                continue
            out[split] = norm = self._split(
                state.params, state.batch_stats, key, it)
            if ema and state.ema is not None:
                # with EMA on, the REPORTED valid/test numbers are the EMA
                # model's (reference train.py:277-280 overwrites
                # rs['valid']/rs['test']); raw weights kept under _raw
                out[split + "_raw"] = norm
                out[split] = out[split + "_ema"] = self._split(
                    state.ema["params"], state.ema["batch_stats"], key, it)
        return out


def _record_train(result: dict, writer, tag: str, epoch: int, epochs: int,
                  train_metrics: dict, lr: float | None = None) -> None:
    """An epoch's train metrics into the scalar writer, the log and `result`."""
    # a token model reports no top-5, and may report further sums of its
    # own (a second loss term)
    further = sorted(set(train_metrics) - {"loss", "top1", "top5", "num"})
    for k in ("loss", "top1", "top5", *further):
        if k in train_metrics:
            writer.add_scalar(k, train_metrics[k], epoch)
    logger.info(
        "[%s %3d/%3d] loss=%.4f top1=%.4f%s%s", tag, epoch, epochs,
        train_metrics["loss"], train_metrics["top1"],
        "".join(f" {k}={train_metrics[k]:.4f}" for k in further),
        "" if lr is None else f" lr={lr:.5f}")
    result.update({f"{k}_train": v for k, v in train_metrics.items()
                   if k != "num"})
    result["epoch"] = epoch


def _record_evals(result: dict, writers: list, tag: str, epoch: int,
                  epochs: int, evals: dict) -> None:
    """An evaluation into the split's writer, the log and `result`."""
    for split, m in evals.items():
        widx = 1 if split.startswith("valid") else 2
        suffix = split[-4:] if split.endswith(("_ema", "_raw")) else ""
        for k in ("loss", "top1", "top5"):
            writers[widx].add_scalar(f"{k}{suffix}", m.get(k, 0.0), epoch)
        for k, v in m.items():
            result[f"{k}_{split}"] = v
        logger.info("[%s%s %3d/%3d] %s", tag, split, epoch, epochs,
                    {k: round(float(v), 4) for k, v in m.items()})


def _ckpt_meta(epoch: int, step: int, *, preempted: bool | None = None,
               in_epoch: dict | None = None, result: dict | None = None) -> dict:
    """A checkpoint's cheap metadata: the last COMPLETED epoch and the
    step; `in_epoch` the position inside the next one (a mid-epoch
    snapshot), `result` the metrics so far."""
    meta: dict = {"epoch": epoch, "step": step}
    if preempted is not None:
        meta["preempted"] = preempted
    if in_epoch is not None:
        meta["in_epoch"] = in_epoch
    if result is not None:
        meta["metrics"] = {k: float(v) for k, v in result.items()
                           if isinstance(v, (int, float))}
    return meta


class _Progress:
    """Live per-batch progress (the reference's tqdm postfix,
    train.py:79-88): FAA_PROGRESS=N prints a loss-EMA line every N batches
    (dispatches on the cache path).  Off by default — reading metrics per
    batch forces a device sync and stalls the dispatch pipeline, which is
    why the epoch loop otherwise never touches metric values mid-epoch."""

    def __init__(self, epoch: int, is_master: bool):
        self.epoch, self.loss_ema = epoch, None
        try:
            every = int(os.environ.get("FAA_PROGRESS", "0") or 0)
        except ValueError:  # cosmetic knob must never kill a run — but
            # the misconfiguration must be VISIBLE, not silently eaten
            logger.warning("FAA_PROGRESS=%r is not an integer — live progress "
                           "line disabled", os.environ.get("FAA_PROGRESS"))
            every = 0
        self.every = every if is_master else 0

    def __call__(self, bi: int, metrics) -> None:
        if self.every and (bi + 1) % self.every == 0:
            cur = float(metrics["loss"]) / max(float(metrics["num"]), 1.0)
            self.loss_ema = (cur if self.loss_ema is None
                             else 0.9 * self.loss_ema + 0.1 * cur)
            sys.stderr.write(f"\r[epoch {self.epoch} batch {bi + 1}] "
                             f"loss_ema={self.loss_ema:.4f} ")
            sys.stderr.flush()

    def close(self) -> None:
        if self.every and self.loss_ema is not None:
            sys.stderr.write("\n")


def _stopped(tag: str, epoch: int, pos: int | None = None, total: int = 0,
             note: str = "") -> PreemptedError:
    """A graceful stop, logged (`tag` "" or "stacked"): mid-epoch at
    dispatch position `pos` of `total`, or (no `pos`) at `epoch`'s boundary."""
    log, err = (f"{tag}: ", f"{tag} ") if tag else ("", "")
    if pos is None:
        logger.warning("%spreempted at epoch %d boundary — checkpointed, exit "
                       "%d means 'resume me'", log, epoch, PREEMPTED_EXIT_CODE)
        return PreemptedError(f"{err}preempted after epoch {epoch}")
    logger.warning(
        "%spreempted at epoch %d dispatch boundary (position %d/%d) — %s"
        "checkpointed, exit %d means 'resume me'", log, epoch, pos, total, note,
        PREEMPTED_EXIT_CODE)
    return PreemptedError(
        f"{err}preempted mid-epoch {epoch} at dispatch position {pos}")


class _CachedFeed:
    """One epoch of the device-resident feed: ``chunk_args(pos, n)`` places
    a chunk of the epoch's index matrix and returns what the program takes
    after the state; each dispatch advances a whole scan chunk.

    Per-dispatch sums are kept as ASYNC device handles and summed on the
    host where they are read (:func:`_sum_metric_dicts` has why).
    `carried` (a resumed epoch's saved sums) seeds the SAME sequential f32
    chain, so the epoch's reported metrics are bit-identical to the
    uninterrupted run's.  `counters` publishes the count sums wherever the
    sums are synced; `fi`, `progress`: the epoch's fault plan and live line."""

    count_from = 0  # dispatches number from where this process entered the epoch

    def __init__(self, label: str, programs: _Programs, chunk_args: Callable,
                 total: int, steps_per_dispatch: int, pos: int = 0,
                 carried: dict | None = None, counters=None, fi=None,
                 progress=None):
        self.label, self.total, self.pos = label, total, pos
        self.fi, self.progress = fi, progress
        self._programs, self._chunk_args = programs, chunk_args
        self._n, self._counters = steps_per_dispatch, counters
        self.kept: list = [dict(carried)] if carried else []
        if counters is not None:
            counters.new_epoch(_split_counts(dict(carried)) if carried else None)

    def __iter__(self):
        pos = self.pos
        for n in split_dispatch_chunks(self.total - pos, self._n):
            yield n, self._programs.multi_step(n), self._chunk_args(pos, n)
            pos += n

    def keep(self, metrics: dict) -> None:
        self.kept.append(metrics)

    def sums(self) -> dict:
        """The epoch's sums so far; the host waits here for the dispatches
        in flight."""
        sums = _sum_metric_dicts(self.kept)
        # the sums replace the pending handles — the continued f32 chain is
        # identical either way
        self.kept = [{k: np.float32(v) for k, v in sums.items()}]
        if self._counters is not None:
            self._counters.publish(_split_counts(dict(sums)))
        return sums

    def drain(self) -> None:
        """The epoch's end, before the boundary's heartbeat: the wait for its
        last dispatches (stage ``metric_sync``)."""
        with telemetry.stage("metric_sync"):
            self.sums()

    def metrics(self) -> dict:
        """The drained epoch's train metrics."""
        acc, sums = Accumulator(), dict(self.kept[0])
        _split_counts(sums)  # published; never divided
        acc.add_dict(sums)
        return acc.normalize()


class _HostFeed:
    """One epoch of the host feed: prefetched batches (made when the loop
    starts to read them), one a dispatch; ``batch_args(batch)`` is what the
    step takes after the state.  A dispatch's sums are added on the device
    and read where a snapshot or the boundary needs them; the rest as the
    cached feed's."""

    def __init__(self, label: str, step: Callable, batches: Callable,
                 batch_args: Callable, total: int, pos: int = 0,
                 carried: dict | None = None, counters=None, fi=None,
                 progress=None):
        self.label, self.total, self.pos = label, total, pos
        self.count_from = pos  # batches number from the epoch's start
        self.fi, self.progress = fi, progress
        self._step, self._batches, self._batch_args = step, batches, batch_args
        self._counters, self._acc = counters, Accumulator()
        if carried:
            self._acc.add_dict(carried)

    def __iter__(self):
        for batch in self._batches():
            yield 1, self._step, self._batch_args(batch)

    def keep(self, metrics: dict) -> None:
        self._acc.add_dict(metrics)

    def sums(self) -> dict:
        return dict(self._acc.items())

    def drain(self) -> None:
        """Nothing to wait for: the sums stay on the device until read."""

    def metrics(self) -> dict:
        if self._counters is not None:
            # the count sums sat on the device until here
            self._counters.new_epoch()
            self._counters.publish(_split_counts(self._acc.metrics))
        return self._acc.normalize()


class _StackedHostFeed(_HostFeed):
    """The fold-stacked host feed: per-fold sums stay DEVICE-side [K]
    vectors until the epoch's end — reading them per batch would sync the
    dispatch pipeline (the same discipline as the sequential feed)."""

    def keep(self, metrics: dict) -> None:
        sums = self._acc.metrics
        sums.update(metrics if not sums else {
            kk: sums[kk] + metrics[kk] for kk in sums})


def _dispatch_loop(feed, state, *, wd, heartbeat, step0: int, every: int = 0,
                   snapshot: Callable | None = None,
                   preempted: Callable | None = None):
    """THE dispatch loop of every feed, sequential or stacked: the state
    after the epoch's last dispatch.  `feed` yields ``(steps, program,
    arguments after the state)`` a dispatch and names its label, length,
    fault plan, progress line and metric keeping; `step0` is the global
    step before the epoch.  Every dispatch boundary is an exact resume
    point: after the heartbeat and the fault signal, a stop request (or
    every `every`-th dispatch) before the epoch's end calls ``snapshot(state,
    pos, sums)`` and then raises ``preempted(pos, total)``, where the site
    passes them."""
    pos, fi = feed.pos, feed.fi
    for di, (n, fn, args) in enumerate(feed, start=feed.count_from):
        state, metrics = _monitored_dispatch(
            wd, feed.label, fi, step0 + pos + n, fn, state, *args)
        feed.keep(metrics)
        if feed.progress is not None:
            feed.progress(di, metrics)
        pos += n
        _beat(heartbeat)
        if fi is not None:
            fi.maybe_signal(step0 + pos)
        periodic = every > 0 and (di + 1) % every == 0
        if pos < feed.total and (preemption_requested() or periodic):
            if snapshot is not None:
                snapshot(state, pos, feed.sums())
            if preempted is not None and preemption_requested():
                raise preempted(pos, feed.total)
    return state


def _restore(save_path, state, *, ckpt_keep: int, steps_per_epoch: int,
             epochs: int, only_eval: bool, cache_dir) -> tuple:
    """The newest intact link of `save_path`'s checkpoint chain into
    `state`: ``(state, epoch_start, resume_pos, resume_sums, retries_done,
    only_eval)``.  A mid-epoch (preempted) snapshot fast-forwards its epoch
    to the dispatch position it names, on either feed; a run restored past
    its last epoch only evaluates."""
    # lenient when the file came from the torch importer (no opt_state)
    lenient = bool((read_metadata(save_path) or {}).get("imported_from"))
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
    if restored is None:
        if only_eval:
            raise FileNotFoundError(
                f"--only-eval requires a checkpoint at {save_path}")
        return state, 1, 0, None, 0, only_eval
    state, meta, used_path = restored
    epoch_start = int(meta.get("epoch", 0)) + 1
    resume_pos, resume_sums, retries_done = 0, None, 0
    in_epoch = meta.get("in_epoch")
    if in_epoch:
        resume_pos = int(in_epoch["pos"])
        resume_sums = {k: np.float32(v)
                       for k, v in (in_epoch.get("sums") or {}).items()}
        retries_done = int(in_epoch.get("retries", 0))
        logger.info(
            "resuming MID-EPOCH: epoch %d from dispatch position %d "
            "(preempted snapshot %s)", epoch_start, resume_pos, used_path)
    if meta.get("imported_from"):
        # the schedule is a pure fn of step: place it at the resume epoch,
        # not back at warmup
        fixes = {"step": jnp.int32((epoch_start - 1) * steps_per_epoch)}
        if state.ema is not None and not meta.get("has_ema"):
            # no EMA in the imported file: seed the shadow from the
            # imported weights, never from random init
            fixes["ema"] = jax.tree.map(
                jnp.copy,
                {"params": state.params, "batch_stats": state.batch_stats})
        state = state.replace(**fixes)
    # resume-cost provenance: whether this resumed process will deserialize
    # its executables (warm cache) or re-pay the full compile tax — the
    # final compile_cache stamp carries the proof
    logger.info("resumed %s at epoch %d (compile cache: %s)", used_path,
                epoch_start - 1, cache_dir or "off — full recompile ahead")
    return (state, epoch_start, resume_pos, resume_sums, retries_done,
            only_eval or epoch_start > epochs)


def _resolve_feed(device_cache, steps_per_dispatch, dataset) -> tuple:
    """``(use_cache, steps_per_dispatch)`` from the two feed options."""
    use_cache = resolve_device_cache(device_cache, dataset,
                                     process_count=jax.process_count())
    steps_per_dispatch = int(steps_per_dispatch)
    if steps_per_dispatch > 1 and not use_cache:
        raise ValueError(
            f"steps_per_dispatch={steps_per_dispatch} needs the device "
            "cache (in-program batch gather); it is "
            f"{'off' if device_cache == 'off' else 'unavailable (lazy dataset or multi-host)'} "
            "here — use --device-cache auto/on with an eager dataset")
    return use_cache, steps_per_dispatch


def _upload(dataset, mesh, steps_per_dispatch: int, tag: str = "") -> DeviceCache:
    cache = DeviceCache(dataset, mesh)
    logger.info(
        "%sdevice cache: %d examples (%.1f MiB) resident as %s %s, "
        "steps_per_dispatch=%d", tag, cache.num_examples, cache.nbytes / 2**20,
        cache.images.dtype, list(cache.images.shape), steps_per_dispatch)
    return cache


def _writers(save_path, is_master: bool) -> list:
    return make_writers(os.path.dirname(save_path) if save_path else None,
                        os.path.basename(save_path or "run"), is_master)


def _finish(result: dict, state, root) -> dict:
    """What every result ends with: the steps taken, the device, the
    compile-tax evidence (hit/miss counts + per-label first-call seconds
    through the seam: a resumed/warm process proves here that it reached
    its first step in seconds, not minutes) and the stage summary."""
    result.update(steps=int(state.step), **device_stamp())
    result["compile_cache"] = compile_cache_stats()
    result["stages"] = root.summary()
    return result


class _Run:
    """One ``train_and_eval`` call.  ``__init__`` is the set-up, stage by
    stage as the tree names them (plan → programs → restore → place);
    the methods are the pieces of an epoch, which move `state`, `epoch`,
    the divergence retries and the best metric."""

    def __init__(self, o):
        self.o, conf, save_path = o, o.conf, o.save_path
        cache_dir_active = configure_compile_cache()
        self.mesh = mesh = make_mesh() if o.mesh is None else o.mesh
        self.is_master = jax.process_index() == 0
        plan = _load_plan(conf, o.dataroot)
        with telemetry.stage("split"):
            self.plan = plan = plan.split(o.test_ratio, o.cv_fold, o.target_lb)
        use_cache, self.steps_per_dispatch = _resolve_feed(
            o.device_cache, o.steps_per_dispatch, plan.total_train)

        batch_per_device = int(conf["batch"])
        self.global_batch = global_batch = batch_per_device * mesh.size
        logger.info("mesh %s over %d %s device(s); global batch %d",
                    dict(mesh.shape), mesh.size,
                    mesh.devices.flat[0].platform, global_batch)
        if not o.only_eval and len(plan.train_idx) < global_batch:
            raise ValueError(
                f"training set has {len(plan.train_idx)} examples < global batch "
                f"{global_batch} ({batch_per_device}/device x {mesh.size} devices); "
                "every epoch would be empty (train batches drop the last partial "
                "batch, reference data.py:215)"
            )
        self.steps_per_epoch = max(1, len(plan.train_idx) // global_batch)
        self.epochs = int(conf["epoch"])

        with telemetry.stage("build"):
            model_conf = model_conf_of(conf)
            model = get_model(model_conf, plan.num_classes)
            self.lr_fn = build_schedule(conf, self.steps_per_epoch,
                                        world_lr_scale=float(mesh.size))
            ema_mu = float(conf["optimizer"].get("ema", 0.0) or 0.0)
            self.ema_interval = int(conf["optimizer"].get("ema_interval", -1) or -1)
            sample = jnp.zeros(plan.sample.shape, plan.sample.dtype)
            self.seed, self.rng = o.seed, jax.random.PRNGKey(o.seed)
            optimizer = build_optimizer(conf["optimizer"], self.lr_fn)
        with telemetry.stage("state_init"):
            state = steps.create_train_state(
                model, optimizer, self.rng, sample, use_ema=ema_mu > 0.0,
                jit_init=plan.jit_init)
        # which family ran at what size, for the journal and /metrics (sizes
        # from shapes: nothing waits for the device)
        num_params = sum(int(p.size) for p in jax.tree.leaves(state.params))
        model_type = str(model_conf["type"])
        telemetry.registry().gauge(
            "faa_model_parameters", "trainable parameters of the model a "
            "trainer built", model=model_type).set(num_params)
        telemetry.emit("model", model_type, parameters=num_params,
                       batch_per_device=batch_per_device,
                       steps_per_epoch=self.steps_per_epoch)

        with telemetry.stage("build"):
            policy = resolve_policy_tensor(conf.get("aug", "default"))
            self.pol = (policy if policy is not None
                        else jnp.zeros((1, 1, 3), jnp.float32))
            self.programs = _build_programs(
                plan, conf, model, optimizer, use_cache=use_cache,
                aug_dispatch=o.aug_dispatch, aug_groups=o.aug_groups)
            self.counters = _CountPublisher(model) if plan.publishes_counts else None
            self.writers = _writers(save_path, self.is_master)
            self.save_path, self.ckpt_keep = save_path, max(1, int(o.ckpt_keep))
            self.saves = bool(save_path and self.is_master)
            self.divergence_retries = max(0, int(o.divergence_retries))
            self.wd = resolve_watchdog(o.watchdog)
            # flag-setting SIGTERM/SIGUSR1 handlers (idempotent, main thread
            # only): the dispatch loop and the epoch boundary poll the flag
            # at safe points — see core/resilience.py and docs/RESILIENCE.md
            install_signal_handlers()

        restored = (state, 1, 0, None, 0, o.only_eval)
        with telemetry.stage("restore"):
            if save_path:
                restored = _restore(
                    save_path, state, ckpt_keep=self.ckpt_keep,
                    steps_per_epoch=self.steps_per_epoch, epochs=self.epochs,
                    only_eval=o.only_eval, cache_dir=cache_dir_active)
        # `resume`: a mid-epoch snapshot's position and sums, the first
        # epoch's; `retries_done`: the divergence retries (fold the PRNG)
        state, self.epoch, pos, sums, self.retries_done, self.only_eval = restored
        self.resume = (pos, sums)

        # commit the carried state to the mesh BEFORE the first dispatch or
        # eval, on either feed.  Cached: an uncommitted state compiled
        # against the mesh-committed cache knocks every later call off the
        # C++ fast dispatch path (steps.make_multistep_train_step note), and an
        # --only-eval restore must lower the SAME replay_eval program the
        # training run cached, not an uncommitted variant of it.  Host-fed:
        # the batches arrive committed, so the first step returns a
        # committed state and the SECOND call no longer matches what the
        # first compiled — the step program was lowered and compiled (or
        # loaded) twice a process (ResNet-50 on one v5e: 49 s of a cold
        # run's first epoch, 7 s of a warm one's; my chip runs, PR 32)
        with telemetry.stage("place_state"):
            self.state = jax.device_put(state, replicated(mesh))
        self.result: dict = {"epoch": self.epoch - 1}
        self.best_metric = -1e9
        self.evaluate = _Evaluator(self.programs, mesh, global_batch, mesh.size,
                                   self.wd)
        self.use_cache, self.cache = use_cache, None  # :meth:`upload`

    def upload(self) -> None:
        """The device-resident feed's data set onto the mesh (``cache_upload``)."""
        if self.use_cache:
            self.cache = _upload(self.plan.total_train, self.mesh,
                                 self.steps_per_dispatch)
            # replicated inputs join the committed state on the mesh
            self.rng = jax.device_put(self.rng, replicated(self.mesh))
            self.pol = jax.device_put(self.pol, replicated(self.mesh))

    def feed(self):
        """Open epoch ``self.epoch``: its fault plan, randomness, progress
        line and feed (the cached one under its ``index_matrix`` stage)."""
        epoch, mesh, plan = self.epoch, self.mesh, self.plan
        # divergence-retry randomness: after any rollback every epoch draws
        # retry-folded augmentation keys and shuffle seeds; retries_done ==
        # 0 is bit-for-bit the historical stream
        rng_epoch, seed_epoch = self.rng, self.seed
        if self.retries_done:
            rng_epoch = jax.random.fold_in(self.rng, 1_000_003 * self.retries_done)
            seed_epoch = self.seed + 1_000_003 * self.retries_done
            if self.cache is not None:
                rng_epoch = jax.device_put(rng_epoch, replicated(mesh))
        (pos, carried), self.resume = self.resume, (0, None)
        shard = dict(process_index=jax.process_index(),
                     process_count=jax.process_count())
        cache, pol = self.cache, self.pol
        epoch_kw = dict(pos=pos, carried=carried, counters=self.counters,
                        fi=faultinject.active_plan(),
                        progress=_Progress(epoch, self.is_master))
        if cache is None:
            # host feed: the same resume points as the device-resident
            # feed, one batch a dispatch.  A resumed epoch skips the
            # batches already trained without decoding them (their crop
            # boxes are still drawn, so the rest of the epoch is the
            # unbroken run's) and continues the saved metric sums.
            return _HostFeed(
                "train_step", self.programs.train_step,
                lambda: prefetch(
                    plan.train_it.train_epoch(self.global_batch, epoch,
                                              seed=seed_epoch, skip=pos, **shard),
                    transform=shard_transform(mesh)),
                lambda batch: (batch["x"], batch["y"], pol, rng_epoch),
                self.steps_per_epoch, **epoch_kw)
        # device-resident feed: the per-epoch shuffle is the IDENTICAL host
        # permutation; only the index matrix is shipped
        with telemetry.stage("index_matrix"):
            mat = train_index_matrix(plan.train_idx, self.global_batch, epoch,
                                     seed=seed_epoch, **shard)
        return _CachedFeed(
            "train_dispatch", self.programs,
            lambda at, n: (cache.images, cache.labels,
                           place_index_matrix(mesh, mat[at:at + n]), pol,
                           rng_epoch),
            len(mat), self.steps_per_dispatch, **epoch_kw)

    def snapshot(self, state, pos: int, sums: dict) -> None:
        """Mid-epoch checkpoint at a dispatch boundary: the exact position
        and the epoch's metric sums so far (either feed)."""
        epoch = self.epoch
        with telemetry.stage("checkpoint"):
            save_checkpoint(
                self.save_path, state,
                _ckpt_meta(epoch - 1, (epoch - 1) * self.steps_per_epoch + pos,
                           preempted=preemption_requested(),
                           in_epoch={"epoch": epoch, "pos": pos,
                                     "sums": {k: float(v) for k, v in sums.items()},
                                     "retries": self.retries_done}),
                keep=self.ckpt_keep)

    def preempted(self, pos: int, total: int) -> PreemptedError:
        return _stopped("", self.epoch, pos, total)

    def boundary(self, feed) -> None:
        """From the loop's end to the next epoch (stage ``epoch_boundary``):
        the epoch's metrics, divergence recovery, the periodic evaluation
        and checkpoint, a stop request; moves ``self.epoch``."""
        epoch, fi = self.epoch, feed.fi
        feed.drain()
        with telemetry.stage("heartbeat"):
            _beat(self.o.heartbeat)
        feed.progress.close()
        with telemetry.stage("metric_sync"):
            train_metrics = feed.metrics()
        if not train_metrics:
            raise RuntimeError(
                f"epoch {epoch} produced zero train batches "
                f"({len(self.plan.train_idx)} examples, global batch "
                f"{self.global_batch}) — feed pipeline bug or dataset/batch "
                "mismatch")
        if fi is not None and fi.nan_loss_in((epoch - 1) * self.steps_per_epoch,
                                             epoch * self.steps_per_epoch):
            train_metrics["loss"] = float("nan")  # injected at the seam
        if not np.isfinite(train_metrics["loss"]):
            if self._roll_back():
                return
            raise RuntimeError(
                "loss is NaN — training diverged (reference train.py:259)")

        # periodic EMA -> model weight restore (reference train.py:262-270)
        if (self.state.ema is not None and self.ema_interval > 0
                and epoch % self.ema_interval == 0):
            logger.info("ema synced into model at epoch %d", epoch)
            # copy: params must not alias the EMA shadow (donated buffers)
            ema = self.state.ema
            self.state = self.state.replace(
                params=jax.tree.map(jnp.copy, ema["params"]),
                batch_stats=jax.tree.map(jnp.copy, ema["batch_stats"]))
        with telemetry.stage("log"):
            _record_train(self.result, self.writers[0], "train", epoch,
                          self.epochs, train_metrics,
                          lr=float(self.lr_fn(int(self.state.step) - 1)))
        if epoch % self.o.evaluation_interval == 0 or epoch == self.epochs:
            self._evaluate_and_keep(train_metrics)
        # graceful preemption at the epoch boundary (both feeds usually
        # caught the flag at a dispatch boundary already; this is the
        # request that arrived with the epoch's last dispatch or during its
        # evaluation): checkpoint the COMPLETED epoch with preempted
        # metadata and exit via the 77 contract
        if preemption_requested():
            self._checkpoint(preempted=True)
            raise _stopped("", epoch)
        self.epoch += 1

    def _roll_back(self) -> bool:
        """Divergence recovery (--divergence-retries R, default 0 = the
        historical raise): roll back to the newest intact EPOCH-BOUNDARY
        chain link and replay with retry-folded randomness; False (the
        caller re-raises) after R failed rollbacks or with no such link."""
        if not (self.retries_done < self.divergence_retries and self.save_path):
            return False
        rolled = load_checkpoint_chain(
            self.save_path, self.state, keep=self.ckpt_keep,
            accept=lambda m: "in_epoch" not in m)
        if rolled is None:
            logger.error(
                "divergence: retries remain but NO intact rollback "
                "checkpoint under %s — re-raising", self.save_path)
            return False
        self.retries_done += 1
        self.state, meta, used = rolled
        if self.cache is not None:
            self.state = jax.device_put(self.state, replicated(self.mesh))
        rollback_epoch = int(meta.get("epoch", 0)) + 1
        logger.warning(
            "divergence: non-finite loss at epoch %d — rolled back to %s "
            "(replaying from epoch %d), retry %d/%d with retry-folded "
            "PRNG/shuffle streams", self.epoch, used, rollback_epoch,
            self.retries_done, self.divergence_retries)
        self.epoch = rollback_epoch
        return True

    def _evaluate_and_keep(self, train_metrics: dict) -> None:
        epoch, result = self.epoch, self.result
        with telemetry.stage("evaluate"):
            evals = self.evaluate(self.state, self.plan)
            _record_evals(result, self.writers, "", epoch, self.epochs, evals)
        if self.o.metric == "last":
            cur = float(epoch)
        elif self.o.metric == "train":
            cur = train_metrics["top1"]
        else:
            cur = evals.get(self.o.metric, {}).get("top1", 0.0)
        if cur >= self.best_metric:
            self.best_metric = cur
            result["best_valid_top1"] = evals.get("valid", {}).get("top1", 0.0)
            result["best_test_top1"] = evals.get("test", {}).get("top1", 0.0)
            self._checkpoint()
        if self.o.reporter is not None:
            self.o.reporter(
                loss_valid=evals.get("valid", {}).get("loss", 0.0),
                top1_valid=evals.get("valid", {}).get("top1", 0.0),
                loss_train=train_metrics["loss"],
                top1_train=train_metrics["top1"],
                epoch=epoch)

    def _checkpoint(self, preempted: bool | None = None) -> None:
        """The COMPLETED epoch's checkpoint (where this process writes one)."""
        if not self.saves:
            return
        with telemetry.stage("checkpoint"):
            save_checkpoint(
                self.save_path, self.state,
                _ckpt_meta(self.epoch, int(self.state.step),
                           preempted=preempted, result=self.result),
                keep=self.ckpt_keep)


@roomy  # the eager set-up too, not the seams' first calls alone
def train_and_eval(
    conf, dataroot: str, *,
    test_ratio: float = 0.0, cv_fold: int = 0,
    reporter: Callable | None = None, metric: str = "last",
    save_path: str | None = None, only_eval: bool = False,
    evaluation_interval: int = 5, mesh=None,
    target_lb: int = -1, seed: int = 0,
    aug_dispatch: str = "exact", aug_groups: int = 8,
    device_cache: str = "auto", steps_per_dispatch: int = 1,
    divergence_retries: int = 0, ckpt_keep: int = 2,
    checkpoint_every_dispatch: int = 0, watchdog="off",
    heartbeat: Callable | None = None,
) -> dict:
    """Train (or just evaluate) one model under `conf`.

    Returns the reference-shaped result dict: per-split loss/top1/top5,
    'epoch', 'steps', the device that ran it (``platform``/``device_kind``/
    ``device_count``), ``compile_cache`` (the persistent cache's evidence,
    ``core/compilecache.py``) and ``stages`` (the call's seconds by dotted
    stage path: docs/OBSERVABILITY.md "Stages").  `metric` in {'last',
    'train', 'valid', 'test'} selects what "best" means (reference
    ``train.py:286-303``).  ``aug_dispatch``/``aug_groups`` pick the
    policy-application kernel ("exact" default; "grouped" scalar dispatch
    — ``ops/augment.py``).

    ``device_cache`` ("auto"/"on"/"off") selects the device-resident feed
    (:class:`_CachedFeed` over ``data.pipeline.DeviceCache``); "auto"
    enables it exactly for eager single-process data sets — lazy
    (ImageNet) ones keep the prefetch/decode feed (:class:`_HostFeed`).
    ``steps_per_dispatch`` (N, needs the cache) fuses N train steps into
    one ``lax.scan`` dispatch: N=1 (default) is bit-for-bit the host-fed
    path; N>1 deviates by the documented ~1 f32 ULP/step scan-kernel
    bound (docs/PARITY.md "Step dispatch & device cache").

    Resilience (docs/RESILIENCE.md): SIGTERM/SIGUSR1 requests a graceful
    stop — :func:`_dispatch_loop` checkpoints at the next dispatch
    boundary with ``preempted: true`` metadata and the position in the
    epoch, and raises :class:`PreemptedError` (exit code 77 = "resume
    me").  ``divergence_retries`` (R, default 0 = raise) rolls a
    non-finite epoch loss back to the newest intact epoch-boundary
    checkpoint up to R times with retry-folded randomness; ``ckpt_keep``
    bounds the rollback chain (``path``, ``path.prev``, …);
    ``checkpoint_every_dispatch`` (M) adds a mid-epoch snapshot every M
    dispatches, resumable bit-identically.  ``watchdog`` ("off" default /
    "auto" / seconds, or a shared ``core.watchdog.DispatchWatchdog``)
    deadline-guards every train dispatch and eval replay
    (``DispatchHungError``); ``heartbeat`` (e.g. a lease renewal) is
    called after every dispatch and at every epoch boundary — a raised
    ``LeaseLostError`` aborts the unit.
    """
    o = types.SimpleNamespace(**locals())  # first: the call's options, by name
    with telemetry.stage("train_and_eval", only_eval=bool(only_eval)) as root:
        run = _Run(o)
        if run.only_eval:
            with telemetry.stage("evaluate"):
                evals = run.evaluate(run.state, run.plan)
            for split, m in evals.items():
                for k, v in m.items():
                    run.result[f"{k}_{split}"] = v
            return _finish(run.result, run.state, root)
        # best-metric guards live AFTER the only_eval return (eval-only runs
        # never consult `metric`, including resumes that auto-flip only_eval)
        if metric not in ("last", "train", "valid", "test"):
            raise ValueError(f"unknown metric {metric!r}: use last/train/valid/test")
        if metric == "valid" and len(run.plan.valid_it) == 0:
            raise ValueError(
                "metric='valid' with an empty validation split (test_ratio=0): "
                "the best-checkpoint tracker would silently follow a constant "
                "0.0 — pass metric='last'/'train'/'test' or a test_ratio > 0"
            )
        if metric == "test" and len(run.plan.test_it) == 0:
            raise ValueError("metric='test' with an empty test split")
        with telemetry.stage("cache_upload"):
            run.upload()
        t_start = wall()
        every = max(0, int(checkpoint_every_dispatch))
        # while (not for): divergence recovery rolls `run.epoch` BACK to the
        # last good checkpoint's successor and replays with fresh randomness
        while run.epoch <= run.epochs:
            with telemetry.stage("epoch", epoch=run.epoch):
                feed = run.feed()
                with telemetry.stage("dispatch_loop"):
                    run.state = _dispatch_loop(
                        feed, run.state, wd=run.wd, heartbeat=heartbeat,
                        step0=(run.epoch - 1) * run.steps_per_epoch, every=every,
                        preempted=run.preempted,
                        snapshot=run.snapshot if run.saves else None)
                with telemetry.stage("epoch_boundary"):
                    run.boundary(feed)

        run.result["elapsed_sec"] = wall() - t_start
        for w in run.writers:
            w.close()
        return _finish(run.result, run.state, root)


def _restore_folds(new_state: Callable, folds, save_paths, *, resume: bool,
                   ckpt_keep: int, use_cache: bool) -> tuple:
    """Per-fold init/restore (newest intact chain link): ``(states,
    epoch_starts, (resume_pos, resume_epoch, resume_sums))``.

    Mid-epoch (preempted) snapshots fast-forward the stacked dispatch loop
    only when EVERY restored record agrees on (epoch, pos) and the
    device-cache index feed is active (positions can be skipped);
    otherwise each mid-epoch fold falls back to its epoch-boundary chain
    link — losing at most the interrupted epoch, never silently
    double-training it."""
    states, epoch_starts, records = [], [], []
    for k, (fold, path) in enumerate(zip(folds, save_paths)):
        state, epoch_start, meta = new_state(k), 1, {}
        if resume and path:
            got = load_checkpoint_chain(path, state, keep=ckpt_keep)
            if got is not None:
                state, meta, _used = got
                epoch_start = int(meta.get("epoch", 0)) + 1
                logger.info(
                    "stacked: resumed fold %d at epoch %d%s", fold,
                    epoch_start - 1,
                    " (mid-epoch snapshot)" if "in_epoch" in meta else "")
        states.append(state)
        epoch_starts.append(epoch_start)
        records.append(meta.get("in_epoch"))
    if not any(records):
        return states, epoch_starts, (0, -1, None)
    ref = next(r for r in records if r)
    agree = use_cache and all(
        (r is not None and r.get("epoch") == ref["epoch"]
         and r.get("pos") == ref["pos"])
        or (r is None and epoch_starts[k] > int(ref["epoch"]))
        for k, r in enumerate(records))
    if agree:
        sum_keys = sorted({kk for r in records if r
                           for kk in (r.get("sums") or {})})
        sums = {kk: np.asarray([(r.get("sums") or {}).get(kk, 0.0) if r else 0.0
                                for r in records], np.float32)
                for kk in sum_keys}
        logger.info("stacked: resuming MID-EPOCH at epoch %d, dispatch "
                    "position %d", int(ref["epoch"]), int(ref["pos"]))
        return states, epoch_starts, (int(ref["pos"]), int(ref["epoch"]), sums)
    for k, r in enumerate(records):
        if r is None:
            continue
        logger.warning(
            "stacked: fold %d mid-epoch snapshot unusable here (position "
            "disagreement or host feed) — falling back to its "
            "epoch-boundary chain link", folds[k])
        got = load_checkpoint_chain(
            save_paths[k], states[k], keep=ckpt_keep,
            accept=lambda m: "in_epoch" not in m)
        if got is not None:
            states[k], meta_k, _used = got
            epoch_starts[k] = int(meta_k.get("epoch", 0)) + 1
        else:
            states[k], epoch_starts[k] = new_state(k), 1
    return states, epoch_starts, (0, -1, None)


class _Stack:
    """One ``train_folds_stacked`` call: ``__init__`` is the set-up; the
    methods are the pieces of an epoch.  `epoch` and `active` (which folds
    train in it: a restored fold waits for its epoch) move with
    :meth:`feed`, `stacked` (the K states as one) with the loop."""

    def __init__(self, o):
        conf, folds, save_paths = o.conf, o.folds, o.save_paths
        configure_compile_cache()
        if len(folds) != len(save_paths):
            raise ValueError(f"{len(folds)} folds but {len(save_paths)} paths")
        self.seeds = seeds = [o.seed] * len(folds) if o.seeds is None else o.seeds
        self.folds, self.save_paths = folds, save_paths
        self.mesh = mesh = make_fold_mesh(len(folds)) if o.mesh is None else o.mesh
        data_size = mesh.shape["data"]
        is_master = jax.process_index() == 0

        plan = _load_plan(conf, o.dataroot, kind=_eager_images)
        if plan.total_train.lazy:
            raise ValueError(
                "train_folds_stacked supports in-memory datasets only; "
                f"{conf['dataset']!r} is lazy — use the sequential per-fold path")
        self.plans = plans = [plan.split(o.cv_ratio, fold) for fold in folds]

        self.global_batch = global_batch = int(conf["batch"]) * data_size
        logger.info("stacked: %d folds on mesh %s over %d %s device(s); "
                    "per-fold global batch %d", len(folds), dict(mesh.shape),
                    mesh.size, mesh.devices.flat[0].platform, global_batch)
        for fold, p in zip(folds, plans):
            if len(p.train_idx) < global_batch:
                raise ValueError(
                    f"fold {fold} has {len(p.train_idx)} train examples < per-fold "
                    f"global batch {global_batch} — every epoch would be empty")
        step_counts = {len(p.train_idx) // global_batch for p in plans}
        if len(step_counts) != 1:
            # the LR schedule is baked into the ONE shared optimizer as a
            # pure function of the step; folds with different step counts
            # need per-fold schedules the stack cannot represent
            raise ValueError(
                f"folds disagree on steps/epoch ({sorted(step_counts)}) — "
                "train them sequentially instead")
        self.steps_per_epoch = step_counts.pop()
        self.epochs, self.evaluation_interval = int(conf["epoch"]), o.evaluation_interval

        model = get_model(model_conf_of(conf), plan.num_classes)
        lr_fn = build_schedule(conf, self.steps_per_epoch,
                               world_lr_scale=float(data_size))
        ema_mu = float(conf["optimizer"].get("ema", 0.0) or 0.0)
        optimizer = build_optimizer(conf["optimizer"], lr_fn)
        sample = jnp.zeros(plan.sample.shape, plan.sample.dtype)
        policy = resolve_policy_tensor(conf.get("aug", "default"))
        pol = policy if policy is not None else jnp.zeros((1, 1, 3), jnp.float32)

        use_cache, self.steps_per_dispatch = _resolve_feed(
            o.device_cache, o.steps_per_dispatch, plan.total_train)
        self.programs = _build_programs(
            plan, conf, model, optimizer, use_cache=use_cache,
            aug_dispatch=o.aug_dispatch, aug_groups=o.aug_groups, stacked=True)
        self.ckpt_keep = max(1, int(o.ckpt_keep))
        self.wd = resolve_watchdog(o.watchdog)
        install_signal_handlers()

        states, self.epoch_starts, self.resume = _restore_folds(
            lambda k: steps.create_train_state(
                model, optimizer, jax.random.PRNGKey(seeds[k]), sample,
                use_ema=ema_mu > 0.0),
            folds, save_paths, resume=o.resume, ckpt_keep=self.ckpt_keep,
            use_cache=use_cache)
        # shard every state leaf's leading fold axis over the mesh fold
        # axis (a no-op layout on fold_shards=1 meshes): folds live on
        # their own device groups instead of replicating
        fold_placed = NamedSharding(mesh, PartitionSpec("fold"))
        self.stacked = jax.device_put(steps.stack_states(states), fold_placed)
        self.keys = jax.device_put(
            jnp.stack([jax.random.PRNGKey(s) for s in seeds]), fold_placed)
        self.writers = [_writers(p, is_master) for p in save_paths]
        self.results = {fold: {"epoch": self.epoch_starts[k] - 1}
                        for k, fold in enumerate(folds)}
        self.evaluate = _Evaluator(self.programs, mesh, global_batch, data_size,
                                   self.wd)
        self.cache = _upload(plan.total_train, mesh, self.steps_per_dispatch,
                             "stacked ") if use_cache else None
        # the stacked state/keys are already mesh-committed (fold placement
        # above); with the cache the policy tensor must be too, or the first
        # compile pins a mixed-commitment signature that knocks later
        # dispatches off the C++ fast path (steps.make_multistep_train_step)
        self.pol = jax.device_put(pol, replicated(mesh)) if use_cache else pol
        self.epoch, self.active = 0, None

    def feed(self, epoch: int):
        self.epoch = epoch
        self.active = active = np.asarray(
            [1.0 if epoch >= start else 0.0 for start in self.epoch_starts],
            np.float32)
        cache, mesh, pol, keys = self.cache, self.mesh, self.pol, self.keys
        train_idx = [p.train_idx for p in self.plans]
        shard = dict(process_index=jax.process_index(),
                     process_count=jax.process_count())
        fi = faultinject.active_plan()
        if cache is None:
            act_dev = jnp.asarray(active)
            return _StackedHostFeed(
                "stacked_step", self.programs.train_step,
                lambda: prefetch(
                    stacked_train_batches(
                        self.plans[0].total_train, train_idx, self.global_batch,
                        epoch, seeds=self.seeds, **shard),
                    transform=stacked_shard_transform(mesh)),
                lambda batch: (batch["x"], batch["y"], pol, keys,
                               batch["a"] * act_dev),
                self.steps_per_epoch, fi=fi)
        chunks, act = stacked_index_matrix(
            train_idx, self.global_batch, epoch, seeds=self.seeds, **shard)
        act = act * active[None, :]

        def chunk_args(at, n):
            idx_dev, act_dev = place_stacked_index_matrix(
                mesh, chunks[at:at + n], act[at:at + n])
            return cache.images, cache.labels, idx_dev, pol, keys, act_dev

        # a snapshot's skipped dispatches and per-fold f32 sum chain
        # (bit-identical continuation, as in the sequential trainer)
        pos, resume_epoch, carried = self.resume
        resumed = bool(pos) and epoch == resume_epoch
        return _CachedFeed("stacked_dispatch", self.programs, chunk_args,
                           len(chunks), self.steps_per_dispatch,
                           pos if resumed else 0, carried if resumed else None,
                           fi=fi)

    def _save_slices(self, stacked, meta_of: Callable) -> None:
        """Checkpoint every active fold's slice (master only)."""
        if jax.process_index() != 0:
            return
        for k, path in enumerate(self.save_paths):
            if self.active[k] and path:
                save_checkpoint(path, slice_state(stacked, k), meta_of(k),
                                keep=self.ckpt_keep)

    def snapshot(self, stacked, pos: int, sums: dict) -> None:
        """Every active fold checkpoints its slice with the shared
        mid-epoch position."""
        epoch = self.epoch
        self._save_slices(stacked, lambda k: _ckpt_meta(
            epoch - 1, (epoch - 1) * self.steps_per_epoch + pos, preempted=True,
            in_epoch={"epoch": epoch, "pos": pos,
                      "sums": {kk: float(np.asarray(v)[k])
                               for kk, v in sums.items()}}))

    def preempted(self, pos: int, total: int) -> PreemptedError:
        return _stopped("stacked", self.epoch, pos, total,
                        f"{int(self.active.sum())} fold slice(s) ")

    def boundary(self, feed) -> None:
        """Every active fold's train metrics and, on an evaluation epoch,
        its evaluation and checkpoint; then a stop request."""
        epoch, stacked = self.epoch, self.stacked
        host_sums = {kk: np.asarray(v) for kk, v in feed.sums().items()}
        evaluates = (epoch % self.evaluation_interval == 0
                     or epoch == self.epochs)
        for k, fold in enumerate(self.folds):
            if self.active[k]:
                self._fold_boundary(k, fold, host_sums,
                                    slice_state(stacked, k) if evaluates else None)
        # epoch-boundary preemption (the host path's only safe point):
        # checkpoint every active fold's COMPLETED epoch, exit via 77
        if preemption_requested():
            self._save_slices(stacked, lambda k: _ckpt_meta(
                epoch, int(slice_state(stacked, k).step), preempted=True))
            raise _stopped("stacked", epoch)

    def _fold_boundary(self, k: int, fold: int, host_sums: dict, state_k) -> None:
        epoch, result = self.epoch, self.results[fold]
        num = float(host_sums["num"][k]) if host_sums else 0.0
        if num <= 0:
            raise RuntimeError(
                f"stacked epoch {epoch} produced zero batches for fold "
                f"{fold} — feed pipeline bug")
        train_metrics = {kk: float(host_sums[kk][k]) / num
                         for kk in ("loss", "top1", "top5")}
        train_metrics["num"] = num
        if np.isnan(train_metrics["loss"]):
            raise RuntimeError(f"fold {fold} loss is NaN — training diverged")
        tag = f"stacked fold {fold}"
        _record_train(result, self.writers[k][0], tag, epoch, self.epochs,
                      train_metrics)
        if state_k is None:
            return
        evals = self.evaluate(state_k, self.plans[k], ema=False, fold=k)
        _record_evals(result, self.writers[k], tag + " ", epoch, self.epochs,
                      evals)
        # metric='last' semantics (the phase-1 contract): every eval epoch
        # is the new best, checkpoint it
        result["best_valid_top1"] = evals.get("valid", {}).get("top1", 0.0)
        result["best_test_top1"] = evals.get("test", {}).get("top1", 0.0)
        if self.save_paths[k] and jax.process_index() == 0:
            save_checkpoint(
                self.save_paths[k], state_k,
                _ckpt_meta(epoch, int(state_k.step), result=result),
                keep=self.ckpt_keep)


@roomy
def train_folds_stacked(
    conf, dataroot: str, *,
    cv_ratio: float, folds: list[int], save_paths: list[str],
    seed: int = 0, seeds: list[int] | None = None,
    evaluation_interval: int = 5, mesh=None, resume: bool = True,
    aug_dispatch: str = "exact", aug_groups: int = 8,
    device_cache: str = "auto", steps_per_dispatch: int = 1,
    ckpt_keep: int = 2, watchdog="off", heartbeat: Callable | None = None,
) -> dict[int, dict]:
    """Train K phase-1 fold models as ONE vmapped program per step.

    The fold-stacked counterpart of calling :func:`train_and_eval` once
    per fold with ``test_ratio=cv_ratio, cv_fold=fold, metric='last'``:
    all K fold states advance together through
    ``steps.make_stacked_train_step``, fed the K per-fold shuffled index
    streams out of the ONE shared dataset.  The fold axis is a pure vmap
    of the sequential step body and each fold's data and key streams are
    reproduced exactly, so the stacked computation is the sequential one
    per fold up to the ~1 f32 ULP/step kernel reduction-order bound that
    docs/PARITY.md "Step dispatch & device cache" records and
    tests/test_stacked_phase1.py pins.

    `mesh` defaults to :func:`make_fold_mesh` over all devices — folds
    shard across device groups when the counts divide (the per-fold
    global batch is then ``conf['batch'] x data_axis_size``).  `seeds`
    gives per-fold seeds (default: `seed` for every fold, matching the
    sequential phase-1 loop).  Per-fold checkpoints save/restore through
    :func:`slice_state` under the caller-supplied paths — the layout the
    sequential path writes, so resume, the fold-oracle gate and
    single-fold retrains consume them unchanged.  Returns ``{fold:
    result_dict}`` with :func:`train_and_eval`-shaped per-fold metrics.

    In-memory image data sets only (:func:`_eager_images`): lazy ones fall
    back to the sequential path in the search driver (per-fold host
    decode streams cannot be multiplexed bit-for-bit;
    ``stacked_train_batches`` docstring).  ``device_cache``/
    ``steps_per_dispatch`` compose with the stack as in
    :func:`train_and_eval`: the multiplexed ``[steps, K, B]`` index
    matrix replaces the image feed (the scan sits outside the fold vmap).

    A stop request (docs/RESILIENCE.md) is honored at the next
    dispatch-chunk boundary (cache path — every active fold checkpoints
    its slice with ``preempted: true`` + the mid-epoch position, resumable
    bit-identically) or epoch boundary (host path).  ``ckpt_keep``,
    ``watchdog`` and ``heartbeat`` follow :func:`train_and_eval`.
    """
    o = types.SimpleNamespace(**locals())  # first: the call's options, by name
    t_start = wall()
    stack = _Stack(o)
    # the host feed takes no mid-epoch snapshot: its stop waits for the boundary
    stops = (dict(snapshot=stack.snapshot, preempted=stack.preempted)
             if stack.cache is not None else {})
    for epoch in range(min(stack.epoch_starts), stack.epochs + 1):
        feed = stack.feed(epoch)
        stack.stacked = _dispatch_loop(
            feed, stack.stacked, wd=stack.wd, heartbeat=heartbeat,
            step0=(epoch - 1) * stack.steps_per_epoch, **stops)
        stack.boundary(feed)

    elapsed = wall() - t_start
    cc = compile_cache_stats()
    logger.info("stacked: compile cache dir=%s hits=%d misses=%d "
                "first_step_secs=%.3f", cc["dir"], cc["hits"], cc["misses"],
                cc["first_step_secs"])
    for k, fold in enumerate(folds):
        stack.results[fold]["elapsed_sec"] = elapsed
        stack.results[fold]["compile_cache"] = cc
        for w in stack.writers[k]:
            w.close()
    return stack.results
