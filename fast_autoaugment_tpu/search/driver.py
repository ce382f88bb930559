"""Three-phase policy search by density matching.

The reference's ``search.py:137-312``: (1) pretrain K=5 models on CV
resamples WITHOUT augmentation, (2) per fold run HyperOpt-TPE over
{op, prob, level}^(num_policy x num_op) with test-time-augmentation
reward against the held-out fold, keep each fold's top-10 samples,
decode + dedup into ``final_policy_set``, (3) retrain on the full data
with and without the found policies and compare.

Differences by design:
- Ray remotes + Redis + checkpoint-polling progress threads become a
  plain in-process loop around ONE compiled TTA step per fold; trial
  state is a JSON file, resumable (`--resume` parity) and readable by
  the launcher for multi-host fold sharding (fold k -> host k % n).
- TPE is in-tree (``search/tpe.py``).
- "GPU-hours" accounting (``search.py:132-133,251``) becomes
  device-seconds = wall x device_count, reported per phase next to
  the platform/device_kind that spent them.

Additions beyond the reference (round-2 post-mortem,
``docs/search_postmortem_r2.md`` — the reference has neither and its
pipeline silently selected accuracy-destroying policies in our round-2
validation run):
- a **fold-oracle quality gate**: after phase 1 each fold model's
  no-candidate-policy baseline accuracy is measured with the compiled
  TTA step; folds below ``fold_quality_floor`` are retrained with a
  fresh seed up to ``fold_retrain_tries`` times and excluded from
  ranking if still weak (a 0.37-accuracy oracle cannot rank policies);
- a **per-sub-policy audit**: every sub-policy surviving the
  reference's top-N selection is evaluated ALONE under the
  *mean*-over-draws reduction (training-time semantics) and dropped
  when it degrades fold accuracy below ``audit_floor`` x baseline —
  the reference's max-over-draws reward (``search.py:116-125``) lets a
  destructive sub-policy hide behind one benign draw of its trial
  siblings.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from fast_autoaugment_tpu.core import fsfault, telemetry
from fast_autoaugment_tpu.core.checkpoint import load_checkpoint, read_metadata
from fast_autoaugment_tpu.core.compilecache import (
    compile_cache_stats,
    configure_compile_cache,
)
from fast_autoaugment_tpu.core.resilience import (
    DispatchHungError,
    PreemptedError,
)
from fast_autoaugment_tpu.core.telemetry import wall
from fast_autoaugment_tpu.core.watchdog import resolve_watchdog
from fast_autoaugment_tpu.data.datasets import cv_split, load_dataset
from fast_autoaugment_tpu.models import get_model, num_class
from fast_autoaugment_tpu.ops.augment import SEARCH_OP_NAMES
from fast_autoaugment_tpu.parallel.mesh import device_stamp, make_mesh
from fast_autoaugment_tpu.policies.archive import (
    policy_decoder,
    policy_to_tensor,
    remove_duplicates,
)
from fast_autoaugment_tpu.search.census import executable_census
from fast_autoaugment_tpu.search.tpe import TPE, choice, uniform
from fast_autoaugment_tpu.search.tta import (
    eval_tta,
    eval_tta_batched,
    make_audit_step,
    make_tta_step,
)
from fast_autoaugment_tpu.train.trainer import train_and_eval, train_folds_stacked
from fast_autoaugment_tpu.utils.logging import get_logger

__all__ = ["search_policies", "search_actor", "make_search_space",
           "SearchResult", "resolve_quality_floor", "resolve_fold_stack",
           "write_json_atomic", "draw_random_policy_set"]

logger = get_logger("faa_tpu.search")


def phase1_device_seconds_attribution(sw, fold_list, stack_groups) -> dict:
    """Per-fold device-seconds from the phase-1 stopwatch ledger.

    ``phase1_fold<k>`` phases (initial train + gate retrains, the same
    accumulating name) credit fold k directly; each ``phase1_stack<i>``
    phase (one measured wall for a whole fold-stacked group) splits
    evenly over `stack_groups[i]`.  The ``device_secs_phase1_per_fold``
    stamp in ``search_result.json`` is THIS function over THIS stopwatch
    — one ledger (mirrored into the telemetry registry as
    ``faa_phase_device_seconds`` gauges), so the stamp cannot drift from
    the measurement; equality is pinned by tests/test_telemetry.py."""
    attr = {int(f): sw.device_seconds(f"phase1_fold{f}") for f in fold_list}
    for i, group in enumerate(stack_groups):
        share = sw.device_seconds(f"phase1_stack{i}") / len(group)
        for f in group:
            attr[int(f)] = attr.get(int(f), 0.0) + share
    return attr


def resolve_quality_floor(floor, num_classes: int) -> float | None:
    """Resolve the fold-oracle quality floor.

    ``"auto"`` (the CLI default since round 4) is chance-relative: the
    fold baseline must close at least 35% of the chance-to-perfect gap,
    ``chance + 0.35 * (1 - chance)`` — 0.415 on a 10-class task, in line
    with the validated 0.45 recipe (docs/search_postmortem_r2.md) while
    scaling to any class count.  Floats pass through; ``None``/``"off"``
    or a non-positive value disables the gate (the pre-round-4
    behavior, which ships the round-2 failure mode — see VERDICT r3)."""
    if floor is None:
        return None
    if isinstance(floor, str):
        if floor == "auto":
            chance = 1.0 / num_classes
            return chance + 0.35 * (1.0 - chance)
        if floor.lower() in ("off", "none"):
            return None
        floor = float(floor)
    return floor if floor > 0 else None


def resolve_fold_stack(fold_stack, num_pending: int) -> int:
    """Resolve the ``--fold-stack`` knob to a stack width.

    ``0`` (default) keeps the sequential per-fold loop bit-for-bit;
    ``"auto"`` stacks every fold that needs training; an int K caps the
    stack at K folds per program.  Widths below 2 degrade to
    sequential (a 1-fold stack buys nothing over the plain path)."""
    if fold_stack in (None, 0, "0"):
        return 0
    if isinstance(fold_stack, str):
        if fold_stack == "auto":
            return num_pending if num_pending >= 2 else 0
        fold_stack = int(fold_stack)
    if fold_stack < 0:
        raise ValueError(f"fold_stack must be >= 0, got {fold_stack}")
    k = min(int(fold_stack), num_pending)
    return k if k >= 2 else 0


def write_json_atomic(path: str, obj) -> None:
    """fsync-then-rename write: a crash mid-write can never tear the
    file, and a crash right after loses nothing (VERDICT r3, weak 4).
    Public: the search CLI persists its result files through this too."""
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(obj, fh)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)


_write_json_atomic = write_json_atomic  # internal call sites


def make_search_space(num_policy: int, num_op: int):
    """The reference's search space (``search.py:214-220``): per (i, j)
    an op choice over the 15 searchable ops, prob ~ U(0,1), level ~ U(0,1)."""
    space = []
    for i in range(num_policy):
        for j in range(num_op):
            space.append(choice(f"policy_{i}_{j}", len(SEARCH_OP_NAMES)))
            space.append(uniform(f"prob_{i}_{j}", 0, 1))
            space.append(uniform(f"level_{i}_{j}", 0, 1))
    return space


class SearchResult(dict):
    @property
    def final_policy_set(self):
        return self["final_policy_set"]


def draw_random_policy_set(num_subs: int, num_policy: int, num_op: int,
                           seed: int) -> list:
    """Uniform draws from the same (op, prob, level) space as
    :func:`make_search_space`, decoded through the same
    ``policy_decoder`` path as TPE proposals.

    The phase-3 control arm (VERDICT r4, next-step 4): density
    matching's actual claim is that SEARCHED policies beat *random*
    ones from the same space — not merely no-augmentation.  Matching
    the searched set's PRE-audit size and auditing identically keeps
    the two arms' selection pipelines aligned except for the ranking
    step under test."""
    rng = np.random.RandomState(seed)
    out: list = []
    stalled = 0
    while len(out) < num_subs:
        proposal = {}
        for i in range(num_policy):
            for j in range(num_op):
                proposal[f"policy_{i}_{j}"] = int(
                    rng.randint(len(SEARCH_OP_NAMES)))
                proposal[f"prob_{i}_{j}"] = float(rng.rand())
                proposal[f"level_{i}_{j}"] = float(rng.rand())
        before = len(out)
        out = remove_duplicates(
            out + policy_decoder(proposal, num_policy, num_op))
        # dedup is by op-name sequence, a space of only
        # len(SEARCH_OP_NAMES)**num_op sequences: demanding more subs
        # than that can never finish — fail instead of spinning
        stalled = stalled + 1 if len(out) == before else 0
        if stalled >= 50:
            raise ValueError(
                f"cannot draw {num_subs} distinct sub-policies: the op-"
                f"sequence space holds only {len(SEARCH_OP_NAMES) ** num_op}"
                f" and {len(out)} are already drawn")
    return out[:num_subs]


def _fold_ckpt_path(save_dir: str, conf, fold: int, cv_ratio: float) -> str:
    tag = f"{conf['model']['type']}_{conf['dataset']}_fold{fold}_ratio{cv_ratio:.2f}"
    return os.path.join(save_dir, f"{tag}.msgpack")


# every per-checkpoint artifact train_and_eval emits: the msgpack, the
# cheap-metadata sidecar, the rollback-chain link (+ its sidecar —
# default --ckpt-keep depth; a stale chain link from a REJECTED retry
# must never survive as rollback material for the promoted fold), and
# the ScalarWriter logs — retry promotion must move/remove all of them
# or the promoted fold keeps the rejected run's training curves
_CKPT_SUFFIXES = ("", ".meta.json", ".prev", ".prev.meta.json",
                  "_train.jsonl", "_valid.jsonl", "_test.jsonl")


def _replace_ckpt(src: str, dst: str):
    """Promote a retrained fold checkpoint (+ all sidecars)."""
    for suffix in _CKPT_SUFFIXES:
        if os.path.exists(dst + suffix):
            os.remove(dst + suffix)
        if os.path.exists(src + suffix):
            shutil.move(src + suffix, dst + suffix)


def _remove_ckpt(path: str):
    for suffix in _CKPT_SUFFIXES:
        if os.path.exists(path + suffix):
            os.remove(path + suffix)


def _call_train_fold_fn(fn: Callable, conf, fold: int, path: str, seed: int):
    """Invoke a phase-1 training override with an explicit seed.

    The hook protocol is ``fn(conf, fold, save_path, seed=...)``;
    legacy three-argument overrides still work — they get the seed
    riding on ``conf['seed']`` (ADVICE r4: a thin wrapper around
    ``train_and_eval(conf, fold, path)`` ignored conf-level seed, so
    quality-gate retries deterministically reproduced the same weak
    oracle)."""
    import inspect

    try:
        params = inspect.signature(fn).parameters
        takes_seed = "seed" in params or any(
            p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
        )
    except (TypeError, ValueError):  # builtins / C callables
        takes_seed = False
    conf = conf.replace(**{"seed": seed})
    if takes_seed:
        return fn(conf, fold, path, seed=seed)
    return fn(conf, fold, path)


class _FoldEval:
    """Lazily-built TTA machinery shared by the fold-quality gate,
    phase 2 and the sub-policy audit: one compiled step, per-fold
    device-resident batch caches, a checkpoint template."""

    def __init__(self, conf, dataroot, mesh, *, num_policy, num_op, cv_ratio,
                 seed, trial_batch: int = 1, aug_dispatch: str = "exact",
                 aug_groups: int = 8, watchdog=None, trace=None):
        from fast_autoaugment_tpu.ops.augment import check_aug_dispatch

        self.conf, self.dataroot, self.mesh = conf, dataroot, mesh
        self.num_policy, self.num_op = num_policy, num_op
        self.cv_ratio, self.seed = cv_ratio, seed
        self.trial_batch = max(1, int(trial_batch))
        self.aug_dispatch = check_aug_dispatch(aug_dispatch)
        self.aug_groups = max(1, int(aug_groups))
        self.watchdog = resolve_watchdog(watchdog)
        # optional DispatchTrace (search/pipeline.py): per-dispatch
        # start/end timestamps for the dispatch-gap evidence
        self.trace = trace
        self._built = False
        # the async pipeline evaluates from several actor threads (and
        # the overlapped phase-1 gate from the trainer thread): build
        # and per-fold batch-cache population are lock-guarded
        self._lock = threading.RLock()
        self._batches: dict[int, Callable] = {}
        # distinct leading policy-tensor shapes fed to the compiled TTA
        # step; the executable-count invariant is exactly one compile
        # per shape (the gate's identity baseline is [1, num_op, 3],
        # trials are [num_policy, num_op, 3])
        self.policy_shapes: set[int] = set()
        # candidate-axis sizes fed to the BATCHED step (trial_batch > 1):
        # its invariant is one executable for the single fixed K
        self.batch_policy_shapes: set[int] = set()

    def _build(self):
        with self._lock:
            self._build_locked()

    def _build_locked(self):
        if self._built:
            return
        conf, mesh = self.conf, self.mesh
        dataset_name = conf["dataset"]
        num_classes = num_class(dataset_name)
        self.num_classes = num_classes
        self.total_train, _test = load_dataset(dataset_name, self.dataroot)
        model_conf = dict(conf["model"], dataset=dataset_name)
        model_conf.setdefault("precision", conf.get("precision", "f32"))
        model = get_model(model_conf, num_classes)
        cutout_length = int(conf.get("cutout", 0) or 0)

        # the TTA loaders use the TRAIN transform stack (the reference's
        # validloader shares the train dataset's transforms, data.py:88-112)
        from fast_autoaugment_tpu.models import input_image_size

        # same conf['imgsize'] override as train_and_eval — phase 2 must
        # evaluate the phase-1 checkpoints at the resolution they trained at
        image = int(conf.get("imgsize", 0) or 0) or input_image_size(
            dataset_name, conf["model"]["type"]
        )
        self.image = image
        if dataset_name.endswith("imagenet"):
            from fast_autoaugment_tpu.ops.preprocess_imagenet import (
                imagenet_train_batch,
                random_crop_box,
            )

            tta_augment_fn = lambda images, pol, key: imagenet_train_batch(  # noqa: E731
                images, key, pol, cutout_length=cutout_length,
                aug_dispatch=self.aug_dispatch, aug_groups=self.aug_groups,
            )
            self._box_fn = lambda rng, w, h: random_crop_box(rng, w, h, image)  # noqa: E731
        else:
            tta_augment_fn = None
            self._box_fn = None
        dispatch_kw = dict(aug_dispatch=self.aug_dispatch,
                           aug_groups=self.aug_groups)
        self.tta_step = make_tta_step(
            model, num_policy=self.num_policy, cutout_length=cutout_length,
            augment_fn=tta_augment_fn, **dispatch_kw,
        )
        # jit wrapping is free; XLA compiles at the first audit_eval call
        self.audit_step = make_audit_step(
            model, num_policy=self.num_policy, cutout_length=cutout_length,
            augment_fn=tta_augment_fn, **dispatch_kw,
        )
        # trial-parallel TTA: K candidate policies per device program
        # (jit wrapping free here too; compiles at the first batch)
        self.tta_step_batch = None
        if self.trial_batch > 1:
            self.tta_step_batch = make_tta_step(
                model, num_policy=self.num_policy,
                cutout_length=cutout_length, augment_fn=tta_augment_fn,
                num_candidates=self.trial_batch, **dispatch_kw,
            )

        # checkpoint template, built once (models are input-size-polymorphic
        # after init, but use the real resolution for clarity)
        from fast_autoaugment_tpu.ops.optim import build_optimizer
        from fast_autoaugment_tpu.train.steps import create_train_state

        sample = jnp.zeros((2, image, image, 3), jnp.float32)
        optimizer = build_optimizer(dict(conf["optimizer"]), lambda s: 0.0)
        self.template = create_train_state(
            model, optimizer, jax.random.PRNGKey(0), sample,
            use_ema=bool(conf.get("optimizer", {}).get("ema", 0)),
        )
        self._built = True

    def load_fold(self, path: str):
        self._build()
        state = load_checkpoint(path, self.template)
        return state.params, state.batch_stats

    def batches_fn(self, fold: int) -> Callable:
        """Batch source for a fold's held-out split.  In-memory datasets
        upload the fold ONCE and replay the device-resident batches for
        every trial (the data never changes between TPE samples — only
        the policy tensor does); lazy on-disk datasets (ImageNet) stream
        through a prefetch worker."""
        self._build()
        with self._lock:
            return self._batches_locked(fold)

    def _batches_locked(self, fold: int) -> Callable:
        if fold in self._batches:
            return self._batches[fold]
        from fast_autoaugment_tpu.data.pipeline import BatchIterator
        from fast_autoaugment_tpu.parallel.mesh import shard_transform

        _train_idx, valid_idx = cv_split(self.total_train.labels, self.cv_ratio, fold)
        batch = int(self.conf["batch"]) * self.mesh.size
        fold_it = BatchIterator(
            self.total_train, valid_idx,
            eval_box_fn=self._box_fn, train_box_fn=self._box_fn,
            imgsize=self.image,
        )

        def _stream():
            # pad the final batch to FULL size (not just the mesh
            # multiple): every batch then has one static shape, so the
            # TTA/audit executables never fork on the remainder batch —
            # one compile serves the entire search (the masks already
            # carry correctness; the waste is <1 batch per fold epoch)
            return fold_it.eval_epoch(
                batch, process_index=jax.process_index(),
                process_count=jax.process_count(), pad_multiple=batch,
            )

        _to_device = shard_transform(self.mesh, ("x", "y", "m"))
        if not self.total_train.lazy:
            cached = [_to_device(t) for t in _stream()]
            fn = lambda: iter(cached)  # noqa: E731
        else:
            from fast_autoaugment_tpu.data.pipeline import prefetch

            fn = lambda: prefetch(_stream(), transform=_to_device)  # noqa: E731
        self._batches[fold] = fn
        return fn

    def _guarded(self, label: str, fn, *args):
        """TTA/audit evaluations through the watchdog seam (one
        monitored window per whole-fold evaluation; the per-label EMA
        tracks the full replay wall).  Off = the direct call."""
        if not self.watchdog.enabled:
            return fn(*args)
        return self.watchdog.run(label, fn, *args)

    def _trace_cb(self):
        return self.trace.record if self.trace is not None else None

    def evaluate(self, fold: int, params, batch_stats, policy_t, key) -> dict:
        self.policy_shapes.add(int(policy_t.shape[0]))
        return self._guarded(
            "tta", eval_tta,
            self.tta_step, params, batch_stats, self.batches_fn(fold)(),
            policy_t, key, self._trace_cb(),
        )

    def evaluate_batch(self, fold: int, params, batch_stats, policies_t,
                       keys) -> list[dict]:
        """K candidate policies against the fold in one vmapped program
        per batch.  `policies_t` is [K, num_sub, num_op, 3] with
        K == trial_batch (the compiled candidate-axis size); `keys` is
        the [K]-stack of per-candidate trial keys."""
        self._build()
        if self.tta_step_batch is None:
            raise RuntimeError("evaluate_batch requires trial_batch > 1")
        if int(policies_t.shape[0]) != self.trial_batch:
            raise ValueError(
                f"candidate axis {int(policies_t.shape[0])} != compiled "
                f"trial_batch {self.trial_batch}")
        self.batch_policy_shapes.add(int(policies_t.shape[0]))
        return self._guarded(
            "tta_batched", eval_tta_batched,
            self.tta_step_batch, params, batch_stats,
            self.batches_fn(fold)(), policies_t, keys, self._trace_cb(),
        )

    def audit_eval(self, params, batch_stats, batch, subs, key) -> dict:
        """Batched audit: S sub-policies against one mesh-placed batch
        in a single compiled call (``make_audit_step``)."""
        from fast_autoaugment_tpu.core.watchdog import (
            dispatch_enqueue_guard,
        )

        self._build()

        def _dispatch(*args):  # serialized enqueue (async pipeline only)
            with dispatch_enqueue_guard():
                return self.audit_step(*args)

        return self._guarded(
            "audit", _dispatch, params, batch_stats, batch["x"],
            batch["y"], batch["m"], subs, key)

    def baseline(self, fold: int, path: str) -> float:
        """No-candidate-policy fold accuracy: the identity policy (one
        all-zero sub-policy row: op 0 gated at prob 0) through the same
        compiled step — i.e. fold accuracy under the default transform
        stack alone.  The oracle-quality measure the gate and audit
        normalize against."""
        params, batch_stats = self.load_fold(path)
        ident = jnp.zeros((1, self.num_op, 3), jnp.float32)
        out = self.evaluate(fold, params, batch_stats, ident,
                            jax.random.PRNGKey(17))
        return float(out["top1_mean"])


def search_policies(
    conf,
    dataroot: str,
    save_dir: str,
    *,
    cv_num: int = 5,
    cv_ratio: float = 0.4,
    num_policy: int = 5,
    num_op: int = 2,
    num_search: int = 200,
    num_top: int = 10,
    smoke_test: bool = False,
    resume: bool = True,
    train_fold_fn: Callable | None = None,
    until: int = 2,
    folds: list[int] | None = None,
    seed: int = 0,
    fold_quality_floor: float | None = None,
    fold_retrain_tries: int = 2,
    phase1_epochs: int | None = None,
    audit_floor: float | None = None,
    random_control: bool = False,
    trial_batch: int = 1,
    fold_stack: int | str = 0,
    aug_dispatch: str = "exact",
    aug_groups: int = 8,
    device_cache: str = "auto",
    steps_per_dispatch: int = 1,
    divergence_retries: int = 0,
    ckpt_keep: int = 2,
    watchdog="off",
    work_queue=None,
    async_pipeline: str | bool = "off",
    pipeline_actors: int = 1,
    pipeline_queue_depth: int = 1,
    telemetry_spec: str = "off",
    fleet_transport=None,
    topup_trials: int = 0,
) -> SearchResult:
    """Run phases 1 and 2; returns the final policy set plus accounting.

    `train_fold_fn(conf, fold, save_path, seed=...)` overrides phase-1
    training (the launcher passes a multi-host scatter; default trains
    in-process sequentially, the single-host analog of the reference's
    Ray scatter, ``search.py:170-206``).  Quality-gate retrains route
    through the same override with a fresh explicit ``seed``; legacy
    three-argument hooks receive it as ``conf['seed']`` instead.

    `folds` restricts BOTH phases to a subset of fold indices — the
    scatter unit for running the search across machines (host k runs
    ``--folds k``, then one host merges the per-fold trial JSONs by
    rerunning with all folds, which resumes instantly from the merged
    trial state).

    `fold_quality_floor` enables the fold-oracle quality gate: folds
    whose no-policy baseline accuracy stays below the floor after
    `fold_retrain_tries` fresh-seed retrains are excluded from ranking.
    `phase1_epochs` overrides conf['epoch'] for phase-1 fold pretraining
    only (weak oracles on small folds are usually under-trained, not
    under-parameterized).  `audit_floor` (None disables) drops any
    selected sub-policy whose standalone mean-over-draws fold accuracy
    falls below ``audit_floor x fold_baseline`` averaged over the folds
    that pass the gate.  All three are additions over the reference —
    see the module docstring and docs/search_postmortem_r2.md.

    `trial_batch` (K, default 1) makes phase 2 TRIAL-PARALLEL ON ONE
    HOST: the TPE proposes K candidates per round (constant-liar
    ``ask(K)``), all K are evaluated by ONE vmapped TTA program per
    batch (K x num_policy x batch forwards filling the device — the
    Podracer batching pattern, arXiv:2104.06272, with the fan-out as a
    mapped primitive in the DrJAX style, arXiv:2403.07128), and the K
    true rewards are told back together.  K=1 takes the sequential code
    path bit-for-bit.  This is the single-host answer to the
    reference's 80 concurrent Ray trials (``search.py:230``); it
    composes with the ``--folds`` multi-host scatter below.  Trial-log
    persistence/resume is per ROUND of K (a crash loses at most the
    in-flight batch).

    `fold_stack` (0, "auto", or K >= 2; default 0) makes phase 1
    FOLD-PARALLEL: every fold needing fresh training advances through
    ONE vmapped K-model program per step (``train_folds_stacked`` — the
    Podracer learner-replica stacking, arXiv:2104.06272), fed by a
    multiplexed iterator that gathers the K per-fold shuffled index
    streams out of the one shared dataset.  0 keeps today's sequential
    loop bit-for-bit; stacked per-fold training reproduces the
    sequential per-fold data and key streams exactly and deviates only
    by the documented ~1 f32 ULP/step batched-kernel bound
    (train_folds_stacked docstring; tests/test_stacked_phase1.py).
    The stacked path only covers the default in-process trainer on
    in-memory datasets: a `train_fold_fn` override, lazy (ImageNet)
    datasets, and every quality-gate retrain take the sequential path
    unchanged.

    `device_cache` ("auto"/"on"/"off") and `steps_per_dispatch` (N)
    select the device-resident data path for every phase-1 training run
    (sequential folds, the fold stack, and quality-gate retrains): the
    dataset is uploaded once, per-epoch index matrices replace the image
    feed, and one dispatch advances N steps (x K folds when stacked) —
    ``train.trainer.train_and_eval`` docstring.  Defaults ("auto", 1)
    are bit-for-bit with the host-fed path on eager datasets; lazy
    (ImageNet) datasets keep the prefetch path under "auto".  Both are
    stamped into ``search_result.json``.  Phase-2 TTA already replays
    device-resident fold batches (``_FoldEval``), so the knob does not
    touch it.

    `aug_dispatch` ("exact" default / "grouped") selects the policy
    application kernel for phase-2 TTA evaluation and the sub-policy
    audit; `aug_groups` is the grouped chunk count.  "exact" reproduces
    the historical vmapped-switch path bit-for-bit; "grouped" keeps the
    ``lax.switch`` op index scalar inside the compiled programs
    (single-branch execution; stratified per-chunk sub-policy draws in
    the multi-sub TTA step, bitwise-identical single-sub lanes in the
    audit and the quality-gate baseline — see docs/PARITY.md
    "Augmentation dispatch").  Both settings are stamped into
    ``search_result.json``.  Phase-1 pretraining is policy-free, so the
    knob does not touch it.

    Resilience (docs/RESILIENCE.md): `divergence_retries` and
    `ckpt_keep` thread into every phase-1/retry training run (rollback
    chains + NaN-epoch replay); a phase-2 trial whose TTA evaluation
    raises is QUARANTINED — told to the TPE as the worst observed
    reward (the constant-liar value) and recorded with its failure in
    the trial log and ``search_result.json['quarantined_trials']`` —
    instead of killing the search.  A preemption request
    (:class:`PreemptedError`) always propagates: per-fold checkpoints
    and the per-trial log make the rerun resume where it stopped.

    `watchdog` ("off" default / "auto" / seconds) deadline-guards every
    device dispatch this search issues — phase-1 train dispatches, TTA
    evaluations, the audit — raising the typed ``DispatchHungError``
    (exit-77 process-restart recovery) when one wedges; fire counts
    and per-label deadlines are stamped into
    ``search_result.json['resilience']['watchdog']``.

    `work_queue` (a :class:`~fast_autoaugment_tpu.launch.workqueue.
    WorkQueue` over a shared directory, or None) makes the multi-host
    scatter ELASTIC: instead of the static ``--folds`` assignment,
    hosts claim phase-1 fold trainings (with their gate retrains) and
    per-fold phase-2 trial searches off a lease queue, renew the lease
    at dispatch/round boundaries, and RECLAIM units whose lease went
    stale — a dead host's fold is finished by a survivor from the PR-5
    checkpoint chain + per-fold trial log, and the search completes
    with any >= 1 live host.  Trial logs are per-fold files
    (``search_trials.fold<k>.json``) in this mode so concurrent hosts
    never clobber one shared file; the accounting (``degraded``,
    ``lost_hosts``, ``reclaimed_units``) is stamped into the result.
    Fold stacking is forced off (work units are per fold).

    `async_pipeline` ("off" default / "on") restructures the search as
    the streaming actor/learner pipeline (``search/pipeline.py``, the
    Podracer decomposition, arXiv:2104.06272): device ACTOR threads
    (`pipeline_actors`) pull ready-built candidate rounds from a
    bounded queue (`pipeline_queue_depth` rounds proposed ahead) and
    run the usual ``_FoldEval`` TTA dispatches, while the TPE LEARNER
    digests completed rounds and refills proposals concurrently through
    the proposal ledger (``tpe.ask_tagged``/``tell(trial_id, ...)`` —
    out-of-order completions apply in canonical trial-id order, so the
    whole schedule is deterministic given the geometry).  On top, a
    PHASE-OVERLAP scheduler starts fold k's phase-2 trials the moment
    fold k's phase-1 training and quality gate complete, while the
    remaining folds still train (the single-host MPMD pipeline seed,
    arXiv:2412.14374).  "off" (default) is bit-for-bit the historical
    serial driver; "on" with ``pipeline_actors=1, pipeline_queue_depth
    =0`` reproduces the serial trial log exactly (in-flight window of
    one round = no constant-liar horizon), and deeper geometries
    deviate only the way a larger `trial_batch` does — pessimistic
    placeholder posteriors for in-flight rounds.  Accounting lands in
    ``search_result.json['pipeline']`` (mode, actors, queue_depth,
    tell_reorders, device_busy_frac + the dispatch-gap histogram);
    the determinism rule is ``search/pipeline.py``'s docstring.
    Async mode is single-host:
    `work_queue` forces it off (work units already scatter folds).

    `fleet_transport` (a :class:`~fast_autoaugment_tpu.search.pipeline.
    FleetTransport` over a shared directory, or None) promotes the
    async pipeline's candidate queue to a CROSS-HOST transport: this
    process becomes the LEARNER host — it trains phase-1 folds,
    publishes each gate-cleared fold checkpoint to the fleet the moment
    the gate clears, and publishes ask rounds as leased work units that
    dedicated ACTOR hosts (``search_cli --search-role actor``) claim,
    evaluate, and answer with posted rewards.  The learner buffers
    out-of-order completions and applies them in trial-id order exactly
    as the in-process pipeline does, so an N-host fleet reproduces the
    single-host ``--async-pipeline`` artifacts BIT FOR BIT when
    launched with the same ``pipeline_actors + pipeline_queue_depth``
    in-flight window; dead or preempted actor hosts are reclaimed for
    free by the lease TTL + the fleet ``--elastic`` stack.  Implies
    ``async_pipeline=on`` (the learner schedule IS the pipeline
    schedule) and is mutually exclusive with `work_queue` (which
    scatters whole folds instead of rounds).

    Every compile this search pays — phase-1 training, TTA, audit,
    retrains — goes through the persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` (or the fixed in-checkout default)
    places it, so a fresh process (exit-77 resume, fleet retry,
    reclaimed unit) deserializes its executables instead of re-lowering
    them; hit/miss counts and per-label first-call seconds are stamped
    into ``search_result.json['compile_cache']``
    (``core/compilecache.py``).

    `topup_trials` (0 default) is the WARM-START entry point the
    control plane's incremental re-search uses (``control/research.py``,
    docs/CONTROL.md): a completed search's per-fold budget extends by
    this many trials — resume replays the persisted trial log (through
    the PR-9 ``replay_trial_log`` ledger under ``async_pipeline``), only
    the top-up dispatches, and the artifact stamps ``warm_start``.  A
    top-up of 0 is a plain resume: `final_policy.json` reproduces the
    one-shot run byte-identically.

    PHASE ordering stays sequential (VERDICT round 1, next-step 9):
    phase-1 fold training and phase-2 TTA evaluation are both
    device-bound on the same chip, so overlapping PHASES cannot shorten
    the critical path.  The reference's concurrent fold trains
    (``search.py:170-206``) exploit a multi-GPU Ray cluster; the
    equivalent concurrency here is `trial_batch` within a fold plus the
    ``--folds`` multi-host scatter across folds (each host pretrains
    AND searches its own folds in parallel with the others), merged by
    ``tools/merge_trials.py``.  Per-fold checkpoint + trial-log resume
    means an interrupted run loses at most the in-flight work.
    """
    if smoke_test:  # reference --smoke-test (search.py:153, 235)
        num_search = 4

    # warm-started incremental re-search (the control plane's entry
    # point, control/research.py + docs/CONTROL.md): `topup_trials` > 0
    # EXTENDS a completed search's per-fold trial budget by that many
    # trials — resume replays the persisted trial log (the async
    # pipeline routes it through the PR-9 replay_trial_log ledger, so
    # the TPE's RNG stream sits exactly where the original run left
    # it), then only the top-up trials dispatch.  0 (default) leaves
    # the historical budget — and the artifact stream — untouched;
    # topup with an EMPTY save_dir is just a longer fresh search.
    topup_trials = max(0, int(topup_trials))
    if topup_trials:
        base_num_search = num_search
        num_search += topup_trials

    # persistent compile cache (core/compilecache.py): every compile
    # this search pays is classified hit/miss and stamped into
    # search_result.json['compile_cache'] — how fleet retries and
    # reclaimed work units prove they warm-started
    configure_compile_cache()
    # flight-recorder journal (core/telemetry.py): "off" (default,
    # bit-for-bit — no file I/O, no new artifact keys) still honors an
    # inherited FAA_TELEMETRY, the fleet/relaunch handoff
    telemetry.configure_telemetry(telemetry_spec)
    fold_quality_floor = resolve_quality_floor(
        fold_quality_floor, num_class(conf["dataset"])
    )
    os.makedirs(save_dir, exist_ok=True)
    mesh = make_mesh()
    watch = {"start": wall()}
    result = SearchResult()
    # device-hours ledger provenance: the ``device_secs_*`` fields are
    # wall x device_count on WHATEVER backend ran — a CPU rehearsal must
    # not read as TPU-hours.  Every consumer can tell from the artifact.
    result.update(device_stamp())
    # the guard settings this run actually used — the defaults-safety
    # regression test reads these back from the committed artifact
    result["guards"] = {
        "fold_quality_floor": fold_quality_floor,
        "fold_retrain_tries": fold_retrain_tries,
        "audit_floor": audit_floor,
        "phase1_epochs": phase1_epochs,
    }
    fold_list = list(folds) if folds is not None else list(range(cv_num))
    bad = [f for f in fold_list if not 0 <= f < cv_num]
    if bad:
        raise ValueError(f"fold indices {bad} out of range [0, {cv_num})")

    trials_path = os.path.join(save_dir, "search_trials.json")
    trials_log: dict = {}
    if resume and os.path.exists(trials_path):
        trials_log = fsfault.load_json(trials_path)

    def _fold_trials_path(fold: int) -> str:
        """Per-fold trial log (work-queue mode): one writer per lease,
        so concurrent hosts can never clobber each other's folds."""
        return os.path.join(save_dir, f"search_trials.fold{fold}.json")

    def _load_fold_trials(fold: int) -> list:
        if work_queue is not None and os.path.exists(_fold_trials_path(fold)):
            return fsfault.load_json(_fold_trials_path(fold))
        return trials_log.get(str(fold), [])

    def _fold_searched(fold: int) -> bool:
        return len(_load_fold_trials(fold)) >= num_search

    trial_batch = max(1, int(trial_batch))
    result["trial_batch"] = trial_batch
    if topup_trials:
        # stamped ONLY on warm-started runs: a default run's artifact
        # carries no new keys (the defaults-bit-for-bit contract)
        result["warm_start"] = {
            "base_num_search": base_num_search,
            "topup_trials": topup_trials,
            "num_search": num_search,
            "resumed_trials_per_fold": {
                str(f): len(_load_fold_trials(f)) for f in fold_list},
        }
    wd = resolve_watchdog(watchdog)
    # async actor/learner pipeline (search/pipeline.py): resolved here
    # so a typo fails loudly before any training; the dispatch trace is
    # armed for async runs and (FAA_PIPELINE_TRACE=1) serial baselines
    # so the pipeline bench can compare gap histograms
    from fast_autoaugment_tpu.search.pipeline import (
        DispatchTrace,
        resolve_async_pipeline,
    )

    pipeline_on = resolve_async_pipeline(async_pipeline)
    pipeline_actors = max(1, int(pipeline_actors))
    pipeline_queue_depth = max(0, int(pipeline_queue_depth))
    if fleet_transport is not None and work_queue is not None:
        raise ValueError(
            "fleet_transport and work_queue are mutually exclusive: the "
            "round transport scatters ask ROUNDS across actor hosts, the "
            "lease workqueue scatters whole FOLDS across peer searches")
    if fleet_transport is not None and not pipeline_on:
        # the learner schedule IS the pipeline schedule (ask horizon,
        # reorder buffer, id-order tells) — rounds just dispatch to
        # actor hosts instead of actor threads
        logger.info("fleet transport: async pipeline forced ON (the "
                    "learner's round schedule is the pipeline schedule)")
        pipeline_on = True
    if pipeline_on and work_queue is not None:
        logger.warning("workqueue: async pipeline forced off — the lease "
                       "queue already scatters folds across hosts")
        pipeline_on = False
    # async mode dispatches compiled programs from several threads:
    # serialize their ENQUEUE so every device queue sees one global
    # program order (the cross-thread collective rendezvous deadlock —
    # core/watchdog.py docstring).  Explicitly disarmed for serial runs
    # so one process can alternate modes.
    from fast_autoaugment_tpu.core.watchdog import arm_dispatch_serializer

    arm_dispatch_serializer(pipeline_on)
    trace = None
    if pipeline_on or os.environ.get("FAA_PIPELINE_TRACE"):
        trace = DispatchTrace()
    evaluator = _FoldEval(
        conf, dataroot, mesh,
        num_policy=num_policy, num_op=num_op, cv_ratio=cv_ratio, seed=seed,
        trial_batch=trial_batch, aug_dispatch=aug_dispatch,
        aug_groups=aug_groups, watchdog=wd, trace=trace,
    )
    # dispatch-mode stamping: the artifact must say which augmentation
    # kernel scored these trials (grouped deviates distributionally)
    result["aug_dispatch"] = evaluator.aug_dispatch
    result["aug_groups"] = evaluator.aug_groups
    # feed-path stamping: which data path trained the phase-1 oracles
    # (steps_per_dispatch>1 deviates by the documented scan ULP bound)
    steps_per_dispatch = max(1, int(steps_per_dispatch))
    result["device_cache"] = device_cache
    result["steps_per_dispatch"] = steps_per_dispatch
    divergence_retries = max(0, int(divergence_retries))
    ckpt_keep = max(1, int(ckpt_keep))
    result["resilience"] = {"divergence_retries": divergence_retries,
                            "ckpt_keep": ckpt_keep,
                            "watchdog": wd.stats()}
    # quarantined phase-2 trials (TTA evaluation raised): recorded, told
    # to TPE as the worst observed reward, never ranked
    quarantined: list[dict] = []
    # shared by the sequential trainer AND the fold stack; the
    # divergence-retry knob is sequential-only (train_and_eval); the
    # ONE watchdog instance threads through so fire counts aggregate
    train_feed_kw = dict(device_cache=device_cache,
                         steps_per_dispatch=steps_per_dispatch,
                         ckpt_keep=ckpt_keep, watchdog=wd)
    seq_train_kw = dict(train_feed_kw, divergence_retries=divergence_retries)

    def _lease_heartbeat(unit: str):
        """Dispatch-boundary callback for the trainer / trial loop:
        renew the unit's lease + this host's liveness beat."""
        if work_queue is None:
            return None

        def beat():
            work_queue.renew(unit)
            work_queue.beat_host()
        return beat
    fold_baselines: dict[int, float] = {}
    excluded_folds: list[int] = []

    # ---------------- phase 1: pretrain without augmentation ----------
    t0 = wall()
    no_aug_conf = conf.replace(aug="default")
    if phase1_epochs:
        no_aug_conf = no_aug_conf.replace(epoch=int(phase1_epochs))
    fold_paths = [_fold_ckpt_path(save_dir, conf, f, cv_ratio)
                  for f in range(cv_num)]
    phase1_epochs_eff = int(no_aug_conf["epoch"])
    # per-fold device-seconds attribution: every phase-1 training wall
    # is measured on ONE PhaseStopwatch ledger (utils/profiling.py,
    # mirrored into the telemetry registry) and attributed per fold by
    # phase1_device_seconds_attribution — stacked groups split their one
    # measured wall evenly; device_secs_phase1 stays the once-recorded
    # phase total and the attribution must sum to (at most) it
    from fast_autoaugment_tpu.utils.profiling import PhaseStopwatch

    phase1_sw = PhaseStopwatch(device_count=mesh.size,
                               registry=telemetry.registry())
    stack_groups: list[list[int]] = []

    def _needs_training(fold: int) -> bool:
        meta = read_metadata(fold_paths[fold])
        return not (resume and meta
                    and meta.get("epoch", 0) >= phase1_epochs_eff)

    # fold-stacked phase 1 (the tentpole): all pending folds advance in
    # one vmapped program; the per-fold loop below then finds their
    # checkpoints complete and only runs the quality gate / accounting.
    stack_trained: set[int] = set()
    pending = [f for f in fold_list
               if not _fold_searched(f) and _needs_training(f)]
    if work_queue is not None and fold_stack not in (None, 0, "0"):
        # lease units are per fold: a stacked group would advance folds
        # this host does not own
        logger.warning("workqueue: fold stacking forced off — work "
                       "units are per fold")
        fold_stack = 0
    stack_k = resolve_fold_stack(fold_stack, len(pending))
    if stack_k and train_fold_fn is not None:
        logger.warning(
            "fold-stack: a train_fold_fn override is set — the stacked "
            "trainer only covers the in-process default; falling back "
            "to the sequential per-fold path")
        stack_k = 0
    if stack_k and conf["dataset"].endswith("imagenet"):
        logger.warning(
            "fold-stack: %s is a lazy on-disk dataset — per-fold host "
            "decode streams cannot be multiplexed bit-for-bit; falling "
            "back to the sequential per-fold path", conf["dataset"])
        stack_k = 0
    result["fold_stack"] = stack_k
    if stack_k:
        for lo in range(0, len(pending), stack_k):
            group = pending[lo:lo + stack_k]
            logger.info("phase1: training folds %s fold-stacked (K=%d)",
                        group, len(group))
            with phase1_sw.phase(f"phase1_stack{len(stack_groups)}"):
                train_folds_stacked(
                    no_aug_conf, dataroot, cv_ratio=cv_ratio, folds=group,
                    save_paths=[fold_paths[f] for f in group], seed=seed,
                    resume=resume, **train_feed_kw,
                )
            stack_groups.append([int(f) for f in group])
            stack_trained.update(group)

    def _phase1_fold(fold: int, heartbeat=None) -> None:
        """The full per-fold phase-1 body: train if needed, then the
        fold-oracle quality gate (+fresh-seed retrains).  `heartbeat`
        (work-queue mode) renews the fold's lease at every trainer
        dispatch boundary."""
        path = fold_paths[fold]
        if _fold_searched(fold):
            # merged trial state from another host: nothing left to train,
            # but the quality gate still applies — a resumed weak oracle
            # must not rank policies (its trial budget is spent, so no
            # retrain: measure and exclude only)
            logger.info("phase1: fold %d already searched (merged trials)", fold)
            if fold_quality_floor is not None:
                if os.path.exists(path):
                    acc = evaluator.baseline(fold, path)
                    fold_baselines[fold] = acc
                    if acc < fold_quality_floor:
                        logger.warning(
                            "phase1: resumed fold %d baseline %.3f below "
                            "floor %.3f — EXCLUDED from ranking", fold, acc,
                            fold_quality_floor,
                        )
                        excluded_folds.append(fold)
                else:
                    logger.warning(
                        "phase1: fold %d searched elsewhere and its "
                        "checkpoint is not on this host — quality gate "
                        "cannot assess it; trials rank ungated", fold,
                    )
            return
        meta = read_metadata(path)
        if fold in stack_trained:
            logger.info("phase1: fold %d trained in the stacked program", fold)
        elif not (resume and meta and meta.get("epoch", 0) >= phase1_epochs_eff):
            logger.info("phase1: training fold %d -> %s", fold, path)
            with phase1_sw.phase(f"phase1_fold{fold}"):
                if train_fold_fn is not None:
                    _call_train_fold_fn(train_fold_fn, no_aug_conf, fold,
                                        path, seed)
                else:
                    train_and_eval(
                        no_aug_conf, dataroot,
                        test_ratio=cv_ratio, cv_fold=fold,
                        save_path=path, metric="last", seed=seed,
                        heartbeat=heartbeat, **seq_train_kw,
                    )
        else:
            logger.info("phase1: fold %d already trained (epoch %d)", fold, meta["epoch"])

        # fold-oracle quality gate (round-2 post-mortem: fold baselines
        # of 0.37-0.65 produced a reward signal that ranked destructive
        # policies on top)
        if fold_quality_floor is None:
            return
        acc = evaluator.baseline(fold, path)
        tries = 0
        while acc < fold_quality_floor and tries < fold_retrain_tries:
            tries += 1
            alt = f"{path}.retry{tries}"
            logger.warning(
                "phase1: fold %d baseline %.3f < floor %.3f — retraining "
                "with a fresh seed (try %d/%d)",
                fold, acc, fold_quality_floor, tries, fold_retrain_tries,
            )
            _remove_ckpt(alt)
            retry_seed = seed + 1009 * tries + fold
            with phase1_sw.phase(f"phase1_fold{fold}"):
                if train_fold_fn is not None:
                    # same mechanism as the initial training (a caller's
                    # scatter/trainer override applies to retries too);
                    # the fresh seed is passed explicitly when the hook
                    # accepts it, and rides on conf['seed'] either way
                    _call_train_fold_fn(
                        train_fold_fn, no_aug_conf, fold, alt, retry_seed
                    )
                else:
                    train_and_eval(
                        no_aug_conf, dataroot, test_ratio=cv_ratio,
                        cv_fold=fold,
                        save_path=alt, metric="last", seed=retry_seed,
                        heartbeat=heartbeat, **seq_train_kw,
                    )
            alt_acc = evaluator.baseline(fold, alt)
            if alt_acc > acc:
                _replace_ckpt(alt, path)
                acc = alt_acc
            else:
                _remove_ckpt(alt)
        fold_baselines[fold] = acc
        if acc < fold_quality_floor:
            logger.warning(
                "phase1: fold %d baseline %.3f still below floor %.3f after "
                "%d retrains — EXCLUDED from policy ranking",
                fold, acc, fold_quality_floor, fold_retrain_tries,
            )
            excluded_folds.append(fold)
        else:
            logger.info("phase1: fold %d baseline %.3f (floor %.3f) ok",
                        fold, acc, fold_quality_floor)

    def _workqueue_phase(units: dict[int, str], run) -> None:
        """Claim-and-run `units` ({fold: unit_id}) until EVERY unit is
        done (by this host or any other).  Passes that find nothing
        claimable wait out a fraction of the TTL — a stale lease (dead
        or wedged owner) is then reclaimed and the unit finished here,
        resuming from the shared checkpoint chain / trial log.  A
        LeaseLostError mid-work abandons the unit to its new owner
        (this host was presumed dead; its writes stay safe — same
        seeds, same atomic chain)."""
        from fast_autoaugment_tpu.launch.workqueue import LeaseLostError

        pending = dict(units)
        while pending:
            progress = False
            for fold, unit in sorted(pending.items()):
                if work_queue.is_done(unit):
                    del pending[fold]
                    progress = True
                    continue
                if not work_queue.claim(unit):
                    continue
                work_queue.beat_host()
                try:
                    info = run(fold, unit)
                    # release() verifies the fencing token at post
                    # time — a robbed host raises here instead of
                    # clobbering the reclaimer's completion record
                    work_queue.release(unit, info=info)
                except LeaseLostError as e:
                    logger.warning(
                        "workqueue: lost the lease on %s mid-work (%s) — "
                        "abandoning it to its new owner", unit, e)
                    continue
                del pending[fold]
                progress = True
            if pending and not progress:
                work_queue.beat_host()
                # TTL-bounded claim poll: the loop's exit is queue
                # completion by ANY host, and each wait is capped well
                # under the lease TTL so reclaims are never starved
                time.sleep(max(0.2, min(5.0, work_queue.lease_ttl / 4.0)))  # robust: allow
        work_queue.beat_host()

    # phase overlap (async pipeline): phase-1 fold training moves onto
    # a trainer thread inside the phase-2 section below — fold k's TPE
    # trials start the moment its gate clears, while fold k+1 still
    # trains.  Stacked groups (if any) already trained above, in the
    # main thread; the overlapped per-fold body then only runs gates.
    overlap_mode = pipeline_on and work_queue is None and until >= 2
    if overlap_mode:
        logger.info(
            "async pipeline: overlapping phase-1 fold training with "
            "phase-2 search (each fold hands over at gate completion)")
    elif work_queue is None:
        for fold in range(cv_num):
            if fold not in fold_list:
                continue
            _phase1_fold(fold)
    else:
        work_queue.beat_host()

        def _run_p1(fold, unit):
            _phase1_fold(fold, heartbeat=_lease_heartbeat(unit))
            return {"baseline": fold_baselines.get(fold),
                    "excluded": fold in excluded_folds}

        _workqueue_phase({f: f"p1-fold{f}" for f in fold_list}, _run_p1)
        # folds finished by other hosts: adopt their gate verdicts from
        # the done markers (the ranking below must honor every
        # exclusion, wherever the gate ran)
        for fold in fold_list:
            info = work_queue.done_info(f"p1-fold{fold}") or {}
            if info.get("baseline") is not None and fold not in fold_baselines:
                fold_baselines[fold] = float(info["baseline"])
            if info.get("excluded") and fold not in excluded_folds:
                excluded_folds.append(fold)
    phase1_t0 = t0

    def _stamp_phase1(end_time: float | None = None):
        # device_secs_* is the honest name; tpu_secs_* stays as a
        # compatibility alias for committed-artifact readers (same value)
        end = wall() if end_time is None else end_time
        result["device_secs_phase1"] = result["tpu_secs_phase1"] = (
            (end - phase1_t0) * mesh.size)
        # per-fold attribution of the phase total, sourced from the ONE
        # stopwatch ledger every phase-1 training ran under (stacked
        # groups record ONE wall measurement and split it evenly — the
        # phase total is never double-counted); the gap between
        # sum(per_fold) and device_secs_phase1 is the gate's baseline
        # evals plus setup, which belong to no single fold
        attr = phase1_device_seconds_attribution(
            phase1_sw, fold_list, stack_groups)
        result["device_secs_phase1_per_fold"] = {
            str(f): attr[f] for f in sorted(attr)}
        result["fold_baselines"] = {
            str(k): v for k, v in fold_baselines.items()}
        result["excluded_folds"] = list(excluded_folds)

    if not overlap_mode:  # overlap re-stamps after the trainer finishes
        _stamp_phase1()
    if until < 2:
        result["final_policy_set"] = []
        result["compile_cache"] = compile_cache_stats()
        result["elapsed_total"] = wall() - watch["start"]
        if fleet_transport is not None:
            # no rounds will ever be published: let actor hosts drain
            fleet_transport.mark_search_done({"until": until})
        return result

    # ---------------- phase 2: TPE search per fold --------------------
    t0 = wall()
    space = make_search_space(num_policy, num_op)
    final_policy_set = []
    # async-pipeline accounting + the cross-thread stop channel: the
    # overlapped trainer pushes its failure here so the in-flight
    # learner stops at the next round boundary instead of finishing
    # the fold against a dying run
    pipeline_fold_stats: list[dict] = []
    pipeline_stop_cell: list[BaseException] = []
    pipeline_overlap_timeline: dict = {}

    def _pipeline_should_stop():
        return pipeline_stop_cell[0] if pipeline_stop_cell else None

    def _phase2_fold_async(fold, params, batch_stats, tpe, key_fold,
                           fold_trials, heartbeat=None) -> dict:
        """One fold's trial budget through the actor/learner pipeline
        (``search/pipeline.py``).  Persistence, quarantine and census
        bookkeeping mirror the serial schedulers; the trial log is
        appended in trial-id order so the artifact stream is
        schedule-invariant."""
        from fast_autoaugment_tpu.search.pipeline import (
            replay_trial_log,
            run_fold_pipeline,
        )

        replay_trial_log(
            tpe, fold_trials, trial_batch, num_search,
            max_inflight=pipeline_actors + pipeline_queue_depth)

        def _persist():
            trials_log[str(fold)] = fold_trials
            if work_queue is not None:
                _write_json_atomic(_fold_trials_path(fold), fold_trials)
            else:
                _write_json_atomic(trials_path, trials_log)

        def _record_quarantine(lo, hi, exc, worst):
            from fast_autoaugment_tpu.search.pipeline import _failure_text

            text = _failure_text(exc)
            logger.warning(
                "phase2 fold %d trial(s) %d-%d: TTA evaluation FAILED "
                "(%s) — QUARANTINED with worst-observed reward %.4f; "
                "the search continues", fold, lo, hi - 1, text, worst)
            for t in range(lo, hi):
                quarantined.append({
                    "fold": fold, "trial": t, "error": text})

        def _on_first_ok():
            if trial_batch > 1:
                if "tta_batched_executables_first" not in result:
                    result["tta_batched_executables_first"] = (
                        executable_census(evaluator.tta_step_batch))
            elif "tta_executables_first" not in result:
                result["tta_executables_first"] = executable_census(
                    evaluator.tta_step)

        backend = None
        on_first_ok = _on_first_ok
        if fleet_transport is not None:
            # rounds dispatch to ACTOR HOSTS: publish instead of
            # enqueue, poll done markers instead of a results queue.
            # key_seed reproduces this fold's key stream on any host
            # (key_fold IS PRNGKey(seed * 77 + fold) — see above)
            backend = fleet_transport.learner_backend(
                fold, key_seed=seed * 77 + fold, trial_batch=trial_batch,
                num_policy=num_policy, num_op=num_op)
            heartbeat = fleet_transport.beat
            # no local TTA dispatches on the learner: the executable
            # census belongs to the actor hosts
            on_first_ok = None

        if trace is not None:
            trace.begin_segment(f"p2-fold{fold}")
        try:
            stats = run_fold_pipeline(
                evaluator, fold, params, batch_stats, tpe, key_fold,
                fold_trials,
                num_search=num_search, trial_batch=trial_batch,
                actors=pipeline_actors, queue_depth=pipeline_queue_depth,
                num_policy=num_policy, num_op=num_op,
                persist=_persist, record_quarantine=_record_quarantine,
                on_first_ok=on_first_ok,
                should_stop=_pipeline_should_stop, heartbeat=heartbeat,
                backend=backend,
            )
        finally:
            if trace is not None:
                trace.end_segment()
        pipeline_fold_stats.append(dict(stats, fold=fold))
        return {"num_trials": len(fold_trials)}

    def _phase2_fold(fold: int, heartbeat=None) -> dict | None:
        """One fold's full TPE trial budget (sequential or batched
        scheduler).  `heartbeat` (work-queue mode) renews the fold's
        lease after every persisted trial/round."""
        if fold in excluded_folds:
            logger.info("phase2: fold %d excluded by the quality gate", fold)
            return None
        if _fold_searched(fold):
            logger.info("phase2: fold %d trials already complete", fold)
            trials_log[str(fold)] = _load_fold_trials(fold)
            return None
        params, batch_stats = evaluator.load_fold(fold_paths[fold])

        # small budgets keep some TPE engagement: the hyperopt default
        # n_startup=20 leaves a 60-trial run barely out of the random
        # phase (round-2 run; docs/SEARCH_QUALITY.md)
        tpe = TPE(space, seed=seed * 1000 + fold,
                  n_startup=min(20, max(5, num_search // 4)))
        key_fold = jax.random.PRNGKey(seed * 77 + fold)
        fold_trials = _load_fold_trials(fold)
        if pipeline_on:
            # async actor/learner scheduler: resume replay goes through
            # the proposal ledger (exact ask/tell interleaving) inside
            return _phase2_fold_async(fold, params, batch_stats, tpe,
                                      key_fold, fold_trials, heartbeat)
        for entry in fold_trials:  # resume previous trials (a third
            # element marks a quarantined trial's failure record)
            tpe.tell(entry[0], entry[1])

        def _persist_trials():
            trials_log[str(fold)] = fold_trials
            if work_queue is not None:
                # one writer per lease: the fold file, not the shared log
                _write_json_atomic(_fold_trials_path(fold), fold_trials)
            else:
                _write_json_atomic(trials_path, trials_log)
            if heartbeat is not None:
                heartbeat()

        def _quarantine(trial_lo: int, trial_hi: int, exc: BaseException,
                        fold=fold) -> float:
            """Record failed trial(s) and return the pessimistic reward
            told to the TPE — the worst observed value, mirroring the
            constant-liar placeholder (search/tpe.py::ask)."""
            worst = (min(r for _, r in tpe.observations)
                     if tpe.observations else 0.0)
            logger.warning(
                "phase2 fold %d trial(s) %d-%d: TTA evaluation FAILED "
                "(%s: %s) — QUARANTINED with worst-observed reward %.4f; "
                "the search continues", fold, trial_lo, trial_hi - 1,
                type(exc).__name__, exc, worst)
            for t in range(trial_lo, trial_hi):
                quarantined.append({
                    "fold": fold, "trial": t,
                    "error": f"{type(exc).__name__}: {exc}"})
            return worst

        fi = None

        def _injected_trial_error(trial_idx: int):
            nonlocal fi
            from fast_autoaugment_tpu.utils import faultinject

            fi = faultinject.active_plan()
            if fi is not None and fi.trial_error_at(trial_idx):
                raise RuntimeError(
                    f"injected trial_error at trial {trial_idx}")

        if trace is not None:  # serial dispatch-gap baseline
            trace.begin_segment(f"p2-fold{fold}")
        while trial_batch <= 1 and len(tpe.observations) < num_search:
            trial_idx = len(tpe.observations)
            proposal = tpe.suggest()
            policies = policy_decoder(proposal, num_policy, num_op)
            policy_t = jnp.asarray(policy_to_tensor(policies))
            failure = None
            try:
                _injected_trial_error(trial_idx)
                metrics = evaluator.evaluate(
                    fold, params, batch_stats, policy_t,
                    jax.random.fold_in(key_fold, trial_idx),
                )
                reward = metrics["top1_valid"]
            except (PreemptedError, DispatchHungError):
                # graceful shutdown is NOT a trial failure, and a hung
                # dispatch means the backend is wedged — quarantining it
                # would keep dispatching into the wedge; both take the
                # exit-77 restart path
                raise
            except (ArithmeticError, RuntimeError, ValueError, OSError) as e:
                reward = _quarantine(trial_idx, trial_idx + 1, e)
                failure = {"quarantined": True,
                           "error": f"{type(e).__name__}: {e}"}
            if failure is None and "tta_executables_first" not in result:
                # snapshot after the very first evaluation: the
                # zero-recompile assertion is final == first
                result["tta_executables_first"] = executable_census(
                    evaluator.tta_step)
            tpe.tell(proposal, reward)
            telemetry.emit("trial", f"fold{fold}", fold=fold,
                           trial=trial_idx, reward=float(reward),
                           quarantined=failure is not None)
            fold_trials.append(
                (proposal, reward) if failure is None
                else (proposal, reward, failure))
            # persist EVERY trial (fsync + atomic rename): a crash loses
            # at most the in-flight evaluation (VERDICT r3, weak 4); the
            # JSON is small and the write is trivially cheap next to a
            # compiled TTA evaluation.  Trial persistence is also the
            # lease-renewal boundary in work-queue mode.
            _persist_trials()
            if trial_idx % 10 == 0 or trial_idx == num_search - 1:
                logger.info(
                    "phase2 fold %d trial %d/%d: top1_valid=%.4f best=%.4f",
                    fold, trial_idx, num_search, reward, tpe.best[1],
                )

        # trial-parallel scheduler (trial_batch = K > 1): ask K
        # constant-liar proposals, evaluate all K in one vmapped TTA
        # program per batch, tell the K true rewards back together.
        # Persistence/resume is per ROUND: a crash loses at most the
        # in-flight K evaluations.
        while trial_batch > 1 and len(tpe.observations) < num_search:
            t_base = len(tpe.observations)
            k_eff = min(trial_batch, num_search - t_base)
            proposals = tpe.ask(k_eff)
            # pad the candidate axis to the compiled K on a short final
            # round (one executable per K — never recompile); padded
            # lanes repeat the last proposal, their results are dropped
            padded = proposals + [proposals[-1]] * (trial_batch - k_eff)
            policies_t = jnp.asarray(np.stack([
                np.asarray(policy_to_tensor(
                    policy_decoder(p, num_policy, num_op)), np.float32)
                for p in padded
            ]))
            # candidate i's trial key is EXACTLY the sequential trial
            # (t_base + i)'s key, so a K-batched evaluation is
            # numerically identical to K sequential ones
            keys = jnp.stack([
                jax.random.fold_in(key_fold, t_base + i)
                for i in range(trial_batch)
            ])
            round_failure = None
            try:
                for i in range(k_eff):
                    _injected_trial_error(t_base + i)
                metrics_list = evaluator.evaluate_batch(
                    fold, params, batch_stats, policies_t, keys)[:k_eff]
                rewards = [m["top1_valid"] for m in metrics_list]
            except (PreemptedError, DispatchHungError):
                raise  # shutdown / wedged backend: restart, not quarantine
            except (ArithmeticError, RuntimeError, ValueError, OSError) as e:
                # one vmapped program evaluates the whole round: a raise
                # cannot be attributed to a single candidate, so the
                # ROUND is quarantined (K x the sequential policy)
                worst = _quarantine(t_base, t_base + k_eff, e)
                rewards = [worst] * k_eff
                round_failure = {"quarantined": True,
                                 "error": f"{type(e).__name__}: {e}"}
            if round_failure is None and \
                    "tta_batched_executables_first" not in result:
                result["tta_batched_executables_first"] = executable_census(
                    evaluator.tta_step_batch)
            tpe.tell_batch(proposals, rewards)
            for i, r in enumerate(rewards):
                telemetry.emit("trial", f"fold{fold}", fold=fold,
                               trial=t_base + i, reward=float(r),
                               quarantined=round_failure is not None)
            fold_trials.extend(
                (p, r) if round_failure is None else (p, r, round_failure)
                for p, r in zip(proposals, rewards))
            _persist_trials()
            logger.info(
                "phase2 fold %d trials %d-%d/%d (batch of %d): "
                "best_in_batch=%.4f best=%.4f",
                fold, t_base, t_base + k_eff - 1, num_search, k_eff,
                max(rewards), tpe.best[1],
            )
        if trace is not None:
            trace.end_segment()
        return {"num_trials": len(fold_trials)}

    if overlap_mode:
        from fast_autoaugment_tpu.search.pipeline import (
            run_overlapped_phases,
        )

        def _p1_overlap(f):
            try:
                _phase1_fold(
                    f, heartbeat=(fleet_transport.beat
                                  if fleet_transport is not None else None))
            except BaseException as e:
                # the in-flight learner must stop at its next round
                # boundary, not finish the fold against a dying run
                pipeline_stop_cell.append(e)
                raise
            if fleet_transport is not None and f not in excluded_folds \
                    and os.path.exists(fold_paths[f]):
                # stream the gate-cleared checkpoint to the fleet the
                # moment the gate clears — fold f's rounds dispatch to
                # actor hosts while fold f+1 still trains HERE
                fleet_transport.publish_checkpoint(f, fold_paths[f])

        timeline = run_overlapped_phases(fold_list, _p1_overlap,
                                         _phase2_fold)
        pipeline_overlap_timeline.update(timeline)
        p1_ends = [v["end"] for v in timeline["phase1"].values()]
        _stamp_phase1(max(p1_ends) if p1_ends else None)
    elif work_queue is None:
        for fold in fold_list:
            _phase2_fold(fold)
    else:
        def _run_p2(fold, unit):
            return _phase2_fold(fold, heartbeat=_lease_heartbeat(unit)) or {}

        _workqueue_phase(
            {f: f"p2-fold{f}" for f in fold_list if f not in excluded_folds},
            _run_p2)
        # every fold's trials (finished here or by other hosts) merge
        # into the in-memory log so the ranking below sees all of them
        for fold in fold_list:
            ft = _load_fold_trials(fold)
            if ft:
                trials_log[str(fold)] = ft

    # top-N per fold from the trial log (covers folds run here, folds
    # merged from other hosts, and folds resumed from disk alike,
    # search.py:253-259); only in-range folds with COMPLETE searches count
    for fold_key in sorted(trials_log, key=int):
        fold_trials = trials_log[fold_key]
        if not 0 <= int(fold_key) < cv_num:
            logger.warning("ignoring stale fold %s in trial log", fold_key)
            continue
        if int(fold_key) in excluded_folds:
            logger.warning("fold %s excluded by the quality gate — its "
                           "trials do not rank", fold_key)
            continue
        if len(fold_trials) < num_search:
            logger.warning(
                "fold %s has %d/%d trials — incomplete, excluded from the "
                "final policy set", fold_key, len(fold_trials), num_search,
            )
            continue
        # quarantined trials (3rd element = failure record) carry the
        # worst-observed placeholder reward; they never rank — a failed
        # evaluation must not nominate policies even in a tiny run
        scored = [t for t in fold_trials
                  if len(t) < 3 or not (t[2] or {}).get("quarantined")]
        ranked = sorted(scored, key=lambda o: -o[1])[:num_top]
        for entry in ranked:
            final_policy_set.extend(
                policy_decoder(entry[0], num_policy, num_op))

    final_policy_set = remove_duplicates(final_policy_set)
    result["num_sub_policies_selected"] = len(final_policy_set)
    # canonical quarantine stamp from the PERSISTED trial log: covers
    # trials failed in this process and ones resumed from disk alike
    quarantined = [
        {"fold": int(fk), "trial": i,
         "error": (t[2] or {}).get("error", "unknown")}
        for fk, trs in sorted(trials_log.items())
        if fk.lstrip("-").isdigit()
        for i, t in enumerate(trs)
        if len(t) >= 3 and (t[2] or {}).get("quarantined")
    ]
    result["quarantined_trials"] = quarantined
    result["num_quarantined_trials"] = len(quarantined)
    if quarantined:
        logger.warning(
            "phase2: %d trial(s) quarantined after failed TTA "
            "evaluations — see search_result.json['quarantined_trials']",
            len(quarantined))
    result["device_secs_phase2"] = result["tpu_secs_phase2"] = (
        (wall() - t0) * mesh.size)
    # async-pipeline accounting (+ the dispatch-gap evidence whenever
    # the trace is armed — FAA_PIPELINE_TRACE=1 captures the serial
    # baseline the pipeline bench compares against).  In overlap mode
    # device_secs_phase2 spans the whole overlapped region; the
    # timeline below carries the per-fold interleaving.
    if pipeline_on or trace is not None:
        gaps = trace.summary() if trace is not None else None
        result["pipeline"] = {
            "mode": "on" if pipeline_on else "off",
            "actors": pipeline_actors if pipeline_on else None,
            "queue_depth": pipeline_queue_depth if pipeline_on else None,
            "max_inflight": (pipeline_actors + pipeline_queue_depth
                             if pipeline_on else None),
            "tell_reorders": sum(
                s["tell_reorders"] for s in pipeline_fold_stats),
            "rounds": sum(s["rounds"] for s in pipeline_fold_stats),
            "per_fold": pipeline_fold_stats,
            "device_busy_frac": (gaps or {}).get("device_busy_frac"),
            "dispatch_gaps": gaps,
            "overlap": pipeline_overlap_timeline or None,
        }
    # compile-cache census: the whole point of policy-as-tensor TTA is
    # that EVERY trial reuses one executable (SURVEY.md hard-part 3) —
    # record it so the search-cost artifact can assert zero recompiles
    # across all num_search x folds evaluations.
    # a fully-resumed run never builds the TTA machinery — there were
    # no evaluations in this process, so there is nothing to census
    result["tta_executables"] = (
        executable_census(evaluator.tta_step) if evaluator._built else None)
    # the expected ABSOLUTE count is one executable per distinct
    # policy-tensor shape actually evaluated: [num_policy, num_op, 3]
    # for every trial, plus [1, num_op, 3] once when the quality gate
    # measured identity baselines — 2 with the gate on, 1 without
    # (VERDICT r4 weak 6: growth-only checking would not catch
    # compiling 2x per shape up front)
    result["tta_executables_expected"] = len(evaluator.policy_shapes)
    census_failures = []
    if (result["tta_executables"] is not None
            and result["tta_executables"] > result["tta_executables_expected"]):
        census_failures.append(
            f"{result['tta_executables']} TTA executables for "
            f"{result['tta_executables_expected']} distinct policy shapes "
            f"{sorted(evaluator.policy_shapes)}")
    if trial_batch > 1:
        # the batched step has its own jit cache: one fixed candidate-
        # axis size K -> exactly one executable for every trial round
        result["tta_batched_executables"] = (
            executable_census(evaluator.tta_step_batch)
            if evaluator._built else None)
        result["tta_batched_executables_expected"] = len(
            evaluator.batch_policy_shapes)
        if (result["tta_batched_executables"] is not None
                and result["tta_batched_executables"]
                > result["tta_batched_executables_expected"]):
            census_failures.append(
                f"{result['tta_batched_executables']} batched-TTA "
                f"executables for {result['tta_batched_executables_expected']}"
                f" candidate-axis shapes "
                f"{sorted(evaluator.batch_policy_shapes)}")
    if census_failures:
        msg = ("phase2: " + "; ".join(census_failures)
               + " — recompilation is leaking into the trial loop "
                 "(policy-as-tensor contract broken)")
        # persist the partial result WITH a failure marker before
        # raising: the trial compute is already spent, and without this
        # write the run would leave no search_result.json to diagnose
        # or resume from (ADVICE r5, driver.py:682)
        result["failure"] = {"stage": "tta_executable_census", "error": msg}
        result["resilience"]["watchdog"] = wd.stats()
        result["compile_cache"] = compile_cache_stats()
        result["final_policy_set_pre_audit_size"] = len(final_policy_set)
        result["elapsed_total"] = wall() - watch["start"]
        _write_json_atomic(
            os.path.join(save_dir, "search_result.json"),
            {k: v for k, v in result.items()
             if k not in ("final_policy_set", "random_policy_set")})
        raise RuntimeError(msg)

    # one audit pipeline for both arms: cached-score reuse (the cache
    # validates its own fold set + baselines inside audit_sub_policies),
    # identical candidate folds/floors, per-arm timing + record file —
    # the searched-vs-random comparison stays fair by construction
    def _audited(policy_set, cache_name: str, secs_key: str):
        t0 = wall()
        apath = os.path.join(save_dir, cache_name)
        cached = None
        if resume and os.path.exists(apath):
            cached = fsfault.read_json(apath)
        kept, audit = audit_sub_policies(
            evaluator, policy_set, fold_paths,
            fold_baselines=fold_baselines,
            candidate_folds=[f for f in range(cv_num)
                             if f not in excluded_folds],
            audit_floor=audit_floor,
            quality_floor=fold_quality_floor,
            cached_audit=cached,
        )
        result[f"device_secs_{secs_key}"] = (wall() - t0) * mesh.size
        result[f"tpu_secs_{secs_key}"] = result[f"device_secs_{secs_key}"]
        _write_json_atomic(apath, audit)
        return kept, audit

    # ---------------- phase 2.5: per-sub-policy audit -----------------
    if audit_floor is not None and final_policy_set:
        final_policy_set, audit = _audited(
            final_policy_set, "audit.json", "audit")
        result["num_sub_policies_dropped"] = len(audit["dropped"])

    # ---------------- random control arm ------------------------------
    # An equal-size uniform draw from the same search space, pushed
    # through the SAME audit: phase 3 can then compare searched vs
    # random vs default instead of searched vs default only.
    if random_control:
        rand_path = os.path.join(save_dir, "random_policy.json")
        n_rand = max(int(result.get("num_sub_policies_selected", 0)), 1)
        if resume and os.path.exists(rand_path):
            # JSON turns the decoder's (op, prob, level) tuples into
            # lists — normalize back so resumed and fresh runs are
            # indistinguishable to callers
            random_set = [[tuple(op) for op in sub]
                          for sub in fsfault.load_json(rand_path)]
            logger.info("random control: resumed %d drawn sub-policies",
                        len(random_set))
        else:
            random_set = draw_random_policy_set(
                n_rand, num_policy, num_op, seed=seed * 31 + 7)
            _write_json_atomic(rand_path, random_set)
            logger.info("random control: drew %d sub-policies (matching the "
                        "searched arm's pre-audit size)", len(random_set))
        result["num_sub_policies_random_drawn"] = len(random_set)
        if audit_floor is not None and random_set:
            random_set, audit_r = _audited(
                random_set, "audit_random.json", "audit_random")
            result["num_sub_policies_random_dropped"] = len(audit_r["dropped"])
        result["random_policy_set"] = random_set
        result["num_sub_policies_random"] = len(random_set)
        _write_json_atomic(os.path.join(save_dir, "random_final_policy.json"),
                           random_set)

    # self-healing accounting, refreshed AFTER all device work so the
    # stamps cover the whole run: watchdog fire counts + (work-queue
    # mode) the degraded-completion evidence any surviving host can
    # reconstruct from the shared queue state
    result["resilience"]["watchdog"] = wd.stats()
    result["watchdog_fires"] = wd.fires
    # compile-tax evidence covering the whole run: a resumed/retried
    # process proves here (hits > 0, first_step_secs in the seconds)
    # that it warm-started instead of re-paying the 23-55 s compile
    result["compile_cache"] = compile_cache_stats()
    if work_queue is not None:
        work_queue.beat_host()  # the census must not see a stale self
        acct = work_queue.accounting()
        result["resilience"]["fleet"] = acct
        result["degraded"] = acct["degraded"]
        result["lost_hosts"] = acct["lost_hosts"]
        result["reclaimed_units"] = [r["unit"]
                                     for r in acct["reclaimed_units"]]
        if acct["degraded"]:
            logger.warning(
                "search completed DEGRADED: lost_hosts=%s, %d unit(s) "
                "reclaimed and finished by survivors",
                acct["lost_hosts"], acct["num_reclaimed_units"])

    if fleet_transport is not None:
        # fleet-search accounting, mirrored from the work-queue stamp:
        # any round finished at lease attempt > 1 was reclaimed from a
        # dead/preempted actor host; stale non-done host beats are the
        # lost hosts.  The trial log itself is already byte-identical
        # to the single-host run — this stamp is the evidence of HOW it
        # got there.
        fleet_transport.beat()
        acct = fleet_transport.accounting()
        result["resilience"]["fleet"] = acct
        result["degraded"] = acct["degraded"]
        result["lost_hosts"] = acct["lost_hosts"]
        result["reclaimed_units"] = [r["unit"]
                                     for r in acct["reclaimed_units"]]
        result["fleet_transport"] = {
            "root": fleet_transport.root,
            "owner": fleet_transport.owner,
            "window": pipeline_actors + pipeline_queue_depth,
        }
        if acct["degraded"]:
            logger.warning(
                "fleet search completed DEGRADED: lost_hosts=%s, %d "
                "round unit(s) reclaimed and finished by surviving "
                "actors", acct["lost_hosts"], acct["num_reclaimed_units"])

    result["final_policy_set"] = final_policy_set
    result["num_sub_policies"] = len(final_policy_set)

    _write_json_atomic(os.path.join(save_dir, "final_policy.json"),
                       final_policy_set)
    if fleet_transport is not None:
        # terminal marker AFTER the final artifacts land: actor hosts
        # drain their claim poll and exit 0
        fleet_transport.mark_search_done(
            {"num_sub_policies": len(final_policy_set)})
    logger.info(
        "search done: %d sub-policies; phase1 %.1f device-s, phase2 %.1f "
        "device-s on %s (%s x%d)",
        len(final_policy_set), result["device_secs_phase1"],
        result["device_secs_phase2"], result["platform"],
        result["device_kind"], result["device_count"],
    )
    result["elapsed_total"] = wall() - watch["start"]
    return result


def search_actor(
    conf,
    dataroot: str,
    save_dir: str,
    fleet_transport,
    *,
    cv_num: int = 5,
    cv_ratio: float = 0.4,
    num_policy: int = 5,
    num_op: int = 2,
    trial_batch: int = 1,
    seed: int = 0,
    aug_dispatch: str = "exact",
    aug_groups: int = 8,
    watchdog="off",
    telemetry_spec: str = "off",
    poll_sec: float = 0.5,
    ckpt_timeout: float = 900.0,
) -> dict:
    """ACTOR-host entry point for the multi-host fleet search: no
    training, no TPE — just the shared ``_FoldEval`` TTA machinery in
    a claim/evaluate/post loop against the learner's published rounds
    (``search_cli --search-role actor``; docs/RESILIENCE.md "Fleet
    search").

    The geometry flags (`trial_batch`, `num_policy`, `num_op`,
    `aug_dispatch`, ...) must match the learner's — they shape the
    compiled TTA step, and a payload mismatch raises loudly instead of
    quarantining every round.  `save_dir` is the SHARED artifact
    directory the learner writes fold checkpoints into; the transport's
    published digests gate loading.  Returns the actor's accounting
    (rounds evaluated/failed, leases lost, units reclaimed from dead
    peers) once the learner marks the search done."""
    from fast_autoaugment_tpu.search.pipeline import run_fleet_actor

    configure_compile_cache()
    telemetry.configure_telemetry(telemetry_spec)
    mesh = make_mesh()
    wd = resolve_watchdog(watchdog)
    evaluator = _FoldEval(
        conf, dataroot, mesh,
        num_policy=num_policy, num_op=num_op, cv_ratio=cv_ratio,
        seed=seed, trial_batch=max(1, int(trial_batch)),
        aug_dispatch=aug_dispatch, aug_groups=aug_groups, watchdog=wd,
    )

    def _fold_path(fold: int) -> str:
        if not 0 <= int(fold) < cv_num:
            raise ValueError(
                f"published round names fold {fold} outside this actor's "
                f"cv_num={cv_num} — launch actors with the learner's flags")
        return _fold_ckpt_path(save_dir, conf, int(fold), cv_ratio)

    logger.info("fleet actor %s: serving rounds from %s (save_dir %s)",
                fleet_transport.owner, fleet_transport.root, save_dir)
    stats = run_fleet_actor(
        evaluator, fleet_transport, _fold_path,
        trial_batch=max(1, int(trial_batch)), num_policy=num_policy,
        num_op=num_op, poll_sec=poll_sec, ckpt_timeout=ckpt_timeout,
    )
    stats["watchdog"] = wd.stats()
    stats["compile_cache"] = compile_cache_stats()
    logger.info(
        "fleet actor %s: done — %d round(s) evaluated, %d failed, "
        "%d lease(s) lost, %d reclaimed", fleet_transport.owner,
        stats["rounds_ok"], stats["rounds_err"], stats["lease_lost"],
        len(stats["reclaimed_units"]))
    return stats


def audit_sub_policies(
    evaluator: _FoldEval,
    policy_set: list,
    fold_paths: list[str],
    *,
    fold_baselines: dict[int, float],
    candidate_folds: list[int],
    audit_floor: float,
    quality_floor: float | None = None,
    num_draws_key: int = 23,
    cached_audit: dict | None = None,
    audit_chunk: int | None = None,
) -> tuple[list, dict]:
    """Drop sub-policies that standalone-degrade fold accuracy.

    Each surviving sub-policy is scored ``mean_f[acc_f(sp)/base_f]``
    over the audit folds, where ``acc_f(sp)`` uses the MEAN-over-draws
    reduction (training applies one sub-policy per image — there is no
    best-of-5 rescue at train time) and ``base_f`` is the fold's
    identity-policy baseline.  Scores below `audit_floor` drop the
    sub-policy.  The reference has no such step: its top-10 selection
    inherits every trial's 5 sub-policies wholesale
    (``search.py:255-259``), which is how round 2's destructive
    policies survived.

    Folds qualify for auditing when their checkpoint exists and their
    baseline clears max(quality_floor, 2x chance).  Returns the kept
    set and an audit record for ``audit.json``.
    """
    evaluator._build()
    chance = 2.0 / evaluator.num_classes
    floor = max(quality_floor or 0.0, chance)
    audit_folds = []
    for fold in candidate_folds:
        path = fold_paths[fold]
        if not os.path.exists(path):
            continue
        if fold not in fold_baselines:
            fold_baselines[fold] = evaluator.baseline(fold, path)
        if fold_baselines[fold] >= floor:
            audit_folds.append(fold)
    record: dict = {
        "audit_floor": audit_floor,
        "audit_folds": audit_folds,
        "fold_baselines": {str(k): v for k, v in fold_baselines.items()},
        "scores": [],
        "dropped": [],
    }
    if not audit_folds:
        logger.warning("audit: no fold passes the baseline floor %.3f — "
                       "audit SKIPPED, policy set unchanged", floor)
        return policy_set, record

    # cached-score validity: the old run must have audited the SAME fold
    # set with the SAME baselines — scores are means over audit folds,
    # so a changed fold set silently changes every score's meaning
    cached_scores: dict = {}
    if cached_audit:
        try:
            same_folds = list(cached_audit.get("audit_folds", [])) == audit_folds
            same_base = same_folds and all(
                abs(cached_audit["fold_baselines"].get(str(f), -1.0)
                    - fold_baselines[f]) < 1e-6
                for f in audit_folds
            )
            if same_base and cached_audit.get("scores"):
                cached_scores = {
                    json.dumps(s["sub_policy"]): s["score"]
                    for s in cached_audit["scores"]
                }
                logger.info("audit: reusing %d cached scores", len(cached_scores))
            else:
                logger.info("audit: cached scores stale (fold set or "
                            "baselines changed) — recomputing")
        except (KeyError, TypeError, ValueError):
            cached_scores = {}

    # evaluate the non-cached sub-policies in CHUNKS of `audit_chunk`
    # per compiled call (make_audit_step): the sub-policy axis is a
    # vmap, so one dispatch covers chunk x draws x batch images — the
    # MXU-shaped layout — instead of one tiny launch per (sub-policy,
    # batch).  The last chunk pads to the fixed size (no recompiles).
    idx_to_eval = [i for i, sub in enumerate(policy_set)
                   if json.dumps(sub) not in cached_scores]
    computed: dict[int, float] = {}
    if idx_to_eval and len(idx_to_eval) <= 4:
        # tiny audits (tests, smoke runs): the already-compiled TTA step
        # beats paying a fresh audit-step compile
        loaded = {f: evaluator.load_fold(fold_paths[f]) for f in audit_folds}
        for i in idx_to_eval:
            sp_t = jnp.asarray(policy_to_tensor([list(map(tuple, policy_set[i]))]))
            ratios = [
                evaluator.evaluate(
                    fold, *loaded[fold], sp_t,
                    jax.random.PRNGKey(num_draws_key * 1000 + i),
                )["top1_mean"] / max(fold_baselines[fold], 1e-6)
                for fold in audit_folds
            ]
            computed[i] = float(np.mean(ratios))
    elif idx_to_eval:
        loaded = {f: evaluator.load_fold(fold_paths[f]) for f in audit_folds}
        if audit_chunk is None:
            # peak memory scales with chunk x image^2: 8 at CIFAR
            # resolution, 1 at ImageNet's 224px (same footprint as the
            # TTA step either way)
            audit_chunk = max(1, (8 * 32 * 32) // (evaluator.image ** 2))
        chunk = max(1, int(audit_chunk))
        n = len(idx_to_eval)
        subs_np = np.stack([
            np.asarray(policy_to_tensor([list(map(tuple, policy_set[i]))]),
                       np.float32)[0]
            for i in idx_to_eval
        ])  # [n, num_op, 3]
        ratio_sums = np.zeros(n)
        for fold in audit_folds:
            params, batch_stats = loaded[fold]
            sums = np.zeros(n)
            cnt = 0.0
            for start in range(0, n, chunk):
                block = subs_np[start:start + chunk]
                real = len(block)
                if real < chunk:
                    block = np.concatenate(
                        [block,
                         np.zeros((chunk - real,) + block.shape[1:], np.float32)])
                bsum = np.zeros(chunk)
                bcnt = 0.0
                block_dev = jnp.asarray(block)  # one upload per chunk
                for bi, batch in enumerate(evaluator.batches_fn(fold)()):
                    # chained fold_in: collision-free for any batch
                    # count (a single mixed integer collides once a
                    # fold yields >131 batches, e.g. ImageNet folds)
                    k = jax.random.PRNGKey(num_draws_key)
                    for part in (fold, start, bi):
                        k = jax.random.fold_in(k, part)
                    out = evaluator.audit_eval(
                        params, batch_stats, batch, block_dev, k,
                    )
                    bsum += np.asarray(out["correct_mean_sum"])
                    bcnt += float(out["cnt"])
                sums[start:start + real] = bsum[:real]
                cnt = bcnt
            ratio_sums += (sums / max(cnt, 1e-6)) / max(fold_baselines[fold], 1e-6)
        for j, i in enumerate(idx_to_eval):
            computed[i] = float(ratio_sums[j] / len(audit_folds))

    kept = []
    for i, sub in enumerate(policy_set):
        cache_key = json.dumps(sub)
        score = (float(cached_scores[cache_key])
                 if cache_key in cached_scores else computed[i])
        record["scores"].append({"sub_policy": sub, "score": score})
        if score >= audit_floor:
            kept.append(sub)
        else:
            record["dropped"].append({"sub_policy": sub, "score": score})
    logger.info(
        "audit: %d/%d sub-policies kept (floor %.2f x baseline over folds %s)",
        len(kept), len(policy_set), audit_floor, audit_folds,
    )
    return kept, record
