"""Density-matching TTA evaluation — the search's inner loop.

The reference's ``eval_tta`` (``search.py:70-134``) loads a fold
checkpoint, builds ``num_policy`` independently-augmented copies of the
held-out fold loader (all applying the SAME candidate policy set, each
with fresh randomness), and per batch records:

- ``minus_loss``: minus the MINIMUM loss over all (policy-draw, sample)
  pairs of the batch — a batch-global scalar, not per-sample
  (SURVEY.md errata 2), and
- ``correct``: per-sample max of top-1 correctness across the draws,

normalized by sample count at the end.

Here that whole inner loop is ONE jitted step: the candidate policy is
a TENSOR argument, the P augmentation draws are a vmap, and the P*B
forward runs as a single batch on the mesh.  Because nothing about the
policy is baked into the compilation, every TPE sample reuses the same
executable — the property that makes search cheap on TPU (SURVEY.md
hard-part 3; the reference pays a fresh loader build per trial
instead, ``search.py:87-91``).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.core.compilecache import seam_jit
from fast_autoaugment_tpu.core.metrics import Accumulator
from fast_autoaugment_tpu.core.watchdog import dispatch_enqueue_guard
from fast_autoaugment_tpu.ops.preprocess import cifar_train_batch

__all__ = ["make_tta_step", "make_audit_step", "eval_tta", "eval_tta_batched"]


def _default_augment_fn(cutout_length: int, aug_dispatch: str = "exact",
                        aug_groups: int = 8) -> Callable:
    """CIFAR-family train stack (crop/flip/normalize + policy + cutout)."""
    def augment_fn(images, policy, key):
        return cifar_train_batch(images, key, policy=policy,
                                 cutout_length=cutout_length,
                                 aug_dispatch=aug_dispatch,
                                 aug_groups=aug_groups)
    return augment_fn


def make_tta_step(model, *, num_policy: int = 5, cutout_length: int = 16,
                  augment_fn: Callable | None = None,
                  num_candidates: int | None = None,
                  aug_dispatch: str = "exact", aug_groups: int = 8):
    """Build the jitted TTA evaluation step.

    With ``num_candidates=None`` (default) returns
    ``fn(params, batch_stats, images_u8, labels, mask, policy, key) ->
    {"minus_loss_sum", "correct_sum", "cnt"}`` where `policy` is a
    [num_sub, num_op, 3] tensor applied `num_policy` times with
    independent randomness.

    With ``num_candidates=K`` the step gains a LEADING CANDIDATE AXIS:
    `policy` becomes a [K, num_sub, num_op, 3] tensor of K independent
    TPE proposals and `key` a [K]-stack of per-candidate PRNG keys; the
    candidate axis is a vmap over the exact single-candidate
    computation, so the K*P*B forwards run as ONE device program and
    every returned field carries a leading [K] (including the
    batch-global min-loss errata, which stays global PER CANDIDATE).
    Candidate k's results are bit-identical to evaluating its
    (policy[k], key[k]) through the single-candidate step — the Podracer
    fan-out (arXiv:2104.06272): homogeneous trials feed the device as
    one batch.  For either variant, one fixed argument shape = one
    executable for the whole search (the zero-recompile invariant;
    census via ``search.census.executable_census``).

    ``aug_dispatch="grouped"`` switches the augmentation to the
    scalar-dispatch kernels (``ops/augment.py``): the P draw axis (and
    for ``num_candidates=K`` the candidate axis) is traversed with
    ``lax.map`` instead of ``vmap`` so the per-chunk sub-policy indices
    stay SCALAR — a vmapped axis would re-batch them and XLA would fall
    back to executing every op branch.  The model forward still runs
    on the full flattened batch either way.  A custom `augment_fn`
    combined with grouped dispatch owns its own internal dispatch; this
    function only serializes the outer axes for it.
    """
    from fast_autoaugment_tpu.ops.augment import check_aug_dispatch

    check_aug_dispatch(aug_dispatch)
    grouped = aug_dispatch == "grouped"
    if augment_fn is None:
        augment_fn = _default_augment_fn(cutout_length, aug_dispatch,
                                         aug_groups)

    def augment_draws(images, policy, key):
        keys = jax.random.split(key, num_policy)

        def one_draw(k):
            return augment_fn(images, policy, k)

        if grouped:
            # scan over draws: each draw's grouped dispatch keeps its
            # scalar switch index (a draw vmap would batch it)
            return jax.lax.map(one_draw, keys)  # [P, B, H, W, C]
        return jax.vmap(one_draw)(keys)  # [P, B, H, W, C]

    def score_augmented(params, batch_stats, augmented, labels, mask):
        p, b = augmented.shape[0], augmented.shape[1]
        flat = augmented.reshape((p * b,) + augmented.shape[2:])
        logits = model.apply(
            {"params": params, "batch_stats": batch_stats}, flat, train=False
        )
        logits = logits.reshape(p, b, -1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[None, :, None], axis=-1)[..., 0]  # [P, B]
        correct = (jnp.argmax(logits, axis=-1) == labels[None, :])  # [P, B]

        # batch-global min loss over every (draw, sample) pair, masked
        nll_masked = jnp.where(mask[None, :] > 0, nll, jnp.inf)
        minus_loss = -jnp.min(nll_masked)
        # per-sample best across draws (the reference's reward,
        # search.py:116-125) — NOTE this is an optimistic reduction: a
        # destructive sub-policy hides behind one benign draw
        correct_max = correct.any(axis=0) * (mask > 0)
        # per-sample MEAN across draws: the pessimistic counterpart the
        # sub-policy audit ranks by (what training-time application of
        # the policy actually costs; round-2 post-mortem,
        # docs/search_postmortem_r2.md)
        correct_mean = correct.mean(axis=0) * (mask > 0)
        return {
            "minus_loss_sum": minus_loss,
            "correct_sum": correct_max.sum().astype(jnp.float32),
            "correct_mean_sum": correct_mean.sum().astype(jnp.float32),
            "cnt": mask.sum().astype(jnp.float32),
        }

    def one_candidate(params, batch_stats, images, labels, mask, policy, key):
        augmented = augment_draws(images, policy, key)
        return score_augmented(params, batch_stats, augmented, labels, mask)

    if num_candidates is None:
        return seam_jit(one_candidate, label="tta")

    def tta_step_batched(params, batch_stats, images, labels, mask,
                         policies, keys):
        if grouped:
            # candidate axis: augment under lax.map (scalar dispatch
            # preserved), then vmap only the forward/metrics over the
            # pre-augmented [K, P, B, ...] tensor
            augmented = jax.lax.map(
                lambda pk: augment_draws(images, pk[0], pk[1]),
                (policies, keys))
            return jax.vmap(
                lambda aug: score_augmented(params, batch_stats, aug,
                                            labels, mask)
            )(augmented)
        return jax.vmap(
            lambda pol, k: one_candidate(
                params, batch_stats, images, labels, mask, pol, k)
        )(policies, keys)

    return seam_jit(tta_step_batched, label="tta_batched")


def make_audit_step(model, *, num_policy: int = 5, cutout_length: int = 16,
                    augment_fn: Callable | None = None,
                    aug_dispatch: str = "exact", aug_groups: int = 8):
    """Batched sub-policy audit step: evaluates S candidate sub-policies
    against one batch in ONE compiled call.

    The per-sub-policy audit (``search/driver.py:audit_sub_policies``)
    needs mean-over-draws accuracy for EVERY selected sub-policy alone;
    done with :func:`make_tta_step` that is one tiny dispatch per
    (sub-policy, batch) — thousands of launches that starve the MXU.
    Here the sub-policy axis is a vmap: ``subs`` is [S, num_op, 3] and
    the model forward runs on the S*P*B flattened batch.  Returns
    ``fn(params, batch_stats, images, labels, mask, subs, key) ->
    {"correct_mean_sum": [S], "cnt": scalar}``.  NOTE peak memory is S x
    the TTA step's (the [S, P, B, H, W, C] augmented tensor) — callers
    size S by image resolution (``audit_sub_policies``).

    ``aug_dispatch="grouped"``: the S axis already fixes the sub-policy
    per lane, so scalar dispatch needs NO distribution change — each
    lane's ops are known per lane, and the grouped single-sub path is
    bitwise identical to the exact one.  The S and draw axes are
    traversed with ``lax.map`` (a vmap would re-batch the op indices
    and lower back to all-branches execution); the forward stays one
    flattened S*P*B batch.
    """
    from fast_autoaugment_tpu.ops.augment import check_aug_dispatch

    check_aug_dispatch(aug_dispatch)
    grouped = aug_dispatch == "grouped"
    if augment_fn is None:
        augment_fn = _default_augment_fn(cutout_length, aug_dispatch,
                                         aug_groups)

    def audit_step(params, batch_stats, images, labels, mask, subs, key):
        s = subs.shape[0]
        keys = jax.random.split(key, s * num_policy).reshape(s, num_policy, 2)

        def per_sub(sub, ks):
            # a [1, num_op, 3] policy: every draw applies this sub-policy
            return jax.vmap(lambda k: augment_fn(images, sub[None], k))(ks)

        if grouped:
            augmented = jax.lax.map(
                lambda sk: jax.lax.map(
                    lambda k: augment_fn(images, sk[0][None], k), sk[1]),
                (subs, keys))  # [S, P, B, H, W, C]
        else:
            augmented = jax.vmap(per_sub)(subs, keys)  # [S, P, B, H, W, C]
        p, b = augmented.shape[1], augmented.shape[2]
        flat = augmented.reshape((s * p * b,) + augmented.shape[3:])
        logits = model.apply(
            {"params": params, "batch_stats": batch_stats}, flat, train=False
        ).reshape(s, p, b, -1)
        correct = (jnp.argmax(logits, axis=-1) == labels[None, None, :])
        correct_mean = correct.mean(axis=1) * (mask[None, :] > 0)  # [S, B]
        return {
            "correct_mean_sum": correct_mean.sum(axis=1).astype(jnp.float32),
            "cnt": mask.sum().astype(jnp.float32),
        }

    return seam_jit(audit_step, label="audit")


def eval_tta(tta_step, params, batch_stats, batches, policy, key,
             trace=None) -> dict:
    """Run the TTA step over a fold's batches; returns
    {'minus_loss', 'top1_valid'} normalized by sample count
    (reference ``search.py:117-133``).

    `batches` yields mesh-placed ``{"x", "y", "m"}`` dicts
    (`parallel.mesh.shard_transform` maps `eval_batches` tuples to
    this shape) — the driver uploads each fold ONCE and replays the
    device-resident batches across all trials (the fold data is
    identical for every TPE sample; only the policy tensor changes),
    or streams them through a prefetch worker for lazy datasets.

    `trace(t0, t1)` (optional) receives each dispatch's start/end
    monotonic timestamps — the per-dispatch evidence behind the
    pipeline bench's gap histogram.  Tracing forces a per-batch
    ``block_until_ready`` (the tiny output scalars are pulled to the
    host right after anyway), so it never changes values.  Every
    dispatch window also feeds the telemetry span seam
    (``core/telemetry.py::record_dispatch``, label ``tta``) — registry
    histogram always, journal event when ``--telemetry`` is armed."""
    acc = Accumulator()
    for i, batch in enumerate(batches):
        t0 = telemetry.mono()
        with dispatch_enqueue_guard():  # async pipeline: one enqueue
            out = tta_step(             # order on every device queue
                params, batch_stats, batch["x"], batch["y"], batch["m"],
                policy, jax.random.fold_in(key, i),
            )
        if trace is not None:
            out = jax.block_until_ready(out)
            t1 = telemetry.mono()
            trace(t0, t1)
        else:
            t1 = telemetry.mono()
        telemetry.record_dispatch("tta", t0, t1,
                                  blocking=trace is not None)
        acc.add_dict(out)
    cnt = acc["cnt"]
    return {
        "minus_loss": acc["minus_loss_sum"] / cnt if cnt else 0.0,
        "top1_valid": acc["correct_sum"] / cnt if cnt else 0.0,
        "top1_mean": acc["correct_mean_sum"] / cnt if cnt else 0.0,
        "cnt": cnt,
    }


def eval_tta_batched(tta_step_k, params, batch_stats, batches, policies,
                     keys, trace=None) -> list[dict]:
    """Batched counterpart of :func:`eval_tta`: K candidate policies
    through a ``make_tta_step(num_candidates=K)`` step in one device
    program per batch.

    `policies` is [K, num_sub, num_op, 3]; `keys` is a [K]-stack of
    per-candidate TRIAL keys.  Candidate k's per-batch key is
    ``fold_in(keys[k], batch_idx)`` — exactly what a sequential
    :func:`eval_tta` call with ``key=keys[k]`` derives — so each entry
    of the returned list is numerically identical to evaluating that
    candidate alone.  One host sync per batch serves all K candidates
    (the sequential loop pays it K times).  `trace(t0, t1)` (optional)
    records each dispatch's start/end monotonic timestamps (the
    per-batch host sync already bounds the dispatch, so tracing adds
    two clock reads and nothing else).  Each dispatch window also feeds
    the telemetry span seam (label ``tta_batched``)."""
    sums: dict[str, np.ndarray] | None = None
    for i, batch in enumerate(batches):
        t0 = telemetry.mono()
        batch_keys = jax.vmap(lambda kk: jax.random.fold_in(kk, i))(keys)
        with dispatch_enqueue_guard():
            out = tta_step_k(
                params, batch_stats, batch["x"], batch["y"], batch["m"],
                policies, batch_keys,
            )
        # accumulate at native f32 on the host: the same sequential
        # f32 additions eval_tta's Accumulator performs on device, so
        # batched == sequential holds bit-for-bit across batches too
        out = {k: np.asarray(v) for k, v in out.items()}
        t1 = telemetry.mono()
        if trace is not None:
            trace(t0, t1)
        telemetry.record_dispatch("tta_batched", t0, t1, blocking=True)
        sums = out if sums is None else {
            k: sums[k] + out[k] for k in sums
        }
    if sums is None:
        k_dim = int(policies.shape[0])
        sums = {f: np.zeros(k_dim) for f in
                ("minus_loss_sum", "correct_sum", "correct_mean_sum", "cnt")}
    results = []
    for k in range(int(sums["cnt"].shape[0])):
        cnt = float(sums["cnt"][k])
        results.append({
            "minus_loss": float(sums["minus_loss_sum"][k]) / cnt if cnt else 0.0,
            "top1_valid": float(sums["correct_sum"][k]) / cnt if cnt else 0.0,
            "top1_mean": float(sums["correct_mean_sum"][k]) / cnt if cnt else 0.0,
            "cnt": cnt,
        })
    return results
