"""Jitted train/eval steps.

One pjit-compiled function per phase is the whole training runtime —
the analog of the reference's per-batch Python loop body
(``run_epoch``, ``train.py:35-107``), but with augmentation, forward,
loss (+wd), backward, clip, optimizer, EMA and metric reduction fused
into a single XLA program over the global batch:

- the global batch arrives sharded over the mesh's ``'data'`` axis;
  params are replicated; XLA inserts gradient allreduces over ICI
  (the DDP/NCCL equivalent, SURVEY.md section 2.2);
- BN statistics are global-batch statistics — cross-replica BN by
  construction (what ``tf_port/tpu_bn.py`` hand-built);
- augmentation policies enter as TENSORS, so changing policies never
  recompiles (the property the TTA search engine relies on);
- EMA is a pytree lerp on device (the reference's Python-loop EMA over
  ``state_dict`` items, ``common.py:46-51``, is a per-step host hot
  loop — SURVEY.md section 3.1 flags it);
- metrics leave the step as count-weighted sums, so the host only syncs
  when it reads them.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import struct

from fast_autoaugment_tpu.core import scopes
from fast_autoaugment_tpu.core.compilecache import seam_jit
from fast_autoaugment_tpu.core.metrics import (
    mixup_batch,
    mixup_cross_entropy,
    smooth_cross_entropy,
    top_k_correct,
)
from fast_autoaugment_tpu.ops.augment import (
    apply_policy_batch_grouped,
    check_aug_dispatch,
)
from fast_autoaugment_tpu.ops.optim import ema_update
from fast_autoaugment_tpu.ops.preprocess import cifar_eval_batch, cifar_train_batch

__all__ = [
    "TrainState",
    "create_train_state",
    "make_train_step",
    "make_train_step_body",
    "make_token_step_body",
    "make_token_train_step",
    "COUNT_PREFIX",
    "make_stacked_train_step",
    "make_stacked_step_body",
    "make_multistep_train_step",
    "default_dispatch_unroll",
    "make_eval_step",
    "make_replay_eval_step",
    "stack_states",
    "slice_state",
]


# domain-separation tag for the stacked grouped-augmentation key
# derivation: the fold's step key is fold_in(keys[k], step[k]) — folding
# this tag on top keeps the grouped policy pass on a stream disjoint
# from the in-body augment/model keys derived from the same pair
_GROUPED_AUG_TAG = 7919


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any
    ema: Any  # {'params', 'batch_stats'} shadow, or None


def create_train_state(model, optimizer, rng, sample_input, use_ema: bool,
                       jit_init: bool = False) -> TrainState:
    """`jit_init` runs ``model.init`` as one program instead of operation
    by operation (a token model's hundreds of operations would each be
    compiled: 73 s of a cold run on the chip's host with the forward pass
    kept, my chip run, PR 35).  The image models keep the eager
    construction: as one program XLA fuses a draw with its scaling, and
    the seeded weights of four of six presets then differ in the last
    bit (Shake-Shake-26 2x96d, ResNet-50, PyramidNet, EfficientNet-B0:
    up to 2.4e-7 on the CPU; WRN-40-2 and WRN-28-10 bit-equal; PR 35),
    which the pinned numeric tests of the image path would see."""
    def init(rngs, sample):
        variables = model.init(rngs, sample, train=False)
        # nothing else comes out, so under jit the forward pass that shaped
        # the parameters is dead code and only their draws are compiled
        return {k: v for k, v in variables.items() if k in ("params", "batch_stats")}

    variables = (seam_jit(init, label="state_init") if jit_init else init)(
        {"params": rng, "shake": jax.random.fold_in(rng, 1)}, sample_input)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    # the EMA shadow must be a DISTINCT set of buffers: the train step
    # donates the whole state, and donating two references to one buffer
    # is an error
    ema = (
        jax.tree.map(jnp.copy, {"params": params, "batch_stats": batch_stats})
        if use_ema
        else None
    )
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=optimizer.init(params),
        ema=ema,
    )


def _make_train_step_body(
    model,
    optimizer,
    *,
    num_classes: int,
    mixup_alpha: float = 0.0,
    lb_smooth: float = 0.0,
    ema_mu: float = 0.0,
    cutout_length: int = 16,
    use_policy: bool = True,
    augment_fn: Callable | None = None,
    aug_dispatch: str = "exact",
    aug_groups: int = 8,
) -> Callable:
    """The UNJITTED per-model train-step body shared by the sequential
    and fold-stacked variants: :func:`make_train_step` jits it directly;
    :func:`make_stacked_train_step` vmaps the identical computation over
    a leading fold axis — the candidate-axis construction of
    ``search/tta.py::make_tta_step``, applied to phase 1.  Unlike the
    eval-only TTA step, a TRAIN step under vmap lowers to batched
    conv/matmul kernels whose reduction order can differ from the
    unbatched ones by ~1 float32 ULP per step (measured; see
    ``train_folds_stacked``), so stacked equality with sequential
    training is ULP-exact per step but only tolerance-bounded over a
    full run — the same deviation class as the repo's documented
    single-vs-multi-device drift (tests/test_train.py).
    """
    check_aug_dispatch(aug_dispatch)
    if augment_fn is None:
        def augment_fn(images, policy, key):
            return cifar_train_batch(
                images, key, policy=policy if use_policy else None,
                cutout_length=cutout_length,
                aug_dispatch=aug_dispatch, aug_groups=aug_groups,
            )

    def loss_fn(params, batch_stats, images, labels, key):
        key_mix, key_shake, key_drop = jax.random.split(key, 3)
        apply = functools.partial(
            model.apply,
            {"params": params, "batch_stats": batch_stats},
            train=True,
            mutable=["batch_stats"],
            rngs={"shake": key_shake, "dropout": key_drop},
        )
        if mixup_alpha > 0.0:
            with jax.named_scope(scopes.LOSS):
                mixed, targets_a, targets_b, lam = mixup_batch(
                    key_mix, images, labels, mixup_alpha)
            with jax.named_scope(scopes.MODEL):
                logits, mutated = apply(mixed)
            with jax.named_scope(scopes.LOSS):
                loss = mixup_cross_entropy(logits, targets_a, targets_b, lam, lb_smooth)
        else:
            with jax.named_scope(scopes.MODEL):
                logits, mutated = apply(images)
            with jax.named_scope(scopes.LOSS):
                loss = smooth_cross_entropy(logits, labels, lb_smooth)
        return loss, (logits, mutated["batch_stats"])

    def step_fn(state: TrainState, images, labels, policy, key):
        key_aug, key_model = jax.random.split(jax.random.fold_in(key, state.step))
        images = augment_fn(images, policy, key_aug)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (loss, (logits, new_batch_stats)), grads = grad_fn(
            state.params, state.batch_stats, images, labels, key_model
        )
        new_state = _advance(state, optimizer, grads, new_batch_stats, ema_mu)
        batch = labels.shape[0]
        with jax.named_scope(scopes.METRICS):
            metrics = {
                "loss": loss * batch,
                "top1": top_k_correct(logits, labels, 1).astype(jnp.float32),
                "top5": top_k_correct(
                    logits, labels, min(5, num_classes)).astype(jnp.float32),
                "num": jnp.float32(batch),
            }
        return new_state, metrics

    return step_fn


def _advance(state: TrainState, optimizer, grads, new_batch_stats,
             ema_mu: float) -> TrainState:
    """The update every step body ends on: optimizer, parameter add, EMA."""
    with jax.named_scope(scopes.OPTIMIZER):
        updates, new_opt_state = optimizer.update(
            grads, state.opt_state, state.params)
        new_params = jax.tree.map(lambda p, u: p + u, state.params, updates)

    new_ema = state.ema
    if state.ema is not None and ema_mu > 0.0:
        with jax.named_scope(scopes.EMA):
            new_ema = ema_update(
                state.ema,
                {"params": new_params, "batch_stats": new_batch_stats},
                ema_mu,
                state.step + 1,  # 1-based, reference train.py:70
            )
    return state.replace(
        step=state.step + 1,
        params=new_params,
        batch_stats=new_batch_stats,
        opt_state=new_opt_state,
        ema=new_ema,
    )


#: metric sums under this prefix are counts: the trainer publishes them
#: as counters where it syncs the epoch's sums and never divides them
COUNT_PREFIX = "n_"


def _next_token_sums(logits, targets):
    """``(nll [B], correct [B])``: a sequence's mean next-token
    cross-entropy and the share of its targets the largest logit hits."""
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = jax.nn.logsumexp(logits, axis=-1) - picked
    correct = jnp.argmax(logits, axis=-1) == targets
    return nll.mean(axis=-1), correct.astype(jnp.float32).mean(axis=-1)


def make_token_step_body(model, optimizer, *, ema_mu: float = 0.0) -> Callable:
    """The UNJITTED train-step body of a token model, with the image
    body's signature so that :func:`make_train_step` 's jit and
    :func:`make_multistep_train_step` 's gather take it as they stand:
    ``(state, ids [B, T + 1], labels, policy, key) -> (state, sums)``.

    Inputs are ``ids[:, :-1]``, targets ``ids[:, 1:]``; no augmentation
    stage, no policy (`labels`, `policy` and `key` are not read: the model
    draws nothing), the loss the mean next-token cross-entropy over
    ``[B, T, V]``.  The sums: ``loss`` and ``top1`` (next-token accuracy)
    a sequence, ``num`` sequences, and counts under :data:`COUNT_PREFIX`
    — tokens, and what the model counted.

    A model may keep a rule that runs between steps and outside the
    gradient (a router's load balancing): it names a collection
    (``model.step_collection``) that its forward pass ``sow``s into, and
    ``model.after_step(params, sown) -> (params, counts)`` is applied to
    the parameters the optimizer left; `counts` (scalars by name) join the
    step's count sums.  A model without the two names has no such rule.

    A model may have more to say about the loss than one array of logits
    (a second head behind a second term): it has a method
    ``loss_terms(inputs, targets) -> (nll [B], top1 [B], further)``, is
    handed inputs *and* targets and computes the main head's two
    per-sequence means itself (so it may take its head a block of
    positions at a time); ``further = {name: (value [B], weight)}`` are
    terms the loss adds at ``weight * mean(value)`` (weight zero: reported
    only).  ``loss`` and ``top1`` stay the main head's; each further term
    is a sum of its own under its name.  A model without the name is
    applied to the inputs and its logits go into one cross-entropy.
    """
    collection = getattr(model, "step_collection", None)
    mutable = ["batch_stats"] + ([collection] if collection else [])
    has_terms = hasattr(model, "loss_terms")

    def loss_fn(params, batch_stats, ids):
        inputs, targets = ids[:, :-1], ids[:, 1:]
        variables = {"params": params, "batch_stats": batch_stats}
        if has_terms:
            with jax.named_scope(scopes.MODEL):
                (nll, correct, further), mutated = model.apply(
                    variables, inputs, targets, mutable=mutable,
                    method="loss_terms")
        else:
            with jax.named_scope(scopes.MODEL):
                logits, mutated = model.apply(variables, inputs, train=True,
                                              mutable=mutable)
            with jax.named_scope(scopes.LOSS):
                nll, correct = _next_token_sums(logits, targets)
            further = {}
        loss = nll.mean()
        for value, weight in further.values():
            if weight:
                loss = loss + weight * value.mean()
        return loss, (nll.sum(), correct.sum(),
                      {name: value.sum() for name, (value, _) in further.items()},
                      mutated.get("batch_stats", batch_stats),
                      mutated.get(collection, {}))

    def step_fn(state: TrainState, ids, labels, policy, key):
        del labels, policy, key
        (_, (nll, correct, further, new_batch_stats, stats)), grads = (
            jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, state.batch_stats, ids))
        new_state = _advance(state, optimizer, grads, new_batch_stats, ema_mu)
        batch, length = ids.shape[0], ids.shape[1] - 1
        counts = {"tokens": batch * length}
        if collection:
            with jax.named_scope(scopes.OPTIMIZER):
                params, counted = model.after_step(
                    new_state.params, jax.lax.stop_gradient(stats))
            new_state = new_state.replace(params=params)
            counts.update(counted)
        with jax.named_scope(scopes.METRICS):
            metrics = {"loss": nll, "top1": correct, "num": jnp.float32(batch),
                       **further,
                       **{f"{COUNT_PREFIX}{name}": jnp.float32(value)
                          for name, value in counts.items()}}
        return new_state, metrics

    return step_fn


# public name: the device-cache multi-step dispatcher wraps this body in
# a lax.scan (make_multistep_train_step), and benches/tests build it too
make_train_step_body = _make_train_step_body


def make_train_step(
    model,
    optimizer,
    *,
    num_classes: int,
    mixup_alpha: float = 0.0,
    lb_smooth: float = 0.0,
    ema_mu: float = 0.0,
    cutout_length: int = 16,
    use_policy: bool = True,
    augment_fn: Callable | None = None,
    aug_dispatch: str = "exact",
    aug_groups: int = 8,
) -> Callable:
    """Build the jitted train step.

    Returns ``step_fn(state, images_u8, labels, policy, key) ->
    (state, metric_sums)``.  `augment_fn(images, policy, key)` defaults
    to the CIFAR/SVHN stack; pass an ImageNet stack for that family.
    ``aug_dispatch``/``aug_groups`` select the policy-application
    kernel of the DEFAULT augment_fn ("exact" = the historical
    per-image vmapped-switch path bit-for-bit; "grouped" = scalar
    dispatch with stratified per-chunk sub-policy draws); a custom
    `augment_fn` owns its own dispatch.
    """
    body = _make_train_step_body(
        model, optimizer, num_classes=num_classes, mixup_alpha=mixup_alpha,
        lb_smooth=lb_smooth, ema_mu=ema_mu, cutout_length=cutout_length,
        use_policy=use_policy, augment_fn=augment_fn,
        aug_dispatch=aug_dispatch, aug_groups=aug_groups,
    )
    # donate the state: params/opt-state/EMA buffers are overwritten in
    # place, halving peak HBM for the update.  Jitted through the
    # compile seam (core/compilecache.py): first-call compile is timed
    # and classified hit/miss against the persistent cache.
    return seam_jit(body, label="train_step", donate_argnums=(0,))


def make_token_train_step(model, optimizer, *, ema_mu: float = 0.0) -> Callable:
    """:func:`make_token_step_body` jitted as :func:`make_train_step` jits
    the image body (the host-fed feed's step; the device cache wraps the
    body in :func:`make_multistep_train_step`)."""
    return seam_jit(make_token_step_body(model, optimizer, ema_mu=ema_mu),
                    label="train_step", donate_argnums=(0,))


def make_stacked_step_body(
    model,
    optimizer,
    *,
    num_classes: int,
    mixup_alpha: float = 0.0,
    lb_smooth: float = 0.0,
    ema_mu: float = 0.0,
    cutout_length: int = 16,
    use_policy: bool = True,
    augment_fn: Callable | None = None,
    aug_dispatch: str = "exact",
    aug_groups: int = 8,
) -> Callable:
    """The UNJITTED fold-stacked step (fold vmap + grouped-dispatch
    hoist + active-lane masking): :func:`make_stacked_train_step` jits
    it directly; :func:`make_multistep_train_step` wraps it in a
    ``lax.scan`` over N steps (the scan sits OUTSIDE the fold vmap, so
    the grouped policy pass stays hoisted with a scalar switch index).
    See :func:`make_stacked_train_step` for the full contract."""
    check_aug_dispatch(aug_dispatch)
    pre_policy = (aug_dispatch == "grouped" and augment_fn is None
                  and use_policy)
    if pre_policy:
        def inner_augment(images, policy, key):
            # the grouped policy pass already ran outside the vmap
            return cifar_train_batch(images, key, policy=None,
                                     cutout_length=cutout_length)

        body = _make_train_step_body(
            model, optimizer, num_classes=num_classes,
            mixup_alpha=mixup_alpha, lb_smooth=lb_smooth, ema_mu=ema_mu,
            cutout_length=cutout_length, use_policy=use_policy,
            augment_fn=inner_augment,
        )
    else:
        body = _make_train_step_body(
            model, optimizer, num_classes=num_classes, mixup_alpha=mixup_alpha,
            lb_smooth=lb_smooth, ema_mu=ema_mu, cutout_length=cutout_length,
            use_policy=use_policy, augment_fn=augment_fn,
            aug_dispatch=aug_dispatch, aug_groups=aug_groups,
        )

    def stacked_fn(states, images, labels, policy, keys, active):
        if pre_policy:
            auged = []
            for k in range(images.shape[0]):  # static fold count
                key_pol = jax.random.fold_in(
                    jax.random.fold_in(keys[k], states.step[k]),
                    _GROUPED_AUG_TAG)
                with jax.named_scope(scopes.AUG_POLICY):
                    auged.append(apply_policy_batch_grouped(
                        images[k].astype(jnp.float32), policy, key_pol,
                        groups=aug_groups))
            images = jnp.stack(auged)
        new_states, metrics = jax.vmap(
            body, in_axes=(0, 0, 0, None, 0)
        )(states, images, labels, policy, keys)

        def select(new, old):
            gate = active.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(gate > 0, new, old)

        new_states = jax.tree.map(select, new_states, states)
        metrics = {k: v * active for k, v in metrics.items()}
        return new_states, metrics

    return stacked_fn


def make_stacked_train_step(
    model,
    optimizer,
    *,
    num_classes: int,
    mixup_alpha: float = 0.0,
    lb_smooth: float = 0.0,
    ema_mu: float = 0.0,
    cutout_length: int = 16,
    use_policy: bool = True,
    augment_fn: Callable | None = None,
    aug_dispatch: str = "exact",
    aug_groups: int = 8,
) -> Callable:
    """Build the fold-stacked train step: K fold models advance in ONE
    jitted program per step (the Podracer whole-learner-replica vmap,
    arXiv:2104.06272, applied to phase-1 fold pretraining).

    Returns ``fn(states, images_u8 [K,B,H,W,C], labels [K,B], policy,
    keys [K,2], active [K]) -> (states, metric_sums)`` where `states` is
    a :class:`TrainState` whose every leaf carries a leading fold axis
    (:func:`stack_states`) and `keys` stacks the per-fold base PRNG keys
    (fold k's per-step key is ``fold_in(keys[k], states.step[k])``
    inside the body — exactly the sequential step's derivation).

    The fold axis is a pure ``jax.vmap`` of the sequential step body:
    fold k's update is the sequential step on its slice, computed by
    batched kernels whose accumulation order may differ by ~1 f32 ULP
    (the documented stacked-vs-sequential bound; module body docstring).
    `active` (float 0/1 per fold) freezes finished lanes:
    inactive folds still ride through the program (one executable for
    any participation set — no recompiles when folds resume at different
    epochs or run out of batches), but their state is passed through
    unchanged and their metric sums are zeroed, so a masked lane is
    indistinguishable from not having stepped at all.

    ``aug_dispatch="grouped"`` needs special handling here: a grouped
    kernel INSIDE the fold-vmapped body would see its per-fold scalar
    sub-policy index re-batched by the fold axis, and ``lax.switch``
    would fall straight back to executing all branches (the exact-mode
    cost, with none of exact mode's distribution).  So the grouped
    policy application is HOISTED out of the vmap: each fold's raw
    batch goes through :func:`apply_policy_batch_grouped` in a static
    per-fold loop (scalar dispatch preserved), keyed by
    ``fold_in(fold_in(keys[k], states.step[k]), _GROUPED_AUG_TAG)`` so
    per-fold streams stay independent and step-fresh, and the
    fold-vmapped body then runs the policy-less per-image stack.
    Exact mode is untouched — augmentation stays inside the body,
    bit-for-bit the historical program.
    """
    body = make_stacked_step_body(
        model, optimizer, num_classes=num_classes, mixup_alpha=mixup_alpha,
        lb_smooth=lb_smooth, ema_mu=ema_mu, cutout_length=cutout_length,
        use_policy=use_policy, augment_fn=augment_fn,
        aug_dispatch=aug_dispatch, aug_groups=aug_groups,
    )
    return seam_jit(body, label="stacked_step", donate_argnums=(0,))


def default_dispatch_unroll(steps_per_dispatch: int) -> int:
    """Measured-default ``unroll`` for :func:`make_multistep_train_step`.

    On XLA:CPU, convolution BACKWARD passes inside a ``while`` loop hit
    a slow kernel path (~3-4x the out-of-loop cost per step, measured
    on wresnet10_1; dense-only bodies are unaffected) — any loop at all
    triggers it, so partial unroll buys nothing and the only fast CPU
    shape is the fully unrolled one (compile time then grows ~linearly
    with N; acceptable at the small N the CPU dev/test path uses).  On
    TPU the rolled scan is the standard pjit-trainer shape and keeps
    compile time independent of N, which is what production wants at
    N=32 on minutes-long WRN compiles.  See docs/PARITY.md "Step
    dispatch & device cache".
    """
    return steps_per_dispatch if jax.default_backend() == "cpu" else 1


def make_multistep_train_step(
    body: Callable,
    *,
    steps_per_dispatch: int,
    stacked: bool = False,
    unroll: int | None = None,
) -> Callable:
    """Fuse N train steps into ONE jitted dispatch over a device-resident
    dataset cache (`data.pipeline.DeviceCache`): a ``lax.scan`` over the
    step axis whose body gathers each batch from the cache BY INDEX
    inside the program — the sequence-of-steps-in-one-program structure
    of the Podracer architectures (arXiv:2104.06272) and the pjit-era
    LLM trainers.  The host loop's per-step work collapses from
    (fancy-gather + H2D image copy + dispatch) x N to shipping one int32
    index matrix and dispatching once.

    ``cache_images`` is the cache's stored form
    (``data.pipeline.StoredRows``, what ``DeviceCache.images`` is): the
    cache owns how its examples are laid out and how a batch is taken
    from them, so this program never sees the layout; labels are a plain
    ``[N]`` array.

    `body` is an UNJITTED step body:

    - sequential (``stacked=False``): :func:`make_train_step_body`'s
      ``(state, images, labels, policy, key) -> (state, metrics)``.
      Returns ``fn(state, cache_images, cache_labels, idx [N, B],
      policy, key) -> (state, metric_sums)``.
    - stacked (``stacked=True``): :func:`make_stacked_step_body`'s
      ``(states, images, labels, policy, keys, active)``.  Returns
      ``fn(states, cache_images, cache_labels, idx [N, K, B], policy,
      keys, active [N, K]) -> (states, metric_sums [K])``.  The scan
      sits OUTSIDE the fold vmap, so the PR-3 grouped-dispatch hoist
      inside the body keeps its scalar switch index.

    Per-step PRNG derivation is untouched: the body folds the carried
    ``state.step`` into the base key, so step t inside the scan draws
    exactly the keys the host loop's t-th dispatch would.  Metrics come
    back summed over the N steps (they are count-weighted sums already);
    with ``steps_per_dispatch=1`` the scan is skipped entirely and the
    program is the single-step body behind a gather — the configuration
    pinned bit-for-bit against the host path (tests/test_device_cache.py).

    The state is donated (same discipline as :func:`make_train_step`);
    the cache arrays are NOT — they persist across dispatches by design.
    Callers must COMMIT the carried state (and the small replicated
    inputs) to the mesh (``jax.device_put(state, replicated(mesh))``)
    before the first call: compiling with an uncommitted state against
    the mesh-committed cache arrays pushes every later call off the C++
    fast dispatch path onto a per-leaf reshard (measured ~17x per-call
    overhead on the 84-leaf WRN state) — the trainer does this, as the
    stacked trainer always has.  ``unroll`` feeds ``lax.scan``
    (default :func:`default_dispatch_unroll`: full unroll on the CPU
    backend, whose conv-backward-in-loop slow path otherwise eats the
    win; rolled on accelerators).
    """
    if steps_per_dispatch < 1:
        raise ValueError(
            f"steps_per_dispatch must be >= 1, got {steps_per_dispatch}")
    if unroll is None:
        unroll = default_dispatch_unroll(steps_per_dispatch)

    def gather(cache_images, cache_labels, idx_n):
        with jax.named_scope(scopes.BATCH_GATHER):
            return (cache_images.take(idx_n),
                    jnp.take(cache_labels, idx_n, axis=0))

    if not stacked:
        def multi_fn(state, cache_images, cache_labels, idx, policy, key):
            def one(carry, idx_n):
                images, labels = gather(cache_images, cache_labels, idx_n)
                return body(carry, images, labels, policy, key)

            if steps_per_dispatch == 1:
                return one(state, idx[0])
            state, metrics = jax.lax.scan(one, state, idx, unroll=unroll)
            return state, jax.tree.map(lambda v: v.sum(axis=0), metrics)
    else:
        def multi_fn(states, cache_images, cache_labels, idx, policy, keys,
                     active):
            def one(carry, step_in):
                idx_n, active_n = step_in
                images, labels = gather(cache_images, cache_labels, idx_n)
                return body(carry, images, labels, policy, keys, active_n)

            if steps_per_dispatch == 1:
                return one(states, (idx[0], active[0]))
            states, metrics = jax.lax.scan(one, states, (idx, active),
                                           unroll=unroll)
            return states, jax.tree.map(lambda v: v.sum(axis=0), metrics)

    # seam labels match the watchdog's dispatch labels so the compile
    # evidence and the deadline evidence line up per entry point
    return seam_jit(multi_fn,
                    label="stacked_dispatch" if stacked else "train_dispatch",
                    donate_argnums=(0,))


def stack_states(states: list[TrainState]) -> TrainState:
    """Stack K per-fold states into one state with a leading fold axis
    on every leaf (``ema=None`` stays None)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def slice_state(states: TrainState, fold_axis_index: int) -> TrainState:
    """Extract fold k's unstacked state from a stacked state — the
    checkpoint-slicing primitive (each fold saves/restores under the
    same per-fold layout the sequential path uses)."""
    return jax.tree.map(lambda x: x[fold_axis_index], states)


def _make_eval_body(model, *, num_classes: int, lb_smooth: float = 0.0,
                    preprocess_fn: Callable | None = None) -> Callable:
    """The unjitted eval body shared by the per-batch and the fused
    replay eval steps."""
    if preprocess_fn is None:
        preprocess_fn = cifar_eval_batch

    def eval_fn(params, batch_stats, images, labels, mask):
        """`mask` [B] of 0/1 marks real examples — eval batches are padded
        up to a multiple of the mesh size and the padding masked out, so
        partial final batches (reference drop_last=False eval loaders)
        still shard evenly."""
        images = preprocess_fn(images)
        logits = model.apply(
            {"params": params, "batch_stats": batch_stats}, images, train=False
        )
        nll = smooth_cross_entropy(logits, labels, lb_smooth, reduce_mean=False)
        top1 = jax.lax.top_k(logits, 1)[1] == labels[:, None]
        topk = jax.lax.top_k(logits, min(5, num_classes))[1] == labels[:, None]
        return {
            "loss": (nll * mask).sum(),
            "top1": (top1.any(axis=-1) * mask).sum().astype(jnp.float32),
            "top5": (topk.any(axis=-1) * mask).sum().astype(jnp.float32),
            "num": mask.sum().astype(jnp.float32),
        }

    return eval_fn


def _make_token_eval_body(model) -> Callable:
    """The eval body of a token model, with :func:`_make_eval_body` 's
    signature: loss and next-token accuracy a sequence, padding masked."""

    def eval_fn(params, batch_stats, ids, labels, mask):
        del labels
        logits = model.apply(
            {"params": params, "batch_stats": batch_stats}, ids[:, :-1],
            train=False)
        nll, correct = _next_token_sums(logits, ids[:, 1:])
        return {
            "loss": (nll * mask).sum(),
            "top1": (correct * mask).sum(),
            "num": mask.sum().astype(jnp.float32),
        }

    return eval_fn


def make_eval_step(model, *, num_classes: int, lb_smooth: float = 0.0,
                   preprocess_fn: Callable | None = None,
                   tokens: bool = False) -> Callable:
    """Build the jitted eval step: ``fn(params, batch_stats, images_u8,
    labels, mask) -> metric_sums`` (loss/top1/top5/num as sums); with
    `tokens` the batch is ids ``[B, T + 1]`` and the sums are the
    next-token loss and accuracy."""
    body = _make_token_eval_body(model) if tokens else _make_eval_body(
        model, num_classes=num_classes, lb_smooth=lb_smooth,
        preprocess_fn=preprocess_fn)
    return seam_jit(body, label="eval_step")


def make_replay_eval_step(model, *, num_classes: int, lb_smooth: float = 0.0,
                          preprocess_fn: Callable | None = None,
                          tokens: bool = False) -> Callable:
    """Whole-split evaluation in ONE dispatch: ``fn(params, batch_stats,
    images [S, B, H, W, C], labels [S, B], masks [S, B]) -> metric_sums``
    — a ``lax.scan`` of the eval body over a device-resident stack of
    batches with the metric sums reduced in-program.

    This is the eval twin of :func:`make_multistep_train_step` for the
    device-cache replay path, and it is a CORRECTNESS fix as well as a
    perf one: evaluating a replayed split per batch queues S eval
    programs plus 4S scalar-add programs, and with a mesh-committed
    state every one of those scalar adds lowers to an all-participant
    collective — on the 8-virtual-device CPU test mesh, hundreds of
    queued tiny collectives interleave their rendezvous and DEADLOCK
    the backend (observed: eval wedged in `Accumulator.add` with XLA
    "waiting for all participants" stalls).  One fused program per
    split sequences its internal collectives correctly and leaves the
    host with a single 4-scalar read.  Forward-only, so the XLA:CPU
    conv-backward-in-while pathology (`default_dispatch_unroll`) does
    not apply — the rolled scan is fast on every backend.
    """
    body = _make_token_eval_body(model) if tokens else _make_eval_body(
        model, num_classes=num_classes, lb_smooth=lb_smooth,
        preprocess_fn=preprocess_fn)

    def replay_fn(params, batch_stats, images, labels, masks):
        def one(carry, batch):
            x, y, m = batch
            return carry, body(params, batch_stats, x, y, m)

        _, sums = jax.lax.scan(one, jnp.zeros(()), (images, labels, masks))
        return jax.tree.map(lambda v: v.sum(axis=0), sums)

    return seam_jit(replay_fn, label="replay_eval")
