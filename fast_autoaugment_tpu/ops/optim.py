"""Optimizers, weight decay, clipping and EMA as optax transforms.

Reproduces the reference training update exactly (``train.py:47-93``):

1. loss adds a manual decoupled L2 term ``wd/2 * sum(p**2)`` over all
   params NOT in BatchNorm modules (``train.py:40,61``) — implemented
   as a masked ``add_decayed_weights`` (identical gradient);
2. global-norm clip at ``optimizer.clip`` (default 5.0) AFTER the wd
   term is folded in (``train.py:63-65``);
3. the core update: torch-semantics SGD with Nesterov momentum
   (``train.py:139-145``), or :func:`rmsprop_tf` — the reference's
   TF-port RMSprop (``tf_port/rmsprop.py:5-101``) whose quirks matter
   for EfficientNet: ms initialized to ONES (not zeros), epsilon INSIDE
   the sqrt, and the learning rate folded into the momentum buffer.

Known deliberate deviation: the reference's non-BN filter is
name-based (``'_bn' in name or '.bn' in name``) and therefore silently
*decays* BN params inside the shake-net branches (which are indexed, not
named ``bn*``).  Here BN params are never decayed, in every model.

Beyond the reference: ``optimizer.type: adamw`` (a token model's
optimizer), Adam's two float32 moments and a *decoupled* decay added
after them, ``p <- p - lr (m_hat / (sqrt(v_hat) + eps) + decay p)``, the
global-norm clip first; its default mask ``matrices`` decays what has two
or more dimensions (projections, expert stacks, embeddings, convolution
taps) and not norms, biases, ``A_log``, ``dt_bias`` or a router's
correction bias.  ``optimizer.decay_mask`` names a mask of
:data:`DECAY_MASKS`; an unknown name raises.

EMA (reference ``common.py:28-51``, applied ``train.py:69-70``): shadow
of params+batch_stats with TF-style warmup ``mu_t = min(mu,
(1+step)/(10+step))``, as a pure pytree lerp inside the jitted step —
not a Python loop over tensors like the reference.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

__all__ = [
    "non_bn_mask",
    "matrices_mask",
    "DECAY_MASKS",
    "build_optimizer",
    "rmsprop_tf",
    "ema_update",
    "init_ema",
]


def non_bn_mask(params) -> Any:
    """Pytree mask: True for params that should receive weight decay
    (everything except BatchNorm scale/bias, identified by module name).

    Passed to optax as a CALLABLE so the optimizer can be built before
    parameters exist — optax evaluates it lazily at ``init``.
    """

    def is_bn_path(path) -> bool:
        return any("bn" in str(getattr(k, "key", k)).lower() for k in path)

    return jax.tree_util.tree_map_with_path(lambda p, _: not is_bn_path(p), params)


#: leaves :func:`matrices_mask` never decays whatever their shape
NEVER_DECAYED = ("A_log", "dt_bias", "e_score_correction_bias")


def matrices_mask(params) -> Any:
    """Pytree mask: True for leaves of two or more dimensions, but for
    the names of :data:`NEVER_DECAYED`.  A callable mask, as
    :func:`non_bn_mask`."""

    def decayed(path, leaf) -> bool:
        name = str(getattr(path[-1], "key", path[-1]))
        return leaf.ndim >= 2 and name not in NEVER_DECAYED

    return jax.tree_util.tree_map_with_path(decayed, params)


#: ``optimizer.decay_mask`` -> mask
DECAY_MASKS = {"non_bn": non_bn_mask, "matrices": matrices_mask}


class RmspropTFState(NamedTuple):
    step: jax.Array
    ms: Any
    mom: Any


def rmsprop_tf(
    learning_rate: Callable[[jax.Array], jax.Array] | float,
    alpha: float = 0.9,
    momentum: float = 0.9,
    eps: float = 1e-3,
) -> optax.GradientTransformation:
    """TF-semantics RMSprop (reference ``tf_port/rmsprop.py:75-100``).

    ms <- ms + (g^2 - ms) * (1 - alpha)        [ms init = ones]
    mom <- momentum * mom + lr * g / sqrt(ms + eps)
    update = -mom
    """

    def init_fn(params):
        return RmspropTFState(
            step=jnp.zeros((), jnp.int32),
            ms=jax.tree.map(jnp.ones_like, params),
            mom=jax.tree.map(jnp.zeros_like, params),
        )

    def update_fn(updates, state, params=None):
        del params
        lr = learning_rate(state.step) if callable(learning_rate) else learning_rate
        ms = jax.tree.map(lambda m, g: m + (g * g - m) * (1.0 - alpha), state.ms, updates)
        mom = jax.tree.map(
            lambda v, g, m: momentum * v + lr * g / jnp.sqrt(m + eps),
            state.mom,
            updates,
            ms,
        )
        new_updates = jax.tree.map(lambda v: -v, mom)
        return new_updates, RmspropTFState(step=state.step + 1, ms=ms, mom=mom)

    return optax.GradientTransformation(init_fn, update_fn)


def build_optimizer(
    optimizer_conf: Any,
    learning_rate: Callable[[jax.Array], jax.Array],
) -> optax.GradientTransformation:
    """Weight-decay -> clip -> core optimizer chain, from the conf schema
    ``optimizer{type, decay, (momentum), (nesterov), (clip), (decay_mask),
    (beta1, beta2, eps)}``; for ``adamw`` clip -> Adam -> decoupled decay.

    The decay mask is a callable, so no parameters are needed up front.
    """
    kind = optimizer_conf["type"]
    decay = float(optimizer_conf.get("decay", 0.0))
    clip = float(optimizer_conf.get("clip", 5.0))
    mask_name = optimizer_conf.get("decay_mask") or (
        "matrices" if kind == "adamw" else "non_bn")
    if mask_name not in DECAY_MASKS:
        raise ValueError(f"invalid optimizer decay_mask {mask_name!r}: "
                         f"use one of {sorted(DECAY_MASKS)}")
    mask = DECAY_MASKS[mask_name]

    if kind == "adamw":
        chain = [optax.clip_by_global_norm(clip)] if clip > 0 else []
        chain.append(optax.scale_by_adam(
            b1=float(optimizer_conf.get("beta1", 0.9)),
            b2=float(optimizer_conf.get("beta2", 0.95)),
            eps=float(optimizer_conf.get("eps", 1e-8))))
        if decay > 0:
            chain.append(optax.add_decayed_weights(decay, mask=mask))
        chain.append(optax.scale_by_learning_rate(learning_rate))
        return optax.chain(*chain)

    chain = []
    if decay > 0:
        chain.append(optax.add_decayed_weights(decay, mask=mask))
    if clip > 0:
        chain.append(optax.clip_by_global_norm(clip))

    if kind == "sgd":
        momentum = float(optimizer_conf.get("momentum", 0.9))
        nesterov = bool(optimizer_conf.get("nesterov", True))
        chain.append(optax.trace(decay=momentum, nesterov=nesterov))
        chain.append(optax.scale_by_learning_rate(learning_rate))
    elif kind == "rmsprop":
        chain.append(rmsprop_tf(learning_rate, alpha=0.9, momentum=0.9, eps=1e-3))
    else:
        raise ValueError(f"invalid optimizer type {kind!r}")
    return optax.chain(*chain)


def init_ema(tree):
    """Initialize the EMA shadow as a copy of (params, batch_stats)."""
    return jax.tree.map(jnp.asarray, tree)


def ema_update(shadow, new_tree, mu: float, step: jax.Array):
    """shadow <- (1 - mu_t) * new + mu_t * shadow, with TF warmup
    ``mu_t = min(mu, (1 + step) / (10 + step))`` (reference ``common.py:39-51``).

    `step` is the 1-based global step, matching ``train.py:70``.
    """
    step = jnp.asarray(step, jnp.float32)
    mu_t = jnp.minimum(mu, (1.0 + step) / (10.0 + step))
    return jax.tree.map(lambda s, x: (1.0 - mu_t) * x + mu_t * s, shadow, new_tree)
