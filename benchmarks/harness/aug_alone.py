"""The augmentation stack timed by itself, outside the window.

Until spans inside the step program give the augmentation's share of a
step, this is the only handle on it: the same ``cifar_train_batch`` the
step programs call, on the same number of images under the same policy
shape, jitted alone.  Inside a step XLA may fuse or overlap it with the
model, so read it as a bound on the share and not as the share."""

from __future__ import annotations

import time

import numpy as np

from benchmarks.harness import trace as tr

_MAX_REPEATS, _BUDGET_S = 20, 5.0


def _median_ms(fn, args) -> float:
    """Median of up to 20 calls, as many as fit into five seconds and one
    at least."""
    import jax

    jax.block_until_ready(fn(*args))  # compiles, or loads from the cache
    times, began = [], time.perf_counter()
    while not times or (len(times) < _MAX_REPEATS
                        and time.perf_counter() - began < _BUDGET_S):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return tr.median(times)


def _images(obs, batch: int):
    import jax

    size = int(obs.cell.config["model"]["image"])
    rng = np.random.default_rng(obs.cell.seed)
    return jax.device_put(
        rng.integers(0, 256, (batch, size, size, 3), dtype=np.uint8),
        obs.devices[0])


def train_batch_ms(obs) -> float:
    """One per-chip training batch under the configuration's policy."""
    import jax

    from fast_autoaugment_tpu.ops.preprocess import cifar_train_batch
    from fast_autoaugment_tpu.train.trainer import resolve_policy_tensor

    conf = obs.cell.conf_dict()
    policy = resolve_policy_tensor(conf.get("aug", "default"))
    cutout = int(conf.get("cutout", 0) or 0)
    fn = jax.jit(lambda x, p, k: cifar_train_batch(
        x, k, policy=p, cutout_length=cutout))
    return _median_ms(fn, (_images(obs, int(conf["batch"])),
                           jax.device_put(policy, obs.devices[0]),
                           jax.random.PRNGKey(obs.cell.seed)))
