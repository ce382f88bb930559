"""Pre-fix regression snippet: mixed mesh-commitment into a jitted
entry point — the measured 17x dispatch-overhead pathology (PR 4,
docs/PARITY.md "Step dispatch & device cache").

The device cache is mesh-committed but the loop-carried TrainState is
not: every dispatch re-resolves placement and falls off the C++ fast
path.  Fixed by ``jax.device_put``-committing the carried state before
the loop.

Intended pass: dispatch (D3).
"""

import jax

from fast_autoaugment_tpu.core.compilecache import seam_jit


def train_epochs(body, dataset, state, sharding, index, steps):
    step = seam_jit(body, label="train_step")
    cache = jax.device_put(dataset, sharding)  # mesh-committed
    for _ in range(steps):
        # PRE-FIX: `state` is never committed while `cache` is —
        # every dispatch pays the slow placement path
        state, metrics = step(state, cache, index)
    return state
