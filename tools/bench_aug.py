"""Micro-benchmark for the on-device augmentation engine.

Times each augmentation op (vmapped over a batch), the full policy
application under BOTH dispatch modes (``exact``: per-image vmapped
``lax.switch``, which XLA lowers to executing all 19 op branches per
image; ``grouped``: scalar-dispatch kernels at each ``--groups`` value),
and the complete CIFAR train-time stack — the pieces that replace the
reference's 8-worker PIL pipeline (``data.py:214-224``).  Run on TPU
(plain env) or CPU mesh for relative numbers:

    python tools/bench_aug.py [--batch 128] [--steps 20] [--groups 4,8,16]

Prints a per-op table plus the dispatch-mode table, and emits ONE JSON
line with ``aug_images_per_sec`` per (mode, G) and the per-mode compile
seconds (the grouped program's branch fan-in differs from the
select-all lowering, so compile time is a first-class metric here).
Use ``--skip-ops`` to bench only the dispatch modes.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def full_19op_policy(num_ops_per_sub: int = 2):
    """A policy touching every registered op: sub-policy i applies ops
    (i, i+1 mod 19) at prob 0.5 — the full-branch-fan-in shape the
    acceptance bench runs (every `lax.switch` branch is live)."""
    import numpy as np

    from fast_autoaugment_tpu.ops.augment import NUM_OPS

    rows = []
    for i in range(NUM_OPS):
        rows.append([[(i + j) % NUM_OPS, 0.5, 0.5 + 0.4 * (j % 2)]
                     for j in range(num_ops_per_sub)])
    return np.asarray(rows, np.float32)


def bench_dispatch_modes(images, key, policy, groups, steps, timed):
    """``aug_images_per_sec`` + compile seconds per (mode, G)."""
    import jax

    from fast_autoaugment_tpu.ops import augment as A

    batch = int(images.shape[0])
    out: dict = {}

    def measure(tag, fn):
        t0 = time.perf_counter()
        first = fn(images, key)
        jax.block_until_ready(first)
        compile_sec = time.perf_counter() - t0
        ms = timed(fn, images, key)
        out[tag] = {
            "images_per_sec": round(batch / (ms / 1e3), 1),
            "ms_per_batch": round(ms, 3),
            "compile_sec": round(compile_sec, 3),
        }
        print(f"{tag:<16} {ms:>10.3f} {ms / batch * 1e3:>10.1f} "
              f"{out[tag]['images_per_sec']:>12.1f} {compile_sec:>10.2f}")

    print(f"{'dispatch':<16} {'ms/batch':>10} {'us/image':>10} "
          f"{'images/sec':>12} {'compile_s':>10}")
    measure("exact", jax.jit(
        lambda imgs, k: A.apply_policy_batch(imgs, policy, k)))
    for g in groups:
        measure(f"grouped_g{g}", jax.jit(
            lambda imgs, k, g=g: A.apply_policy_batch_grouped(
                imgs, policy, k, groups=g)))
    best = max((v["images_per_sec"] for t, v in out.items()
                if t.startswith("grouped")), default=None)
    if best and out["exact"]["images_per_sec"]:
        out["speedup_grouped_best_vs_exact"] = round(
            best / out["exact"]["images_per_sec"], 2)
    return out


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--size", type=int, default=32)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--groups", default="4,8,16",
                   help="comma-separated grouped-dispatch chunk counts")
    p.add_argument("--skip-ops", action="store_true",
                   help="skip the per-op table (dispatch modes only)")
    args = p.parse_args(argv)
    groups = [int(g) for g in str(args.groups).split(",") if g]

    # loadavg/process provenance, shared with bench.py: a busy-host
    # capture must be visible in the output itself, and
    # FAA_BENCH_REQUIRE_QUIET=1 refuses instead
    import json

    from bench import (
        host_contention_stamp,
        refuse_or_flag_contention,
        telemetry_stamp,
    )

    contention = refuse_or_flag_contention(host_contention_stamp())
    print(f"contention: {json.dumps(contention)}")
    from fast_autoaugment_tpu.core.compilecache import configure_compile_cache

    configure_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fast_autoaugment_tpu.ops import augment as A
    from fast_autoaugment_tpu.ops.preprocess import cifar_train_batch
    from fast_autoaugment_tpu.parallel.mesh import device_stamp
    from fast_autoaugment_tpu.policies.archive import load_policy, policy_to_tensor

    images = jnp.asarray(
        np.random.default_rng(0).integers(
            0, 256, (args.batch, args.size, args.size, 3), dtype=np.uint8
        ),
        jnp.float32,
    )
    key = jax.random.PRNGKey(0)

    def timed(fn, *fn_args):
        out = fn(*fn_args)
        jax.block_until_ready(out)  # compile
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = fn(*fn_args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.steps * 1e3  # ms

    print(f"backend={jax.devices()[0].platform} batch={args.batch} "
          f"size={args.size} steps={args.steps}")
    if not args.skip_ops:
        print(f"{'op':<16} {'ms/batch':>10} {'us/image':>10}")
        for idx, name in enumerate(A.OP_NAMES):
            fn = jax.jit(
                lambda imgs, k, i=idx: jax.vmap(
                    lambda im, kk: A.apply_op(im, jnp.int32(i), jnp.float32(0.7), kk)
                )(imgs, jax.random.split(k, imgs.shape[0]))
            )
            ms = timed(fn, images, key)
            print(f"{name:<16} {ms:>10.3f} {ms / args.batch * 1e3:>10.1f}")

    # dispatch modes on the full-19-op policy (every branch live): the
    # acceptance shape for the grouped >= 3x exact criterion
    policy19 = jnp.asarray(full_19op_policy())
    modes = bench_dispatch_modes(images, key, policy19, groups, args.steps,
                                 timed)

    policy = jnp.asarray(policy_to_tensor(load_policy("fa_reduced_cifar10")))
    fn = jax.jit(lambda imgs, k: A.apply_policy_batch(imgs, policy, k))
    ms = timed(fn, images, key)
    print(f"{'policy(493)':<16} {ms:>10.3f} {ms / args.batch * 1e3:>10.1f}")
    policy493 = {"exact_ms_per_batch": round(ms, 3)}
    g0 = groups[0] if groups else 8
    fn = jax.jit(lambda imgs, k: A.apply_policy_batch_grouped(
        imgs, policy, k, groups=g0))
    ms_g = timed(fn, images, key)
    print(f"{'policy(493) g' + str(g0):<16} {ms_g:>10.3f} "
          f"{ms_g / args.batch * 1e3:>10.1f}")
    policy493[f"grouped_g{g0}_ms_per_batch"] = round(ms_g, 3)

    fn = jax.jit(lambda imgs, k: cifar_train_batch(imgs, k, policy=policy,
                                                   cutout_length=16))
    ms = timed(fn, images, key)
    print(f"{'full stack':<16} {ms:>10.3f} {ms / args.batch * 1e3:>10.1f}")
    stack = {"exact_ms_per_batch": round(ms, 3)}
    fn = jax.jit(lambda imgs, k: cifar_train_batch(
        imgs, k, policy=policy, cutout_length=16, aug_dispatch="grouped",
        aug_groups=g0))
    ms_g = timed(fn, images, key)
    print(f"{'full stack g' + str(g0):<16} {ms_g:>10.3f} "
          f"{ms_g / args.batch * 1e3:>10.1f}")
    stack[f"grouped_g{g0}_ms_per_batch"] = round(ms_g, 3)

    # unified provenance block (bench.telemetry_stamp): schema_version
    # + contention + shadow watchdog + compile cache + registry counters
    # — the per-(mode, G) compile_sec entries above remain raw timings
    print(json.dumps({
        "metric": "aug_images_per_sec",
        "unit": "images/sec",
        **device_stamp(),
        "batch": args.batch,
        "size": args.size,
        "steps": args.steps,
        "policy": "full19 (every op branch live, 2 ops/sub)",
        "modes": modes,
        "policy_493": policy493,
        "full_stack": stack,
        **telemetry_stamp([ms / 1e3], label="train_aug_stack",
                          contention=contention),
    }))


if __name__ == "__main__":
    main()
