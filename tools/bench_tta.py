"""TTA/eval-shape throughput sample (VERDICT r4, next-step 1 + weak 3).

The <1 TPU-hour search-cost certification converts CPU-measured unit
costs to TPU with a ratio taken from the TRAIN-step benchmark
(``bench.py``); the search's actual inner loop is the compiled TTA
step (``search/tta.py``), whose arithmetic intensity differs (forward
only, num_policy draws per image, no optimizer).  This tool measures
that step directly at production shape — WRN-40-2, batch 128, 5 draws,
the ``confs/wresnet40x2_cifar.yaml`` search shape — so the CPU->TPU
conversion for trial cost rests on a measured TTA-shape rate, not the
train-shape proxy.  Reference anchor: ``search.py:112-125`` (the
TTA reward evaluation this step replaces).

Run on either backend; the JSON records which one actually measured:

    python tools/bench_tta.py --out docs/tta_bench_tpu.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="wresnet40_2")
    p.add_argument("--dataset", default="cifar10")
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--image", type=int, default=32)
    p.add_argument("--num-policy", type=int, default=5)
    p.add_argument("--num-op", type=int, default=2)
    p.add_argument("--calls", type=int, default=20)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    from fast_autoaugment_tpu.core.compilecache import configure_compile_cache

    configure_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fast_autoaugment_tpu.models import get_model, num_class
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.search.tta import make_tta_step
    from fast_autoaugment_tpu.train.steps import create_train_state

    num_classes = num_class(args.dataset)
    model = get_model({"type": args.model, "dataset": args.dataset},
                      num_classes)
    tta_step = make_tta_step(model, num_policy=args.num_policy,
                             cutout_length=16)

    rng = np.random.RandomState(0)
    images = jnp.asarray(
        rng.rand(args.batch, args.image, args.image, 3).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, num_classes, size=args.batch))
    mask = jnp.ones((args.batch,), jnp.float32)
    sample = jnp.zeros((2, args.image, args.image, 3), jnp.float32)
    optimizer = build_optimizer(
        {"type": "sgd", "lr": 0.1, "momentum": 0.9}, lambda s: 0.0)
    state = create_train_state(model, optimizer, jax.random.PRNGKey(0), sample,
                               use_ema=False)

    def policy_t(i: int):
        r = np.random.RandomState(100 + i)
        t = np.stack([
            np.stack([r.randint(0, 15, size=args.num_op).astype(np.float32),
                      r.rand(args.num_op).astype(np.float32),
                      r.rand(args.num_op).astype(np.float32)], axis=-1)
            for _ in range(args.num_policy)
        ])
        return jnp.asarray(t)

    t0 = time.perf_counter()
    out = tta_step(state.params, state.batch_stats, images, labels, mask,
                   policy_t(0), jax.random.PRNGKey(0))
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for i in range(args.calls):
        out = tta_step(state.params, state.batch_stats, images, labels, mask,
                       policy_t(i + 1), jax.random.fold_in(
                           jax.random.PRNGKey(1), i))
    jax.block_until_ready(out)
    steady = time.perf_counter() - t0

    ms_per_call = steady / args.calls * 1e3
    # each call forwards batch x num_policy augmented images
    imgs_per_sec = args.batch * args.num_policy * args.calls / steady
    from bench import telemetry_stamp

    from fast_autoaugment_tpu.parallel.mesh import device_stamp

    summary = {
        **device_stamp(),
        "model": args.model,
        "batch": args.batch,
        "image": args.image,
        "num_policy": args.num_policy,
        "compile_s": round(compile_s, 2),
        "tta_ms_per_call": round(ms_per_call, 3),
        "tta_images_per_sec": round(imgs_per_sec, 1),
        "unix_time": time.time(),
    }
    # unified provenance block (schema_version + contention + shadow
    # watchdog + compile cache + telemetry counters) — one helper
    # across every bench tool (bench.telemetry_stamp)
    summary.update(telemetry_stamp([ms_per_call / 1e3], label="tta"))
    line = json.dumps(summary)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        tmp = args.out + ".tmp"
        with open(tmp, "w") as fh:
            fh.write(line + "\n")
        os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
