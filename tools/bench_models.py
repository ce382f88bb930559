"""Model-zoo training-throughput benchmark: img/s + MFU per family.

Runs the SAME fused production train step `bench.py` measures (policy
augmentation + fwd/bwd + optimizer, bf16 activations) across model
families on the backend JAX finds — every row names its `platform`,
`device_kind` and `device_count`, and there is no fallback: a CPU run
says `platform: "cpu"` and carries no MFU.
Complements `bench.py` (single headline config) with the zoo-wide view:
the reference's cost table spans WRN/Shake-Shake/PyramidNet/ResNet/
EfficientNet (reference ``README.md:16-41``), so the TPU story should
too.

    python tools/bench_models.py [--models wresnet40_2,resnet50]
        [--steps 15] [--out docs/model_bench.md]

Each entry prints a JSON line and, with --out, the table is appended
as markdown. CIFAR families run at 32px / their conf batch; ImageNet
families at 224px with a reduced batch so a single chip holds them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (model conf, dataset family, batch/device, policy archive)
ZOO = {
    "wresnet40_2": ({"type": "wresnet40_2"}, "cifar", 128, "fa_reduced_cifar10"),
    "wresnet28_10": ({"type": "wresnet28_10"}, "cifar", 128, "fa_reduced_cifar10"),
    "shake26_2x32d": ({"type": "shakeshake26_2x32d"}, "cifar", 128, "fa_reduced_cifar10"),
    "shake26_2x96d": ({"type": "shakeshake26_2x96d"}, "cifar", 128, "fa_reduced_cifar10"),
    "pyramid272": (
        {"type": "pyramid", "depth": 272, "alpha": 200, "bottleneck": True},
        "cifar", 64, "fa_reduced_cifar10",
    ),
    "resnet50": ({"type": "resnet50"}, "imagenet", 64, "fa_resnet50_rimagenet"),
    "resnet200": ({"type": "resnet200"}, "imagenet", 16, "fa_resnet50_rimagenet"),
    "efficientnet_b0": (
        {"type": "efficientnet-b0"}, "imagenet", 64, "fa_resnet50_rimagenet",
    ),
}


def bench_one(name, steps, warmup):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.parallel.mesh import make_mesh, shard_batch
    from fast_autoaugment_tpu.policies.archive import load_policy, policy_to_tensor
    from fast_autoaugment_tpu.train.steps import create_train_state, make_train_step

    from bench import _chip_peak_flops, _step_flops  # reuse headline helpers

    model_conf, family, batch, archive = ZOO[name]
    mesh = make_mesh()
    global_batch = batch * mesh.size
    size = 224 if family == "imagenet" else 32
    num_classes = 120 if family == "imagenet" else 10

    model = get_model(dict(model_conf, precision="bf16"), num_classes)
    optimizer = build_optimizer(
        {"type": "sgd", "decay": 2e-4, "clip": 5.0, "momentum": 0.9,
         "nesterov": True},
        lambda step: 0.1,
    )
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((2, size, size, 3), jnp.float32)
    state = create_train_state(model, optimizer, rng, sample, use_ema=False)

    if family == "imagenet":
        from fast_autoaugment_tpu.ops.preprocess_imagenet import imagenet_train_batch

        augment_fn = lambda images, pol, key: imagenet_train_batch(  # noqa: E731
            images, key, pol, cutout_length=0
        )
    else:
        augment_fn = None  # default CIFAR stack, cutout 16
    train_step = make_train_step(
        model, optimizer, num_classes=num_classes, cutout_length=16,
        use_policy=True, augment_fn=augment_fn,
    )

    host = np.random.default_rng(0)
    images = host.integers(0, 256, (global_batch, size, size, 3), dtype=np.uint8)
    labels = host.integers(0, num_classes, (global_batch,), np.int32).astype(np.int32)
    policy = jnp.asarray(policy_to_tensor(load_policy(archive)))
    batch_sharded = shard_batch(mesh, {"x": images, "y": labels})

    t0 = time.perf_counter()
    step_exec = train_step.lower(
        state, batch_sharded["x"], batch_sharded["y"], policy, rng
    ).compile()
    compile_s = time.perf_counter() - t0
    for _ in range(warmup):
        state, _ = step_exec(state, batch_sharded["x"], batch_sharded["y"], policy, rng)
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, _ = step_exec(state, batch_sharded["x"], batch_sharded["y"], policy, rng)
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0

    ips = steps * global_batch / dt / mesh.size
    flops = _step_flops(step_exec)
    peak = _chip_peak_flops(jax.devices()[0])
    mfu = round(flops * (steps / dt) / peak, 4) if flops and peak else None
    from bench import watchdog_stamp  # hang-vs-straggler provenance

    return {
        "model": name, "family": family, "batch_per_device": batch,
        "image_size": size, "images_per_sec_per_chip": round(ips, 1),
        "mfu": mfu, "step_flops": flops, "compile_s": round(compile_s, 1),
        "devices": mesh.size,
        "watchdog": watchdog_stamp([dt / steps], label=name),
    }


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--models", default=",".join(ZOO))
    p.add_argument("--steps", type=int, default=15)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    from bench import (  # load provenance (bench.py)
        host_contention_stamp,
        refuse_or_flag_contention,
        telemetry_stamp,
    )
    from fast_autoaugment_tpu.core.compilecache import configure_compile_cache
    from fast_autoaugment_tpu.parallel.mesh import device_stamp

    contention = refuse_or_flag_contention(host_contention_stamp())
    configure_compile_cache()

    rows = []
    for name in args.models.split(","):
        name = name.strip()
        if name not in ZOO:
            print(f"[bench_models] unknown model {name!r}; skipping", file=sys.stderr)
            continue
        print(f"[bench_models] {name}: compiling + measuring...", file=sys.stderr)
        try:
            row = bench_one(name, args.steps, args.warmup)
        except Exception as e:  # noqa: BLE001 — keep sweeping on OOM etc.
            print(f"[bench_models] {name} FAILED: {e}", file=sys.stderr)
            row = {"model": name, "error": str(e).splitlines()[0][:200]}
        row.update(device_stamp())
        # unified provenance block (bench.telemetry_stamp) — the
        # per-model watchdog stamp bench_one computed rides through
        row.update(telemetry_stamp(contention=contention,
                                   watchdog=row.get("watchdog")))
        rows.append(row)
        print(json.dumps(row), flush=True)

    if args.out:
        lines = [
            "| model | family | batch | img/s/chip | MFU | compile (s) |",
            "|---|---|---|---|---|---|",
        ]
        for r in rows:
            if "error" in r:
                lines.append(f"| {r['model']} | — | — | FAILED | — | — |")
            else:
                lines.append(
                    f"| {r['model']} | {r['family']} | {r['batch_per_device']} "
                    f"| {r['images_per_sec_per_chip']} | {r['mfu']} "
                    f"| {r['compile_s']} |"
                )
        with open(args.out, "a") as fh:
            fh.write("\n".join(lines) + "\n")
    return rows


if __name__ == "__main__":
    # the sweep keeps going past a failed family so the table is whole,
    # but a failed row fails the run
    raise SystemExit(1 if any("error" in r for r in main()) else 0)
