"""Host-feed throughput benchmark: native C++ loader vs PIL, and the
prefetch-depth sweep (VERDICT round 1, next-step 6).

Measures, on a directory of real JPEGs (generated on the fly if absent):

1. decode+crop+resize images/sec — native libjpeg thread-pool loader
   (``native/faa_loader.cpp``) vs the PIL fallback, batch after batch;
2. end-to-end `train_batches` + `prefetch(depth)` feed rate at several
   depths — the rate at which the host can actually hand batches to the
   device layer (reference baseline: 8 torch DataLoader workers per GPU,
   reference ``data.py:214-224``).

    python tools/bench_loader.py --n 512 --size 320 --target 224 \
        --report docs/loader_bench.md
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_jpegs(root: str, n: int, size: int) -> list[str]:
    """Synthesize photographic-ish JPEGs (smooth gradients + texture so
    entropy, and thus decode cost, is realistic)."""
    import PIL.Image

    os.makedirs(root, exist_ok=True)
    paths = []
    rng = np.random.default_rng(0)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    for i in range(n):
        base = np.stack([
            127 + 120 * np.sin(2 * np.pi * (xx * rng.uniform(1, 3) + rng.uniform())),
            127 + 120 * np.cos(2 * np.pi * (yy * rng.uniform(1, 3) + rng.uniform())),
            127 + 120 * np.sin(2 * np.pi * ((xx + yy) * rng.uniform(1, 2))),
        ], axis=-1)
        noise = rng.normal(0, 20, (size, size, 3))
        img = np.clip(base + noise, 0, 255).astype(np.uint8)
        p = os.path.join(root, f"img_{i:05d}.jpg")
        PIL.Image.fromarray(img).save(p, quality=90)
        paths.append(p)
    return paths


def bench_decoder(paths, target: int, batch: int, use_native: bool,
                  threads: int | None = None) -> float:
    """images/sec for full-frame decode+resize over all paths."""
    from fast_autoaugment_tpu.data import native_loader

    boxes = None  # full-frame
    t0 = time.perf_counter()
    n = 0
    for s in range(0, len(paths), batch):
        chunk = paths[s:s + batch]
        if use_native:
            full = np.array(
                [[0, 0, w, h] for w, h in
                 (native_loader.image_size(p) for p in chunk)], np.float32)
            out, failures = native_loader.decode_resize_batch(
                chunk, target, full, threads=threads)
            assert failures == 0
        else:
            import PIL.Image

            out = np.stack([
                np.asarray(
                    PIL.Image.open(p).convert("RGB")
                    .resize((target, target), PIL.Image.BICUBIC), np.uint8)
                for p in chunk
            ])
        n += len(chunk)
    return n / (time.perf_counter() - t0)


def bench_feed(paths, target: int, batch: int, depth: int, steps: int) -> float:
    """images/sec of the full train feed path (lazy dataset -> boxed
    decode -> prefetch queue), consumed as fast as possible."""
    from fast_autoaugment_tpu.data.datasets import ArrayDataset
    from fast_autoaugment_tpu.data.pipeline import SizeCache, prefetch, train_batches

    ds = ArrayDataset(np.asarray(paths, object),
                      np.zeros(len(paths), np.int32), 10, lazy=True)
    box = lambda rng, w, h: (0, 0, w, h)  # noqa: E731
    cache = SizeCache()
    it = prefetch(
        train_batches(ds, None, batch, epoch=1, box_fn=box, imgsize=target,
                      size_cache=cache),
        depth=depth,
    )
    n = 0
    t0 = time.perf_counter()
    for images, _labels in it:
        n += len(images)
        if n >= steps * batch:
            break
    return n / (time.perf_counter() - t0)


def bench_gather(n_examples=4096, img=32, batch=256, iters=30) -> dict:
    """Host-gather vs device-gather per-batch feed latency.

    The two ways a train step gets its batch from an eager dataset:

    - host: numpy fancy-index into the in-RAM array + ``device_put``
      onto the mesh per step (today's `train_batches` + shard path);
    - device: the array resident in HBM once (`DeviceCache`), a jitted
      index gather per step, only the int32 indices crossing the host
      boundary (`--device-cache`; docs/BENCHMARKS.md "Step dispatch &
      device cache").

    Emitted as one JSON line so the two feed paths are comparable next
    to the decode/prefetch numbers above — this is the in-memory
    (CIFAR) analog of the lazy-decode feed this tool historically
    benches.
    """
    import jax
    import jax.numpy as jnp

    from fast_autoaugment_tpu.data.datasets import ArrayDataset
    from fast_autoaugment_tpu.data.pipeline import DeviceCache
    from fast_autoaugment_tpu.parallel.mesh import (
        make_mesh,
        place_index_matrix,
        shard_batch,
    )

    rng = np.random.default_rng(0)
    ds = ArrayDataset(
        rng.integers(0, 256, (n_examples, img, img, 3), dtype=np.uint8),
        rng.integers(0, 10, (n_examples,), np.int32), 10)
    mesh = make_mesh()
    idx_all = [rng.permutation(n_examples)[:batch] for _ in range(iters)]

    def host_once(idx):
        b = shard_batch(mesh, {"x": ds.images[idx], "y": ds.labels[idx]})
        jax.block_until_ready(b["x"])
        return b

    cache = DeviceCache(ds, mesh)
    gather = jax.jit(lambda xs, ys, i: (jnp.take(xs, i, axis=0),
                                        jnp.take(ys, i, axis=0)))

    def device_once(idx):
        x, y = gather(cache.images, cache.labels,
                      place_index_matrix(mesh, idx))
        jax.block_until_ready(x)
        return x

    host_once(idx_all[0])  # warm any layout/transfer paths
    device_once(idx_all[0])  # compile outside the timed loop
    t0 = time.perf_counter()
    for idx in idx_all:
        host_once(idx)
    host_ms = (time.perf_counter() - t0) / iters * 1e3
    t0 = time.perf_counter()
    for idx in idx_all:
        device_once(idx)
    device_ms = (time.perf_counter() - t0) / iters * 1e3
    return {
        "metric": "feed_gather_ms_per_batch",
        "host_gather_device_put_ms": round(host_ms, 3),
        "device_resident_gather_ms": round(device_ms, 3),
        "speedup_device_vs_host": round(host_ms / device_ms, 2)
        if device_ms else None,
        "probe": {"n_examples": n_examples, "image": img, "batch": batch,
                  "iters": iters, "devices": mesh.size},
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dir", default="/tmp/faa_loader_bench")
    p.add_argument("--n", type=int, default=512)
    p.add_argument("--size", type=int, default=320, help="source JPEG side")
    p.add_argument("--target", type=int, default=224)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--depths", default="1,2,4,8")
    p.add_argument("--threads-sweep", default=None,
                   help="comma list (e.g. 1,2,4,8,16): additionally bench "
                        "the native decoder's thread-pool scaling — the "
                        "measurement that justifies (or not) the C++ pool "
                        "on multi-core TPU-VM hosts.  On this 1-core "
                        "container the curve is flat by construction; the "
                        "claim stays 'unproven at scale' until run on a "
                        "real multi-core host (docs/loader_bench.md)")
    p.add_argument("--report", default=None)
    args = p.parse_args(argv)

    # loadavg/process provenance, shared with bench.py;
    # FAA_BENCH_REQUIRE_QUIET=1 refuses on a busy host
    import json

    from bench import (
        host_contention_stamp,
        refuse_or_flag_contention,
        telemetry_stamp,
    )

    contention = refuse_or_flag_contention(host_contention_stamp())
    print(f"contention: {json.dumps(contention)}")
    from fast_autoaugment_tpu.core.compilecache import configure_compile_cache
    from fast_autoaugment_tpu.parallel.mesh import device_stamp

    configure_compile_cache()

    from fast_autoaugment_tpu.data import native_loader

    existing = sorted(
        os.path.join(args.dir, f) for f in os.listdir(args.dir)
        if f.endswith(".jpg")
    ) if os.path.isdir(args.dir) else []
    paths = existing if len(existing) >= args.n else make_jpegs(
        args.dir, args.n, args.size)

    rows = {}
    rows["pil"] = bench_decoder(paths, args.target, args.batch, use_native=False)
    print(f"PIL decode+resize:    {rows['pil']:8.1f} img/s")
    if native_loader.available():
        rows["native"] = bench_decoder(paths, args.target, args.batch, use_native=True)
        print(f"native decode+resize: {rows['native']:8.1f} img/s "
              f"({rows['native'] / rows['pil']:.1f}x PIL)")
    else:
        print("native loader not built (make -C native)")

    thread_rows = {}
    if args.threads_sweep and native_loader.available():
        sweep = [int(t) for t in args.threads_sweep.split(",")]
        for th in sweep:
            thread_rows[th] = bench_decoder(paths, args.target, args.batch,
                                            use_native=True, threads=th)
        base_th = 1 if 1 in thread_rows else min(thread_rows)
        base = thread_rows[base_th]
        for th in sweep:
            print(f"native threads={th}: {thread_rows[th]:8.1f} img/s "
                  f"({thread_rows[th] / base:.2f}x vs {base_th} thread)")

    depth_rows = {}
    steps = max(2, len(paths) // args.batch - 1)
    for depth in [int(d) for d in args.depths.split(",")]:
        r = bench_feed(paths, args.target, args.batch, depth, steps)
        depth_rows[depth] = r
        print(f"feed depth={depth}:  {r:8.1f} img/s")

    # eager-dataset feed paths: host fancy-gather + device_put vs the
    # device-resident cache gather, one comparable JSON line
    gather = bench_gather()
    # unified provenance block (bench.telemetry_stamp): schema_version
    # + contention + compile cache + registry counters in one schema
    gather.update(telemetry_stamp(contention=contention))
    gather.update(device_stamp())  # bench_gather device_puts batches
    print(json.dumps(gather))

    if args.report:
        with open(args.report, "w") as fh:
            fh.write(
                "# Host-feed throughput\n\n"
                f"{args.n} JPEGs {args.size}px -> {args.target}px, batch "
                f"{args.batch} (this machine; see docs/BENCHMARKS.md for "
                "context).\n\n"
                "| path | img/s |\n|---|---|\n"
                + f"| PIL decode+resize | {rows['pil']:.1f} |\n"
                + (f"| native decode+resize | {rows['native']:.1f} |\n"
                   if "native" in rows else "")
                + "".join(
                    f"| feed (prefetch depth {d}) | {r:.1f} |\n"
                    for d, r in depth_rows.items()
                )
                + "".join(
                    f"| native decoder, {t} threads | {r:.1f} |\n"
                    for t, r in thread_rows.items()
                )
                + (f"\nHost CPU count: {os.cpu_count()} — thread scaling "
                   "measured on fewer cores than threads is queueing, not "
                   "parallelism.\n" if thread_rows else "")
            )
        print(f"wrote {args.report}")
    return rows, depth_rows, thread_rows


if __name__ == "__main__":
    main()
