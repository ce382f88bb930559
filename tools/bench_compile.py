"""Cold/warm compile-tax benchmark: two processes, one shared cache
(``make bench-compile``).

The acceptance measurement for the persistent compile cache
(docs/BENCHMARKS.md "Compile cost & cache"): the SAME child workload —
build the real jitted train step (``make_train_step``) for ``--model``
and run it to completion once, i.e. time-to-first-train-step — runs in
two fresh processes, one after the other, sharing the persistent
compile cache (``JAX_COMPILATION_CACHE_DIR`` or the fixed in-checkout
default, ``core/compilecache.py``).  The first process pays whatever the
cache does not hold yet (a full XLA compile when the cache is empty —
pass ``--cache-dir`` an empty directory for a guaranteed-cold first
process); the second deserializes the executables.  One JSON line
stamps both processes' ``compile_cache`` blocks (the warm one proves
``hits > 0, misses == 0``), the first-step walls, the speedup, and the
device the children ran on.  The parent never touches JAX and the
children run one at a time, so the chip has one owner.

    python tools/bench_compile.py [--model wresnet40_2] [--batch 8]
        [--cache-dir DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def child_main(args) -> int:
    """One process's workload: build the real train step, reach the
    first completed step, print the evidence as one JSON line."""
    t0 = time.perf_counter()
    from fast_autoaugment_tpu.core.compilecache import (
        compile_cache_stats,
        configure_compile_cache,
    )

    from fast_autoaugment_tpu.parallel.mesh import device_stamp

    configure_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fast_autoaugment_tpu.models import get_model
    from fast_autoaugment_tpu.ops.optim import build_optimizer
    from fast_autoaugment_tpu.policies.archive import (
        load_policy,
        policy_to_tensor,
    )
    from fast_autoaugment_tpu.train.steps import (
        create_train_state,
        make_train_step,
    )

    model = get_model({"type": args.model}, 10)
    optimizer = build_optimizer(
        {"type": "sgd", "decay": 2e-4, "clip": 5.0, "momentum": 0.9,
         "nesterov": True}, lambda s: 0.05)
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((2, args.image, args.image, 3), jnp.float32)
    state = create_train_state(model, optimizer, rng, sample, use_ema=False)
    step = make_train_step(model, optimizer, num_classes=10,
                           cutout_length=16, use_policy=True)
    policy = jnp.asarray(policy_to_tensor(load_policy("fa_reduced_cifar10")))
    host = np.random.default_rng(0)
    x = jnp.asarray(host.integers(0, 256,
                                  (args.batch, args.image, args.image, 3),
                                  dtype=np.uint8))
    y = jnp.asarray(host.integers(0, 10, (args.batch,), np.int32))
    # phase split: tracing/lowering is Python work NO cache can skip;
    # compile() is the XLA tax the persistent cache kills
    # (warm = executable deserialization); exec is the step itself
    t_step = time.perf_counter()
    lowered = step.lower(state, x, y, policy, rng)
    t_lower = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter()
    state, metrics = compiled(state, x, y, policy, rng)
    jax.block_until_ready(state.params)
    now = time.perf_counter()
    print(json.dumps({
        "first_step_sec": round(now - t_step, 3),
        "trace_lower_sec": round(t_lower - t_step, 3),
        "compile_sec": round(t_compile - t_lower, 3),
        "exec_sec": round(now - t_compile, 3),
        "proc_to_first_step_sec": round(now - t0, 3),
        "compile_cache": compile_cache_stats(),
        **device_stamp(),
    }))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default=os.environ.get(
        "FAA_BENCH_CC_MODEL", "wresnet40_2"))
    p.add_argument("--batch", type=int, default=int(os.environ.get(
        "FAA_BENCH_CC_BATCH", 8)))
    p.add_argument("--image", type=int, default=32)
    p.add_argument("--cache-dir", default=None,
                   help="place the children's cache here (exported to "
                        "them as JAX_COMPILATION_CACHE_DIR); default: "
                        "wherever the environment already places it")
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.child:
        return child_main(args)

    from bench import (
        host_contention_stamp,
        refuse_or_flag_contention,
        telemetry_stamp,
    )

    contention = refuse_or_flag_contention(host_contention_stamp())

    def run(tag: str) -> dict:
        env = dict(os.environ)
        if args.cache_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = os.path.abspath(args.cache_dir)
        cmd = [sys.executable, os.path.abspath(__file__), "--child",
               "--model", args.model, "--batch", str(args.batch),
               "--image", str(args.image)]
        t0 = time.perf_counter()
        r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=1800)
        wall = time.perf_counter() - t0
        if r.returncode != 0:
            raise RuntimeError(
                f"{tag} child failed rc={r.returncode}: {r.stderr[-1500:]}")
        rec = json.loads(r.stdout.strip().splitlines()[-1])
        rec["process_wall_sec"] = round(wall, 3)
        print(f"[bench_compile] {tag}: compile={rec['compile_sec']}s "
              f"(trace {rec['trace_lower_sec']}s, exec {rec['exec_sec']}s) "
              f"first_step={rec['first_step_sec']}s "
              f"to-first-step={rec['proc_to_first_step_sec']}s "
              f"(hits={rec['compile_cache']['hits']} "
              f"misses={rec['compile_cache']['misses']})", file=sys.stderr)
        return rec

    cold = run("cold")
    warm = run("warm")
    out = {
        # the headline is the COMPILE tax: warm = executable
        # deserialization, the piece the persistent cache kills.
        # Tracing/lowering is Python work no cache can skip; the
        # per-phase walls ride in cold/warm.
        "metric": "warm_process_compile_sec",
        "value": warm["compile_sec"],
        "unit": "seconds",
        "model": args.model,
        "batch": args.batch,
        "cache_dir": warm["compile_cache"]["dir"],
        "cold": cold,
        "warm": warm,
        "speedup_compile": (
            round(cold["compile_sec"] / warm["compile_sec"], 1)
            if warm["compile_sec"] else None),
        "speedup_first_step": (
            round(cold["first_step_sec"] / warm["first_step_sec"], 1)
            if warm["first_step_sec"] else None),
        # the acceptance bits, spelled out: the warm process observed
        # cache hits and zero misses, and its compile took seconds
        "warm_hits": warm["compile_cache"]["hits"],
        "warm_misses": warm["compile_cache"]["misses"],
        **{k: warm[k] for k in ("platform", "device_kind", "device_count")},
        # unified provenance block (bench.telemetry_stamp) — the
        # supervisor process compiles nothing, so its own compile_cache
        # block is empty; the cold/warm children carry the real stamps
        **telemetry_stamp(contention=contention),
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
