"""Serial vs async phase-2 scheduling: dispatch-gap histograms + wall.

The async actor/learner pipeline (``search/pipeline.py``,
``--async-pipeline on``) exists to drive the idle time BETWEEN device
dispatches to ~0: in the serial scheduler every round pays host-side
TPE math (``tools/bench_tpe.py`` measures ~3-5 ms/trial on the real
30-D space), policy decode + tensor upload, and an fsync'd trial-log
persist while the device waits.  This bench runs the SAME seeded search
twice — serial (``FAA_PIPELINE_TRACE=1`` arms the dispatch trace on the
historical scheduler) and async — and reports, per arm:

- the dispatch-gap histogram (p50/p99 inter-dispatch idle, log-bucket
  counts) and the device busy fraction during phase 2,
- end-to-end ``search_secs`` (phase-2 wall) and the async speedup,
- the host ask/tell latency rows for the configured trial batch (the
  overlap headroom the pipeline hides), and
- contention + compile-cache stamps (every number on this host is a
  1-core CPU plumbing number; the cache keeps the first dispatch from
  reading as a 7 s "busy" window in both arms).

Phase 1 is trained once in a warmup run and its fold checkpoint is
copied into every arm's save dir, so the comparison is pure phase-2
scheduling.  Arms run as PAIRED ALTERNATING rounds (serial,async /
async,serial / ...) and the report takes per-arm MEDIANS — the same
1-core A/B discipline as ``tools/bench_router.py``: fixed-order arms
on this host read the allocator's ±2-3% slow drift as signal, and the
alternation + medians cancel it.  ``single_core_caveat`` is stamped in
the JSON line: every wall ratio here is a plumbing number (all threads
share one core); the transferable evidence is the gap histogram.
Honors ``FAA_BENCH_REQUIRE_QUIET=1`` (refuses on a contended host,
exit 3).

    python tools/bench_pipeline.py --num-search 32 --trial-batch 4
    make bench-pipeline
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _conf(batch: int, epoch: int):
    from fast_autoaugment_tpu.core.config import Config

    return Config({
        "model": {"type": "wresnet10_1"},
        "dataset": "synthetic",
        "aug": "default",
        "cutout": 8,
        "batch": batch,
        "epoch": epoch,
        "lr": 0.05,
        "lr_schedule": {"type": "cosine"},
        "optimizer": {"type": "sgd", "decay": 1e-4, "clip": 5.0,
                      "momentum": 0.9, "nesterov": True},
    })


_CKPT_COPY_SUFFIXES = ("", ".meta.json")


def _copy_fold_ckpt(src_dir: str, dst_dir: str, name: str) -> None:
    os.makedirs(dst_dir, exist_ok=True)
    for suffix in _CKPT_COPY_SUFFIXES:
        src = os.path.join(src_dir, name + suffix)
        if os.path.exists(src):
            shutil.copy2(src, os.path.join(dst_dir, name + suffix))


def _median(xs):
    xs = sorted(x for x in xs if x is not None)
    n = len(xs)
    if n == 0:
        return None
    mid = n // 2
    return xs[mid] if n % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def run_pipeline_bench(args, workdir: str) -> dict:
    import jax

    from fast_autoaugment_tpu.search.driver import (
        _fold_ckpt_path,
        search_policies,
    )

    conf = _conf(args.batch, 1)
    common = dict(
        dataroot=workdir, cv_num=1, cv_ratio=args.cv_ratio,
        num_policy=args.num_policy, num_op=args.num_op,
        num_top=5, trial_batch=args.trial_batch, seed=args.seed,
    )
    devices = jax.device_count()

    # warmup: train the shared phase-1 fold + fill the compile cache
    # (one round of trials compiles the TTA step into the cache, so no
    # measured round's first dispatch is a compile window)
    warm_dir = os.path.join(workdir, "warm")
    search_policies(conf, save_dir=warm_dir,
                    num_search=max(1, args.trial_batch), **common)
    ckpt_name = os.path.basename(_fold_ckpt_path(warm_dir, conf, 0,
                                                 args.cv_ratio))

    def _one_arm(tag: str, async_on: bool) -> dict:
        save_dir = os.path.join(workdir, tag)
        _copy_fold_ckpt(warm_dir, save_dir, ckpt_name)
        if not async_on:
            os.environ["FAA_PIPELINE_TRACE"] = "1"
        try:
            t0 = time.time()
            result = search_policies(
                conf, save_dir=save_dir, num_search=args.num_search,
                async_pipeline="on" if async_on else "off",
                pipeline_actors=args.actors,
                pipeline_queue_depth=args.queue_depth, **common)
            wall = time.time() - t0
        finally:
            os.environ.pop("FAA_PIPELINE_TRACE", None)
        pipe = result.get("pipeline") or {}
        gaps = pipe.get("dispatch_gaps") or {}
        return {
            "mode": "async" if async_on else "serial",
            "search_secs": round(wall, 3),
            "phase2_secs": round(
                result["device_secs_phase2"] / max(1, devices), 3),
            "device_busy_frac": pipe.get("device_busy_frac"),
            "gap_p50_ms": gaps.get("gap_p50_ms"),
            "gap_p99_ms": gaps.get("gap_p99_ms"),
            "gap_total_secs": gaps.get("gap_total_secs"),
            "num_gaps": gaps.get("num_gaps"),
            "num_dispatches": gaps.get("num_dispatches"),
            "tell_reorders": pipe.get("tell_reorders"),
            "num_sub_policies": result.get("num_sub_policies"),
        }

    # paired alternating arm order + per-arm medians: the 1-core A/B
    # discipline (bench_router.py) — fixed-order arms read ±2-3%
    # allocator drift as signal on this host
    rounds: list[dict] = []
    for i in range(max(1, args.pairs)):
        order = (("serial", "async") if i % 2 == 0
                 else ("async", "serial"))
        for name in order:
            rounds.append(_one_arm(f"{name}{i}", name == "async"))

    arms = {}
    for name in ("serial", "async"):
        rows = [r for r in rounds if r["mode"] == name]
        arms[name] = {
            "rounds": len(rows),
            "phase2_secs_median": _median([r["phase2_secs"] for r in rows]),
            "search_secs_median": _median([r["search_secs"] for r in rows]),
            "device_busy_frac_median": _median(
                [r["device_busy_frac"] for r in rows]),
            "gap_p50_ms_median": _median([r["gap_p50_ms"] for r in rows]),
            "gap_p99_ms_median": _median([r["gap_p99_ms"] for r in rows]),
            "gap_total_secs_median": _median(
                [r["gap_total_secs"] for r in rows]),
            "num_dispatches": rows[-1]["num_dispatches"],
            "tell_reorders_total": sum(r["tell_reorders"] or 0
                                       for r in rows),
        }
    arms["async"].update(actors=args.actors, queue_depth=args.queue_depth)
    s_med = arms["serial"]["phase2_secs_median"]
    a_med = arms["async"]["phase2_secs_median"]
    speedup = (s_med / a_med) if (s_med and a_med) else None
    return {
        "bench": "pipeline",
        "devices": devices,
        "num_search": args.num_search,
        "trial_batch": args.trial_batch,
        "num_policy": args.num_policy,
        "num_op": args.num_op,
        "pairs": args.pairs,
        "serial": arms["serial"],
        "async": arms["async"],
        "rounds": rounds,
        "phase2_speedup": round(speedup, 3) if speedup else None,
        # every process here shares ONE core: wall ratios measure
        # scheduling plumbing, not device overlap — the transferable
        # evidence is the gap histogram (docs/BENCHMARKS.md)
        "single_core_caveat": True,
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-search", type=int, default=24)
    p.add_argument("--trial-batch", type=int, default=4)
    p.add_argument("--num-policy", type=int, default=5)
    p.add_argument("--num-op", type=int, default=2)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--cv-ratio", type=float, default=0.4)
    p.add_argument("--actors", type=int, default=1)
    p.add_argument("--queue-depth", type=int, default=1)
    p.add_argument("--pairs", type=int, default=2,
                   help="paired alternating (serial,async) rounds; "
                        "per-arm medians reported")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--workdir", default=None,
                   help="scratch dir (default: a fresh tempdir, removed "
                        "on success)")
    p.add_argument("--out", default=None, help="also write the JSON line here")
    args = p.parse_args(argv)

    from bench import (
        host_contention_stamp,
        refuse_or_flag_contention,
        telemetry_stamp,
    )
    from bench_tpe import bench_ask_tell_latency

    contention = refuse_or_flag_contention(host_contention_stamp())
    print(f"contention: {json.dumps(contention)}")

    workdir = args.workdir or tempfile.mkdtemp(prefix="faa_bench_pipeline_")
    made_temp = args.workdir is None
    record = run_pipeline_bench(args, workdir)
    # unified provenance block (bench.telemetry_stamp): contention +
    # compile cache + registry counters in the shared schema
    record.update(telemetry_stamp(contention=contention))
    from fast_autoaugment_tpu.parallel.mesh import device_stamp

    record.update(device_stamp())
    # the overlap headroom the async arm hides: host ask/tell latency
    # at this bench's trial batch (same JSON line, per the bench_tpe
    # citation contract)
    record["tpe_latency"] = bench_ask_tell_latency(
        ks=(args.trial_batch,), reps=20)

    for arm in ("serial", "async"):
        a = record[arm]
        print(f"{arm} (medians over {a['rounds']} alternating rounds): "
              f"phase2 {a['phase2_secs_median']}s, busy_frac "
              f"{a['device_busy_frac_median']}, gap p50 "
              f"{a['gap_p50_ms_median']}ms p99 {a['gap_p99_ms_median']}ms "
              f"({a['num_dispatches']} dispatches/round)")
    print(f"phase2_speedup (median/median): {record['phase2_speedup']}x "
          "[single_core_caveat: wall on this host is plumbing, the gap "
          "histogram is the evidence]")
    busy = record["async"]["device_busy_frac_median"] or 0.0
    ok = busy >= 0.9 or (record["phase2_speedup"] or 0.0) >= 1.5
    print("acceptance (median busy_frac >= 0.9 during phase 2 OR >= 1.5x "
          f"phase-2 speedup): {'PASS' if ok else 'FAIL'}")

    line = json.dumps(record)
    print(line)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    if made_temp:
        shutil.rmtree(workdir, ignore_errors=True)
    return record


if __name__ == "__main__":
    main()
