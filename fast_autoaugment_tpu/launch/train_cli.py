"""Training CLI — the ``python FastAutoAugment/train.py -c conf.yaml``
equivalent (reference ``train.py:325-356``).

    python -m fast_autoaugment_tpu.launch.train_cli -c confs/wresnet40x2_cifar.yaml \
        --dataroot /data --save ckpt/wrn.msgpack --tag wrn40x2

Multi-host: run the SAME command on every host (JAX multi-controller;
``--coordinator host0:1234 --num-hosts N --host-id k`` or TPU-pod
auto-detection) — there is no torch.distributed.launch equivalent to
wrangle, which is the point.
"""

from __future__ import annotations

import argparse
import json
import time

from fast_autoaugment_tpu.core.config import load_config
from fast_autoaugment_tpu.core.resilience import (
    PREEMPTED_EXIT_CODE,
    DispatchHungError,
    PreemptedError,
    install_signal_handlers,
)
from fast_autoaugment_tpu.train.trainer import train_and_eval
from fast_autoaugment_tpu.utils.logging import add_filehandler, get_logger

logger = get_logger("faa_tpu.train_cli")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="fast-autoaugment-tpu trainer")
    p.add_argument("-c", "--conf", required=True, help="YAML preset (confs/*.yaml)")
    p.add_argument("--dataroot", default="./data")
    p.add_argument("--save", default="", help="checkpoint path (.msgpack)")
    p.add_argument("--tag", default="")
    p.add_argument("--cv-ratio", type=float, default=0.0)
    p.add_argument("--cv", type=int, default=0, help="CV resample index")
    p.add_argument("--only-eval", action="store_true")
    p.add_argument("--evaluation-interval", type=int, default=5)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--aug-dispatch", default="exact",
                   choices=("exact", "grouped"),
                   help="policy-application kernel: 'exact' (default) is "
                        "the per-image vmapped-switch path bit-for-bit; "
                        "'grouped' keeps op dispatch scalar (one lax.switch "
                        "branch executes; stratified per-chunk sub-policy "
                        "draws — docs/PARITY.md 'Augmentation dispatch')")
    p.add_argument("--aug-groups", type=int, default=8,
                   help="chunks per batch for --aug-dispatch grouped")
    p.add_argument("--device-cache", default="auto",
                   choices=("auto", "on", "off"),
                   help="device-resident data path: upload the eager "
                        "dataset to HBM once (sharded over the mesh data "
                        "axis) and gather batches by index INSIDE the "
                        "compiled step — no per-step host image copy.  "
                        "'auto' (default) enables it for in-memory "
                        "datasets on a single host (bit-for-bit with the "
                        "host feed at --steps-per-dispatch 1); lazy "
                        "ImageNet datasets keep the prefetch path; 'on' "
                        "errors where auto would fall back "
                        "(docs/PARITY.md 'Step dispatch & device "
                        "cache')")
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="fuse N train steps into ONE dispatch (lax.scan "
                        "over the device cache; needs --device-cache "
                        "auto/on).  1 (default) = the historical "
                        "one-dispatch-per-step loop bit-for-bit; N>1 "
                        "deviates by the documented ~1 f32 ULP/step scan "
                        "bound and amortizes per-dispatch host overhead")
    p.add_argument("--divergence-retries", type=int, default=0,
                   help="on a NaN/inf epoch loss, roll back to the newest "
                        "intact checkpoint and replay with retry-folded "
                        "randomness up to R times before re-raising.  0 "
                        "(default) = the historical immediate raise "
                        "(docs/RESILIENCE.md)")
    p.add_argument("--ckpt-keep", type=int, default=2,
                   help="rollback-chain depth: the live checkpoint plus "
                        "N-1 predecessors (path, path.prev, ...).  Restore "
                        "walks to the newest INTACT link (sha256-verified), "
                        "so one torn/corrupt file costs an epoch, not the "
                        "run.  1 = the pre-chain overwrite-in-place")
    p.add_argument("--ckpt-every-dispatch", type=int, default=0,
                   help="checkpoint every M dispatch chunks MID-epoch "
                        "(resumable bit-identically "
                        "from the exact dispatch boundary).  0 (default) = "
                        "checkpoint at evaluation epochs only")
    p.add_argument("--watchdog", default="off",
                   help="dispatch watchdog {off,auto,SECONDS}: run every "
                        "train dispatch / eval replay under a deadline "
                        "(auto = EMA of observed dispatch wall times with "
                        "a generous first-call compile allowance) and "
                        "treat expiry as a HUNG dispatch — exit 77 so the "
                        "supervisor relaunches and the rerun resumes from "
                        "the newest checkpoint-chain link (pair with "
                        "--ckpt-every-dispatch to bound replayed work).  "
                        "'off' (default) keeps the historical async "
                        "dispatch bit-for-bit (docs/RESILIENCE.md)")
    p.add_argument("--telemetry", default="off", metavar="{off,DIR}",
                   help="flight-recorder journal (core/telemetry.py): "
                        "typed dispatch/compile/checkpoint events under "
                        "DIR with rotation-bounded size, renderable as a "
                        "Chrome trace via tools/trace_export.py.  'off' "
                        "(default, bit-for-bit — no journal I/O) still "
                        "honors an inherited FAA_TELEMETRY")
    p.add_argument("--telemetry-port", type=int, default=0,
                   help="serve GET /metrics (Prometheus text exposition "
                        "of the in-memory telemetry registry, read-only) "
                        "while training runs.  0 = off")
    p.add_argument("--coordinator", default=None, help="host0 addr for multi-host")
    p.add_argument("--num-hosts", type=int, default=None)
    p.add_argument("--host-id", type=int, default=None)
    p.add_argument("override", nargs="*", help="dotted conf overrides key=value")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.coordinator:
        from fast_autoaugment_tpu.parallel.mesh import distributed_init

        distributed_init(args.coordinator, args.num_hosts, args.host_id)

    conf = load_config(args.conf, overrides=args.override)
    if args.tag:
        add_filehandler(logger, f"train_{args.tag}.log")
    if args.only_eval and not args.save:
        logger.warning("--only-eval requires --save (reference train.py:337)")
        raise SystemExit(1)

    # SIGTERM/SIGUSR1 -> graceful preemption: checkpoint at the next
    # safe boundary, exit 77 ("resume me" — docs/RESILIENCE.md)
    install_signal_handlers()
    from fast_autoaugment_tpu.core import telemetry

    telemetry.configure_telemetry(args.telemetry)
    metrics_httpd = None
    if args.telemetry_port:
        metrics_httpd, _port = telemetry.start_metrics_server(
            args.telemetry_port)
    t0 = time.time()
    try:
        result = train_and_eval(
            conf,
            args.dataroot,
            test_ratio=args.cv_ratio,
            cv_fold=args.cv,
            save_path=args.save or None,
            only_eval=args.only_eval,
            evaluation_interval=args.evaluation_interval,
            metric="last",
            seed=args.seed,
            aug_dispatch=args.aug_dispatch,
            aug_groups=args.aug_groups,
            device_cache=args.device_cache,
            steps_per_dispatch=args.steps_per_dispatch,
            divergence_retries=args.divergence_retries,
            ckpt_keep=args.ckpt_keep,
            checkpoint_every_dispatch=args.ckpt_every_dispatch,
            watchdog=args.watchdog,
        )
    except PreemptedError as e:
        logger.warning("preempted (%s) — exiting %d so the supervisor "
                       "resumes this run", e, PREEMPTED_EXIT_CODE)
        telemetry.emit("preempt", "train_cli", kind="preempted",
                       exit_code=PREEMPTED_EXIT_CODE)
        raise SystemExit(PREEMPTED_EXIT_CODE)
    except DispatchHungError as e:
        logger.error("dispatch HUNG (%s) — in-flight device state is "
                     "unrecoverable; exiting %d so the supervisor "
                     "relaunches and the rerun resumes from the newest "
                     "checkpoint-chain link", e, PREEMPTED_EXIT_CODE)
        telemetry.emit("preempt", "train_cli", kind="dispatch_hung",
                       label_detail=e.label, exit_code=PREEMPTED_EXIT_CODE)
        raise SystemExit(PREEMPTED_EXIT_CODE)
    finally:
        if metrics_httpd is not None:
            metrics_httpd.shutdown()
    elapsed = time.time() - t0
    cc = result.get("compile_cache") or {}
    if cc:
        # grep-stable line: the exit-77 resume e2e asserts the RESUMED
        # process reports hits here (docs/RESILIENCE.md resume cost)
        logger.info("compile cache: dir=%s hits=%d misses=%d "
                    "first_step_secs=%.3f", cc.get("dir"),
                    cc.get("hits", 0), cc.get("misses", 0),
                    cc.get("first_step_secs", 0.0))
    logger.info("done %s: %s", args.tag, json.dumps(
        {k: round(v, 5) if isinstance(v, float) else v for k, v in result.items()}))
    logger.info("elapsed: %.1f s (%.2f h)", elapsed, elapsed / 3600.0)
    return result


def _cli() -> None:
    """Script entry: the result (device stamp, steps, losses, compile-
    cache evidence) is the one line on stdout, so a supervisor such as
    ``chip_smoke.py`` can check the run from outside the process."""
    print(json.dumps(main()), flush=True)


if __name__ == "__main__":
    _cli()
