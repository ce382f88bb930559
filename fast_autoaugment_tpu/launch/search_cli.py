"""Policy-search CLI — the ``python search.py -c conf.yaml --redis ...``
equivalent (reference ``search.py:137-154``) without Ray/Redis.

    python -m fast_autoaugment_tpu.launch.search_cli -c confs/wresnet40x2_cifar.yaml \
        --dataroot /data --save-dir search_out --smoke-test

Runs phases 1+2 (K-fold no-aug pretrain, TPE TTA search) and then
phase 3 (``--num-result-per-cv`` full retrains with default vs found
policies, averaged — reference ``search.py:264-312``).
"""

from __future__ import annotations

import argparse
import math
import time

import numpy as np

from fast_autoaugment_tpu.core.config import load_config
from fast_autoaugment_tpu.core.resilience import (
    PREEMPTED_EXIT_CODE,
    DispatchHungError,
    PreemptedError,
    install_signal_handlers,
)
from fast_autoaugment_tpu.search.driver import search_policies, write_json_atomic
from fast_autoaugment_tpu.train.trainer import train_and_eval
from fast_autoaugment_tpu.utils.logging import get_logger

logger = get_logger("faa_tpu.search_cli")


def _quality_floor_arg(value: str) -> str:
    """Validate ``--fold-quality-floor`` at parse time (ADVICE r4): the
    accepted forms are 'auto', 'off'/'none', or a float literal; a typo
    fails as a CLI usage error instead of a float() traceback deep in
    the search."""
    if value.lower() in ("auto", "off", "none"):
        return value.lower()
    try:
        f = float(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected 'auto', 'off' or a float, got {value!r}")
    if not math.isfinite(f):
        # float('nan') parses but nan > 0 is False, which would
        # silently disable the gate downstream
        raise argparse.ArgumentTypeError(
            f"expected a finite float, got {value!r}")
    return value


def _watchdog_arg(value: str) -> str:
    """Validate ``--watchdog`` at parse time: 'off', 'auto', or a
    positive float deadline in seconds."""
    v = value.lower()
    if v in ("off", "auto"):
        return v
    try:
        f = float(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected 'off', 'auto' or SECONDS, got {value!r}")
    if not math.isfinite(f) or f <= 0:
        raise argparse.ArgumentTypeError(
            f"expected a positive finite deadline, got {value!r}")
    return value


def _fold_stack_arg(value: str) -> "str | int":
    """Validate ``--fold-stack`` at parse time: '0' (sequential,
    bit-for-bit the pre-stacking path), 'auto' (stack every fold that
    needs training), or an int K >= 2 (stack width cap)."""
    if value.lower() == "auto":
        return "auto"
    try:
        k = int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected 'auto' or an integer, got {value!r}")
    if k < 0:
        raise argparse.ArgumentTypeError(
            f"expected a non-negative stack width, got {value!r}")
    return k


def random_arm_skip_reason(result: dict) -> str | None:
    """Why a requested --phase3-random control arm cannot run, or None.

    The random set can legitimately come back empty — the 0.95 audit
    floor dropping every uniform draw is plausible for destructive
    random policies — but silently persisting a two-arm artifact
    defeats the three-way comparison the flag asked for (ADVICE r5,
    medium).  The caller logs the reason prominently and records it in
    the artifact as ``random_arm_skip_reason``."""
    if result.get("random_policy_set"):
        return None
    drawn = int(result.get("num_sub_policies_random_drawn") or 0)
    dropped = int(result.get("num_sub_policies_random_dropped") or 0)
    if drawn and dropped >= drawn:
        return (f"all {drawn} drawn random sub-policies were dropped by "
                "the audit")
    if drawn:
        return (f"random set empty after audit ({drawn} drawn, "
                f"{dropped} recorded dropped)")
    return ("no random policy set was drawn (search ended before the "
            "random-control step)")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="fast-autoaugment-tpu policy search")
    p.add_argument("-c", "--conf", required=True)
    p.add_argument("--dataroot", default="./data")
    p.add_argument("--save-dir", default="search_out")
    p.add_argument("--num-fold", type=int, default=5, help="K (reference cv_num=5)")
    p.add_argument("--cv-ratio", type=float, default=0.4)
    p.add_argument("--num-policy", type=int, default=5)
    p.add_argument("--num-op", type=int, default=2)
    p.add_argument("--num-search", type=int, default=200)
    p.add_argument("--topup-trials", type=int, default=0,
                   help="warm-started incremental RE-SEARCH (the control "
                        "plane's entry point, docs/CONTROL.md): extend a "
                        "completed --save-dir's per-fold trial budget by "
                        "this many trials.  Resume replays the persisted "
                        "trial log — --async-pipeline on routes it "
                        "through the PR-9 replay_trial_log ledger, so "
                        "the TPE continues exactly where the original "
                        "run left off — and only the top-up trials "
                        "dispatch; search_result.json stamps "
                        "'warm_start'.  0 (default) = the historical "
                        "budget, artifact stream untouched")
    p.add_argument("--num-top", type=int, default=10)
    p.add_argument("--async-pipeline", default="off", choices=("off", "on"),
                   help="streaming actor/learner phase-2 scheduler "
                        "(search/pipeline.py): device actor threads pull "
                        "ready-built candidate rounds from a bounded "
                        "queue while the TPE learner digests completed "
                        "results and refills proposals concurrently "
                        "(tells apply in trial-id order, so the schedule "
                        "is deterministic), and phase-2 trials on fold k "
                        "start the moment fold k's phase-1 gate clears "
                        "while later folds still train.  'off' (default) "
                        "= the historical serial driver bit-for-bit; "
                        "'on' with --pipeline-actors 1 --pipeline-queue-"
                        "depth 0 reproduces the serial trial log exactly "
                        "(search/pipeline.py's docstring has the rule)")
    p.add_argument("--pipeline-actors", type=int, default=1,
                   help="device actor threads per fold in --async-"
                        "pipeline on (each runs one monitored TTA "
                        "dispatch at a time against the shared compiled "
                        "step)")
    p.add_argument("--pipeline-queue-depth", type=int, default=1,
                   help="candidate rounds proposed AHEAD of the actors "
                        "in --async-pipeline on (the in-flight window is "
                        "actors + depth rounds; pending rounds contribute "
                        "constant-liar placeholders to the posterior).  "
                        "0 = lockstep ask-after-tell")
    p.add_argument("--trial-batch", type=int, default=1,
                   help="K concurrent TPE trials per fold, evaluated by ONE "
                        "vmapped TTA program per batch (constant-liar "
                        "proposals; the single-host answer to the "
                        "reference's 80 concurrent Ray trials, "
                        "search.py:230).  1 (default) = the sequential "
                        "scheduler, bit-for-bit")
    p.add_argument("--aug-dispatch", default="exact",
                   choices=("exact", "grouped"),
                   help="policy-application kernel for phase-2 TTA, the "
                        "sub-policy audit and phase-3 policy-on retrains. "
                        "'exact' (default) = the historical per-image "
                        "vmapped-switch path bit-for-bit (XLA executes all "
                        "19 op branches per image); 'grouped' = scalar "
                        "dispatch (one branch executes; stratified "
                        "per-chunk sub-policy draws with identical "
                        "per-image marginals — docs/PARITY.md "
                        "'Augmentation dispatch')")
    p.add_argument("--aug-groups", type=int, default=8,
                   help="chunks per batch for --aug-dispatch grouped "
                        "(each chunk shares one sub-policy draw)")
    p.add_argument("--fold-stack", default=0, type=_fold_stack_arg,
                   help="phase-1 fold stacking: train K fold models as "
                        "ONE vmapped program per step, folds sharded "
                        "onto the mesh data axis when the counts divide "
                        "(the phase-1 counterpart of --trial-batch).  "
                        "0 (default) = the sequential per-fold loop "
                        "bit-for-bit; 'auto' stacks every fold needing "
                        "training; K caps the stack width")
    p.add_argument("--device-cache", default="auto",
                   choices=("auto", "on", "off"),
                   help="device-resident data path for phase-1 fold "
                        "pretraining, gate retrains and phase-3 retrains: "
                        "upload the eager dataset once, gather batches by "
                        "index inside the compiled step.  'auto' "
                        "(default) = on for in-memory single-host "
                        "datasets, bit-for-bit at --steps-per-dispatch 1; "
                        "lazy ImageNet datasets keep the prefetch path "
                        "(docs/PARITY.md 'Step dispatch & device "
                        "cache')")
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="fuse N train steps into ONE dispatch (lax.scan "
                        "over the device cache; composes with "
                        "--fold-stack: one dispatch then advances "
                        "K folds x N steps).  1 (default) = historical "
                        "per-step dispatch bit-for-bit; N>1 deviates by "
                        "the documented ~1 f32 ULP/step scan bound")
    p.add_argument("--num-result-per-cv", type=int, default=5,
                   help="phase-3 retrains per mode (reference search.py:270)")
    p.add_argument("--until", type=int, default=3,
                   help="run phases up to this number (1, 2 or 3)")
    p.add_argument("--folds", default=None,
                   help="comma-separated fold subset for multi-host scatter")
    p.add_argument("--smoke-test", action="store_true")
    p.add_argument("--no-resume", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--fold-quality-floor", default="auto",
                   type=_quality_floor_arg,
                   help="fold-oracle gate: retrain (fresh seed) folds whose "
                        "no-policy baseline accuracy is below this, exclude "
                        "them from ranking if still weak.  'auto' (default) "
                        "= chance + 0.35*(1-chance); a float sets it "
                        "explicitly; 'off' disables "
                        "(docs/search_postmortem_r2.md)")
    p.add_argument("--fold-retrain-tries", type=int, default=2)
    p.add_argument("--phase1-epochs", type=int, default=None,
                   help="override conf['epoch'] for phase-1 fold pretraining")
    p.add_argument("--phase3-random", action="store_true",
                   help="add a random-policy control arm to phase 3: an "
                        "equal-size uniform draw from the search space, "
                        "audited identically, retrained on the same seeds "
                        "(the density-matching claim is searched > random, "
                        "not just searched > no-aug)")
    p.add_argument("--divergence-retries", type=int, default=0,
                   help="phase-1/3 training runs: on a NaN/inf epoch "
                        "loss, roll back to the newest intact checkpoint "
                        "and replay with retry-folded randomness up to R "
                        "times before re-raising.  0 (default) = the "
                        "historical immediate raise (docs/RESILIENCE.md)")
    p.add_argument("--ckpt-keep", type=int, default=2,
                   help="rollback-chain depth for every checkpoint this "
                        "search writes (path, path.prev, ...); restore "
                        "walks to the newest sha256-intact link")
    p.add_argument("--ckpt-every-dispatch", type=int, default=0,
                   help="mid-epoch snapshot every M dispatch chunks in "
                        "phase-3 retrains (device-cache path; bit-"
                        "identical dispatch-boundary resume).  0 = off")
    p.add_argument("--watchdog", default="off", type=_watchdog_arg,
                   help="dispatch watchdog: deadline-guard every device "
                        "dispatch (train chunks, TTA/eval replays) and "
                        "treat one that blows its deadline as HUNG — the "
                        "typed DispatchHungError maps to exit 77 and the "
                        "relaunch resumes from the newest checkpoint-chain "
                        "link.  'off' (default) = the historical async "
                        "dispatch bit-for-bit; 'auto' = deadlines from an "
                        "EMA of observed dispatch wall times (generous "
                        "first-call compile allowance); SECONDS = a fixed "
                        "deadline (docs/RESILIENCE.md)")
    p.add_argument("--fleet-transport", default=None, metavar="DIR",
                   help="multi-host MPMD fleet search: promote the "
                        "--async-pipeline candidate queue to a cross-"
                        "host round transport under DIR (a directory "
                        "every host mounts).  The LEARNER host "
                        "(--search-role learner) trains phase-1 folds, "
                        "publishes gate-cleared checkpoints, and "
                        "publishes TPE ask rounds as leased work "
                        "units; ACTOR hosts (--search-role actor) "
                        "claim rounds, run the TTA dispatches, and "
                        "post rewards back.  The fleet reproduces the "
                        "single-host --async-pipeline artifacts bit "
                        "for bit when every host shares the same "
                        "flags; dead actors are reclaimed by the lease "
                        "TTL.  Default: inherited FAA_FLEET_TRANSPORT "
                        "(the fleet launcher's --fleet-transport "
                        "exports it); 'off'/unset = single host "
                        "(docs/RESILIENCE.md 'Fleet search')")
    p.add_argument("--search-role", default="auto",
                   choices=("auto", "learner", "actor"),
                   help="this host's role in a --fleet-transport "
                        "search.  'auto' (default) reads "
                        "FAA_SEARCH_ROLE (the fleet launcher's --roles "
                        "exports it per host) and falls back to "
                        "'learner'.  'actor' runs no training and no "
                        "TPE: it serves published rounds until the "
                        "learner marks the search done, then exits 0 "
                        "(preemption/hang map to exit 77 like every "
                        "other worker)")
    p.add_argument("--ckpt-publish-timeout", type=float, default=900.0,
                   help="actor hosts: seconds to wait for a claimed "
                        "round's fold checkpoint to be published (and "
                        "digest-match locally) before exiting loudly")
    p.add_argument("--workqueue", default=None, metavar="DIR",
                   help="elastic multi-host scatter: claim phase-1 fold "
                        "trainings and per-fold phase-2 searches off a "
                        "lease queue under DIR (a directory every host "
                        "mounts), renewing leases at dispatch/round "
                        "boundaries and RECLAIMING units whose lease went "
                        "stale — a dead host's fold is finished by a "
                        "survivor and the search completes with any >= 1 "
                        "live host, stamping degraded/lost_hosts/"
                        "reclaimed_units into search_result.json.  "
                        "Replaces the static --folds assignment "
                        "(docs/RESILIENCE.md 'Self-healing fleet')")
    p.add_argument("--lease-ttl", type=float, default=60.0,
                   help="seconds without a heartbeat before a --workqueue "
                        "lease counts as stale and survivors may reclaim "
                        "its unit (must dominate NTP skew + the longest "
                        "dispatch gap between renewals)")
    p.add_argument("--host-tag", default=None,
                   help="this host's stable owner id in the --workqueue "
                        "(default: host<--host-id> under the fleet "
                        "launcher, else host<pid>).  A relaunch must "
                        "REUSE its predecessor's tag to resume its own "
                        "leases without waiting out the TTL")
    # accepted so the fleet launcher can drive this CLI like train_cli;
    # --host-id doubles as the default --host-tag
    p.add_argument("--coordinator", default=None,
                   help="host0 addr for multi-host JAX (fleet launcher "
                        "passes it; only used when --workqueue is unset)")
    p.add_argument("--num-hosts", type=int, default=None)
    p.add_argument("--host-id", type=int, default=None)
    p.add_argument("--telemetry", default="off", metavar="{off,DIR}",
                   help="flight-recorder journal (core/telemetry.py): "
                        "typed dispatch/compile/checkpoint/lease/trial "
                        "events under DIR with rotation-bounded size, "
                        "renderable as a Chrome trace via tools/"
                        "trace_export.py and aggregated fleet-wide via "
                        "tools/faa_status.py.  'off' (default, bit-for-"
                        "bit — no journal I/O) still honors an inherited "
                        "FAA_TELEMETRY")
    p.add_argument("--telemetry-port", type=int, default=0,
                   help="serve GET /metrics (Prometheus text exposition "
                        "of the in-memory telemetry registry, read-only) "
                        "on this port while the search runs.  0 = off")
    p.add_argument("--audit-floor", type=float, default=0.95,
                   help="drop selected sub-policies whose standalone "
                        "mean-over-draws fold accuracy < floor x baseline "
                        "(<=0 disables).  Default 0.95: the validated "
                        "round-3 recipe — the old 0.7 default measurably "
                        "ships destructive policies "
                        "(search_e2e_r3/search_result_floor0.70.json)")
    p.add_argument("override", nargs="*")
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    conf = load_config(args.conf, overrides=args.override)
    if args.coordinator and not args.workqueue:
        # one JAX job spanning hosts (the train_cli contract); in
        # --workqueue mode every host is its own single-process JAX job
        # sharing only the artifact directory
        from fast_autoaugment_tpu.parallel.mesh import distributed_init

        distributed_init(args.coordinator, args.num_hosts, args.host_id)
    # SIGTERM/SIGUSR1 -> graceful preemption: the in-flight training run
    # checkpoints at its next safe boundary (per-trial logs are already
    # persisted per round) and the process exits 77 = "resume me"
    install_signal_handlers()
    from fast_autoaugment_tpu.core import telemetry

    # journal + read-only /metrics exposition (core/telemetry.py);
    # both default off = the historical stream
    telemetry.configure_telemetry(args.telemetry)
    metrics_httpd = None
    if args.telemetry_port:
        metrics_httpd, _port = telemetry.start_metrics_server(
            args.telemetry_port)
    t_start = time.time()

    try:
        return _run(args, conf, t_start)
    except PreemptedError as e:
        logger.warning(
            "preempted (%s) — exiting %d; rerunning the same command "
            "resumes from the per-fold checkpoints and trial log",
            e, PREEMPTED_EXIT_CODE)
        telemetry.emit("preempt", "search_cli", kind="preempted",
                       exit_code=PREEMPTED_EXIT_CODE)
        raise SystemExit(PREEMPTED_EXIT_CODE)
    except DispatchHungError as e:
        logger.error(
            "dispatch HUNG (%s) — the in-flight device state is "
            "unrecoverable; exiting %d so the supervisor relaunches and "
            "the rerun resumes from the newest checkpoint-chain link",
            e, PREEMPTED_EXIT_CODE)
        telemetry.emit("preempt", "search_cli", kind="dispatch_hung",
                       label_detail=e.label, exit_code=PREEMPTED_EXIT_CODE)
        raise SystemExit(PREEMPTED_EXIT_CODE)
    finally:
        if metrics_httpd is not None:
            metrics_httpd.shutdown()


def _owner_tag(args) -> str:
    """Stable owner id for lease-holding layers: --host-tag, then
    host<--host-id> (the fleet launcher's per-host identity — a
    relaunch reclaims its own leases immediately), then host<pid>."""
    import os

    return args.host_tag or (
        f"host{args.host_id}" if args.host_id is not None
        else f"host{os.getpid()}")


def _build_workqueue(args):
    """The shared lease queue (or None)."""
    if not args.workqueue:
        return None
    from fast_autoaugment_tpu.launch.workqueue import WorkQueue

    tag = _owner_tag(args)
    wq = WorkQueue(args.workqueue, tag, lease_ttl=args.lease_ttl)
    logger.info("workqueue: owner=%s root=%s lease_ttl=%.1fs",
                tag, args.workqueue, args.lease_ttl)
    return wq


def _resolve_fleet_transport(args):
    """``(transport, role)``: the cross-host round transport (or None)
    plus this host's resolved role.  The dir falls back to the
    FAA_FLEET_TRANSPORT env handoff (the fleet launcher exports it to
    every host launch and retry)."""
    import os

    from fast_autoaugment_tpu.search.pipeline import (
        FLEET_TRANSPORT_ENV_VAR,
        FleetTransport,
        resolve_search_role,
    )

    role = resolve_search_role(args.search_role)
    spec = (args.fleet_transport or "").strip()
    if spec.lower() in ("", "off"):
        spec = os.environ.get(FLEET_TRANSPORT_ENV_VAR, "").strip()
    if spec.lower() in ("", "off"):
        if role == "actor":
            raise SystemExit(
                "search_cli: --search-role actor needs a --fleet-"
                "transport DIR (or the FAA_FLEET_TRANSPORT handoff) — "
                "an actor host without a transport has nothing to serve")
        return None, role
    if args.workqueue:
        raise SystemExit(
            "search_cli: --fleet-transport and --workqueue are mutually "
            "exclusive (rounds-over-hosts vs folds-over-hosts)")
    transport = FleetTransport(spec, _owner_tag(args),
                               lease_ttl=args.lease_ttl, role=role)
    logger.info("fleet transport: role=%s owner=%s root=%s "
                "lease_ttl=%.1fs", role, transport.owner, spec,
                args.lease_ttl)
    return transport, role


def _run_actor(args, conf, transport):
    """The --search-role actor main path: serve published rounds until
    the learner marks the search done; write no search artifacts."""
    from fast_autoaugment_tpu.search.driver import search_actor

    stats = search_actor(
        conf,
        dataroot=args.dataroot,
        save_dir=args.save_dir,
        fleet_transport=transport,
        cv_num=args.num_fold,
        cv_ratio=args.cv_ratio,
        num_policy=args.num_policy,
        num_op=args.num_op,
        trial_batch=args.trial_batch,
        seed=args.seed,
        aug_dispatch=args.aug_dispatch,
        aug_groups=args.aug_groups,
        watchdog=args.watchdog,
        telemetry_spec=args.telemetry,
        ckpt_timeout=args.ckpt_publish_timeout,
    )
    transport.mark_host_done({"rounds_ok": stats["rounds_ok"],
                              "rounds_err": stats["rounds_err"]})
    return stats


def _run(args, conf, t_start):
    transport, role = _resolve_fleet_transport(args)
    if role == "actor":
        return _run_actor(args, conf, transport)
    work_queue = _build_workqueue(args)
    result = search_policies(
        conf,
        dataroot=args.dataroot,
        save_dir=args.save_dir,
        cv_num=args.num_fold,
        cv_ratio=args.cv_ratio,
        num_policy=args.num_policy,
        num_op=args.num_op,
        num_search=args.num_search,
        num_top=args.num_top,
        smoke_test=args.smoke_test,
        resume=not args.no_resume,
        until=args.until,
        folds=[int(f) for f in args.folds.split(",")] if args.folds else None,
        seed=args.seed,
        fold_quality_floor=args.fold_quality_floor,
        fold_retrain_tries=args.fold_retrain_tries,
        phase1_epochs=args.phase1_epochs,
        audit_floor=args.audit_floor if args.audit_floor > 0 else None,
        random_control=args.phase3_random,
        trial_batch=args.trial_batch,
        fold_stack=args.fold_stack,
        aug_dispatch=args.aug_dispatch,
        aug_groups=args.aug_groups,
        device_cache=args.device_cache,
        steps_per_dispatch=args.steps_per_dispatch,
        divergence_retries=args.divergence_retries,
        ckpt_keep=args.ckpt_keep,
        watchdog=args.watchdog,
        work_queue=work_queue,
        async_pipeline=args.async_pipeline,
        pipeline_actors=args.pipeline_actors,
        pipeline_queue_depth=args.pipeline_queue_depth,
        telemetry_spec=args.telemetry,
        fleet_transport=transport,
        topup_trials=args.topup_trials,
    )
    final_policy_set = result["final_policy_set"]
    random_policy_set = result.get("random_policy_set") or []
    logger.info("final policy set: %d sub-policies", len(final_policy_set))

    if args.phase3_random:
        skip_reason = random_arm_skip_reason(result)
        if skip_reason is not None:
            logger.warning(
                "=" * 66 + "\n"
                "--phase3-random was requested but the RANDOM CONTROL ARM "
                "WILL NOT RUN: %s.\nPhase 3 degrades to a two-arm "
                "(default vs augment) comparison — the searched-beats-"
                "random claim is NOT being tested by this run.\n" + "=" * 66,
                skip_reason,
            )
            result["random_arm_skipped"] = True
            result["random_arm_skip_reason"] = skip_reason

    _UNSERIALIZED = ("final_policy_set", "random_policy_set")

    def persist():
        """(Re)write search_result.json — called after EVERY phase-3
        run so a killed process still leaves the partial record
        (per-seed values to date) on disk."""
        import jax

        hours = (time.time() - t_start) * jax.device_count() / 3600.0
        # honest name + legacy alias; `platform` (from search_policies)
        # says what actually measured these hours
        result["device_hours_total"] = hours
        result["tpu_hours_total"] = hours
        # refresh: phase-3 retrains pay compiles after search_policies
        # stamped its snapshot
        from fast_autoaugment_tpu.core.compilecache import compile_cache_stats

        result["compile_cache"] = compile_cache_stats()
        write_json_atomic(
            f"{args.save_dir}/search_result.json",
            {k: v for k, v in result.items() if k not in _UNSERIALIZED})
        return result

    if args.until < 3 or not final_policy_set:
        if work_queue is not None:
            work_queue.mark_host_done()
        if transport is not None:
            transport.mark_host_done()
        return persist()

    phase3_hb = None
    if transport is not None:
        # the learner retrains alone (actors drained on search_done),
        # but its host beat must stay fresh or the fleet's wedge
        # detector would SIGKILL a healthy learner mid-retrain
        phase3_hb = transport.beat
    if work_queue is not None:
        # phase 3 is one unit: exactly one host runs the retrains (a
        # stale lease lets a survivor reclaim them; per-run checkpoints
        # make the rerun resume)
        if not work_queue.claim("phase3"):
            logger.info(
                "workqueue: phase 3 is owned elsewhere (or done) — this "
                "host is finished; the owner persists the final result")
            work_queue.mark_host_done()
            return persist()

        def phase3_hb():
            work_queue.renew("phase3")
            work_queue.beat_host()

    # phase 3: full retrains, default vs augmented (search.py:264-312)
    # plus an optional random-policy control arm.  Unlike the
    # reference's bare means, record per-seed values, the spread and
    # paired t-tests (runs pair by seed: identical data and init, only
    # the augmentation differs) — VERDICT r3 next-4 / r4 next-4.
    num_runs = 1 if args.smoke_test else args.num_result_per_cv
    seeds = [args.seed + run for run in range(num_runs)]
    modes = [("default", "default"), ("augment", final_policy_set)]
    if args.phase3_random and random_policy_set:
        modes.append(("random", random_policy_set))
    outcomes: dict[str, list[float]] = {name: [] for name, _ in modes}
    phase3: dict = {"num_runs": num_runs, "seeds": seeds}
    result["phase3"] = phase3

    def update_stats():
        from fast_autoaugment_tpu.utils.stats import paired_t_test

        for name, _aug in modes:
            vals = outcomes[name]
            if not vals:
                continue
            phase3[name] = {
                "per_seed": vals,
                "mean": float(np.mean(vals)),
                "std": float(np.std(vals, ddof=1)) if len(vals) > 1 else 0.0,
            }
        for a, b in (("augment", "default"), ("augment", "random"),
                     ("random", "default")):
            n = min(len(outcomes.get(a, [])), len(outcomes.get(b, [])))
            if n > 1:
                phase3[f"paired_{a}_minus_{b}"] = paired_t_test(
                    outcomes[a][:n], outcomes[b][:n])
        if outcomes["default"]:
            result["top1_test_default_mean"] = float(
                np.mean(outcomes["default"]))
        if outcomes["augment"]:
            result["top1_test_augment_mean"] = float(
                np.mean(outcomes["augment"]))

    # seed-major order: every completed seed adds one PAIRED
    # observation to all arms, so an interrupted run still yields a
    # balanced three-way comparison at whatever n it reached
    for run in range(num_runs):
        for mode, aug in modes:
            mode_conf = conf.replace(aug=aug)
            path = f"{args.save_dir}/final_{mode}_{run}.msgpack"
            res = train_and_eval(
                mode_conf, args.dataroot, test_ratio=0.0,
                save_path=path, metric="last", seed=seeds[run],
                aug_dispatch=args.aug_dispatch, aug_groups=args.aug_groups,
                device_cache=args.device_cache,
                steps_per_dispatch=args.steps_per_dispatch,
                divergence_retries=args.divergence_retries,
                ckpt_keep=args.ckpt_keep,
                checkpoint_every_dispatch=args.ckpt_every_dispatch,
                watchdog=args.watchdog, heartbeat=phase3_hb,
            )
            outcomes[mode].append(float(res.get("top1_test", 0.0)))
            logger.info("phase3 %s run %d: top1_test=%.4f", mode, run,
                        outcomes[mode][-1])
            update_stats()
            persist()

    summary = " vs ".join(
        "%s %.4f±%.4f" % (name, phase3[name]["mean"], phase3[name]["std"])
        for name, _ in modes if name in phase3)
    pvals = ", ".join(
        "%s p=%.3f" % (k[len("paired_"):], phase3[k]["p_value"])
        for k in sorted(phase3) if k.startswith("paired_"))
    logger.info("phase3 (n=%d): %s%s", num_runs, summary,
                " [%s]" % pvals if pvals else "")

    if work_queue is not None:
        work_queue.release("phase3", info={"num_runs": num_runs})
        work_queue.mark_host_done()
    if transport is not None:
        transport.mark_host_done()
    persist()
    logger.info("search complete: %.3f device-hours on %s",
                result["device_hours_total"], result.get("platform", "?"))
    return result


if __name__ == "__main__":
    main()
