#!/usr/bin/env python
"""Multi-host MPMD fleet search bench (``make bench-fleet-search``).

Runs the SAME seeded search through two arms:

- **single**: the single-host ``--async-pipeline on`` scheduler (the
  PR-9 baseline — actors are threads);
- **fleet**: a real 3-process fleet — one LEARNER(+trainer) host and N
  ACTOR hosts (``search_cli --search-role``) over a shared
  ``--fleet-transport`` dir, with the telemetry journal pointed at the
  same dir so every host's evidence lands in one place.

The JSON line reports:

- **transport overhead** from the journaled ``round`` events:
  round publish->claim and reward return->tell-apply latencies
  (p50/p99), plus the measured learner-side cost per round (the
  publish write + the result read) against the ask(K) TPE latency
  already measured by ``tools/bench_tpe.py`` — the transport must stay
  cheaper than the host math it overlaps, or it becomes the new
  dispatch gap (the acceptance budget);
- **per-host busy fractions** from union-merged journal dispatch
  windows and the **journal-proven concurrent phase-1/phase-2 lanes on
  distinct host ids** (``tools/faa_status.py`` math — the same numbers
  ``make status`` renders);
- **byte-identity** of ``search_trials.json`` + ``final_policy.json``
  between the arms (the fleet determinism acceptance);
- wall per arm, stamped ``single_core_caveat``: every "host" here
  shares ONE core, so the wall ratio measures scheduling plumbing —
  the transferable evidence is the lane concurrency + the latency
  table, not wall.

Honors ``FAA_BENCH_REQUIRE_QUIET=1`` (refuses on a contended host,
exit 3).

CPU-only: the learner and the actor "hosts" are processes on this
machine pinned to ``JAX_PLATFORMS=cpu`` — three processes cannot share
one chip (a chip has one owner process), so this is a host-side drill
and its JSON says ``"platform": "cpu"``; it needs device-pinned hosts
before it can measure the device.

    python tools/bench_fleet_search.py --num-search 8 --actor-hosts 2
    make bench-fleet-search
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))

_CONF_YAML = (
    "model:\n  type: wresnet10_1\ndataset: synthetic\naug: default\n"
    "cutout: 8\nbatch: 8\nepoch: 1\nlr: 0.05\n"
    "lr_schedule:\n  type: cosine\n"
    "optimizer:\n  type: sgd\n  decay: 0.0001\n  momentum: 0.9\n"
    "  nesterov: true\n")


def _pct(xs, q):
    import numpy as np

    xs = [x for x in xs if x is not None]
    return round(float(np.percentile(np.asarray(xs, float), q)), 3) \
        if xs else None


def _base_cmd(conf, dataroot, args):
    return [
        sys.executable, "-m", "fast_autoaugment_tpu.launch.search_cli",
        "-c", conf, "--dataroot", dataroot,
        "--num-fold", str(args.num_fold),
        "--num-search", str(args.num_search),
        "--num-policy", str(args.num_policy),
        "--num-op", str(args.num_op), "--num-top", "2",
        "--trial-batch", str(args.trial_batch),
        "--until", "2", "--fold-quality-floor", "off",
        "--seed", str(args.seed),
        "--async-pipeline", "on",
        "--pipeline-actors", str(args.actor_hosts),
        "--pipeline-queue-depth", str(args.queue_depth),
    ]


def round_transport_stats(journal: list[dict]) -> dict:
    """Per-unit transport latencies from the journaled round events:
    publish->claim (cross-host wall clocks — same machine here, NTP-
    bounded on a real fleet), return->apply (stamped by the learner at
    adoption), and the learner's measured per-round transport cost
    (publish write + result read — the part that could crowd the ask
    horizon)."""
    publish: dict[str, dict] = {}
    claim: dict[str, dict] = {}
    apply_: dict[str, dict] = {}
    for r in journal:
        if r.get("type") != "round":
            continue
        unit = str(r.get("label"))
        a = r.get("action")
        if a == "publish":
            publish[unit] = r
        elif a == "claim" and unit not in claim:  # first claim wins
            claim[unit] = r
        elif a == "apply":
            apply_[unit] = r
    pub_to_claim = [
        (claim[u]["t_wall"] - publish[u]["t_wall"]) * 1e3
        for u in publish if u in claim
        if isinstance(publish[u].get("t_wall"), (int, float))
        and isinstance(claim[u].get("t_wall"), (int, float))
    ]
    ret_to_apply = [r.get("return_to_apply_ms") for r in apply_.values()]
    learner_cost = [
        (publish[u].get("publish_secs") or 0.0) * 1e3
        + (apply_[u].get("poll_secs") or 0.0) * 1e3
        for u in publish if u in apply_
    ]
    return {
        "rounds_published": len(publish),
        "rounds_claimed": len(claim),
        "rounds_applied": len(apply_),
        "publish_to_claim_ms": {"p50": _pct(pub_to_claim, 50),
                                "p99": _pct(pub_to_claim, 99)},
        "return_to_apply_ms": {"p50": _pct(ret_to_apply, 50),
                               "p99": _pct(ret_to_apply, 99)},
        "learner_cost_per_round_ms": {"p50": _pct(learner_cost, 50),
                                      "p99": _pct(learner_cost, 99)},
    }


def run_fleet_search_bench(args, workdir: str) -> dict:
    from faa_status import (
        dispatch_stats,
        read_heartbeats,
        search_fleet_status,
    )
    from trace_export import read_journal

    conf = os.path.join(workdir, "conf.yaml")
    with open(conf, "w") as fh:
        fh.write(_CONF_YAML)
    base = _base_cmd(conf, workdir, args)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FAA_FAULT", None)

    # ---- arm 1: single host (threads); also warms the compile cache
    single_dir = os.path.join(workdir, "single")
    t0 = time.time()
    r = subprocess.run(base + ["--save-dir", single_dir], env=env,
                       capture_output=True, text=True,
                       timeout=args.timeout, cwd=_REPO)
    single_wall = time.time() - t0
    if r.returncode != 0:
        raise RuntimeError(
            f"single-host arm failed rc={r.returncode}:\n"
            + r.stdout[-3000:])

    # ---- arm 2: 1 learner + N actor hosts over the shared transport
    transport = os.path.join(workdir, "transport")
    fleet_dir = os.path.join(workdir, "fleet")
    fleet_base = base + ["--save-dir", fleet_dir,
                         "--fleet-transport", transport,
                         "--telemetry", transport,
                         "--lease-ttl", str(args.lease_ttl)]
    t0 = time.time()
    # each host logs to a FILE: hosts on PIPEs read one after the other
    # deadlock once one fills its pipe while we block on another's
    roles = ["learner"] + ["actor"] * args.actor_hosts
    logs = [os.path.join(workdir, f"host{i}.log") for i in range(len(roles))]
    procs = []
    try:
        for i, role in enumerate(roles):
            with open(logs[i], "w") as log:
                procs.append(subprocess.Popen(
                    fleet_base + ["--search-role", role,
                                  "--host-id", str(i)],
                    env=dict(env, FAA_HOST_ID=str(i)), stdout=log,
                    stderr=subprocess.STDOUT, cwd=_REPO))
        for p in procs:
            p.wait(timeout=args.timeout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    fleet_wall = time.time() - t0
    if any(p.returncode for p in procs):
        tails = []
        for path in logs:
            with open(path) as fh:
                tails.append(fh.read()[-1500:])
        raise RuntimeError(
            "fleet arm failed rcs="
            + str([p.returncode for p in procs]) + ":\n" + "\n".join(tails))

    # ---- byte-identity: the fleet determinism acceptance
    trials_match = (
        open(os.path.join(single_dir, "search_trials.json"), "rb").read()
        == open(os.path.join(fleet_dir, "search_trials.json"),
                "rb").read())
    final_match = (
        open(os.path.join(single_dir, "final_policy.json"), "rb").read()
        == open(os.path.join(fleet_dir, "final_policy.json"),
                "rb").read())

    # ---- journal evidence (the same math make status renders)
    journal = read_journal(transport)
    beats = read_heartbeats(transport)
    by_host: dict[str, list[dict]] = {}
    for rec in journal:
        by_host.setdefault(str(rec.get("host")), []).append(rec)
    per_host = {h: dict(dispatch_stats(rs),
                        role=(beats.get(h) or {}).get("role"))
                for h, rs in sorted(by_host.items())}
    fleet_topo = search_fleet_status(transport, journal, beats) or {}
    transport_stats = round_transport_stats(journal)

    result = json.load(open(os.path.join(fleet_dir,
                                         "search_result.json")))
    return {
        "bench": "fleet_search",
        # the search children stamp what they ran on; both arms are
        # pinned to the CPU (module docstring)
        "platform": result.get("platform"),
        "actor_hosts": args.actor_hosts,
        "num_fold": args.num_fold,
        "num_search": args.num_search,
        "trial_batch": args.trial_batch,
        "window": args.actor_hosts + args.queue_depth,
        "single_wall_secs": round(single_wall, 3),
        "fleet_wall_secs": round(fleet_wall, 3),
        "wall_ratio_single_over_fleet": round(
            single_wall / fleet_wall, 3) if fleet_wall else None,
        "artifacts_bitwise_match": bool(trials_match and final_match),
        "transport": transport_stats,
        "per_host": per_host,
        "concurrent_lane_secs": fleet_topo.get("concurrent_lane_secs"),
        "concurrent_lane_pairs": fleet_topo.get("concurrent_lane_pairs"),
        "degraded": result.get("degraded"),
        "reclaimed_units": result.get("reclaimed_units"),
        "compile_cache": result.get("compile_cache"),
        # every "host" shares ONE core: the wall ratio is scheduling
        # plumbing, NOT the multi-host win — the transferable evidence
        # is concurrent_lane_secs on distinct host ids plus the
        # transport latency table staying under the ask(K) headroom
        "single_core_caveat": True,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-fold", type=int, default=2)
    p.add_argument("--num-search", type=int, default=8)
    p.add_argument("--num-policy", type=int, default=1)
    p.add_argument("--num-op", type=int, default=1)
    p.add_argument("--trial-batch", type=int, default=2)
    p.add_argument("--actor-hosts", type=int, default=2)
    p.add_argument("--queue-depth", type=int, default=2)
    p.add_argument("--lease-ttl", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--timeout", type=float, default=1800.0)
    p.add_argument("--workdir", default=None,
                   help="scratch dir (default: a fresh tempdir, "
                        "removed on success)")
    p.add_argument("--out", default=None,
                   help="also write the JSON line here")
    args = p.parse_args(argv)

    from bench import (
        host_contention_stamp,
        refuse_or_flag_contention,
        telemetry_stamp,
    )
    from bench_tpe import bench_ask_tell_latency

    contention = refuse_or_flag_contention(host_contention_stamp())
    print(f"contention: {json.dumps(contention)}")

    workdir = args.workdir or tempfile.mkdtemp(prefix="faa_bench_fleet_")
    made_temp = args.workdir is None
    record = run_fleet_search_bench(args, workdir)
    record.update(telemetry_stamp(contention=contention))

    # the acceptance budget: added learner-side overhead per round must
    # stay within the ask(K) host latency the pipeline already pays —
    # otherwise the transport becomes the new dispatch gap
    tpe_rows = bench_ask_tell_latency(ks=(args.trial_batch,), reps=20)
    record["tpe_latency"] = tpe_rows
    ask_ms = tpe_rows[0]["ask_ms_mean"]
    learner_ms = (record["transport"]["learner_cost_per_round_ms"]["p99"]
                  or 0.0)
    record["transport_within_ask_budget"] = bool(learner_ms <= ask_ms)

    t = record["transport"]
    print(f"transport: publish->claim p50 "
          f"{t['publish_to_claim_ms']['p50']}ms p99 "
          f"{t['publish_to_claim_ms']['p99']}ms; return->apply p50 "
          f"{t['return_to_apply_ms']['p50']}ms p99 "
          f"{t['return_to_apply_ms']['p99']}ms; learner cost/round p99 "
          f"{t['learner_cost_per_round_ms']['p99']}ms vs ask({args.trial_batch}) "
          f"{ask_ms}ms")
    for host, row in record["per_host"].items():
        print(f"  {host}: role={row.get('role')} "
              f"busy_frac={row.get('busy_frac')} "
              f"dispatches={row.get('dispatches')}")
    print(f"concurrent phase-1/phase-2 lanes on distinct hosts: "
          f"{record['concurrent_lane_secs']}s "
          f"(wall single/fleet {record['wall_ratio_single_over_fleet']}x "
          "— single_core_caveat)")
    ok = (record["artifacts_bitwise_match"]
          and record["transport_within_ask_budget"]
          and (record["concurrent_lane_secs"] or 0.0) > 0.0)
    print("acceptance (bitwise artifacts AND transport <= ask(K) budget "
          "AND journal-proven cross-host lane overlap): "
          f"{'PASS' if ok else 'FAIL'}")

    line = json.dumps(record)
    print(line)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    if made_temp:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0 if ok else 4


if __name__ == "__main__":
    raise SystemExit(main())
