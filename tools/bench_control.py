#!/usr/bin/env python
"""Closed-loop control-plane bench (``make bench-control``).

Measures the two numbers docs/CONTROL.md promises: DETECT->PROMOTE
LATENCY (injected drift at the serve dispatch seam -> the journaled
``promote`` event) and ROLLOVER GOODPUT (served requests/s while the
canary rollout + fleet-wide promotion are in flight, vs the same
fleet's steady-state goodput).

Per round a real 3-replica plane comes up — ``serve_cli
--traffic-stats --telemetry`` replicas announcing into a shared
``--port-dir`` — and one of two arms runs:

- **steady**: closed-loop traffic, no drift, no controller;
- **rollover**: the same traffic with ``FAA_FAULT
  drift@dispatch=N,shift=S`` armed in every replica and a
  ``control_cli`` (drill mode: pre-built candidate, so the measured
  latency is the CONTROL PLANE's, not a search wall) that detects,
  canaries and promotes mid-run.

Arms run as PAIRED ALTERNATING rounds with per-arm MEDIANS (the
1-core A/B discipline: fixed-order arms read allocator drift as
signal) and the JSON line carries the latency breakdown
(shift->detect, detect->promote), both arms' goodput, the zero-drop
verdict, the unified telemetry stamp and the ``single_core_caveat`` —
every process here shares one core, so the goodput ratio measures
PLUMBING overhead, not fleet behavior at scale.

CPU-only: every replica and the controller are spawned with ``JAX_PLATFORMS=cpu`` — three replicas at once
cannot share one chip (a chip has one owner process), so this is a
host-side drill and its JSON says ``"platform": "cpu"``; it needs an
in-process or device-pinned mode before it can measure the device.

    python tools/bench_control.py [--pairs 2] [--seconds-per-arm 12]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))

#: baseline / candidate single-sub policies (exact dispatch, distinct
#: digests — the canary comparator must be able to tell them apart)
POLICY_A = [[["Rotate", 0.5, 0.4], ["Invert", 0.2, 0.0]]]
POLICY_B = [[["ShearX", 0.9, 0.1], ["Solarize", 0.3, 0.7]]]

DRIFT_DISPATCH = 40      # the fault's dispatch coordinate
DRIFT_SHIFT = 60.0       # injected pixel shift (sigmas >> cusum h)


def _read_journal_events(tel_dir: str, etypes: set[str]) -> list[dict]:
    out = []
    for path in sorted(glob.glob(
            os.path.join(tel_dir, "**", "journal-*.jsonl"),
            recursive=True)):
        try:
            with open(path) as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(rec, dict) and rec.get("type") in etypes:
                        out.append(rec)
        except OSError:
            continue
    out.sort(key=lambda r: r.get("t_wall") or 0)
    return out


def _drive_traffic(ports, seconds, imgs_per_request, image,
                   until_fn=None, check_every: int = 32):
    """Round-robin closed-loop client over the replica ports; returns
    (per-request (t_wall_done, ok, latency_s) rows, elapsed_s).

    `until_fn` (rollover arm) is polled every `check_every` requests
    once `seconds` has passed: traffic CONTINUES until it returns True
    (the promote landed) or the hard bound — the rollover arm must
    cover the whole detect->promote window, however long the AOT
    reloads take on this host."""
    import io

    import numpy as np

    from bench_router import _http

    rng = np.random.default_rng(0)
    pool = rng.integers(0, 256, (64, image, image, 3),
                        dtype=np.uint8).astype(np.float32)
    rows = []
    i = 0
    t0 = time.monotonic()
    t_end = t0 + seconds
    t_hard = t0 + max(seconds, 150.0)
    while True:
        now = time.monotonic()
        if until_fn is None:
            if now >= t_end:
                break
        elif now >= t_hard:
            break
        elif now >= t_end and i % check_every == 0 and until_fn():
            break
        batch = pool[(i * imgs_per_request) % 48:
                     (i * imgs_per_request) % 48 + imgs_per_request]
        buf = io.BytesIO()
        np.savez(buf, images=batch)
        port = ports[i % len(ports)]
        t_req = time.monotonic()
        try:
            status, _h, _b = _http("127.0.0.1", port, "POST", "/augment",
                                   body=buf.getvalue(), timeout=30.0)
            ok = status == 200
        except OSError:
            ok = False
        rows.append((time.time(), ok, time.monotonic() - t_req))
        i += 1
    return rows, time.monotonic() - t0


def run_round(arm: str, args) -> dict:
    from bench_router import wait_port_record, wait_ready

    procs: list[subprocess.Popen] = []
    with tempfile.TemporaryDirectory(prefix=f"bench_control_{arm}_") as tmp:
        tel_dir = os.path.join(tmp, "telemetry")
        port_dir = os.path.join(tmp, "replicas")
        path_a = os.path.join(tmp, "a.json")
        path_b = os.path.join(tmp, "b.json")
        with open(path_a, "w") as fh:
            json.dump(POLICY_A, fh)
        with open(path_b, "w") as fh:
            json.dump(POLICY_B, fh)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("FAA_TELEMETRY", None)
        if arm == "rollover":
            env["FAA_FAULT"] = (f"drift@dispatch={DRIFT_DISPATCH},"
                                f"shift={DRIFT_SHIFT:g}")
        try:
            ports = []
            for i in range(args.replicas):
                env_i = dict(env, FAA_HOST_ID=str(i))
                procs.append(subprocess.Popen([
                    sys.executable, "-m",
                    "fast_autoaugment_tpu.serve.serve_cli",
                    "--policy", path_a, "--image", str(args.image),
                    "--shapes", args.shapes,
                    "--max-wait-ms", "2",
                    "--traffic-stats",
                    "--telemetry", tel_dir,
                    "--port", "0", "--port-dir", port_dir,
                    "--host-tag", f"replica{i}",
                ], env=env_i, cwd=_REPO))
            for i in range(args.replicas):
                port = wait_port_record(port_dir, f"replica{i}", procs[i],
                                        args.startup_timeout)
                wait_ready("127.0.0.1", port, procs[i],
                           args.startup_timeout)
                ports.append(port)

            ctl = None
            stats_file = os.path.join(tmp, "control_stats.json")
            if arm == "rollover":
                ctl_env = dict(env)
                ctl_env.pop("FAA_FAULT", None)  # the fault is serve-side
                ctl = subprocess.Popen([
                    sys.executable, "-m",
                    "fast_autoaugment_tpu.launch.control_cli",
                    "--telemetry", tel_dir, "--port-dir", port_dir,
                    "--baseline-policy", path_a,
                    "--candidate-policy", path_b,
                    "--baseline-samples", "10",
                    "--cusum-h", "4", "--gate-polls", "2",
                    "--quality-margin", "1.0",
                    "--poll-interval", "0.2",
                    "--reload-timeout", str(args.startup_timeout),
                    "--stats-file", stats_file,
                ], env=ctl_env, cwd=_REPO)
                procs.append(ctl)

            until_fn = None
            if arm == "rollover":
                def until_fn():
                    return any(
                        e["type"] == "promote" for e in
                        _read_journal_events(tel_dir, {"promote"}))

            rows, elapsed = _drive_traffic(
                ports, args.seconds_per_arm, args.imgs_per_request,
                args.image, until_fn=until_fn)

            row: dict = {"arm": arm}
            oks = [r for r in rows if r[1]]
            lats = sorted(r[2] for r in oks)
            row["requests_ok"] = len(oks)
            row["requests_failed"] = len(rows) - len(oks)
            row["elapsed_s"] = round(elapsed, 2)
            row["rps"] = round(len(oks) / elapsed, 1)
            if lats:
                row["p50_ms"] = round(lats[len(lats) // 2] * 1e3, 3)
                row["p99_ms"] = round(
                    lats[min(len(lats) - 1,
                             int(0.99 * len(lats)))] * 1e3, 3)
            if arm == "rollover":
                evs = _read_journal_events(
                    tel_dir, {"drift", "canary", "promote", "rollback",
                              "dispatch"})
                drift = next((e for e in evs if e["type"] == "drift"),
                             None)
                promote = next((e for e in evs
                                if e["type"] == "promote"), None)
                rollout = next((e for e in evs
                                if e["type"] == "canary"
                                and e.get("action") == "rollout"), None)
                # the shift lands at a known dispatch event: the first
                # journal dispatch whose input_mean jumped past half
                # the injected shift over the pre-shift level
                shifted = None
                pre = [e for e in evs if e["type"] == "dispatch"
                       and isinstance(e.get("input_mean"), (int, float))]
                if pre:
                    base = pre[0]["input_mean"]
                    shifted = next(
                        (e for e in pre
                         if e["input_mean"] - base > DRIFT_SHIFT / 2),
                        None)
                row["promoted"] = promote is not None
                if shifted and drift:
                    row["shift_to_detect_s"] = round(
                        drift["t_wall"] - shifted["t_wall"], 3)
                if drift and promote:
                    row["detect_to_promote_s"] = round(
                        promote["t_wall"] - drift["t_wall"], 3)
                if rollout and promote:
                    window = [r for r in rows
                              if rollout["t_wall"] <= r[0]
                              <= promote["t_wall"]]
                    w_ok = [r for r in window if r[1]]
                    span = max(promote["t_wall"] - rollout["t_wall"],
                               1e-9)
                    row["rollover_window_s"] = round(span, 3)
                    row["rollover_rps"] = round(len(w_ok) / span, 1)
                    row["rollover_failed"] = len(window) - len(w_ok)
            return row
        finally:
            for proc in reversed(procs):
                if proc.poll() is None:
                    try:
                        proc.send_signal(signal.SIGTERM)
                    except ProcessLookupError:
                        pass
            deadline = time.monotonic() + 30.0
            for proc in procs:
                left = max(0.5, deadline - time.monotonic())
                try:
                    proc.wait(timeout=left)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(timeout=5.0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--replicas", type=int, default=3)
    p.add_argument("--pairs", type=int, default=2,
                   help="paired alternating rounds per arm (medians "
                        "reported)")
    p.add_argument("--seconds-per-arm", type=float, default=14.0)
    p.add_argument("--image", type=int, default=8)
    p.add_argument("--shapes", default="1,8")
    p.add_argument("--imgs-per-request", type=int, default=4)
    p.add_argument("--startup-timeout", type=float, default=240.0)
    args = p.parse_args(argv)

    from bench import (
        host_contention_stamp,
        refuse_or_flag_contention,
        telemetry_stamp,
    )
    from bench_router import _median

    contention = refuse_or_flag_contention(host_contention_stamp())

    rounds = []
    for i in range(max(1, args.pairs)):
        order = (("steady", "rollover") if i % 2 == 0
                 else ("rollover", "steady"))
        for arm in order:
            rounds.append(run_round(arm, args))

    meds = {}
    for arm in ("steady", "rollover"):
        sel = [r for r in rounds if r["arm"] == arm]
        meds[arm] = {
            "rps_median": round(_median([r["rps"] for r in sel]), 1),
            "p50_ms_median": round(_median(
                [r.get("p50_ms", 0.0) for r in sel]), 3),
            "p99_ms_median": round(_median(
                [r.get("p99_ms", 0.0) for r in sel]), 3),
            "requests_ok": sum(r["requests_ok"] for r in sel),
            "requests_failed": sum(r["requests_failed"] for r in sel),
        }
    roll = [r for r in rounds if r["arm"] == "rollover"]
    promoted = all(r.get("promoted") for r in roll)
    out = {
        "metric": "control_detect_to_promote",
        "platform": "cpu",  # every replica is pinned to it
        "replicas": args.replicas,
        "pairs": args.pairs,
        "seconds_per_arm": args.seconds_per_arm,
        "drift_dispatch": DRIFT_DISPATCH,
        "drift_shift": DRIFT_SHIFT,
        "arms": meds,
        "all_rounds_promoted": promoted,
        "shift_to_detect_s_median": _median(
            [r["shift_to_detect_s"] for r in roll
             if "shift_to_detect_s" in r]),
        "detect_to_promote_s_median": _median(
            [r["detect_to_promote_s"] for r in roll
             if "detect_to_promote_s" in r]),
        "rollover_rps_median": _median(
            [r["rollover_rps"] for r in roll if "rollover_rps" in r]),
        "rollover_dropped_total": sum(
            r.get("rollover_failed", 0) for r in roll),
        "rollover_over_steady_rps": (
            round(_median([r["rollover_rps"] for r in roll
                           if "rollover_rps" in r])
                  / meds["steady"]["rps_median"], 3)
            if meds["steady"]["rps_median"]
            and any("rollover_rps" in r for r in roll) else None),
        "rounds": rounds,
        # every replica, the controller and the client share ONE core:
        # ratios here are plumbing overhead, not fleet behavior
        "single_core_caveat": True,
        **telemetry_stamp(contention=contention),
    }
    print(json.dumps(out))
    ok = promoted and out["rollover_dropped_total"] == 0 \
        and meds["steady"]["requests_ok"] > 0
    return 0 if ok else 4


if __name__ == "__main__":
    raise SystemExit(main())
