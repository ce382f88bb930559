#!/usr/bin/env python
"""Routed-fleet vs direct-replica serving bench (``make bench-router``).

Spawns a real serving plane — N ``serve_cli`` replicas announcing
themselves into a shared ``--port-dir``, two policies resident per
replica (the default + one tenancy-warmed), and a ``router_cli`` front
door over them — then measures closed-loop HTTP load through two arms:

- **direct**: clients against ONE replica (the single-replica
  baseline);
- **routed**: the same traffic through the router, mixed across both
  policy digests (digest-affinity routing decides the landing
  replica).

Arms run as PAIRED ALTERNATING rounds (direct,routed / routed,direct /
...) and the report takes per-arm MEDIANS — on this 1-core host the
client loop, every replica and the router all contend for the same
core, so absolute numbers are plumbing-level and ordering effects are
first-order (docs/BENCHMARKS.md measurement notes); the alternation +
medians cancel the slow drift, and the contention stamp records the
conditions.  The JSON line carries both arms' rps/p50/p99 medians, the
routed/direct throughput ratio, the router's own topology + affinity
accounting, and the unified telemetry stamp.

CPU-only: every replica and the router are spawned with ``JAX_PLATFORMS=cpu`` — N replicas at once
cannot share one chip (a chip has one owner process), so this is a
host-side drill and its JSON says ``"platform": "cpu"``; it needs an
in-process or device-pinned mode before it can measure the device.

    python tools/bench_router.py [--replicas 3] [--pairs 3]
        [--seconds-per-arm 2] [--image 8] [--shapes 1,8]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))

#: two deterministic single-sub policies (exact dispatch — the fast
#: shape); different ops so their digests (and served bytes) differ
POLICY_A = [[["Rotate", 0.5, 0.4], ["Invert", 0.2, 0.0]]]
POLICY_B = [[["ShearX", 0.9, 0.1], ["Solarize", 0.3, 0.7]]]


def _http(host, port, method, path, body=None, headers=None, timeout=30.0):
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(method, path, body=body, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def wait_ready(host, port, proc, timeout=180.0, path="/readyz"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(
                f"process died before ready: rc={proc.returncode}")
        try:
            status, _h, _b = _http(host, port, "GET", path, timeout=5.0)
            if status == 200:
                return
        except OSError:
            pass
        time.sleep(0.2)
    raise RuntimeError(f"{host}:{port}{path} never went ready "
                      f"within {timeout:.0f}s")


def wait_port_record(port_dir, tag, proc, timeout=180.0) -> int:
    path = os.path.join(port_dir, f"{tag}.json")
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica {tag} died before binding: rc={proc.returncode}")
        try:
            with open(path) as fh:
                return int(json.load(fh)["port"])
        except (OSError, ValueError, KeyError):
            time.sleep(0.2)
    raise RuntimeError(f"replica {tag} never wrote its port record")


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    if n == 0:
        return 0.0
    mid = n // 2
    return xs[mid] if n % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--replicas", type=int, default=3)
    p.add_argument("--pairs", type=int, default=3,
                   help="paired alternating rounds per arm (medians "
                        "reported)")
    p.add_argument("--seconds-per-arm", type=float, default=2.0)
    p.add_argument("--image", type=int, default=8)
    p.add_argument("--shapes", default="1,8")
    p.add_argument("--imgs-per-request", type=int, default=4)
    p.add_argument("--concurrency", type=int, default=4)
    p.add_argument("--max-wait-ms", type=float, default=2.0)
    p.add_argument("--startup-timeout", type=float, default=180.0)
    args = p.parse_args(argv)

    from bench import (
        host_contention_stamp,
        refuse_or_flag_contention,
        telemetry_stamp,
    )
    from bench_serve import run_router_load

    contention = refuse_or_flag_contention(host_contention_stamp())

    import numpy as np

    from fast_autoaugment_tpu.policies.archive import policy_to_tensor
    from fast_autoaugment_tpu.serve.policy_server import policy_digest

    digest_a = policy_digest(policy_to_tensor(
        [[(op, float(pr), float(lv)) for op, pr, lv in sub]
         for sub in POLICY_A]))
    digest_b = policy_digest(policy_to_tensor(
        [[(op, float(pr), float(lv)) for op, pr, lv in sub]
         for sub in POLICY_B]))

    procs: list[subprocess.Popen] = []
    out = {}
    with tempfile.TemporaryDirectory(prefix="bench_router_") as tmp:
        port_dir = os.path.join(tmp, "replicas")
        policy_dir = os.path.join(tmp, "policies")
        os.makedirs(policy_dir)
        path_a = os.path.join(policy_dir, "a.json")
        path_b = os.path.join(policy_dir, "b.json")
        with open(path_a, "w") as fh:
            json.dump(POLICY_A, fh)
        with open(path_b, "w") as fh:
            json.dump(POLICY_B, fh)

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            # ---- the replica fleet (default policy A, tenancy for B)
            replica_ports = []
            for i in range(args.replicas):
                env_i = dict(env, FAA_HOST_ID=str(i))
                procs.append(subprocess.Popen([
                    sys.executable, "-m",
                    "fast_autoaugment_tpu.serve.serve_cli",
                    "--policy", path_a, "--image", str(args.image),
                    "--shapes", args.shapes,
                    "--max-wait-ms", str(args.max_wait_ms),
                    "--tenant-capacity", "2",
                    "--policy-dir", policy_dir,
                    "--port", "0", "--port-dir", port_dir,
                    "--host-tag", f"replica{i}",
                ], env=env_i, cwd=_REPO))
            for i in range(args.replicas):
                port = wait_port_record(port_dir, f"replica{i}", procs[i],
                                        args.startup_timeout)
                wait_ready("127.0.0.1", port, procs[i],
                           args.startup_timeout)
                replica_ports.append(port)
                # pre-warm policy B so mixed traffic is warm everywhere
                status, _h, body = _http(
                    "127.0.0.1", port, "POST", "/tenants/warm",
                    body=json.dumps({"policy": path_b}).encode(),
                    timeout=args.startup_timeout)
                if status != 200:
                    raise RuntimeError(
                        f"tenant warm failed on replica{i}: "
                        f"{status} {body[:200]!r}")

            # ---- the router over the fleet
            router_pf = os.path.join(tmp, "router.port")
            router = subprocess.Popen([
                sys.executable, "-m",
                "fast_autoaugment_tpu.serve.router_cli",
                "--port-dir", port_dir, "--port", "0",
                "--port-file", router_pf, "--poll-interval", "0.2",
            ], env=env, cwd=_REPO)
            procs.append(router)
            t0 = time.monotonic()
            while not os.path.exists(router_pf) \
                    and time.monotonic() - t0 < args.startup_timeout:
                time.sleep(0.1)
            with open(router_pf) as fh:
                router_port = int(fh.read().strip())
            wait_ready("127.0.0.1", router_port, router,
                       args.startup_timeout)

            rng = np.random.default_rng(0)
            pool = rng.integers(
                0, 256, (max(64, 2 * args.imgs_per_request), args.image,
                         args.image, 3),
                dtype=np.uint8).astype(np.float32)
            digests = [digest_a, digest_b]

            def run_arm(name: str) -> dict:
                target = (f"127.0.0.1:{router_port}" if name == "routed"
                          else f"127.0.0.1:{replica_ports[0]}")
                row = run_router_load(
                    target, pool, args.seconds_per_arm,
                    args.imgs_per_request, digests, args.concurrency)
                row["arm"] = name
                return row

            # paired alternating arm order + medians: the 1-core A/B
            # discipline (ordering effects are first-order here)
            rounds = []
            for i in range(max(1, args.pairs)):
                order = (("direct", "routed") if i % 2 == 0
                         else ("routed", "direct"))
                for name in order:
                    rounds.append(run_arm(name))

            meds = {}
            for name in ("direct", "routed"):
                rows = [r for r in rounds if r["arm"] == name]
                meds[name] = {
                    "rps_median": round(_median(
                        [r["rps"] for r in rows]), 1),
                    "p50_ms_median": round(_median(
                        [r["latency_ms"]["p50"] for r in rows]), 3),
                    "p99_ms_median": round(_median(
                        [r["latency_ms"]["p99"] for r in rows]), 3),
                    "requests_ok": sum(r["requests_ok"] for r in rows),
                    "requests_failed": sum(r["requests_failed"]
                                           for r in rows),
                }
            ratio = (meds["routed"]["rps_median"]
                     / meds["direct"]["rps_median"]
                     if meds["direct"]["rps_median"] else None)
            _s, _h, stats_body = _http("127.0.0.1", router_port, "GET",
                                       "/stats", timeout=10.0)
            topology = json.loads(stats_body)
            out = {
                "metric": "serve_router_paired_rps",
                "platform": "cpu",  # every replica is pinned to it
                "replicas": args.replicas,
                "pairs": args.pairs,
                "seconds_per_arm": args.seconds_per_arm,
                "image": args.image,
                "imgs_per_request": args.imgs_per_request,
                "concurrency": args.concurrency,
                "digests": digests,
                "arms": meds,
                "routed_over_direct_rps": (round(ratio, 3)
                                           if ratio else None),
                "affinity": topology.get("affinity"),
                "router_topology": topology,
                "rounds": rounds,
                # the 1-core caveat, stamped not implied: every process
                # shares one core, so routed/direct ratios here measure
                # PLUMBING overhead, not fleet scaling — multi-host
                # replicas are where routed ~ N x direct appears
                "single_core_caveat": True,
                **telemetry_stamp(contention=contention),
            }
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

    print(json.dumps(out))
    ok = bool(out) and out["arms"]["routed"]["requests_ok"] > 0 \
        and out["arms"]["direct"]["requests_ok"] > 0
    return 0 if ok else 4


if __name__ == "__main__":
    raise SystemExit(main())
