#!/usr/bin/env python
"""Serving data-plane hotpath bench (``make bench-serve-hotpath``).

Measures the per-request HOST overhead the zero-copy data plane
removes, as two real ``serve_cli`` replicas under saturating
closed-loop HTTP load:

- **legacy**: default replica (no donation, no double-buffering),
  clients speak the npz wire format over a FRESH TCP connection per
  request — the pre-zero-copy client shape, byte for byte;
- **zerocopy**: ``--donate --double-buffer`` replica, clients speak
  the raw tensor wire format (FAAR1) over pooled keep-alive
  connections (``wire.ConnectionPool``).

Host overhead is taken from the replica's own instrumentation, not
inferred from wall latency: each round snapshots
``faa_serve_stage_seconds_sum{stage=}`` before and after the load
window and charges the HOST-side stages (decode + pad + h2d + scatter
+ serialize) per request served in that window.  ``queue_wait`` and
``dispatch`` are excluded — queueing and device time are what the
overhead rides on top of, and in the pipelined (double-buffered)
replica the dispatch wall includes overlap wait by design.

Arms run as PAIRED ALTERNATING rounds (legacy,zerocopy /
zerocopy,legacy / ...) with per-arm MEDIANS — the 1-core A/B
discipline (docs/BENCHMARKS.md measurement notes).  Before the load
rounds, one fixed seeded batch is pushed through BOTH replicas in BOTH
wire formats and the four decoded results are compared bitwise — the
acceptance gate that the zero-copy plane (and the raw format) changes
no served byte.

CPU-only: both replicas are spawned with ``JAX_PLATFORMS=cpu`` — two live replicas at once
cannot share one chip (a chip has one owner process), so this is a
host-side drill and its JSON says ``"platform": "cpu"``; it needs an
in-process or device-pinned mode before it can measure the device.

    python tools/bench_serve_hotpath.py [--pairs 3]
        [--seconds-per-arm 2] [--image 8] [--shapes 1,4]
        [--out BENCH_r09_serve_hotpath.json]
"""

from __future__ import annotations

import argparse
import io
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))

from bench_router import _http, _median, wait_port_record, wait_ready

#: one deterministic single-sub policy (exact dispatch — the fast shape)
POLICY = [[["Rotate", 0.5, 0.4], ["Invert", 0.2, 0.0]]]

#: the host-side stages charged as per-request overhead (decode /
#: serialize live in the HTTP front, pad / h2d / scatter around the
#: dispatch); queue_wait and dispatch are the work itself, not overhead
HOST_STAGES = ("decode", "pad", "h2d", "scatter", "serialize")

_SUM_RE = re.compile(
    r'^faa_serve_stage_seconds_sum\{[^}]*stage="([^"]+)"[^}]*\} '
    r'([0-9.eE+-]+)$')
_REQ_RE = re.compile(r"^faa_serve_requests_total(?:\{[^}]*\})? "
                     r"([0-9.eE+-]+)$")


def scrape_stages(host: str, port: int) -> tuple[dict, float]:
    """One ``/metrics`` scrape -> (stage -> seconds-sum, requests
    served).  Missing stages read as 0 (a fresh replica has not lazily
    registered them yet)."""
    _s, _h, body = _http(host, port, "GET", "/metrics", timeout=10.0)
    stages: dict[str, float] = {}
    requests = 0.0
    for line in body.decode().splitlines():
        m = _SUM_RE.match(line)
        if m:
            stages[m.group(1)] = float(m.group(2))
            continue
        m = _REQ_RE.match(line)
        if m:
            requests = float(m.group(1))
    return stages, requests


def run_arm(name: str, port: int, body: bytes, ctype: str, pool,
            seconds: float, concurrency: int) -> dict:
    """One closed-loop load round against one replica: `concurrency`
    client threads re-posting `body` until the window closes.  The
    legacy arm pays a fresh TCP connection per request (pool=None);
    the zerocopy arm reuses pooled keep-alive connections."""
    import numpy as np

    lock = threading.Lock()
    lats: list[float] = []
    failed = [0]
    stop_at = time.perf_counter() + seconds
    headers = {"Content-Type": ctype}

    def client():
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            try:
                if pool is None:
                    status, _h, _d = _http("127.0.0.1", port, "POST",
                                           "/augment", body, headers)
                else:
                    status, _h, _d = pool.request("127.0.0.1", port,
                                                  "POST", "/augment",
                                                  body, headers)
            except OSError:
                with lock:
                    failed[0] += 1
                continue
            wall = time.perf_counter() - t0
            with lock:
                if status == 200:
                    lats.append(wall)
                else:
                    failed[0] += 1

    before, req_before = scrape_stages("127.0.0.1", port)
    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(max(1, concurrency))]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 60.0)
    wall = time.perf_counter() - t_start
    after, req_after = scrape_stages("127.0.0.1", port)

    served = req_after - req_before
    host_s = sum(after.get(s, 0.0) - before.get(s, 0.0)
                 for s in HOST_STAGES)
    lat_ms = np.asarray(lats) * 1e3 if lats else np.asarray([0.0])
    return {
        "arm": name,
        "requests_ok": len(lats),
        "requests_failed": failed[0],
        "rps": round(len(lats) / wall, 1) if wall > 0 else 0.0,
        "latency_ms": {
            "p50": round(float(np.percentile(lat_ms, 50)), 3),
            "p99": round(float(np.percentile(lat_ms, 99)), 3),
        },
        "host_overhead_ms_per_request": (
            round(host_s / served * 1e3, 4) if served else None),
        "host_stage_ms_per_request": {
            s: round((after.get(s, 0.0) - before.get(s, 0.0))
                     / served * 1e3, 4)
            for s in HOST_STAGES} if served else {},
        "requests_served_window": int(served),
    }


def bitwise_probe(ports: dict, images, seeds) -> dict:
    """Push ONE fixed seeded batch through both replicas in both wire
    formats; decode the four results and compare bitwise.  The raw
    format carries the per-image PRNG keys the npz path derives
    server-side (serve_cli ``_seed_keys``), so all four requests name
    the identical device computation."""
    import jax
    import numpy as np

    from fast_autoaugment_tpu.serve import wire

    keys = np.asarray(
        jax.vmap(jax.random.PRNGKey)(
            np.asarray(seeds, np.int64) & 0x7FFFFFFF), np.uint32)

    buf = io.BytesIO()
    np.savez(buf, images=images, seeds=np.asarray(seeds, np.int64))
    npz_body = buf.getvalue()
    raw_body = wire.encode_raw(images, seeds=keys)

    results = {}
    for arm, port in ports.items():
        status, _h, data = _http(
            "127.0.0.1", port, "POST", "/augment", npz_body,
            {"Content-Type": "application/octet-stream"}, timeout=60.0)
        if status != 200:
            raise RuntimeError(f"{arm} npz probe failed: {status}")
        results[(arm, "npz")] = np.asarray(
            np.load(io.BytesIO(data))["images"])
        status, _h, data = _http(
            "127.0.0.1", port, "POST", "/augment", raw_body,
            {"Content-Type": wire.RAW_CONTENT_TYPE}, timeout=60.0)
        if status != 200:
            raise RuntimeError(f"{arm} raw probe failed: {status}")
        out, _k = wire.decode_raw(data)
        results[(arm, "raw")] = np.asarray(out)

    ref = results[("legacy", "npz")]
    verdict = {f"{arm}_{fmt}": bool(np.array_equal(ref, r))
               for (arm, fmt), r in results.items()}
    return {
        "bitwise_match": all(verdict.values()),
        "per_request": verdict,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--pairs", type=int, default=3,
                   help="paired alternating rounds per arm (medians "
                        "reported)")
    p.add_argument("--seconds-per-arm", type=float, default=2.0)
    p.add_argument("--image", type=int, default=8)
    p.add_argument("--shapes", default="1,4")
    p.add_argument("--imgs-per-request", type=int, default=4)
    p.add_argument("--concurrency", type=int, default=4)
    p.add_argument("--max-wait-ms", type=float, default=2.0)
    p.add_argument("--startup-timeout", type=float, default=180.0)
    p.add_argument("--out", default="",
                   help="also write the JSON line here "
                        "(BENCH_r09_serve_hotpath.json)")
    args = p.parse_args(argv)

    from bench import (
        host_contention_stamp,
        refuse_or_flag_contention,
        telemetry_stamp,
    )

    contention = refuse_or_flag_contention(host_contention_stamp())

    import numpy as np

    from fast_autoaugment_tpu.serve import wire

    procs: list[subprocess.Popen] = []
    out = {}
    with tempfile.TemporaryDirectory(prefix="bench_hotpath_") as tmp:
        port_dir = os.path.join(tmp, "replicas")
        policy_path = os.path.join(tmp, "policy.json")
        with open(policy_path, "w") as fh:
            json.dump(POLICY, fh)

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            # ---- the two replicas: identical policy/shapes, the data
            # plane is the only variable
            common = [
                sys.executable, "-m",
                "fast_autoaugment_tpu.serve.serve_cli",
                "--policy", policy_path, "--image", str(args.image),
                "--shapes", args.shapes,
                "--max-wait-ms", str(args.max_wait_ms),
                "--port", "0", "--port-dir", port_dir,
            ]
            procs.append(subprocess.Popen(
                common + ["--host-tag", "legacy"], env=env, cwd=_REPO))
            procs.append(subprocess.Popen(
                common + ["--host-tag", "zerocopy", "--donate",
                          "--double-buffer"], env=env, cwd=_REPO))
            ports = {}
            for i, arm in enumerate(("legacy", "zerocopy")):
                port = wait_port_record(port_dir, arm, procs[i],
                                        args.startup_timeout)
                wait_ready("127.0.0.1", port, procs[i],
                           args.startup_timeout)
                ports[arm] = port

            rng = np.random.default_rng(0)
            images = rng.integers(
                0, 256, (args.imgs_per_request, args.image, args.image,
                         3), dtype=np.uint8)
            seeds = np.arange(args.imgs_per_request)

            # ---- acceptance gate first: both wire formats, both data
            # planes, one seeded batch, bitwise
            bitwise = bitwise_probe(ports, images, seeds)

            # ---- the load bodies (no seeds: the latency rounds reuse
            # the replica's default keys; determinism is the probe's
            # job).  Same pixels on both arms.
            buf = io.BytesIO()
            np.savez(buf, images=images)
            npz_body = buf.getvalue()
            raw_body = wire.encode_raw(images)
            pool = wire.ConnectionPool(
                timeout_s=30.0, max_idle_per_key=max(1, args.concurrency))

            def one_round(name: str) -> dict:
                if name == "legacy":
                    return run_arm(name, ports[name], npz_body,
                                   "application/octet-stream", None,
                                   args.seconds_per_arm,
                                   args.concurrency)
                return run_arm(name, ports[name], raw_body,
                               wire.RAW_CONTENT_TYPE, pool,
                               args.seconds_per_arm, args.concurrency)

            # warm both dispatch paths out of the measured windows
            for name, port in ports.items():
                body = npz_body if name == "legacy" else raw_body
                ctype = ("application/octet-stream" if name == "legacy"
                         else wire.RAW_CONTENT_TYPE)
                _http("127.0.0.1", port, "POST", "/augment", body,
                      {"Content-Type": ctype}, timeout=60.0)

            rounds = []
            for i in range(max(1, args.pairs)):
                order = (("legacy", "zerocopy") if i % 2 == 0
                         else ("zerocopy", "legacy"))
                for name in order:
                    rounds.append(one_round(name))

            meds = {}
            for name in ("legacy", "zerocopy"):
                rows = [r for r in rounds if r["arm"] == name]
                ovh = [r["host_overhead_ms_per_request"] for r in rows
                       if r["host_overhead_ms_per_request"] is not None]
                meds[name] = {
                    "rps_median": round(_median(
                        [r["rps"] for r in rows]), 1),
                    "p50_ms_median": round(_median(
                        [r["latency_ms"]["p50"] for r in rows]), 3),
                    "p99_ms_median": round(_median(
                        [r["latency_ms"]["p99"] for r in rows]), 3),
                    "host_overhead_ms_median": round(_median(ovh), 4),
                    "requests_ok": sum(r["requests_ok"] for r in rows),
                    "requests_failed": sum(r["requests_failed"]
                                           for r in rows),
                }
            ratio = (meds["legacy"]["host_overhead_ms_median"]
                     / meds["zerocopy"]["host_overhead_ms_median"]
                     if meds["zerocopy"]["host_overhead_ms_median"]
                     else None)
            out = {
                "metric": "serve_hotpath_host_overhead",
                "platform": "cpu",  # both replicas are pinned to it
                "pairs": args.pairs,
                "seconds_per_arm": args.seconds_per_arm,
                "image": args.image,
                "shapes": args.shapes,
                "imgs_per_request": args.imgs_per_request,
                "concurrency": args.concurrency,
                "host_stages": list(HOST_STAGES),
                "arms": meds,
                "legacy_over_zerocopy_host_overhead": (
                    round(ratio, 2) if ratio else None),
                "client_connections": pool.stats(),
                **bitwise,
                "rounds": rounds,
                # every process shares one core: absolute rps is
                # plumbing-level; the per-request host-overhead ratio
                # is the portable number (docs/BENCHMARKS.md)
                "single_core_caveat": True,
                **telemetry_stamp(contention=contention),
            }
            pool.close_all()
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

    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    ok = bool(out) and out.get("bitwise_match") \
        and out["arms"]["legacy"]["requests_ok"] > 0 \
        and out["arms"]["zerocopy"]["requests_ok"] > 0
    return 0 if ok else 4


if __name__ == "__main__":
    raise SystemExit(main())
