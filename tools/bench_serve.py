"""AOT policy-serving benchmark: p50/p99 latency + imgs/s at fixed
offered QPS (``make bench-serve``), and the OVERLOAD drill
(``make bench-overload``).

Drives the real serving pair — :class:`AotPolicyApplier` (AOT-compiled
padded-shape executables) behind :class:`PolicyServer` (batch
coalescing) — with an OPEN-LOOP arrival process at ``--qps``: requests
are submitted on a fixed schedule regardless of completion (the
heavy-traffic model; a closed loop would hide queueing collapse).  One
JSON line reports:

- ``latency_ms``: p50/p90/p99/max submit-to-scatter per request;
- ``images_per_sec``: achieved serving throughput over the run;
- ``aot_compile_sec`` per shape + the unified ``compile_cache`` block
  (a re-run deserializes the executables from the persistent cache —
  the warm-start story applied to serving);
- ``serve_robustness``: the admission/shed/breaker/reload counters
  (docs/RESILIENCE.md "Serving under overload");
- the standard contention + shadow-watchdog stamps, plus a per-run
  ``bitwise_match`` re-verification that exact-dispatch served outputs
  equal direct ``apply_policy`` application.

``--overload`` sweeps offered QPS PAST capacity (calibrated
closed-loop, then ``--multipliers`` x capacity) twice — shedding ON
(bounded queue + per-request deadline + adaptive-LIFO watermarks) vs
OFF (the unbounded clean-weather config) — and reports per arm:
goodput (admitted requests completing within the deadline, per
second), shed rate, deadline-miss rate of admitted, and p50/p99 of
ADMITTED requests.  The acceptance shape: with shedding on, goodput
holds near the clean-weather plateau while p99-of-admitted stays
bounded; with shedding off, every request "succeeds" into a queue
whose latency has already collapsed past the deadline.

    python tools/bench_serve.py [--qps 200] [--seconds 5] [--image 32]
        [--dispatch auto] [--shapes 1,8,32,128]
    python tools/bench_serve.py --overload [--multipliers 1,2,4]
        [--deadline-ms 100] [--overload-seconds 2]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def synthetic_policy(num_sub: int, num_op: int):
    """Deterministic multi-sub policy shaped like a search result (ops
    cycle through the searchable table, probs/levels spread)."""
    import numpy as np

    rows = []
    for i in range(num_sub):
        rows.append([[(i * num_op + j) % 15, 0.4 + 0.1 * (i % 5),
                      0.2 + 0.15 * ((i + j) % 5)]
                     for j in range(num_op)])
    return np.asarray(rows, np.float32)


def verify_bitwise(applier, images, keys) -> bool:
    """Exact-dispatch acceptance: served == direct apply_policy, bitwise
    (grouped dispatch is checked against its own batch kernel)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fast_autoaugment_tpu.ops.augment import (
        apply_policy,
        apply_policy_batch_grouped,
    )

    got = applier.apply(images, keys)
    if applier.dispatch == "exact":
        ref = np.stack([
            np.asarray(apply_policy(
                jnp.asarray(images[i], jnp.float32),
                applier.policy, jnp.asarray(keys[i])))
            for i in range(images.shape[0])])
    else:
        from fast_autoaugment_tpu.serve.policy_server import pick_shape

        s = pick_shape(applier.shapes, images.shape[0])
        padded = np.zeros((s,) + images.shape[1:], np.float32)
        padded[:images.shape[0]] = images
        ref = np.asarray(apply_policy_batch_grouped(
            jnp.asarray(padded), applier.policy, jnp.asarray(keys),
            groups=applier.groups))[:images.shape[0]]
    return bool(np.array_equal(got, ref))


def run_offered_load(server, images_pool, qps: float, seconds: float,
                     imgs_per_request: int):
    """Open-loop offered load: submit on schedule, collect latencies."""
    import numpy as np

    n_requests = max(1, int(qps * seconds))
    interval = 1.0 / qps
    pending = []
    t0 = time.perf_counter()
    for i in range(n_requests):
        sched = t0 + i * interval
        now = time.perf_counter()
        if sched > now:
            time.sleep(sched - now)
        lo = (i * imgs_per_request) % (images_pool.shape[0]
                                       - imgs_per_request + 1)
        pending.append(server.submit(images_pool[lo:lo + imgs_per_request]))
    for p in pending:
        server.result(p, timeout=120.0)
    t_end = max(p.t_done for p in pending)
    lat_ms = np.asarray([p.latency() * 1e3 for p in pending])
    total_imgs = sum(p.n for p in pending)
    return {
        "requests": n_requests,
        "qps_offered": round(qps, 1),
        "qps_achieved": round(n_requests / (t_end - t0), 1),
        "images_per_sec": round(total_imgs / (t_end - t0), 1),
        "latency_ms": {
            "p50": round(float(np.percentile(lat_ms, 50)), 3),
            "p90": round(float(np.percentile(lat_ms, 90)), 3),
            "p99": round(float(np.percentile(lat_ms, 99)), 3),
            "max": round(float(lat_ms.max()), 3),
        },
    }


def _robustness_stamp(stats: dict) -> dict:
    """The flat serve-robustness block every bench JSON line carries
    (admitted/shed/expired/breaker_fires/reloads — BENCH rounds track
    them alongside latency)."""
    adm = stats.get("admission", {})
    brk = stats.get("breaker", {})
    # mean coalesced batch over DISPATCHED work only: images_served /
    # dispatches counts requests the coalescer actually batched — shed
    # and expired requests never reach a dispatch, so an offered-load
    # denominator would understate batch efficiency under overload
    disp = stats.get("dispatches", 0)
    mean_coalesced = (round(stats.get("images_served", 0) / disp, 2)
                      if disp else None)
    return {
        "mean_coalesced_batch": mean_coalesced,
        "admitted": adm.get("admitted", 0),
        "shed_overload": adm.get("shed_overload", 0),
        "shed_breaker": adm.get("shed_breaker", 0),
        "expired": adm.get("expired", 0),
        "deadline_misses": adm.get("deadline_misses", 0),
        "lifo_takes": adm.get("lifo_takes", 0),
        "breaker_fires": brk.get("fires", 0),
        "breaker_state": brk.get("state", "disabled"),
        "reloads": stats.get("reloads", 0),
    }


def _parse_addr(url: str) -> tuple[str, int]:
    from urllib.parse import urlparse

    u = urlparse(url if "//" in url else f"http://{url}")
    return u.hostname or "127.0.0.1", int(u.port or 80)


def _http(host: str, port: int, method: str, path: str, body=None,
          headers=None, timeout: float = 30.0):
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(method, path, body=body, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def run_router_load(router_url: str, images_pool, seconds: float,
                    imgs_per_request: int, digests: list[str],
                    concurrency: int = 4) -> dict:
    """Closed-loop HTTP load through a serving-plane ROUTER: N client
    threads alternate requests across `digests` (mixed multi-policy
    traffic), HONOR ``Retry-After`` on 429/503 instead of hot
    retrying, and collect end-to-end latencies.  The result stamps the
    router's own topology + affinity accounting (``GET /stats``) so
    the JSON line records WHICH fleet served the numbers."""
    import io
    import threading

    import numpy as np

    from fast_autoaugment_tpu.serve import wire

    host, port = _parse_addr(router_url)
    buf = io.BytesIO()
    np.savez(buf, images=images_pool[:imgs_per_request].astype(np.uint8))
    body = buf.getvalue()
    lat_lock = threading.Lock()
    lats: list[float] = []
    outcomes = {"ok": 0, "retried": 0, "failed": 0}
    stop_at = time.perf_counter() + seconds
    # keep-alive clients: each thread reuses pooled connections instead
    # of paying a TCP handshake per request (wire.ConnectionPool)
    pool = wire.ConnectionPool(timeout_s=30.0,
                               max_idle_per_key=max(1, concurrency))

    def client(idx: int):
        k = idx
        while time.perf_counter() < stop_at:
            headers = {}
            if digests:
                headers["X-FAA-Policy-Digest"] = digests[k % len(digests)]
            k += 1
            t0 = time.perf_counter()
            try:
                status, rheaders, _data = pool.request(
                    host, port, "POST", "/augment", body, headers)
            except OSError:
                with lat_lock:
                    outcomes["failed"] += 1
                continue
            if status in (429, 503):
                # the Retry-After contract: back off what the plane
                # asked for, never hot-retry
                try:
                    ra = float(rheaders.get("Retry-After", "1") or 1)
                except ValueError:
                    ra = 1.0
                with lat_lock:
                    outcomes["retried"] += 1
                time.sleep(min(ra, 2.0))
                continue
            wall = time.perf_counter() - t0
            with lat_lock:
                if status == 200:
                    outcomes["ok"] += 1
                    lats.append(wall)
                else:
                    outcomes["failed"] += 1

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(max(1, concurrency))]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 60.0)
    wall = time.perf_counter() - t_start
    lat_ms = np.asarray(lats) * 1e3 if lats else np.asarray([0.0])
    conn_stats = pool.stats()
    pool.close_all()
    row = {
        "requests_ok": outcomes["ok"],
        "client_connections": conn_stats,
        "requests_retried": outcomes["retried"],
        "requests_failed": outcomes["failed"],
        "rps": round(outcomes["ok"] / wall, 1) if wall > 0 else 0.0,
        "latency_ms": {
            "p50": round(float(np.percentile(lat_ms, 50)), 3),
            "p99": round(float(np.percentile(lat_ms, 99)), 3),
            "max": round(float(lat_ms.max()), 3),
        },
    }
    # the router-topology stamp: which replicas, what rotation, what
    # affinity hit rate produced these numbers
    try:
        status, _h, data = _http(host, port, "GET", "/stats", timeout=10.0)
        if status == 200:
            row["router_topology"] = json.loads(data)
    except (OSError, ValueError):
        row["router_topology"] = None
    return row


def calibrate_capacity(make_server, images_pool, imgs_per_request: int,
                       seconds: float = 0.75) -> float:
    """Closed-loop capacity estimate: keep ``2 x max_batch`` requests
    in flight for `seconds`, return achieved requests/s — the
    saturation throughput the overload multipliers scale from.

    A 429 (typed overload rejection) is honored the way a production
    client honors it: BACK OFF ``retry_after_s`` before re-offering.
    The old immediate hot retry hammered the admission path in a tight
    loop, inflating the replica's shed counters during calibration and
    biasing the measured capacity downward (admission-path contention
    on this 1-core host)."""
    from fast_autoaugment_tpu.serve.policy_server import (
        ServerOverloadedError,
    )

    server = make_server()
    try:
        n_window = max(2, 2 * server.max_batch)
        done = 0
        t0 = time.perf_counter()
        inflight = []
        while time.perf_counter() - t0 < seconds:
            while len(inflight) < n_window:
                lo = done % (images_pool.shape[0] - imgs_per_request + 1)
                try:
                    inflight.append(server.submit(
                        images_pool[lo:lo + imgs_per_request]))
                except ServerOverloadedError as e:
                    # honor Retry-After instead of re-offering hot
                    time.sleep(min(e.retry_after_s, 0.25))
                    continue
                done += 1
            server.result(inflight.pop(0), timeout=60.0)
        for p in inflight:
            server.result(p, timeout=60.0)
        wall = time.perf_counter() - t0
        return done / wall
    finally:
        server.stop()


def run_overload_arm(server, images_pool, qps: float, seconds: float,
                     imgs_per_request: int, deadline_ms: float,
                     shed: bool) -> dict:
    """One overload arm: open-loop offered load at `qps`, submissions
    never block (typed rejections counted as shed), goodput = admitted
    requests completing WITHIN the deadline."""
    import numpy as np

    from fast_autoaugment_tpu.serve.policy_server import ServeError

    n_requests = max(1, int(qps * seconds))
    interval = 1.0 / qps
    admitted, shed_n = [], 0
    t0 = time.perf_counter()
    for i in range(n_requests):
        sched = t0 + i * interval
        now = time.perf_counter()
        if sched > now:
            time.sleep(sched - now)
        lo = (i * imgs_per_request) % (images_pool.shape[0]
                                       - imgs_per_request + 1)
        try:
            # shedding-on stamps the deadline; the off arm submits the
            # clean-weather way (no deadline, unbounded queue)
            admitted.append(server.submit(
                images_pool[lo:lo + imgs_per_request],
                deadline_ms=deadline_ms if shed else None))
        except ServeError:
            shed_n += 1
    good_lat, completed_lat, miss_n = [], [], 0
    for p in admitted:
        try:
            server.result(p, timeout=120.0)
        except ServeError:
            miss_n += 1  # shed in queue (deadline) or failed
            continue
        except TimeoutError:
            miss_n += 1
            continue
        lat = p.latency()
        completed_lat.append(lat)
        if lat * 1e3 <= deadline_ms:
            good_lat.append(lat)
        else:
            miss_n += 1  # completed, but past the deadline budget
    wall = (max((p.t_done for p in admitted), default=time.perf_counter())
            - t0)
    # percentiles over requests that were admitted AND served — a shed
    # request's t_done is its error delivery, not a service latency
    lat_ms = (np.asarray(completed_lat) * 1e3 if completed_lat
              else np.asarray([0.0]))
    return {
        "shedding": "on" if shed else "off",
        "qps_offered": round(qps, 1),
        "requests_offered": n_requests,
        "admitted": len(admitted),
        "shed": shed_n,
        "shed_rate": round(shed_n / n_requests, 4),
        "goodput_rps": round(len(good_lat) / wall, 1) if wall > 0 else 0.0,
        "deadline_miss_rate": (round(miss_n / len(admitted), 4)
                               if admitted else 0.0),
        "admitted_latency_ms": {
            "p50": round(float(np.percentile(lat_ms, 50)), 3),
            "p99": round(float(np.percentile(lat_ms, 99)), 3),
            "max": round(float(lat_ms.max()), 3),
        },
    }


def run_overload(args, applier, pool) -> dict:
    """The full overload sweep: calibrate capacity, then every
    multiplier x capacity with shedding on and off."""
    from fast_autoaugment_tpu.serve.policy_server import PolicyServer

    # the drill serves ONE request per dispatch (requests carry
    # --overload-imgs-per-request images, default 32): with full
    # coalescing of 1-image requests this host's submit loop cannot
    # offer more than the device serves and nothing ever queues — the
    # drill is about queue behavior, not batching efficiency
    imgs_per_request = max(1, args.overload_imgs_per_request)
    max_batch = max(imgs_per_request, args.overload_max_batch)

    def make_server(shed: bool = False):
        if shed:
            return PolicyServer(
                applier, max_batch=max_batch,
                max_wait_ms=args.max_wait_ms,
                queue_depth=args.overload_queue_depth,
                default_deadline_ms=args.deadline_ms,
                lifo_depth=max(2, args.overload_queue_depth // 2),
                lifo_age_ms=args.deadline_ms / 2).start()
        return PolicyServer(applier, max_batch=max_batch,
                            max_wait_ms=args.max_wait_ms).start()

    capacity = calibrate_capacity(lambda: make_server(False), pool,
                                  imgs_per_request)
    multipliers = [float(m) for m in str(args.multipliers).split(",") if m]
    rows = []
    last_stats = {}
    for shed in (True, False):
        for m in multipliers:
            server = make_server(shed)
            try:
                row = run_overload_arm(
                    server, pool, m * capacity, args.overload_seconds,
                    imgs_per_request, args.deadline_ms, shed)
            finally:
                stats = server.stats()
                server.stop()
            row["multiplier"] = m
            row["serve_robustness"] = _robustness_stamp(stats)
            rows.append(row)
            last_stats = stats
    return {
        "capacity_qps": round(capacity, 1),
        "deadline_ms": args.deadline_ms,
        "imgs_per_request": imgs_per_request,
        "overload_queue_depth": args.overload_queue_depth,
        "arms": rows,
        "serving": last_stats,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--policy", default=None,
                   help="final_policy.json / archive name (default: a "
                        "deterministic synthetic --num-sub policy)")
    p.add_argument("--num-sub", type=int, default=5)
    p.add_argument("--num-op", type=int, default=2)
    p.add_argument("--image", type=int, default=32)
    p.add_argument("--shapes", default="1,8,32,128")
    p.add_argument("--dispatch", default="auto",
                   choices=("auto", "exact", "grouped"))
    p.add_argument("--groups", type=int, default=8)
    p.add_argument("--max-wait-ms", type=float, default=5.0)
    p.add_argument("--qps", type=float, default=200.0)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--imgs-per-request", type=int, default=1)
    # --------------------------------------------------- router mode
    p.add_argument("--router", default=None, metavar="URL",
                   help="measure THROUGH a serving-plane router "
                        "(router_cli) instead of an in-process server: "
                        "closed-loop HTTP clients honoring Retry-After, "
                        "with the router topology + affinity stamp in "
                        "the JSON line (docs/SERVING.md)")
    p.add_argument("--router-digests", default="",
                   help="comma-separated policy digests to alternate "
                        "across requests (mixed multi-policy traffic); "
                        "empty = no digest header (default policy)")
    p.add_argument("--router-concurrency", type=int, default=4,
                   help="closed-loop client threads in --router mode")
    # ------------------------------------------------- overload drill
    p.add_argument("--overload", action="store_true",
                   help="sweep offered QPS past calibrated capacity, "
                        "shedding on vs off (make bench-overload)")
    p.add_argument("--multipliers", default="1,2,4",
                   help="offered-QPS multipliers over calibrated capacity")
    p.add_argument("--deadline-ms", type=float, default=100.0,
                   help="per-request deadline budget in the overload "
                        "drill (shed + goodput reference)")
    p.add_argument("--overload-seconds", type=float, default=2.0,
                   help="seconds of offered load per overload arm")
    p.add_argument("--overload-queue-depth", type=int, default=64,
                   help="bounded queue depth for the shedding-on arms")
    p.add_argument("--overload-max-batch", type=int, default=1,
                   help="coalescer cap during the drill (defaults to the "
                        "per-request image count = one request per "
                        "dispatch, so offered load can actually exceed "
                        "served capacity on a small host)")
    p.add_argument("--overload-imgs-per-request", type=int, default=32,
                   help="images per request in the drill: enough device "
                        "work per dispatch that the open-loop generator "
                        "can out-offer the served rate")
    args = p.parse_args(argv)

    from bench import (
        host_contention_stamp,
        refuse_or_flag_contention,
        telemetry_stamp,
    )

    contention = refuse_or_flag_contention(host_contention_stamp())

    if args.router:
        # host-only HTTP client mode: the plane (router + replicas) is
        # already up; this process never imports jax
        import numpy as np

        rng = np.random.default_rng(0)
        pool = rng.integers(
            0, 256, (max(64, args.imgs_per_request * 2), args.image,
                     args.image, 3), dtype=np.uint8).astype(np.float32)
        digests = [d for d in str(args.router_digests).split(",") if d]
        load = run_router_load(args.router, pool, args.seconds,
                               args.imgs_per_request, digests,
                               args.router_concurrency)
        out = {
            "metric": "serve_router_latency_ms",
            "router": args.router,
            "image": args.image,
            "imgs_per_request": args.imgs_per_request,
            "digests": digests,
            "seconds": args.seconds,
            **load,
            **telemetry_stamp(contention=contention),
        }
        print(json.dumps(out))
        return 0

    import jax
    import numpy as np

    from fast_autoaugment_tpu.core.compilecache import configure_compile_cache
    from fast_autoaugment_tpu.parallel.mesh import device_stamp
    from fast_autoaugment_tpu.serve.policy_server import (
        AotPolicyApplier,
        PolicyServer,
    )

    # a second bench run deserializes the AOT executables from the
    # persistent cache instead of re-lowering them
    configure_compile_cache()

    if args.policy:
        from fast_autoaugment_tpu.serve.serve_cli import build_policy_tensor

        policy = build_policy_tensor(args.policy)
    else:
        policy = synthetic_policy(args.num_sub, args.num_op)
    shapes = tuple(int(s) for s in str(args.shapes).split(",") if s)

    t0 = time.perf_counter()
    applier = AotPolicyApplier(policy, image=args.image, shapes=shapes,
                               dispatch=args.dispatch, groups=args.groups)
    aot_secs = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    pool = rng.integers(
        0, 256, (max(shapes) * 2, args.image, args.image, 3),
        dtype=np.uint8).astype(np.float32)
    # acceptance re-verification on this exact build: served outputs
    # match the direct kernel bit-for-bit
    n_check = min(3, max(shapes))
    check_keys = (np.stack([np.asarray(jax.random.PRNGKey(i), np.uint32)
                            for i in range(n_check)])
                  if applier.dispatch == "exact"
                  else np.asarray(jax.random.PRNGKey(7), np.uint32))
    bitwise = verify_bitwise(applier, pool[:n_check], check_keys)

    if args.overload:
        # warm the dispatch path once, then run the sweep
        warm = PolicyServer(applier, max_wait_ms=args.max_wait_ms).start()
        warm.augment(pool[:1])
        warm.stop()
        sweep = run_overload(args, applier, pool)
        out = {
            "metric": "serve_overload_goodput",
            **device_stamp(),
            "policy": args.policy or f"synthetic_{args.num_sub}sub",
            "num_sub": int(policy.shape[0]),
            "image": args.image,
            "dispatch": applier.dispatch,
            "shapes": list(applier.shapes),
            "max_wait_ms": args.max_wait_ms,
            "imgs_per_request": args.imgs_per_request,
            **sweep,
            "bitwise_match": bitwise,
            "aot_compile_sec_total": round(aot_secs, 3),
            # unified provenance block (bench.telemetry_stamp)
            **telemetry_stamp(contention=contention),
        }
        print(json.dumps(out))
        return 0 if bitwise else 4

    server = PolicyServer(applier, max_wait_ms=args.max_wait_ms).start()
    # warm the dispatch path (first calls already AOT-compiled)
    server.augment(pool[:1])
    load = run_offered_load(server, pool, args.qps, args.seconds,
                            args.imgs_per_request)
    stats = server.stats()
    server.stop()

    out = {
        "metric": "serve_policy_latency_ms",
        **device_stamp(),
        "policy": args.policy or f"synthetic_{args.num_sub}sub",
        "num_sub": int(policy.shape[0]),
        "image": args.image,
        "dispatch": applier.dispatch,
        "groups": applier.groups,
        "shapes": list(applier.shapes),
        "max_wait_ms": args.max_wait_ms,
        "imgs_per_request": args.imgs_per_request,
        **load,
        "serving": stats,
        "serve_robustness": _robustness_stamp(stats),
        "bitwise_match": bitwise,
        "aot_compile_sec_total": round(aot_secs, 3),
        "aot_compile": {str(s): r for s, r in applier.compile_log.items()},
        # unified provenance block (bench.telemetry_stamp): contention +
        # shadow watchdog + compile cache + registry counters
        **telemetry_stamp(stats.get("mean_dispatch_ms", 0) and
                          [stats["mean_dispatch_ms"] / 1e3] or [],
                          label="serve_dispatch", contention=contention),
    }
    print(json.dumps(out))
    return 0 if bitwise else 4


if __name__ == "__main__":
    raise SystemExit(main())
