"""The serving-plane acceptance drill (slow): a routed 3-replica fleet
with real AOT appliers serving mixed 2-policy traffic with digest
affinity; a COLD third policy warming into the tenancy LRU while warm
traffic keeps completing; one replica killed mid-run ejecting from
rotation with traffic failing over instead of collapsing; SIGTERM
drains at teardown (docs/SERVING.md "Acceptance")."""

from __future__ import annotations

import io
import json
import os
import signal
import subprocess
import sys
import threading
import time
from http.server import ThreadingHTTPServer

import numpy as np
import pytest

from plane_helpers import wait_port_record

from fast_autoaugment_tpu.serve.router import Router
from fast_autoaugment_tpu.serve.router_cli import make_router_handler

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

IMG = 8
POLICY_A = [[["Rotate", 0.5, 0.4], ["Invert", 0.2, 0.0]]]
POLICY_B = [[["ShearX", 0.9, 0.1], ["Solarize", 0.3, 0.7]]]
POLICY_C = [[["Posterize", 0.7, 0.6], ["Contrast", 0.4, 0.5]]]


def _http(port, method, path, body=None, headers=None, timeout=60):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request(method, path, body=body, headers=headers or {})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp, data


def _npz_body(imgs, seeds=None):
    buf = io.BytesIO()
    if seeds is None:
        np.savez(buf, images=imgs.astype(np.uint8))
    else:
        np.savez(buf, images=imgs.astype(np.uint8), seeds=seeds)
    return buf.getvalue()


@pytest.mark.slow
def test_serving_plane_three_replica_drill(tmp_path):
    from fast_autoaugment_tpu.serve.policy_server import policy_digest
    from fast_autoaugment_tpu.serve.serve_cli import build_policy_tensor

    policy_dir = tmp_path / "policies"
    policy_dir.mkdir()
    paths = {}
    for name, spec in (("a", POLICY_A), ("b", POLICY_B), ("c", POLICY_C)):
        p = policy_dir / f"{name}.json"
        p.write_text(json.dumps(spec))
        paths[name] = str(p)
    digests = {name: policy_digest(build_policy_tensor(paths[name]))
               for name in paths}
    assert len(set(digests.values())) == 3

    port_dir = str(tmp_path / "replicas")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = []
    router = None
    httpd = None
    try:
        # ---- 3 replicas: default policy A, tenancy capacity 2,
        # policy-dir recipes for B and C
        for i in range(3):
            env_i = dict(env, FAA_HOST_ID=str(i))
            procs.append(subprocess.Popen([
                sys.executable, "-m",
                "fast_autoaugment_tpu.serve.serve_cli",
                "--policy", paths["a"], "--image", str(IMG),
                "--shapes", "1,4", "--max-wait-ms", "2",
                "--tenant-capacity", "2",
                "--policy-dir", str(policy_dir),
                "--port", "0", "--port-dir", port_dir,
                "--host-tag", f"replica{i}",
            ], env=env_i, cwd=_REPO))
        ports = {}
        for i in range(3):
            ports[f"replica{i}"] = wait_port_record(
                port_dir, f"replica{i}", procs[i])
        # pre-warm policy B everywhere (mixed warm 2-policy traffic)
        for tag, port in ports.items():
            resp, data = _http(port, "POST", "/tenants/warm",
                               body=json.dumps(
                                   {"policy": paths["b"]}).encode(),
                               timeout=180)
            assert resp.status == 200, (tag, data[:300])

        # ---- the router, in-process over the subprocess fleet
        router = Router(port_dir=port_dir, poll_interval_s=0.2,
                        eject_after=2, readmit_after=1,
                        name="e2e").start()
        deadline = time.monotonic() + 60.0
        while len(router.stats()["in_rotation"]) < 3 \
                and time.monotonic() < deadline:
            time.sleep(0.1)
        assert len(router.stats()["in_rotation"]) == 3
        httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                    make_router_handler(router))
        httpd.daemon_threads = True
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        rport = httpd.server_address[1]

        rng = np.random.default_rng(0)
        imgs = rng.integers(0, 256, (2, IMG, IMG, 3), np.uint8)
        body = _npz_body(imgs)

        # ---- mixed 2-policy traffic: every request 200, affinity
        # hit rate >= 95% (clean weather: every request lands on its
        # digest's rendezvous primary)
        for i in range(40):
            d = digests["a"] if i % 2 else digests["b"]
            resp, data = _http(rport, "POST", "/augment", body=body,
                               headers={"X-FAA-Policy-Digest": d})
            assert resp.status == 200, data[:300]
        affinity = router.stats()["affinity"]
        assert affinity["hit_rate"] >= 0.95, affinity

        # ---- cold third policy: first request 503 tenant_cold with
        # warming kicked; it becomes servable while WARM traffic keeps
        # completing with zero errors
        warm_errors = []
        stop = threading.Event()

        def warm_traffic():
            k = 0
            while not stop.is_set():
                d = digests["a"] if k % 2 else digests["b"]
                k += 1
                try:
                    resp, _data = _http(rport, "POST", "/augment",
                                        body=body,
                                        headers={"X-FAA-Policy-Digest":
                                                 d})
                    if resp.status != 200:
                        warm_errors.append(resp.status)
                except OSError as e:
                    warm_errors.append(repr(e))

        wt = threading.Thread(target=warm_traffic, daemon=True)
        wt.start()
        try:
            t0 = time.monotonic()
            status = None
            while time.monotonic() - t0 < 120.0:
                resp, data = _http(rport, "POST", "/augment", body=body,
                                   headers={"X-FAA-Policy-Digest":
                                            digests["c"]})
                status = resp.status
                if status == 200:
                    break
                rec = json.loads(data)
                assert rec.get("type") in ("tenant_cold", "no_replica",
                                           "upstream_unreachable"), rec
                time.sleep(0.5)
            assert status == 200, "cold policy never warmed in"
        finally:
            stop.set()
            wt.join(timeout=30.0)
        assert warm_errors == []  # warm tenants unbothered by the warm

        # ---- kill one replica (the unannounced-death case): it
        # ejects from rotation and traffic fails over — goodput
        # degrades (one fewer replica), availability does not collapse
        victim = procs[0]
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=30)
        deadline = time.monotonic() + 30.0
        while len(router.stats()["in_rotation"]) > 2 \
                and time.monotonic() < deadline:
            time.sleep(0.1)
        st = router.stats()
        assert len(st["in_rotation"]) == 2, st["replicas"]
        ok = 0
        for i in range(30):
            d = digests["a"] if i % 2 else digests["b"]
            resp, _data = _http(rport, "POST", "/augment", body=body,
                                headers={"X-FAA-Policy-Digest": d})
            ok += resp.status == 200
        assert ok == 30  # bounded failover keeps every request alive

        # ---- SIGTERM drain: serving exit contract (exit 0) and the
        # discovery records disappear
        for p in procs[1:]:
            p.send_signal(signal.SIGTERM)
        for p in procs[1:]:
            assert p.wait(timeout=60) == 0
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            left = [n for n in os.listdir(port_dir)
                    if n.endswith(".json")]
            if len(left) <= 1:  # the SIGKILLed record lingers
                break
            time.sleep(0.2)
        assert len([n for n in os.listdir(port_dir)
                    if n.endswith(".json")]) <= 1
    finally:
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        if router is not None:
            router.stop()
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
