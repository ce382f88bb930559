"""THE ISSUE-15 controller-crash drill (slow): a live 3-replica routed
fleet, drift injected, the controller SIGKILLed MID-CANARY — then
``control_cli --resume`` reconstructs the dangling episode from the
journal WAL and drives it to a clean journaled promote with no
dangling router split and ZERO dropped requests.

The un-resumed world is pinned as the regression shape: after the
SIGKILL the router's canary split is still armed with nobody scoring
it — the traffic-split-forever failure ``--resume`` exists to end.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow


def _http(host, port, method, path, body=None, headers=None,
          timeout=60.0):
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(method, path, body=body, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def _read_journal(tel_dir):
    import glob

    records = []
    for path in sorted(glob.glob(
            os.path.join(tel_dir, "**", "journal-*.jsonl"),
            recursive=True)):
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and "type" in rec:
                    records.append(rec)
    return records


def test_controller_sigkilled_mid_canary_resumes_to_promote(tmp_path):
    from fast_autoaugment_tpu.control.research import policy_file_digest

    tmp = str(tmp_path)
    tel_dir = os.path.join(tmp, "telemetry")
    port_dir = os.path.join(tmp, "replicas")
    baseline_policy = os.path.join(tmp, "baseline.json")
    candidate_policy = os.path.join(tmp, "candidate.json")
    with open(baseline_policy, "w") as fh:
        json.dump([[["Rotate", 0.5, 0.4], ["Invert", 0.2, 0.0]]], fh)
    with open(candidate_policy, "w") as fh:
        json.dump([[["ShearX", 0.9, 0.1], ["Solarize", 0.3, 0.7]]], fh)
    baseline_digest = policy_file_digest(baseline_policy)
    candidate_digest = policy_file_digest(candidate_policy)

    def _ctl_cmd(extra):
        return [sys.executable, "-m",
                "fast_autoaugment_tpu.launch.control_cli",
                "--telemetry", tel_dir, "--port-dir", port_dir,
                "--router-url", f"http://127.0.0.1:{router_port}",
                "--baseline-policy", baseline_policy,
                "--candidate-policy", candidate_policy,
                "--baseline-samples", "10",
                "--canary-replicas", "1", "--split-every", "2",
                "--quality-margin", "10",
                "--min-arm-dispatches", "1",
                "--reload-timeout", "600"] + extra

    procs = []
    failures = []
    ok_rows = []
    stop = threading.Event()
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   FAA_FAULT="drift@dispatch=12,shift=60")
        env.pop("FAA_TELEMETRY", None)
        for i in range(3):
            procs.append(subprocess.Popen([
                sys.executable, "-m",
                "fast_autoaugment_tpu.serve.serve_cli",
                "--policy", baseline_policy, "--image", "8",
                "--shapes", "1,8", "--max-wait-ms", "2",
                "--dispatch", "exact",
                "--traffic-stats", "--telemetry", tel_dir,
                "--port", "0", "--port-dir", port_dir,
                "--host-tag", f"replica{i}",
            ], env=dict(env, FAA_HOST_ID=str(i)), cwd=_REPO))
        from plane_helpers import wait_port_record, wait_ready

        ports = []
        for i in range(3):
            port = wait_port_record(port_dir, f"replica{i}", procs[i],
                                    600.0)
            wait_ready("127.0.0.1", port, procs[i], 600.0)
            ports.append(port)

        router_pf = os.path.join(tmp, "router.port")
        router_env = dict(env)
        router_env.pop("FAA_FAULT", None)
        router = subprocess.Popen([
            sys.executable, "-m",
            "fast_autoaugment_tpu.serve.router_cli",
            "--port-dir", port_dir, "--port", "0",
            "--port-file", router_pf, "--poll-interval", "0.2",
            "--telemetry", tel_dir,
        ], env=router_env, cwd=_REPO)
        procs.append(router)
        t0 = time.monotonic()
        while not os.path.exists(router_pf) \
                and time.monotonic() - t0 < 120:
            time.sleep(0.1)
        with open(router_pf) as fh:
            router_port = int(fh.read().strip())
        wait_ready("127.0.0.1", router_port, router, 120.0)

        # ---- continuous traffic, across the controller's death ------
        rng = np.random.default_rng(0)
        pool = rng.integers(0, 256, (64, 8, 8, 3),
                            dtype=np.uint8).astype(np.float32)

        def _traffic():
            import io

            i = 0
            while not stop.is_set():
                batch = pool[(4 * i) % 48:(4 * i) % 48 + 4]
                buf = io.BytesIO()
                np.savez(buf, images=batch)
                try:
                    status, _h, _b = _http(
                        "127.0.0.1", router_port, "POST", "/augment",
                        body=buf.getvalue(), timeout=120.0)
                except OSError as e:
                    failures.append(f"transport: {e}")
                    continue
                if status == 200:
                    ok_rows.append(time.time())
                else:
                    failures.append(f"status {status}")
                i += 1

        client = threading.Thread(target=_traffic, daemon=True)
        client.start()

        # ---- controller #1: a WIDE gate window so the kill lands ----
        ctl_env = dict(env)
        ctl_env.pop("FAA_FAULT", None)
        ctl = subprocess.Popen(
            _ctl_cmd(["--gate-polls", "40", "--gate-timeout-polls",
                      "200", "--poll-interval", "0.5"]),
            env=ctl_env, cwd=_REPO)
        procs.append(ctl)

        # wait for the canary split to be ARMED on the live router
        deadline = time.monotonic() + 600
        armed = None
        while time.monotonic() < deadline and armed is None:
            assert ctl.poll() is None, "controller died before canary"
            _s, _h, body = _http("127.0.0.1", router_port, "GET",
                                 "/stats")
            armed = (json.loads(body) or {}).get("canary")
            time.sleep(0.2)
        assert armed is not None, "canary split never armed"
        assert armed["digest"] == candidate_digest

        # ---- SIGKILL mid-canary ------------------------------------
        ctl.kill()
        ctl.wait(timeout=30)
        time.sleep(1.0)

        # THE pre-fix regression shape, pinned: the dead controller
        # left the router splitting traffic with NOBODY scoring the
        # canary arm — and nothing in the system will ever clear it
        _s, _h, body = _http("127.0.0.1", router_port, "GET", "/stats")
        dangling = (json.loads(body) or {}).get("canary")
        assert dangling is not None, \
            "expected a DANGLING canary split after the controller kill"
        assert dangling["digest"] == candidate_digest

        # ---- controller #2: --resume -------------------------------
        stats_file = os.path.join(tmp, "resume_stats.json")
        ctl2 = subprocess.Popen(
            _ctl_cmd(["--gate-polls", "2", "--poll-interval", "0.3",
                      "--resume", "--stats-file", stats_file]),
            env=ctl_env, cwd=_REPO)
        procs.append(ctl2)

        deadline = time.monotonic() + 600
        promote = None
        while time.monotonic() < deadline and promote is None:
            assert ctl2.poll() is None, "resumed controller died"
            evs = _read_journal(tel_dir)
            promote = next((r for r in evs if r["type"] == "promote"),
                           None)
            time.sleep(0.5)
        assert promote is not None, "the resumed loop never promoted"
        time.sleep(2.0)
        stop.set()
        client.join(timeout=120)
        ctl2.send_signal(15)
        ctl2.wait(timeout=60)

        # no dangling split: the resumed episode TERMINATED
        _s, _h, body = _http("127.0.0.1", router_port, "GET", "/stats")
        assert (json.loads(body) or {}).get("canary") is None

        # fleet-wide on the promoted candidate
        for i, port in enumerate(ports):
            _s, _h, body = _http("127.0.0.1", port, "GET", "/stats")
            st = json.loads(body)
            assert st["policy_digest"] == candidate_digest, f"replica{i}"
    finally:
        stop.set()
        for proc in reversed(procs):
            if proc.poll() is None:
                try:
                    proc.send_signal(15)
                except ProcessLookupError:
                    pass
        deadline = time.monotonic() + 60
        for proc in procs:
            left = max(1.0, deadline - time.monotonic())
            try:
                proc.wait(timeout=left)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)

    # ---- ZERO dropped requests through kill + resume + promote ------
    assert not failures, failures[:10]
    assert len(ok_rows) > 20

    # ---- the WAL story: canary ... resume(canary) ... promote -------
    evs = _read_journal(tel_dir)
    resumes = [r for r in evs if r["type"] == "mark"
               and r.get("event") == "resume"]
    assert resumes and resumes[0]["stage"] == "canary"
    assert resumes[0]["digest"] == candidate_digest
    promote = next(r for r in evs if r["type"] == "promote")
    assert promote["digest"] == candidate_digest
    assert promote["digest"] != baseline_digest
    # one drift episode end to end: detected pre-crash, promoted
    # post-resume by a DIFFERENT process
    drift = next(r for r in evs if r["type"] == "drift")
    assert promote["drift_id"] == drift["id"]
    assert promote["pid"] != drift["pid"]
    stats = json.load(open(stats_file))
    assert stats["promotes"] == 1 and stats["rollbacks"] == 0
    assert stats["state"] == "watching"
