"""Closed-loop control plane, end to end (ISSUE 14, slow):

- the driver-level warm-start contract: ``topup_trials=0`` resume
  reproduces the one-shot ``final_policy.json`` byte-identically, and
  a top-up extends the trial log without touching the base entries;
- THE acceptance drill: a live 3-replica routed fleet under FAA_FAULT
  ``drift@...`` injection runs detect -> warm-started re-search (a
  real ``search_cli --topup-trials`` subprocess) -> canary -> promote
  with ZERO dropped requests during rollover, ``make trace`` rendering
  the whole causal chain from one journal, and ``make status``
  summarizing it.

Everything here is compile-heavy and slow-marked (the 870s tier-1
wall); the host-only logic is covered by tests/test_control.py.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow


def _tiny_conf():
    from fast_autoaugment_tpu.core.config import Config

    return Config({
        "model": {"type": "wresnet10_1"},
        "dataset": "synthetic",
        "aug": "default",
        "cutout": 8,
        "batch": 8,
        "epoch": 1,
        "lr": 0.05,
        "lr_schedule": {"type": "cosine"},
        "optimizer": {"type": "sgd", "decay": 1e-4, "clip": 5.0,
                      "momentum": 0.9, "nesterov": True},
    })


CONF_YAML = (
    "model:\n  type: wresnet10_1\ndataset: synthetic\naug: default\n"
    "cutout: 8\nbatch: 8\nepoch: 1\nlr: 0.05\n"
    "lr_schedule:\n  type: cosine\n"
    "optimizer:\n  type: sgd\n  decay: 0.0001\n  momentum: 0.9\n"
    "  nesterov: true\n")


def test_warm_start_topup_driver_contract(tmp_path):
    """``search_policies(topup_trials=...)`` through the ledger warm
    start: zero top-up = byte-identical final_policy.json (the
    no-drift defaults pin), a real top-up extends the log with the
    base prefix untouched and stamps ``warm_start``."""
    from fast_autoaugment_tpu.control.research import warm_started_research
    from fast_autoaugment_tpu.search.driver import search_policies

    conf = _tiny_conf()
    common = dict(cv_num=1, cv_ratio=0.4, num_policy=1, num_op=1,
                  num_search=5, num_top=2, trial_batch=2,
                  async_pipeline="on", pipeline_actors=1,
                  pipeline_queue_depth=1, fold_quality_floor=None,
                  seed=0)
    base = str(tmp_path / "base")
    r0 = search_policies(conf, str(tmp_path), base, **common)
    assert "warm_start" not in r0  # defaults: no new artifact keys
    final_bytes = open(os.path.join(base, "final_policy.json"),
                       "rb").read()
    log0 = json.load(open(os.path.join(base, "search_trials.json")))

    # ---- zero top-up: the one-shot artifact, byte for byte ----------
    zero = warm_started_research(
        conf, str(tmp_path), base, str(tmp_path / "zero"),
        topup_trials=0, **common)
    assert open(zero["policy"], "rb").read() == final_bytes
    assert "warm_start" not in zero["result"]
    assert zero["provenance"]["topup_trials"] == 0
    # the candidate digest names the same bytes the fleet would verify
    from fast_autoaugment_tpu.control.research import policy_file_digest

    assert zero["provenance"]["policy_digest"] == \
        policy_file_digest(os.path.join(base, "final_policy.json"))
    zero_log = json.load(open(tmp_path / "zero" / "search_trials.json"))
    assert zero_log == log0  # zero new trials dispatched

    # ---- real top-up: base prefix byte-identical, budget extended ---
    topped = warm_started_research(
        conf, str(tmp_path), base, str(tmp_path / "top"),
        topup_trials=3,
        drift={"id": "drift-test-1", "metric": "input_mean"},
        **common)
    log1 = json.load(open(tmp_path / "top" / "search_trials.json"))
    assert len(log1["0"]) == 8
    assert json.dumps(log1["0"][:5]) == json.dumps(log0["0"])
    ws = topped["result"]["warm_start"]
    assert ws["base_num_search"] == 5 and ws["topup_trials"] == 3
    assert ws["resumed_trials_per_fold"]["0"] == 5
    assert topped["provenance"]["drift"]["id"] == "drift-test-1"
    assert topped["provenance"]["warm_start"] == ws


# ------------------------------- fleet-routed re-search (ISSUE 15 sat)


def test_research_through_fleet_learner_actor_byte_identical(tmp_path,
                                                             spawn_logged):
    """The PR-14 REMAINING item, measured: the control loop's
    warm-started re-search pointed at a REAL PR-13 learner+actor fleet
    launch (``search_cli --search-role``) produces artifacts
    BYTE-IDENTICAL to the controller-host re-search — so
    ``--research-cmd`` can offload the top-up to a fleet without
    changing a single candidate byte."""
    from fast_autoaugment_tpu.control.research import seed_research_dir

    tmp = str(tmp_path)
    conf_yaml = os.path.join(tmp, "conf.yaml")
    with open(conf_yaml, "w") as fh:
        fh.write(CONF_YAML)
    flags = [
        "-c", conf_yaml, "--dataroot", tmp,
        "--num-fold", "1", "--num-search", "4", "--num-policy", "1",
        "--num-op", "1", "--num-top", "2", "--trial-batch", "2",
        "--until", "2", "--fold-quality-floor", "off",
        "--audit-floor", "0", "--async-pipeline", "on",
        "--pipeline-actors", "2", "--pipeline-queue-depth", "2",
        "--seed", "0"]
    cli = [sys.executable, "-m",
           "fast_autoaugment_tpu.launch.search_cli"]
    # every subprocess shares the session's compile cache (conftest.py)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FAA_FAULT", None)

    # ---- the base search whose log both re-searches warm-start from
    base_dir = os.path.join(tmp, "base")
    r = subprocess.run(cli + flags + ["--save-dir", base_dir], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]

    # ---- arm A: the controller-host re-search (the PR-14 default)
    out_a = os.path.join(tmp, "research_host")
    seed_research_dir(base_dir, out_a)
    t0 = time.monotonic()
    r = subprocess.run(
        cli + flags + ["--save-dir", out_a, "--topup-trials", "2"],
        env=env, capture_output=True, text=True, timeout=900)
    host_wall = time.monotonic() - t0
    assert r.returncode == 0, r.stderr[-3000:]

    # ---- arm B: the SAME re-search through a learner+actor fleet
    out_b = os.path.join(tmp, "research_fleet")
    seed_research_dir(base_dir, out_b)
    tr = os.path.join(tmp, "transport")
    fleet_flags = flags + ["--save-dir", out_b, "--topup-trials", "2",
                           "--fleet-transport", tr, "--lease-ttl", "30"]
    t0 = time.monotonic()
    learner = spawn_logged(
        cli + fleet_flags + ["--search-role", "learner",
                             "--host-id", "0"],
        env=dict(env, FAA_HOST_ID="0"), name="learner")
    actor = spawn_logged(
        cli + fleet_flags + ["--search-role", "actor",
                             "--host-id", "1"],
        env=dict(env, FAA_HOST_ID="1"), name="actor")
    out_l = spawn_logged.finish(learner, 900)
    out_ac = spawn_logged.finish(actor, 300)
    fleet_wall = time.monotonic() - t0
    assert learner.returncode == 0, out_l[-3000:]
    assert actor.returncode == 0, out_ac[-3000:]

    # ---- byte-identity: the fleet path changes NOTHING --------------
    for name in ("final_policy.json", "search_trials.json"):
        assert (open(os.path.join(out_a, name), "rb").read()
                == open(os.path.join(out_b, name), "rb").read()), name
    res_a = json.load(open(os.path.join(out_a, "search_result.json")))
    res_b = json.load(open(os.path.join(out_b, "search_result.json")))
    assert res_a["warm_start"]["topup_trials"] == 2
    assert res_b["warm_start"] == res_a["warm_start"]
    # the base prefix is the base log verbatim, extended by the top-up
    base_log = json.load(open(os.path.join(base_dir,
                                           "search_trials.json")))
    log_b = json.load(open(os.path.join(out_b, "search_trials.json")))
    assert json.dumps(log_b["0"][:4]) == json.dumps(base_log["0"])
    assert len(log_b["0"]) == 6
    # the fleet really evaluated remotely: the actor posted rounds
    assert "fleet_transport" in res_b and "fleet_transport" not in res_a
    print("RESEARCH_FLEET " + json.dumps({
        "research_fleet": {
            "host_wall_sec": round(host_wall, 1),
            "fleet_wall_sec": round(fleet_wall, 1),
            "topup_trials": 2,
            "single_core_caveat": True,
        }}))


# ----------------------------------------------------------- THE drill


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


def test_drift_detect_research_canary_promote_drill(tmp_path):
    """The ISSUE-14 acceptance drill: seeded FAA_FAULT drift injection
    against a live 3-replica routed fleet triggers detect ->
    warm-started re-search -> canary -> promote with zero dropped
    requests during rollover, and the journal renders the full causal
    chain via trace_export + faa_status."""
    from fast_autoaugment_tpu.control.research import policy_file_digest
    from fast_autoaugment_tpu.search.driver import search_policies

    tmp = str(tmp_path)
    tel_dir = os.path.join(tmp, "telemetry")
    port_dir = os.path.join(tmp, "replicas")
    base_dir = os.path.join(tmp, "base_search")
    conf_yaml = os.path.join(tmp, "conf.yaml")
    with open(conf_yaml, "w") as fh:
        fh.write(CONF_YAML)

    # ---- the one-shot search whose policy the fleet serves ----------
    # (every subprocess below is warmed by the session's shared
    # compile cache — conftest.py)
    conf = _tiny_conf()
    search_policies(conf, tmp, base_dir, cv_num=1, cv_ratio=0.4,
                    num_policy=1, num_op=1, num_search=4, num_top=1,
                    trial_batch=2, async_pipeline="on",
                    fold_quality_floor=None, seed=0)
    baseline_policy = os.path.join(base_dir, "final_policy.json")
    baseline_digest = policy_file_digest(baseline_policy)

    procs = []
    failures = []
    ok_rows = []
    stop = threading.Event()
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   # the seeded drill fault: every replica's input
                   # stream shifts from its 12th coalesced dispatch on
                   FAA_FAULT="drift@dispatch=12,shift=60")
        env.pop("FAA_TELEMETRY", None)
        for i in range(3):
            env_i = dict(env, FAA_HOST_ID=str(i))
            procs.append(subprocess.Popen([
                sys.executable, "-m",
                "fast_autoaugment_tpu.serve.serve_cli",
                "--policy", baseline_policy, "--image", "8",
                "--shapes", "1,8", "--max-wait-ms", "2",
                # pinned: 'auto' would flip exact->grouped when the
                # candidate's sub-policy count crosses 1, and a reload
                # may not change dispatch mode (serving contract)
                "--dispatch", "exact",
                "--traffic-stats", "--telemetry", tel_dir,
                "--port", "0", "--port-dir", port_dir,
                "--host-tag", f"replica{i}",
            ], env=env_i, cwd=_REPO))
        from plane_helpers import wait_port_record, wait_ready

        ports = []
        for i in range(3):
            port = wait_port_record(port_dir, f"replica{i}", procs[i],
                                    600.0)
            wait_ready("127.0.0.1", port, procs[i], 600.0)
            ports.append(port)

        # ---- the router front door ------------------------------
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

        # ---- the control loop: REAL warm-started re-search ------
        research_cmd = (
            f"{sys.executable} -m fast_autoaugment_tpu.launch.search_cli"
            f" -c {conf_yaml} --dataroot {tmp} --save-dir {{out}}"
            f" --num-fold 1 --num-search 4 --topup-trials 2"
            f" --num-policy 1 --num-op 1 --num-top 2 --trial-batch 2"
            f" --until 2 --fold-quality-floor off --audit-floor 0"
            f" --async-pipeline on --seed 0")
        stats_file = os.path.join(tmp, "control_stats.json")
        ctl_env = dict(env)
        ctl_env.pop("FAA_FAULT", None)
        ctl = subprocess.Popen([
            sys.executable, "-m",
            "fast_autoaugment_tpu.launch.control_cli",
            "--telemetry", tel_dir, "--port-dir", port_dir,
            "--router-url", f"http://127.0.0.1:{router_port}",
            "--baseline-policy", baseline_policy,
            "--base-search-dir", base_dir,
            "--research-cmd", research_cmd,
            "--candidate-dir", os.path.join(tmp, "research"),
            "--baseline-samples", "10",
            "--canary-replicas", "1", "--split-every", "2",
            "--gate-polls", "2", "--quality-margin", "10",
            "--min-arm-dispatches", "1",
            "--poll-interval", "0.3",
            "--reload-timeout", "600",
            "--stats-file", stats_file,
        ], env=ctl_env, cwd=_REPO)
        procs.append(ctl)

        # ---- continuous traffic through the router --------------
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

        # ---- wait for the promote event -------------------------
        deadline = time.monotonic() + 900
        promote = None
        while time.monotonic() < deadline and promote is None:
            if ctl.poll() is not None:
                raise AssertionError(
                    f"control_cli died early rc={ctl.returncode}")
            evs = _read_journal(tel_dir)
            promote = next((r for r in evs if r["type"] == "promote"),
                           None)
            time.sleep(1.0)
        assert promote is not None, "the loop never promoted"
        # a little post-promote traffic proves the fleet still serves
        time.sleep(3.0)
        stop.set()
        client.join(timeout=120)

        ctl.send_signal(15)
        ctl.wait(timeout=60)

        # every replica (still live) answers with the promoted digest
        # + provenance — the reload-verification surface, fleet-wide
        promoted_digest = promote["digest"]
        for i, port in enumerate(ports):
            _s, _h, body = _http("127.0.0.1", port, "GET", "/stats")
            st = json.loads(body)
            assert st["policy_digest"] == promoted_digest, f"replica{i}"
            assert st["policy_provenance"]["policy_digest"] == \
                promoted_digest
            assert st["traffic"]["samples"] > 0
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

    # ---- ZERO dropped requests through the whole drill --------------
    assert not failures, failures[:10]
    assert len(ok_rows) > 20

    # ---- the causal chain, in order, from ONE journal ---------------
    evs = _read_journal(tel_dir)
    by_type = {}
    for r in evs:
        if r["type"] in ("drift", "research", "canary", "promote",
                         "rollback"):
            by_type.setdefault(r["type"], []).append(r)
    assert "rollback" not in by_type
    drift = by_type["drift"][0]
    research = by_type["research"][0]
    rollouts = [r for r in by_type["canary"]
                if r.get("action") == "rollout"]
    promote = by_type["promote"][0]
    assert drift["t_wall"] < research["t_wall"] \
        < rollouts[0]["t_wall"] < promote["t_wall"]
    assert drift["metric"] in ("input_mean", "reward_proxy")
    assert drift["stat"] > drift["threshold"]
    # the re-search really warm-started: its provenance names the base
    cand_dir = os.path.join(tmp, "research", "episode1")
    cand_result = json.load(open(
        os.path.join(cand_dir, "search_result.json")))
    assert cand_result["warm_start"]["topup_trials"] == 2
    assert cand_result["warm_start"]["resumed_trials_per_fold"]["0"] == 4
    prov = json.load(open(
        os.path.join(cand_dir, "final_policy.provenance.json")))
    assert prov["policy_digest"] == promote["digest"]
    assert prov["policy_digest"] != baseline_digest
    # base prefix of the candidate's trial log is the base log verbatim
    base_log = json.load(open(
        os.path.join(base_dir, "search_trials.json")))
    cand_log = json.load(open(
        os.path.join(cand_dir, "search_trials.json")))
    assert json.dumps(cand_log["0"][:4]) == json.dumps(base_log["0"])
    assert len(cand_log["0"]) == 6
    # the canary subset was the candidate digest's rendezvous prefix
    from fast_autoaugment_tpu.control.canary import select_canary_replicas

    expect = select_canary_replicas(
        promote["digest"], ["replica0", "replica1", "replica2"], 1)
    assert sorted({r["replica"] for r in rollouts}) == expect
    assert promote["drift_id"] == drift["id"]
    assert promote["detect_to_promote_sec"] > 0

    # the loop settled: one episode, one promote, monitor re-baselined
    stats = json.load(open(stats_file))
    assert stats["promotes"] == 1 and stats["rollbacks"] == 0
    assert stats["state"] == "watching"
    assert stats["baseline_digest"] == promote["digest"]
    assert not stats["monitor"]["latched"]

    # ---- make trace renders the chain; make status summarizes it ----
    trace_out = os.path.join(tmp, "trace.json")
    r = subprocess.run(
        [sys.executable, "tools/trace_export.py", "--telemetry",
         tel_dir, "--out", trace_out],
        cwd=_REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-1000:]
    trace = json.load(open(trace_out))
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "i"}
    for marker in ("drift:", "research:", "canary:", "promote:"):
        assert any(n.startswith(marker) for n in names), (marker, names)
    r = subprocess.run(
        [sys.executable, "tools/faa_status.py", "--dir", tel_dir,
         "--json"],
        cwd=_REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-1000:]
    status = json.loads(r.stdout)
    assert status["control"]["promotes"] == 1
    assert status["control"]["last_decision"]["action"] == "promote"
    assert status["control"]["drift_verdict_total"] >= 1
