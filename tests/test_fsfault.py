"""Hostile shared-substrate survival (ISSUE 15): the FAA_FSFAULT seam
(``core/fsfault.py``), skew at the telemetry ``wall()`` seam, the
hardened journal tailing, and the workqueue/transport behavior under
injected lag — all fast, host-only, no jax.

The slow tests are THE acceptance drill (a 3-process fleet search
under ``lag+skew+eio`` with a SIGKILLed skewed actor, byte-identical
artifacts, epoch-stamped reclaim provenance) and the ``make chaos``
composed-fault smoke.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from fast_autoaugment_tpu.core import fsfault, telemetry
from fast_autoaugment_tpu.launch.workqueue import WorkQueue

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_fsfault_env(monkeypatch):
    monkeypatch.delenv("FAA_FSFAULT", raising=False)
    monkeypatch.delenv("FAA_FAULT", raising=False)
    fsfault.reset()
    yield
    os.environ.pop("FAA_FSFAULT", None)
    fsfault.reset()


def _arm(spec: str):
    os.environ["FAA_FSFAULT"] = spec
    fsfault.reset()
    return fsfault.active_plan()


# ------------------------------------------------------------- grammar


def test_grammar_parses_all_kinds():
    faults = fsfault.parse_fsfault_spec(
        "lag@dir=work,secs=2;stale@dir=done,window=1.5;"
        "eio@p=0.05,seed=7;skew@host=1,offset=-45;torn@path=*.json")
    kinds = [f["kind"] for f in faults]
    assert kinds == ["lag", "stale", "eio", "skew", "torn"]
    assert faults[0]["secs"] == 2.0
    assert faults[2]["seed"] == 7
    assert faults[3]["offset"] == -45.0


@pytest.mark.parametrize("bad", [
    "nonsense@x=1",            # unknown kind
    "lag@secs=2",              # missing dir
    "lag@dir=work",            # missing secs
    "eio@p=1.5",               # p outside [0, 1]
    "lag@dir=work,bogus=1",    # unknown key
    "skew@host=,offset=1",     # empty value
    "lag=work",                # no @
])
def test_grammar_rejects_loudly(bad):
    with pytest.raises(ValueError):
        fsfault.parse_fsfault_spec(bad)


def test_unset_env_means_no_plan_and_passthrough(tmp_path):
    assert fsfault.active_plan() is None
    p = tmp_path / "x.json"
    p.write_text(json.dumps({"a": 1}))
    assert fsfault.read_json(str(p)) == {"a": 1}
    assert fsfault.load_json(str(p)) == {"a": 1}
    assert fsfault.listdir(str(tmp_path)) == ["x.json"]
    assert fsfault.getsize(str(p)) == len(json.dumps({"a": 1}))
    assert fsfault.exists(str(p))
    assert fsfault.read_json(str(tmp_path / "missing.json")) is None


# ---------------------------------------------------------------- skew


def test_skew_offsets_wall_for_matching_host_only(monkeypatch):
    monkeypatch.setenv("FAA_HOST_ID", "1")
    _arm("skew@host=1,offset=45")
    assert abs(telemetry.wall() - time.time() - 45.0) < 1.0
    # a different host sees an honest clock
    monkeypatch.setenv("FAA_HOST_ID", "2")
    fsfault.reset()
    assert abs(telemetry.wall() - time.time()) < 1.0
    # host form 'host1' matches too
    monkeypatch.setenv("FAA_HOST_ID", "1")
    _arm("skew@host=host1,offset=-30")
    assert abs(telemetry.wall() - time.time() + 30.0) < 1.0


def test_mono_is_never_skewed(monkeypatch):
    monkeypatch.setenv("FAA_HOST_ID", "1")
    _arm("skew@host=1,offset=3600")
    m0 = telemetry.mono()
    assert abs(telemetry.mono() - m0) < 1.0  # no hour-sized jump


# ----------------------------------------------------------------- lag


def test_lag_hides_fresh_foreign_files_but_not_own_writes(tmp_path):
    work = tmp_path / "work"
    work.mkdir()
    foreign = str(work / "foreign.json")
    with open(foreign, "w") as fh:  # written OUTSIDE the seam
        json.dump({"who": "other-host"}, fh)
    _arm("lag@dir=work,secs=30")
    # the foreign write is too fresh: invisible to reads, lists, stats
    assert fsfault.read_json(foreign) is None
    assert fsfault.listdir(str(work)) == []
    assert not fsfault.exists(foreign)
    with pytest.raises(OSError):
        fsfault.getsize(foreign)
    # but THIS process's seam writes are always visible to itself
    own = str(work / "own.json")
    fsfault.write_json_atomic(own, {"who": "me"})
    assert fsfault.read_json(own) == {"who": "me"}
    assert fsfault.listdir(str(work)) == ["own.json"]
    # an OLD foreign file (mtime outside the window) is visible
    old = str(work / "old.json")
    with open(old, "w") as fh:
        json.dump({"who": "old"}, fh)
    past = time.time() - 120
    os.utime(old, (past, past))
    assert fsfault.read_json(old) == {"who": "old"}
    # paths outside the matched dir never lag
    outside = str(tmp_path / "outside.json")
    with open(outside, "w") as fh:
        json.dump({"who": "outside"}, fh)
    assert fsfault.read_json(outside) == {"who": "outside"}


def test_lag_expires_after_the_window(tmp_path):
    work = tmp_path / "work"
    work.mkdir()
    p = str(work / "f.json")
    with open(p, "w") as fh:
        json.dump({"v": 1}, fh)
    _arm("lag@dir=work,secs=0.2")
    assert fsfault.read_json(p) is None
    time.sleep(0.3)
    assert fsfault.read_json(p) == {"v": 1}


# --------------------------------------------------------------- stale


def test_stale_rereads_serve_the_previous_version(tmp_path):
    d = tmp_path / "done"
    d.mkdir()
    p = str(d / "m.json")
    with open(p, "w") as fh:
        json.dump({"v": 1}, fh)
    past = time.time() - 60
    os.utime(p, (past, past))
    _arm("stale@dir=done,window=30")
    assert fsfault.read_json(p) == {"v": 1}  # first read caches v1
    with open(p, "w") as fh:                 # foreign update to v2
        json.dump({"v": 2}, fh)
    # within the window: the observer's attribute cache answers v1
    assert fsfault.read_json(p) == {"v": 1}
    plan = fsfault.active_plan()
    assert plan.injected.get("stale", 0) >= 1
    # after the window the fresh bytes win
    os.utime(p, (past, past))
    assert fsfault.read_json(p) == {"v": 2}


# ----------------------------------------------------------------- eio


def test_eio_is_seeded_and_seam_retries_absorb_most(tmp_path):
    p = str(tmp_path / "x.json")
    with open(p, "w") as fh:
        json.dump({"a": 1}, fh)
    _arm("eio@p=1.0,seed=3")
    # p=1.0: every attempt fails, retries exhaust, the error surfaces
    with pytest.raises(OSError):
        fsfault.load_json(p)
    assert fsfault.read_json(p) is None  # absorbing variant
    plan = fsfault.active_plan()
    assert plan.injected["eio"] >= 2
    # p=0.3: the in-seam retry (3 attempts) absorbs nearly everything
    _arm("eio@p=0.3,seed=3")
    vals = [fsfault.read_json(p) for _ in range(30)]
    assert vals.count({"a": 1}) >= 28
    # determinism: the same seed gives the same injection stream
    _arm("eio@p=0.3,seed=3")
    again = [fsfault.read_json(p) for _ in range(30)]
    assert vals == again


# ---------------------------------------------------------------- torn


def test_torn_truncates_first_read_only(tmp_path):
    p = str(tmp_path / "t.json")
    payload = {"k": "v" * 200}
    with open(p, "w") as fh:
        json.dump(payload, fh)
    past = time.time() - 60
    os.utime(p, (past, past))
    _arm("torn@path=t.json")
    assert fsfault.read_json(p) is None       # torn tail: unparseable
    assert fsfault.read_json(p) == payload    # the write "completed"
    assert fsfault.active_plan().injected["torn"] == 1


# ------------------------------------------- workqueue under the seam


def test_workqueue_claim_poll_rides_out_lag(tmp_path):
    """An actor polling open_units/claim under publish lag simply sees
    the unit a little later — no torn reads, no spurious claims."""
    root = str(tmp_path / "wq")
    learner = WorkQueue(root, "learner", lease_ttl=5.0)
    _arm("lag@dir=work,secs=0.3")
    learner.publish_unit("p2r-f0-t000000", {"ids": [0, 1]})
    # the learner sees its own publish instantly (own-write exemption)
    assert learner.open_units("p2r-") == ["p2r-f0-t000000"]
    actor = WorkQueue(root, "actor", lease_ttl=5.0)
    # both queues share this test process; drop the own-write record
    # to see the publish exactly as a REMOTE actor host would
    fsfault.active_plan().own_writes.clear()
    assert actor.open_units("p2r-") == []  # not yet visible there
    time.sleep(0.4)
    assert actor.open_units("p2r-") == ["p2r-f0-t000000"]
    assert actor.unit_payload("p2r-f0-t000000")["ids"] == [0, 1]
    assert actor.claim("p2r-f0-t000000")
    actor.release("p2r-f0-t000000", info={"rewards": [0.5, 0.6]})
    time.sleep(0.1)
    assert learner.done_info("p2r-f0-t000000") == {
        "rewards": [0.5, 0.6]}


def test_workqueue_lease_protocol_survives_eio(tmp_path):
    _arm("eio@p=0.1,seed=11")
    a = WorkQueue(str(tmp_path / "wq"), "a", lease_ttl=5.0)
    for i in range(10):
        unit = f"u{i}"
        assert a.claim(unit)
        a.renew(unit)
        a.release(unit, info={"i": i})
        assert a.is_done(unit)


# ------------------------------------ journal tailing under the seam


def _write_journal(path, records):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as fh:
        for rec in records:
            fh.write(json.dumps(rec) + "\n")


def _recs(host, seqs, mean=100.0):
    return [{"type": "dispatch", "label": "serve_dispatch",
             "input_mean": mean, "reward_proxy": 0.1,
             "host": host, "pid": 1, "seq": s} for s in seqs]


def test_reader_watermark_dedups_stale_rereads(tmp_path):
    from fast_autoaugment_tpu.control.drift import TrafficSampleReader

    tel = str(tmp_path / "tel")
    jpath = os.path.join(tel, "journal-0.jsonl")
    _write_journal(jpath, _recs("h0", range(5)))
    reader = TrafficSampleReader(tel)
    assert len(reader.poll()) == 5
    # a stale re-read / shrink-then-grow share hands the reader the
    # same bytes again: offsets reset, the seq watermark deduplicates
    reader._offsets.clear()
    assert reader.poll() == []
    _write_journal(jpath, _recs("h0", range(5, 8)))
    assert [r["seq"] for r in reader.poll()] == [5, 6, 7]


def test_reader_rides_out_eio_and_torn(tmp_path):
    from fast_autoaugment_tpu.control.drift import TrafficSampleReader

    tel = str(tmp_path / "tel")
    jpath = os.path.join(tel, "journal-0.jsonl")
    _write_journal(jpath, _recs("h0", range(10)))
    past = time.time() - 60
    os.utime(jpath, (past, past))
    _arm("eio@p=0.2,seed=5;torn@path=journal-*.jsonl")
    reader = TrafficSampleReader(tel)
    got: list = []
    for _ in range(20):  # a torn/eio poll just retries next time
        got.extend(reader.poll())
    assert [r["seq"] for r in got] == list(range(10))


def test_reader_skip_to_end_for_resume(tmp_path):
    from fast_autoaugment_tpu.control.drift import TrafficSampleReader

    tel = str(tmp_path / "tel")
    jpath = os.path.join(tel, "journal-0.jsonl")
    _write_journal(jpath, _recs("h0", range(50), mean=500.0))
    reader = TrafficSampleReader(tel)
    assert reader.skip_to_end() == 1
    assert reader.poll() == []  # the pre-crash history is never replayed
    _write_journal(jpath, _recs("h0", range(50, 53)))
    assert [r["seq"] for r in reader.poll()] == [50, 51, 52]


# ------------------------------------------------- status integration


def test_faa_status_lease_epochs_skew_suspects_and_counters(tmp_path):
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        from faa_status import search_fleet_status
    finally:
        sys.path.pop(0)

    root = str(tmp_path)
    os.makedirs(os.path.join(root, "leases"))
    with open(os.path.join(root, "leases", "p2r-f0-t000000.json"),
              "w") as fh:
        json.dump({"unit": "p2r-f0-t000000", "owner": "host2",
                   "attempt": 2, "epoch": 2, "reclaimed_from": "host1",
                   "heartbeat": time.time() + 600}, fh)
    journal = [{"type": "fsfault", "label": "lag"},
               {"type": "fsfault", "label": "lag"},
               {"type": "fsfault", "label": "eio"},
               {"type": "round", "action": "claim", "host": "host2"}]
    beats = {"host1": {"owner": "host1",
                       "heartbeat": time.time() + 45, "role": "actor"}}
    st = search_fleet_status(root, journal, beats)
    assert st["lease_epochs"]["p2r-f0-t000000"]["epoch"] == 2
    assert st["lease_epochs"]["p2r-f0-t000000"]["reclaimed_from"] == \
        "host1"
    assert st["fsfault_injections"] == {"lag": 2, "eio": 1}
    kinds = {(s["kind"], s["name"]) for s in st["skew_suspects"]}
    assert ("lease", "p2r-f0-t000000") in kinds
    assert ("host", "host1") in kinds


def test_fsfault_event_type_is_in_taxonomy():
    assert "fsfault" in telemetry.EVENT_TYPES


def test_fsfault_injection_counter_lands_in_registry(tmp_path):
    p = str(tmp_path / "x.json")
    with open(p, "w") as fh:
        json.dump({}, fh)
    _arm("eio@p=1.0,seed=0")
    before = telemetry.registry().counter(
        "faa_fsfault_injections_total", "d", kind="eio").value
    assert fsfault.read_json(p) is None
    after = telemetry.registry().counter(
        "faa_fsfault_injections_total", "d", kind="eio").value
    assert after > before


# ================================================== slow: THE drills


_CONF_YAML = (
    "model:\n  type: wresnet10_1\ndataset: synthetic\naug: default\n"
    "cutout: 8\nbatch: 8\nepoch: 1\nlr: 0.05\n"
    "lr_schedule:\n  type: cosine\n"
    "optimizer:\n  type: sgd\n  decay: 0.0001\n  momentum: 0.9\n"
    "  nesterov: true\n")


def _fleet_cmd(conf, tmp):
    return [sys.executable, "-m",
            "fast_autoaugment_tpu.launch.search_cli",
            "-c", str(conf), "--dataroot", tmp,
            "--num-fold", "2", "--num-search", "4", "--num-policy", "1",
            "--num-op", "1", "--num-top", "2", "--trial-batch", "2",
            "--until", "2", "--fold-quality-floor", "off",
            "--seed", "0",
            "--async-pipeline", "on", "--pipeline-actors", "2",
            "--pipeline-queue-depth", "2"]


@pytest.mark.slow
def test_fleet_search_byte_identical_under_lag_skew_eio(tmp_path,
                                                        spawn_logged):
    """THE ISSUE-15 acceptance drill: a 3-process fleet search under
    ``FAA_FSFAULT=lag@dir=work,secs=2;skew@host=1,offset=45;
    eio@p=0.05,seed=7`` — publish->claim visibility lag, a +45s wall
    clock on actor host1, and seeded transient read errors everywhere —
    completes with ``final_policy.json`` BYTE-IDENTICAL to the
    fault-free single-host run.  Host1 (the SKEWED host) is also
    SIGKILLed mid-round: its future-stamped lease must still be
    reclaimed (observer-local staleness) and the reclaim provenance
    carries the bumped epoch."""
    tmp = str(tmp_path)
    conf = tmp_path / "conf.yaml"
    conf.write_text(_CONF_YAML)
    base = _fleet_cmd(conf, tmp)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FAA_FAULT", None)
    env.pop("FAA_FSFAULT", None)

    # ---- fault-free single-host reference (warms the shared cache)
    ref = subprocess.run(base + ["--save-dir", f"{tmp}/ref"], env=env,
                         capture_output=True, text=True, timeout=900)
    assert ref.returncode == 0, ref.stderr[-3000:]

    # ---- the 3-process fleet on a hostile substrate ---------------
    fsf = "lag@dir=work,secs=2;skew@host=1,offset=45;eio@p=0.05,seed=7"
    tr, save = f"{tmp}/transport", f"{tmp}/fleet"
    fleet_base = base + ["--save-dir", save, "--fleet-transport", tr,
                         "--lease-ttl", "6"]
    learner = spawn_logged(
        fleet_base + ["--search-role", "learner", "--host-id", "0"],
        env=dict(env, FAA_HOST_ID="0", FAA_FSFAULT=fsf), name="learner")
    # trial=1: the doomed actor dies on the FIRST round it evaluates
    # (any round covers a trial index >= 1), and it launches ahead of
    # the survivor so it reliably wins a claim race before dying
    doomed = spawn_logged(
        fleet_base + ["--search-role", "actor", "--host-id", "1"],
        env=dict(env, FAA_HOST_ID="1", FAA_FSFAULT=fsf,
                 FAA_FAULT="sigkill_trial@trial=1"), name="doomed")
    time.sleep(5.0)
    survivor = spawn_logged(
        fleet_base + ["--search-role", "actor", "--host-id", "2"],
        env=dict(env, FAA_HOST_ID="2", FAA_FSFAULT=fsf), name="survivor")
    out_l = spawn_logged.finish(learner, 900)
    out_d = spawn_logged.finish(doomed, 300)
    out_s = spawn_logged.finish(survivor, 300)
    assert learner.returncode == 0, out_l[-3000:]
    assert survivor.returncode == 0, out_s[-3000:]
    assert doomed.returncode == -9, (doomed.returncode, out_d[-1500:])

    # byte-identity through lag + skew + eio + kill + reclaim
    assert (open(f"{tmp}/ref/search_trials.json", "rb").read()
            == open(f"{save}/search_trials.json", "rb").read())
    assert (open(f"{tmp}/ref/final_policy.json", "rb").read()
            == open(f"{save}/final_policy.json", "rb").read())
    result = json.load(open(f"{save}/search_result.json"))
    assert result["degraded"] is True
    assert result["reclaimed_units"], "the dead actor's round reclaimed"
    assert all(u.startswith("p2r-") for u in result["reclaimed_units"])
    # THE epoch-provenance acceptance bit: every reclaim in the full
    # accounting carries the bumped fencing token, robbed from host1
    for rec in result["resilience"]["fleet"]["reclaimed_units"]:
        assert rec["epoch"] >= 2, rec
        assert rec["reclaimed_from"] == "host1", rec


@pytest.mark.slow
def test_chaos_composed_fault_smoke(tmp_path, spawn_logged):
    """``make chaos``: FAA_FAULT (sigkill) layered with FAA_FSFAULT
    (lag + eio) over a bounded fleet drill — the composed-fault smoke.
    Asserts completion and artifact integrity (the byte-identity
    deep-dive is the acceptance drill above) and prints the reclaim
    evidence."""
    tmp = str(tmp_path)
    conf = tmp_path / "conf.yaml"
    conf.write_text(_CONF_YAML)
    tel = f"{tmp}/tel"
    base = _fleet_cmd(conf, tmp)
    env = dict(os.environ, JAX_PLATFORMS="cpu", FAA_TELEMETRY=tel)
    env.pop("FAA_FAULT", None)
    env.pop("FAA_FSFAULT", None)
    fsf = "lag@dir=work,secs=1;eio@p=0.05,seed=13"
    tr, save = f"{tmp}/transport", f"{tmp}/chaos"
    fleet_base = base + ["--save-dir", save, "--fleet-transport", tr,
                         "--lease-ttl", "5"]
    t0 = time.monotonic()
    learner = spawn_logged(
        fleet_base + ["--search-role", "learner", "--host-id", "0"],
        env=dict(env, FAA_HOST_ID="0", FAA_FSFAULT=fsf), name="learner")
    doomed = spawn_logged(
        fleet_base + ["--search-role", "actor", "--host-id", "1"],
        env=dict(env, FAA_HOST_ID="1", FAA_FSFAULT=fsf,
                 FAA_FAULT="sigkill_trial@trial=1"), name="doomed")
    time.sleep(5.0)  # the doomed actor claims first, then dies
    survivor = spawn_logged(
        fleet_base + ["--search-role", "actor", "--host-id", "2"],
        env=dict(env, FAA_HOST_ID="2", FAA_FSFAULT=fsf), name="survivor")
    out_l = spawn_logged.finish(learner, 900)
    spawn_logged.finish(doomed, 300)
    out_s = spawn_logged.finish(survivor, 300)
    assert learner.returncode == 0, out_l[-3000:]
    assert survivor.returncode == 0, out_s[-3000:]
    assert doomed.returncode == -9

    result = json.load(open(f"{save}/search_result.json"))
    policy = json.load(open(f"{save}/final_policy.json"))
    assert policy, "chaos run produced an empty policy"
    assert result["degraded"] is True
    reclaims = result["resilience"]["fleet"]["reclaimed_units"]
    line = {
        "chaos": {"fsfault": fsf, "fault": "sigkill_trial@trial=1",
                  "wall_sec": round(time.monotonic() - t0, 1),
                  "reclaimed_units": reclaims,
                  "lost_hosts": result["lost_hosts"]},
    }
    print("CHAOS " + json.dumps(line))
    assert reclaims
    for rec in reclaims:
        assert rec["epoch"] >= 2
