"""Multi-host MPMD fleet search: the cross-host actor/learner round
transport (``search/pipeline.py::FleetTransport``/``run_fleet_actor``,
``launch/workqueue.py`` round-unit verbs, ``search_cli --search-role``)
plus the role-aware fleet launcher.

Fast tests are host-only (stub evaluators, no XLA compiles beyond tiny
PRNG ops); the slow tests are the subprocess acceptance drills —
cross-process steal-fence racing and THE 3-process fleet producing
byte-identical artifacts through a SIGKILLed actor host.
docs/RESILIENCE.md "Fleet search".
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

from fast_autoaugment_tpu.core.resilience import clear_preemption
from fast_autoaugment_tpu.launch import fleet as fleet_mod
from fast_autoaugment_tpu.launch.workqueue import WorkQueue
from fast_autoaugment_tpu.search.driver import make_search_space
from fast_autoaugment_tpu.search.pipeline import (
    FleetTransport,
    RemoteEvalError,
    _failure_text,
    replay_trial_log,
    resolve_search_role,
    run_fleet_actor,
    run_fold_pipeline,
)
from fast_autoaugment_tpu.search.tpe import TPE


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    monkeypatch.delenv("FAA_FAULT", raising=False)
    monkeypatch.delenv("FAA_SEARCH_ROLE", raising=False)
    monkeypatch.delenv("FAA_FLEET_TRANSPORT", raising=False)
    from fast_autoaugment_tpu.utils import faultinject

    faultinject.reset()
    clear_preemption()
    yield
    # explicit scrub: tests set FAA_FAULT via os.environ directly, and
    # monkeypatch cannot restore a var that was ABSENT at setup — a
    # leaked spec would quarantine trials in unrelated later tests
    os.environ.pop("FAA_FAULT", None)
    faultinject.reset()
    clear_preemption()


# ------------------------------------------------- workqueue round verbs


def test_publish_unit_payload_roundtrip_and_open_menu(tmp_path):
    q = WorkQueue(str(tmp_path), "host0")
    assert q.open_units() == []
    q.publish_unit("p2r-f0-t000000", {"ids": [0, 1], "fold": 0})
    q.publish_unit("p2r-f0-t000002", {"ids": [2, 3], "fold": 0})
    q.publish_unit("other-unit", {"x": 1})
    assert q.unit_payload("p2r-f0-t000000")["ids"] == [0, 1]
    assert q.unit_payload("p2r-f0-t000000")["unit"] == "p2r-f0-t000000"
    assert q.unit_payload("missing") is None
    assert q.open_units("p2r-") == ["p2r-f0-t000000", "p2r-f0-t000002"]
    assert "other-unit" in q.open_units()
    # a posted result (release info) hides the unit from the claim menu
    assert q.claim("p2r-f0-t000000")
    q.release("p2r-f0-t000000", info={"rewards": [0.5, 0.25]})
    assert q.open_units("p2r-") == ["p2r-f0-t000002"]
    rec = q.done_record("p2r-f0-t000000")
    assert rec["info"]["rewards"] == [0.5, 0.25]
    assert rec["owner"] == "host0" and rec["attempt"] == 1
    # republishing a done unit never resurrects it
    q.publish_unit("p2r-f0-t000000", {"ids": [0, 1], "fold": 0})
    assert q.open_units("p2r-") == ["p2r-f0-t000002"]


def test_tpe_pending_rounds_grouping():
    space = make_search_space(1, 1)
    tpe = TPE(space, seed=3, n_startup=4)
    tpe.ask_tagged(3)
    tpe.ask_tagged(3)
    tpe.ask_tagged(2)  # short final round of an 8-trial budget
    assert tpe.pending_rounds(3) == [[0, 1, 2], [3, 4, 5], [6, 7]]
    for t in (0, 1, 2):
        tpe.tell(t, 0.5)
    assert tpe.pending_rounds(3) == [[3, 4, 5], [6, 7]]
    # round_payload round-trips the pending proposals JSON-exactly
    payload = tpe.round_payload([3, 4])
    assert payload == [json.loads(json.dumps(p)) for p in payload]
    assert payload[0] == tpe.pending_proposal(3)


# --------------------------------------------------- transport primitives


class _Light:
    """A light round (what the learner submits): ids + proposals."""

    def __init__(self, idx, ids, proposals):
        self.idx, self.ids, self.proposals = idx, list(ids), proposals

    @property
    def t_base(self):
        return self.ids[0]

    @property
    def k_eff(self):
        return len(self.ids)


def test_round_unit_names_are_t_base_keyed_and_sortable():
    assert FleetTransport.round_unit(0, 2) == "p2r-f0-t000002"
    units = [FleetTransport.round_unit(0, t) for t in (10, 2, 0, 100)]
    assert sorted(units) == [FleetTransport.round_unit(0, t)
                             for t in (0, 2, 10, 100)]


def test_transport_publish_claim_post_poll_roundtrip(tmp_path):
    learner = FleetTransport(str(tmp_path), "learner0", role="learner")
    actor = FleetTransport(str(tmp_path), "actor0", role="actor")
    rnd = _Light(0, [0, 1], [{"policy_0_0": 1}, {"policy_0_0": 2}])
    unit = learner.publish_round(0, rnd, key_seed=77, trial_batch=2,
                                 num_policy=1, num_op=1)
    assert learner.poll_round(0, 0) is None  # in flight
    assert actor.open_rounds() == [unit]
    payload = actor.wq.unit_payload(unit)
    assert payload["ids"] == [0, 1] and payload["key_seed"] == 77
    assert actor.wq.claim(unit)
    actor.post_result(unit, payload, {"rewards": [0.5, 0.75]})
    kind, rewards = learner.poll_round(0, 0)
    assert kind == "ok" and rewards == [0.5, 0.75]
    assert actor.open_rounds() == []
    # error returns surface as RemoteEvalError with the actor's text
    rnd2 = _Light(1, [2, 3], [{"policy_0_0": 1}, {"policy_0_0": 2}])
    unit2 = learner.publish_round(0, rnd2, key_seed=77, trial_batch=2,
                                  num_policy=1, num_op=1)
    assert actor.wq.claim(unit2)
    actor.post_result(unit2, actor.wq.unit_payload(unit2),
                      {"error": "RuntimeError: boom at trial 2"})
    kind, exc = learner.poll_round(0, 2)
    assert kind == "err" and isinstance(exc, RemoteEvalError)
    # the quarantine text is the actor's formatted text VERBATIM — how
    # fleet quarantine records stay byte-identical to in-process ones
    assert _failure_text(exc) == "RuntimeError: boom at trial 2"
    assert _failure_text(ValueError("x")) == "ValueError: x"


def test_checkpoint_publish_wait_and_digest_gate(tmp_path):
    tr = FleetTransport(str(tmp_path / "tr"), "learner0")
    ckpt = tmp_path / "fold0.msgpack"
    ckpt.write_bytes(b"payload")
    (tmp_path / "fold0.msgpack.meta.json").write_text(
        json.dumps({"epoch": 3, "digest": "abc123"}))
    rec = tr.publish_checkpoint(0, str(ckpt))
    assert rec["digest"] == "abc123" and rec["epoch"] == 3
    assert tr.checkpoint_record(0)["digest"] == "abc123"
    # matching local digest: returns immediately
    got = tr.wait_checkpoint(0, str(ckpt), timeout=5.0, poll_sec=0.01)
    assert got["digest"] == "abc123"
    # digest mismatch (half-synced share): times out loudly
    (tmp_path / "fold0.msgpack.meta.json").write_text(
        json.dumps({"epoch": 3, "digest": "stale"}))
    with pytest.raises(TimeoutError, match="checkpoint"):
        tr.wait_checkpoint(0, str(ckpt), timeout=0.2, poll_sec=0.02)
    # unpublished fold: times out too
    with pytest.raises(TimeoutError):
        tr.wait_checkpoint(7, str(ckpt), timeout=0.2, poll_sec=0.02)


def test_search_done_marker_drains_idle_actor(tmp_path):
    tr = FleetTransport(str(tmp_path), "learner0")
    assert not tr.search_done()
    tr.mark_search_done({"num_sub_policies": 4})
    assert tr.search_done()
    actor_tr = FleetTransport(str(tmp_path), "actor0", role="actor")
    stats = run_fleet_actor(object(), actor_tr, lambda f: "/nope",
                            trial_batch=2, num_policy=1, num_op=1,
                            poll_sec=0.05)
    assert stats["rounds_ok"] == 0 and stats["folds"] == []
    beats = actor_tr.wq.known_hosts()
    assert beats["actor0"]["role"] == "actor"


# ------------------------------------------- fleet learner/actor (stubs)


class _StubFleetEval:
    """Host-only _FoldEval stand-in shared by the thread and fleet
    arms: deterministic per-lane rewards from the policy tensor."""

    def load_fold(self, path):
        return None, None

    @staticmethod
    def _reward(policy_lane):
        return round(float(np.asarray(policy_lane).sum()) % 1.0, 6)

    def evaluate(self, fold, params, batch_stats, policy_t, key):
        return {"top1_valid": self._reward(policy_t)}

    def evaluate_batch(self, fold, params, batch_stats, policies_t, keys):
        return [{"top1_valid": self._reward(policies_t[i])}
                for i in range(int(policies_t.shape[0]))]


def _drive(tmp_path, *, fleet: bool, num_search=8, k=2, actors=2,
           queue_depth=1, seed=11, fold_trials=None):
    """One fold's budget through the thread backend (fleet=False) or
    the cross-host transport serviced by an in-test actor thread
    (fleet=True) — everything else identical."""
    import jax

    tpe = TPE(make_search_space(1, 1), seed=seed, n_startup=4)
    log = list(fold_trials) if fold_trials is not None else []
    replay_trial_log(tpe, log, k, num_search,
                     max_inflight=actors + queue_depth)
    quars = []

    kw = dict(num_search=num_search, trial_batch=k, actors=actors,
              queue_depth=queue_depth, num_policy=1, num_op=1,
              persist=lambda: None,
              record_quarantine=lambda lo, hi, exc, worst: quars.append(
                  (lo, hi, _failure_text(exc), worst)))
    if not fleet:
        stats = run_fold_pipeline(
            _StubFleetEval(), 0, None, None, tpe, jax.random.PRNGKey(7),
            log, **kw)
        return log, stats, quars, None

    root = str(tmp_path / "tr")
    learner_tr = FleetTransport(root, "learner0", role="learner")
    learner_tr.publish_checkpoint(0, str(tmp_path / "missing.msgpack"))
    actor_tr = FleetTransport(root, "actor0", role="actor")
    actor_out: list = []

    def _actor():
        try:
            actor_out.append(run_fleet_actor(
                _StubFleetEval(), actor_tr,
                lambda f: str(tmp_path / "missing.msgpack"),
                trial_batch=k, num_policy=1, num_op=1, poll_sec=0.05))
        except BaseException as e:  # surfaced by the assertions below
            actor_out.append(e)

    th = threading.Thread(target=_actor, daemon=True)
    th.start()
    try:
        backend = learner_tr.learner_backend(
            0, key_seed=7, trial_batch=k, num_policy=1, num_op=1)
        stats = run_fold_pipeline(
            _StubFleetEval(), 0, None, None, tpe, jax.random.PRNGKey(7),
            log, backend=backend, **kw)
    finally:
        learner_tr.mark_search_done()
        th.join(timeout=30)
    assert not th.is_alive(), "actor never drained on search_done"
    return log, stats, quars, actor_out[0] if actor_out else None


def test_fleet_backend_reproduces_thread_backend_bit_for_bit(tmp_path):
    """THE determinism core: the same learner loop over the cross-host
    transport produces the identical trial log (and posterior stream)
    as the in-process thread backend — rewards are pure functions of
    (proposals, id-derived keys) wherever they run."""
    ref, ref_stats, _q, _ = _drive(tmp_path / "a", fleet=False)
    got, stats, quars, actor_stats = _drive(tmp_path / "b", fleet=True)
    assert got == ref
    assert not quars
    assert isinstance(actor_stats, dict), actor_stats
    assert actor_stats["rounds_ok"] == stats["rounds"] == 4
    assert actor_stats["folds"] == [0]
    assert stats["trials"] == ref_stats["trials"] == 8


def test_fleet_resume_adopts_posted_results(tmp_path):
    """A learner that died after actors posted results: the rerun
    replays the log, republishes the pending rounds onto the SAME
    t_base-keyed units, finds the posted done markers immediately, and
    completes identically."""
    full, _s, _q, _ = _drive(tmp_path / "full", fleet=True)
    # crash simulation in the same transport dir: keep only round 0's
    # trials persisted, leave every done marker on disk
    resumed, _s2, _q2, _ = _drive(
        tmp_path / "full", fleet=True, fold_trials=full[:2])
    assert resumed == full


def test_fleet_quarantine_matches_in_process_format(tmp_path):
    """FAA_FAULT trial_error fires on the ACTOR host; the posted error
    quarantines the round on the learner with entry text byte-identical
    to the in-process scheduler's."""
    os.environ["FAA_FAULT"] = "trial_error@trial=2"
    from fast_autoaugment_tpu.utils import faultinject

    faultinject.reset()
    ref, _s, ref_q, _ = _drive(tmp_path / "a", fleet=False)
    os.environ["FAA_FAULT"] = "trial_error@trial=2"
    faultinject.reset()
    got, _s2, got_q, actor_stats = _drive(tmp_path / "b", fleet=True)
    assert got == ref
    assert [q[:3] for q in got_q] == [q[:3] for q in ref_q]
    assert "injected trial_error at trial 2" in got_q[0][2]
    assert actor_stats["rounds_err"] == 1
    bad = got[2:4]
    assert all(m["quarantined"] for _p, _r, m in bad)
    assert all("RuntimeError: injected trial_error" in m["error"]
               for _p, _r, m in bad)


def test_actor_geometry_mismatch_raises_loudly(tmp_path):
    learner = FleetTransport(str(tmp_path), "learner0")
    learner.publish_round(
        0, _Light(0, [0, 1], [{"policy_0_0": 1}, {"policy_0_0": 2}]),
        key_seed=7, trial_batch=2, num_policy=1, num_op=1)
    actor_tr = FleetTransport(str(tmp_path), "actor0")
    with pytest.raises(ValueError, match="geometry mismatch"):
        run_fleet_actor(_StubFleetEval(), actor_tr, lambda f: "/nope",
                        trial_batch=4, num_policy=1, num_op=1,
                        poll_sec=0.05)


def test_sigkill_trial_fault_verb_parses_and_gates():
    from fast_autoaugment_tpu.utils.faultinject import parse_fault_spec

    faults = parse_fault_spec("sigkill_trial@trial=2,attempt=1")
    assert faults[0]["kind"] == "sigkill_trial"
    assert faults[0]["trial"] == 2 and faults[0]["attempt"] == 1
    with pytest.raises(ValueError):
        parse_fault_spec("sigkill_trial@step=2")  # wrong coordinate


# ---------------------------------------------------- roles / CLI / env


def test_resolve_search_role(monkeypatch):
    assert resolve_search_role(None) == "learner"
    assert resolve_search_role("auto") == "learner"
    assert resolve_search_role("actor") == "actor"
    monkeypatch.setenv("FAA_SEARCH_ROLE", "actor")
    assert resolve_search_role("auto") == "actor"
    assert resolve_search_role("learner") == "learner"  # flag wins
    monkeypatch.setenv("FAA_SEARCH_ROLE", "banana")
    with pytest.raises(ValueError, match="role"):
        resolve_search_role("auto")
    with pytest.raises(ValueError):
        resolve_search_role("trainer")


def test_cli_fleet_flags_parse_and_guards(tmp_path, monkeypatch):
    from fast_autoaugment_tpu.launch.search_cli import (
        _resolve_fleet_transport,
        build_parser,
    )

    p = build_parser()
    args = p.parse_args(["-c", "x.yaml"])
    assert args.fleet_transport is None and args.search_role == "auto"
    transport, role = _resolve_fleet_transport(args)
    assert transport is None and role == "learner"
    # actor without a transport dir is a launch error
    args = p.parse_args(["-c", "x.yaml", "--search-role", "actor"])
    with pytest.raises(SystemExit, match="actor"):
        _resolve_fleet_transport(args)
    # env handoff arms the transport without flags
    monkeypatch.setenv("FAA_FLEET_TRANSPORT", str(tmp_path / "tr"))
    monkeypatch.setenv("FAA_SEARCH_ROLE", "actor")
    args = p.parse_args(["-c", "x.yaml"])
    transport, role = _resolve_fleet_transport(args)
    assert role == "actor" and transport is not None
    assert transport.root == str(tmp_path / "tr")
    # transport + workqueue is a contradiction, not a preference
    args = p.parse_args(["-c", "x.yaml", "--fleet-transport",
                         str(tmp_path / "tr"), "--workqueue",
                         str(tmp_path / "wq")])
    monkeypatch.delenv("FAA_SEARCH_ROLE")
    with pytest.raises(SystemExit, match="mutually exclusive"):
        _resolve_fleet_transport(args)


def test_fleet_roles_resolve():
    assert fleet_mod.resolve_roles(None, 3) == [None, None, None]
    assert fleet_mod.resolve_roles("actor", 3) == ["actor"] * 3
    assert fleet_mod.resolve_roles("learner,actor,actor", 3) == [
        "learner", "actor", "actor"]
    with pytest.raises(ValueError, match="roles"):
        fleet_mod.resolve_roles("learner,actor", 3)


def test_fleet_exports_per_host_role(tmp_path, monkeypatch):
    """--roles exports FAA_SEARCH_ROLE per host, re-exported on every
    retry (a relaunched actor must stay an actor)."""
    log = tmp_path / "roles.log"
    monkeypatch.setattr(
        fleet_mod, "_remote_argv",
        lambda host, wire: ["bash", "-c", wire])
    code = fleet_mod.launch_fleet(
        ["a", "b"],
        ["sh", "-c", f'echo "$FAA_HOST_ID=$FAA_SEARCH_ROLE" >> {log}; '
                     f'[ "$FAA_HOST_ID" = 1 ] && exit 1; exit 0'],
        "x:1", host_retries=1, retry_backoff=0.01, rank_args=False,
        roles=["learner", "actor"])
    assert code == 1
    lines = sorted(log.read_text().split())
    # host 0 launched once as learner; host 1 twice (retry) as actor
    assert lines == ["0=learner", "1=actor", "1=actor"]


def test_env_passthrough_pin_includes_fleet_search_vars(tmp_path,
                                                       monkeypatch):
    """The satellite pin: FAA_PIPELINE_TRACE and the fleet-search
    transport env ride the default passthrough to every host launch
    AND retry, exactly like FAA_TELEMETRY and the compile cache's
    placement — which is JAX's own variable, not a private handoff."""
    assert fleet_mod.DEFAULT_ENV_PASSTHROUGH == (
        "JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR", "FAA_TELEMETRY",
        "FAA_PIPELINE_TRACE", "FAA_SEARCH_ROLE", "FAA_FLEET_TRANSPORT")
    log = tmp_path / "env.log"
    monkeypatch.setenv("FAA_PIPELINE_TRACE", "1")
    monkeypatch.setenv("FAA_FLEET_TRANSPORT", "/shared/tr")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/shared/xla")
    monkeypatch.setattr(
        fleet_mod, "_remote_argv",
        lambda host, wire: ["env", "-u", "JAX_COMPILATION_CACHE_DIR",
                            "bash", "-c", wire])
    code = fleet_mod.launch_fleet(
        ["a"],
        ["sh", "-c",
         f'echo "$FAA_PIPELINE_TRACE $FAA_FLEET_TRANSPORT '
         f'$JAX_COMPILATION_CACHE_DIR" >> {log}; exit 1'],
        "x:1", host_retries=1, retry_backoff=0.01, rank_args=False)
    assert code == 1
    # launch AND retry: the wire command itself carries the variables
    # (the "remote" shell starts without the cache variable)
    assert log.read_text().splitlines() == ["1 /shared/tr /shared/xla"] * 2


def test_telemetry_round_event_type_is_in_taxonomy():
    from fast_autoaugment_tpu.core import telemetry

    assert "round" in telemetry.EVENT_TYPES


# ------------------------------------------------- faa_status topology


def test_faa_status_renders_fleet_search_topology(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import faa_status

    root = tmp_path
    (root / "hosts").mkdir()
    now = time.time()
    for owner, role in (("host0", "learner"), ("host1", "actor")):
        (root / "hosts" / f"{owner}.json").write_text(json.dumps(
            {"owner": owner, "heartbeat": now, "role": role}))
    (root / "leases").mkdir()
    (root / "leases" / "p2r-f0-t000002.json").write_text(json.dumps(
        {"unit": "p2r-f0-t000002", "owner": "host1", "attempt": 1,
         "heartbeat": now}))
    (root / "work").mkdir()
    (root / "done").mkdir()
    for unit in ("p2r-f0-t000000", "p2r-f0-t000002"):
        (root / "work" / f"{unit}.json").write_text(json.dumps(
            {"unit": unit, "fold": 0}))
    (root / "done" / "p2r-f0-t000000.json").write_text(json.dumps(
        {"unit": "p2r-f0-t000000", "owner": "host1", "attempt": 1,
         "info": {"rewards": [0.5]}}))
    # journal: learner publishes + a phase1 lane; actor claims/returns
    # + a phase2 lane overlapping the learner's phase1 window
    events = [
        {"type": "round", "label": "p2r-f0-t000000", "action": "publish",
         "host": "host0", "t_wall": now, "t_mono": 100.0, "seq": 0},
        {"type": "round", "label": "p2r-f0-t000000", "action": "claim",
         "host": "host1", "t_wall": now + 0.1, "t_mono": 50.0, "seq": 0},
        {"type": "round", "label": "p2r-f0-t000000", "action": "return",
         "host": "host1", "t_wall": now + 1.0, "t_mono": 51.0, "seq": 1},
        {"type": "round", "label": "p2r-f0-t000000", "action": "apply",
         "host": "host0", "t_wall": now + 1.1, "t_mono": 101.1, "seq": 1},
        {"type": "phase", "label": "phase1-fold1", "lane": "phase1",
         "host": "host0", "t_wall": now + 2.0, "t_mono": 102.0,
         "t_mono_start": 100.0, "t_mono_end": 102.0, "seq": 2},
        {"type": "phase", "label": "phase2-fold0", "lane": "phase2",
         "host": "host1", "t_wall": now + 1.0, "t_mono": 51.0,
         "t_mono_start": 50.0, "t_mono_end": 51.0, "seq": 2},
    ]
    with open(root / "journal-host0-a1-p1.000.jsonl", "w") as fh:
        for e in events:
            fh.write(json.dumps(e) + "\n")

    status = faa_status.fleet_status(str(root), ttl=60.0)
    sf = status["search_fleet"]
    assert sf["hosts"]["host0"]["role"] == "learner"
    assert sf["hosts"]["host1"]["role"] == "actor"
    assert sf["hosts"]["host0"]["published"] == 1
    assert sf["hosts"]["host1"]["claimed"] == 1
    assert sf["hosts"]["host1"]["claimed_units"] == ["p2r-f0-t000002"]
    assert sf["open_rounds"] == ["p2r-f0-t000002"]
    assert sf["inflight_rounds"] == 1
    # phase1@host0 spans wall [now, now+2]; phase2@host1 spans
    # [now, now+1] — 1s of cross-host lane concurrency
    assert sf["concurrent_lane_secs"] == pytest.approx(1.0, abs=0.05)
    assert sf["concurrent_lane_pairs"][0]["phase1_host"] == "host0"
    table = faa_status.render_table(status)
    assert "fleet search:" in table
    assert "role=learner" in table and "role=actor" in table
    assert "in-flight window: 1 open round(s)" in table
    assert "concurrent lanes" in table


# ------------------------------------------------------ slow: processes


@pytest.mark.slow
def test_steal_fence_cross_process_racing_claimants(tmp_path):
    """The satellite: the PR-6 steal fence under TRUE cross-process
    racing (the existing races are thread-barrier drills in one
    process).  Four processes gate on a shared go-file and race to
    reclaim one stale lease; exactly one must win, with the reclaim
    provenance (attempt=2, reclaimed_from) intact."""
    root = tmp_path / "wq"
    seeder = WorkQueue(str(root), "dead-host", lease_ttl=1.0)
    assert seeder.claim("unit-x")
    # age the lease well past the TTL
    lease = json.load(open(root / "leases" / "unit-x.json"))
    lease["heartbeat"] -= 300.0
    (root / "leases" / "unit-x.json").write_text(json.dumps(lease))

    go = tmp_path / "go"
    script = (
        "import json, sys, time, os\n"
        "from fast_autoaugment_tpu.launch.workqueue import WorkQueue\n"
        "root, owner, go = sys.argv[1:4]\n"
        "q = WorkQueue(root, owner, lease_ttl=1.0)\n"
        "assert not q.claim('unit-x')  # observer-local: watch first\n"
        "t_obs = time.monotonic()\n"
        "deadline = time.monotonic() + 60\n"
        "while not os.path.exists(go):\n"
        "    if time.monotonic() > deadline: sys.exit(3)\n"
        "    time.sleep(0.005)\n"
        "# everyone's observation must be a full TTL old at race time\n"
        "time.sleep(max(0.0, 1.05 - (time.monotonic() - t_obs)))\n"
        "print('WON' if q.claim('unit-x') else 'LOST')\n")
    procs = [subprocess.Popen(
        [sys.executable, "-c", script, str(root), f"racer{i}", str(go)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
        for i in range(4)]
    time.sleep(2.0)  # let the interpreters reach the gate + observe
    go.write_text("go")
    outs = [p.communicate(timeout=300) for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    verdicts = [o[0].strip().splitlines()[-1] for o in outs]
    assert sorted(verdicts) == ["LOST", "LOST", "LOST", "WON"]
    lease = json.load(open(root / "leases" / "unit-x.json"))
    assert lease["attempt"] == 2
    assert lease["epoch"] == 2  # the fencing token rode the steal
    assert lease["reclaimed_from"] == "dead-host"
    assert lease["owner"].startswith("racer")
    # the fence file never survives the steal
    assert not os.path.exists(str(root / "leases" / "unit-x.json.steal"))


_CONF_YAML = (
    "model:\n  type: wresnet10_1\ndataset: synthetic\naug: default\n"
    "cutout: 8\nbatch: 8\nepoch: 1\nlr: 0.05\n"
    "lr_schedule:\n  type: cosine\n"
    "optimizer:\n  type: sgd\n  decay: 0.0001\n  momentum: 0.9\n"
    "  nesterov: true\n")


@pytest.mark.slow
def test_fleet_search_e2e_bit_identical_through_actor_sigkill(tmp_path,
                                                              spawn_logged):
    """THE acceptance drill: a 3-process fleet (1 learner+trainer, 2
    actor hosts) over a shared transport + compile cache produces
    search_trials.json and final_policy.json BYTE-IDENTICAL to the
    single-host --async-pipeline run — including after one actor host
    is SIGKILLed mid-round (FAA_FAULT sigkill_trial) and its round is
    reclaimed by the survivor."""
    tmp = str(tmp_path)
    conf = tmp_path / "conf.yaml"
    conf.write_text(_CONF_YAML)
    base = [sys.executable, "-m",
            "fast_autoaugment_tpu.launch.search_cli",
            "-c", str(conf), "--dataroot", tmp,
            "--num-fold", "2", "--num-search", "4", "--num-policy", "1",
            "--num-op", "1", "--num-top", "2", "--trial-batch", "2",
            "--until", "2", "--fold-quality-floor", "off",
            "--seed", "0",
            "--async-pipeline", "on", "--pipeline-actors", "2",
            "--pipeline-queue-depth", "2"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FAA_FAULT", None)

    # ---- single-host reference (also warms the shared compile cache)
    ref = subprocess.run(base + ["--save-dir", f"{tmp}/ref"], env=env,
                         capture_output=True, text=True, timeout=900)
    assert ref.returncode == 0, ref.stderr[-3000:]

    # ---- the 3-process fleet; actor host1 dies mid-round, every time
    tr, save = f"{tmp}/transport", f"{tmp}/fleet"
    fleet_base = base + ["--save-dir", save, "--fleet-transport", tr,
                         "--lease-ttl", "6"]
    learner = spawn_logged(
        fleet_base + ["--search-role", "learner", "--host-id", "0"],
        env=dict(env, FAA_HOST_ID="0"), name="learner")
    # trial=1 + a head start, as in the fsfault drills: the doomed actor
    # dies on the FIRST round it evaluates (any round covers a trial
    # index >= 1) and reliably wins a claim before the survivor exists —
    # with every process warm-started from the shared compile cache, two
    # actors launched together race evenly and the doomed one could
    # finish having only drawn rounds below its kill index
    doomed = spawn_logged(
        fleet_base + ["--search-role", "actor", "--host-id", "1"],
        env=dict(env, FAA_HOST_ID="1",
                 FAA_FAULT="sigkill_trial@trial=1"), name="doomed")
    time.sleep(5.0)
    survivor = spawn_logged(
        fleet_base + ["--search-role", "actor", "--host-id", "2"],
        env=dict(env, FAA_HOST_ID="2"), name="survivor")
    out_l = spawn_logged.finish(learner, 900)
    out_d = spawn_logged.finish(doomed, 120)
    out_s = spawn_logged.finish(survivor, 300)
    assert learner.returncode == 0, out_l[-3000:]
    assert survivor.returncode == 0, out_s[-3000:]
    assert doomed.returncode == -9, (doomed.returncode, out_d[-1500:])

    # byte-identity through the kill + reclaim
    assert (open(f"{tmp}/ref/search_trials.json", "rb").read()
            == open(f"{save}/search_trials.json", "rb").read())
    assert (open(f"{tmp}/ref/final_policy.json", "rb").read()
            == open(f"{save}/final_policy.json", "rb").read())
    result = json.load(open(f"{save}/search_result.json"))
    assert result["degraded"] is True
    assert result["reclaimed_units"], "the dead actor's round reclaimed"
    assert all(u.startswith("p2r-") for u in result["reclaimed_units"])
    assert "host1" in result["lost_hosts"]
    assert result["fleet_transport"]["window"] == 4
    # the single-host reference artifact carries NO fleet stamps
    ref_result = json.load(open(f"{tmp}/ref/search_result.json"))
    assert "fleet_transport" not in ref_result
