"""Unified telemetry (core/telemetry.py): registry semantics, the
flight-recorder journal, the span seam, Prometheus exposition, the TB
bridge, and the one-source-of-truth equality pins that keep artifact
stamps from drifting away from the counters the hot paths bump.

All host-only / no-XLA-compile (tier-1 discipline): the only jax
touched is import-time.
"""

import glob
import json
import os
import sys
import threading
import time
import urllib.request

import pytest

from fast_autoaugment_tpu.core import telemetry as T

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))


@pytest.fixture()
def journal_dir(tmp_path):
    """Arm the process journal in a tmp dir, detach afterwards."""
    d = str(tmp_path / "tel")
    T.enable_telemetry(d, tb_bridge=True)
    yield d
    T._disable_for_tests()


@pytest.fixture(autouse=True)
def _no_env_journal(monkeypatch):
    """An inherited FAA_TELEMETRY must not leak into these tests."""
    monkeypatch.delenv("FAA_TELEMETRY", raising=False)
    yield
    T._disable_for_tests()


def _read_records(directory):
    T.journal_flush()  # events are interval-buffered; force them out
    records = []
    for path in sorted(glob.glob(os.path.join(directory,
                                              "journal-*.jsonl"))):
        with open(path) as fh:
            records.extend(json.loads(ln) for ln in fh if ln.strip())
    records.sort(key=lambda r: r["seq"])
    return records


# ------------------------------------------------------------ registry


def test_counter_gauge_histogram_basics():
    reg = T.MetricsRegistry()
    c = reg.counter("faa_x_total", "x", label="a")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("faa_g")
    g.set(7)
    g.inc(-2)
    assert g.value == 5.0
    h = reg.histogram("faa_h_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 3
    assert snap["buckets"] == {"0.1": 1, "1": 2, "+Inf": 3}
    assert abs(snap["sum"] - 5.55) < 1e-9


def test_registry_get_or_create_and_label_children():
    reg = T.MetricsRegistry()
    a1 = reg.counter("faa_c_total", label="a")
    a2 = reg.counter("faa_c_total", label="a")
    b = reg.counter("faa_c_total", label="b")
    assert a1 is a2 and a1 is not b
    a1.inc()
    snap = reg.snapshot()
    assert snap["counters"]['faa_c_total{label="a"}'] == 1.0
    assert snap["counters"]['faa_c_total{label="b"}'] == 0.0


def test_registry_kind_and_bucket_conflicts_raise():
    reg = T.MetricsRegistry()
    reg.counter("faa_c_total")
    with pytest.raises(ValueError):
        reg.gauge("faa_c_total")
    reg.histogram("faa_h_seconds", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("faa_h_seconds", buckets=(5.0,))
    with pytest.raises(ValueError):
        reg.counter("not a name!")


def test_prometheus_text_exposition_format():
    reg = T.MetricsRegistry()
    reg.counter("faa_c_total", "the counter", label="x").inc(3)
    reg.gauge("faa_g").set(1.5)
    reg.histogram("faa_h_seconds", buckets=(0.1, 1.0),
                  label="y").observe(0.5)
    text = reg.prometheus_text()
    assert "# HELP faa_c_total the counter" in text
    assert "# TYPE faa_c_total counter" in text
    assert 'faa_c_total{label="x"} 3' in text
    assert "faa_g 1.5" in text
    assert '# TYPE faa_h_seconds histogram' in text
    assert 'faa_h_seconds_bucket{label="y",le="0.1"} 0' in text
    assert 'faa_h_seconds_bucket{label="y",le="1"} 1' in text
    assert 'faa_h_seconds_bucket{label="y",le="+Inf"} 1' in text
    assert 'faa_h_seconds_sum{label="y"} 0.5' in text
    assert 'faa_h_seconds_count{label="y"} 1' in text


def test_registry_reset_for_tests_keeps_registrations():
    reg = T.MetricsRegistry()
    c = reg.counter("faa_c_total")
    c.inc(5)
    reg._reset_for_tests()
    assert c.value == 0.0
    assert reg.counter("faa_c_total") is c


# ------------------------------------------------------------- journal


def test_emit_is_noop_when_off(tmp_path):
    assert not T.journal_active()
    T.emit("mark", "nothing-happens")  # must not raise or write


def test_journal_records_carry_identity_and_both_clocks(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv("FAA_HOST_ID", "7")
    monkeypatch.setenv("FAA_ATTEMPT", "3")
    d = str(tmp_path / "tel")
    T.enable_telemetry(d, tb_bridge=False)
    try:
        T.emit("mark", "hello", value=1.5)
    finally:
        T._disable_for_tests()
    (rec,) = _read_records(d)
    assert rec["type"] == "mark" and rec["label"] == "hello"
    assert rec["host"] == "host7" and rec["attempt"] == 3
    assert rec["pid"] == os.getpid() and rec["tid"] > 0
    assert rec["thread"] == threading.current_thread().name
    assert isinstance(rec["t_wall"], float)
    assert isinstance(rec["t_mono"], float)
    assert rec["value"] == 1.5
    assert "a3" in os.path.basename(glob.glob(
        os.path.join(d, "journal-*.jsonl"))[0])


def test_journal_taxonomy_is_closed(journal_dir):
    with pytest.raises(ValueError):
        T.emit("made_up_event")
    for etype in sorted(T.EVENT_TYPES):
        T.emit(etype, "ok")  # every documented type is accepted


def test_journal_segment_rotation_bounds_size(tmp_path):
    d = str(tmp_path / "tel")
    rec = T.FlightRecorder(d, max_segment_bytes=400, max_segments=3,
                           tb_bridge=False)
    for i in range(60):
        rec.emit("mark", "m", i=i)
    rec.close()
    segs = sorted(glob.glob(os.path.join(d, "journal-*.jsonl")))
    assert len(segs) == 3  # older segments were deleted
    total = sum(os.path.getsize(s) for s in segs)
    assert total < 3 * (400 + 400)  # bounded: ring, not an archive
    # the SURVIVING records are the newest ones, seq-contiguous
    records = _read_records(d)
    seqs = [r["seq"] for r in records]
    assert seqs == list(range(seqs[0], 60))


def test_env_handoff_and_resolve(monkeypatch, tmp_path):
    assert T.resolve_telemetry("off") is None
    assert T.resolve_telemetry(None) is None
    monkeypatch.setenv("FAA_TELEMETRY", str(tmp_path / "env"))
    assert T.resolve_telemetry(None) == str(tmp_path / "env")
    assert T.resolve_telemetry("off") == str(tmp_path / "env")
    explicit = str(tmp_path / "flag")
    assert T.resolve_telemetry(explicit) == explicit
    got = T.configure_telemetry(explicit)
    try:
        assert got == os.path.abspath(explicit)
        assert os.environ["FAA_TELEMETRY"] == got  # child-process handoff
        assert T.telemetry_dir() == got
    finally:
        T._disable_for_tests()


# ----------------------------------------------------------- span seam


def test_span_inside_nested_stages_feeds_registry_and_journal(journal_dir):
    """A dispatch span keeps its records wherever it runs, and the stages
    around it nest: each knows its parent, its depth and its dotted path."""
    reg = T.registry()
    c0 = reg.counter("faa_dispatches_total",
                     label="train_dispatch").value
    with T.stage("nest_root", run=7) as root:
        with T.stage("outer") as outer:
            with T.stage("inner") as inner:
                with T.span("train_dispatch", step=4):
                    time.sleep(0.01)
    assert (root.path, outer.path, inner.path) == (
        "nest_root", "nest_root.outer", "nest_root.outer.inner")
    assert (root.depth, outer.depth, inner.depth) == (0, 1, 2)
    assert root.parent is None and inner.parent is outer
    assert reg.counter("faa_dispatches_total",
                       label="train_dispatch").value == c0 + 1
    records = _read_records(journal_dir)
    rec = [r for r in records if r["type"] == "dispatch"][-1]
    assert rec["label"] == "train_dispatch" and rec["step"] == 4
    assert rec["t_mono_end"] >= rec["t_mono_start"]
    assert abs(rec["dur_sec"]
               - (rec["t_mono_end"] - rec["t_mono_start"])) < 1e-6
    phases = {r["label"]: r for r in records if r["type"] == "phase"}
    assert phases["nest_root.outer.inner"]["parent"] == "nest_root.outer"
    assert phases["nest_root.outer.inner"]["depth"] == 2
    assert phases["nest_root"]["parent"] is None
    assert phases["nest_root"]["run"] == 7
    # the dispatch lies inside the innermost stage's window
    inner_rec = phases["nest_root.outer.inner"]
    assert inner_rec["t_mono_start"] <= rec["t_mono_start"]
    assert rec["t_mono_end"] <= inner_rec["t_mono_end"]


# --------------------------------------------------------------- stages


def test_stage_tree_records_children_in_order_inside_their_parent():
    with T.stage("tree_root", kind="t"):
        with T.stage("a"):
            time.sleep(0.002)
        with T.stage("b"):
            with T.stage("c"):
                pass
    root = T.stage_trees()[-1]
    assert root["name"] == "tree_root" and root["fields"] == {"kind": "t"}
    assert set(root) == {"name", "fields", "t_wall_start", "t_mono_start",
                         "dur", "children"}
    assert [c["name"] for c in root["children"]] == ["a", "b"]
    assert [c["name"] for c in root["children"][1]["children"]] == ["c"]
    a, b = root["children"]
    assert root["t_mono_start"] <= a["t_mono_start"]
    assert a["t_mono_start"] + a["dur"] <= b["t_mono_start"]
    assert b["t_mono_start"] + b["dur"] <= root["t_mono_start"] + root["dur"]
    assert abs(root["t_wall_start"] - time.time()) < 60.0


def test_stage_closes_on_exception_and_reraises(journal_dir):
    n0 = len(T.stage_trees())

    class Boom(Exception):
        pass

    with pytest.raises(Boom):
        with T.stage("exc_root"):
            with T.stage("child"):
                raise Boom("x")
    root = T.stage_trees()[-1]
    assert len(T.stage_trees()) <= n0 + 1
    assert root["name"] == "exc_root"
    assert [c["name"] for c in root["children"]] == ["child"]
    assert root["dur"] >= root["children"][0]["dur"] >= 0.0
    labels = [r["label"] for r in _read_records(journal_dir)
              if r["type"] == "phase"]
    assert labels[-2:] == ["exc_root.child", "exc_root"]
    # the thread's stack is empty again: the next stage is a root
    with T.stage("after_exc") as st:
        pass
    assert st.parent is None and st.depth == 0


def test_stage_on_a_second_thread_has_its_own_stack():
    seen = {}
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with T.stage("thread_root") as st:
            seen["parent"], seen["path"] = st.parent, st.path
            inside.set()
            assert release.wait(10.0)

    with T.stage("main_root") as main:
        th = threading.Thread(target=worker)
        th.start()
        assert inside.wait(10.0)
        with T.stage("main_child") as child:
            pass
        release.set()
        th.join(10.0)
        assert not th.is_alive()
    assert seen == {"parent": None, "path": "thread_root"}
    assert child.parent is main and child.path == "main_root.main_child"
    names = [r["name"] for r in T.stage_trees()[-2:]]
    assert names == ["thread_root", "main_root"]
    assert [c["name"] for c in T.stage_trees()[-1]["children"]] == [
        "main_child"]


def test_stage_adds_its_duration_to_the_phase_counter():
    counter = T.registry().counter("faa_phase_seconds_total",
                                   label="count_root.work")
    v0 = counter.value
    for _ in range(2):
        with T.stage("count_root") as root:
            with T.stage("work"):
                time.sleep(0.005)
    work = T.stage_trees()[-1]["children"][0]
    prev = T.stage_trees()[-2]["children"][0]
    assert counter.value - v0 == pytest.approx(work["dur"] + prev["dur"])
    assert root.summary()["count_root.work"]["n"] == 1
    assert root.summary()["count_root.work"]["sec"] == pytest.approx(
        work["dur"], abs=1e-5)


def test_armed_journal_gets_one_phase_event_per_stage(journal_dir):
    with T.stage("journal_root"):
        with T.stage("only", epoch=3):
            pass
    phases = [r for r in _read_records(journal_dir) if r["type"] == "phase"]
    mine = [r for r in phases if r["label"] == "journal_root.only"]
    assert len(mine) == 1
    rec = mine[0]
    assert rec["lane"] == "trainer" and rec["epoch"] == 3
    assert rec["parent"] == "journal_root" and rec["depth"] == 1
    assert rec["t_mono_end"] >= rec["t_mono_start"]
    assert "phase" in T.EVENT_TYPES  # no event type of its own


def test_stage_trees_is_bounded():
    with T.stage("bound_root"):
        with T.stage("setup_a"):
            pass
        for i in range(T.STAGE_SIBLINGS_KEPT + 40):
            with T.stage("epoch", epoch=i):
                pass
        with T.stage("setup_b"):
            pass
    children = T.stage_trees()[-1]["children"]
    epochs = [c["fields"]["epoch"] for c in children if c["name"] == "epoch"]
    # the newest are kept, and every stage of another name
    assert epochs == list(range(40, T.STAGE_SIBLINGS_KEPT + 40))
    assert children[0]["name"] == "setup_a" and children[-1]["name"] == "setup_b"
    for i in range(T.STAGE_ROOTS_KEPT + 5):
        with T.stage("many_roots", i=i):
            pass
    trees = T.stage_trees()
    assert len(trees) == T.STAGE_ROOTS_KEPT
    assert trees[-1]["fields"] == {"i": T.STAGE_ROOTS_KEPT + 4}


def test_import_and_stage_initialise_no_backend():
    """``tools/faa_status.py`` and the journal's readers import this module
    where no accelerator may be touched."""
    import subprocess

    code = (
        "import sys\n"
        "from fast_autoaugment_tpu.core import telemetry as T\n"
        "assert 'jax' not in sys.modules, 'import pulled jax in'\n"
        "with T.stage('r'):\n"
        "    with T.span('d'):\n"
        "        pass\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print(T.stage_trees()[-1]['name'])\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "r"


def test_dispatch_journal_rate_bound_registry_stays_exact(tmp_path):
    """A kHz dispatch loop journals at most the per-label budget of
    slices per second (suppressed slices are counted), while the
    registry histogram observes EVERY dispatch — exact counts, bounded
    journal cost."""
    d = str(tmp_path / "tel")
    T.enable_telemetry(d, dispatch_events_per_sec=50, tb_bridge=False)
    reg = T.registry()
    hist = reg.histogram("faa_dispatch_seconds", label="rate_test")
    sup = reg.counter("faa_dispatch_events_suppressed_total",
                      label="rate_test")
    h0, s0 = hist.snapshot()["count"], sup.value
    try:
        for i in range(500):
            T.record_dispatch("rate_test", 1.0, 1.001, step=i)
        assert hist.snapshot()["count"] == h0 + 500  # registry: exact
        journaled = [r for r in _read_records(d)
                     if r["type"] == "dispatch"
                     and r["label"] == "rate_test"]
        # the tight loop runs well under a second: one 50-slice window
        assert len(journaled) <= 101
        assert sup.value - s0 == 500 - len(journaled) > 0
    finally:
        T._disable_for_tests()


def test_record_dispatch_histogram_observation():
    reg = T.registry()
    h = reg.histogram("faa_dispatch_seconds", label="unit_test_label")
    before = h.snapshot()["count"]
    T.record_dispatch("unit_test_label", 10.0, 10.5)
    snap = h.snapshot()
    assert snap["count"] == before + 1
    assert snap["sum"] >= 0.5


def test_phase_event_counter_and_journal(journal_dir):
    reg = T.registry()
    c = reg.counter("faa_phase_seconds_total", label="phase1-fold9")
    T.phase_event("phase1-fold9", 100.0, 101.5, fold=9, lane="phase1")
    assert abs(c.value - 1.5) < 1e-9
    rec = [r for r in _read_records(journal_dir)
           if r["type"] == "phase"][-1]
    assert rec["lane"] == "phase1" and rec["fold"] == 9


# ----------------------------------------------------------- TB bridge


def test_tb_bridge_crc_verified_roundtrip(journal_dir):
    from fast_autoaugment_tpu.utils.tb_events import read_events

    T.emit("trial", "fold0", fold=0, trial=5, reward=0.875, step=5)
    T.emit("trial", "fold0", fold=0, trial=6, reward=0.9, step=6)
    (tb_file,) = glob.glob(os.path.join(journal_dir, "tb",
                                        "events.out.tfevents.*"))
    events = read_events(tb_file, verify_crc=True)  # raises on bad CRC
    scalars = {(e.get("tag"), e.get("step")): e.get("value")
               for e in events if "tag" in e}
    assert abs(scalars[("trial/fold0/reward", 5)] - 0.875) < 1e-6
    assert abs(scalars[("trial/fold0/reward", 6)] - 0.9) < 1e-6
    # non-numeric and identity fields never become scalars
    assert all(not (tag or "").endswith("/host")
               for tag, _ in scalars)


# ------------------------------------------- one-source-of-truth pins


def test_compile_cache_stats_sourced_from_registry():
    from fast_autoaugment_tpu.core import compilecache as cc

    cc._reset_stats_for_tests()
    try:
        reg_hits = T.registry().counter("faa_compile_cache_hits_total")
        reg_misses = T.registry().counter("faa_compile_cache_misses_total")
        assert cc.compile_cache_stats()["hits"] == int(reg_hits.value) == 0
        cc._listener("/jax/compilation_cache/cache_hits")
        cc._listener("/jax/compilation_cache/cache_hits")
        cc._listener("/jax/compilation_cache/cache_misses")
        stats = cc.compile_cache_stats()
        assert stats["hits"] == int(reg_hits.value) == 2
        assert stats["misses"] == int(reg_misses.value) == 1
    finally:
        cc._reset_stats_for_tests()


def test_watchdog_fire_mirrors_registry_and_journal(journal_dir):
    from fast_autoaugment_tpu.core.watchdog import DispatchWatchdog

    wd = DispatchWatchdog(0.2, compile_allowance=0.2)
    ctr = T.registry().counter("faa_watchdog_fires_total",
                               label="unit_wd_label")
    before = ctr.value
    from fast_autoaugment_tpu.core.resilience import DispatchHungError

    with pytest.raises(DispatchHungError):
        wd.run("unit_wd_label", time.sleep, 5.0)
    assert wd.fires == 1
    assert ctr.value == before + 1
    rec = [r for r in _read_records(journal_dir)
           if r["type"] == "watchdog_fire"][-1]
    assert rec["label"] == "unit_wd_label"
    assert rec["deadline_sec"] == pytest.approx(0.2, abs=0.05)


def test_watchdog_ema_mirrors_registry_gauge():
    from fast_autoaugment_tpu.core.watchdog import DispatchWatchdog

    wd = DispatchWatchdog("auto")
    wd.observe("unit_ema_label", 0.5)
    wd.observe("unit_ema_label", 1.0)
    g = T.registry().gauge("faa_watchdog_ema_seconds",
                           label="unit_ema_label")
    assert g.value == pytest.approx(wd.ema("unit_ema_label"))


def test_breaker_fire_counts_and_journals(journal_dir):
    from fast_autoaugment_tpu.core.resilience import CircuitBreaker

    br = CircuitBreaker(threshold=2, cooldown_s=60.0, name="unit_breaker")
    ctr = T.registry().counter("faa_breaker_fires_total",
                               breaker="unit_breaker")
    br.record_failure()
    assert ctr.value == 0  # below threshold: no fire
    br.record_failure()
    assert br.fires == 1 and ctr.value == 1
    rec = [r for r in _read_records(journal_dir)
           if r["type"] == "breaker_fire"][-1]
    assert rec["label"] == "unit_breaker"
    assert rec["consecutive_failures"] == 2


def test_serve_counters_one_source_of_truth():
    import numpy as np

    from fast_autoaugment_tpu.serve.policy_server import (
        PolicyServer,
        ServerOverloadedError,
    )

    class _Applier:
        dispatch = "grouped"
        max_batch = 4
        image = 8
        channels = 3
        num_sub = 1
        shapes = (4,)

        def apply(self, images, keys):
            return images

    srv = PolicyServer(_Applier(), queue_depth=1)
    img = np.zeros((1, 8, 8, 3), np.float32)
    srv.submit(img)
    with pytest.raises(ServerOverloadedError):
        srv.submit(img)
    reg = T.registry()
    adm = reg.counter("faa_serve_robustness_total", counter="admitted",
                      server=srv._server_id)
    shed = reg.counter("faa_serve_robustness_total",
                       counter="shed_overload", server=srv._server_id)
    # attribute view == /stats view == registry child — one number
    assert srv.admitted == int(adm.value) == 1
    assert srv.shed_overload == int(shed.value) == 1
    assert srv.stats()["admission"]["admitted"] == 1
    assert srv.stats()["admission"]["shed_overload"] == 1
    srv.stop()


def test_lease_events_counters_and_journal(journal_dir, tmp_path):
    from fast_autoaugment_tpu.launch.workqueue import WorkQueue

    reg = T.registry()
    claims = reg.counter("faa_lease_events_total", action="claim")
    reclaims = reg.counter("faa_lease_events_total", action="reclaim")
    releases = reg.counter("faa_lease_events_total", action="release")
    c0, r0, d0 = claims.value, reclaims.value, releases.value

    q1 = WorkQueue(str(tmp_path / "wq"), "hostA", lease_ttl=0.05)
    assert q1.claim("u1")
    q2 = WorkQueue(str(tmp_path / "wq"), "hostB", lease_ttl=0.05)
    assert not q2.claim("u1")  # hostB observes the foreign lease...
    time.sleep(0.12)           # ...which sits unchanged past the TTL
    assert q2.claim("u1")  # reclaim (observer-local staleness)
    q2.release("u1", info={"ok": True})
    assert claims.value == c0 + 1
    assert reclaims.value == r0 + 1
    assert releases.value == d0 + 1
    recs = [r for r in _read_records(journal_dir) if r["type"] == "lease"]
    actions = [r["action"] for r in recs if r["label"] == "u1"]
    assert actions == ["claim", "reclaim", "release"]
    reclaim_rec = recs[[r["action"] for r in recs].index("reclaim")]
    assert reclaim_rec["reclaimed_from"] == "hostA"
    assert reclaim_rec["lease_attempt"] == 2


def test_checkpoint_events_on_save_and_load(journal_dir, tmp_path):
    import numpy as np

    from fast_autoaugment_tpu.core.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )

    reg = T.registry()
    saved = reg.counter("faa_checkpoints_saved_total")
    loaded = reg.counter("faa_checkpoints_loaded_total")
    s0, l0 = saved.value, loaded.value
    path = str(tmp_path / "ck" / "state.msgpack")
    state = {"w": np.arange(4, dtype=np.float32)}
    save_checkpoint(path, state, metadata={"epoch": 3})
    load_checkpoint(path, {"w": np.zeros(4, np.float32)})
    assert saved.value == s0 + 1 and loaded.value == l0 + 1
    recs = [r for r in _read_records(journal_dir)
            if r["type"] == "checkpoint"]
    assert [r["action"] for r in recs] == ["save", "load"]
    assert recs[0]["epoch"] == 3 and recs[0]["nbytes"] > 0


# ------------------------------------- profiling satellite (stopwatch)


def test_phase_stopwatch_mirrors_registry_gauges():
    from fast_autoaugment_tpu.utils.profiling import PhaseStopwatch

    reg = T.MetricsRegistry()
    sw = PhaseStopwatch(device_count=4, registry=reg)
    with sw.phase("unit_phase"):
        time.sleep(0.01)
    wall_g = reg.gauge("faa_phase_wall_seconds", phase="unit_phase")
    dev_g = reg.gauge("faa_phase_device_seconds", phase="unit_phase")
    assert wall_g.value == pytest.approx(sw.wall_seconds("unit_phase"))
    assert dev_g.value == pytest.approx(sw.device_seconds("unit_phase"))
    assert dev_g.value == pytest.approx(4 * wall_g.value)
    # accumulation: a second window updates BOTH views identically
    with sw.phase("unit_phase"):
        time.sleep(0.01)
    assert wall_g.value == pytest.approx(sw.wall_seconds("unit_phase"))


def test_phase1_attribution_identity_matches_stopwatch():
    """The device_secs_phase1_per_fold identity: the stamp is the
    attribution helper over the stopwatch ledger — per-fold phases
    credit directly, stacked groups split one measured wall evenly, and
    the registry gauges carry the same numbers."""
    from fast_autoaugment_tpu.search.driver import (
        phase1_device_seconds_attribution,
    )
    from fast_autoaugment_tpu.utils.profiling import PhaseStopwatch

    reg = T.MetricsRegistry()
    sw = PhaseStopwatch(device_count=2, registry=reg)
    with sw.phase("phase1_fold0"):
        time.sleep(0.01)
    with sw.phase("phase1_fold0"):  # a gate retrain accumulates
        time.sleep(0.01)
    with sw.phase("phase1_stack0"):  # folds 1+2 trained stacked
        time.sleep(0.02)
    attr = phase1_device_seconds_attribution(sw, [0, 1, 2], [[1, 2]])
    assert attr[0] == pytest.approx(sw.device_seconds("phase1_fold0"))
    assert attr[1] == attr[2] == pytest.approx(
        sw.device_seconds("phase1_stack0") / 2)
    assert attr[0] > 0 and attr[1] > 0
    # registry mirror: the gauge holds exactly the ledger's number
    assert reg.gauge("faa_phase_device_seconds",
                     phase="phase1_stack0").value == pytest.approx(
        sw.device_seconds("phase1_stack0"))


# -------------------------------------------------- export surfaces


def test_metrics_http_server_scrape():
    T.registry().counter("faa_scrape_test_total").inc(3)
    httpd, port = T.start_metrics_server(0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        assert "faa_scrape_test_total 3" in text
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
            assert resp.status == 200
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_faa_status_aggregates_journals_and_beats(tmp_path):
    from faa_status import fleet_status, render_table

    d = str(tmp_path)
    now = time.time()
    # host0: journal with dispatch windows + a watchdog fire
    rec = T.FlightRecorder(d, host="host0", attempt=1, tb_bridge=False)
    rec.emit("dispatch", "tta", t_mono_start=1.0, t_mono_end=2.0)
    rec.emit("dispatch", "tta", t_mono_start=2.5, t_mono_end=3.0)
    rec.emit("watchdog_fire", "tta", deadline_sec=1.0, waited_sec=2.0)
    rec.close()
    # heartbeats: host0 alive, host1 stale, host2 done
    os.makedirs(os.path.join(d, "hosts"))
    for owner, beat, done in (("host0", now, False),
                              ("host1", now - 600, False),
                              ("host2", now - 600, True)):
        with open(os.path.join(d, "hosts", f"{owner}.json"), "w") as fh:
            json.dump({"owner": owner, "heartbeat": beat, "done": done},
                      fh)
    # done markers: one reclaimed unit finished by host0
    os.makedirs(os.path.join(d, "done"))
    with open(os.path.join(d, "done", "p1-fold1.json"), "w") as fh:
        json.dump({"unit": "p1-fold1", "owner": "host0", "attempt": 2,
                   "reclaimed_from": "host1"}, fh)

    status = fleet_status(d, ttl=60.0, now=now)
    h0 = status["hosts"]["host0"]
    assert h0["dispatches"] == 2
    assert h0["busy_frac"] == pytest.approx(1.5 / 2.0)
    assert h0["gap_p50_ms"] == pytest.approx(500.0)
    assert h0["watchdog_fires"] == 1
    assert h0["beat"] == "alive"
    assert h0["units_done"] == 1
    assert status["hosts"]["host1"]["beat"].startswith("STALE")
    assert status["hosts"]["host2"]["beat"] == "done"
    assert status["reclaimed_units"] == [{
        "unit": "p1-fold1", "attempt": 2, "finished_by": "host0",
        "reclaimed_from": "host1"}]
    table = render_table(status)
    assert "host0" in table and "STALE" in table and "reclaimed" in table
