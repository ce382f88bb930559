"""Chrome-trace export (tools/trace_export.py): journal -> trace.json
schema round-trip, clock alignment, and the phase-overlap lane
rendering (the PR-9 drill's evidence as a timeline).

Host-only / no-XLA-compile (tier-1 discipline): the overlap drill runs
``run_overlapped_phases`` with stub phase bodies.
"""

import json
import os
import sys
import time

import pytest

from fast_autoaugment_tpu.core import telemetry as T

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))

from trace_export import (  # noqa: E402
    PHASE_LANES,
    journal_to_trace,
    read_journal,
    validate_trace,
)
import trace_export  # noqa: E402


@pytest.fixture()
def journal_dir(tmp_path):
    d = str(tmp_path / "tel")
    T.enable_telemetry(d, tb_bridge=False)
    yield d
    T._disable_for_tests()


def _slices(trace, cat=None):
    return [e for e in trace["traceEvents"] if e["ph"] == "X"
            and (cat is None or e.get("cat") == cat)]


def test_roundtrip_validates_against_chrome_schema(journal_dir):
    with T.span("train_dispatch", step=0):
        time.sleep(0.002)
    with T.span("serve_dispatch", etype="dispatch", batch=8):
        time.sleep(0.002)
    T.emit("shed", "serve0", reason="overload", n=2)
    T.emit("breaker_fire", "serve0", fires=1)
    T.phase_event("phase1-fold0", 1.0, 2.0, fold=0, lane="phase1")

    T.journal_flush()
    records = read_journal(journal_dir)
    assert len(records) == 5
    trace = journal_to_trace(records)
    assert validate_trace(trace) == []  # the schema gate
    # and the file round-trips through JSON intact
    again = json.loads(json.dumps(trace))
    assert validate_trace(again) == []

    slices = _slices(trace, "dispatch")
    assert {s["name"] for s in slices} == {"train_dispatch",
                                           "serve_dispatch"}
    for s in slices:
        assert s["dur"] > 0 and s["ts"] >= 0
    marks = [e for e in trace["traceEvents"] if e["ph"] == "i"]
    assert {m["cat"] for m in marks} == {"shed", "breaker_fire"}
    assert all(m["s"] == "t" for m in marks)
    # args carry the typed payload fields
    (shed,) = [m for m in marks if m["cat"] == "shed"]
    assert shed["args"]["reason"] == "overload" and shed["args"]["n"] == 2


def test_stage_events_nest_on_their_real_thread_lane(journal_dir):
    """A stage's ``phase`` event (``lane: "trainer"``) is a slice on the
    thread that ran it, inside its parent's and around the dispatches it
    made: not on a synthetic phase lane."""
    import threading

    with T.stage("train_and_eval"):
        with T.stage("epoch", epoch=1):
            with T.stage("dispatch_loop"):
                with T.span("train_dispatch", step=1):
                    time.sleep(0.002)
    T.phase_event("phase2-fold0", 1.0, 2.0, fold=0)
    T.journal_flush()
    trace = journal_to_trace(read_journal(journal_dir))
    assert validate_trace(trace) == []
    by_name = {s["name"]: s for s in _slices(trace)}
    stages = [by_name[n] for n in (
        "train_and_eval", "train_and_eval.epoch",
        "train_and_eval.epoch.dispatch_loop", "train_dispatch")]
    tid = threading.get_native_id()
    assert [s["tid"] for s in stages] == [tid] * 4
    for outer, inner in zip(stages, stages[1:]):
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert by_name["train_and_eval.epoch"]["args"]["epoch"] == 1
    assert by_name["train_and_eval.epoch"]["args"]["parent"] == "train_and_eval"
    # any other lane keeps its synthetic track
    assert by_name["phase2-fold0"]["tid"] == PHASE_LANES["phase2"]


def test_validate_trace_catches_schema_violations():
    assert validate_trace({"traceEvents": "nope"})
    bad = {"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 1,
                            "ts": 0}]}  # X without dur
    assert any("dur" in p for p in validate_trace(bad))
    bad = {"traceEvents": [{"ph": "i", "name": "x", "pid": 1, "tid": 1,
                            "ts": 0}]}  # instant without scope
    assert any("'s'" in p for p in validate_trace(bad))
    ok = {"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 1,
                           "ts": 0, "dur": 1}]}
    assert validate_trace(ok) == []


def test_overlap_drill_renders_distinct_phase_lanes(journal_dir):
    """The PR-9 overlap evidence as a timeline: fold k's phase-2 slice
    overlaps fold k+1's phase-1 slice, on two DISTINCT lanes."""
    from fast_autoaugment_tpu.search.pipeline import run_overlapped_phases

    def p1(fold):
        time.sleep(0.05)

    def p2(fold):
        with T.span("tta", step=fold):
            time.sleep(0.02)

    timeline = run_overlapped_phases([0, 1, 2], p1, p2, poll_sec=0.01)
    assert timeline["overlap_secs"] > 0  # the drill really overlapped

    T.journal_flush()
    trace = journal_to_trace(read_journal(journal_dir))
    assert validate_trace(trace) == []
    phases = _slices(trace, "phase")
    by_lane = {}
    for s in phases:
        by_lane.setdefault(s["tid"], []).append(s)
    # two distinct lanes, one per phase, each holding all three folds
    assert set(by_lane) == set(PHASE_LANES.values())
    assert len(by_lane[PHASE_LANES["phase1"]]) == 3
    assert len(by_lane[PHASE_LANES["phase2"]]) == 3
    # lane names are human-readable in the metadata
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"phase-1 (train)", "phase-2 (search)"} <= names
    # the rendered timeline shows the overlap: fold 0's phase-2 slice
    # intersects fold 1's phase-1 slice in trace time
    p2_f0 = next(s for s in by_lane[PHASE_LANES["phase2"]]
                 if s["name"] == "phase2-fold0")
    p1_f1 = next(s for s in by_lane[PHASE_LANES["phase1"]]
                 if s["name"] == "phase1-fold1")
    lo = max(p2_f0["ts"], p1_f1["ts"])
    hi = min(p2_f0["ts"] + p2_f0["dur"], p1_f1["ts"] + p1_f1["dur"])
    assert hi > lo, "phase lanes do not overlap in the rendered trace"
    # the TTA dispatch spans landed on the real (main) thread lane,
    # separate from the synthetic phase lanes
    tta = [s for s in _slices(trace, "dispatch") if s["name"] == "tta"]
    assert len(tta) == 3
    assert all(s["tid"] not in PHASE_LANES.values() for s in tta)


def test_cross_process_wall_alignment():
    """Records from two processes with different monotonic origins land
    on one shared wall timeline via the per-process offset median."""
    base_wall = 1_700_000_000.0
    records = [
        # process A: mono origin ~0 (offset = base_wall)
        {"type": "dispatch", "label": "a", "host": "host0", "pid": 1,
         "tid": 1, "thread": "t", "attempt": 1, "seq": 0,
         "t_wall": base_wall + 10.0, "t_mono": 10.0,
         "t_mono_start": 9.0, "t_mono_end": 10.0},
        # process B: mono origin shifted by 1000 (offset differs)
        {"type": "dispatch", "label": "b", "host": "host1", "pid": 2,
         "tid": 2, "thread": "t", "attempt": 1, "seq": 0,
         "t_wall": base_wall + 10.0, "t_mono": 1010.0,
         "t_mono_start": 1009.0, "t_mono_end": 1010.0},
    ]
    trace = journal_to_trace(records)
    assert validate_trace(trace) == []
    a, b = _slices(trace)
    # both windows cover the same wall second -> identical ts/dur
    assert a["ts"] == pytest.approx(b["ts"], abs=1.0)
    assert a["dur"] == pytest.approx(1e6, rel=1e-6)


def test_cli_writes_trace_file(journal_dir, tmp_path, capsys):
    with T.span("train_dispatch"):
        time.sleep(0.001)
    T.journal_flush()
    out = str(tmp_path / "trace.json")
    rc = trace_export.main(["--telemetry", journal_dir, "--out", out])
    assert rc == 0
    with open(out) as fh:
        trace = json.load(fh)
    assert validate_trace(trace) == []
    assert "trace_export:" in capsys.readouterr().out


def test_cli_empty_dir_is_loud(tmp_path):
    rc = trace_export.main(["--telemetry", str(tmp_path / "empty"),
                            "--out", str(tmp_path / "t.json")])
    assert rc == 2
    assert not os.path.exists(tmp_path / "t.json")
