"""The host's time by the program's own stages
(``benchmarks/harness/stages.py`` and the seven readers over it): on a
hand-made tree where every number can be worked out by hand, and on what
one traced run of ``wrn40x2_train`` left on the chip
(``benchmarks/testdata/v5e_train_epoch_boundary_stages.json``: the stage
tree that run's readers wrote, and the device and host lines of its trace
around one epoch boundary)."""

import dataclasses
import os

import numpy as np
import pytest

from benchmarks.harness import stages as hs
from benchmarks.harness import trace as tr
from benchmarks.harness import window as win
from benchmarks.harness.observed import Observed
from benchmarks.harness.spec import BENCH_DIR, load_json, load_module, resolve_cell
from fast_autoaugment_tpu.core import telemetry

SETUP_READERS = ("setup_before_entry_s", "setup_data_s", "setup_state_init_s",
                 "setup_warmup_dispatch_s", "setup_first_boundary_s")
READERS = SETUP_READERS + ("setup_unaccounted_share",
                           "epoch_boundary_device_idle_ms")
RECORDED = load_json(os.path.join(
    BENCH_DIR, "testdata", "v5e_train_epoch_boundary_stages.json"))


def _node(name, start, dur, children=(), **fields):
    return {"name": name, "fields": fields, "t_wall_start": 1000.0 + start,
            "t_mono_start": 50.0 + start, "dur": dur,
            "children": list(children)}


def _tree(only_eval=False, start=0.0):
    """A process created at wall 990: ``train_and_eval`` entered 10 s later
    (wall 1000 = mono 50), set-up stages of 1 + 0.5 + 2 + 3 + 1 + 0.25 +
    0.25 + 1 s with gaps of 0.5 s in all, an epoch whose loop of 4 s holds
    a first call of 1.5 s and whose boundary of 1 s holds one of 0.25 s,
    then a second epoch whose loop the window's opening cuts 0.5 s in."""
    s = start
    first_epoch = _node("epoch", s + 9.5, 5.5, [
        _node("index_matrix", s + 9.5, 0.25),
        _node("dispatch_loop", s + 9.75, 4.0, [
            _node("first_call:train_dispatch", s + 10.0, 1.5)]),
        _node("epoch_boundary", s + 13.75, 1.0, [
            _node("metric_sync", s + 13.75, 0.25),
            _node("heartbeat", s + 14.0, 0.125),
            _node("log", s + 14.25, 0.5, [
                _node("first_call:lr", s + 14.25, 0.25)])])], epoch=1)
    second_epoch = _node("epoch", s + 15.0, 3.0, [
        _node("index_matrix", s + 15.0, 0.25),
        _node("dispatch_loop", s + 15.25, 2.0),
        _node("epoch_boundary", s + 17.25, 0.75)], epoch=2)
    return _node("train_and_eval", s, 18.0, [
        _node("load_dataset", s + 0.0, 1.0),
        _node("split", s + 1.0, 0.5),
        _node("build", s + 1.5, 2.0),
        _node("state_init", s + 3.75, 3.0),
        _node("build", s + 6.75, 1.0),
        _node("restore", s + 7.75, 0.25),
        _node("place_state", s + 8.0, 0.25),
        _node("cache_upload", s + 8.5, 1.0),
        first_epoch, second_epoch], only_eval=only_eval)


START_WALL, SETUP_S = 990.0, 25.75  # the window opens at wall 1015.75


def test_the_parts_the_first_calls_and_the_rest_add_up_to_setup_s():
    split = hs.split_setup(_tree(), START_WALL, SETUP_S)
    assert split.before_entry == 10.0
    assert split.data == 1.0 + 0.5 + 1.0
    assert split.state_init == 2.0 + 3.0 + 1.0 + 0.25 + 0.25
    # both index matrices, the first loop less its first call, and the
    # second loop up to the opening
    assert split.warmup_dispatch == 0.25 + (4.0 - 1.5) + 0.25 + 0.5
    assert split.first_boundary == 1.0 - 0.25
    assert split.first_calls == 1.5 + 0.25
    assert split.unaccounted == pytest.approx(
        SETUP_S - 10.0 - 2.5 - 6.5 - 3.5 - 0.75 - 1.75)
    assert split.unaccounted == pytest.approx(0.75)  # the gaps between stages
    assert split.unaccounted_share == pytest.approx(100 * 0.75 / 25.75)


def test_a_stage_that_straddles_the_opening_counts_up_to_it():
    inside = hs.split_setup(_tree(), START_WALL, SETUP_S)
    # an opening 1 s later takes 1 s more of the second loop and nothing else
    later = hs.split_setup(_tree(), START_WALL, SETUP_S + 1.0)
    assert later.warmup_dispatch == inside.warmup_dispatch + 1.0
    assert later.unaccounted == pytest.approx(inside.unaccounted)
    # one inside the first epoch's first call cuts the call and its loop
    early = hs.split_setup(_tree(), START_WALL, 10.0 + 10.5)
    assert early.first_calls == 0.5
    assert early.warmup_dispatch == 0.25 + 0.25
    assert early.first_boundary == 0.0


def test_a_wall_clock_that_steps_inside_the_run_moves_nothing():
    tree = _tree()
    for node, _ in hs.walk(tree):
        if node["name"] != "train_and_eval":
            node["t_wall_start"] += 3600.0  # only the root's wall stamp is read
    assert hs.split_setup(tree, START_WALL, SETUP_S) == hs.split_setup(
        _tree(), START_WALL, SETUP_S)


def test_training_root_is_the_first_that_trained_and_was_open_then():
    evaluated, trained = _tree(only_eval=True), _tree(start=100.0)
    other = _node("first_call:replay_eval", 5.0, 1.0)
    assert hs.training_root([other, evaluated, trained]) is trained
    assert hs.training_root([evaluated, other]) is None
    earlier = _tree(start=-500.0)
    assert hs.training_root([earlier, trained]) is earlier
    assert hs.training_root([earlier, trained], opening=1110.0) is trained
    assert hs.training_root([earlier, trained], opening=2000.0) is None


def _observed(monkeypatch, tmp_path, trees, setup_s=SETUP_S):
    cell = dataclasses.replace(resolve_cell("wrn40x2_train", trace=True),
                               work=str(tmp_path / "work"))
    monkeypatch.setattr(telemetry, "stage_trees", lambda: trees)
    monkeypatch.setattr(win, "process_start_wall", lambda: START_WALL)
    end_to_end = {"train_images_per_s": 1.0}
    if setup_s is not None:
        end_to_end["setup_s"] = setup_s
    return Observed(cell=cell, devices=[], end_to_end=end_to_end, window_s=1.0,
                    attempted=0, failed=0, checks={}, compile_stats={},
                    memory_peak_bytes=0, step_program=cell.traffic["step_program"])


def _read(obs, name):
    return load_module("layer_metrics", name).read(obs)


def test_the_readers_report_the_parts_and_leave_the_tree_they_read(
        monkeypatch, tmp_path):
    obs = _observed(monkeypatch, tmp_path, [_tree(only_eval=True, start=-50.0),
                                            _tree(), _tree(only_eval=True, start=30.0)])
    values = {name: _read(obs, name) for name in READERS}
    assert [values[n] for n in SETUP_READERS] == [10.0, 2.5, 6.5, 3.5, 0.75]
    assert values["setup_unaccounted_share"] == pytest.approx(100 * 0.75 / 25.75)
    assert values["epoch_boundary_device_idle_ms"] is None  # no trace
    # with every first call before the opening they are setup_s
    assert sum(values[n] for n in SETUP_READERS) + 1.75 + 0.75 == pytest.approx(SETUP_S)
    held = load_json(os.path.join(obs.cell.work, hs.FILE_NAME))
    assert held["cell"] == "wrn40x2_train" and held["setup_s"] == SETUP_S
    assert held["start_wall"] == START_WALL and held["root"] == _tree()
    text = hs.table(os.path.join(obs.cell.work, hs.FILE_NAME))
    assert "warmup_dispatch" in text and "first_call:train_dispatch" in text


def test_over_a_fifth_unaccounted_the_five_report_nothing(monkeypatch, tmp_path):
    """Time before the root is a part, not unaccounted; a tree whose
    ``state_init`` and ``build`` stages are missing leaves their 6 s under
    no stage: 6.75 of 25.75 s, 26%."""
    moved = _tree()
    for node, _ in hs.walk(moved):
        node["t_wall_start"] += 9.0
    obs = _observed(monkeypatch, tmp_path, [moved], setup_s=SETUP_S + 9.0)
    assert _read(obs, "setup_before_entry_s") == 19.0
    assert _read(obs, "setup_unaccounted_share") == pytest.approx(100 * 0.75 / 34.75)
    tree = _tree()
    tree["children"] = [c for c in tree["children"]
                        if c["name"] not in ("state_init", "build")]
    obs = _observed(monkeypatch, tmp_path, [tree])
    assert _read(obs, "setup_unaccounted_share") == pytest.approx(
        100 * (0.75 + 6.0) / 25.75)
    assert [_read(obs, n) for n in SETUP_READERS] == [None] * 5


def test_a_program_without_stages_gives_none_and_raises_nothing(
        monkeypatch, tmp_path):
    """The parent under this PR's benchmark files: no ``stage_trees`` at
    all, an empty one, or a run that measured no ``setup_s``."""
    obs = _observed(monkeypatch, tmp_path, [])
    assert [_read(obs, name) for name in READERS] == [None] * 7
    obs = _observed(monkeypatch, tmp_path, [_tree()], setup_s=None)
    assert [_read(obs, name) for name in READERS] == [None] * 7
    obs = _observed(monkeypatch, tmp_path, [_tree()])
    monkeypatch.delattr(telemetry, "stage_trees")
    assert hs.program_trees() == []
    assert [_read(obs, name) for name in READERS] == [None] * 7
    assert not os.path.exists(os.path.join(obs.cell.work, hs.FILE_NAME))


def test_the_journal_gives_the_tree_the_process_held(tmp_path):
    """What an operator has: the same stages as ``phase`` events, a child's
    written before its parent's; a second thread's stages are its own
    roots."""
    import threading

    telemetry.enable_telemetry(str(tmp_path / "tel"), tb_bridge=False)
    try:
        with telemetry.stage("train_and_eval", only_eval=False):
            for epoch in (1, 2):
                with telemetry.stage("epoch", epoch=epoch):
                    with telemetry.stage("dispatch_loop"):
                        if epoch == 1:
                            with telemetry.stage("first_call:train_dispatch"):
                                pass
                    with telemetry.stage("epoch_boundary"):
                        for _ in range(2):
                            with telemetry.stage("metric_sync"):
                                pass
            th = threading.Thread(target=lambda: telemetry.stage(
                "first_call:replay_eval").__enter__().__exit__(None, None, None))
            th.start()
            th.join(10.0)
        telemetry.phase_event("phase1-fold0", 1.0, 2.0, lane="phase1")
        telemetry.journal_flush()
    finally:
        telemetry._disable_for_tests()
    held = telemetry.stage_trees()[-1]
    roots = hs.trees_from_journal(hs.read_journal(str(tmp_path / "tel")))
    assert [r["name"] for r in roots] == ["first_call:replay_eval",
                                          "train_and_eval"]

    def shape(node):
        return (node["name"], node["fields"], [shape(c) for c in node["children"]])

    assert shape(roots[1]) == shape(held)
    for (a, _), (b, _) in zip(hs.walk(roots[1]), hs.walk(held)):
        assert a["t_mono_start"] == b["t_mono_start"]
        assert a["dur"] == pytest.approx(b["dur"], abs=1e-6)
        assert a["t_wall_start"] == pytest.approx(b["t_wall_start"], abs=0.05)
    text = hs.table(str(tmp_path / "tel"))
    assert "      metric_sync" in text and "epoch epoch=2" in text
    os.makedirs(tmp_path / "empty")
    with pytest.raises(SystemExit):
        hs.table(str(tmp_path / "empty"))  # a directory without a journal


# ---------------------------------------------------- recorded on the chip


def test_recorded_tree_adds_up_to_the_runs_setup_s():
    held = RECORDED["stages"]
    split = hs.split_setup(held["root"], held["start_wall"], held["setup_s"])
    parts = (split.before_entry + split.data + split.state_init
             + split.warmup_dispatch + split.first_boundary)
    assert parts + split.first_calls + split.unaccounted == pytest.approx(
        held["setup_s"], abs=1e-9)
    expected = RECORDED["expected"]
    for name in ("before_entry", "data", "state_init", "warmup_dispatch",
                 "first_boundary", "first_calls", "unaccounted_share"):
        assert getattr(split, name) == pytest.approx(expected[name], abs=1e-6), name
    assert 0.0 <= split.unaccounted_share < 10.0
    names = [c["name"] for c in held["root"]["children"]]
    assert names[:8] == ["load_dataset", "split", "build", "state_init",
                         "build", "restore", "place_state", "cache_upload"]
    assert set(names[8:]) == {"epoch"}


def test_recorded_boundary_idle_time_and_a_trace_without_a_host_plane():
    planes = tr.planes_from_json(RECORDED["planes"])
    chips = tr.device_planes(planes)
    window = tr.traced_window(chips)
    hosts = [p for p in planes if p.name.startswith("/host:")]
    annotations = [e for p in hosts for ln in p.lines for e in ln.events
                   if e.name == hs.BOUNDARY_ANNOTATION]
    assert len(annotations) == 1
    # the boundary's children and the dispatch that follows it are on the
    # host's python3 line, the children inside the boundary's annotation
    (line,) = [ln for p in hosts for ln in p.lines if annotations[0] in ln.events]
    assert line.name == "python3"
    names = {e.name for e in line.events}
    assert {hs.BOUNDARY_ANNOTATION + ".metric_sync",
            hs.BOUNDARY_ANNOTATION + ".heartbeat",
            hs.BOUNDARY_ANNOTATION + ".log", "train_dispatch"} <= names
    for e in line.events:
        if e.name.startswith(hs.BOUNDARY_ANNOTATION + "."):
            assert annotations[0].start_ns <= e.start_ns
            assert e.end_ns <= annotations[0].end_ns
    got = hs.boundary_idle_ms(chips, window, planes)
    assert got == pytest.approx(RECORDED["expected"]["boundary_idle_ms"], abs=1e-9)
    # by another method: every operation painted onto a grid of nanoseconds
    lo, hi = int(max(annotations[0].start_ns, window[0])), int(
        min(annotations[0].end_ns, window[1]))
    busy = np.zeros(hi - lo, bool)
    for e in chips[0].line(tr.OPS_LINE).events:
        busy[max(0, int(e.start_ns) - lo):max(0, int(e.end_ns) - lo)] = True
    assert (hi - lo - busy.sum()) / 1e6 == pytest.approx(got, abs=1e-6)
    # the device falls idle inside the annotation: no offset was applied
    gaps = tr.idle_gaps(chips[0], window)
    longest = max(gaps, key=lambda g: g[1] - g[0])
    assert annotations[0].start_ns < longest[0] < annotations[0].end_ns
    # no host plane (host_tracer_level 0), and a run that was not traced
    assert hs.boundary_idle_ms(chips, window, chips) is None
    obs = Observed(cell=resolve_cell("wrn40x2_train", trace=True), devices=[],
                   end_to_end={"train_images_per_s": 1.0}, window_s=1.0,
                   attempted=0, failed=0, checks={}, compile_stats={},
                   memory_peak_bytes=0)
    assert load_module("layer_metrics", "epoch_boundary_device_idle_ms").read(obs) is None
