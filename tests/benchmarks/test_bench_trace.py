"""The reduction from a trace to numbers, on a small trace recorded on
the chip (``benchmarks/testdata/v5e_train_step_boundary.json``) and on
hand-made events where the recording has no such case (nesting,
collectives, several chips)."""

import os

import numpy as np
import pytest

from benchmarks.harness import trace as tr
from benchmarks.harness.observed import CLOCK_MARKER, Observed, TraceView
from benchmarks.harness.spec import BENCH_DIR, load_json, load_module, resolve_cell

RECORDED = tr.planes_from_json(load_json(
    os.path.join(BENCH_DIR, "testdata", "v5e_train_step_boundary.json"))["planes"])
CHIP = tr.device_planes(RECORDED)[0]


def test_recorded_trace_busy_idle_and_gaps():
    """Numbers worked out by hand from the 140 recorded operations: they
    span 92,554 ns, the union of their intervals is 71,748 ns, and the
    four longest idle stretches are 15,325 (between the two steps), 3,784,
    840 and 704 ns."""
    assert [p.name for p in tr.device_planes(RECORDED)] == ["/device:TPU:0"]
    window = tr.traced_window([CHIP])
    assert window == (2081406160.0, 2081498714.0)
    assert tr.busy_seconds(CHIP) * 1e9 == pytest.approx(71748.0)
    gaps = sorted((b - a for a, b in tr.idle_gaps(CHIP, window)), reverse=True)
    assert gaps[:4] == [15325.0, 3784.0, 840.0, 704.0]
    assert sum(gaps) == pytest.approx(92554.0 - 71748.0)
    view = TraceView([CHIP], window, None)
    assert view.window_s == pytest.approx(92.554e-6)
    assert 1 - view.busy_s / view.window_s == pytest.approx(0.224798, abs=1e-6)


def test_recorded_trace_against_a_rasterised_count():
    """The same union by another method: paint every operation onto a
    grid of nanoseconds and count."""
    ops = CHIP.line(tr.OPS_LINE).events
    lo = min(e.start_ns for e in ops)
    grid = np.zeros(int(max(e.end_ns for e in ops) - lo), bool)
    for e in ops:
        grid[int(e.start_ns - lo):int(e.end_ns - lo)] = True
    assert tr.busy_seconds(CHIP) * 1e9 == pytest.approx(float(grid.sum()))


def test_recorded_trace_program_time_and_gaps_between_steps():
    runs = tr.program_runs(CHIP, "^jit_multi_fn")
    assert [r.dur_ns / 1e6 for r in runs] == [1019.644002, 1019.637131, 1019.655431]
    assert tr.median([r.dur_ns / 1e6 for r in runs]) == 1019.644002
    assert tr.gaps_between(runs) == pytest.approx([6.442e-6, 6.441e-6])
    assert tr.program_runs(CHIP, "^jit_no_such_program") == []
    assert tr.median([]) is None and tr.median([1.0, 3.0]) == 2.0


def test_recorded_trace_names_survive_a_recompile():
    names = {tr.stable_name(e) for e in CHIP.line(tr.OPS_LINE).events}
    assert not any(name.startswith("%") or "{" in name for name in names)
    assert not any(__import__("re").search(r"fusion\.\d", name) for name in names)
    top = tr.top_operations([CHIP])
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    assert sum(s for _, s in top) <= tr.busy_seconds(CHIP) + 1e-12
    fusion = tr.Event("%fusion.2361 = s32[526336]{0:T(1024)S(1)} fusion(s32[2048,1024]"
                      "{1,0:T(8,128)S(1)} %get-tuple-element.3073), kind=kCustom, "
                      "calls=%fused_computation.5.clone", 0, 1)
    assert tr.stable_name(fusion) == "fusion:kCustom s32[526336]"
    assert tr.opcode(fusion) == "fusion"
    assert tr.stable_name(tr.Event("dot_general.1", 0, 1)) == "dot_general"


def test_clock_marker_ties_the_host_clock_to_the_trace():
    marker = tr.find_marker(RECORDED, CLOCK_MARKER)
    assert (marker.start_ns, marker.dur_ns) == (41127675.0, 2800.0)
    assert tr.find_marker(RECORDED, "no_such_span") is None
    # perf_counter read 100 s when the marker was emitted
    view = TraceView([CHIP], tr.traced_window([CHIP]),
                     marker.start_ns - 100.0 * 1e9)
    spans = view.host_spans_ns([("dispatch loop", 102.0404, 102.0405)])
    assert spans == [("dispatch loop", pytest.approx(2081527675.0),
                      pytest.approx(2081627675.0))]
    assert TraceView([CHIP], (0.0, 1.0), None).host_spans_ns(
        [("x", 1.0, 2.0)]) == []


def _plane(name, ops, modules=()):
    def events(rows):
        out = [tr.Event(n, float(s), float(d)) for n, s, d in rows]
        return sorted(out, key=lambda e: (e.start_ns, -e.dur_ns))
    return tr.Plane(name, [tr.Line(tr.OPS_LINE, events(ops)),
                           tr.Line(tr.MODULES_LINE, events(modules))])


def test_nested_operations_count_once():
    """A while of 100 ns holds two body operations of 30 and 50 ns: its
    own time is 20 ns, and the union stays 100 ns."""
    plane = _plane("/device:TPU:0", [
        ("%while.1 = (s32[]) while((s32[]) %t), body=%b", 0, 100),
        ("%fusion.1 = f32[8]{0} fusion(f32[8] %a), kind=kLoop", 10, 30),
        ("%fusion.2 = f32[8]{0} fusion(f32[8] %a), kind=kLoop", 45, 50),
        ("%copy.3 = f32[8]{0} copy(f32[8] %a)", 120, 10)])
    ops = plane.line(tr.OPS_LINE).events
    assert tr.self_times(ops) == [20.0, 30.0, 50.0, 10.0]
    assert tr.busy_seconds(plane) * 1e9 == pytest.approx(110.0)
    assert tr.idle_gaps(plane, (0.0, 140.0)) == [(100.0, 120.0), (130.0, 140.0)]
    assert tr.top_operations([plane]) == [
        ["fusion:kLoop f32[8]", pytest.approx(80e-9)],
        ["while (s32[])", pytest.approx(20e-9)],
        ["copy f32[8]", pytest.approx(10e-9)]]


def test_collectives_and_several_chips():
    ops0 = [("%fusion.1 = f32[4]{0} fusion(f32[4] %a), kind=kOutput", 0, 60),
            ("%all-reduce.1 = f32[4]{0} all-reduce(f32[4] %g), replica_groups={}", 60, 25),
            ("%all-reduce-start.2 = f32[4]{0} all-reduce-start(f32[4] %g)", 85, 1),
            ("%fusion.3 = f32[4]{0} fusion(f32[4] %a), kind=kLoop", 86, 10),
            ("%all-reduce-done.2 = f32[4]{0} all-reduce-done(f32[4] %s)", 96, 4)]
    ops1 = [("%fusion.1 = f32[4]{0} fusion(f32[4] %a), kind=kOutput", 0, 50)]
    planes = [_plane("/device:TPU:1", ops1), _plane("/device:TPU:0", ops0),
              _plane("/device:TPU:0 SparseCore", ops1),
              tr.Plane("/host:CPU", [])]
    chips = tr.device_planes(planes)
    assert [p.name for p in chips] == ["/device:TPU:0", "/device:TPU:1"]
    assert tr.device_planes(planes, 1) == chips[:1]
    assert tr.collective_seconds(chips[0]) * 1e9 == pytest.approx(30.0)
    assert tr.collective_seconds(chips[1]) == 0.0
    window = tr.traced_window(chips)
    assert window == (0.0, 100.0)
    view = TraceView(chips, window, None)
    assert view.busy_s * 1e9 == pytest.approx((100.0 + 50.0) / 2)

    reader = load_module("layer_metrics", "collective_exposed_share")
    # no cell runs on four chips yet; the reader looks at the trace alone
    cell = resolve_cell("wrn28x10_train", trace=True)
    obs = Observed(cell=cell, devices=[], end_to_end={}, window_s=1.0,
                   attempted=0, failed=0, checks={}, compile_stats={},
                   memory_peak_bytes=0)
    obs.__dict__["trace"] = view  # what the cached property would hold
    assert reader.read(obs) == pytest.approx(100.0 * 15e-9 / 100e-9)
    obs.__dict__["trace"] = TraceView(chips[:1], window, None)
    assert reader.read(obs) is None  # one chip exchanges nothing
    idle = load_module("layer_metrics", "device_idle_share")
    obs.__dict__["trace"] = view
    assert idle.read(obs) == pytest.approx(25.0)


def test_idle_time_goes_to_what_the_host_was_doing():
    gaps = [(10.0, 20.0), (50.0, 90.0)]
    spans = [("dispatch loop", 0.0, 15.0), ("epoch boundary", 55.0, 80.0),
             ("dispatch loop", 80.0, 100.0)]
    by_name = dict(map(tuple, tr.attribute_gaps(gaps, spans)))
    assert by_name == {"dispatch loop": pytest.approx(15e-9),
                       "epoch boundary": pytest.approx(25e-9),
                       "unattributed": pytest.approx(10e-9)}
    assert tr.attribute_gaps(gaps, []) == [["unattributed", pytest.approx(50e-9)]]
    assert tr.attribute_gaps([], spans) == []


def test_step_readers_read_the_recorded_trace():
    cell = resolve_cell("wrn40x2_train", trace=True)
    obs = Observed(cell=cell, devices=[], end_to_end={}, window_s=1.0,
                   attempted=0, failed=0, checks={}, compile_stats={},
                   memory_peak_bytes=0, step_program=cell.traffic["step_program"])
    obs.__dict__["trace"] = TraceView([CHIP], tr.traced_window([CHIP]), None)
    # the middle one of three: the outer two may be cut by a trace's edges
    assert load_module("layer_metrics", "step_device_ms").read(obs) == 1019.637131
    assert load_module("layer_metrics", "dispatch_gap_ms").read(obs) == pytest.approx(
        0.0064415)
    obs.__dict__["trace"] = None
    assert load_module("layer_metrics", "step_device_ms").read(obs) is None
