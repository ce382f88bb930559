"""What one run observed: the record a program hands back, which the
end-to-end line, the correctness verdict and every per-layer reader are
computed from."""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

from benchmarks.harness import trace as tr
from benchmarks.harness.spec import Cell

#: the host span that ties ``time.perf_counter`` to the trace's clock
CLOCK_MARKER = "bench_clock_sync"


@dataclass
class TraceView:
    """The traced stretch of the window, reduced once."""

    planes: list[tr.Plane]          # the chips the cell used
    window_ns: tuple[float, float]  # first device op start .. last end
    #: trace nanoseconds minus perf_counter nanoseconds, or None when
    #: the clock marker is not in the trace
    clock_offset_ns: float | None

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @functools.cached_property
    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the chips used."""
        return sum(tr.busy_seconds(p) for p in self.planes) / len(self.planes)

    def host_spans_ns(self, spans: list[tuple[str, float, float]]):
        """perf_counter spans -> spans on the trace's clock."""
        if self.clock_offset_ns is None:
            return []
        return [(name, a * 1e9 + self.clock_offset_ns,
                 b * 1e9 + self.clock_offset_ns) for name, a, b in spans]


@dataclass
class Observed:
    cell: Cell
    devices: list
    #: end-to-end values the program measured (``setup_s`` among them)
    end_to_end: dict[str, float]
    window_s: float
    attempted: int
    failed: int
    #: named checks, each ``{"ok": bool, ...evidence}``; `correct` is
    #: their conjunction
    checks: dict[str, dict]
    #: the program's ``compile_cache_stats()`` when the window opened
    compile_stats: dict
    memory_peak_bytes: int
    #: units of model work (images through the forward pass, or through
    #: forward and backward) per second and chip, with which of the two
    work: dict = field(default_factory=dict)
    #: regex for the step program's name on the ``XLA Modules`` line
    step_program: str = ""
    trace_dir: str | None = None
    #: ``(name, t0, t1)`` in ``time.perf_counter`` seconds: what the host
    #: was doing, as far as the benchmark's own hooks can tell
    host_spans: list[tuple[str, float, float]] = field(default_factory=list)
    #: ``time.perf_counter`` when :data:`CLOCK_MARKER` was emitted
    marker_perf: float | None = None
    journal: list[dict] | None = None

    @functools.cached_property
    def trace(self) -> TraceView | None:
        """None when the run was not traced or no operation ran on a
        device plane (the CPU backend has none)."""
        if not self.trace_dir:
            return None
        path = tr.newest_xplane(self.trace_dir)
        if path is None:
            return None
        planes = tr.load_xplane(path)
        chips = tr.device_planes(planes, self.cell.chips)
        window = tr.traced_window(chips)
        if window is None:
            return None
        marker = tr.find_marker(planes, CLOCK_MARKER)
        offset = None
        if marker is not None and self.marker_perf is not None:
            offset = marker.start_ns - self.marker_perf * 1e9
        return TraceView(chips, window, offset)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.get("ok") for c in self.checks.values())
