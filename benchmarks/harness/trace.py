"""From a profiler trace to numbers.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load_xplane` reads it with JAX alone into plain :class:`Plane` /
:class:`Line` / :class:`Event` records (the same records
:func:`planes_from_json` builds from the small recorded trace the
self-tests use), and every reduction below is a pure function of those
records, so one code path serves the chip's trace and the test's.

What a TPU's trace looks like (read off a v5e trace by hand, PR 22): one
plane per chip named ``/device:TPU:<n>``; on it the lines ``Steps``,
``XLA Modules`` (one event per executed program, named
``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event per executed HLO
operation, named by its HLO text, nested where an operation contains
others) and ``Async XLA Ops`` (copies in flight beside them).  Host
threads are lines of the plane ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` lands on the ``python3`` line there, on
the clock the device lines use.  An event carries its device offset and
duration and little else: no ``op_name`` metadata.

Times inside a trace are nanoseconds on the profiler's clock; every
function here returns seconds.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: HLO opcodes that move data between chips (their ``-start`` and
#: ``-done`` halves included)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%([\w\-]+?)(?:\.\d+)* = (.*?) ([\w\-]+)\(")
_KIND = re.compile(r"kind=(\w+)")
_NAME_CHARS, _SHAPE_CHARS = 120, 72


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Line:
    name: str
    events: list[Event]


@dataclass
class Plane:
    name: str
    lines: list[Line]

    def line(self, name: str) -> Line | None:
        for ln in self.lines:
            if ln.name == name:
                return ln
        return None


# ------------------------------------------------------------- reading


def newest_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str, *, keep_line=None) -> list[Plane]:
    """Read an ``.xplane.pb``.  `keep_line(plane_name, line_name)` limits
    what is materialised (default: device planes' ``XLA Ops`` and ``XLA
    Modules`` lines and every host line)."""
    from jax.profiler import ProfileData

    if keep_line is None:
        def keep_line(plane_name, line_name):
            if DEVICE_PLANE.match(plane_name):
                return line_name in (OPS_LINE, MODULES_LINE)
            return plane_name.startswith("/host:")

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            if not keep_line(plane.name, line.name):
                continue
            events = [Event(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events]
            events.sort(key=lambda e: (e.start_ns, -e.dur_ns))
            lines.append(Line(line.name, events))
        if lines:
            planes.append(Plane(plane.name, lines))
    return planes


def planes_from_json(obj: list) -> list[Plane]:
    """Planes from ``[{"name", "lines": [{"name", "events": [[name,
    start_ns, dur_ns], ...]}]}]``, the form of the recorded test trace."""
    return [Plane(p["name"], [
        Line(ln["name"], [Event(n, float(s), float(d)) for n, s, d in ln["events"]])
        for ln in p["lines"]]) for p in obj]


# ------------------------------------------------------------ intervals


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of closed intervals, as a sorted list of disjoint ones."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def self_times(events: list[Event]) -> list[float]:
    """Each event's duration minus what the events nested inside it
    cover, in nanoseconds and in the order of `events` (which must be
    sorted by start, longer first on ties — as :func:`load_xplane`
    leaves them)."""
    own = [e.dur_ns for e in events]
    stack: list[int] = []
    for i, ev in enumerate(events):
        while stack and events[stack[-1]].end_ns <= ev.start_ns:
            stack.pop()
        if stack and ev.end_ns <= events[stack[-1]].end_ns:
            own[stack[-1]] -= ev.dur_ns
        stack.append(i)
    return [max(0.0, t) for t in own]


def device_planes(planes: list[Plane], chips: int | None = None) -> list[Plane]:
    found = sorted((int(DEVICE_PLANE.match(p.name).group(1)), p)
                   for p in planes if DEVICE_PLANE.match(p.name))
    out = [p for _, p in found]
    return out if chips is None else out[:chips]


def _ops(plane: Plane) -> list[Event]:
    line = plane.line(OPS_LINE) or plane.line(MODULES_LINE)
    return line.events if line else []


# ----------------------------------------------------------- reductions


def traced_window(planes: list[Plane]) -> tuple[float, float] | None:
    """``(start_ns, end_ns)`` from the first device operation's start to
    the last one's end, over `planes`; None when nothing ran."""
    starts = [ev.start_ns for p in planes for ev in _ops(p)]
    ends = [ev.end_ns for p in planes for ev in _ops(p)]
    return (min(starts), max(ends)) if starts else None


def busy_seconds(plane: Plane) -> float:
    """Seconds in which some operation ran on this chip."""
    return sum(b - a for a, b in merge(
        [(e.start_ns, e.end_ns) for e in _ops(plane)])) / 1e9


def idle_gaps(plane: Plane, window: tuple[float, float]) -> list[tuple[float, float]]:
    """The stretches of `window` (ns) in which nothing ran on this chip."""
    gaps, cursor = [], window[0]
    for a, b in merge([(e.start_ns, e.end_ns) for e in _ops(plane)]):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if window[1] > cursor:
        gaps.append((cursor, window[1]))
    return gaps


def program_runs(plane: Plane, pattern: str) -> list[Event]:
    """Executions of the programs whose name matches `pattern`, from the
    ``XLA Modules`` line, in time order."""
    line = plane.line(MODULES_LINE)
    rx = re.compile(pattern)
    return [e for e in (line.events if line else []) if rx.search(e.name)]


def gaps_between(runs: list[Event]) -> list[float]:
    """Seconds from one execution's end to the next one's start."""
    return [max(0.0, b.start_ns - a.end_ns) / 1e9
            for a, b in zip(runs, runs[1:])]


def collective_seconds(plane: Plane) -> float:
    """Seconds this chip's operation line spent in collectives: on that
    line operations run one after another, so while a collective (or the
    ``-done`` half of an asynchronous one) occupies it, no compute does —
    this is the part of the exchange that compute did not hide."""
    events = _ops(plane)
    own = self_times(events)
    return sum(t for e, t in zip(events, own)
               if COLLECTIVE.match(opcode(e))) / 1e9


def opcode(event: Event) -> str:
    """The HLO opcode of an ``XLA Ops`` event (``fusion``, ``all-reduce``,
    ``copy-start``); the event's own name where it is not HLO text."""
    m = _HLO.match(_LAYOUT.sub("", event.name))
    return m.group(3) if m else event.name


def stable_name(event: Event) -> str:
    """A name for an operation that survives a recompile.

    On the ``XLA Ops`` line an event's name is the instruction's HLO
    text, e.g. ``%fusion.2361 = s32[526336]{0:T(1024)S(1)} fusion(...),
    kind=kCustom, calls=%fused_computation.5`` (the ``op_name`` metadata
    with the flax module path is not among the stats this reader gets).
    XLA numbers instructions anew on every compile, so ``fusion.2361``
    names nothing; what stays while the program's shapes stay is the
    instruction's stem (XLA names many fusions after their content:
    ``multiply_reduce_fusion``), the fusion kind, and the output shape
    without its layout: ``fusion:kCustom s32[526336]``."""
    text = _LAYOUT.sub("", event.name)
    m = _HLO.match(text)
    if not m:
        return re.sub(r"[.\d]+$", "", text)[:_NAME_CHARS] or text[:_NAME_CHARS]
    stem, shape = m.group(1), m.group(2).strip()
    kind = _KIND.search(text)
    if stem == "fusion" and kind:
        stem = f"fusion:{kind.group(1)}"
    if len(shape) > _SHAPE_CHARS:
        shape = shape[:_SHAPE_CHARS] + "..."
    return f"{stem} {shape}"


def top_operations(planes: list[Plane], limit: int = 10) -> list[list]:
    """``[[name, seconds], ...]``: device time by :func:`stable_name`,
    nested time counted once, averaged over `planes`, largest first."""
    totals: dict[str, float] = {}
    for plane in planes:
        events = _ops(plane)
        for ev, own in zip(events, self_times(events)):
            name = stable_name(ev)
            totals[name] = totals.get(name, 0.0) + own
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, ns / 1e9 / max(1, len(planes))] for name, ns in ranked]


def find_marker(planes: list[Plane], name: str) -> Event | None:
    """The first host event called `name` (a ``TraceAnnotation``)."""
    hits = [e for p in planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events if e.name == name]
    return min(hits, key=lambda e: e.start_ns) if hits else None


def attribute_gaps(gaps: list[tuple[float, float]],
                   host_spans: list[tuple[str, float, float]],
                   limit: int = 10) -> list[list]:
    """``[[name, seconds], ...]``: idle time by what the host was doing.

    `host_spans` are ``(name, start_ns, end_ns)`` on the trace's clock
    and may overlap; each gap is split among the spans it intersects
    (an earlier span wins an overlap) and what no span covers is
    ``unattributed``."""
    totals: dict[str, float] = {}
    spans = sorted(host_spans, key=lambda s: s[1])
    for g0, g1 in gaps:
        cursor = g0
        for name, s0, s1 in spans:
            lo, hi = max(cursor, s0), min(g1, s1)
            if hi <= lo:
                continue
            if lo > cursor:
                totals["unattributed"] = totals.get("unattributed", 0.0) + lo - cursor
            totals[name] = totals.get(name, 0.0) + hi - lo
            cursor = hi
            if cursor >= g1:
                break
        if cursor < g1:
            totals["unattributed"] = totals.get("unattributed", 0.0) + g1 - cursor
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, ns / 1e9] for name, ns in ranked]


def median(values: list[float]) -> float | None:
    if not values:
        return None
    ordered = sorted(values)
    mid = len(ordered) // 2
    if len(ordered) % 2:
        return ordered[mid]
    return 0.5 * (ordered[mid - 1] + ordered[mid])
