#!/usr/bin/env python
"""Render a telemetry flight-recorder journal as a Chrome trace.

    python tools/trace_export.py --telemetry DIR --out trace.json
    # then open chrome://tracing (or https://ui.perfetto.dev) and load it

Reads every ``journal-*.jsonl`` segment under the ``--telemetry`` dir
(``core/telemetry.py::FlightRecorder`` — one file set per process
chain, host/attempt identity in the filename and in every record) and
emits the Chrome trace-event format (the JSON Perfetto and
chrome://tracing both load):

- ``dispatch``/``compile`` events (and any record carrying a
  ``t_mono_start``/``t_mono_end`` pair) become COMPLETE ("X") slices on
  their real thread lane — per-actor TTA dispatches, trainer dispatch
  chunks, serve dispatches and compile windows all land where they
  actually ran;
- ``phase`` events become slices on two synthetic per-process lanes —
  "phase-1 (train)" and "phase-2 (search)" — so a PR-9 overlapped run
  renders fold k's search visibly overlapping fold k+1's training;
  a stage's (``lane: "trainer"``, ``telemetry.stage``) stays on its real
  thread lane instead, nested in its parent's slice, the trainer's
  dispatches inside ``dispatch_loop``;
- everything else (``shed``, ``breaker_fire``, ``watchdog_fire``,
  ``lease``, ``trial``, ``checkpoint``, ``reload``, ``preempt``,
  ``scenario``, ``verdict``, ``mark``) becomes an INSTANT ("i")
  marker — so a game-day run (docs/GAMEDAYS.md) shows its scenario
  phases, kills and verdict rows on the same timeline as the plane's
  dispatches, sheds and scale events.

Clock alignment: monotonic stamps are consistent only within a
process, so each record's own ``(t_wall, t_mono)`` pair (taken at emit)
estimates that process's wall-minus-mono offset; slices are placed at
``offset + t_mono_start``.  Offsets are estimated per (host, pid) as
the median over that process's records, which absorbs per-record jitter
and aligns multiple hosts onto one shared wall timeline (good to NTP
skew — the same bound the workqueue lease protocol already accepts).

Host-only and dependency-free (no jax import): safe to run anywhere,
including next to a live run.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

#: synthetic tids for the phase-overlap lanes (well above real OS tids
#: never collide in practice; metadata names make them readable)
PHASE_LANES = {"phase1": 10_000_001, "phase2": 10_000_002}
PHASE_LANE_NAMES = {"phase1": "phase-1 (train)",
                    "phase2": "phase-2 (search)"}

#: the lane of ``telemetry.stage``'s ``phase`` events: slices on the thread
#: that ran them, a child inside its parent (docs/OBSERVABILITY.md "Stages")
STAGE_LANE = "trainer"

#: journal event types rendered as duration slices when they carry a
#: mono window; everything else becomes an instant marker
_SLICE_TYPES = {"dispatch", "compile", "phase"}


def read_journal(directory: str) -> list[dict]:
    """Load every journal segment under `directory` (recursively — a
    fleet shares one dir, or each host nests its own), tolerating a
    torn trailing line per segment (killed writer)."""
    records: list[dict] = []
    pattern = os.path.join(directory, "**", "journal-*.jsonl")
    files = sorted(glob.glob(pattern, recursive=True))
    for path in files:
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail of a killed writer
                if isinstance(rec, dict) and "type" in rec:
                    records.append(rec)
    records.sort(key=lambda r: (str(r.get("host")), r.get("pid", 0),
                                r.get("seq", 0)))
    return records


def _median(xs: list[float]) -> float:
    xs = sorted(xs)
    n = len(xs)
    if n == 0:
        return 0.0
    mid = n // 2
    return xs[mid] if n % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def _wall_offsets(records: list[dict]) -> dict[tuple, float]:
    """Per-(host, pid) wall-minus-mono offset (median over records)."""
    samples: dict[tuple, list[float]] = {}
    for r in records:
        tw, tm = r.get("t_wall"), r.get("t_mono")
        if isinstance(tw, (int, float)) and isinstance(tm, (int, float)):
            samples.setdefault((str(r.get("host")), r.get("pid", 0)),
                               []).append(float(tw) - float(tm))
    return {k: _median(v) for k, v in samples.items()}


def _args_of(rec: dict) -> dict:
    """Extra fields -> the slice's args payload (identity/clock fields
    are already encoded in pid/tid/ts)."""
    skip = {"type", "label", "t_wall", "t_mono", "t_mono_start",
            "t_mono_end", "host", "attempt", "pid", "tid", "thread",
            "seq"}
    return {k: v for k, v in rec.items() if k not in skip}


def journal_to_trace(records: list[dict]) -> dict:
    """Records -> ``{"traceEvents": [...], "displayTimeUnit": "ms"}``.

    pids are dense ints per (host, attempt, os-pid) with process_name
    metadata ``host/attempt/pid``; thread_name metadata carries the
    recorded thread names plus the two synthetic phase lanes."""
    offsets = _wall_offsets(records)
    if not records:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    # trace ts is microseconds from the earliest aligned wall stamp —
    # chrome://tracing renders absolute epoch µs poorly, so re-base
    def aligned_wall(rec: dict, mono: float) -> float:
        key = (str(rec.get("host")), rec.get("pid", 0))
        return offsets.get(key, 0.0) + float(mono)

    t_base: float | None = None
    for r in records:
        start = r.get("t_mono_start", r.get("t_mono"))
        if isinstance(start, (int, float)):
            w = aligned_wall(r, float(start))
            t_base = w if t_base is None else min(t_base, w)
    t_base = t_base or 0.0

    pid_map: dict[tuple, int] = {}
    events: list[dict] = []
    thread_named: set[tuple] = set()

    def pid_of(rec: dict) -> int:
        key = (str(rec.get("host")), rec.get("attempt", 1),
               rec.get("pid", 0))
        if key not in pid_map:
            pid_map[key] = len(pid_map) + 1
            events.append({
                "ph": "M", "name": "process_name", "pid": pid_map[key],
                "tid": 0,
                "args": {"name": f"{key[0]} a{key[1]} pid{key[2]}"},
            })
        return pid_map[key]

    def name_thread(pid: int, tid: int, name: str) -> None:
        if (pid, tid) in thread_named:
            return
        thread_named.add((pid, tid))
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": name}})

    for rec in records:
        etype = str(rec.get("type"))
        label = rec.get("label") or etype
        pid = pid_of(rec)
        has_window = isinstance(rec.get("t_mono_start"), (int, float)) \
            and isinstance(rec.get("t_mono_end"), (int, float))
        if etype in _SLICE_TYPES and has_window:
            t0 = aligned_wall(rec, float(rec["t_mono_start"]))
            t1 = aligned_wall(rec, float(rec["t_mono_end"]))
            if etype == "phase" and rec.get("lane") != STAGE_LANE:
                lane = rec.get("lane")
                if lane not in PHASE_LANES:
                    lane = "phase1" if str(label).startswith("phase1") \
                        else "phase2"
                tid = PHASE_LANES[lane]
                name_thread(pid, tid, PHASE_LANE_NAMES[lane])
            else:
                tid = int(rec.get("tid", 0))
                name_thread(pid, tid, str(rec.get("thread", f"tid{tid}")))
            events.append({
                "ph": "X", "name": str(label), "cat": etype,
                "pid": pid, "tid": tid,
                "ts": round((t0 - t_base) * 1e6, 3),
                "dur": round(max(0.0, t1 - t0) * 1e6, 3),
                "args": _args_of(rec),
            })
        else:
            tm = rec.get("t_mono")
            if not isinstance(tm, (int, float)):
                continue
            tid = int(rec.get("tid", 0))
            name_thread(pid, tid, str(rec.get("thread", f"tid{tid}")))
            events.append({
                "ph": "i", "name": f"{etype}:{label}", "cat": etype,
                "pid": pid, "tid": tid, "s": "t",
                "ts": round((aligned_wall(rec, float(tm)) - t_base) * 1e6,
                            3),
                "args": _args_of(rec),
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def validate_trace(trace: dict) -> list[str]:
    """Schema check against the Chrome trace-event format; returns a
    list of problems (empty = valid).  The round-trip test gates on
    this, so a format regression fails loudly instead of silently
    producing a file chrome://tracing refuses."""
    problems: list[str] = []
    if not isinstance(trace, dict):
        return ["trace must be a JSON object"]
    evs = trace.get("traceEvents")
    if not isinstance(evs, list):
        return ["traceEvents must be a list"]
    for i, ev in enumerate(evs):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            problems.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "i", "M", "B", "E"):
            problems.append(f"{where}: unknown ph {ph!r}")
            continue
        for field in ("name", "pid", "tid"):
            if field not in ev:
                problems.append(f"{where}: missing {field}")
        if ph in ("X", "i"):
            ts = ev.get("ts")
            if not isinstance(ts, (int, float)) or ts < 0:
                problems.append(f"{where}: bad ts {ts!r}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"{where}: bad dur {dur!r}")
        if ph == "i" and ev.get("s") not in ("t", "p", "g"):
            problems.append(f"{where}: instant event missing scope 's'")
    return problems


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="telemetry journal -> Chrome trace-event JSON "
                    "(chrome://tracing / Perfetto)")
    p.add_argument("--telemetry", required=True, metavar="DIR",
                   help="the --telemetry journal dir (FAA_TELEMETRY)")
    p.add_argument("--out", default="trace.json",
                   help="output path (default ./trace.json)")
    args = p.parse_args(argv)

    records = read_journal(args.telemetry)
    if not records:
        print(f"trace_export: no journal-*.jsonl records under "
              f"{args.telemetry}", file=sys.stderr)
        return 2
    trace = journal_to_trace(records)
    problems = validate_trace(trace)
    if problems:
        for pr in problems[:20]:
            print(f"trace_export: INVALID: {pr}", file=sys.stderr)
        return 1
    with open(args.out, "w") as fh:
        json.dump(trace, fh)
    slices = sum(1 for e in trace["traceEvents"] if e["ph"] == "X")
    marks = sum(1 for e in trace["traceEvents"] if e["ph"] == "i")
    print(f"trace_export: {len(records)} journal records -> "
          f"{slices} slices + {marks} markers -> {args.out} "
          f"(open in chrome://tracing or ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
