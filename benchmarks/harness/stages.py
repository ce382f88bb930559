"""The host's time by the program's own stages.

The program names its host work with ``telemetry.stage``
(``fast_autoaugment_tpu/core/telemetry.py``): a tree under
``train_and_eval`` whose finished roots ``telemetry.stage_trees()`` hands
out in the process that ran them, whose every stage is a ``phase`` event
in an armed journal, and whose every stage lies as a
``TraceAnnotation`` on a profiler trace's host line, on the clock the
device lines use.  This module reads all three.

**Set-up, split.**  ``setup_s`` runs from process creation to the
window's opening.  The first root that trained (``only_eval`` false)
covers it from the moment ``train_and_eval`` was entered; a stage that
straddles the opening counts up to it.  Five parts, each less the
first calls (``first_call:<label>``, the compile seam's: compile or
cache load) nested in it:

``before_entry``     process creation -> the root's start
``data``             ``load_dataset`` + ``split`` + ``cache_upload``
``state_init``       ``build`` + ``state_init`` + ``restore`` + ``place_state``
``warmup_dispatch``  every ``index_matrix`` + ``dispatch_loop``
``first_boundary``   the first ``epoch_boundary``

The five, every first call before the opening and the *unaccounted*
rest (the root's and the epochs' own time between stages) add up to
``setup_s``; a split that leaves more than 20% unaccounted is not
reported as a split.

**The boundary's idle time** comes from the trace alone: the device's
idle stretches that fall under a ``train_and_eval.epoch.epoch_boundary``
annotation, a boundary.

The readers leave the tree they read as ``bench_work/<cell>/stages.json``;

    python3 -m benchmarks.harness.stages bench_work/<cell>/stages.json
    python3 -m benchmarks.harness.stages <journal dir> [<start_wall> <setup_s>]

prints the whole tree: every stage with its seconds, its own time (less
its children) and its share of the root.  The journal form is what an
operator has (``--telemetry DIR``); with the process's creation time and
``setup_s`` (``benchmarks/run.py`` prints the second) it prints the
split too.

A program from before the stages has no ``stage_trees``: every reader
here then returns None, and the result line leaves the metric out.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass

from benchmarks.harness import trace as tr

ROOT = "train_and_eval"
FIRST_CALL = "first_call:"
BOUNDARY_ANNOTATION = f"{ROOT}.epoch.epoch_boundary"
#: the journal lane of a stage's ``phase`` event
LANE = "trainer"
#: a split that leaves more than this share of ``setup_s`` under no
#: stage is not reported as a split
MAX_UNACCOUNTED_SHARE = 20.0
#: the root's children a part sums
PARTS = {
    "data": ("load_dataset", "split", "cache_upload"),
    "state_init": ("build", "state_init", "restore", "place_state"),
}
FILE_NAME = "stages.json"


# --------------------------------------------------------------- the tree


def program_trees() -> list[dict]:
    """The finished root stages of this process; none for a program from
    before the stages."""
    try:
        from fast_autoaugment_tpu.core import telemetry
    except ImportError:
        return []
    trees = getattr(telemetry, "stage_trees", None)
    return trees() if trees is not None else []


def training_root(trees: list[dict], opening: float | None = None) -> dict | None:
    """The first root that trained (every program calls ``train_and_eval``
    again with ``only_eval`` after its window); with `opening`, a wall
    time, the first such root that was open then."""
    for tree in trees:
        if tree["name"] != ROOT or tree["fields"].get("only_eval"):
            continue
        if opening is None or (tree["t_wall_start"] <= opening
                               <= tree["t_wall_start"] + tree["dur"]):
            return tree
    return None


def walk(node: dict, depth: int = 0):
    """``(node, depth)`` over the tree, a parent before its children."""
    yield node, depth
    for child in node["children"]:
        yield from walk(child, depth + 1)


def self_seconds(node: dict) -> float:
    return max(0.0, node["dur"] - sum(c["dur"] for c in node["children"]))


@dataclass
class Setup:
    """``setup_s`` by part, in seconds."""

    setup_s: float
    before_entry: float
    data: float
    state_init: float
    warmup_dispatch: float
    first_boundary: float
    first_calls: float

    @property
    def unaccounted(self) -> float:
        return self.setup_s - (self.before_entry + self.data + self.state_init
                               + self.warmup_dispatch + self.first_boundary
                               + self.first_calls)

    @property
    def unaccounted_share(self) -> float:
        return 100.0 * self.unaccounted / self.setup_s


def split_setup(root: dict, start_wall: float, setup_s: float) -> Setup:
    """`root`'s time before the window's opening (`start_wall` +
    `setup_s`, wall seconds) by part.  A stage's place in wall time is
    the root's wall stamp plus its monotonic distance from the root, so
    a wall clock that steps inside the run moves nothing."""
    opening = start_wall + setup_s
    to_wall = root["t_wall_start"] - root["t_mono_start"]

    def before_opening(node) -> float:
        start = node["t_mono_start"] + to_wall
        return max(0.0, min(start + node["dur"], opening) - start)

    def first_calls(node) -> float:  # the outermost ones at or under `node`
        if node["name"].startswith(FIRST_CALL):
            return before_opening(node)
        return sum(first_calls(c) for c in node["children"])

    def own(node) -> float:
        return before_opening(node) - first_calls(node)

    epochs = [c for c in root["children"] if c["name"] == "epoch"]
    boundaries = [c for e in epochs for c in e["children"]
                  if c["name"] == "epoch_boundary"]
    return Setup(
        setup_s=setup_s,
        before_entry=root["t_wall_start"] - start_wall,
        data=sum(own(c) for c in root["children"] if c["name"] in PARTS["data"]),
        state_init=sum(own(c) for c in root["children"]
                       if c["name"] in PARTS["state_init"]),
        warmup_dispatch=sum(own(c) for e in epochs for c in e["children"]
                            if c["name"] in ("index_matrix", "dispatch_loop")),
        first_boundary=own(boundaries[0]) if boundaries else 0.0,
        first_calls=first_calls(root))


# ------------------------------------------------------------ the readers


def setup_split(obs) -> Setup | None:
    """This run's split, computed once a run; the tree it read is left as
    ``<work>/stages.json``.  None where the program has no stages or the
    run no ``setup_s``."""
    cached = vars(obs).get("_setup_split", False)
    if cached is not False:
        return cached
    from benchmarks.harness.window import process_start_wall

    split = None
    setup_s = obs.end_to_end.get("setup_s")
    start_wall = process_start_wall()
    root = (training_root(program_trees(), start_wall + float(setup_s))
            if setup_s else None)
    if root is not None:
        split = split_setup(root, start_wall, float(setup_s))
        os.makedirs(obs.cell.work, exist_ok=True)
        with open(os.path.join(obs.cell.work, FILE_NAME), "w") as fh:
            json.dump({"cell": obs.cell.name, "start_wall": start_wall,
                       "setup_s": float(setup_s), "root": root}, fh)
    vars(obs)["_setup_split"] = split
    return split


def setup_part_s(obs, part: str) -> float | None:
    """Seconds of ``setup_s`` under one of the five parts; None where
    there is no split or it leaves too much unaccounted."""
    split = setup_split(obs)
    if split is None or split.unaccounted_share > MAX_UNACCOUNTED_SHARE:
        return None
    return getattr(split, part)


def setup_unaccounted_share(obs) -> float | None:
    split = setup_split(obs)
    return None if split is None else split.unaccounted_share


def boundary_idle_ms(chips: list[tr.Plane], window_ns: tuple[float, float],
                     planes: list[tr.Plane]) -> float | None:
    """Milliseconds a boundary in which nothing ran on the device while a
    :data:`BOUNDARY_ANNOTATION` was open on a host line of `planes`,
    averaged over `chips`; None where no host line carries one."""
    spans = [(e.start_ns, e.end_ns) for p in planes if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events
             if e.name == BOUNDARY_ANNOTATION]
    if not spans or not chips:
        return None
    idle_ns = sum(max(0.0, min(g1, s1) - max(g0, s0))
                  for chip in chips for g0, g1 in tr.idle_gaps(chip, window_ns)
                  for s0, s1 in spans)
    return idle_ns / len(chips) / len(spans) / 1e6


def epoch_boundary_device_idle_ms(obs) -> float | None:
    view = obs.trace
    if view is None:
        return None
    hosts = tr.load_xplane(
        tr.newest_xplane(obs.trace_dir),
        keep_line=lambda plane, line: plane.startswith("/host:"))
    return boundary_idle_ms(view.planes, view.window_ns, hosts)


# ----------------------------------------------------- the journal's form


_RECORD_KEYS = frozenset({
    "type", "label", "t_wall", "t_mono", "host", "attempt", "pid", "tid",
    "thread", "seq", "t_mono_start", "t_mono_end", "dur_sec", "lane",
    "parent", "depth"})


def read_journal(directory: str) -> list[dict]:
    """Every ``phase`` event of lane ``trainer`` under `directory`, in the
    order each process wrote them (``tools/trace_export.py``'s reader: a
    torn last line is left out)."""
    from tools.trace_export import read_journal as every_record

    return [rec for rec in every_record(directory)
            if rec["type"] == "phase" and rec.get("lane") == LANE]


def trees_from_journal(records: list[dict]) -> list[dict]:
    """The root stages in :func:`read_journal`'s records, in the form of
    ``stage_trees()``.  A stage's event is written when it closes, after
    its children's: a closing stage takes the stages of its thread that
    name it as their parent and started inside it."""
    waiting: dict[tuple, list[dict]] = {}
    roots = []
    for rec in records:
        path, parent = rec["label"], rec.get("parent")
        node = {
            "name": path[len(parent) + 1:] if parent else path,
            "fields": {k: v for k, v in rec.items() if k not in _RECORD_KEYS},
            "t_wall_start": rec["t_wall"] - (rec["t_mono"] - rec["t_mono_start"]),
            "t_mono_start": rec["t_mono_start"],
            "dur": rec["t_mono_end"] - rec["t_mono_start"],
            "children": []}
        thread = (rec.get("host"), rec.get("pid"), rec.get("tid"))
        mine = waiting.get((thread, path), [])
        node["children"] = [c for c in mine
                            if c["t_mono_start"] >= node["t_mono_start"]]
        waiting[(thread, path)] = [c for c in mine
                                   if c["t_mono_start"] < node["t_mono_start"]]
        if parent:
            waiting.setdefault((thread, parent), []).append(node)
        else:
            roots.append(node)
    return roots


# -------------------------------------------------------------- the table


def format_tree(root: dict) -> list[str]:
    lines = [f"{'stage':<52} {'seconds':>10} {'own':>10} {'of root':>8}"]
    for node, depth in walk(root):
        fields = "".join(f" {k}={v}" for k, v in node["fields"].items())
        share = 100.0 * node["dur"] / root["dur"] if root["dur"] else 0.0
        lines.append(f"{'  ' * depth + node['name'] + fields:<52} "
                     f"{node['dur']:>10.4f} {self_seconds(node):>10.4f} "
                     f"{share:>7.2f}%")
    return lines


def format_setup(split: Setup) -> list[str]:
    rows = [(name, getattr(split, name)) for name in (
        "before_entry", "data", "state_init", "warmup_dispatch",
        "first_boundary", "first_calls", "unaccounted")]
    return ([f"setup_s {split.setup_s:.3f} s, by part:"]
            + [f"  {name:<18} {sec:>9.3f} s {100 * sec / split.setup_s:>6.2f}%"
               for name, sec in rows])


def table(path: str, start_wall: float | None = None,
          setup_s: float | None = None) -> str:
    """The tree of `path`: a ``stages.json`` a reader left, or a journal
    directory (every root in it that trained or evaluated)."""
    if os.path.isdir(path):
        roots = trees_from_journal(read_journal(path))
        if not roots:
            raise SystemExit(f"{path}: no stage in any journal-*.jsonl; was "
                             f"the journal armed (--telemetry DIR)?")
    else:
        with open(path) as fh:
            held = json.load(fh)
        roots, start_wall, setup_s = [held["root"]], held["start_wall"], held["setup_s"]
    lines = []
    trained = training_root(roots)
    if trained is not None and start_wall is not None and setup_s is not None:
        lines += format_setup(split_setup(trained, start_wall, setup_s))
    for root in roots:
        lines += format_tree(root)
    return "\n".join(lines)


if __name__ == "__main__":
    if len(sys.argv) not in (2, 4):
        raise SystemExit(__doc__)
    print(table(sys.argv[1], *map(float, sys.argv[2:])))
