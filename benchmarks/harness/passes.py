"""Device time of the step program by named scope *and pass*.

``harness/scopes.py`` knows two passes, forward and backward, and files the
forward pass that ``nn.remat`` runs a second time under backward: its
instructions sit under ``transpose(jvp(faa_model))/.../checkpoint/
rematted_computation/...``.  The program's ``core/scopes.py::pass_of`` tells
the three apart (``forward``, ``recompute``, ``backward``), and this module
splits the same executions ``step_device_ms`` uses by (chain of scopes,
pass): ``harness/scopes.py::split_plane`` over the trace and the
``scope_map.<label>.json`` that ``step_split`` wrote, asked through a view
of the program's scope table that puts an instruction's pass where that
module puts its backward mark.  A key is ``faa_model/faa_gqa/
faa_mixer_proj/recompute``; what carries no scope is ``unscoped``, so the
three passes and ``unscoped`` add up to an execution's device time.  The
same rule as there: a split that leaves more than 20% of the step unscoped
is not reported.

    python3 -m benchmarks.harness.passes bench_work/<cell>

prints every scope by pass from a traced run's files, for any cell whose
program has ``pass_of`` — those no ``per_layer`` entry lists too.

A program from before ``pass_of`` gives every reader here None.
"""

from __future__ import annotations

import glob
import json
import os
import sys

from benchmarks.harness import scopes as hs
from benchmarks.harness import trace as tr

UNSCOPED = hs.UNSCOPED


class _ByPass:
    """The program's scope table as ``split_plane`` asks it, an
    instruction's pass as the last member of its chain and no backward
    mark."""

    def __init__(self, names):
        self._names = names

    def scope_of(self, op_name: str) -> tuple[str, ...]:
        chain = self._names.scope_of(op_name)
        return chain + (self._names.pass_of(op_name),) if chain else chain

    @staticmethod
    def is_backward(op_name: str) -> bool:
        return False


def program_passes():
    """The program's ``core.scopes`` module where it has ``pass_of``, else
    None."""
    names = hs.program_scopes()
    return names if hasattr(names, "pass_of") else None


def pass_key(key: str) -> tuple[tuple[str, ...], str | None]:
    """A key of the split as ``(chain, pass)``; ``((), None)`` for
    :data:`UNSCOPED`."""
    if key == UNSCOPED:
        return (), None
    *chain, which = key.split("/")
    return tuple(chain), which


def split_by_pass(planes, pattern: str, modules: dict, names) -> hs.Split | None:
    """The executions of the programs matching `pattern` on `planes`, each
    as nanoseconds by ``<chain>/<pass>``; None where there is none."""
    split = hs.Split()
    for plane in planes:
        hs.split_plane(plane, pattern, modules, _ByPass(names), split)
    return split if split.executions else None


def pass_split(obs) -> hs.Split | None:
    """The split by pass of this run's step program, computed once a run
    from the map ``step_split`` wrote beside the trace."""
    cached = vars(obs).get("_pass_split", False)
    if cached is not False:
        return cached
    split = None
    names = program_passes()
    if names is not None and hs.step_split(obs) is not None:
        label = obs.cell.traffic["dispatch_label"]
        with open(hs.map_path(obs.trace_dir, label)) as fh:
            modules = json.load(fh)["modules"]
        split = split_by_pass(obs.trace.planes, obs.step_program, modules, names)
    vars(obs)["_pass_split"] = split
    return split


def _wanted(name, which):
    """A predicate over a key: under a scope of `name` (a name, several, or
    None for any scope) in a pass of `which` (likewise)."""
    scopes_wanted = (name,) if isinstance(name, str) else name
    passes_wanted = (which,) if isinstance(which, str) else which

    def select(key):
        chain, in_pass = pass_key(key)
        return (in_pass is not None
                and (scopes_wanted is None or not set(scopes_wanted).isdisjoint(chain))
                and (passes_wanted is None or in_pass in passes_wanted))
    return select


def scope_pass_ms(obs, name=None, which=None) -> float | None:
    """Median device milliseconds of one execution under the scope `name`
    wherever it is nested (several names: under any of them; None: under
    any scope), in the pass `which` (several: in any of them; None: all
    three).  None where there is no split by pass or it leaves more than
    ``harness/scopes.py::MAX_UNSCOPED_SHARE`` unexplained."""
    split = pass_split(obs)
    if split is None:
        return None
    share = split.unscoped_share()
    if share is None or share > hs.MAX_UNSCOPED_SHARE:
        return None
    return split.median_ms(_wanted(name, which))


# ------------------------------------------------------------ the table


def format_table(label: str, split: hs.Split, names) -> list[str]:
    """The lines of one program's table, median milliseconds an execution:
    every chain of scopes with its own time by pass (a nested scope on a
    row of its own, under its parent), then every scope with what is
    nested under it, wherever it is nested."""
    passes = tuple(names.PASSES)
    step_ms = tr.median([ns / 1e6 for ns in split.durations_ns])
    lines = [f"{label}: {len(split.executions)} executions, "
             f"median {step_ms:.3f} ms",
             f"  {'':<44}" + "".join(f"{p:>11}" for p in passes) + f"{'all':>11}"]

    def row(name, select):
        by_pass = [split.median_ms(lambda k, p=p: select(k) and pass_key(k)[1] == p)
                   for p in passes]
        lines.append(f"  {name:<44}" + "".join(f"{ms:>11.3f}" for ms in by_pass)
                     + f"{split.median_ms(select):>11.3f}")

    keys = {k for parts in split.executions for k in parts if k != UNSCOPED}
    row("all scoped", lambda k: k != UNSCOPED)
    lines.append(f"  {UNSCOPED:<44}{'':>33}"
                 f"{split.median_ms(lambda k: k == UNSCOPED):>11.3f}")
    lines.append("  by chain (a nested scope under its parent, its own time only):")
    for chain in sorted({pass_key(k)[0] for k in keys}):
        row("  " * len(chain) + chain[-1],
            lambda k, chain=chain: pass_key(k)[0] == chain)
    lines.append("  by scope (wherever it is nested, what is nested under it included):")
    for scope in sorted({s for k in keys for s in pass_key(k)[0]}):
        row("  " + scope, _wanted(scope, None))
    return lines


def table_from_files(work_dir: str) -> str:
    """The scope by pass table of the newest traced run under `work_dir`
    (``bench_work/<cell>``), from its trace and its map."""
    names = program_passes()
    if names is None:
        raise SystemExit("the program's core/scopes.py has no pass_of")
    trace_dir = os.path.join(work_dir, "trace")
    path = tr.newest_xplane(trace_dir)
    maps = sorted(glob.glob(hs.map_path(trace_dir, "*")))
    if path is None or not maps:
        raise SystemExit(f"{trace_dir}: no trace or no scope_map.*.json; "
                         f"run the cell with --trace 1 first")
    chips = tr.device_planes(tr.load_xplane(path))
    lines = []
    for map_file in maps:
        with open(map_file) as fh:
            held = json.load(fh)
        split = split_by_pass(chips, held["step_program"], held["modules"], names)
        if split is not None:
            lines += format_table(
                f"{held['label']} ({held['step_program']})", split, names)
    return "\n".join(lines)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(__doc__)
    print(table_from_files(sys.argv[1]))
