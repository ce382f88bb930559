"""Device time of the step program by the program's own named scopes.

The program names its work with ``jax.named_scope``
(``fast_autoaugment_tpu/core/scopes.py``); the names end up in the
``op_name`` metadata of the compiled module, which
``core/compilecache.py::scope_map`` hands out as ``{module: {instruction:
op_name}}``.  A trace's ``XLA Ops`` events are named by their HLO text,
``%fusion.2361 = s32[526336]... fusion(...)``, so the join is event ->
instruction name -> ``op_name`` -> scope.  For every execution of the
step program that ``step_device_ms`` uses, the self-times of the
operations inside it are summed by the instruction's whole chain of
scopes, outermost first (nested time counted once); what carries no
scope, is not in the map, or is no operation at all (the device waiting
inside the program) is ``unscoped``, so the parts of an execution add up
to its device time.  A metric is the median over executions.

A chain belongs to the partition family of its *outermost* scope that
has one, so whatever a model names inside ``faa_model`` (a gate, a
routing step, a mix with a ``custom_vjp``) stays in the model's forward
or backward time, and :func:`scope_ms` reads such a scope by its name
wherever it is nested: a reader file for it needs no edit here.

The map is written once a run to ``<trace_dir>/scope_map.<label>.json``,
so that

    python3 -m benchmarks.harness.scopes bench_work/<cell>

prints the whole table from files: every scope, all 19 operations,
unscoped, and the largest unscoped instructions by a name that survives
a recompile.

A program from before the scopes has neither module: every reader here
then returns None, and the result line leaves the metric out.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import sys
import time
import traceback
from dataclasses import dataclass, field

from benchmarks.harness import trace as tr

UNSCOPED = "unscoped"
#: appended to a scope where its instruction sits under ``transpose(``
BACKWARD = "/backward"
#: a split that leaves more than this share of the step unexplained is
#: not reported as a split
MAX_UNSCOPED_SHARE = 20.0

HISTOGRAM_OPS = ("AutoContrast", "Equalize")
GEOMETRIC_OPS = ("ShearX", "ShearY", "TranslateX", "TranslateY", "Rotate",
                 "TranslateXAbs", "TranslateYAbs")
#: the families that partition the scoped time of an execution
PARTITION = ("policy", "fixed", "forward", "backward", "optimizer", "gather")


def program_scopes():
    """The program's table of scope names (its ``core.scopes`` module),
    or None where the program has none."""
    try:
        from fast_autoaugment_tpu.core import scopes
    except ImportError:
        return None
    return scopes


def split_key(key: str) -> tuple[tuple[str, ...], bool]:
    """A key of :class:`Split` as ``(chain, backward)``."""
    backward = key.endswith(BACKWARD)
    return tuple(key.removesuffix(BACKWARD).split("/")), backward


def families(names) -> dict[str, callable]:
    """``{family: predicate over a key of Split}``: which time a metric
    sums.  The six of :data:`PARTITION` divide the scoped time by the
    outermost scope of a chain that one of them names (the model's
    forward or backward by where the instruction sits), so what is
    nested under a family's scope is that family's; the two families of
    operations lie inside ``policy`` and match their operations' scopes
    anywhere in a chain.  ``geometric_ops`` is the seven operations'
    scopes (a 2x3 matrix each since PR 29) and the one resampling an op
    slot those matrices drive, ``names.AUG_WARP``, which stays the
    policy's in the partition."""
    owners = {names.AUG_POLICY: "policy", names.AUG_FIXED: "fixed",
              names.MODEL: "model", names.LOSS: "model",
              names.OPTIMIZER: "optimizer", names.EMA: "optimizer",
              names.METRICS: "optimizer", names.BATCH_GATHER: "gather"}
    histogram = {names.aug_op(n) for n in HISTOGRAM_OPS}
    geometric = {names.aug_op(n) for n in GEOMETRIC_OPS} | (
        {names.AUG_WARP} if hasattr(names, "AUG_WARP") else set())

    def owner(key):
        chain, backward = split_key(key)
        for scope in chain:
            family = owners.get(scope) or (
                "policy" if scope.startswith(names.AUG_OP_PREFIX) else None)
            if family == "model":
                return "backward" if backward else "forward"
            if family:
                return family
        return None

    out = {family: (lambda k, family=family: owner(k) == family)
           for family in PARTITION}
    out["histogram_ops"] = lambda k: not histogram.isdisjoint(split_key(k)[0])
    out["geometric_ops"] = lambda k: not geometric.isdisjoint(split_key(k)[0])
    return out


def instruction_name(event_name: str) -> str:
    """``%fusion.2361 = s32[526336]{0} fusion(...)`` -> ``fusion.2361``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_name(run_name: str) -> str:
    """``jit_multi_fn(9872292373413704833)`` -> ``jit_multi_fn``."""
    return run_name.split("(", 1)[0]


def scope_key(op_name: str | None, names) -> str:
    """The chain of scopes of an ``op_name``, outermost first and joined
    by ``/``, marked where it belongs to the backward pass;
    :data:`UNSCOPED` where there is none.  A scope that comes back
    further in (a nested ``jit`` repeats the whole path) counts once."""
    chain = dict.fromkeys(names.scope_of(op_name)) if op_name else ()
    if not chain:
        return UNSCOPED
    return "/".join(chain) + (BACKWARD if names.is_backward(op_name) else "")


@dataclass
class Split:
    """The step program's executions, each as nanoseconds by scope key;
    :data:`UNSCOPED` is the execution's device time less the scoped."""

    executions: list[dict[str, float]] = field(default_factory=list)
    durations_ns: list[float] = field(default_factory=list)
    #: nanoseconds of operations without a scope, by stable name, over
    #: all executions (the rest of ``unscoped`` is no operation at all)
    unscoped_ops: dict[str, float] = field(default_factory=dict)

    def median_ms(self, select) -> float | None:
        """Median over executions of the time under the keys `select`
        accepts."""
        return tr.median([sum(ns for key, ns in parts.items() if select(key)) / 1e6
                          for parts in self.executions])

    def unscoped_share(self) -> float | None:
        """Median percent of an execution's device time left unscoped."""
        return tr.median([100.0 * parts[UNSCOPED] / total
                          for parts, total in zip(self.executions,
                                                  self.durations_ns) if total > 0])


def split_plane(plane: tr.Plane, pattern: str, modules: dict, names,
                into: Split | None = None) -> Split:
    """Add to `into` the executions of the programs matching `pattern` on
    this chip, the first and the last left out as ``step_device_ms``
    leaves them out."""
    split = into if into is not None else Split()
    line = plane.line(tr.OPS_LINE)
    ops = line.events if line else []
    starts = [e.start_ns for e in ops]
    for run in tr.program_runs(plane, pattern)[1:-1]:
        table = modules.get(module_name(run.name), {})
        inside = ops[bisect.bisect_left(starts, run.start_ns):
                     bisect.bisect_left(starts, run.end_ns)]
        parts: dict[str, float] = {}
        for event, own in zip(inside, tr.self_times(inside)):
            key = scope_key(table.get(instruction_name(event.name)), names)
            if key == UNSCOPED:
                stable = tr.stable_name(event)
                split.unscoped_ops[stable] = split.unscoped_ops.get(stable, 0.0) + own
            else:
                parts[key] = parts.get(key, 0.0) + own
        parts[UNSCOPED] = max(0.0, run.dur_ns - sum(parts.values()))
        split.executions.append(parts)
        split.durations_ns.append(run.dur_ns)
    return split


def map_path(trace_dir: str, label: str) -> str:
    return os.path.join(trace_dir, f"scope_map.{label}.json")


def _scope_map_compiled_afresh(scope_map, label: str) -> dict:
    """The map from a compile that the persistent cache does not answer.

    The cache's key leaves metadata out, so where a checkout from before
    a scope was added has filled the cache this run reads (parent and
    change measured in turn on one machine, one cache directory), the
    step ran from that checkout's executable and its text names no
    scope.  Same key, same program: compiled once more with the cache
    off, it comes out with the same instruction names and this
    checkout's metadata.  After the window and after the checks; costs
    one compile of the step."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()  # the executable the step ran from is held in memory too
    try:
        return scope_map(label)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _scope_modules(obs, label: str) -> dict | None:
    """The program's scope map for `label`, written beside the trace;
    None where the program has no such function, or where it raises and
    a compile with the cache off raises too (what it said is on stderr)."""
    try:
        from fast_autoaugment_tpu.core.compilecache import ScopeMapError, scope_map
    except ImportError:
        return None
    began = time.perf_counter()
    try:
        try:
            modules = scope_map(label)
        except ScopeMapError as stale:
            print(f"benchmarks/harness/scopes.py: {stale}; compiling {label!r} "
                  f"once more with the cache off", file=sys.stderr)
            modules = _scope_map_compiled_afresh(scope_map, label)
    except Exception:  # a reader leaves its metric out; it never ends the run
        traceback.print_exc(file=sys.stderr)
        return None
    print(f"benchmarks/harness/scopes.py: scope_map({label!r}) took "
          f"{time.perf_counter() - began:.1f} s, after the window",
          file=sys.stderr)
    with open(map_path(obs.trace_dir, label), "w") as fh:
        json.dump({"label": label, "step_program": obs.step_program,
                   "modules": modules}, fh)
    return modules


def step_split(obs) -> Split | None:
    """The split of this run's step program, computed once a run."""
    cached = vars(obs).get("_scope_split", False)
    if cached is not False:
        return cached
    split = None
    view, names = obs.trace, program_scopes()
    label = obs.cell.traffic.get("dispatch_label")
    if view is not None and names is not None and obs.step_program and label:
        modules = _scope_modules(obs, label)
        if modules:
            split = Split()
            for plane in view.planes:
                split_plane(plane, obs.step_program, modules, names, split)
            if not split.executions:
                split = None
    vars(obs)["_scope_split"] = split
    return split


def step_unscoped_share(obs) -> float | None:
    split = step_split(obs)
    return None if split is None else split.unscoped_share()


def _median_ms(obs, select) -> float | None:
    """What :func:`family_ms` and :func:`scope_ms` share: the median over
    the keys `select` accepts."""
    split = step_split(obs)
    if split is None:
        return None
    share = split.unscoped_share()
    if share is None or share > MAX_UNSCOPED_SHARE:
        return None
    return split.median_ms(select)


def family_ms(obs, family: str) -> float | None:
    """Median device milliseconds of one execution under one of
    :func:`families`; None where there is no split or it leaves too much
    unexplained."""
    names = program_scopes()
    return None if names is None else _median_ms(obs, families(names)[family])


def scope_ms(obs, name: str, backward: bool | None = None) -> float | None:
    """Median device milliseconds of one execution under the scope `name`
    wherever it is nested, what is nested under it included; the forward
    pass alone with ``backward=False``, the backward pass alone with
    ``True``.  None where :func:`family_ms` would give None."""
    def select(key):
        chain, is_backward = split_key(key)
        return name in chain and backward in (None, is_backward)
    return _median_ms(obs, select)


# ------------------------------------------------------------ the table


def format_table(label: str, split: Split, names) -> list[str]:
    """The lines of one program's table: the families the metrics read,
    every scope with all 19 operations, and the largest unscoped
    operations, each in median milliseconds an execution and percent of
    the median execution."""
    from fast_autoaugment_tpu.ops.augment import OP_NAMES

    step_ms = tr.median([ns / 1e6 for ns in split.durations_ns])
    lines = [f"{label}: {len(split.executions)} executions, "
             f"median {step_ms:.3f} ms"]

    def row(name, ms):
        lines.append(f"  {name:<36} {ms:>10.3f} ms {100 * ms / step_ms:>6.2f}%")

    for family, select in families(names).items():
        row(family, split.median_ms(select))
    row(UNSCOPED, split.median_ms(lambda k: k == UNSCOPED))
    lines.append("  by scope (a nested scope under its parent, its own time only):")
    keys = sorted({k for parts in split.executions for k in parts if k != UNSCOPED}
                  | {f"{names.AUG_POLICY}/{names.aug_op(n)}" for n in OP_NAMES})
    for key in keys:
        chain, backward = split_key(key)
        row("  " * len(chain) + chain[-1] + (BACKWARD if backward else ""),
            split.median_ms(lambda k, key=key: k == key))
    lines.append("  largest unscoped operations (the rest of unscoped is "
                 "the device waiting inside the program):")
    ranked = sorted(split.unscoped_ops.items(), key=lambda kv: -kv[1])[:10]
    for stable, ns in ranked:
        row("  " + stable[:34], ns / 1e6 / len(split.executions))
    return lines


def table_from_files(work_dir: str) -> str:
    """The whole per-scope table of the newest traced run under
    `work_dir` (``bench_work/<cell>``), from its trace and its map."""
    names = program_scopes()
    trace_dir = os.path.join(work_dir, "trace")
    path = tr.newest_xplane(trace_dir)
    maps = sorted(glob.glob(map_path(trace_dir, "*")))
    if path is None or not maps:
        raise SystemExit(f"{trace_dir}: no trace or no scope_map.*.json; "
                         f"run the cell with --trace 1 first")
    chips = tr.device_planes(tr.load_xplane(path))
    lines = []
    for map_file in maps:
        with open(map_file) as fh:
            held = json.load(fh)
        split = Split()
        for plane in chips:
            split_plane(plane, held["step_program"], held["modules"], names, split)
        if split.executions:
            lines += format_table(
                f"{held['label']} ({held['step_program']})", split, names)
    return "\n".join(lines)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(__doc__)
    print(table_from_files(sys.argv[1]))
