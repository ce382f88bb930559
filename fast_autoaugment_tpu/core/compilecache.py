"""Persistent-compile-cache placement + the instrumented compile seam.

Every process in this stack pays an XLA compile before its first real
step, and the resilience machinery multiplies that tax: every exit-77
resume, fleet retry and reclaimed work unit is a FRESH process that
would recompile everything from scratch.  Two pieces remove the
recurrence:

1. **One resolver for where the cache lives**
   (:func:`configure_compile_cache`).  If ``JAX_COMPILATION_CACHE_DIR``
   is set, JAX itself already keeps its on-disk executable cache there
   and this module sets no directory in code — the machine that runs
   the program decides the place.  If it is unset, the cache is on at
   one fixed path inside the checkout (:data:`DEFAULT_CACHE_DIR`): the
   directory is part of what makes a second process hit, so it is never
   a temp name, a pid or a time.  There is no flag and no off spec;
   JAX's own ``JAX_ENABLE_COMPILATION_CACHE=0`` is the off switch.
   Either way the persistence floor is dropped (JAX's 1 s
   ``jax_persistent_cache_min_compile_time_secs`` default would skip
   most of the small modules a warm process must also find) and the
   hit/miss listener is registered.  Caching never changes numerics —
   only where executables come from.

2. **The compile seam** (:func:`seam_jit` / :func:`aot_compile`): every
   jit entry point in ``train/``, ``search/`` and ``serve/`` routes
   through one wrapper that times each first-call lowering, classifies
   it hit/miss against the persistent cache's monitoring events, and
   aggregates the evidence so ``search_result.json``, the bench JSON
   lines, the trainer result and the resilience resume path can PROVE a
   warm process reached its first step in seconds (``compile_cache{dir,
   hits, misses, first_step_secs}``).  faalint rule R5 keeps future hot
   paths on the seam.

The hit/miss counters come from JAX's own monitoring events
(``/jax/compilation_cache/cache_{hits,misses}``), so they count every
XLA module the process compiles — including the small auxiliary ones
(``convert_element_type`` etc.) outside any seam label.  Per-label
classification snapshots the counters around the label's first call;
the repo's dispatch discipline is single-threaded per step factory, so
the deltas attribute cleanly in practice (a concurrent compile would
merely make a verdict pessimistic, never silently wrong the other way).

The watchdog coupling (``core/watchdog.py``): once this process has
OBSERVED cache hits and no misses (:func:`process_is_warm`), the
watchdog shrinks its generous first-call compile allowance — a warm
process must not be able to hide a genuine multi-minute hang behind a
compile grace window it no longer needs.
"""

from __future__ import annotations

import collections
import functools
import os
import re
import threading
import time
import weakref
from typing import Any, Callable

from fast_autoaugment_tpu.core import scopes, telemetry
from fast_autoaugment_tpu.utils.logging import get_logger

__all__ = [
    "ENV_VAR",
    "DEFAULT_CACHE_DIR",
    "configure_compile_cache",
    "seam_jit",
    "roomy",
    "instrument_jitted",
    "aot_compile",
    "compile_cache_stats",
    "cache_dir",
    "process_is_warm",
    "ScopeMapError",
    "parse_scope_map",
    "parse_scope_members",
    "stale_scopes",
    "scope_map",
    "scope_members",
]

logger = get_logger("faa_tpu.compilecache")

#: JAX's own variable: whoever runs the program places the cache with it
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the cache's place when :data:`ENV_VAR` is unset — fixed, inside the
#: checkout (git-ignored), shared by every process started from it
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_dir: str | None = None
# hit/miss live in the process-wide telemetry registry (one source of
# truth: compile_cache_stats, /metrics and the bench stamps all read
# the same counters; pinned by tests/test_telemetry.py)
_HITS = telemetry.registry().counter(
    "faa_compile_cache_hits_total",
    "persistent-compile-cache modules deserialized instead of compiled")
_MISSES = telemetry.registry().counter(
    "faa_compile_cache_misses_total",
    "persistent-compile-cache modules compiled fresh")
# per-seam-label first-call evidence:
# {label: {"sec": float, "hit": n, "miss": n, "uncached": n, "none": n}}
_labels: dict[str, dict] = {}
_listener_registered = False
# per-seam-label, the wrapped callables that have made their first call
# (scope_map re-lowers them): weakly held, but for the newest of a
# label, which is pinned so that a reader who asks after the trainer
# has returned still finds the program it ran
_called: dict[str, "weakref.WeakSet[_SeamWrapped]"] = {}
_newest: dict[str, "_SeamWrapped"] = {}


def _listener(event: str, **_kwargs: Any) -> None:
    if event == _HIT_EVENT:
        _HITS.inc()
    elif event == _MISS_EVENT:
        _MISSES.inc()


def configure_compile_cache() -> str | None:
    """Arm the persistent compilation cache where it was placed.

    With :data:`ENV_VAR` set, JAX read the directory at import and this
    function leaves it alone; unset, it turns the cache on at
    :data:`DEFAULT_CACHE_DIR`.  Both branches drop the compile-time
    persistence floor and register the hit/miss listener.  Idempotent —
    every entry point (trainer, search driver, serve CLI, benches) calls
    it before its first compile.  Returns the directory JAX is using, or
    None when ``JAX_ENABLE_COMPILATION_CACHE=0`` switched the cache off.
    """
    global _dir, _listener_registered
    import jax

    if (not os.environ.get(ENV_VAR, "").strip()
            and jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    directory = (jax.config.jax_compilation_cache_dir
                 if jax.config.jax_enable_compilation_cache else None)
    with _lock:
        if not _listener_registered:
            jax.monitoring.register_event_listener(_listener)
            _listener_registered = True
        changed = directory != _dir
        _dir = directory
    if changed:
        logger.info("persistent compile cache: %s",
                    directory or "off (JAX_ENABLE_COMPILATION_CACHE=0)")
    return directory


def cache_dir() -> str | None:
    """The active persistent-cache directory, or None when disabled."""
    return _dir


def process_is_warm() -> bool:
    """True once this process has PROVEN the cache warm: enabled, at
    least one observed hit, and not a single miss.  The watchdog uses
    this to shrink its first-call compile allowance
    (``core/watchdog.py``) — a miss anywhere means cold compiles may
    still be coming and the generous window stays."""
    return _dir is not None and _HITS.value > 0 and _MISSES.value == 0


def _snapshot() -> tuple[int, int]:
    return int(_HITS.value), int(_MISSES.value)


def _classify(h0: int, m0: int) -> str:
    """Verdict for a compile window bounded by the (h0, m0) snapshot:
    ``uncached`` (cache off), ``miss`` (any module compiled fresh),
    ``hit`` (every module deserialized), ``none`` (no cache event — the
    in-process tracing cache already held the executable)."""
    if _dir is None:
        return "uncached"
    dh, dm = int(_HITS.value) - h0, int(_MISSES.value) - m0
    if dm > 0:
        return "miss"
    if dh > 0:
        return "hit"
    return "none"


def _record(label: str, sec: float, verdict: str) -> None:
    with _lock:
        rec = _labels.setdefault(
            label, {"sec": 0.0, "hit": 0, "miss": 0, "uncached": 0, "none": 0})
        rec["sec"] += float(sec)
        rec[verdict] += 1
    # journal evidence (no-op with telemetry off): when/where this
    # process paid its compile tax, and whether the cache absorbed it
    telemetry.emit("compile", label, sec=round(float(sec), 6),
                   verdict=verdict, cache_dir=_dir)
    if sec >= 1.0:
        logger.info("compile seam %r: first call %.1fs (%s)",
                    label, sec, verdict)


def roomy(fn: Callable) -> Callable:
    """`fn` itself, its frame given a stretch of the interpreter's frame
    stack for all that it calls (a decorator; no frame is added).

    CPython (3.11 on) keeps a thread's frames in chunks of 16 KiB and
    unmaps a chunk the moment its first frame returns, so a frame that
    happens to END a chunk maps and unmaps one for every call it makes.
    Where JAX's trace and lowering recurse 50-300 frames deep and loop
    there over thousands of equations that bites, and where a chunk ends
    is decided by the sizes of all the frames under the jit call — the
    trainer's, its caller's, the script's.  One frame more under
    ``train_and_eval`` put Mosaic's loop over a kernel's equations on
    such an end: the Kimi cell's ``jaxpr to MLIR`` read 32 s against 3 s,
    every call a ``munmap`` in a process full of the TPU runtime's
    threads (my chip runs, PR 45: PERF.md §6).  `fn`'s code is made to
    declare a stack of 2**15 slots it never uses, so the interpreter
    gives its frame a chunk of its own (512 KiB, the upper half free)
    and what it calls runs inside that: no end of a chunk to sit on,
    whatever is under it."""
    fn.__code__ = fn.__code__.replace(co_stacksize=1 << 15)
    return fn


@roomy
def _with_room(fn: Callable, *args: Any, **kwargs: Any):
    """``fn(*args, **kwargs)`` in a :func:`roomy` frame: every seam's
    first call, whoever makes it."""
    return fn(*args, **kwargs)


class _SeamWrapped:
    """A jitted callable instrumented at its first invocation.

    Transparent otherwise: ``lower``/``_cache_size``/every other
    attribute delegates to the wrapped jit object (a caller may AOT-
    lower through ``.lower``; ``search/census.py`` probes
    ``_cache_size``), and post-first-call invocations are a single
    attribute load + call on top of the C++ fast dispatch path.
    """

    def __init__(self, jitted: Callable, label: str):
        self._jitted = jitted
        self._seam_label = label
        self._first_done = False
        self._first_call_specs: tuple | None = None
        self._first_call_verdict: str | None = None
        functools.update_wrapper(self, jitted, updated=())

    def __call__(self, *args: Any, **kwargs: Any):
        if self._first_done:
            return self._jitted(*args, **kwargs)
        # before the call: a donated argument is deleted by it
        self._first_call_specs = _abstract((args, kwargs))
        with _lock:
            _called.setdefault(self._seam_label, weakref.WeakSet()).add(self)
            _newest[self._seam_label] = self
        h0, m0 = _snapshot()
        # a stage of its own, nested in whatever stage is open: the
        # parent's self-time is then its own work, not this compile or load
        with telemetry.stage(f"first_call:{self._seam_label}"):
            t0 = time.perf_counter()
            out = _with_room(self._jitted, *args, **kwargs)
            sec = time.perf_counter() - t0
        self._first_done = True
        self._first_call_verdict = _classify(h0, m0)
        _record(self._seam_label, sec, self._first_call_verdict)
        return out

    def __getattr__(self, name: str):
        return getattr(self._jitted, name)


def _abstract(tree: Any) -> Any:
    """`tree` with every array leaf replaced by its
    ``jax.ShapeDtypeStruct``; no array is kept.  Lowering these again
    must give the module of the first call, so a committed array's spec
    carries its sharding and an uncommitted one's carries none."""
    import jax
    import numpy as np

    def spec(leaf):
        if isinstance(leaf, jax.Array):
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, weak_type=leaf.weak_type,
                sharding=leaf.sharding if leaf.committed else None)
        if isinstance(leaf, (np.ndarray, np.generic)):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
        return leaf

    return jax.tree.map(spec, tree)


def instrument_jitted(jitted: Callable, *, label: str) -> Callable:
    """Wrap an ALREADY-jitted callable in the compile seam."""
    return _SeamWrapped(jitted, label)


def seam_jit(fn: Callable, *, label: str, **jit_kwargs: Any) -> Callable:
    """``jax.jit`` through the compile seam — THE way train/search/serve
    build jitted entry points (lint rule R5 flags direct ``jax.jit``
    there).  `label` names the entry point in the stats; reuse the
    watchdog's dispatch labels where one exists so the two evidence
    streams line up."""
    import jax

    return _SeamWrapped(jax.jit(fn, **jit_kwargs), label)


def aot_compile(fn: Callable, *, label: str, example_args: tuple,
                jit_kwargs: dict | None = None,
                donate_argnums: tuple | None = None) -> tuple[Any, dict]:
    """``jax.jit(fn).lower(*example_args).compile()`` through the seam.

    The ahead-of-time half of the seam (the serving path's executables,
    the Anakin dispatch-only execution style — PAPERS.md *Podracer
    architectures*): compile cost lands HERE, at load time, and the
    serving loop only ever dispatches.  `example_args` are arrays or
    ``jax.ShapeDtypeStruct`` specs.  Returns ``(compiled_executable,
    {"sec", "verdict"})``; with the persistent cache enabled and warm,
    the verdict is ``hit`` and `sec` is deserialization, not lowering.

    `donate_argnums` compiles a DONATING executable: the named input
    buffers alias the outputs, so the device never holds input and
    output live at once — the zero-allocation serving dispatch
    (docs/SERVING.md "Serving data plane").  A donated input is
    deleted by the dispatch and must never be read after it; the output
    is bitwise-identical to the undonated executable's, which the
    donation tests pin.
    """
    import jax

    kw = dict(jit_kwargs or {})
    if donate_argnums is not None:
        kw["donate_argnums"] = tuple(donate_argnums)
    h0, m0 = _snapshot()
    t0 = time.perf_counter()
    compiled = jax.jit(fn, **kw).lower(*example_args).compile()
    sec = time.perf_counter() - t0
    verdict = _classify(h0, m0)
    _record(label, sec, verdict)
    return compiled, {"sec": round(sec, 3), "verdict": verdict}


def compile_cache_stats() -> dict:
    """The artifact stamp: ``compile_cache{dir, enabled, hits, misses,
    first_step_secs, labels}``.

    ``hits``/``misses`` are the process-wide persistent-cache event
    counts; ``first_step_secs`` is the total first-call seconds paid
    through the seam — the compile tax this process actually spent
    before its steps/evals/serves ran.  Stamped into
    ``search_result.json``, every bench JSON line, the trainer result,
    and logged on the resilience resume path.
    """
    with _lock:
        labels = {
            lb: {"sec": round(r["sec"], 3), "hit": r["hit"],
                 "miss": r["miss"], "uncached": r["uncached"],
                 "none": r["none"]}
            for lb, r in sorted(_labels.items())
        }
        first_step = round(sum(r["sec"] for r in _labels.values()), 3)
    return {
        "dir": _dir,
        "enabled": _dir is not None,
        # sourced from the telemetry registry — the same counters a
        # /metrics scrape exports (equality pinned by tests)
        "hits": int(_HITS.value),
        "misses": int(_MISSES.value),
        "first_step_secs": first_step,
        "labels": labels,
    }


class ScopeMapError(RuntimeError):
    """A compiled module's text is not this checkout's: no instruction of
    it carries a ``faa_`` scope, or a scope this checkout's own lowering
    names is nowhere in it while its instructions are (the persistent
    cache answered with an executable that a checkout from before that
    scope compiled)."""


# "  ROOT %fusion.5 = f32[8]{0} fusion(...), kind=kLoop, calls=%fused_computation.2, metadata={op_name="..."}"
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HLO_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|false_computation|"
    r"branch_computations|called_computations)=(?:\{([^}]*)\}|(%?[\w.\-]+))")


def _parse_hlo(hlo_text: str):
    """``(module_name, own, members, calls)`` of a compiled module's text:
    every instruction's own ``op_name`` (may be empty), the instructions
    of every computation in the order printed, and the computations an
    instruction calls."""
    module = ""
    own: dict[str, str] = {}
    members: dict[str, list[str]] = {}      # computation -> instructions
    calls: dict[str, list[str]] = {}        # instruction -> computations
    computation = None
    for line in hlo_text.splitlines():
        if computation is None:
            found = _HLO_MODULE.match(line) if not module else None
            if found:
                module = found.group(1)
                continue
            found = _HLO_COMPUTATION.match(line)
            if found:
                computation = found.group(1)
                members[computation] = []
            continue
        if line.startswith("}"):
            computation = None
            continue
        found = _HLO_INSTRUCTION.match(line)
        if not found:
            continue
        name = found.group(1)
        members[computation].append(name)
        op_name = _HLO_OP_NAME.search(line)
        own[name] = op_name.group(1) if op_name else ""
        called = [c.strip().lstrip("%")
                  for several, one in _HLO_CALLED.findall(line)
                  for c in (several or one).split(",")]
        if called:
            calls[name] = called
    return module, own, members, calls


def parse_scope_map(hlo_text: str) -> tuple[str, dict[str, str]]:
    """``(module_name, {instruction_name: op_name})`` from a compiled
    module's text, over all its computations.

    Two fallbacks name what carries no ``faa_`` scope of its own.  An
    instruction that calls computations (a fusion, a while loop) takes
    the ``op_name`` of the commonest scope chain among the instructions
    it calls, callees before callers.  Then an instruction that is still
    unnamed takes the ``op_name`` of the instruction that calls its
    computation, callers before callees: the loop XLA:TPU makes of a
    batched ``dynamic_slice`` has a named ``while`` and a body without a
    single ``op_name``.  What neither reaches keeps its own name, which
    may be empty."""
    module, own, members, calls = _parse_hlo(hlo_text)

    resolved: dict[str, str] = {}

    def resolve(name: str) -> str:
        if name in resolved:
            return resolved[name]
        resolved[name] = own[name]
        if not scopes.scope_of(own[name]) and name in calls:
            votes = collections.Counter()
            speaker: dict[tuple, str] = {}
            for comp in calls[name]:
                for callee in members.get(comp, ()):
                    chain = scopes.scope_of(resolve(callee))
                    if chain:
                        votes[chain] += 1
                        speaker.setdefault(chain, resolved[callee])
            if votes:
                resolved[name] = speaker[votes.most_common(1)[0][0]]
        return resolved[name]

    for name in own:
        resolve(name)
    # callers are printed after their callees: walk them first
    for computation in reversed(members):
        for name in members[computation]:
            if name in calls and scopes.scope_of(resolved[name]):
                for comp in calls[name]:
                    for callee in members.get(comp, ()):
                        if not scopes.scope_of(resolved[callee]):
                            resolved[callee] = resolved[name]
    return module, resolved


def parse_scope_members(hlo_text: str) -> tuple[str, dict[str, tuple[str, ...]]]:
    """``(module_name, {instruction_name: scopes})``: every scope that an
    instruction or anything it calls, however deep, carries in its own
    ``op_name``; instructions that hold none are left out.  A fusion has
    one ``op_name``, its root's, and :func:`parse_scope_map` files its
    whole time there; this is the other reading, by membership: which
    fusions a scope's instructions ended up in, where XLA fused them
    into a neighbour's (an elementwise mix into the BatchNorm before
    it)."""
    module, own, members, calls = _parse_hlo(hlo_text)
    held: dict[str, frozenset] = {}

    def hold(name: str) -> frozenset:
        if name not in held:
            held[name] = frozenset(scopes.scope_of(own[name])).union(
                *(hold(callee) for comp in calls.get(name, ())
                  for callee in members.get(comp, ())))
        return held[name]

    return module, {name: tuple(sorted(hold(name))) for name in own if hold(name)}


# `loc("jit(multi_fn)/jvp(faa_model)/.../dot_general"(...))` in a lowering's
# text with debug info: the names XLA's ``op_name`` metadata is made from
# (`loc("/root/repo/.../steps.py":12:4)` is a file's, and names nothing)
_MLIR_LOC_NAME = re.compile(r'loc\("([^"]*)"(?!:)')
_ANY_SCOPE = re.compile(re.escape(scopes.PREFIX) + r"\w+")


def stale_scopes(lowered_text: str, compiled_text: str) -> set[str]:
    """The scopes of a lowering's text (``lowered.as_text(debug_info=
    True)``, the ``loc("...")`` names) that `compiled_text` got from a
    checkout from before them: no ``op_name`` of it holds the scope
    anywhere, not in a fused computation either, *and* it names an
    instruction the way this lowering would without the scope — the
    lowering's name less the ``/<scope>/`` component, where the lowering
    itself names nothing so.  A scope XLA optimised away entirely (one
    operation's branch folded into its twin's) leaves no such name behind
    and is not stale; one that is never a plain component of a path (the
    outermost, under ``jvp(...)``) has no name to look for and is."""
    names = set(_MLIR_LOC_NAME.findall(lowered_text))
    pieces = {piece for op_name in _HLO_OP_NAME.findall(compiled_text)
              for piece in op_name.split(";")}
    missing = ({scope for name in names for scope in scopes.scope_of(name)}
               - set(_ANY_SCOPE.findall("\n".join(pieces))))
    stale = set()
    for scope in missing:
        part = f"/{scope}/"
        without = {name.replace(part, "/") for name in names if part in name} - names
        if not without or without & pieces:
            stale.add(scope)
    return stale


def scope_map(label: str) -> dict[str, dict[str, str]]:
    """``{hlo_module_name: {instruction_name: op_name}}`` of the programs
    compiled under seam `label` that have made their first call and are
    still alive (the newest always is): the join between a profiler
    trace, whose ``XLA Ops`` events are named by HLO instruction, and
    the named scopes of ``core/scopes.py``.

    On demand only.  Each program is lowered again from the abstract
    arguments of its first call and compiled: in the process that ran
    it JAX still holds the executable (half a second for the WRN train
    steps on a v5e), elsewhere the persistent cache answers with a hit
    (trace + lower + load) — never a miss, while the specs reproduce the
    first call; a full compile where the cache is off.

    The map must be this checkout's.  The cache's key leaves metadata
    out, so an executable cached by a checkout from before a scope was
    added comes back on a hit with its old metadata.  Raises
    :class:`ScopeMapError` where a module carries no scope at all, and,
    where the persistent cache may have answered, where a scope that this
    checkout's own lowering of the program names is nowhere in the
    compiled text while its instructions are, named without it
    (:func:`stale_scopes`): a reader of that scope would read nothing, in
    silence.  Where this process compiled the program itself
    — its first call was a miss, or the cache is off
    (``jax_enable_compilation_cache`` false, as
    ``benchmarks/harness/scopes.py`` sets it to answer this error with one
    compile of its own) — the text is this checkout's, and a scope XLA
    optimised away entirely is then a fact, not staleness."""
    import jax

    out: dict[str, dict[str, str]] = {}
    for lowered, compiled_here in _lowerings(label):
        text = lowered.compile().as_text()
        module, table = parse_scope_map(text)
        if not any(scopes.scope_of(op_name) for op_name in table.values()):
            raise ScopeMapError(
                f"compile seam {label!r}: no instruction of module "
                f"{module!r} carries a {scopes.PREFIX!r} scope.  The "
                f"persistent compile cache ({_dir or 'off'}) answered with "
                f"an executable cached before the scopes were added (its "
                f"key leaves metadata out): clear that directory and run "
                f"again")
        if jax.config.jax_enable_compilation_cache and not compiled_here:
            stale = stale_scopes(lowered.as_text(debug_info=True), text)
            if stale:
                raise ScopeMapError(
                    f"compile seam {label!r}: module {module!r} holds "
                    f"{', '.join(sorted(stale))} nowhere and names its "
                    f"instructions without it, which this checkout's "
                    f"lowering does not.  The persistent compile "
                    f"cache ({_dir or 'off'}) answered with an executable "
                    f"cached by a checkout from before (its key leaves "
                    f"metadata out): compile with the cache off")
        out.setdefault(module, {}).update(table)
    return out


def scope_members(label: str) -> dict[str, dict[str, tuple[str, ...]]]:
    """``{hlo_module_name: {instruction_name: scopes}}`` of the same
    programs at the same cost as :func:`scope_map`: for every instruction
    the scopes found anywhere inside it (:func:`parse_scope_members`), so
    that a reader can sum the time of the fusions that *hold* a scope's
    work where none is rooted in it."""
    out: dict[str, dict[str, tuple[str, ...]]] = {}
    for text in _compiled_texts(label):
        module, table = parse_scope_members(text)
        out.setdefault(module, {}).update(table)
    return out


def _lowerings(label: str) -> list[tuple[Any, bool]]:
    """Each live program of seam `label`, lowered again from the abstract
    arguments of its first call, and whether that first call compiled it
    in this process (a miss of the persistent cache)."""
    with _lock:
        wrapped = list(_called.get(label, ()))
    lowered = []
    for fn in wrapped:
        args, kwargs = fn._first_call_specs
        lowered.append((fn._jitted.lower(*args, **kwargs),
                        fn._first_call_verdict == "miss"))
    return lowered


def _compiled_texts(label: str) -> list[str]:
    """The compiled text of each live program of seam `label`."""
    return [lowered.compile().as_text() for lowered, _ in _lowerings(label)]


def _reset_stats_for_tests() -> None:
    """Zero the counters/labels (NOT the cache config) — test isolation
    only; the listener stays registered."""
    _HITS._reset()
    _MISSES._reset()
    with _lock:
        _labels.clear()
