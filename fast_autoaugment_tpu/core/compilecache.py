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

import functools
import os
import threading
import time
from typing import Any, Callable

from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.utils.logging import get_logger

__all__ = [
    "ENV_VAR",
    "DEFAULT_CACHE_DIR",
    "configure_compile_cache",
    "seam_jit",
    "instrument_jitted",
    "aot_compile",
    "compile_cache_stats",
    "cache_dir",
    "process_is_warm",
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


class _SeamWrapped:
    """A jitted callable instrumented at its first invocation.

    Transparent otherwise: ``lower``/``_cache_size``/every other
    attribute delegates to the wrapped jit object (``bench.py`` AOT-
    lowers through ``.lower``; ``search/census.py`` probes
    ``_cache_size``), and post-first-call invocations are a single
    attribute load + call on top of the C++ fast dispatch path.
    """

    def __init__(self, jitted: Callable, label: str):
        self._jitted = jitted
        self._seam_label = label
        self._first_done = False
        functools.update_wrapper(self, jitted, updated=())

    def __call__(self, *args: Any, **kwargs: Any):
        if self._first_done:
            return self._jitted(*args, **kwargs)
        h0, m0 = _snapshot()
        t0 = time.perf_counter()
        out = self._jitted(*args, **kwargs)
        sec = time.perf_counter() - t0
        self._first_done = True
        _record(self._seam_label, sec, _classify(h0, m0))
        return out

    def __getattr__(self, name: str):
        return getattr(self._jitted, name)


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
    (docs/BENCHMARKS.md "Serving data plane").  A donated input is
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


def _reset_stats_for_tests() -> None:
    """Zero the counters/labels (NOT the cache config) — test isolation
    only; the listener stays registered."""
    _HITS._reset()
    _MISSES._reset()
    with _lock:
        _labels.clear()
