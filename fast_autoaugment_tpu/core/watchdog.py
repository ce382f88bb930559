"""Monitored device dispatch: deadline-guarded execution, hang recovery.

The scalar-collective rendezvous deadlock measured in PR 4 (a few
hundred queued unsynced collective programs wedge the virtual-device
CPU backend's rendezvous — ``train/steps.py::make_replay_eval_step``)
is the concrete local instance of a general multi-host hazard: an XLA
dispatch that never completes.  Multi-host pjit deployments treat hang
detection as table stakes (PAPERS.md: *Scalable Training of Language
Models using JAX pjit and TPUv4*), because a wedged rendezvous blocks
EVERY participant forever — there is no exception to catch, the
process just stops making progress.

:class:`DispatchWatchdog` wraps a device dispatch (a jitted call plus
the ``block_until_ready`` on its outputs) in a worker thread and waits
with a deadline:

- the deadline derives from an **EMA of observed per-dispatch wall
  time** (``auto`` mode: ``max(min_deadline, hang_factor x EMA)``) or
  is a fixed operator-supplied number of seconds;
- the **first call per label gets a separate, generous compile
  allowance** — XLA compiles on first dispatch, and a cold compile
  (86-88 s for the WRN-40-2 batch-128 train dispatch on a TPU v5e,
  chip run of PR 21 — PERF.md) must never read as a hang;
- expiry raises the typed
  :class:`~fast_autoaugment_tpu.core.resilience.DispatchHungError`.
  The hung computation holds the donated state buffers, so there is
  nothing to checkpoint — the CLIs map the error to exit 77 and the
  relaunch resumes from the newest intact chain link (pair with
  ``--ckpt-every-dispatch M`` to bound the replayed work).

Blocking on each monitored dispatch serializes the dispatch pipeline,
which is why the default is **off** (bit-for-bit the historical async
stream — blocking changes wall time, never values).  ``--watchdog
auto`` (or an explicit deadline) buys hang detection for that cost.

Deterministic tests drive this through the ``FAA_FAULT`` verbs
``hang@step=K`` (the dispatch covering step K sleeps forever) and
``slow@step=K,factor=F`` (a straggler: the dispatch takes F x the
current EMA) — ``utils/faultinject.py``.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Any, Callable

from fast_autoaugment_tpu.core import telemetry
from fast_autoaugment_tpu.core.resilience import DispatchHungError
from fast_autoaugment_tpu.utils.logging import get_logger

__all__ = ["DispatchWatchdog", "resolve_watchdog", "DispatchHungError",
           "arm_dispatch_serializer", "dispatch_enqueue_guard"]

# ---------------------------------------------------------------------
# Process-wide device-dispatch ENQUEUE serializer.
#
# The virtual-multi-device CPU backend deadlocks when two THREADS
# enqueue collective programs concurrently: each thread walks the
# per-device executors in its own interleaving, so device i can see
# program A before B while device j sees B before A — every
# participant then waits at a rendezvous the other program's
# participants never reach (observed live: CollectivePermute
# participants of two run_ids cross-blocked during an overlapped
# phase-1 train + 2-actor TTA run; the cross-thread sibling of the
# PR-4 scalar-collective deadlock, which was single-threaded queue
# depth).  The async search pipeline ARMS this lock so every compiled
# program launch in the process (trainer dispatch chunks, eval
# replays, TTA/audit rounds) enqueues under ONE lock — a consistent
# global program order on every device queue — while completion stays
# async: the lock covers the enqueue, never the wait, so the
# host/device overlap the pipeline exists for is untouched.  Device
# puts/gets are single-participant and stay unguarded.  Disarmed
# (the default, and every serial path) this is a no-op context.

_ENQUEUE_LOCK = threading.RLock()
_ENQUEUE_SERIALIZED = False


def arm_dispatch_serializer(on: bool = True) -> None:
    """Turn cross-thread enqueue serialization on/off (process-wide).
    ``search_policies`` arms it for async-pipeline runs and disarms it
    for serial runs, so one process can do both in sequence."""
    global _ENQUEUE_SERIALIZED
    _ENQUEUE_SERIALIZED = bool(on)


def dispatch_enqueue_guard():
    """Context manager for ONE compiled-program enqueue: the
    serializer lock when armed, a no-op otherwise."""
    if _ENQUEUE_SERIALIZED:
        return _ENQUEUE_LOCK
    return contextlib.nullcontext()

logger = get_logger("faa_tpu.watchdog")

#: first-call-per-label deadline: covers XLA compile with slack.  The
#: one model measured on the chip (WRN-40-2: 86-101 s cold for the
#: train dispatch, 74-84 s for the candidate-vmapped TTA step —
#: PERF.md) leaves ~6x; a deeper family
#: (PyramidNet-272) has not been compiled there yet, so re-check this
#: before relying on ``--watchdog auto`` for it
DEFAULT_COMPILE_ALLOWANCE_SEC = 600.0
#: first-call deadline once the compile tax is KNOWN paid (persistent
#: compile cache hit / AOT-loaded executable): covers executable
#: deserialization plus a long first dispatch, nothing like a compile —
#: a warm process must not hide a 10-minute hang behind the blind
#: compile window above (core/compilecache.py)
DEFAULT_WARM_ALLOWANCE_SEC = 60.0
#: auto mode: deadline = max(min_deadline, hang_factor * EMA)
DEFAULT_HANG_FACTOR = 20.0
DEFAULT_MIN_DEADLINE_SEC = 10.0
#: EMA smoothing for observed dispatch wall times
DEFAULT_EMA_ALPHA = 0.2


class DispatchWatchdog:
    """Deadline-guarded dispatch execution with per-label EMA timing.

    ``mode`` is ``"off"`` (disabled — :meth:`run` calls through with
    zero overhead), ``"auto"`` (EMA-derived deadlines), or a positive
    float (fixed steady-state deadline in seconds; the first call per
    label still gets ``max(seconds, compile_allowance)``).

    One instance is shared across a whole run (trainer + search) so
    :attr:`fires` aggregates every monitored seam; labels keep their
    own EMA because a train dispatch chunk and a whole-split eval
    replay have very different steady-state walls.

    THREAD-SAFE: the async search pipeline (``search/pipeline.py``)
    runs one monitored dispatch per actor thread concurrently, plus
    the overlapped phase-1 trainer thread — every read/write of the
    shared label state (EMAs, call counts, warm labels, fire count)
    goes through one internal lock.  :meth:`run` itself holds the lock
    only around that bookkeeping, never across the monitored wait, so
    concurrent dispatches still overlap freely.
    """

    def __init__(self, mode: str | float = "off", *,
                 compile_allowance: float = DEFAULT_COMPILE_ALLOWANCE_SEC,
                 warm_allowance: float = DEFAULT_WARM_ALLOWANCE_SEC,
                 hang_factor: float = DEFAULT_HANG_FACTOR,
                 min_deadline: float = DEFAULT_MIN_DEADLINE_SEC,
                 ema_alpha: float = DEFAULT_EMA_ALPHA):
        if isinstance(mode, str):
            mode = mode.strip().lower()
            if mode not in ("off", "auto"):
                mode = float(mode)  # "SECONDS" string from the CLI
        if isinstance(mode, (int, float)):
            if float(mode) <= 0:
                raise ValueError(f"watchdog deadline must be > 0, got {mode}")
            mode = float(mode)
        self.mode = mode
        self.compile_allowance = float(compile_allowance)
        self.warm_allowance = float(warm_allowance)
        self.hang_factor = float(hang_factor)
        self.min_deadline = float(min_deadline)
        self.ema_alpha = float(ema_alpha)
        self.fires = 0
        self._ema: dict[str, float] = {}
        self._calls: dict[str, int] = {}
        # labels whose executable is KNOWN pre-compiled (AOT-loaded) —
        # their first call gets the warm allowance, never the blind
        # compile window
        self._warm_labels: set[str] = set()
        # guards every access to the shared label state above: the
        # async pipeline dispatches from several actor threads at once
        self._lock = threading.RLock()

    @property
    def enabled(self) -> bool:
        return self.mode != "off"

    def ema(self, label: str) -> float | None:
        """Current EMA of observed wall seconds for `label` (None until
        the first completed call)."""
        with self._lock:
            return self._ema.get(label)

    def mark_compile_warm(self, label: str) -> None:
        """Declare `label`'s executable pre-compiled (AOT-loaded / known
        persistent-cache hit): its first call gets the bounded
        ``warm_allowance`` instead of the blind compile window."""
        with self._lock:
            self._warm_labels.add(label)

    def _first_call_warm(self, label: str) -> bool:
        """Whether `label`'s FIRST call should be treated as compile-free:
        explicitly marked warm, or the process has already proven the
        persistent compile cache warm (hits observed, zero misses —
        ``core/compilecache.py``)."""
        with self._lock:
            if label in self._warm_labels:
                return True
        try:
            from fast_autoaugment_tpu.core import compilecache
        except ImportError:  # pragma: no cover — core package is intact
            return False
        return compilecache.process_is_warm()

    def deadline(self, label: str) -> float:
        """The deadline the NEXT :meth:`run` for `label` will use.

        The first call per label normally gets the generous compile
        allowance (a 23-55 s first compile must never read as a hang);
        when the compile seam has reported cache hits and no misses —
        or the label's executable was AOT-loaded
        (:meth:`mark_compile_warm`) — that allowance shrinks to the
        normal deadline floor (``warm_allowance``), so a warm process
        cannot hide a genuine multi-minute hang behind a compile grace
        window it no longer needs."""
        with self._lock:
            first = self._calls.get(label, 0) == 0
        warm = first and self._first_call_warm(label)
        with self._lock:
            if isinstance(self.mode, float):
                if first and not warm:
                    return max(self.mode, self.compile_allowance)
                return self.mode
            # auto: generous compile allowance first, then EMA-derived
            if first or label not in self._ema:
                if warm:
                    return max(self.min_deadline, self.warm_allowance)
                return self.compile_allowance
            return max(self.min_deadline, self.hang_factor * self._ema[label])

    def observe(self, label: str, wall_sec: float) -> None:
        """Fold one observed dispatch wall time into the label's EMA.

        The first observation seeds the EMA directly — it is the
        compile call, but using it only ever makes deadlines MORE
        generous until steady-state observations pull the EMA down."""
        with self._lock:
            self._calls[label] = self._calls.get(label, 0) + 1
            prev = self._ema.get(label)
            if prev is None:
                self._ema[label] = float(wall_sec)
            else:
                self._ema[label] = (self.ema_alpha * float(wall_sec)
                                    + (1.0 - self.ema_alpha) * prev)
            ema = self._ema[label]
        # registry mirror (telemetry): the EMA any /metrics scrape or
        # bench stamp reads is the one the deadline math uses
        telemetry.registry().gauge(
            "faa_watchdog_ema_seconds",
            "per-label EMA of observed dispatch wall seconds",
            label=label).set(ema)

    def run(self, label: str, fn: Callable, *args: Any,
            inject_delay: float = 0.0) -> Any:
        """Run ``fn(*args)`` (plus ``block_until_ready`` on its result)
        under the label's deadline.

        Disabled mode calls through inline with zero overhead — except
        that an injected delay (the ``hang``/``slow`` fault verbs)
        still sleeps, reproducing the real unwatched wedge.  Raises
        :class:`DispatchHungError` on expiry; the worker thread is a
        daemon, so an actually-wedged dispatch cannot block process
        exit (the recovery IS a process exit)."""
        import jax

        if not self.enabled:
            _sleep(inject_delay)
            with dispatch_enqueue_guard():
                out = fn(*args)
            return jax.block_until_ready(out)

        deadline = self.deadline(label)
        out_q: queue.Queue = queue.Queue(maxsize=1)
        t0 = time.monotonic()

        def _worker():
            try:
                _sleep(inject_delay)
                with dispatch_enqueue_guard():
                    out = fn(*args)
                out = jax.block_until_ready(out)
                # put_nowait: maxsize-1 queue, single producer, one
                # put per worker — can never block (lint R9)
                out_q.put_nowait(("ok", out, time.monotonic() - t0))
            except BaseException as e:  # delivered to the caller below
                out_q.put_nowait(("err", e, time.monotonic() - t0))

        worker = threading.Thread(target=_worker, daemon=True,
                                  name=f"watchdog-{label}")
        worker.start()
        try:
            kind, value, wall = out_q.get(timeout=deadline)
        except queue.Empty:
            with self._lock:
                self.fires += 1
                ema = self._ema.get(label)
            waited = time.monotonic() - t0
            telemetry.registry().counter(
                "faa_watchdog_fires_total",
                "dispatch watchdog deadline expiries", label=label).inc()
            telemetry.emit("watchdog_fire", label,
                           deadline_sec=round(deadline, 3),
                           waited_sec=round(waited, 3),
                           ema_sec=None if ema is None else round(ema, 6))
            logger.error(
                "watchdog FIRED on %r: no completion after %.1fs "
                "(deadline %.1fs, ema %s) — dispatch presumed hung",
                label, waited, deadline,
                f"{ema:.3f}s" if ema is not None else "n/a")
            raise DispatchHungError(label, deadline, waited)
        if kind == "err":
            raise value
        self.observe(label, wall)
        return value

    def stats(self) -> dict:
        """Artifact-ready accounting: mode, fire count, per-label
        deadlines + EMAs (stamped into bench JSON and
        ``search_result.json`` so hangs and stragglers are
        distinguishable after the fact)."""
        with self._lock:
            labels = list(self._calls)
            ema = dict(self._ema)
            fires = self.fires
            warm = sorted(self._warm_labels)
        return {
            "mode": self.mode if isinstance(self.mode, str) else float(self.mode),
            "fires": fires,
            # deadline() re-locks per label: a concurrent observe
            # between snapshots only ever yields a FRESHER deadline
            "deadline_sec": {lb: self.deadline(lb) for lb in labels},
            "ema_sec": {lb: round(v, 6) for lb, v in ema.items()},
            "warm_labels": warm,
        }


def _sleep(delay: float) -> None:
    """Sleep `delay` seconds in bounded chunks (`inf` = sleep forever —
    the injected-hang case; chunking sidesteps time.sleep's OverflowError
    on infinite values)."""
    if not delay or delay <= 0:
        return
    remaining = float(delay)
    while remaining > 0:
        time.sleep(min(remaining, 60.0))  # robust: allow — deadline-bounded chunked sleep; inf = the deliberate injected wedge
        remaining -= 60.0


def resolve_watchdog(spec, **kwargs) -> DispatchWatchdog:
    """``--watchdog {off,auto,SECONDS}`` (or an existing instance) to a
    :class:`DispatchWatchdog`.  Passing an instance through unchanged
    lets one watchdog aggregate fire counts across the whole search."""
    if isinstance(spec, DispatchWatchdog):
        return spec
    if spec is None:
        spec = "off"
    return DispatchWatchdog(spec, **kwargs)
